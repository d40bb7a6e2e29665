#!/usr/bin/env bash
# Lint / format gate (capability analog of the reference's format.sh, which
# ran yapf + flake8 over the diff vs mergebase; reference: format.sh +
# .style.yapf).  Usage:
#   ./format.sh          # check files changed vs origin/main (or HEAD~1)
#   ./format.sh --all    # check the whole tree
#
# Uses flake8 when installed (CI installs it); falls back to a byte-compile
# sweep so the script still gates syntax errors in minimal environments.

set -euo pipefail
cd "$(dirname "$0")"

if [[ "${1:-}" == "--all" ]]; then
    FILES=$(git ls-files '*.py')
else
    BASE=$(git merge-base origin/main HEAD 2>/dev/null || git rev-parse HEAD~1)
    FILES=$(git diff --name-only --diff-filter=ACMR "$BASE" -- '*.py')
fi

if [[ -z "$FILES" ]]; then
    echo "format.sh: no python files to check"
    exit 0
fi

if python -c 'import flake8' 2>/dev/null; then
    # E501 relaxed to 88 to match the prevailing style; E731/W503 match the
    # reference's flake8 tolerances for lambda-heavy framework code
    echo "$FILES" | xargs python -m flake8 \
        --max-line-length=88 --extend-ignore=E731,W503,E203
    echo "format.sh: flake8 clean"
else
    echo "$FILES" | xargs python -m py_compile
    echo "format.sh: flake8 not installed; byte-compile check passed"
fi

# graftlint: the JAX-aware invariant checks (host syncs in hot paths,
# retrace hazards, knob/wire registry drift, SPMD collective/rank-
# divergence safety) — exits nonzero on findings
python scripts/graftlint.py ray_lightning_accelerators_tpu
echo "format.sh: graftlint clean"

# sharding audit: regenerate SHARDING_INVENTORY.json (the ShardingPlan
# reconnaissance artifact).  Drift (a PartitionSpec literal outside the
# inventoried modules) already failed the graftlint step above as an
# active `sharding-inventory` finding, so the audit skips its own lint
# pass here — extraction only, one lint per format.sh run.
python scripts/sharding_audit.py --out SHARDING_INVENTORY.json --skip-drift
echo "format.sh: sharding inventory refreshed (drift gated by graftlint above)"

# perf gate: the newest bench window vs PERF_BASELINE.json floors
# (scripts/perf_gate.py).  rc 1 = a gated metric regressed -> fail here,
# where lint fails.  rc 2 = UNGATED (dead-backend/zero-numbers window):
# reported loudly, not fatal — a window without numbers must not block lint.
set +e
python bench.py --gate
gate_rc=$?
set -e
if [[ $gate_rc -eq 1 ]]; then
    echo "format.sh: perf gate REGRESSION (see report above)"
    exit 1
elif [[ $gate_rc -eq 2 ]]; then
    echo "format.sh: perf gate UNGATED — newest window has no gateable numbers"
else
    echo "format.sh: perf gate clean"
fi
