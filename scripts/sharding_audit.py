"""Sharding inventory audit: one JSON map of every PartitionSpec/axis
declaration — the reconnaissance artifact for the ShardingPlan refactor.

ROADMAP item 5 (unified ShardingPlan) needs one answer to "where does
this repo declare layouts?".  Today the answer is scattered across the
five parallel modules plus the trainer and accelerator seams; this
script extracts it statically (AST only — never imports jax, safe on a
wedged machine) into ``SHARDING_INVENTORY.json``:

- per inventoried module: every ``PartitionSpec(...)`` / ``P(...)``
  construction (line, source text), every ``jax.shard_map`` call
  site, and the module's axis-name constants;
- the canonical axis registry from ``parallel/mesh.py`` (string + tuple
  constants — ``DATA_AXIS`` ... ``BATCH_AXES``);
- totals, so diffs of the committed artifact show inventory drift in
  review.

Drift gate: the ``sharding-inventory`` graftlint rule flags any
PartitionSpec literal OUTSIDE the inventoried modules.  This script
reuses the lint findings in their machine-readable ``--format json``
shape (``lint.report_json`` — same payload the CLI prints, produced
in-process so the mtime parse cache warmed by the extraction pass is
reused instead of re-parsing in a subprocess) and exits nonzero when
such a finding is active — wired into ``format.sh``, so new sharding
logic cannot silently grow off the audited surface.

Usage::

    python scripts/sharding_audit.py [--out SHARDING_INVENTORY.json]
                                     [--no-write] [--quiet]
                                     [--skip-drift]

Exit codes: 0 clean, 1 uninventoried PartitionSpec literals (listed).
``--skip-drift`` extracts the inventory only (no lint pass) — what
``format.sh`` uses, because its graftlint step one line earlier ALREADY
fails on any active ``sharding-inventory`` finding; standalone runs
keep the built-in gate.
"""

import ast
import importlib
import importlib.util
import json
import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO_ROOT, "ray_lightning_accelerators_tpu")
DEFAULT_OUT = os.path.join(REPO_ROOT, "SHARDING_INVENTORY.json")


def _load_lint():
    """analysis.lint without the package __init__ (no jax import)."""
    pkg_dir = os.path.join(PACKAGE, "analysis")
    spec = importlib.util.spec_from_file_location(
        "_audit_analysis", os.path.join(pkg_dir, "__init__.py"),
        submodule_search_locations=[pkg_dir])
    mod = importlib.util.module_from_spec(spec)
    sys.modules["_audit_analysis"] = mod
    spec.loader.exec_module(mod)
    return importlib.import_module("_audit_analysis.lint")


def _unparse(node, lines):
    """Source text of an AST node: ast.unparse when available, the
    stripped source line otherwise."""
    try:
        return ast.unparse(node)
    except Exception:
        return lines[node.lineno - 1].strip()


def _spec_call_names(info):
    """Names bound to PartitionSpec in one module — THE rule's own alias
    table (analysis/rules/sharding_inventory.py), imported rather than
    mirrored so the audit and the lint can never drift."""
    rule = importlib.import_module(
        "_audit_analysis.rules.sharding_inventory")
    return rule._spec_aliases(info)


def extract_inventory(lint):
    """The inventory dict (schema 1) over the configured modules."""
    modules, errors = lint.discover_modules(PACKAGE)
    config = lint.LintConfig.for_tree(
        {k: "\n".join(m.lines) for k, m in modules.items()})
    inv_modules = {}
    total_specs = total_shard_maps = 0
    for key in config.inventory_modules:
        info = modules.get(key)
        if info is None:
            inv_modules[key] = {"missing": True}
            continue
        aliases = _spec_call_names(info)
        specs, shard_maps = [], []
        for node in ast.walk(info.tree):
            if not isinstance(node, ast.Call):
                continue
            # lint.dotted IS the analyzer's name resolution — reusing it
            # keeps the audit's counts from drifting off the rule's
            fname = lint.dotted(node.func) or ""
            leaf = fname.split(".")[-1] if fname else ""
            if leaf == "PartitionSpec" or fname in aliases:
                specs.append({"line": node.lineno,
                              "text": _unparse(node, info.lines)})
            elif leaf == "shard_map":
                shard_maps.append({"line": node.lineno})
        axis_consts = {n: v for n, v in info.consts.items()
                       if key == config.axes_module}
        tuple_consts = {n: list(v) for n, v in info.tuple_consts.items()
                        if key == config.axes_module}
        inv_modules[key] = {
            "partition_specs": specs,
            "shard_map_sites": shard_maps,
        }
        if axis_consts or tuple_consts:
            inv_modules[key]["axis_constants"] = axis_consts
            inv_modules[key]["axis_tuples"] = tuple_consts
        total_specs += len(specs)
        total_shard_maps += len(shard_maps)
    return {
        "schema": 1,
        "axis_names": sorted(config.spmd_axis_names),
        "inventory_modules": list(config.inventory_modules),
        "modules": inv_modules,
        "totals": {"partition_spec_literals": total_specs,
                   "shard_map_sites": total_shard_maps,
                   "modules": len(config.inventory_modules)},
        "parse_errors": [f.format() for f in errors],
    }


def drift_findings(lint):
    """Active sharding-inventory findings in the ``--format json``
    payload shape (lint.report_json — the machine-readable contract CI
    and this script share).  Runs in-process: the extraction pass
    already warmed the mtime parse cache, so this lint reparses
    nothing."""
    payload = lint.report_json(lint.lint_path(PACKAGE), target=PACKAGE)
    return [f for f in payload["findings"]
            if f["rule"] == "sharding-inventory"
            and not f["suppressed"]]


def main(argv) -> int:
    out_path = DEFAULT_OUT
    if "--out" in argv:
        out_path = argv[argv.index("--out") + 1]
    write = "--no-write" not in argv
    quiet = "--quiet" in argv

    lint = _load_lint()
    inventory = extract_inventory(lint)
    # the committed artifact always records the drift verdict; only the
    # redundant-lint case (format.sh, gated by graftlint one step
    # earlier) skips the pass
    drift = [] if "--skip-drift" in argv else drift_findings(lint)
    inventory["uninventoried"] = drift

    if write:
        tmp = out_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(inventory, f, indent=2, sort_keys=True)
            f.write("\n")
        os.replace(tmp, out_path)

    # bench-style artifact line (value-less on purpose: bench.py's
    # newest-value-bearing-line parser must never pick this up)
    record = {
        "kind": "sharding_audit",
        "partition_spec_literals":
            inventory["totals"]["partition_spec_literals"],
        "shard_map_sites": inventory["totals"]["shard_map_sites"],
        "modules": inventory["totals"]["modules"],
        "axis_names": len(inventory["axis_names"]),
        # None = drift pass skipped (format.sh: graftlint already gated)
        "uninventoried": (None if "--skip-drift" in argv else len(drift)),
        "out": out_path if write else None,
    }
    print(json.dumps(record, sort_keys=True))
    if drift:
        if not quiet:
            print("sharding_audit: PartitionSpec literals OUTSIDE the "
                  "inventoried modules (add a reasoned pragma, or move "
                  "the layout behind parallel/sharding.py):",
                  file=sys.stderr)
            for f in drift:
                print(f"  {f['path']}:{f['line']}: {f['message'][:100]}",
                      file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
