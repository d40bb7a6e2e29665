"""Static analysis & runtime guards: the repo's prose invariants, enforced.

Two halves (see docs/API.md "Static analysis & compile guard"):

- **graftlint** (`lint.py` + `rules/`): an AST-based, JAX-aware analyzer
  that checks the invariants every perf PR has paid for — no host syncs
  in the hot step/decode paths, no retrace hazards at jit boundaries, no
  tracer leakage out of jitted functions, every ``RLA_TPU_*`` env knob
  declared in the `knobs` registry, every worker-raised typed exception
  wire-rebuildable (`runtime/wire.py`) — plus the SPMD safety pass:
  collective axis arguments resolve to declared mesh axes, no
  rank-divergent control flow around collectives/barriers/commits, no
  PartitionSpec literals off the audited sharding surface
  (``scripts/sharding_audit.py``).  CLI: ``scripts/graftlint.py``
  (``--format json`` for CI / the audit script).
- **compile-guard** (`compile_guard.py`): a runtime complement counting
  the programs jax hands to the backend (compiles and persistent-cache
  loads) via ``jax.monitoring``, so a test (or bench) can assert "this
  block compiles at most N programs" — the serve engine's 3-program
  invariant and the trainer's no-retrace-after-warmup are pinned this
  way in ``tests/test_analysis.py`` — and keeping the compile ledger:
  every program by name, with its seconds, its cache outcome and the
  Trainer phase it fell in.

``knobs`` is imported eagerly (it is a leaf: stdlib only); the analyzer
loads lazily, and the package's own ``__init__`` installs the guard's
listeners (a registration: nothing runs until jax compiles).
"""

from . import knobs  # noqa: F401  (leaf module: registry + typed getters)

__all__ = ["knobs", "lint", "compile_guard"]


def __getattr__(name):
    if name in ("lint", "compile_guard"):
        import importlib
        return importlib.import_module(f".{name}", __name__)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
