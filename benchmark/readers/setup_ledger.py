"""What jax's pipeline did before the window, from the program's compile
ledger (``analysis/compile_guard.ledger``: one row a program traced,
lowered, compiled or loaded, by name, on ``time.monotonic()``), folded
by ``compile_guard.summary``.  Every row of the PROCESS that ends before
set-up does (``setup_fit.before_window``), the reference's programs
included; what the driver lowers after the fit is out.

    what="compile_s"  trace_s + lower_s + backend_s, s
    what="built"      programs compiled in this process (cache misses,
                      programs under jax's persistence thresholds, and
                      programs jax does not cache)
    what="loaded"     programs taken from the persistent cache

Left out (``None``) on a program with no ledger or no ``fit_ready``.
"""
from benchmark.readers import setup_fit


def read(context, what):
    found = setup_fit.before_window(context)
    if found is None:
        return None
    try:
        from ray_lightning_accelerators_tpu.analysis import compile_guard
        rows = compile_guard.ledger(until=found[0])
    except (ImportError, AttributeError):   # the parent's program: no ledger
        return None
    s = compile_guard.summary(rows)
    if what == "compile_s":
        return s["trace_s"] + s["lower_s"] + s["backend_s"]
    return s[what]
