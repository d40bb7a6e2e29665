"""The program's share of set-up, s, from its own start-up ledger: the
``fit_ready`` flight-recorder event (``Trainer.fit``'s entry on
``time.monotonic()``, the four ``setup_*`` phases, the first epoch) and
the ``epoch_end`` events, which stand on the same clock.

Set-up ends, for a reader, at the ``epoch_end`` event that precedes the
window's first epoch (the window holds ``len(counters["epoch_s"])``
epochs): where the drivers put ``t0``.

    what="fit"          fit's entry to that event: ``setup_s`` less this
                        is the benchmark's own (imports, weights from the
                        seed, the reference check, spreading)
    what="first_epoch"  wall seconds of the epochs before the window: the
                        first (``fit_ready.first_epoch_s``: first
                        execution, the step programs traced, lowered and
                        compiled or loaded inside its dispatch) and any
                        later warm epoch's five fields

Left out (``None``) on a program that emits no ``fit_ready``.
"""

FIELDS = ("plan_s", "dispatch_s", "readback_s", "log_s", "callbacks_s")


def before_window(context):
    """``(t_end, fit_ready's data, the warm epochs' epoch_end data after
    the first)``, or None: ``t_end`` is ``time.monotonic()`` where set-up
    ends."""
    n = len(context["counters"].get("epoch_s") or ())
    try:
        from ray_lightning_accelerators_tpu import telemetry
        recorder = telemetry.get_recorder()
        ready = recorder.last("fit_ready")
        ends = [e for e in recorder.events() if e["kind"] == "epoch_end"]
    except (ImportError, AttributeError):   # the parent's program: no last()
        return None
    if not n or ready is None or not ends:
        return None
    warm = ends[-1]["data"]["epoch"] - n      # epochs before the window
    by_epoch = {e["data"]["epoch"]: e for e in ends}
    later = [by_epoch.get(k) for k in range(2, warm + 1)]
    if warm < 1 or None in later:
        return None     # the ring has rolled over a warm epoch
    # fit_ready follows the first epoch's epoch_end by microseconds, and
    # unlike it is kept when the ring rolls over
    t_end = by_epoch[warm]["ts"] if warm in by_epoch else ready["ts"]
    return t_end, ready["data"], [e["data"] for e in later]


def read(context, what):
    found = before_window(context)
    if found is None:
        return None
    t_end, ready, later = found
    if what == "fit":
        return t_end - ready["fit_start"]
    if what == "first_epoch":
        return ready["first_epoch_s"] + sum(
            d.get(f, 0.0) for d in later for f in FIELDS)
    raise ValueError(f"setup_fit: no such reading: {what!r}")
