"""Host time of an epoch outside the device wait, ms: percentile ``q``
over the window's ``epoch_end`` events (the Trainer's flight recorder)
of ``plan_s + dispatch_s + log_s + callbacks_s``.  ``readback_s``, where
the host waits for the device, is not host work and stays out.  The
epochs in which the benchmark's own profiler started or stopped are left
out (its stop sits inside the epoch-end callback).  Left out on a
program whose ``epoch_end`` carries no such fields."""
from benchmark.lib import stats

FIELDS = ("plan_s", "dispatch_s", "log_s", "callbacks_s")


def read(context, q):
    c = context["counters"]
    n = len(c.get("epoch_s") or ())
    try:
        from ray_lightning_accelerators_tpu import telemetry
        events = [e.get("data") or {} for e in
                  telemetry.get_recorder().events()
                  if e["kind"] == "epoch_end"][-n:] if n else []
    except Exception:
        return None
    traced = range(1, 1 + c.get("trace_steps", 0) // max(
        1, c.get("steps_per_epoch", 1)))
    host = [sum(d[f] for f in FIELDS) for i, d in enumerate(events)
            if i not in traced and all(f in d for f in FIELDS)]
    return stats.percentile(host, q) * 1e3 if host else None
