"""Share of the traced window, on the first chip, spent in collective
operations on the core's own op line (all-gather, reduce-scatter,
all-reduce, all-to-all, collective-permute and their -start/-done
halves): while one of those runs there, no other op does, so this is
the collective time that compute did not hide, %."""
from benchmark.lib import trace


def read(context):
    reduced = context["trace"]
    if reduced is None or context["counters"].get("chips", 1) < 2:
        return None
    seconds = sum(s for name, s in reduced["exclusive"].items()
                  if trace.is_collective(name))
    return 100.0 * seconds / reduced["window_s"]
