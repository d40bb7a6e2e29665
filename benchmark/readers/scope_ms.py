"""Exclusive device time of one scope bucket per step, ms: summed over
the first chip's ops whose compiled op name falls in ``bucket``
(``benchmark/lib/scopes.py``), over the traced steps.  Left out without
a trace or a usable scope table."""
from benchmark.lib import scopes


def read(context, bucket):
    joined = scopes.of(context)
    steps = context["counters"].get("trace_steps")
    if joined is None or not steps:
        return None
    seconds = joined["seconds"].get(bucket, 0.0)
    return seconds / steps * 1e3 if seconds > 0 else None
