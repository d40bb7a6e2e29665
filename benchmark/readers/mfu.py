"""Model FLOP/s utilization, %: the benchmark's own operation count per
token x tokens per second / (chips x the published bf16 peak of this
device kind).  Recomputed operations do not count."""
from benchmark.lib import peaks


def read(context):
    c = context["counters"]
    if "flops_per_token" not in c:
        return None
    peak = peaks.peak(context["device"]["kind"], "bf16_flops")
    return 100.0 * c["flops_per_token"] * c["tokens_per_s"] / (
        c["chips"] * peak)
