"""The Mamba-2 layers' chunked scan's share of its roofline, %.

Time: exclusive device time of the ops under scope ``gpt/ssm_scan`` and
``kernel/ssm_scan`` (whatever implements the scan: plain XLA
contractions today, a kernel if one is written), forward + recompute +
backward, over the traced steps.  Needed work:
``ssd_scan_work(model, tokens)`` of the configuration's own ``flops``
module for the traced steps' tokens -- the chunked algorithm's four
contractions at the published chunk, forward and backward, and its
operands and result read or written once in bfloat16 with their
gradients.  The least time is the larger of operations over the
published bf16 peak and bytes over the published HBM bandwidth: at the
cell's shapes (chunk 128, state 128, head 64, 16 heads and one group
held) the operations are the larger, 20.0 ns a token and layer against
11.3 ns.  Recompute is time the count leaves out, so the share is a lower
bound and cannot pass 100.  Left out where the configuration's count has
no such function or the program no such scope (the parent commit)."""
import importlib

from benchmark.lib import peaks, scopes as scopes_lib
from benchmark.readers import scope_sum_ms


def read(context):
    c, config = context["counters"], context["cell"]["config"]
    joined = scopes_lib.of(context)
    if joined is None or "trace_steps" not in c or "flops" not in config:
        return None
    flops = importlib.import_module("benchmark.lib." + config["flops"])
    s = scope_sum_ms.seconds(joined, ("ssm_scan", "kernel/ssm_scan"))
    if s <= 0 or not hasattr(flops, "ssd_scan_work"):
        return None
    tokens = (c["trace_steps"] * c["global_batch"] * c["sequence_tokens"]
              / c["chips"])
    ops, nbytes = flops.ssd_scan_work(config["model"], tokens)
    kind = context["device"]["kind"]
    least = max(ops / peaks.peak(kind, "bf16_flops"),
                nbytes / peaks.peak(kind, "hbm_bytes_per_s"))
    return 100.0 * least / s
