"""The held experts' grouped matmuls' share of the compute roofline, %.

Time: exclusive device time of the ops under scope ``gpt/moe_experts``
and ``kernel/moe_gmm`` (whatever implements them: a Pallas grouped
matmul, ``ragged_dot``, the SwiGLU between them), forward + recompute +
backward, over the traced steps.  Needed work: 18 x d_model x expert
width for every row routed to a held expert in those same steps
(``benchmark/lib/flops_lfm2.py``; the rows are the program's own counter
summed over the traced steps, ``moe_rows_traced``), over the published
bf16 peak.  Recompute and
tile padding are time the count leaves out, so the share is a lower
bound and cannot pass 100."""
import importlib

from benchmark.lib import peaks, scopes as scopes_lib
from benchmark.readers import scope_sum_ms


def read(context):
    c = context["counters"]
    joined = scopes_lib.of(context)
    rows = c.get("moe_rows_traced")
    if joined is None or not rows:
        return None
    s = scope_sum_ms.seconds(joined, ("moe_experts", "kernel/moe_gmm"))
    if s <= 0:
        return None
    config = context["cell"]["config"]
    flops = importlib.import_module("benchmark.lib." + config["flops"])
    needed = flops.expert_matmul_flops(config["model"], rows)
    peak = peaks.peak(context["device"]["kind"], "bf16_flops")
    return 100.0 * needed / peak / s
