"""Flash-attention kernels' share of their compute roofline, %.

Kernel time: summed exclusive device time, on the first chip, of the
Pallas custom calls whose first operand is ``[.., seq, head_dim]`` (the
trace carries no kernel name; the rms-norm kernel's operand is
``[.., d_model]``).  Needed work: causal attention's matmul operations
for the traced steps' sequences on that chip, forward and backward, from
``benchmark/lib/flops.py`` (recompute not counted, so a lower bound),
over the published bf16 peak.  Attention at these shapes is
compute-bound, so the compute side is the roofline."""
from benchmark.lib import flops, peaks, trace


def read(context):
    reduced, c = context["trace"], context["counters"]
    if reduced is None or "trace_steps" not in c:
        return None
    model = context["cell"]["config"]["model"]
    seq, head_dim = c["sequence_tokens"], model["d_model"] // model["n_heads"]
    def is_flash(name):
        shapes = trace.operand_shapes(name) if trace.is_pallas(name) else []
        return bool(shapes) and shapes[0][-2:] == (seq, head_dim)

    seconds = sum(s for name, s in reduced["exclusive"].items()
                  if is_flash(name))
    if seconds <= 0:
        return None
    sequences = c["trace_steps"] * c["global_batch"] // c["chips"]
    needed = flops.causal_attention_flops(model, sequences, seq,
                                          backward=True)
    peak = peaks.peak(context["device"]["kind"], "bf16_flops")
    return 100.0 * needed / peak / seconds
