"""Flash-attention kernels' share of their compute roofline, %, in a cell
whose configuration names its own operation count (``"flops"``): a stack
in which only some layers are attention.

Kernel time as ``readers/flash_roofline.py`` takes it: summed exclusive
device time, on the first chip, of the Pallas custom calls whose first
operand is ``[.., seq, head_dim]`` (forward, remat's second forward, and
the backward's kernels: at several k blocks a sequence ``flash_bwd_dq``
and ``flash_bwd_dkv``).  Needed work: causal attention's matmul
operations of the traced steps' sequences, forward and backward, in the
attention layers alone, from the configuration's count; recompute is
time the count leaves out, so the share is a lower bound."""
import importlib

from benchmark.lib import peaks, trace


def read(context):
    reduced, c = context["trace"], context["counters"]
    config = context["cell"]["config"]
    if reduced is None or "trace_steps" not in c or "flops" not in config:
        return None
    model = config["model"]
    seq, head_dim = c["sequence_tokens"], model["d_model"] // model["n_heads"]

    def is_flash(name):
        shapes = trace.operand_shapes(name) if trace.is_pallas(name) else []
        return bool(shapes) and shapes[0][-2:] == (seq, head_dim)

    seconds = sum(s for name, s in reduced["exclusive"].items()
                  if is_flash(name))
    if seconds <= 0:
        return None
    flops = importlib.import_module("benchmark.lib." + config["flops"])
    sequences = c["trace_steps"] * c["global_batch"] // c["chips"]
    needed = flops.causal_attention_flops(model, sequences, seq,
                                          backward=True)
    peak = peaks.peak(context["device"]["kind"], "bf16_flops")
    return 100.0 * needed / peak / seconds
