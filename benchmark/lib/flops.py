"""Operations the algorithm needs, computed from shapes (never from the
program).  ``model`` is a configuration file's ``model`` group."""
from __future__ import annotations


def n_params(model: dict) -> int:
    d, f, layers = model["d_model"], model["d_ff"], model["n_layers"]
    per_layer = 4 * d * d + 2 * d * f + 2 * d   # q,k,v,o + wi,wo + 2 norms
    n = model["vocab_size"] * d + layers * per_layer + d
    if not model.get("tie_embeddings", True):
        n += d * model["vocab_size"]
    return n


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """Forward + backward of one token: 6 per parameter (the tied
    embedding counts once, as the LM-head matmul) plus attention's
    12 * layers * d_model * seq.  Recomputed operations (remat, the flash
    backward's score recompute) do not count."""
    return (6.0 * n_params(model)
            + 12.0 * model["n_layers"] * model["d_model"] * seq_len)


def causal_attention_flops(model: dict, n_sequences: int, seq_len: int,
                           backward: bool) -> float:
    """Matmul operations of causal attention over whole sequences, all
    layers: forward is QK^T and PV over the lower triangle
    (2 * 2 * S^2/2 * head_dim per head); backward adds dV, dP, dQ, dK
    (twice the forward).  The backward kernel's recompute of QK^T is not
    counted, so a share computed from this is a lower bound."""
    heads = model["n_heads"]
    head_dim = model["d_model"] // heads
    fwd = 2.0 * seq_len * seq_len * head_dim * heads * n_sequences \
        * model["n_layers"]
    return fwd * (3.0 if backward else 1.0)
