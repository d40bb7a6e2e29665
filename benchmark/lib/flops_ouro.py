"""Operations a looped stack needs, computed from shapes (never from the
program).  ``model`` is a configuration file's ``model`` group, as run:
``n_layers`` layers applied ``loop_passes`` times on the same weights,
the head after every pass."""
from __future__ import annotations


def _sizes(model: dict) -> dict:
    d = model["d_model"]
    attn = model["n_heads"] * (model.get("attn_head_dim")
                               or d // model["n_heads"])
    return {"attention": 4 * d * attn, "mlp": 3 * d * model["d_ff"],
            "norms": 4 * d, "vocabulary": model["vocab_size"] * d,
            "gate": d + 1, "attn_width": attn}


def n_params(model: dict) -> int:
    """Parameters held: every layer ONCE (the passes share them), the
    embedding, the untied head, the final norm and the gate."""
    z = _sizes(model)
    n = model["n_layers"] * (z["attention"] + z["mlp"] + z["norms"])
    n += z["vocabulary"] * (1 if model.get("tie_embeddings", True) else 2)
    n += model["d_model"]
    return n + (z["gate"] if model.get("exit_gate") else 0)


def params_multiplied_per_token(model: dict) -> int:
    """Parameters one token multiplies in a forward: the layers'
    matrices and the head once a PASS (the embedding lookup multiplies
    nothing; the norms' scales are no matrices), and the gate's weights
    after every pass but the last."""
    z, passes = _sizes(model), model.get("loop_passes", 1)
    n = passes * (model["n_layers"] * (z["attention"] + z["mlp"])
                  + z["vocabulary"])
    return n + (passes - 1) * model["d_model"] * bool(model.get("exit_gate"))


def train_flops_per_token(model: dict, seq_len: int) -> float:
    """Forward + backward of one token: 6 per parameter multiplied, plus
    the CAUSAL attention work of every application, 6 x attention width
    x seq (QK^T and PV over the lower triangle are 2 x seq x width a
    token forward, the backward twice that): the count of
    ``causal_attention_flops``.  ``lib/flops.py`` counts 12, the whole
    square: at 24 applications of 8k attention that would put 2.4 GFLOP
    a token, a fifth more, of work nobody does into ``mfu.train``.
    Recomputed operations (remat, the flash backward's score recompute)
    do not count."""
    applications = model.get("loop_passes", 1) * model["n_layers"]
    return (6.0 * params_multiplied_per_token(model)
            + 6.0 * applications * _sizes(model)["attn_width"] * seq_len)


def causal_attention_flops(model: dict, n_sequences: int, seq_len: int,
                           backward: bool) -> float:
    """Matmul operations of causal attention over whole sequences in
    every APPLICATION of a layer (``loop_passes`` x ``n_layers``):
    forward is QK^T and PV over the lower triangle (2 * 2 * S^2/2 *
    head_dim a head); backward adds dV, dP, dQ, dK (twice the forward).
    The backward kernels' recompute of QK^T is not counted, so a share
    computed from this is a lower bound."""
    applications = model.get("loop_passes", 1) * model["n_layers"]
    fwd = 2.0 * seq_len * seq_len * _sizes(model)["attn_width"] \
        * n_sequences * applications
    return fwd * (3.0 if backward else 1.0)
