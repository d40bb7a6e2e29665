"""Operations the LFM2-MoE stack needs, computed from shapes and from the
MEASURED rows of the held experts (never from the program).  ``model`` is
a configuration file's ``model`` group, as run: the layers, the experts
held and the vocabulary slice of this chip."""
from __future__ import annotations


def _layers(model: dict) -> list:
    """``[(operator, sparse?)]`` per layer."""
    types = model.get("layer_types") or ["full_attention"] * model["n_layers"]
    return [(kind, i >= model.get("num_dense_layers", 0))
            for i, kind in enumerate(types)]


def _sizes(model: dict) -> dict:
    d = model["d_model"]
    head_dim = d // model["n_heads"]
    kv = model.get("n_kv_heads") or model["n_heads"]
    return {
        "conv": 3 * d * d + d * d + d * model.get("conv_kernel", 3),
        "attention": 2 * d * d + 2 * d * kv * head_dim + 2 * head_dim,
        "dense_mlp": 3 * d * model["d_ff"],
        "expert": 3 * d * (model.get("moe_d_ff") or model["d_ff"]),
        "router": d * model["num_experts"],
        "norms": 2 * d,
    }


def n_sparse_layers(model: dict) -> int:
    return sum(sparse for _, sparse in _layers(model))


def n_params(model: dict) -> int:
    """Parameters held on this chip (the tied embedding once, the
    selection bias not: it is a buffer)."""
    z = _sizes(model)
    held = len(model["moe_experts_held"])
    n = model["vocab_size"] * model["d_model"] + model["d_model"]
    for kind, sparse in _layers(model):
        n += z["conv"] if kind == "conv" else z["attention"]
        n += z["router"] + held * z["expert"] if sparse else z["dense_mlp"]
        n += z["norms"]
    return n


def active_params_per_token(model: dict, expert_rows_per_token: float
                            ) -> float:
    """Parameters one token multiplies: every dense one (the tied head
    once, the embedding lookup multiplies nothing) and one expert for
    each row routed to a HELD expert.  ``expert_rows_per_token`` is that
    row count summed over the sparse layers, as the program counted it
    (4 of 32 chosen, 8 held: about 1 a layer)."""
    z = _sizes(model)
    n = model["vocab_size"] * model["d_model"]
    for kind, sparse in _layers(model):
        n += z["conv"] if kind == "conv" else z["attention"]
        n += z["router"] if sparse else z["dense_mlp"]
    return n + z["expert"] * expert_rows_per_token


def train_flops_per_token(model: dict, seq_len: int,
                          expert_rows_per_token: float) -> float:
    """Forward + backward of one token: 6 per active parameter plus
    12 * d_model * seq for each ATTENTION layer (a conv layer's taps are
    3 multiply-adds a channel: not counted).  Recomputed operations
    (remat, the flash backward's score recompute) do not count."""
    attention_layers = sum(kind != "conv" for kind, _ in _layers(model))
    return (6.0 * active_params_per_token(model, expert_rows_per_token)
            + 12.0 * attention_layers * model["d_model"] * seq_len)


def expert_matmul_flops(model: dict, routed_rows: float) -> float:
    """Needed operations of the held experts' three matmuls for
    ``routed_rows`` rows, forward and backward (18 * d_model * expert
    width a row); tile padding and recompute not counted, so a share
    computed from this is a lower bound."""
    return 6.0 * _sizes(model)["expert"] * routed_rows


def causal_attention_flops(model: dict, n_sequences: int, seq_len: int,
                           backward: bool) -> float:
    """Matmul operations of causal attention over whole sequences in the
    ATTENTION layers alone (a conv layer has none): forward is QK^T and
    PV over the lower triangle (2 * 2 * S^2/2 * head_dim a head);
    backward adds dV, dP, dQ, dK (twice the forward).  Remat's second
    forward and the backward kernels' recompute of QK^T are not counted,
    so a share computed from this is a lower bound."""
    heads = model["n_heads"]
    head_dim = model["d_model"] // heads
    attention_layers = sum(kind != "conv" for kind, _ in _layers(model))
    fwd = 2.0 * seq_len * seq_len * head_dim * heads * n_sequences \
        * attention_layers
    return fwd * (3.0 if backward else 1.0)
