"""Operations the Nemotron-H hybrid stack needs, computed from shapes and
from the MEASURED rows of the held experts (never from the program).
``model`` is a configuration file's ``model`` group, as run: the letters
of the pattern, and of each layer the share this chip holds (Mamba-2
groups, query heads, experts, the vocabulary slice)."""
from __future__ import annotations


def _held(model: dict) -> dict:
    """Counts held here: Mamba-2 heads and groups, query and KV heads,
    experts."""
    groups = model.get("ssm_groups_held")
    groups = model["ssm_groups"] if groups is None else len(groups)
    heads = model.get("attn_heads_held")
    heads = list(range(model["n_heads"])) if heads is None else heads
    per_kv = model["n_heads"] // (model.get("n_kv_heads")
                                  or model["n_heads"])
    experts = model.get("moe_experts_held")
    return {
        "ssm_groups": groups,
        "ssm_heads": groups * (model["ssm_heads"] // model["ssm_groups"]),
        "q_heads": len(heads), "kv_heads": len({h // per_kv for h in heads}),
        "experts": model["num_experts"] if experts is None else len(experts),
    }


def _sizes(model: dict) -> dict:
    """Parameters of each part that a token MULTIPLIES (``*_matmul``) and
    the small leaves beside them (``*_rest``: taps, biases, rates, norm
    scales, which the count of operations leaves out)."""
    d, held = model["d_model"], _held(model)
    inner = held["ssm_heads"] * model["ssm_head_dim"]
    conv = inner + 2 * held["ssm_groups"] * model["ssm_state"]
    hd = model.get("attn_head_dim") or d // model["n_heads"]
    return {
        "mamba_matmul": d * (inner + conv + held["ssm_heads"]) + inner * d,
        "mamba_rest": conv * model["conv_kernel"] + conv
        + 3 * held["ssm_heads"] + inner + d,
        "attention_matmul": 2 * d * held["q_heads"] * hd
        + 2 * d * held["kv_heads"] * hd,
        "attention_rest": d,
        # router, the two latent projections, the shared expert
        "latent_matmul": d * model["num_experts"]
        + 2 * d * model["moe_latent_dim"] + 2 * d * model["moe_shared_d_ff"],
        "latent_rest": d,
        "expert": 2 * model["moe_latent_dim"] * model["moe_d_ff"],
    }


def _count(model: dict, letter: str) -> int:
    return model["hybrid_pattern"].count(letter)


def n_params(model: dict) -> int:
    """Parameters held on this chip (embedding and untied head each hold
    the vocabulary slice; the selection bias not: it is a buffer)."""
    z, d = _sizes(model), model["d_model"]
    return (2 * model["vocab_size"] * d + d
            + _count(model, "M") * (z["mamba_matmul"] + z["mamba_rest"])
            + _count(model, "*") * (z["attention_matmul"]
                                    + z["attention_rest"])
            + _count(model, "E") * (z["latent_matmul"] + z["latent_rest"]
                                    + _held(model)["experts"] * z["expert"]))


def active_params_per_token(model: dict, expert_rows_per_token: float
                            ) -> float:
    """Parameters one token multiplies: every matrix outside the routed
    experts (the untied head once, the embedding lookup multiplies
    nothing) and one routed expert for each row routed to a HELD expert.
    ``expert_rows_per_token`` is that row count summed over the expert
    layers, as the program counted it (22 of 512 chosen, 8 held: about
    0.34 a layer)."""
    z = _sizes(model)
    return (model["vocab_size"] * model["d_model"]
            + _count(model, "M") * z["mamba_matmul"]
            + _count(model, "*") * z["attention_matmul"]
            + _count(model, "E") * z["latent_matmul"]
            + z["expert"] * expert_rows_per_token)


def _scan_flops_per_token_head(model: dict) -> float:
    """The chunked algorithm's forward at the published chunk ``Q``,
    state ``N`` and head width ``P``, a token and head: ``C B^T`` 2 Q N,
    its masked product with x 2 Q P, the chunk's state 2 N P, ``C`` times
    the carried state 2 N P."""
    q, n, p = model["ssm_chunk"], model["ssm_state"], model["ssm_head_dim"]
    return 2.0 * q * n + 2.0 * q * p + 4.0 * n * p


def ssd_scan_work(model: dict, tokens: float) -> tuple:
    """``(operations, bytes)`` the chunked scans of the Mamba-2 layers
    need for ``tokens`` tokens, forward and backward (the backward twice
    the forward's operations): bytes are x, B, C, dt read and y written
    once in bfloat16, and their gradients once.  Recompute (remat's
    second forward) is not counted, so a share computed from this is a
    lower bound."""
    held, layers = _held(model), _count(model, "M")
    ops = 3.0 * _scan_flops_per_token_head(model) * held["ssm_heads"]
    elements = (2 * held["ssm_heads"] * model["ssm_head_dim"]       # x, y
                + 2 * held["ssm_groups"] * model["ssm_state"]       # B, C
                + held["ssm_heads"])                                # dt
    return ops * tokens * layers, 2.0 * 2 * elements * tokens * layers


def train_flops_per_token(model: dict, seq_len: int,
                          expert_rows_per_token: float) -> float:
    """Forward + backward of one token: 6 per active parameter held, 12 *
    (query heads held * head size) * seq for each ATTENTION layer, the
    chunked scan's operations for each Mamba-2 layer (its taps, gates
    and norms are a few multiply-adds a channel: not counted).
    Recomputed operations (remat, the flash backward's score recompute)
    do not count."""
    held = _held(model)
    hd = model.get("attn_head_dim") or model["d_model"] // model["n_heads"]
    return (6.0 * active_params_per_token(model, expert_rows_per_token)
            + 12.0 * _count(model, "*") * held["q_heads"] * hd * seq_len
            + ssd_scan_work(model, 1.0)[0])


def expert_matmul_flops(model: dict, routed_rows: float) -> float:
    """Needed operations of the held experts' two matmuls for
    ``routed_rows`` rows, forward and backward (12 * latent width *
    expert width a row); tile padding and recompute not counted, so a
    share computed from this is a lower bound."""
    return 6.0 * _sizes(model)["expert"] * routed_rows


def causal_attention_flops(model: dict, n_sequences: int, seq_len: int,
                           backward: bool) -> float:
    """Matmul operations of causal attention over whole sequences in the
    ATTENTION layers alone, over the query heads held: forward is QK^T
    and PV over the lower triangle (2 * 2 * S^2/2 * head size a head);
    backward adds dV, dP, dQ, dK (twice the forward).  Remat's second
    forward and the backward kernels' recompute of QK^T are not counted,
    so a share computed from this is a lower bound."""
    hd = model.get("attn_head_dim") or model["d_model"] // model["n_heads"]
    fwd = (2.0 * seq_len * seq_len * hd * _held(model)["q_heads"]
           * n_sequences * _count(model, "*"))
    return fwd * (3.0 if backward else 1.0)
