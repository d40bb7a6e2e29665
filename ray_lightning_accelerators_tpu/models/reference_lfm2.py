"""The plain reference of the LFM2-MoE layer stack (``model_type:
lfm2_moe``): forward, loss and (by ``jax.grad``) gradients in
straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  No kernel, no sort, no
cache: the sparse block is a sum over the held experts, one at a time,
each applied to every position and masked by the selection; attention is
computed a block of queries at a time so that 8k positions fit.

    layer l:   h = x + op_l(rms(x; ln1))        op_l = conv | attention
               y = h + ff_l(rms(h; ln2))        ff_l = dense SwiGLU | sparse
    model:     logits = rms(h_L; ln_f) @ E^T    E the token embedding

    conv:      [B, C, u] = split3(x @ W_in);  v = B * u
               c_t = sum_j w[:, j] * v_{t-(L-1)+j}   (zeros before t = 0)
               out = (C * c) @ W_out
    attention: q, k = rope(rms_head(q)), rope(rms_head(k))   rotate-half
               softmax(q k^T / sqrt(head_dim), causal) v; each KV head
               serves n_heads / n_kv_heads query heads
    sparse:    s = sigmoid(x W_g);  sel = top_k(s + b)
               w = s[sel] / (sum(s[sel]) + 1e-6) * routed_scale
               out = sum_{e in sel, e held} w_e * SwiGLU_e(x)
               (w carries no gradient where only some experts are held:
               the router's gradient is a sum over every chip's share)

It reads the program's parameter tree (``layers_<i>`` runs of stacked
layers: ``conv`` | ``attn``, ``mlp`` with a ``router`` when sparse,
``ln1``, ``ln2``) and the configuration's ``model`` group, and nothing
else of the program.  Departures from the published model are the
configuration file's ``assumed``: tied LM head, a frozen selection bias
drawn from the seed, no auxiliary loss.

``benchmark/lib/reference_lfm2.py`` is a copy of this file (a test holds
the two identical).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = "highest"
Q_BLOCK = 1024      # queries per attention block


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope_half(x, theta):  # [b, h, s, hd]: dimension i pairs with i + hd/2
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def conv_operator(x, p):
    """The doubly gated causal depthwise convolution.  x: [b, s, d]."""
    with jax.default_matmul_precision(HIGHEST):
        gate_b, gate_c, u = jnp.split(x @ p["w_in"], 3, axis=-1)
        v = gate_b * u
        s, taps = v.shape[1], p["conv_w"].shape[1]
        c = jnp.zeros_like(v)
        for j in range(taps):
            back = taps - 1 - j      # tap j reads position t - back
            c = c + p["conv_w"][:, j] * jnp.pad(
                v, ((0, 0), (back, 0), (0, 0)))[:, :s]
        return (gate_c * c) @ p["w_out"]


@functools.partial(jax.checkpoint, static_argnums=(3,))
def _attend_block(q, k, v, first):
    """Causal attention of one block of queries (positions ``first``..)
    over all keys; checkpointed so that a gradient keeps no [s, s]
    probabilities."""
    with jax.default_matmul_precision(HIGHEST):
        s = jnp.einsum("bhqk,bhtk->bhqt", q, k) * q.shape[-1] ** -0.5
        rows = first + jnp.arange(q.shape[2])[:, None]
        s = jnp.where(jnp.arange(k.shape[2])[None] <= rows, s, -jnp.inf)
        return jnp.einsum("bhqt,bhtk->bhqk", jax.nn.softmax(s, -1), v)


def attention_operator(x, p, model):
    """GQA with per-head RMSNorm on q and k, rotate-half rotary."""
    eps, theta = model["norm_eps"], model["rope_theta"]
    with jax.default_matmul_precision(HIGHEST):
        q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"])
        k = jnp.einsum("bsd,dhk->bhsk", x, p["wk"])
        v = jnp.einsum("bsd,dhk->bhsk", x, p["wv"])
        if "q_norm" in p:
            q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
        q, k = _rope_half(q, theta), _rope_half(k, theta)
        groups = q.shape[1] // k.shape[1]
        k, v = jnp.repeat(k, groups, 1), jnp.repeat(v, groups, 1)
        blocks = [_attend_block(q[:, :, i:i + Q_BLOCK], k, v, i)
                  for i in range(0, q.shape[2], Q_BLOCK)]
        return jnp.einsum("bhsk,hkd->bsd", jnp.concatenate(blocks, 2),
                          p["wo"])


def _swiglu(x, w1, w3, w2):
    return (jax.nn.silu(x @ w1) * (x @ w3)) @ w2


def route(x, p, model):
    """``(ids [.., k] sorted, weights [.., k], margin [..])``: the chosen
    experts in ascending id order, their normalised scores, and the 4th
    minus the 5th biased score (how far the choice is from flipping)."""
    k = model["moe_top_k"]
    with jax.default_matmul_precision(HIGHEST):
        s = jax.nn.sigmoid(x @ p["router"])
    biased = s + p["expert_bias"]
    top, _ = jax.lax.top_k(biased, k + 1)
    chosen = biased >= top[..., k - 1:k]
    ids = jnp.sort(jnp.where(chosen, jnp.arange(s.shape[-1]),
                             s.shape[-1]), -1)[..., :k]
    w = jnp.take_along_axis(s, ids, -1)
    if model.get("moe_norm_topk", True):
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-6)
    return ids, w * model.get("moe_routed_scale", 1.0), \
        top[..., k - 1] - top[..., k]


def sparse_block(x, p, model, held):
    """``sum_{e chosen and held} w_e * SwiGLU_e(x)``: every held expert
    over every position, masked by the selection.  ``p["w1"][i]`` is
    expert ``held[i]``.  Returns ``(out, ids, margin)``."""
    ids, w, margin = route(x, p, model)
    if len(held) < model["num_experts"]:
        w = jax.lax.stop_gradient(w)
    out = jnp.zeros_like(x)
    with jax.default_matmul_precision(HIGHEST):
        for slot, expert in enumerate(held):
            gate = jnp.sum(jnp.where(ids == expert, w, 0.0), -1)
            out = out + gate[..., None] * _swiglu(
                x, p["w1"][slot], p["w3"][slot], p["w2"][slot])
    return out, ids, margin


@functools.partial(jax.jit, static_argnames=("kind", "model", "held"))
def _layer(h, p, *, kind, model, held):
    """One layer of ``kind`` = (operator, feed-forward).  ``model`` is the
    configuration's ``model`` group as a sorted tuple of items (static)."""
    model = dict(model)
    eps = model["norm_eps"]
    x = _rms(h, p["ln1"], eps)
    h = h + (conv_operator(x, p["conv"]) if kind[0] == "conv"
             else attention_operator(x, p["attn"], model))
    x = _rms(h, p["ln2"], eps)
    if kind[1] == "sparse":
        y, ids, margin = sparse_block(x, p["mlp"], model, held)
        return h + y, (ids, margin)
    with jax.default_matmul_precision(HIGHEST):
        m = p["mlp"]
        return h + _swiglu(x, m["w1"], m["w3"], m["w2"]), None


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(h, ln_f, embed, *, eps):
    with jax.default_matmul_precision(HIGHEST):
        return _rms(h, ln_f, eps) @ embed.T


def _static(model: dict) -> tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in model.items()))


def _held(model: dict, held) -> tuple:
    if held is None:
        held = model.get("moe_experts_held")
    return tuple(range(model["num_experts"])) if held is None \
        else tuple(held)


def forward(params, tokens, model: dict, held=None, remat: bool = False):
    """``(logits [b, s, vocab], routing)`` of ``tokens`` under ``params``
    (any float dtype; widened to float32 layer by layer).  ``routing`` is
    ``{"selected": [n_sparse, b, s, k] sorted ids, "margin": [n_sparse,
    b, s]}`` (None without a sparse layer).  ``held`` overrides the
    configuration's ``moe_experts_held``.  ``remat`` keeps only each
    layer's input for a gradient (the same arithmetic, computed twice):
    at 8k positions the float32 intermediates of every layer do not fit
    one chip together."""
    if "unembed" in params:
        raise NotImplementedError("the reference ties the LM head")
    held, static = _held(model, held), _static(model)
    embed = params["embed"].astype(jnp.float32)
    h = embed[tokens]
    routing = []
    run = 0
    while f"layers_{run}" in params:
        stack = params[f"layers_{run}"]
        kind = ("conv" if "conv" in stack else "attn",
                "sparse" if "router" in stack["mlp"] else "dense")
        for i in range(jax.tree.leaves(stack)[0].shape[0]):
            layer = jax.tree.map(lambda a: a[i].astype(jnp.float32), stack)
            apply = functools.partial(_layer, kind=kind, model=static,
                                      held=held)
            h, routed = (jax.checkpoint(apply) if remat else apply)(h, layer)
            if routed is not None:
                routing.append(routed)
        run += 1
    out = _head(h, params["ln_f"].astype(jnp.float32), embed,
                eps=model["norm_eps"])
    if not routing:
        return out, None
    return out, {"selected": jnp.stack([r[0] for r in routing]),
                 "margin": jnp.stack([r[1] for r in routing])}


@jax.jit
def lm_loss(all_logits, tokens):
    """Mean next-token cross entropy, positions 0..S-2 -> targets 1..S-1."""
    lp = jax.nn.log_softmax(all_logits[:, :-1], -1)
    return -jnp.mean(jnp.take_along_axis(lp, tokens[:, 1:, None], -1))


def loss(params, tokens, model: dict, held=None):
    return lm_loss(forward(params, tokens, model, held, remat=True)[0],
                   tokens)


def loss_and_grads(params, tokens, model: dict, held=None):
    """The loss and its gradient with respect to every parameter."""
    return jax.value_and_grad(loss)(params, tokens, model, held)


GROUPS = ("router", "experts", "conv", "attention", "dense_mlp",
          "embedding")


def grad_group_norms(grads) -> dict:
    """L2 norm of a gradient tree by group: ``router``, ``experts``
    (w1, w3, w2 of the sparse layers), ``conv``, ``attention``,
    ``dense_mlp``, ``embedding``.  Norm scales ride with the group of
    their layer's operator (ln1) or feed-forward (ln2); the final norm
    with the embedding; the selection bias has no gradient."""
    sq = dict.fromkeys(GROUPS, 0.0)

    def add(group, tree):
        sq[group] = sq[group] + sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree.leaves(tree))

    add("embedding", [grads["embed"], grads["ln_f"]])
    run = 0
    while f"layers_{run}" in grads:
        g = grads[f"layers_{run}"]
        op = "conv" if "conv" in g else "attn"
        add("conv" if op == "conv" else "attention", [g[op], g["ln1"]])
        if "router" in g["mlp"]:
            add("router", g["mlp"]["router"])
            add("experts", [g["mlp"][w] for w in ("w1", "w3", "w2")]
                + [g["ln2"]])
        else:
            add("dense_mlp", [g["mlp"], g["ln2"]])
        run += 1
    return {k: float(jnp.sqrt(v)) for k, v in sq.items()}
