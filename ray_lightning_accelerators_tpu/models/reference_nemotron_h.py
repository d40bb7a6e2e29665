"""The plain reference of the Nemotron-H hybrid stack with a latent
expert layer (``model_type: nemotron_h``, the Nemotron 3 family):
forward, loss and (by ``jax.grad``) gradients in straightforward float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``.  No
kernel, no sort, no chunked scan: the state-space recurrence is a
``lax.scan`` over positions, the routed experts a sum over the held
ones, one at a time, each applied to every position and masked by the
selection; attention is computed a block of queries at a time so that 8k
positions fit.

    every layer:  h = h + part(rms(h; w, eps)),  the part by the letter of
                  ``hybrid_pattern``; then rms and the untied head

    M (Mamba-2), H heads of P, G groups, state N, H/G heads a group:
      [z | xBC | dt] = u W_in            d -> H*P + (H*P + 2*G*N) + H
      xBC = silu(conv(xBC) + b_conv)     causal, depthwise
      [x | B | C] = split(xBC)           x: [H, P]; B, C: [G, N]
      dt = softplus(dt + dt_bias);  A = -exp(A_log)      per head
      S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T;  y_t = S_t C_t + D x_t
      out = group_rms(y * silu(z); w_norm, eps) W_out    gate, then norm
    * (attention): GQA, softmax(q k^T / sqrt(head size)) v, causal; no
      rotary, no QK-norm
    E (latent expert layer):
      s = sigmoid(u W_r);  sel = top_k(s + bias)
      w_e = scale * s_e / (sum_{e in sel} s_e + 1e-20)
      l = u W_fc1;  r = sum_{e in sel, e held} w_e relu(l U_e)^2 V_e
      out = r W_fc2 + relu(u U_s)^2 V_s
      (w carries no gradient where only some experts are held: the
      router's gradient is a sum over every chip's share)

It reads the program's parameter tree (``layers_<i>`` runs of stacked
blocks; a block holds ``ssm`` or ``attn`` with ``ln1``, and/or ``mlp``
with ``ln2``) and the configuration's ``model`` group, and nothing else
of the program.  The share of a layer held here is read off the
parameters' own shapes (Mamba-2 heads and groups, query and KV heads);
the experts held are ids (``moe_experts_held``).  Departures from the
published model are the configuration file's ``assumed``.

``benchmark/lib/reference_nemotron_h.py`` is a copy of this file (a test
holds the two identical).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = "highest"
Q_BLOCK = 1024      # queries per attention block
SCAN_SEGMENT = 128  # positions whose states a gradient keeps at a time


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _recurrence(x, dt, a, b_in, c_in):
    """``y_t = S_t C_t`` with ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t
    B_t^T``, one position at a time.  x: [b, s, h, p]; dt: [b, s, h];
    a: [h]; b_in, c_in: [b, s, h, n] (each head its group's).  The scan
    runs in checkpointed segments so that a gradient keeps one segment's
    states, not every position's; the arithmetic is the stepwise one."""
    bsz, s, h, p = x.shape
    n = b_in.shape[-1]

    def step(state, at):
        x_t, dt_t, b_t, c_t = at
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return state, jnp.sum(state * c_t[..., None, :], -1)

    @jax.checkpoint
    def segment(state, at):
        return jax.lax.scan(step, state, at)

    seg = SCAN_SEGMENT if s % SCAN_SEGMENT == 0 else s
    # time-major, in segments: [s / seg, seg, b, ...]
    at = tuple(t.swapaxes(0, 1).reshape(s // seg, seg, *t.shape[:1],
                                        *t.shape[2:])
               for t in (x, dt, b_in, c_in))
    _, y = jax.lax.scan(segment, jnp.zeros((bsz, h, p, n), x.dtype), at)
    return y.reshape(s, bsz, h, p).swapaxes(0, 1)


def mamba_mixer(u, p, model):
    """The Mamba-2 mixer over the heads and groups ``p`` holds."""
    eps, hd, n = model["norm_eps"], model["ssm_head_dim"], model["ssm_state"]
    heads = p["a_log"].shape[0]
    groups = heads // (model["ssm_heads"] // model["ssm_groups"])
    inner, bc = heads * hd, groups * n
    bsz, s, _ = u.shape
    with jax.default_matmul_precision(HIGHEST):
        z, xbc, dt = jnp.split(u @ p["w_in"], [inner, 2 * inner + 2 * bc], -1)
        taps = p["conv_w"].shape[1]
        conv = jnp.zeros_like(xbc)
        for j in range(taps):
            back = taps - 1 - j      # tap j reads position t - back
            conv = conv + p["conv_w"][:, j] * jnp.pad(
                xbc, ((0, 0), (back, 0), (0, 0)))[:, :s]
        xbc = jax.nn.silu(conv + p["conv_b"])
        x, b_in, c_in = jnp.split(xbc, [inner, inner + bc], -1)
        x = x.reshape(bsz, s, heads, hd)
        per_group = heads // groups
        b_in = jnp.repeat(b_in.reshape(bsz, s, groups, n), per_group, 2)
        c_in = jnp.repeat(c_in.reshape(bsz, s, groups, n), per_group, 2)
        dt = jax.nn.softplus(dt + p["dt_bias"])
        y = _recurrence(x, dt, -jnp.exp(p["a_log"]), b_in, c_in)
        y = y + p["d_skip"][:, None] * x
        y = y.reshape(bsz, s, inner) * jax.nn.silu(z)
        y = _rms(y.reshape(bsz, s, groups, inner // groups), 1.0, eps)
        return (y.reshape(bsz, s, inner) * p["norm"]) @ p["w_out"]


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


def attention_operator(x, p):
    """GQA over the query and KV heads ``p`` holds: each KV head serves
    as many query heads as the others, in order."""
    with jax.default_matmul_precision(HIGHEST):
        q = jnp.einsum("bsd,dhk->bhsk", x, p["wq"])
        k = jnp.einsum("bsd,dhk->bhsk", x, p["wk"])
        v = jnp.einsum("bsd,dhk->bhsk", x, p["wv"])
        groups = q.shape[1] // k.shape[1]
        k, v = jnp.repeat(k, groups, 1), jnp.repeat(v, groups, 1)
        blocks = [_attend_block(q[:, :, i:i + Q_BLOCK], k, v, i)
                  for i in range(0, q.shape[2], Q_BLOCK)]
        return jnp.einsum("bhsk,hkd->bsd", jnp.concatenate(blocks, 2),
                          p["wo"])


def route(x, p, model, held):
    """``(ids [.., k] sorted, weights [.., k], margin [..], held_margin
    [..])``: the chosen experts in ascending id order, their normalised
    and scaled scores, the last chosen minus the first rejected biased
    score (how far the choice is from flipping), and how far the nearest
    HELD expert is from changing sides: a chosen one above the first
    rejected score, a rejected one below the last chosen."""
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
        w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    mine = biased[..., jnp.asarray(held)]
    held_margin = jnp.min(jnp.where(
        mine >= top[..., k - 1:k], mine - top[..., k:k + 1],
        top[..., k - 1:k] - mine), -1)
    return ids, w * model.get("moe_routed_scale", 1.0), \
        top[..., k - 1] - top[..., k], held_margin


def routed_experts(latent, p, ids, w, held):
    """``sum_{e chosen and held} w_e relu(l U_e)^2 V_e`` in the latent:
    every held expert over every position, masked by the selection.
    ``p["w1"][i]`` is expert ``held[i]``."""
    out = jnp.zeros_like(latent)
    with jax.default_matmul_precision(HIGHEST):
        for slot, expert in enumerate(held):
            gate = jnp.sum(jnp.where(ids == expert, w, 0.0), -1)
            out = out + gate[..., None] * (
                jnp.square(jax.nn.relu(latent @ p["w1"][slot]))
                @ p["w2"][slot])
    return out


def latent_block(x, p, model, held):
    """The latent expert layer: ``(out, ids, (margin, held_margin))``."""
    ids, w, *margin = route(x, p["experts"], model, held)
    if len(held) < model["num_experts"]:
        w = jax.lax.stop_gradient(w)
    with jax.default_matmul_precision(HIGHEST):
        routed = routed_experts(x @ p["fc1"], p["experts"], ids, w, held)
        shared = jnp.square(jax.nn.relu(x @ p["shared_w1"])) @ p["shared_w2"]
        return routed @ p["fc2"] + shared, ids, margin


@functools.partial(jax.jit, static_argnames=("parts", "model", "held"))
def _block(h, p, *, parts, model, held):
    """One block of the program's tree: the operator it holds (if any),
    then the feed-forward it holds (if any), each with its norm and
    residual.  ``parts`` names what the block holds (``ssm`` | ``attn``,
    ``mlp``); ``model`` is the configuration's ``model`` group as a
    sorted tuple of items (both static)."""
    model = dict(model)
    eps, routed = model["norm_eps"], None
    if "ssm" in parts:
        h = h + mamba_mixer(_rms(h, p["ln1"], eps), p["ssm"], model)
    elif "attn" in parts:
        h = h + attention_operator(_rms(h, p["ln1"], eps), p["attn"])
    if "mlp" in parts:
        y, ids, margin = latent_block(_rms(h, p["ln2"], eps), p["mlp"],
                                      model, held)
        h, routed = h + y, (ids, margin)
    return h, routed


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(h, ln_f, unembed, *, eps):
    with jax.default_matmul_precision(HIGHEST):
        return _rms(h, ln_f, eps) @ unembed


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
    (any float dtype; widened to float32 block by block).  ``routing`` is
    ``{"selected": [n_expert_layers, b, s, k] sorted ids, "margin",
    "held_margin": [n_expert_layers, b, s]}`` (``route``; None without an
    expert layer).  ``held``
    overrides the configuration's ``moe_experts_held``.  ``remat`` keeps
    only each block's input for a gradient (the same arithmetic, computed
    twice): at 8k positions the float32 intermediates of every block do
    not fit one chip together."""
    held, static = _held(model, held), _static(model)
    h = params["embed"].astype(jnp.float32)[tokens]
    routing = []
    run = 0
    while f"layers_{run}" in params:
        stack = params[f"layers_{run}"]
        for i in range(jax.tree.leaves(stack)[0].shape[0]):
            block = jax.tree.map(lambda a: a[i].astype(jnp.float32), stack)
            apply = functools.partial(
                _block, parts=tuple(k for k in stack if not k.startswith(
                    "ln")), model=static, held=held)
            h, routed = (jax.checkpoint(apply) if remat else apply)(h, block)
            if routed is not None:
                routing.append(routed)
        run += 1
    out = _head(h, params["ln_f"].astype(jnp.float32),
                params["unembed"].astype(jnp.float32),
                eps=model["norm_eps"])
    if not routing:
        return out, None
    return out, {"selected": jnp.stack([r[0] for r in routing]),
                 "margin": jnp.stack([r[1][0] for r in routing]),
                 "held_margin": jnp.stack([r[1][1] for r in routing])}


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


GROUPS = ("router", "experts", "latent", "shared", "ssm", "attention",
          "embedding", "head")


def grad_group_norms(grads) -> dict:
    """L2 norm of a gradient tree by group: ``router``, ``experts`` (the
    routed experts' two products), ``latent`` (the projections into and
    out of the latent), ``shared`` (the shared expert), ``ssm``,
    ``attention``, ``embedding``, ``head`` (final norm and untied head).
    A norm scale rides with its layer's part: ln1 with the mixer, ln2
    with ``latent``; the selection bias has no gradient."""
    sq = dict.fromkeys(GROUPS, 0.0)

    def add(group, tree):
        sq[group] = sq[group] + sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree.leaves(tree))

    add("embedding", grads["embed"])
    add("head", [grads["ln_f"], grads["unembed"]])
    run = 0
    while f"layers_{run}" in grads:
        g = grads[f"layers_{run}"]
        if "ssm" in g:
            add("ssm", [g["ssm"], g["ln1"]])
        elif "attn" in g:
            add("attention", [g["attn"], g["ln1"]])
        if "mlp" in g:
            m = g["mlp"]
            add("router", m["experts"]["router"])
            add("experts", [m["experts"]["w1"], m["experts"]["w2"]])
            add("latent", [m["fc1"], m["fc2"], g["ln2"]])
            add("shared", [m["shared_w1"], m["shared_w2"]])
        run += 1
    return {k: float(jnp.sqrt(v)) for k, v in sq.items()}
