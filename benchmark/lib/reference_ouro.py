"""The plain reference of the looped language model (``model_type:
ouro``; "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741): forward, the exit-weighted training loss and (by
``jax.grad``) gradients in straightforward float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  No kernel, no fused loss,
no scan over layers or passes: a Python loop over both.  Attention is
computed a block of queries at a time and each pass's cross-entropy a
block of positions at a time, so that 8k positions against 49,152 ids
fit; the blocks run under ``lax.map``, one after the other (given them
side by side in one program, the chip's compiler kept ten blocks'
float32 logits alive at once and passed its memory).

    layer:     a  = h + N2(Attn(N1(h)))                sandwich norm: four
               h' = a + N4(SwiGLU(N3(a)))              RMSNorms a layer
    Attn:      q, k, v = x Wq, x Wk, x Wv (no bias); rotate-half rotary
               over the whole head; softmax(q k^T / sqrt(head_dim),
               causal) v; then Wo
    SwiGLU:    (silu(x W1) * (x W3)) W2
    model:     h_0 = E[tokens];  h_t = N_f(M(h_{t-1})),  t = 1..T
               M the L layers in order, THE SAME weights every pass; N_f
               the one final RMSNorm, whose output feeds the next pass
               logits_t = h_t W_head;  g_t = sigmoid(h_t . w_g + b_g)
               the model's output is logits_T
    loss:      p_1 = g_1;  p_t = g_t prod_{j<t} (1 - g_j), 1 < t < T
               p_T = prod_{j<T} (1 - g_j)      (g_T is not used)
               l_t = cross-entropy of logits_t
               mean over positions of  sum_t p_t l_t - beta H(p)
               H(p) = - sum_t p_t log p_t

It reads the program's parameter tree (``embed``, ``unembed`` when the
head is untied, ``ln_f``, ``exit_gate`` = ``{w [d, 1], b [1]}`` when T >
1, and ``layers`` = one stack of ``attn``, ``mlp``, ``ln1``,
``ln1_post``, ``ln2``, ``ln2_post``) and the configuration's ``model``
group (``loop_passes``, ``exit_beta``, ``norm_eps``, ``rope_theta``),
and nothing else of the program.

Departures from the published description, each the configuration
file's ``assumed``: the catalog's ``config.json`` carries neither the
sandwich norm, nor where the final norm sits, nor the gate, nor the
loss; they follow the family's paper and modelling code as recalled
(norms after both parts; N_f at the end of EVERY pass; the gate a
``Linear(hidden, 1)`` with bias on the normed state; the stage-I
objective with ``beta`` its second value, no stage-II gate training);
``p`` and ``H`` are made from log-sigmoids, which is the same function
and needs no guard at ``p = 0``.

``benchmark/lib/reference_ouro.py`` is a copy of this file (a test holds
the two identical).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = "highest"
Q_BLOCK = 512       # queries per attention block
ROW_BLOCK = 2048    # positions per block of the cross-entropy


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope_half(x, theta):  # [b, h, s, hd]: dimension i pairs with i + hd/2
    hd = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _blocks(x, axis, size):
    """``x`` cut along ``axis`` into blocks of ``size`` (one block, where
    it is no longer), the blocks leading: what ``lax.map`` walks."""
    n = x.shape[axis]
    if n <= size:
        return x[None]
    if n % size:
        raise ValueError(f"{n} positions are no multiple of the block {size}")
    return jnp.moveaxis(x.reshape(x.shape[:axis] + (n // size, size)
                                  + x.shape[axis + 1:]), axis, 0)


def _attend(q, k, v):
    """Causal softmax(q k^T / sqrt(head_dim)) v, [b, h, s, hd]: a block
    of queries over all keys at a time, checkpointed so that a gradient
    keeps no [s, s] probabilities."""
    @jax.checkpoint
    def block(args):
        q_b, first = args
        with jax.default_matmul_precision(HIGHEST):
            s = jnp.einsum("bhqk,bhtk->bhqt", q_b, k) * q.shape[-1] ** -0.5
            rows = first + jnp.arange(q_b.shape[2])[:, None]
            s = jnp.where(jnp.arange(k.shape[2])[None] <= rows, s, -jnp.inf)
            return jnp.einsum("bhqt,bhtk->bhqk", jax.nn.softmax(s, -1), v)

    q_blocks = _blocks(q, 2, Q_BLOCK)
    out = jax.lax.map(block, (q_blocks, jnp.arange(len(q_blocks))
                              * q_blocks.shape[3]))
    return jnp.moveaxis(out, 0, 2).reshape(q.shape)


def attention_operator(x, p, theta):
    """Multi-head causal attention, rotate-half rotary, no bias."""
    with jax.default_matmul_precision(HIGHEST):
        q = _rope_half(jnp.einsum("bsd,dhk->bhsk", x, p["wq"]), theta)
        k = _rope_half(jnp.einsum("bsd,dhk->bhsk", x, p["wk"]), theta)
        v = jnp.einsum("bsd,dhk->bhsk", x, p["wv"])
        return jnp.einsum("bhsk,hkd->bsd", _attend(q, k, v), p["wo"])


@functools.partial(jax.jit, static_argnames=("eps", "theta"))
def _layer(h, p, *, eps, theta):
    """One sandwich-norm layer."""
    a = h + _rms(attention_operator(_rms(h, p["ln1"], eps), p["attn"], theta),
                 p["ln1_post"], eps)
    with jax.default_matmul_precision(HIGHEST):
        m, x = p["mlp"], _rms(a, p["ln2"], eps)
        y = (jax.nn.silu(x @ m["w1"]) * (x @ m["w3"])) @ m["w2"]
    return a + _rms(y, p["ln2_post"], eps)


@functools.partial(jax.jit, static_argnames=("eps",))
def _final_norm(h, ln_f, *, eps):
    return _rms(h, ln_f, eps)


@jax.jit
def _logits(h, head):
    with jax.default_matmul_precision(HIGHEST):
        return h @ head


@jax.jit
def _gate(h, gate):
    """``h . w_g + b_g``, [b, s]: the gate before its sigmoid."""
    with jax.default_matmul_precision(HIGHEST):
        return (h @ gate["w"])[..., 0] + gate["b"][0]


def exit_distribution(gate_logits):
    """``(p, log p)``, each [T, b, s], of the T - 1 gates' logits
    [T - 1, b, s]: a pass exits with its gate's probability times that
    of having stayed so far, the last takes what has stayed."""
    log_p, stayed = [], jnp.zeros_like(gate_logits[0])
    for z in gate_logits:
        log_p.append(jax.nn.log_sigmoid(z) + stayed)
        stayed = stayed + jax.nn.log_sigmoid(-z)
    log_p = jnp.stack(log_p + [stayed])
    return jnp.exp(log_p), log_p


@functools.partial(jax.jit, static_argnames=("remat",))
def _row_loss(h, head, tokens, remat: bool = False):
    """Next-token cross entropy of every position with a target,
    [b, s - 1], from a pass's normed state [b, s, d]: a block of
    positions against the whole head at a time; ``remat`` keeps no
    block's logits for a gradient.  (The last position has no target:
    it is given id 0 and dropped.)"""
    def block(args):
        h_b, targets = args
        lp = jax.nn.log_softmax(_logits(h_b, head), -1)
        return -jnp.take_along_axis(lp, targets[..., None], -1)[..., 0]

    targets = jnp.pad(tokens[:, 1:], ((0, 0), (0, 1)))
    out = jax.lax.map(jax.checkpoint(block) if remat else block,
                      (_blocks(h, 1, ROW_BLOCK),
                       _blocks(targets, 1, ROW_BLOCK)))
    return jnp.moveaxis(out, 0, 1).reshape(tokens.shape)[:, :-1]


def run_passes(h, params, model: dict, remat: bool = False):
    """The T normed states, each [b, s, d] float32, from the embedded
    input ``h``: the layers of ``params["layers"]`` in order, the final
    norm, and again on the same weights."""
    eps, theta = model.get("norm_eps", 1e-6), model["rope_theta"]
    stack = params["layers"]
    layers = [jax.tree.map(lambda a: a[i].astype(jnp.float32), stack)
              for i in range(jax.tree.leaves(stack)[0].shape[0])]
    apply = functools.partial(_layer, eps=eps, theta=theta)
    if remat:
        apply = jax.checkpoint(apply)
    states = []
    for _ in range(model.get("loop_passes", 1)):
        for layer in layers:
            h = apply(h, layer)
        h = _final_norm(h, params["ln_f"].astype(jnp.float32), eps=eps)
        states.append(h)
    return states


def _head_of(params):
    head = params["unembed"] if "unembed" in params else params["embed"].T
    return head.astype(jnp.float32)


def objective(params, tokens, model: dict, remat: bool = False):
    """``(loss, {"pass_loss": [T], "exit_p": [T, b, s - 1], "state":
    h_T})``: the exit-weighted objective over the positions with a
    target, each pass's mean cross-entropy, the exit distribution there
    and the last pass's normed state.  ``remat`` keeps only each
    layer's input and each pass's state for a gradient (the same
    arithmetic, computed twice): at 8k positions the float32
    intermediates of every application do not fit one chip together."""
    states = run_passes(params["embed"].astype(jnp.float32)[tokens], params,
                        model, remat)
    head = _head_of(params)
    row_loss = jnp.stack([_row_loss(h, head, tokens, remat=remat)
                          for h in states])
    if len(states) == 1:
        p = jnp.ones_like(row_loss)
        entropy = jnp.zeros_like(row_loss[0])
    else:
        gate = jax.tree.map(lambda a: a.astype(jnp.float32),
                            params["exit_gate"])
        p, log_p = exit_distribution(jnp.stack(
            [_gate(h[:, :-1], gate) for h in states[:-1]]))
        entropy = -jnp.sum(p * log_p, 0)
    loss = jnp.mean(jnp.sum(p * row_loss, 0)
                    - model.get("exit_beta", 0.0) * entropy)
    return loss, {"pass_loss": jnp.mean(row_loss, (1, 2)), "exit_p": p,
                  "state": states[-1]}


def forward(params, tokens, model: dict, remat: bool = False):
    """``(logits_T [b, s, vocab], {"pass_logits_loss": [T], "exit_p":
    [T, b, s - 1], "loss": the objective})`` of ``tokens`` under
    ``params`` (any float dtype; widened to float32 layer by layer)."""
    loss, out = objective(params, tokens, model, remat)
    return _logits(out["state"], _head_of(params)), {
        "pass_logits_loss": out["pass_loss"], "exit_p": out["exit_p"],
        "loss": loss}


def lm_loss(params, tokens, model: dict):
    """The training objective (``objective``'s first)."""
    return objective(params, tokens, model, remat=True)[0]


def loss_and_grads(params, tokens, model: dict):
    """The objective and its gradient with respect to every parameter;
    a shared weight's is the sum over its T uses, by the chain rule
    through the Python loop."""
    return jax.value_and_grad(lm_loss)(params, tokens, model)


GROUPS = ("embedding", "head", "attention", "mlp", "norms", "gate")


def grad_group_norms(grads) -> dict:
    """L2 norm of a gradient tree by group: ``embedding``, ``head`` (the
    untied head; a tied one rides with the embedding), ``attention``
    (wq, wk, wv, wo), ``mlp`` (w1, w3, w2), ``norms`` (the four scales
    of every layer and the final norm's), ``gate`` (w, b)."""
    sq = dict.fromkeys(GROUPS, 0.0)

    def add(group, tree):
        sq[group] = sq[group] + sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)))
            for g in jax.tree.leaves(tree))

    layers = grads["layers"]
    add("embedding", grads["embed"])
    add("head", grads.get("unembed", ()))
    add("attention", layers["attn"])
    add("mlp", layers["mlp"])
    add("norms", [grads["ln_f"]] + [layers[k] for k in (
        "ln1", "ln1_post", "ln2", "ln2_post")])
    add("gate", grads.get("exit_gate", ()))
    return {k: float(jnp.sqrt(v)) for k, v in sq.items()}
