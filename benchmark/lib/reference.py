"""The plain reference: the repo's GPT block in straightforward float32
``jax.numpy`` under ``jax.default_matmul_precision("highest")``.

Departures from the published GPT-2 block, shared with the system under
test and listed under ``assumed`` in each configuration file: RMSNorm
(eps 1e-6) for LayerNorm, rotary positions (interleaved pairs, theta
10000) for learned ones, no biases, vocabulary padded to a multiple of
128, tanh-approximated GELU (``gelu_new``), tied LM head.

One jitted layer function is called per layer from Python, so compile
time does not grow with depth.  No kernels, no cache, no batching tricks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

EPS = 1e-6
ROPE_THETA = 10000.0


def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * scale


def _rope(x):  # [b, h, s, hd]
    hd = x.shape[-1]
    freqs = ROPE_THETA ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * freqs[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


@jax.jit
def _layer(h, p):
    with jax.default_matmul_precision("highest"):
        x = _rms(h, p["ln1"])
        q = _rope(jnp.einsum("bsd,dhk->bhsk", x, p["attn"]["wq"]))
        k = _rope(jnp.einsum("bsd,dhk->bhsk", x, p["attn"]["wk"]))
        v = jnp.einsum("bsd,dhk->bhsk", x, p["attn"]["wv"])
        s = jnp.einsum("bhqk,bhtk->bhqt", q, k) * q.shape[-1] ** -0.5
        n = s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
        a = jnp.einsum("bhqt,bhtk->bhqk", jax.nn.softmax(s, -1), v)
        h = h + jnp.einsum("bhsk,hkd->bsd", a, p["attn"]["wo"])
        x = _rms(h, p["ln2"])
        up = jax.nn.gelu(x @ p["mlp"]["wi"], approximate=True)
        return h + up @ p["mlp"]["wo"]


@jax.jit
def _head(h, ln_f, embed):
    with jax.default_matmul_precision("highest"):
        return _rms(h, ln_f) @ embed.T


@jax.jit
def _layer_params(layers, i):
    """Layer ``i`` of the stacked tree, widened to float32 (``i`` is a
    traced index: one program for every layer)."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False)
        .astype(jnp.float32), layers)


def logits(params, tokens):
    """``[batch, seq, vocab]`` float32 logits of ``tokens`` under
    ``params`` (any float dtype; widened to float32 layer by layer)."""
    if "unembed" in params:
        raise NotImplementedError("the reference ties the LM head")
    embed = params["embed"].astype(jnp.float32)
    h = embed[tokens]
    n_layers = jax.tree.leaves(params["layers"])[0].shape[0]
    for i in range(n_layers):
        h = _layer(h, _layer_params(params["layers"], i))
    return _head(h, params["ln_f"].astype(jnp.float32), embed)


@jax.jit
def lm_loss(all_logits, tokens):
    """Mean next-token cross entropy, positions 0..S-2 -> targets 1..S-1."""
    lp = jax.nn.log_softmax(all_logits[:, :-1], -1)
    return -jnp.mean(jnp.take_along_axis(lp, tokens[:, 1:, None], -1))


@jax.jit
def tie_margins(all_logits, tokens):
    """For every position t >= 1: how far token t's logit trails the
    maximum of row t-1, in standard deviations of that row (0 = token t
    is the reference's own greedy choice).  ``[batch, seq - 1]``."""
    rows = all_logits[:, :-1]
    chosen = jnp.take_along_axis(rows, tokens[:, 1:, None], -1)[..., 0]
    return (rows.max(-1) - chosen) / rows.std(-1)
