"""Mamba-2 mixer: the state-space sequence operator of hybrid stacks.

    [z | xBC | dt] = u W_in          W_in: d -> H*P + (H*P + 2*G*N) + H
    xBC = silu(conv(xBC) + b_conv)   causal, depthwise, ``taps`` wide
    [x | B | C] = split(xBC)         x: [H, P]; B, C: [G, N]
    dt = softplus(dt + dt_bias)      per head, float32
    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T      A = -exp(A_log) < 0
    y_t = S_t C_t + D x_t            S in R^{P x N} per head, S_0 = 0
    out = group_rms(y * silu(z); w_norm) W_out      gate first, then norm

``H`` heads of width ``P`` in ``G`` groups, the ``H / G`` heads of one
group sharing its ``B`` and ``C`` and one RMS norm over their ``H * P /
G`` channels: a group is what one rank of a tensor-parallel deployment
holds, so the parameters here are those of the groups HELD (the caller
is told which; every leaf is laid out group by group along its wide
axis, and with all groups held it is the whole mixer).

The recurrence runs in its chunked form (``ssd_scan``, "state-space
duality"): inside a chunk of ``Q`` positions the masked decay matrix
times ``C B^T`` is a ``Q x Q`` attention-like product, across chunks the
state is carried by a short ``lax.scan``.  Four contractions a chunk
(``C B^T``, its masked product with ``x``, the chunk's state ``B^T x``,
``C`` times the carried state), matmul operands in the compute dtype
with float32 accumulation, the decays and the state in float32 always.
The backward is autodiff of the same: with ``jax.checkpoint`` around the
layer its residuals are one layer's, and they hold at 8192 positions.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .conv import causal_depthwise_conv

# the published initialisation of the step size (``time_step_min``,
# ``time_step_max``, ``time_step_floor`` of the family's configs)
DT_MIN, DT_MAX, DT_FLOOR = 0.001, 0.1, 1e-4


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b_in: jax.Array,
             c_in: jax.Array, chunk: int) -> jax.Array:
    """``y_t = C_t . S_t`` with ``S_t = exp(dt_t a) S_{t-1} + dt_t x_t
    B_t^T``, chunked.

    x: [b, s, h, p]; dt: [b, s, h] float32, positive; a: [h] float32,
    negative; b_in, c_in: [b, s, g, n] (``h / g`` heads a group).
    Returns [b, s, h, p] in ``x.dtype``.  Any ``s``: the tail is padded
    with ``dt = 0`` (decay 1, no input), which changes no earlier
    position."""
    bsz, s, h, p = x.shape
    g, n = b_in.shape[2:]
    q = min(chunk, s)
    pad = -s % q
    if pad:
        x, dt, b_in, c_in = (jnp.pad(t, ((0, 0), (0, pad))
                                     + ((0, 0),) * (t.ndim - 2))
                             for t in (x, dt, b_in, c_in))
    nc = (s + pad) // q
    r = h // g                              # heads a group
    dtype, f32 = x.dtype, jnp.float32
    # the heads of one group side by side; the chunk's positions are the
    # minor axis of everything that is not a matmul operand
    x = x.reshape(bsz, nc, q, g, r, p)
    b_in = b_in.reshape(bsz, nc, q, g, n)
    c_in = c_in.reshape(bsz, nc, q, g, n)
    dt = dt.astype(f32).reshape(bsz, nc, q, g, r).transpose(0, 1, 3, 4, 2)
    # log decay from the chunk's start up to and including position i
    cum = jnp.cumsum(dt * a.astype(f32).reshape(g, r, 1), axis=-1)
    total = cum[..., -1]                                    # [b, c, g, r]
    # within the chunk: (L o C B^T) (dt x), L_ij = exp(cum_i - cum_j)
    # for j <= i; the mask sits inside the exponent (a masked entry's
    # difference is positive and may overflow)
    seg = cum[..., :, None] - cum[..., None, :]         # [b, c, g, r, i, j]
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((q, q), bool)), seg,
                              -jnp.inf))
    scores = jnp.einsum("bcign,bcjgn->bcgij", c_in, b_in,
                        preferred_element_type=f32)
    mix = (decay * scores[:, :, :, None] * dt[..., None, :]).astype(dtype)
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp", mix, x,
                   preferred_element_type=f32)
    # the chunk's own state at its end, from zero
    to_end = (jnp.exp(total[..., None] - cum) * dt).astype(dtype)
    states = jnp.einsum(
        "bcjgn,bcjgrp->bcgrpn", b_in,
        x * to_end.transpose(0, 1, 4, 2, 3)[..., None],
        preferred_element_type=f32)

    # across chunks: the state that ENTERS each chunk
    def carry_on(state, chunk_in):
        own, decay_all = chunk_in
        return state * decay_all[..., None, None] + own, state

    _, entering = jax.lax.scan(
        carry_on, jnp.zeros_like(states[:, 0]),
        (states.swapaxes(0, 1), jnp.exp(total).swapaxes(0, 1)))
    entering = entering.swapaxes(0, 1).astype(dtype)     # [b, c, g, r, p, n]
    from_start = jnp.exp(cum).transpose(0, 1, 4, 2, 3)   # [b, c, i, g, r]
    y = y + from_start[..., None] * jnp.einsum(
        "bcign,bcgrpn->bcigrp", c_in, entering, preferred_element_type=f32)
    return y.astype(dtype).reshape(bsz, nc * q, h, p)[:, :s]


def mamba2_mixer(u: jax.Array, params: Dict[str, jax.Array], *, heads: int,
                 head_dim: int, groups: int, state: int, chunk: int,
                 eps: float, compute_dtype=jnp.bfloat16) -> jax.Array:
    """The whole operator on normed rows ``u`` [b, s, d]; ``heads`` and
    ``groups`` are the counts HELD (``params`` of ``init_mamba2_params``
    at the same counts).  Scope ``gpt/ssm`` is the caller's; the
    recurrence books itself under ``gpt/ssm_scan``."""
    dt_, f32 = compute_dtype, jnp.float32
    bsz, s, _ = u.shape
    inner, bc = heads * head_dim, groups * state
    proj = jnp.einsum("bsd,de->bse", u, params["w_in"].astype(dt_))
    z, xbc, dt = jnp.split(proj, [inner, 2 * inner + 2 * bc], axis=-1)
    xbc = jax.nn.silu(causal_depthwise_conv(xbc, params["conv_w"])
                      + params["conv_b"].astype(dt_))
    x, b_in, c_in = jnp.split(xbc, [inner, inner + bc], axis=-1)
    x = x.reshape(bsz, s, heads, head_dim)
    dt = jax.nn.softplus(dt.astype(f32) + params["dt_bias"])
    with jax.named_scope("gpt/ssm_scan"):
        y = ssd_scan(x, dt, -jnp.exp(params["a_log"].astype(f32)),
                     b_in.reshape(bsz, s, groups, state),
                     c_in.reshape(bsz, s, groups, state), chunk)
    y = y + params["d_skip"].astype(dt_)[:, None] * x
    # gate, then an RMS norm over each group's channels
    y = (y.reshape(bsz, s, inner) * jax.nn.silu(z)).astype(f32)
    y = y.reshape(bsz, s, groups, inner // groups)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    y = (y.reshape(bsz, s, inner) * params["norm"]).astype(dt_)
    return jnp.einsum("bse,ed->bsd", y, params["w_out"].astype(dt_))


def init_mamba2_params(rng, d_model: int, heads: int, head_dim: int,
                       groups: int, state: int, taps: int
                       ) -> Dict[str, jax.Array]:
    """One layer's mixer at ``heads`` / ``groups`` held.  ``a_log`` = log
    of uniform [1, 16]; ``dt_bias`` the inverse softplus of a log-uniform
    step in [``DT_MIN``, ``DT_MAX``] floored at ``DT_FLOOR``; ``d_skip``
    1; the projections normal at fan_in ** -0.5, the taps likewise, the
    conv bias uniform within taps ** -0.5 (a depthwise ``Conv1d``'s
    default)."""
    k_in, k_conv, k_bias, k_a, k_dt, k_out = jax.random.split(rng, 6)
    inner, width = heads * head_dim, heads * head_dim + 2 * groups * state

    def dense(key, shape, fan_in):
        return jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5

    step = jnp.exp(jax.random.uniform(
        k_dt, (heads,), jnp.float32, math.log(DT_MIN), math.log(DT_MAX)))
    step = jnp.maximum(step, DT_FLOOR)
    return {
        "w_in": dense(k_in, (d_model, inner + width + heads), d_model),
        "conv_w": dense(k_conv, (width, taps), taps),
        "conv_b": jax.random.uniform(k_bias, (width,), jnp.float32,
                                     -taps ** -0.5, taps ** -0.5),
        "a_log": jnp.log(jax.random.uniform(k_a, (heads,), jnp.float32,
                                            1.0, 16.0)),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "d_skip": jnp.ones((heads,), jnp.float32),
        "norm": jnp.ones((inner,), jnp.float32),
        "w_out": dense(k_out, (inner, d_model), inner),
    }


def mamba2_logical_axes() -> Dict[str, Any]:
    """Logical axis names for an ``init_mamba2_params`` tree (one layer)."""
    return {"w_in": ("embed", "mlp"), "conv_w": (None, None),
            "conv_b": (None,), "a_log": (None,), "dt_bias": (None,),
            "d_skip": (None,), "norm": (None,), "w_out": ("mlp", "embed")}
