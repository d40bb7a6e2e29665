"""Gated short convolution: the sequence operator of conv-hybrid stacks.

    [B, C, u] = split3(x @ W_in)          W_in: d -> 3d, no bias
    v   = B * u
    c_t = sum_j w[:, j] * v_{t-(L-1)+j}   depthwise, causal, zeros before t=0
    out = (C * c) @ W_out                 W_out: d -> d

The convolution is ``L`` shifted multiply-adds (``L`` is 3 in the
published models): a shift is a pad and a slice, whose transposes are a
slice and a pad, so the backward is the same three passes mirrored and
holds at any sequence length.  No ``conv_general_dilated`` detour: at
``L = 3`` a depthwise convolution is memory-bound elementwise work, and
XLA fuses the taps with both gates into one pass over ``[b, s, d]``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def causal_depthwise_conv(v: jax.Array, w: jax.Array) -> jax.Array:
    """``v``: [b, s, d]; ``w``: [d, L] taps, ``w[:, L-1]`` on the current
    position.  Position t sees t-(L-1)..t and zeros before the start."""
    s, taps = v.shape[1], w.shape[1]
    w = w.astype(v.dtype)
    out = v * w[:, taps - 1]
    for back in range(1, taps):
        shifted = jnp.pad(v, ((0, 0), (back, 0), (0, 0)))[:, :s]
        out = out + shifted * w[:, taps - 1 - back]
    return out


def gated_short_conv(x: jax.Array, w_in: jax.Array, conv_w: jax.Array,
                     w_out: jax.Array) -> jax.Array:
    """The whole operator on ``x`` [b, s, d] (weights already in the
    compute dtype)."""
    gate_b, gate_c, u = jnp.split(
        jnp.einsum("bsd,de->bse", x, w_in), 3, axis=-1)
    c = causal_depthwise_conv(gate_b * u, conv_w)
    return jnp.einsum("bsd,de->bse", gate_c * c, w_out)
