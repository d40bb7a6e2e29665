"""Fused normalization: Pallas RMSNorm/LayerNorm kernels for TPU.

The reference delegates all compute to the user's torch model; this
framework ships transformer models where norms sit on every residual
branch.  Each norm is a bandwidth-bound row reduction — the win is doing
the reduce + scale in one VMEM pass per row block instead of trusting XLA
to fuse the mean/rsqrt/mul chain across dialect boundaries.

Same structure as ops/attention.py: Pallas kernel on TPU when shapes are
lane-aligned, jnp reference elsewhere (and as the recompute backward via
``jax.custom_vjp``), interpreter-mode entries for CPU correctness tests.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..analysis import knobs


# --------------------------------------------------------------------- #
# References (CPU fallback + backward recompute)                         #
# --------------------------------------------------------------------- #
def rms_norm_reference(x: jax.Array, scale: jax.Array,
                       eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)
            ).astype(x.dtype)


def layer_norm_reference(x: jax.Array, scale: jax.Array, bias: jax.Array,
                         eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean((xf - mu) ** 2, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)
            ).astype(x.dtype)


# --------------------------------------------------------------------- #
# Pallas kernels                                                         #
# --------------------------------------------------------------------- #
def _rms_kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(ms + eps)
                  * s_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _ln_kernel(x_ref, s_ref, b_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * s_ref[...].astype(jnp.float32)
                  + b_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


# a whole-array block must fit VMEM next to its f32 working copy
_MAX_WHOLE_ROWS = 1024
# elements of one block: 512 rows of 2048 (in and out double-buffered
# beside the f32 working copy is what the v5e's 16 MiB of VMEM takes; the
# described compile refuses 512 rows of 4096)
_MAX_BLOCK_ELEMENTS = 512 * 2048


def _row_block(rows: int, d: int = 0) -> Optional[int]:
    """Rows per grid cell, at most ``_MAX_BLOCK_ELEMENTS`` of width
    ``d``.  The TPU lowering takes a block whose row count is a multiple
    of 8 (the sublane tile) or the whole array -- a prompt of 57 tokens
    is one 57-row block, not 57 one-row blocks.  None when neither fits
    (a long array with an odd row count)."""
    for cand in (512, 256, 128, 64, 32, 16, 8):
        if rows % cand == 0 and cand * d <= _MAX_BLOCK_ELEMENTS:
            return cand
    return rows if rows <= _MAX_WHOLE_ROWS else None


def _norm_call(kernel, x2: jax.Array, params, eps: float, interpret: bool,
               name: str):
    rows, d = x2.shape
    br = _row_block(rows, d)
    in_specs = [pl.BlockSpec((br, d), lambda i: (i, 0))]
    # scale/bias are [1, d] rows shared by every block
    in_specs += [pl.BlockSpec((1, d), lambda i: (0, 0)) for _ in params]
    return pl.pallas_call(
        functools.partial(kernel, eps=eps),
        grid=(rows // br,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x2.dtype),
        interpret=interpret,
        name=name,
    )(x2, *[p.reshape(1, d) for p in params])


def _use_pallas(x: jax.Array) -> bool:
    if knobs.get_flag("RLA_TPU_DISABLE_PALLAS"):
        return False
    if jax.default_backend() != "tpu":
        return False
    d = x.shape[-1]
    return d % 128 == 0 and _row_block(x.size // d, d) is not None


# --------------------------------------------------------------------- #
# Public ops                                                             #
# --------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm over the last axis.  x: [..., d], scale: [d]."""
    d = x.shape[-1]
    if not _use_pallas(x):
        return rms_norm_reference(x, scale, eps)
    x2 = x.reshape(-1, d)
    with jax.named_scope("kernel/rms_norm"):
        out = _norm_call(_rms_kernel, x2, (scale,), eps, interpret=False,
                         name="rms_norm")
    return out.reshape(x.shape)


def _rms_fwd(x, scale, eps):
    return rms_norm(x, scale, eps), (x, scale)


def _rms_bwd(eps, res, g):
    x, scale = res
    _, vjp = jax.vjp(lambda x_, s_: rms_norm_reference(x_, s_, eps), x, scale)
    return vjp(g)


rms_norm.defvjp(_rms_fwd, _rms_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-6) -> jax.Array:
    """LayerNorm over the last axis.  x: [..., d], scale/bias: [d]."""
    d = x.shape[-1]
    if not _use_pallas(x):
        return layer_norm_reference(x, scale, bias, eps)
    x2 = x.reshape(-1, d)
    out = _norm_call(_ln_kernel, x2, (scale, bias), eps, interpret=False,
                     name="layer_norm")
    return out.reshape(x.shape)


def _ln_fwd(x, scale, bias, eps):
    return layer_norm(x, scale, bias, eps), (x, scale, bias)


def _ln_bwd(eps, res, g):
    x, scale, bias = res
    _, vjp = jax.vjp(
        lambda x_, s_, b_: layer_norm_reference(x_, s_, b_, eps),
        x, scale, bias)
    return vjp(g)


layer_norm.defvjp(_ln_fwd, _ln_bwd)


# interpreter-mode entries (CPU correctness tests for the kernels)
def rms_norm_interpret(x, scale, eps: float = 1e-6):
    d = x.shape[-1]
    with jax.named_scope("kernel/rms_norm"):
        return _norm_call(_rms_kernel, x.reshape(-1, d), (scale,), eps,
                          interpret=True, name="rms_norm").reshape(x.shape)


def layer_norm_interpret(x, scale, bias, eps: float = 1e-6):
    d = x.shape[-1]
    return _norm_call(_ln_kernel, x.reshape(-1, d), (scale, bias), eps,
                      interpret=True, name="layer_norm").reshape(x.shape)
