"""int8 weight-only matmul Pallas kernels (decode path).

Autoregressive decode re-reads every weight for every generated token, so
it is weight-HBM-bandwidth-bound; int8 storage halves the bytes per read
vs bf16 -- but only if int8 is what actually crosses HBM.  XLA's
dequantize-then-dot on a scanned weight stack materializes the bf16
dequant in HBM (int8 read + bf16 write + bf16 read > plain bf16 read),
which is why the framework's own round-3 measurement showed the "int8"
path at 1.03x instead of ~2x.  These kernels stream the int8 blocks into
VMEM, widen in-registers, and feed the MXU -- HBM only ever sees int8.

No reference analog (the reference has no inference path at all; predict
there is plain ``model(x)``, reference: ray_lightning/tests/utils.py:
137-152).

Two layouts, matching how per-out-channel scales fall out of
``GPT.quantize_weights`` (models/transformer.py):

- ``int8_matmul(x [M,K], wq [K,N], scale [N]) -> [M,N]``: contraction
  over the leading weight dim, scales on the output channels -- the
  q/k/v/o and MLP projections.
- ``int8_matmul_nt(x [M,K], wq [N,K]) -> [M,N]``: weight stored
  transposed (the tied-embedding unembed ``W[V,d]``), whose scales vary
  along the CONTRACTION dim d -- fold them into ``x`` first
  (``(x*s) @ Wq.T``), so the kernel takes no scale operand.

CPU/tests run the same kernels in interpreter mode; unsupported shapes
fall back to the XLA dequant path at the call site.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import _pick_block
from ..utils.scope import scoped

# the kernels take the whole M dimension per grid cell: the f32
# accumulator scratch [M, bn] + the [M, bk] input block must fit VMEM
# (~16 MB/core) with room for double-buffered weight blocks.  Decode
# rows are tiny (batch, or batch*chunk for speculative scoring); beyond
# this bound the call site falls back to the XLA dequant path instead of
# failing at Mosaic compile time.
_MAX_M = 1024


def _mm_kernel(x_ref, w_ref, s_ref, o_ref, acc_scr):
    """One (j, k) cell: acc[j] += x[:, k-block] @ w[k-block, j-block].

    The int8 block widens to bf16 IN VMEM (the HBM read was int8); the
    accumulate is f32 on the MXU; the final k step applies the per-out-
    channel scales and writes bf16."""
    k = pl.program_id(1)
    last_k = pl.num_programs(1) - 1

    @pl.when(k == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    acc_scr[:] += jax.lax.dot_general(
        x_ref[...], w_ref[...].astype(x_ref.dtype),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == last_k)
    def _finish():
        o_ref[...] = (acc_scr[:] * s_ref[...].astype(jnp.float32)
                      ).astype(o_ref.dtype)


def _mm_nt_kernel(x_ref, w_ref, o_ref, acc_scr):
    """Transposed-weight cell: acc[j] += x[:, k-block] @ w[j-block, k-block]^T
    (scales pre-folded into x by the caller)."""
    k = pl.program_id(1)
    last_k = pl.num_programs(1) - 1

    @pl.when(k == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    acc_scr[:] += jax.lax.dot_general(
        x_ref[...], w_ref[...].astype(x_ref.dtype),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(k == last_k)
    def _finish():
        o_ref[...] = acc_scr[:].astype(o_ref.dtype)


def supported(m: int, k: int, n: int) -> bool:
    """Shapes the kernels tile cleanly (int8 sublane tiles are 32-row,
    lanes 128-wide; see pallas_guide tiling table) within the VMEM
    budget (_MAX_M rows)."""
    return (1 <= m <= _MAX_M and _pick_block(512, k) is not None
            and _pick_block(512, n) is not None and k % 32 == 0)


def _check_supported(fn: str, m: int, k: int, n: int) -> None:
    """Typed rejection of shapes the kernels cannot tile.  Call sites
    that want the silent XLA-dequant fallback pre-check ``supported()``;
    a direct call with a bad shape gets a ValueError naming the
    constraint instead of a Mosaic compile error (or a silent
    None-arithmetic TypeError) deep in pallas_call."""
    if not 1 <= m <= _MAX_M:
        raise ValueError(
            f"{fn}: m={m} outside [1, {_MAX_M}] (whole-M-per-cell kernels "
            f"must fit the [M, block] accumulator in VMEM)")
    if k % 32 != 0 or _pick_block(512, k) is None:
        raise ValueError(
            f"{fn}: contraction dim k={k} is not tileable -- k must be a "
            f"multiple of 32 (int8 sublane tile) and divisible into "
            f"128-lane blocks; check supported(m, k, n) and fall back to "
            f"the XLA dequant path")
    if _pick_block(512, n) is None:
        raise ValueError(
            f"{fn}: output dim n={n} is not divisible into 128-lane "
            f"blocks; check supported(m, k, n) and fall back to the XLA "
            f"dequant path")


@functools.partial(jax.jit, static_argnames=("interpret",))
@scoped("kernel/q8_matmul")
def int8_matmul(x: jax.Array, wq: jax.Array, scale: jax.Array,
                interpret: bool = False) -> jax.Array:
    """x [M,K] (bf16/f32) @ dequant(wq [K,N] int8, scale [N]) -> [M,N].

    ``scale`` is per-out-channel (column j of the result is scaled by
    scale[j]) -- exactly ``x @ (wq.astype(f32) * scale[None, :])``."""
    m, k = x.shape
    k2, n = wq.shape
    if k != k2:
        raise ValueError(
            f"int8_matmul: x contraction dim {k} != wq leading dim {k2} "
            f"(x {x.shape} @ wq {wq.shape})")
    if scale.shape != (n,):
        raise ValueError(
            f"int8_matmul: scale must be per-out-channel with shape "
            f"({n},), got {scale.shape}")
    _check_supported("int8_matmul", m, k, n)
    bk = _pick_block(512, k)
    bn = _pick_block(512, n)
    s2 = scale.reshape(1, n).astype(jnp.float32)
    grid = (n // bn, k // bk)
    return pl.pallas_call(
        _mm_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, bk), lambda j, kk: (0, kk)),
            pl.BlockSpec((bk, bn), lambda j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((m, bn), lambda j, kk: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((m, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="q8_matmul",
    )(x, wq, s2)


@functools.partial(jax.jit, static_argnames=("interpret",))
@scoped("kernel/q8_matmul")
def int8_matmul_nt(x: jax.Array, wq: jax.Array,
                   interpret: bool = False) -> jax.Array:
    """x [M,K] @ wq[N,K]^T -> [M,N], weight int8, no scale (fold
    contraction-dim scales into x first)."""
    m, k = x.shape
    n, k2 = wq.shape
    if k != k2:
        raise ValueError(
            f"int8_matmul_nt: x contraction dim {k} != wq trailing dim "
            f"{k2} (x {x.shape} @ wq {wq.shape}^T)")
    _check_supported("int8_matmul_nt", m, k, n)
    bk = _pick_block(512, k)
    bn = _pick_block(512, n)
    grid = (n // bn, k // bk)
    return pl.pallas_call(
        _mm_nt_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, bk), lambda j, kk: (0, kk)),
            pl.BlockSpec((bn, bk), lambda j, kk: (j, kk)),
        ],
        out_specs=pl.BlockSpec((m, bn), lambda j, kk: (0, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((m, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="q8_matmul_nt",
    )(x, wq)
