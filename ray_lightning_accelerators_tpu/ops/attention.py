"""Fused attention: Pallas flash-attention kernel for TPU + XLA fallback.

The reference has no attention op (it delegates all compute to the user's
torch model); this framework ships transformer models, and attention is the
hot op, so it gets a hand-written TPU kernel:

- flash attention over (block_q, block_k) grid blocks, 512 unless the
  caller or ``RLA_TPU_FLASH_BLOCK_Q/K`` says otherwise: bf16 operands feed
  the MXU directly with f32 accumulation, row reductions fold the lane
  tiles on the VPU before the one cross-lane reduce, and the forward also
  emits per-row log-sum-exp for the backward.  At head width 64 the
  kernels are bound by the MXU (both matmuls half-fill it) and by
  per-block overheads, not by HBM traffic (PERF.md, PR 26);
- a key length of several blocks runs the k-walk (online softmax carried
  in scratch) with whole-block causal skipping: blocks strictly above the
  diagonal do no MXU work;
- a key length of one block runs without a carry, and where that block is
  the whole causal square (block == sequence) forward and backward walk
  only its causal triangle, in strips of sub-tiles (``_diag_tile``,
  ``causal_tiles``);
- hand-written backward kernels (``jax.custom_vjp``) recompute score
  blocks from q/k and the saved lse in TRANSPOSED [block_k, block_q]
  space (per-query rows broadcast along lanes), never materializing
  [S, S] in HBM.  One pass makes dq, dk and dv from each recomputed
  block: over the one key block, or over the k-walk with one head's
  float32 dq held in VMEM (``kwalk_fused``); past that budget a dq pass
  and a dk/dv pass each recompute the blocks.

On non-TPU backends (tests on the virtual CPU mesh), dispatch falls back to
a reference jnp implementation with identical semantics.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..analysis import knobs
from ..utils.scope import scoped

_NEG_INF = -1e30
# checkpoint names of the k-walk's output and log-sum-exp (``_fa_fwd``)
FLASH_RESIDUALS = ("flash_out", "flash_lse")


# --------------------------------------------------------------------- #
# Reference implementation (also the backward path + CPU fallback)      #
# --------------------------------------------------------------------- #
def attention_reference(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = False,
                        scale: Optional[float] = None,
                        window: Optional[int] = None) -> jax.Array:
    """Plain XLA attention.  q,k,v: [batch, heads, seq, head_dim].

    ``window``: sliding-window (Mistral-style) causal attention — query i
    sees keys in [i-window+1, i].  Implies causal masking.
    """
    *_, q_len, head_dim = q.shape
    k_len = k.shape[-2]
    scale = scale if scale is not None else head_dim ** -0.5
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                        preferred_element_type=jnp.float32) * scale
    if causal or window is not None:
        qi = jax.lax.broadcasted_iota(jnp.int32, (q_len, k_len), 0)
        ki = jax.lax.broadcasted_iota(jnp.int32, (q_len, k_len), 1)
        mask = qi >= ki
        if window is not None:
            mask &= (qi - ki) < window
        logits = jnp.where(mask[None, None], logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v)


# --------------------------------------------------------------------- #
# Which scores a block computes                                          #
# --------------------------------------------------------------------- #
# A walk lists, statically, what a kernel computes of one grid block:
#   [(query rows, [(key rows, mask_at), ...]), ...]
# rows are slices into the block; ``mask_at`` is the (first query, first
# key) position the causal / window mask of that part is taken at, None
# where every score of the part is live.  The query rows of one entry see
# all of their parts at once: one softmax, one dq write.
_LANES = 128


def _block_needed(qi, ki, block_q: int, block_k: int, causal: bool,
                  window: Optional[int]):
    """Whether block (qi, ki) holds a live score.  Causal: not strictly
    above the diagonal; a sliding window additionally not entirely left
    of every query's window start.  Takes ints or traced program ids."""
    needed = (not causal) or (qi * block_q + block_q - 1 >= ki * block_k)
    if window is not None:
        needed = needed & (ki * block_k + block_k - 1
                           >= qi * block_q - window + 1)
    return needed


def _diag_tile(q_len: int, k_len: int, block_q: int, block_k: int,
               causal: bool, window: Optional[int]) -> Optional[int]:
    """Side of the square tiles the causal block is walked in, None where
    a block runs as one masked square.

    The walk engages where one block holds the whole causal square
    (block == sequence, what the 1024-block configs run).  On the v5e at
    [64, 1024, 64] bf16 it takes forward + backward from 11.1 to 7.4 us a
    head, while the diagonal blocks of a 2x2 grid of 512-blocks lose
    6-10 % to it: their strips meet in the k-walk's scratch and run one
    after the other (PERF.md, PR 26).  A sliding window keeps the masked
    square: its left edge would want a second mask.  Tile 256 beat 128 by
    1 % and 512 by 19 % at block 1024; 512- and 256-blocks tie between
    their tiles, so smaller blocks take two strips."""
    if (not causal or window is not None
            or not q_len == k_len == block_q == block_k):
        return None
    tiles = [t for t in range(_LANES, min(256, block_q // 2) + 1, _LANES)
             if block_q % t == 0]
    return max(tiles, default=None)


def _square(qi, ki, block_q: int, block_k: int, causal: bool,
            window: Optional[int]) -> tuple:
    """The walk entry of the whole block (qi, ki) as one part, masked at
    its own position (not at all where nothing masks)."""
    mask_at = ((qi * block_q, ki * block_k)
               if causal or window is not None else None)
    return slice(0, block_q), [(slice(0, block_k), mask_at)]


def _triangle(block: int, tile: int) -> list:
    """The walk of the causal block on the diagonal in strips of ``tile``
    query rows: strip i needs keys [0, (i+1) tile), all left of its
    diagonal tile is live by construction, so only that tile is masked,
    and what lies right of it is never computed -- n(n+1)/2 of n^2
    tiles."""
    walk = []
    for lo in range(0, block, tile):
        rows = slice(lo, lo + tile)
        left = [(slice(0, lo), None)] if lo else []
        walk.append((rows, left + [(rows, (0, 0))]))
    return walk


def _one_k_block_walk(qi, block_q: int, block_k: int, causal: bool,
                      window: Optional[int], tile: Optional[int]) -> list:
    """The walk of q block ``qi`` against the one key block: the
    triangle where ``_diag_tile`` gave a tile, else the masked square."""
    if tile is not None:
        return _triangle(block_q, tile)
    return [_square(qi, 0, block_q, block_k, causal, window)]


def causal_tiles(q_len: int, k_len: int, block_q: int, block_k: int,
                 causal: bool, window: Optional[int] = None) -> tuple:
    """(visited, total) score tiles of one head's [q_len, k_len] square
    at these (effective) grid blocks, counted off what the kernels run.
    The unit is the diagonal walk's tile, or the whole block where that
    walk does not engage.  Static: engagement is decided from shapes at
    trace time, so this is the counter of how often it engages."""
    tile = _diag_tile(q_len, k_len, block_q, block_k, causal, window)
    if tile is None:
        blocks = [(qi, ki) for qi in range(q_len // block_q)
                  for ki in range(k_len // block_k)]
        return (sum(bool(_block_needed(qi, ki, block_q, block_k, causal,
                                       window)) for qi, ki in blocks),
                len(blocks))
    visited = sum((rows.stop - rows.start) // tile
                  for _, parts in _triangle(block_q, tile)
                  for rows, _ in parts)
    return visited, (block_q // tile) ** 2


def _masked(s: jax.Array, q0, k0, window: Optional[int],
            q_axis: int) -> jax.Array:
    """``s`` with the scores no query may see at -inf.  Queries run along
    ``q_axis`` from position q0, keys along the other axis from k0."""
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    mask = qpos >= kpos
    if window is not None:
        mask &= (qpos - kpos) < window
    return jnp.where(mask, s, _NEG_INF)


# --------------------------------------------------------------------- #
# Forward kernels                                                        #
# --------------------------------------------------------------------- #
def _lane_fold(x: jax.Array, op) -> jax.Array:
    """[rows, n * 128] -> [rows, 128]: ``op`` across the lane tiles, on
    the VPU.  A row reduction then costs one cross-lane (XLU) reduce per
    eight rows instead of one per vreg of the block; those were a quarter
    of the forward kernel's time (4.99 -> 3.79 us a head, PERF.md PR 26)."""
    out = x[:, :_LANES]
    for lo in range(_LANES, x.shape[1], _LANES):
        out = op(out, x[:, lo:lo + _LANES])
    return out


def _scores(q_ref, k_ref, rows, parts, scale: float,
            window: Optional[int]) -> list:
    """[rows, cols] f32 scores of each part of one walk entry."""
    out = []
    for cols, mask_at in parts:
        # bf16 operands straight into the MXU with an f32 accumulator --
        # casting to f32 first would halve MXU throughput for no accuracy
        # gain (the accumulate is f32 either way)
        s = jax.lax.dot_general(
            q_ref[0, rows], k_ref[0, cols], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if mask_at is not None:
            s = _masked(s, *mask_at, window, q_axis=0)
        out.append(s)
    return out


def _row_max(scores: list) -> jax.Array:
    """[rows, 1] maximum over every part of a walk entry."""
    folded = functools.reduce(
        jnp.maximum, [_lane_fold(s, jnp.maximum) for s in scores])
    return jnp.max(folded, axis=1, keepdims=True)


def _weigh(scores: list, parts: list, m: jax.Array, v_ref) -> tuple:
    """exp(s - m) of every part against its rows of V: ([rows, 128]
    lane-partial row sums, [rows, d] weighted values), both f32."""
    sums = pv = None
    for s, (cols, _) in zip(scores, parts):
        p = jnp.exp(s - m)
        part_sums = _lane_fold(p, jnp.add)
        part_pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, cols], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        sums = part_sums if sums is None else sums + part_sums
        pv = part_pv if pv is None else pv + part_pv
    return sums, pv


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                  acc_scr, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  window: Optional[int]):
    """The k-walk: online softmax over the key blocks of one q block,
    carried in scratch (running max, lane-partial denominator, output
    accumulator)."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    last_k = pl.num_programs(2) - 1

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: blocks strictly above the diagonal contribute nothing;
    # sliding window additionally skips blocks entirely left of every
    # query's window start
    @pl.when(_block_needed(qi, ki, block_q, block_k, causal, window))
    def _compute():
        rows, parts = _square(qi, ki, block_q, block_k, causal, window)
        scores = _scores(q_ref, k_ref, rows, parts, scale, window)
        m_prev = m_scr[:, :1]                        # [block_q, 1]
        m_new = jnp.maximum(m_prev, _row_max(scores))
        alpha = jnp.exp(m_prev - m_new)              # [block_q, 1]
        sums, pv = _weigh(scores, parts, m_new, v_ref)
        l_scr[:] = alpha * l_scr[:] + sums
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)

    @pl.when(ki == last_k)
    def _finish():
        l = jnp.sum(l_scr[:], axis=1, keepdims=True)
        l = jnp.where(l == 0.0, 1.0, l)              # fully-masked rows -> 0
        o_ref[0] = (acc_scr[:] / l).astype(o_ref.dtype)
        # log-sum-exp per query row, for the backward recompute (the
        # transpose moves [block_q, 1] sublanes onto lanes once per block)
        lse = m_scr[:, :1] + jnp.log(l)
        lse_ref[...] = jnp.transpose(lse, (1, 0))[None]


def _flash_one_k_block_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                              scale: float, causal: bool, block_q: int,
                              block_k: int, window: Optional[int],
                              tile: Optional[int]):
    """The whole key length in one block: every query row sees all of
    its keys at once, so there is no online-softmax carry and nothing
    goes through scratch -- each strip of the walk is an independent
    chain of values, which is what lets the causal triangle pay (strips
    that met in the k-walk's scratch ran one after the other)."""
    walk = _one_k_block_walk(pl.program_id(1), block_q, block_k, causal,
                             window, tile)
    # a strip's QK^T is issued before the strip ahead of it goes through
    # softmax and PV: the compiler keeps the strips in program order, and
    # this order gives the MXU work while the VPU and EUP are on the
    # softmax (2.54 against 3.07 us a head strip after strip)
    ahead = _scores(q_ref, k_ref, *walk[0], scale, window)
    for i, (rows, parts) in enumerate(walk):
        scores = ahead
        if i + 1 < len(walk):
            ahead = _scores(q_ref, k_ref, *walk[i + 1], scale, window)
        m = _row_max(scores)
        sums, pv = _weigh(scores, parts, m, v_ref)
        # l >= 1: the row's maximum contributes exp(0)
        l = jnp.sum(sums, axis=1, keepdims=True)
        o_ref[0, rows] = (pv / l).astype(o_ref.dtype)
        lse_ref[0, :, rows] = jnp.transpose(m + jnp.log(l), (1, 0))


@scoped("kernel/flash_fwd")
def _flash_forward(q3: jax.Array, k3: jax.Array, v3: jax.Array, scale: float,
                   causal: bool, block_q: int, block_k: int,
                   interpret: bool, window: Optional[int] = None):
    """q3,k3,v3: [bh, seq, d] (batch*heads folded).
    Returns (out [bh, seq, d], lse [bh, 1, seq] f32)."""
    bh, q_len, d = q3.shape
    k_len = k3.shape[1]
    common = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, window=window)
    if block_k == k_len:
        kernel = functools.partial(
            _flash_one_k_block_kernel, **common,
            tile=_diag_tile(q_len, k_len, block_q, block_k, causal, window))
        scratch = []
    else:
        kernel = functools.partial(_flash_kernel, **common)
        scratch = [
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # lane-partial denom
            pltpu.VMEM((block_q, d), jnp.float32),       # output accumulator
        ]
    return pl.pallas_call(
        kernel,
        grid=(bh, q_len // block_q, k_len // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            # [bh, 1, q_len]: the middle singleton keeps the block's
            # second-to-last dim equal to the array's (TPU lowering
            # constraint on 2D row vectors)
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, q_len, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, 1, q_len), jnp.float32),
        ],
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            # bh and q blocks are independent; only the kv walk carries
            # the online-softmax state
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_fwd",
    )(q3, k3, v3)


# --------------------------------------------------------------------- #
# Backward kernels                                                       #
# --------------------------------------------------------------------- #
# Flash-style backward: recompute the score block from q/k and the saved
# per-row log-sum-exp, never materializing [S, S] in HBM.  The kernels
# work in the TRANSPOSED score space [block_k, block_q] so the per-QUERY
# lse/delta rows broadcast along lanes ([1, block_q]) -- no sublane
# broadcasts or in-kernel transposes in the hot loop.
#
#   dP  = dO @ V^T          dS = P * (dP - delta) * scale
#   dQ  = dS @ K            dK = dS^T @ Q           dV = P^T @ dO
#   delta_i = sum_d dO_id * O_id     P = exp(S - lse)

def _bwd_strip(q_ref, k_ref, v_ref, do_ref, lse_ref, dta_ref, q_rows, parts,
               *, scale, window):
    """Shared recompute of one walk entry, all of its parts in one set of
    matmuls (the parts are adjacent key rows; only the mask tells them
    apart, and its pieces are whole sublane tiles): returns (key rows,
    pT [k, q] f32, dsT [k, q] f32)."""
    k_rows = slice(parts[0][0].start, parts[-1][0].stop)
    sT = jax.lax.dot_general(
        k_ref[0, k_rows], q_ref[0, q_rows], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale       # [k, q]
    pieces = []
    for rows, mask_at in parts:
        piece = sT[rows.start - k_rows.start:rows.stop - k_rows.start]
        if mask_at is not None:
            piece = _masked(piece, *mask_at, window, q_axis=1)
        pieces.append(piece)
    sT = pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces, axis=0)
    pT = jnp.exp(sT - lse_ref[0, :, q_rows])              # [k, q]
    dpT = jax.lax.dot_general(
        v_ref[0, k_rows], do_ref[0, q_rows], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)               # [k, q]
    dsT = pT * (dpT - dta_ref[0, :, q_rows]) * scale
    return k_rows, pT, dsT


def _bwd_block(q_ref, k_ref, v_ref, do_ref, lse_ref, dta_ref, qi, ki, *,
               scale, causal, block_q, block_k, window):
    """The whole block (qi, ki) as one masked square: (pT, dsT)."""
    q_rows, parts = _square(qi, ki, block_q, block_k, causal, window)
    _, pT, dsT = _bwd_strip(q_ref, k_ref, v_ref, do_ref, lse_ref, dta_ref,
                            q_rows, parts, scale=scale, window=window)
    return pT, dsT


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dta_ref,
                         dq_ref, dq_scr, *, scale, causal, block_q,
                         block_k, window):
    qi, ki = pl.program_id(1), pl.program_id(2)
    last_k = pl.num_programs(2) - 1

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    @pl.when(_block_needed(qi, ki, block_q, block_k, causal, window))
    def _compute():
        _, dsT = _bwd_block(q_ref, k_ref, v_ref, do_ref, lse_ref, dta_ref,
                            qi, ki, scale=scale, causal=causal,
                            block_q=block_q, block_k=block_k, window=window)
        # dQ[bq, d] += dsT^T @ K == contract dsT dim0 with K dim0
        dq_scr[:] += jax.lax.dot_general(
            dsT.astype(k_ref.dtype), k_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == last_k)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dta_ref,
                          dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                          block_q, block_k, window):
    ki, qi = pl.program_id(1), pl.program_id(2)
    last_q = pl.num_programs(2) - 1

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(_block_needed(qi, ki, block_q, block_k, causal, window))
    def _compute():
        pT, dsT = _bwd_block(q_ref, k_ref, v_ref, do_ref, lse_ref, dta_ref,
                             qi, ki, scale=scale, causal=causal,
                             block_q=block_q, block_k=block_k, window=window)
        dv_scr[:] += jax.lax.dot_general(
            pT.astype(do_ref.dtype), do_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[:] += jax.lax.dot_general(
            dsT.astype(q_ref.dtype), q_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == last_q)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dta_ref,
                            dq_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                            scale, causal, block_q, block_k, window, tile):
    """Single-k-block fused backward: one pass computes dq for this q
    block AND accumulates dk/dv across q blocks, sharing the sT/dpT
    recompute the split kernels each redo (5 MXU matmuls per strip vs
    3+4).  Engaged when the whole key length fits one block
    (block_k == k_len), which the large-block configs hit; where that
    block is the whole causal square it is walked as a triangle."""
    qi = pl.program_id(1)
    last_q = pl.num_programs(1) - 1

    @pl.when(qi == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    # with the full K extent in-block every causal/window q block has
    # live keys, so there is no whole-block skip
    for q_rows, parts in _one_k_block_walk(qi, block_q, block_k, causal,
                                           window, tile):
        k_rows, pT, dsT = _bwd_strip(
            q_ref, k_ref, v_ref, do_ref, lse_ref, dta_ref, q_rows, parts,
            scale=scale, window=window)
        dv_scr[k_rows] += jax.lax.dot_general(
            pT.astype(do_ref.dtype), do_ref[0, q_rows],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        dk_scr[k_rows] += jax.lax.dot_general(
            dsT.astype(q_ref.dtype), q_ref[0, q_rows],
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        # dQ[q, d] = dsT^T @ K == contract dsT dim0 with K dim0
        dq_ref[0, q_rows] = jax.lax.dot_general(
            dsT.astype(k_ref.dtype), k_ref[0, k_rows],
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(dq_ref.dtype)

    @pl.when(qi == last_q)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_backward_fused(q3, k3, v3, g3, lse, delta, scale, causal,
                          block_q, block_k, interpret, window):
    """One-kernel backward for k_len == block_k."""
    bh, q_len, d = q3.shape
    k_len = k3.shape[1]
    qspec = pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0))
    kspec = pl.BlockSpec((1, block_k, d), lambda b, i: (b, 0, 0))
    rowspec = pl.BlockSpec((1, 1, block_q), lambda b, i: (b, 0, i))
    return pl.pallas_call(
        functools.partial(
            _flash_bwd_fused_kernel, scale=scale, causal=causal,
            block_q=block_q, block_k=block_k, window=window,
            tile=_diag_tile(q_len, k_len, block_q, block_k, causal, window)),
        grid=(bh, q_len // block_q),
        in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec],
        out_specs=[qspec, kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct((bh, q_len, d), q3.dtype),
                   jax.ShapeDtypeStruct((bh, k_len, d), k3.dtype),
                   jax.ShapeDtypeStruct((bh, k_len, d), v3.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            # the q walk carries the dk/dv accumulators
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_fused",
    )(q3, k3, v3, g3, lse, delta)


def _flash_bwd_kwalk_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dta_ref,
                            dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, *,
                            scale, causal, block_q, block_k, window):
    """The k-walk's backward in one pass: key blocks outer, q blocks
    inner, each visited block's scores recomputed ONCE for all three
    gradients (5 MXU matmuls a block where the split pair runs 3+4).
    dk/dv accumulate over the q walk as in the dkv pass; dq accumulates
    in ``dq_scr``, the whole query length of this head, and each q block
    receives its key blocks in increasing order, as in the dq pass."""
    ki, qi = pl.program_id(1), pl.program_id(2)
    q_rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)

    @pl.when(qi == 0)
    def _init_kv():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(ki == 0)
    def _init_q():
        dq_scr[q_rows] = jnp.zeros((block_q, dq_scr.shape[1]), jnp.float32)

    @pl.when(_block_needed(qi, ki, block_q, block_k, causal, window))
    def _compute():
        pT, dsT = _bwd_block(q_ref, k_ref, v_ref, do_ref, lse_ref, dta_ref,
                             qi, ki, scale=scale, causal=causal,
                             block_q=block_q, block_k=block_k, window=window)
        dv_scr[:] += jax.lax.dot_general(
            pT.astype(do_ref.dtype), do_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[:] += jax.lax.dot_general(
            dsT.astype(q_ref.dtype), q_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dq_scr[q_rows] += jax.lax.dot_general(
            dsT.astype(k_ref.dtype), k_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == pl.num_programs(2) - 1)
    def _finish_kv():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)

    @pl.when(ki == pl.num_programs(1) - 1)
    def _finish_q():
        dq_ref[0] = dq_scr[q_rows].astype(dq_ref.dtype)


# One head's float32 dq accumulator stays in VMEM for the whole of its
# k-walk: q_len x round_up(d, 128) x 4 B, 4 MiB at 8,192 positions (a
# 64-wide head pads to the 128 lanes).  Beside it the kernel's blocks,
# dk/dv accumulators and [block_k, block_q] float32 temporaries take
# 11.5 MiB at 1024-blocks: 15.5 of the default 16 MiB scoped VMEM (the
# v5e compiler refuses 10,240 positions at 16.49).  Past the budget the
# split pair runs, whose working set does not grow with the sequence.
_KWALK_DQ_BYTES = 4 * 2 ** 20


def kwalk_fused(q_len: int, d: int) -> bool:
    """Whether the k-walk's backward runs as one pass: one head's float32
    dq accumulator fits ``_KWALK_DQ_BYTES``."""
    return q_len * -(-d // _LANES) * _LANES * 4 <= _KWALK_DQ_BYTES


def _flash_backward_kwalk(q3, k3, v3, g3, lse, delta, scale, causal,
                          block_q, block_k, interpret, window):
    """One-kernel backward for k_len > block_k."""
    bh, q_len, d = q3.shape
    k_len = k3.shape[1]
    n_k = k_len // block_k
    qspec = pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0))
    kspec = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0))
    rowspec = pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i))
    # dq's block stays at the head's first q block until the last key
    # block, so the pipeline writes nothing back before a q block's sum
    # is whole, and each block once
    dqspec = pl.BlockSpec(
        (1, block_q, d),
        lambda b, j, i: (b, jnp.where(j == n_k - 1, i, 0), 0))
    return pl.pallas_call(
        functools.partial(_flash_bwd_kwalk_kernel, scale=scale,
                          causal=causal, block_q=block_q, block_k=block_k,
                          window=window),
        grid=(bh, n_k, q_len // block_q),
        in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec],
        out_specs=[dqspec, kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct((bh, q_len, d), q3.dtype),
                   jax.ShapeDtypeStruct((bh, k_len, d), k3.dtype),
                   jax.ShapeDtypeStruct((bh, k_len, d), v3.dtype)],
        scratch_shapes=[pltpu.VMEM((q_len, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            # both walks carry accumulators: dk/dv over q, dq over k
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_kwalk",
    )(q3, k3, v3, g3, lse, delta)


@scoped("kernel/flash_bwd")
def _flash_backward(q3, k3, v3, o3, lse, g3, scale, causal, block_q,
                    block_k, interpret, window=None):
    """dq, dk, dv for folded [bh, seq, d] operands (the scope holds the
    delta row-sum pre-pass with the kernels it feeds)."""
    bh, q_len, d = q3.shape
    k_len = k3.shape[1]
    # delta_i = rowsum(dO * O): tiny elementwise pass in XLA
    delta = jnp.sum(g3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1)[:, None, :]                   # [bh, 1, q_len]
    if block_k == k_len:
        return _flash_backward_fused(q3, k3, v3, g3, lse, delta, scale,
                                     causal, block_q, block_k, interpret,
                                     window)
    if kwalk_fused(q_len, d):
        return _flash_backward_kwalk(q3, k3, v3, g3, lse, delta, scale,
                                     causal, block_q, block_k, interpret,
                                     window)
    qspec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    kspec = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0))
    rowspec = pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i))
    common = dict(scale=scale, causal=causal, block_q=block_q,
                  block_k=block_k, window=window)
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **common),
        grid=(bh, q_len // block_q, k_len // block_k),
        in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((bh, q_len, d), q3.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q3, k3, v3, g3, lse, delta)
    # dkv walks q inside k: swap the roles of the two inner grid dims
    qspec_t = pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0))
    kspec_t = pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0))
    rowspec_t = pl.BlockSpec((1, 1, block_q), lambda b, j, i: (b, 0, i))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **common),
        grid=(bh, k_len // block_k, q_len // block_q),
        in_specs=[qspec_t, kspec_t, kspec_t, qspec_t, rowspec_t, rowspec_t],
        out_specs=[kspec_t, kspec_t],
        out_shape=[jax.ShapeDtypeStruct((bh, k_len, d), k3.dtype),
                   jax.ShapeDtypeStruct((bh, k_len, d), v3.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q3, k3, v3, g3, lse, delta)
    return dq, dk, dv


def _pick_block(requested: int, length: int) -> Optional[int]:
    """Largest 128-multiple block <= requested that divides ``length``
    (TPU tiles need 128-aligned blocks; unaligned lengths fall back).
    None when no such block exists."""
    best = None
    for cand in range(128, min(requested, length) + 1, 128):
        if length % cand == 0:
            best = cand
    return best


def _use_pallas(q: jax.Array, block_q: Optional[int],
                block_k: Optional[int]) -> bool:
    if knobs.get_flag("RLA_TPU_DISABLE_PALLAS"):
        return False
    if jax.default_backend() != "tpu":
        return False
    d = q.shape[-1]
    # below one MXU-sized q block the launch overhead beats any tiling win;
    # XLA handles short sequences fine
    return block_q is not None and block_k is not None and d >= 64


def _default_blocks() -> tuple:
    """Kernel block sizes: (block_q, block_k), overridable via
    RLA_TPU_FLASH_BLOCK_Q/K for shape-specific tuning (read at trace
    time, so set before the first jit of a given shape).  A malformed
    value warns (naming the variable) and keeps the default — the knobs
    contract: a typo'd tuning knob must not kill a training run."""
    return (knobs.get_int("RLA_TPU_FLASH_BLOCK_Q", 512),
            knobs.get_int("RLA_TPU_FLASH_BLOCK_K", 512))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = False, scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    window: Optional[int] = None) -> jax.Array:
    """Fused attention.  q,k,v: [batch, heads, seq, head_dim].

    Uses the Pallas TPU kernel when shapes allow, XLA reference otherwise.
    ``window`` enables sliding-window causal attention (see
    attention_reference).

    ``block_q`` / ``block_k`` are the grid blocks (default 512, clipped to
    the largest 128-multiple that divides the sequence).  Fewer, larger
    blocks win on the v5e: a block that holds the whole sequence runs
    without the k-walk's carry and, when causal, over the causal
    triangle only (at [64, 1024, 64] bf16, us a head forward / backward:
    2.5 / 4.8 with block 1024, 5.4 / 5.0 with 512; PERF.md, PR 26).
    """
    b, h, q_len, d = q.shape
    scale_v = scale if scale is not None else d ** -0.5
    dq, dk_ = _default_blocks()
    block_q = dq if block_q is None else block_q
    block_k = dk_ if block_k is None else block_k
    # effective blocks: the largest 128-aligned divisors of the extents,
    # so e.g. seq 640 tiles as 128-blocks instead of losing the kernel
    block_q = _pick_block(block_q, q_len)
    block_k = _pick_block(block_k, k.shape[2])
    if not _use_pallas(q, block_q, block_k):
        return attention_reference(q, k, v, causal=causal, scale=scale_v,
                                   window=window)
    q3 = q.reshape(b * h, q_len, d)
    k3 = k.reshape(b * h, k.shape[2], d)
    v3 = v.reshape(b * h, v.shape[2], d)
    out, _ = _flash_forward(q3, k3, v3, scale_v, causal, block_q, block_k,
                            interpret=False, window=window)
    return out.reshape(b, h, q_len, d)


def _fa_fwd(q, k, v, causal, scale, block_q, block_k, window):
    b, h, q_len, d = q.shape
    scale_v = scale if scale is not None else d ** -0.5
    dq_, dk_ = _default_blocks()
    block_q = dq_ if block_q is None else block_q
    block_k = dk_ if block_k is None else block_k
    eff_q = _pick_block(block_q, q_len)
    eff_k = _pick_block(block_k, k.shape[2])
    if not _use_pallas(q, eff_q, eff_k):
        out = attention_reference(q, k, v, causal=causal, scale=scale_v,
                                  window=window)
        return out, (q, k, v, None, None)
    q3 = q.reshape(b * h, q_len, d)
    k3 = k.reshape(b * h, k.shape[2], d)
    v3 = v.reshape(b * h, v.shape[2], d)
    out3, lse = _flash_forward(q3, k3, v3, scale_v, causal, eff_q, eff_k,
                               interpret=False, window=window)
    if k.shape[2] > eff_k:
        # the k-walk: what a layer's remat would pay to run it again
        # grows with the sequence (2 s d FLOPs a row of output), what it
        # pays to hold the output does not: named, so a remat policy
        # keeps both (models/transformer.py ``_KEPT_UNDER_REMAT``).  One
        # k block holds the sequences where running again is the cheaper
        # side (PERF.md, PR 32); outside jax.checkpoint a name is the
        # identity
        out3, lse = map(checkpoint_name, (out3, lse), FLASH_RESIDUALS)
    return out3.reshape(b, h, q_len, d), (q, k, v, out3, lse)


def _fa_bwd(causal, scale, block_q, block_k, window, residuals, g):
    q, k, v, o3, lse = residuals
    b, h, q_len, d = q.shape
    scale_v = scale if scale is not None else d ** -0.5
    dq_, dk_ = _default_blocks()
    block_q = dq_ if block_q is None else block_q
    block_k = dk_ if block_k is None else block_k
    if o3 is None:
        # reference forward path: grads of the reference formulation
        _, vjp = jax.vjp(
            lambda q_, k_, v_: attention_reference(q_, k_, v_, causal=causal,
                                                   scale=scale_v,
                                                   window=window),
            q, k, v)
        return vjp(g)
    q3 = q.reshape(b * h, q_len, d)
    k3 = k.reshape(b * h, k.shape[2], d)
    v3 = v.reshape(b * h, v.shape[2], d)
    g3 = g.reshape(b * h, q_len, d)
    dq, dk, dv = _flash_backward(q3, k3, v3, o3, lse, g3, scale_v, causal,
                                 _pick_block(block_q, q_len),
                                 _pick_block(block_k, k.shape[2]),
                                 interpret=False, window=window)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape))


flash_attention.defvjp(_fa_fwd, _fa_bwd)


def flash_attention_interpret(q, k, v, causal=False, scale=None,
                              block_q=128, block_k=128, window=None):
    """Interpreter-mode kernel entry (CPU correctness tests)."""
    b, h, q_len, d = q.shape
    scale_v = scale if scale is not None else d ** -0.5
    q3 = q.reshape(b * h, q_len, d)
    k3 = k.reshape(b * h, k.shape[2], d)
    v3 = v.reshape(b * h, v.shape[2], d)
    out, _ = _flash_forward(q3, k3, v3, scale_v, causal, block_q, block_k,
                            interpret=True, window=window)
    return out.reshape(b, h, q_len, d)


def flash_attention_grads_interpret(q, k, v, g, causal=False, scale=None,
                                    block_q=128, block_k=128, window=None):
    """Interpreter-mode backward-kernel entry (CPU correctness tests):
    returns (dq, dk, dv) for cotangent ``g``."""
    b, h, q_len, d = q.shape
    scale_v = scale if scale is not None else d ** -0.5
    q3 = q.reshape(b * h, q_len, d)
    k3 = k.reshape(b * h, k.shape[2], d)
    v3 = v.reshape(b * h, v.shape[2], d)
    g3 = g.reshape(b * h, q_len, d)
    out3, lse = _flash_forward(q3, k3, v3, scale_v, causal, block_q,
                               block_k, interpret=True, window=window)
    dq, dk, dv = _flash_backward(q3, k3, v3, out3, lse, g3, scale_v,
                                 causal, block_q, block_k, interpret=True,
                                 window=window)
    return (dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape))
