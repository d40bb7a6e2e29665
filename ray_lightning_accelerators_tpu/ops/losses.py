"""Fused linear + softmax-cross-entropy for language-model heads.

The reference delegates loss computation to the user's torch module
(reference: ray_lightning/tests/utils.py:33-37 — plain eager losses); this
framework ships its own LM head op because on TPU the naive path

    logits = h @ W            # [rows, V] materialized in HBM
    loss   = xent(logits, y)  # AD saves softmax residuals, another [rows, V]

is the peak-memory hog of the whole training step once V is tens of
thousands: for a 4k-token batch and 50k vocab, logits + saved softmax
residuals are ~1.6 GB of HBM that exists only to be reduced to one scalar.

``fused_linear_cross_entropy`` streams row chunks through the unembedding
matmul: each chunk computes its logits [chunk, V], reduces them to per-row
loss/correctness, and discards them, so the full logits tensor never
exists.  Peak extra memory drops from O(rows*V) to O(chunk*V).

**Under differentiation the forward makes the gradient** (the forward
rule of the ``jax.custom_vjp``): the cotangent that reaches the loss is one
scalar, so while a chunk's logits are held its ``softmax - onehot`` is
contracted at once into ``dh`` and ``dw`` — THREE ``rows x d x V``
products a step (logits, dh, dw), not a fourth to make the logits again
in the backward: on the chip the products are 85 % of this op's time, the
MXU is its bottleneck.  Between forward and backward it holds ``dh``
(float32 ``[rows, d]``) and ``dw`` (float32 ``[d, V]``), not ``h``, ``w``
or the targets; the backward multiplies both by the cotangent and rounds
each to its operand's dtype, once.  A call nobody differentiates
(validation, serving) runs the one product and no gradient work.

**Sharded batches:** chunking the globally-flattened row dim under GSPMD
would force an all-gather of the hidden states and replicate the whole head
on every device (each device would stream ALL rows).  So when the batch is
sharded over data/fsdp axes, pass ``mesh=``: the op drops into
``jax.shard_map`` over those axes — each device streams only its local rows
and the scalar sums are ``psum``'d, which is exactly the gradient
all-reduce data parallelism needs anyway.

**Row weights:** ``row_weights=`` turns the mean into ``sum_r w_r *
loss_r`` with a gradient for ``w`` too (``d / d w_r`` is the row's own
loss, kept as a ``[rows]`` float32 residual): a looped model's passes go
through ONE streamed call against the head, each row weighted by its
pass's exit probability (``models/transformer.GPT._loop_loss``).
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

DEFAULT_CHUNK_ROWS = 1024


def linear_cross_entropy_reference(h: jax.Array, w: jax.Array,
                                   targets: jax.Array
                                   ) -> Tuple[jax.Array, jax.Array]:
    """Naive path: materializes logits.  h: [rows, d], w: [d, V],
    targets: [rows] int (negative = masked out).  Returns (mean loss over
    valid rows, accuracy over valid rows)."""
    valid = targets >= 0
    tgt = jnp.where(valid, targets, 0)
    logits = (h.astype(jnp.float32) @ w.astype(jnp.float32))
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt_logit = jnp.take_along_axis(logits, tgt[:, None], axis=-1)[:, 0]
    losses = jnp.where(valid, lse - tgt_logit, 0.0)
    correct = jnp.where(valid, jnp.argmax(logits, -1) == tgt, False)
    n = jnp.maximum(jnp.sum(valid), 1)
    return jnp.sum(losses) / n, jnp.sum(correct) / n


def _pad_rows(h: jax.Array, targets: jax.Array, chunk: int):
    rows = h.shape[0]
    nc = -(-rows // chunk)
    pad = nc * chunk - rows
    if pad:
        h = jnp.pad(h, ((0, pad), (0, 0)))
        targets = jnp.pad(targets, (0, pad), constant_values=-1)
    return h, targets, nc


def _row_losses(h_c: jax.Array, w: jax.Array, tgt_c: jax.Array,
                label_smoothing: float, z_loss: float):
    """Per-chunk forward, row by row: returns (loss [chunk], logits
    [chunk, V], valid [chunk], targets with masked entries at 0, lse
    [chunk]).

    The matmul runs in the inputs' dtype (bf16 from the model) with f32
    accumulation — MXU-native — instead of upcasting the operands.

    Per row: ``lse - (1-eps)*tgt_logit - (eps/V)*sum(logits)`` (cross
    entropy against the eps-smoothed target distribution) plus the PaLM
    stability term ``z_loss * lse**2`` that keeps the softmax normalizer
    near 1."""
    valid = tgt_c >= 0
    tgt = jnp.where(valid, tgt_c, 0)
    logits = jnp.dot(h_c, w, preferred_element_type=jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    # target logit from gathered weight COLUMNS: a [d, chunk] gather plus
    # a row-wise dot.  take_along_axis over the [chunk, V] logits lowers
    # to an iota-compare-reduce that re-reads the whole logits block from
    # HBM (XPlane-traced at ~0.55 ms/chunk on the GPT bench) just to pick
    # one element per row.
    w_tgt = jnp.take(w, tgt, axis=1)                    # [d, chunk]
    tgt_logit = jnp.einsum("cd,dc->c", h_c, w_tgt,
                           preferred_element_type=jnp.float32)
    row_loss = lse - (1.0 - label_smoothing) * tgt_logit
    if label_smoothing:
        row_loss -= (label_smoothing / w.shape[1]) * jnp.sum(logits, -1)
    if z_loss:
        row_loss += z_loss * lse * lse
    return row_loss, logits, valid, tgt, lse


def _stream(h, w, targets, row_scale, chunk_rows, label_smoothing, z_loss,
            grad: bool):
    """The row chunks through the head, ONCE.  ``row_scale`` None: per
    chunk ``(loss sum, hits, valid rows)``; given: per row ``(loss,
    hit)``, ``[chunks, chunk_rows]`` each.

    With ``grad`` also ``dh f32[rows, d]`` and ``dw f32[d, V]`` of ``sum_r
    row_scale_r * loss_r`` (None: every row 1), made while the chunk's
    logits are held: its softmax is contracted at once into dh and dw, in
    the products' accumulator type, so that the backward can scale them
    before their one rounding."""
    rows, d = h.shape
    hp, tp, nc = _pad_rows(h, targets, chunk_rows)
    xs = (hp.reshape(nc, chunk_rows, d), tp.reshape(nc, chunk_rows))
    per_row = row_scale is not None

    def forward(h_c, t_c):
        row_loss, logits, valid, tgt, lse = _row_losses(
            h_c, w, t_c, label_smoothing, z_loss)
        # in this order: a call nobody differentiates traces the program
        # it traced before the gradient moved here (tests/test_ouro.py)
        loss = jnp.where(valid, row_loss, 0.0)
        if not per_row:
            loss = jnp.sum(loss)
        hit = jnp.where(valid, jnp.argmax(logits, -1) == tgt, 0)
        if per_row:
            out = loss, hit.astype(jnp.float32)
        else:
            out = (loss, jnp.sum(hit).astype(jnp.float32),
                   jnp.sum(valid).astype(jnp.float32))
        return out, (logits, valid, tgt, lse)

    def step(dw_acc, args):
        h_c, t_c, *r_c = args
        out, (logits, valid, tgt, lse) = forward(h_c, t_c)
        # d row_loss / d logits = p*(1 + 2*z*lse) - (1-eps)*onehot - eps/V
        p = jnp.exp(logits - lse[:, None])
        if z_loss:
            p *= 1.0 + 2.0 * z_loss * lse[:, None]
        gl = p - (1.0 - label_smoothing) * jax.nn.one_hot(
            tgt, w.shape[1], dtype=jnp.float32)
        if label_smoothing:
            gl -= label_smoothing / w.shape[1]
        gl = jnp.where(valid[:, None], gl, 0.0)
        if r_c:
            gl *= r_c[0][:, None]
        glc = gl.astype(h_c.dtype)  # grads ride the MXU in compute dtype
        dh_c = jnp.dot(glc, w.T, preferred_element_type=jnp.float32)
        dw_acc = dw_acc + jnp.dot(h_c.T, glc,
                                  preferred_element_type=jnp.float32)
        return dw_acc, (out, dh_c)

    if not grad:
        out = jax.lax.map(lambda args: forward(*args)[0], xs)
    else:
        if per_row:
            xs += (jnp.pad(row_scale.astype(jnp.float32),
                           (0, nc * chunk_rows - rows)
                           ).reshape(nc, chunk_rows),)
        # init carry inherits h's varying-manual-axes type so the scan
        # carry stays consistent when this runs inside shard_map (the
        # `+ 0*h[0,0]` is free after fusion and a no-op outside shard_map)
        dw_init = jnp.zeros((d, w.shape[1]), jnp.float32) + \
            0.0 * hp[0, 0].astype(jnp.float32)
        dw, (out, dhcs) = jax.lax.scan(step, dw_init, xs)
    return (out, dhcs.reshape(-1, d)[:rows], dw) if grad else out


def _weighted_out(weights, row_loss, correct):
    """``_weighted_rows``' returns from the chunks' rows."""
    rows = weights.shape[0]
    row_loss = row_loss.reshape(-1)[:rows]
    return (jnp.sum(weights.astype(jnp.float32) * row_loss), row_loss,
            correct.reshape(-1)[:rows])


@jax.tree_util.register_static
class _Dtypes(tuple):
    """The operands' dtypes among a forward rule's residuals (no array:
    the operands themselves do not cross to the backward)."""


def _scaled(dh, dw, dtypes, scale, psum_axes):
    """The backward: what the forward made, times the cotangent's scalar,
    each rounded to its operand's dtype once."""
    dw = dw * scale
    if psum_axes:
        dw = jax.lax.psum(dw, psum_axes)
    return (dh * scale).astype(dtypes[0]), dw.astype(dtypes[1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _streamed_sums(h, w, targets, chunk_rows, psum_axes=(),
                   label_smoothing=0.0, z_loss=0.0):
    """(loss_sum, correct_sum, n_valid) streamed over row chunks; only
    loss_sum carries gradient.

    ``psum_axes``: when called inside shard_map with ``w`` replicated over
    those mesh axes, the backward all-reduces dW over them itself — the
    shard_map transpose cannot infer that the custom bwd's dW needs
    replication (it would reject the out_spec otherwise)."""
    return tuple(jnp.sum(o) for o in _stream(
        h, w, targets, None, chunk_rows, label_smoothing, z_loss, False))


def _sums_fwd(h, w, targets, chunk_rows, psum_axes, label_smoothing,
              z_loss):
    out, dh, dw = _stream(h, w, targets, None, chunk_rows, label_smoothing,
                          z_loss, True)
    return tuple(jnp.sum(o) for o in out), (dh, dw,
                                            _Dtypes((h.dtype, w.dtype)))


def _sums_bwd(chunk_rows, psum_axes, label_smoothing, z_loss, res, g):
    # correct/valid counts carry no grad
    return *_scaled(*res, g[0].astype(jnp.float32), psum_axes), None


_streamed_sums.defvjp(_sums_fwd, _sums_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _weighted_rows(h, w, targets, weights, chunk_rows, psum_axes=(),
                   label_smoothing=0.0, z_loss=0.0):
    """``(sum_r weights_r * loss_r, loss [rows], correct [rows])``
    streamed over row chunks.  Only the sum carries gradient: to ``h``
    and ``w`` through the rows' losses, each scaled by its weight, and
    to ``weights``, whose cotangent is the row's own loss (the one
    residual this keeps beside dh and dw).  ``psum_axes`` as
    ``_streamed_sums``."""
    return _weighted_out(weights, *_stream(
        h, w, targets, weights, chunk_rows, label_smoothing, z_loss, False))


def _weighted_fwd(h, w, targets, weights, chunk_rows, psum_axes,
                  label_smoothing, z_loss):
    rows, dh, dw = _stream(h, w, targets, weights, chunk_rows,
                           label_smoothing, z_loss, True)
    out = _weighted_out(weights, *rows)
    return out, (dh, dw, _Dtypes((h.dtype, w.dtype, weights.dtype)),
                 out[1])


def _weighted_bwd(chunk_rows, psum_axes, label_smoothing, z_loss, res, g):
    dh, dw, dtypes, row_loss = res
    scale = g[0].astype(jnp.float32)
    return *_scaled(dh, dw, dtypes, scale, psum_axes), None, \
        (scale * row_loss).astype(dtypes[2])


_weighted_rows.defvjp(_weighted_fwd, _weighted_bwd)


def _batch_axes_in(mesh) -> Tuple[str, ...]:
    from ..parallel import mesh as mesh_lib
    return tuple(ax for ax in mesh_lib.BATCH_AXES
                 if ax in mesh.shape and mesh.shape[ax] > 1)


def fused_linear_cross_entropy(h: jax.Array, w: jax.Array,
                               targets: jax.Array,
                               chunk_rows: int = DEFAULT_CHUNK_ROWS,
                               mesh=None, label_smoothing: float = 0.0,
                               z_loss: float = 0.0,
                               row_weights: Optional[jax.Array] = None):
    """Streaming LM-head loss.  h: [rows, d], w: [d, V], targets: [rows]
    int32 (negative entries masked).  Returns (mean_loss f32, accuracy f32);
    only ``mean_loss`` is differentiable (accuracy grad is zero).

    With ``row_weights`` ([rows] float) it returns ``(sum_r weights_r *
    loss_r, loss [rows], correct [rows])`` instead: the weighted SUM
    over all rows (the caller's to normalise) with a gradient to ``h``,
    ``w`` and ``row_weights``; the rows' own losses and hits come out as
    values (no gradient through them).  Without it the program is the
    unweighted one, op for op.

    Logits are computed chunk-by-chunk and never materialized whole — see
    module docstring.  ``chunk_rows`` bounds the live logits block
    [chunk_rows, V]; rows are zero-padded to a multiple of it.

    When ``mesh`` has sharded data/fsdp axes the op runs under
    ``shard_map`` so each device streams only its local rows; the row
    dim of ``h``/``targets`` must then be sharded over exactly those
    axes.  Already INSIDE a manual (shard_map) trace — the compressed
    gradient exchange runs the whole model in one — the rows are
    device-local and the batch axes are bound, so the op streams them
    directly and psums the scalar sums without nesting another
    shard_map.
    """
    if mesh is not None and _batch_axes_in(mesh):
        from ..parallel.sharding import manual_axes
        axes = _batch_axes_in(mesh)
        if row_weights is not None:
            return _weighted_sharded(h, w, targets, row_weights, chunk_rows,
                                     mesh, axes, set(axes) <= manual_axes(),
                                     label_smoothing, z_loss)
        if set(axes) <= manual_axes():
            return _streamed_psum_mean(h, w, targets, chunk_rows, axes,
                                       label_smoothing, z_loss)
        return _fused_sharded(h, w, targets, chunk_rows, mesh,
                              label_smoothing, z_loss)
    if row_weights is not None:
        return _weighted_rows(h, w, targets, row_weights, chunk_rows, (),
                              label_smoothing, z_loss)
    ls, cs, n = _streamed_sums(h, w, targets, chunk_rows, (),
                               label_smoothing, z_loss)
    n = jnp.maximum(n, 1.0)
    return ls / n, cs / n


def _streamed_psum_mean(h_l, w_r, t_l, chunk_rows, axes, label_smoothing,
                        z_loss):
    """Local rows -> psum'd mean loss/accuracy (runs with ``axes`` bound:
    either as a shard_map body or inline inside an enclosing manual
    trace)."""
    ls, cs, n = _streamed_sums(h_l, w_r, t_l, chunk_rows, axes,
                               label_smoothing, z_loss)
    ls = jax.lax.psum(ls, axes)
    # accuracy and the valid-row count are not differentiated (only
    # mean_loss is, per the public contract): cut the dead AD paths
    cs = jax.lax.psum(jax.lax.stop_gradient(cs), axes)
    n = jnp.maximum(jax.lax.psum(jax.lax.stop_gradient(n), axes), 1.0)
    return ls / n, cs / n


def _fused_sharded(h, w, targets, chunk_rows, mesh, label_smoothing=0.0,
                   z_loss=0.0):
    axes = _batch_axes_in(mesh)
    P = jax.sharding.PartitionSpec

    def body(h_l, w_r, t_l):
        return _streamed_psum_mean(h_l, w_r, t_l, chunk_rows, axes,
                                   label_smoothing, z_loss)

    return jax.shard_map(
        body, mesh=mesh,
        # graftlint: ok(sharding-inventory) — fused-loss shard_map specs
        in_specs=(P(axes, None), P(None, None), P(axes)),
        # graftlint: ok(sharding-inventory) — scalar replicated outputs
        out_specs=(P(), P()))(h, w, targets)


def _weighted_sharded(h, w, targets, weights, chunk_rows, mesh, axes,
                      bound: bool, label_smoothing=0.0, z_loss=0.0):
    """The weighted loss over rows sharded on ``axes``: each device
    streams its own rows, the weighted sum is psum'd, the rows' losses
    and hits stay where their rows are.  ``bound``: the axes are manual
    already (the caller runs inside a shard_map)."""
    P = jax.sharding.PartitionSpec

    def body(h_l, w_r, t_l, r_l):
        total, row_loss, correct = _weighted_rows(
            h_l, w_r, t_l, r_l, chunk_rows, axes, label_smoothing, z_loss)
        return jax.lax.psum(total, axes), row_loss, correct

    if bound:
        return body(h, w, targets, weights)
    return jax.shard_map(
        body, mesh=mesh,
        # graftlint: ok(sharding-inventory) — fused-loss shard_map specs
        in_specs=(P(axes, None), P(None, None), P(axes), P(axes)),
        # graftlint: ok(sharding-inventory) — a replicated sum, local rows
        out_specs=(P(), P(axes), P(axes)))(h, w, targets, weights)
