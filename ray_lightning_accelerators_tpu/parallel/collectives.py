"""Communication-efficient gradient exchange: quantized allreduce + ZeRO-1.

The trainer's data-parallel gradient exchange is an *implicit* fp32
allreduce: params are replicated, the batch is sharded, and XLA emits the
psum inside the backward pass (core/trainer.py).  That is correct and
fast on one host, but past a single host the two dominant costs of scaling
data parallelism are (1) full-precision gradient bytes on the wire and
(2) every replica holding a full copy of the optimizer state.  This module
attacks both, each opt-in and composable, both living INSIDE the jitted
train step so XLA fuses them (no extra dispatch):

**Quantized allreduce** (EQuARX-style, arxiv 2506.17615).  Each replica's
local gradients are exchanged explicitly through a ``shard_map`` over the
batch axes: per-block int8 (or bf16) compression with per-block scales, a
two-phase bandwidth-optimal exchange (block-quantized all_to_all =
reduce-scatter in int8, then re-quantize + all_gather), and persistent
error-feedback residuals so the quantization error is carried forward
instead of lost (residuals live in ``TrainState.residual``).  Leaves
smaller than ``min_compress_size`` stay fp32 through a plain psum — tiny
tensors are latency-, not bandwidth-bound, and scales would dominate.

**ZeRO-1 optimizer-state sharding** (Xu et al., arxiv 2004.13336).  Each
replica owns a ``1/N`` shard of the optimizer state (dim 0 of every
param-shaped moment, where divisible), applies its shard of the update,
and the updated params are all-gathered — expressed purely as sharding
constraints, so XLA partitions the update computation.  The gradient
allreduce is pinned replicated first, which is what makes the result
**bit-identical** to replicated training: the reduce is unchanged and the
update itself is elementwise.

**Compressed FSDP (ZeRO-2/3)** (composition of Xu et al. 2004.13336's
sharded weight update with EQuARX-style quantized collectives, the ZeRO++
wire recipe).  When params are sharded over the ``fsdp`` mesh axis the
exchange stops being an allreduce: per-replica gradients flow through a
block-int8 (or bf16) **reduce-scatter into the shard owner** along the
fsdp axis (``build_fsdp_exchange``), the optimizer update runs
shard-locally on the owner (optimizer state inherits the 1/N fsdp
layout), and the updated shards are **bf16 all-gathered** back to the
replicated-for-compute view for the next forward
(``build_param_gather``).  Error-feedback residuals are kept
SHARD-LOCAL (1/N): each replica carries the quantization error of the
chunk it owns — the cross-chunk error terms other replicas' quantizers
introduce are dropped (they are zero-mean per block; carrying them
would need a full-size residual per replica, exactly the memory FSDP
exists to shed — the ZeRO++ trade).  Tensor/sequence/pipeline-sharded
params cannot ride this path (their gradients are not replicas) and
refuse with the typed :class:`TensorShardedParamsError`.

Wire accounting is analytic (``wire_bytes_per_step``): ring-allreduce
fp32 moves ``2*(N-1)/N * 4`` bytes per element per device; the two-phase
int8 exchange moves ``2*(N-1)/N * (1 + 4/block)`` — a ~3.9x reduction at
block 256 — and the FSDP regime (``param_shardings=`` given) accounts
the int8 reduce-scatter + bf16 param all-gather against the same fp32
baseline (~2.6x), reported per-step through
``utils.profiler.Profiler``'s comms hook so the win is observable, not
asserted.

No reference analog: the reference delegated gradient exchange wholesale
to torch DDP's bucketed fp32 allreduce (ray_lightning/ray_ddp.py:222-237).
"""

from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import mesh as mesh_lib

COMPRESSION_MODES = (None, "int8", "bf16")

# how the bf16 compute view of fsdp-sharded params is assembled inside
# the train step: "tree" all-gathers the WHOLE param tree before the
# forward (PR 8 — simple, but the gather latency serializes with
# compute); "scan" keeps the stacked per-layer leaves sharded as scan
# operands and all-gathers each layer INSIDE the layer scan, so XLA can
# overlap layer k+1's gather with layer k's matmuls and the backward
# re-gathers per layer under the remat policy instead of holding the
# full replicated tree live (the ZeRO-3 latency-hiding schedule)
GATHER_MODES = ("tree", "scan")

# int8 quantization granularity: one f32 scale per this many elements.
# 256 keeps scale overhead at 4/256 = 1.6% of payload while staying well
# inside the regime where a block's maxabs tracks its contents.
DEFAULT_BLOCK = 256

# leaves below this element count stay fp32 (plain psum): biases and norm
# scales are latency-bound, and per-block scales would eat the savings
DEFAULT_MIN_COMPRESS_SIZE = 2048


@dataclasses.dataclass(frozen=True)
class ExchangeConfig:
    """Gradient-exchange policy for one trainer run."""

    mode: Optional[str] = None          # None | "int8" | "bf16"
    block: int = DEFAULT_BLOCK
    min_compress_size: int = DEFAULT_MIN_COMPRESS_SIZE

    def __post_init__(self):
        if self.mode not in COMPRESSION_MODES:
            raise ValueError(
                f"grad_compression must be one of {COMPRESSION_MODES}, "
                f"got {self.mode!r}")
        if self.block < 1:
            raise ValueError(f"block must be >= 1, got {self.block}")


def dp_axis_names(mesh: Mesh) -> Tuple[str, ...]:
    """The mesh axes a data-parallel gradient exchange reduces over."""
    return tuple(mesh_lib.BATCH_AXES)


def dp_size(mesh: Mesh) -> int:
    return mesh_lib.data_parallel_size(mesh)


def validate_mesh_for_compression(mesh: Mesh) -> None:
    """Quantized exchange replaces the DP psum only: params must be
    replicated over every mesh axis, so any model-parallel axis > 1 (whose
    gradients are NOT pure replicas) is a configuration error."""
    bad = {a: s for a, s in mesh.shape.items()
           if a not in mesh_lib.BATCH_AXES and s > 1}
    if bad:
        raise ValueError(
            f"grad_compression requires a pure data-parallel mesh; "
            f"model-parallel axes {bad} are > 1.  Quantized allreduce "
            f"exchanges replicated-param gradients over {mesh_lib.BATCH_AXES} "
            f"only — drop the compression flag or the model-parallel axes.")


def compressible(leaf, cfg: ExchangeConfig) -> bool:
    """Static (shape/dtype-level) decision: does this gradient leaf ride
    the compressed path or stay fp32?"""
    if cfg.mode is None or not hasattr(leaf, "shape"):
        return False
    dtype = getattr(leaf, "dtype", None)
    if dtype is None or not jnp.issubdtype(dtype, jnp.floating):
        return False
    return int(np.prod(leaf.shape)) >= cfg.min_compress_size


# --------------------------------------------------------------------- #
# Block quantization (pure, also used by tests and the bench probe)      #
# --------------------------------------------------------------------- #
def quantize_blocks(v: jax.Array, block: int) -> Tuple[jax.Array, jax.Array]:
    """Flat f32 vector -> (int8 [nb, block], f32 scales [nb]).

    ``v.size`` must already be a multiple of ``block`` (pad first).  Scales
    are per-block symmetric maxabs/127; an all-zero block gets scale 1 so
    dequantization never divides by zero."""
    blocks = v.astype(jnp.float32).reshape(-1, block)
    maxabs = jnp.max(jnp.abs(blocks), axis=1)
    scale = jnp.where(maxabs > 0, maxabs / 127.0, 1.0)
    q = jnp.clip(jnp.round(blocks / scale[:, None]), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_blocks(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of ``quantize_blocks``; returns flat f32 [nb * block]."""
    return (q.astype(jnp.float32) * scale[:, None]).reshape(-1)


def _pad_to(v: jax.Array, multiple: int) -> Tuple[jax.Array, int]:
    n = v.size
    pad = (-n) % multiple
    if pad:
        v = jnp.concatenate([v, jnp.zeros((pad,), v.dtype)])
    return v, n


# --------------------------------------------------------------------- #
# In-step exchange (runs INSIDE a shard_map body)                        #
# --------------------------------------------------------------------- #
def _exchange_int8(v, axes, n, block):
    """Two-phase block-int8 allreduce-mean of one flat local leaf.

    Phase 1 — quantized reduce-scatter: quantize the whole local leaf in
    blocks, all_to_all the int8 blocks (+ scales) so each replica receives
    every peer's copy of its owned 1/N block range, dequantize and sum.
    Phase 2 — quantized all-gather: re-quantize the owned reduced range,
    all_gather the int8 blocks (+ scales), dequantize into the full mean.
    int8 is what crosses the wire in both phases; scales are f32 but
    1/block the volume.  Returns (global_mean_flat, local_dequant_flat)
    — the latter is what error feedback subtracts."""
    q, s = quantize_blocks(v, block)                # [nb, block], [nb]
    # error feedback compensates the local (phase-1) quantization error
    local_dq = dequantize_blocks(q, s)
    # shard blocks over replicas for the all_to_all; nb is padded to a
    # multiple of n by the caller
    peers_q = jax.lax.all_to_all(q, axes, split_axis=0, concat_axis=0,
                                 tiled=True)        # [nb, block]
    peers_s = jax.lax.all_to_all(s, axes, split_axis=0, concat_axis=0,
                                 tiled=True)        # [nb]
    nb = q.shape[0]
    own = (peers_q.astype(jnp.float32).reshape(n, nb // n, block)
           * peers_s.reshape(n, nb // n, 1)).sum(0) / n   # [nb/n, block]
    q2, s2 = quantize_blocks(own.reshape(-1), block)
    all_q = jax.lax.all_gather(q2, axes, axis=0, tiled=True)   # [nb, block]
    all_s = jax.lax.all_gather(s2, axes, axis=0, tiled=True)   # [nb]
    return dequantize_blocks(all_q, all_s), local_dq


def _exchange_bf16(v, axes, n):
    """bf16-on-the-wire allreduce-mean: cast, all_to_all shards, sum in
    f32, re-cast, all_gather.  Same two-phase structure as int8 (2x wire
    reduction); error feedback compensates the local cast error."""
    c = v.astype(jnp.bfloat16)
    local_dq = c.astype(jnp.float32)
    shards = c.reshape(n, -1)
    peers = jax.lax.all_to_all(shards, axes, split_axis=0, concat_axis=0,
                               tiled=True).reshape(n, -1)
    own = peers.astype(jnp.float32).sum(0) / n
    gathered = jax.lax.all_gather(own.astype(jnp.bfloat16), axes,
                                  axis=0, tiled=True)
    return gathered.astype(jnp.float32), local_dq


def _exchange_leaf_in_body(g, r, axes, n, cfg: ExchangeConfig):
    """One leaf inside the shard_map body: (local grad, local residual) ->
    (global mean grad, new residual).  ``g``/``r`` carry the leading
    length-1 replica axis shard_map gives per-device blocks."""
    g = g.reshape(g.shape[1:])   # drop the replica axis ([1, ...] block)
    r = r.reshape(r.shape[1:])
    if not compressible(g, cfg):
        # fp32 path: plain psum-mean, no residual (no compression error)
        out = jax.lax.psum(g, axes) / n
        return out, r
    orig_dtype, shape = g.dtype, g.shape
    v = g.astype(jnp.float32).reshape(-1) + r.reshape(-1)
    if cfg.mode == "bf16":
        v_pad, true_n = _pad_to(v, n)
        mean, local_dq = _exchange_bf16(v_pad, axes, n)
    else:
        v_pad, true_n = _pad_to(v, n * cfg.block)
        mean, local_dq = _exchange_int8(v_pad, axes, n, cfg.block)
    new_r = (v_pad - local_dq)[:true_n]
    out = mean[:true_n].reshape(shape).astype(orig_dtype)
    return out, new_r.reshape(r.shape)


def residual_zeros(params, n: int, cfg: ExchangeConfig):
    """Per-replica error-feedback residuals: a [n, leaf.size] f32 buffer
    per compressible leaf, a [n, 1] placeholder otherwise (keeps the tree
    congruent with the gradient tree for tree_map without burning memory
    on leaves the fp32 path never touches)."""
    def one(p):
        size = int(np.prod(p.shape)) if compressible(p, cfg) else 1
        return jnp.zeros((n, size), jnp.float32)
    return jax.tree.map(one, params)


def accum_zeros(params, n: int):
    """Per-replica local-gradient accumulators ([n, *leaf.shape]) for
    compress-once-per-accumulation-boundary micro-batching."""
    return jax.tree.map(
        lambda p: jnp.zeros((n,) + tuple(p.shape), jnp.float32), params)


def stacked_shardings(mesh: Mesh, tree):
    """NamedShardings for [n, ...]-stacked per-replica trees (residuals,
    accumulators); the layout is authored in ``plan.py`` (the single
    spec-producing module — see stacked_replica_spec)."""
    from . import plan as plan_lib
    sh = plan_lib.stacked_replica_sharding(mesh)
    return jax.tree.map(lambda _: sh, tree)


def build_exchange(mesh: Mesh, cfg: ExchangeConfig):
    """The jit-composable exchange: (stacked local grads [n, *shape],
    stacked residuals [n, size]) -> (global mean grads, new residuals).

    Inputs/outputs are stacked over a leading replica axis sharded on the
    batch axes; outputs' gradient tree is replicated.  Call inside the
    jitted train step — XLA fuses the collectives with the surrounding
    program."""
    axes = dp_axis_names(mesh)
    n = dp_size(mesh)

    def body(stacked_grads, stacked_res):
        flat_g, treedef = jax.tree.flatten(stacked_grads)
        flat_r = treedef.flatten_up_to(stacked_res)
        outs = [_exchange_leaf_in_body(g, r, axes, n, cfg)
                for g, r in zip(flat_g, flat_r)]
        grads = treedef.unflatten([o[0] for o in outs])
        new_res = treedef.unflatten([o[1][None] for o in outs])
        return grads, new_res

    lead = P(mesh_lib.BATCH_AXES)
    return jax.shard_map(body, mesh=mesh, in_specs=(lead, lead),
                     out_specs=(P(), lead), check_vma=False)


def build_local_grads(mesh: Mesh, value_and_grad_fn, batch_spec,
                      extra_metrics=None):
    """Per-replica gradient computation: runs ``value_and_grad_fn(params,
    batch, rng) -> ((loss, metrics), grads)`` on each replica's batch
    shard WITHOUT the implicit psum, returning pmean'd metrics (replicated)
    and the raw local grads stacked [n, *shape] (sharded on batch axes).

    ``extra_metrics(grads) -> dict`` (optional) runs in-body on the LOCAL
    grads with the dp axes bound, so it may use psum/pmean — the
    grad-norm hook rides this."""
    axes = dp_axis_names(mesh)

    def body(params, batch, rng):
        # decorrelate per-replica stochasticity: the incoming key is
        # replicated, and a shared key would sample IDENTICAL dropout/
        # augmentation masks on every replica (the baseline path draws
        # one mask over the whole global batch; here each replica must
        # draw its own for its shard)
        rng = jax.random.fold_in(rng, jax.lax.axis_index(axes))
        (_, metrics), grads = value_and_grad_fn(params, batch, rng)
        metrics = jax.tree.map(lambda m: jax.lax.pmean(m, axes), metrics)
        if extra_metrics is not None:
            metrics.update(extra_metrics(grads))
        stacked = jax.tree.map(lambda g: g[None], grads)
        return metrics, stacked

    return jax.shard_map(
        body, mesh=mesh, in_specs=(P(), batch_spec, P()),
        out_specs=(P(), P(mesh_lib.BATCH_AXES)), check_vma=False)


# --------------------------------------------------------------------- #
# Compressed FSDP (ZeRO-2/3): reduce-scatter into the shard owner        #
# --------------------------------------------------------------------- #
class TensorShardedParamsError(ValueError):
    """Typed refusal: ``grad_compression`` composes with replicated (pure
    DP) and fsdp-sharded params only.  Tensor/sequence/pipeline/expert-
    sharded params have gradients that are NOT pure replicas over the
    batch axes — a quantized replica exchange of them would be silently
    wrong, so the configuration refuses loudly and typed."""


def fsdp_shard_dim(sharding_or_spec) -> Optional[int]:
    """The one param dim sharded over the ``fsdp`` axis, or None for a
    fully replicated leaf.  Raises :class:`TensorShardedParamsError` for
    any model-parallel (non-fsdp) axis in the spec — the layouts the
    compressed exchange cannot treat as replicas.

    Mesh-aware: a NamedSharding's spec may name model-parallel axes the
    MESH holds at size 1 (rule-based logical shardings always emit the
    full axis table — a GPT on a pure data x fsdp mesh still says
    ``P('layers'->pipeline, 'embed'->fsdp, ...)``).  Size-1 axes shard
    nothing, so they are ignored; a bare PartitionSpec (no mesh) keeps
    the strict reading — every named axis counts."""
    spec = getattr(sharding_or_spec, "spec", sharding_or_spec)
    mesh = getattr(sharding_or_spec, "mesh", None)

    def real(axis: str) -> bool:
        return mesh is None or mesh_lib.mesh_axis_size(mesh, axis) > 1

    dim = None
    for d, entry in enumerate(tuple(spec)):
        if entry is None:
            continue
        axes = entry if isinstance(entry, tuple) else (entry,)
        axes = tuple(a for a in axes if real(a))
        if not axes:
            continue
        bad = [a for a in axes if a != mesh_lib.FSDP_AXIS]
        if bad or (mesh_lib.FSDP_AXIS in axes and len(axes) > 1):
            raise TensorShardedParamsError(
                f"grad_compression supports replicated or fsdp-sharded "
                f"params only; found a param dim sharded over mesh axes "
                f"{axes} (tensor/sequence/pipeline-style model "
                f"parallelism).  Drop grad_compression or the "
                f"model-parallel sharding (use_fsdp composes; "
                f"param_logical_axes mapping to '{mesh_lib.TENSOR_AXIS}' "
                f"etc. does not).")
        if dim is not None:
            raise TensorShardedParamsError(
                "grad_compression supports at most one fsdp-sharded dim "
                f"per param; spec {tuple(spec)} shards two")
        dim = d
    return dim


def _fsdp_chunk_elems(shape, dim: int, nf: int,
                      cfg: ExchangeConfig) -> Tuple[int, int]:
    """(chunk, chunk_pad) element counts of one owner's flat slice of a
    leaf sharded on ``dim`` over an fsdp axis of size ``nf``.  int8 pads
    each chunk up to a block multiple so quantization blocks never span
    chunk (= destination) boundaries."""
    if shape[dim] % nf:
        # only reachable via explicit param_logical_axes shardings —
        # infer_fsdp_shardings never picks an indivisible dim.  Refuse
        # typed HERE (the shared choke point of residual init, wire
        # accounting and the exchange body) instead of dying in an
        # obscure reshape mismatch mid-trace
        raise TensorShardedParamsError(
            f"param dim {dim} of shape {tuple(shape)} is sharded over "
            f"the fsdp axis but its size {shape[dim]} is not divisible "
            f"by fsdp={nf}; the compressed reduce-scatter needs "
            f"equal-size owner chunks — pad the dim, drop its fsdp "
            f"sharding, or drop grad_compression")
    size = int(np.prod(shape))
    chunk = size // nf
    if cfg.mode == "int8":
        return chunk, chunk + ((-chunk) % cfg.block)
    return chunk, chunk


def _leaf_regime(leaf, sharding_or_spec, cfg: ExchangeConfig) -> str:
    """Which exchange a gradient leaf rides under FSDP composition:
    ``rs`` (fsdp-sharded + compressible: quantized reduce-scatter into
    the owner), ``allreduce`` (replicated + compressible: the two-phase
    quantized allreduce), ``exact`` (everything else: fp32 psum, sliced
    to the shard when the param is sharded)."""
    dim = fsdp_shard_dim(sharding_or_spec)
    if dim is not None and compressible(leaf, cfg):
        return "rs"
    if compressible(leaf, cfg):
        return "allreduce"
    return "exact"


def fsdp_residual_zeros(params, param_shardings, cfg: ExchangeConfig,
                        scanned: Tuple[str, ...] = ()):
    """Shard-local error-feedback residuals for the FSDP exchange: a
    stacked ``[n, chunk_pad]`` f32 buffer per reduce-scattered leaf
    (each replica holds its OWNED chunk's error — 1/nf of the leaf, the
    whole point), a full ``[n, size]`` buffer for compressible leaves
    that stayed replicated (they ride the two-phase allreduce, whose EF
    is sender-complete), and a ``[n, 1]`` placeholder otherwise.

    ``scanned`` (gather_mode='scan'): leaves of the named top-level
    subtrees never ride the quantized exchange — their gradients are
    reduce-scattered exactly (bf16 cotangent) by the in-scan gather's
    autodiff transpose — so they all get the placeholder."""

    def one(p, sh, in_scan=False):
        if in_scan:
            return jnp.zeros((n, 1), jnp.float32)
        regime = _leaf_regime(p, sh, cfg)
        if regime == "rs":
            _, chunk_pad = _fsdp_chunk_elems(p.shape, fsdp_shard_dim(sh),
                                             nf, cfg)
            return jnp.zeros((n, chunk_pad), jnp.float32)
        size = int(np.prod(p.shape)) if regime == "allreduce" else 1
        return jnp.zeros((n, size), jnp.float32)

    mesh = jax.tree.leaves(param_shardings)[0].mesh
    n = dp_size(mesh)
    nf = mesh_lib.mesh_axis_size(mesh, mesh_lib.FSDP_AXIS)
    if not scanned:
        return jax.tree.map(one, params, param_shardings)
    return {
        k: jax.tree.map(
            lambda p, sh, _s=(k in scanned): one(p, sh, in_scan=_s),
            sub, param_shardings[k])
        for k, sub in params.items()}


def _rs_leaf_in_body(g, r, dim, nf, n, data_axes, cfg: ExchangeConfig):
    """One fsdp-sharded compressible leaf inside the shard_map body:
    (local grad [*shape], own-chunk residual [chunk_pad]) -> (reduced
    OWNED grad shard [shard shape], new residual [chunk_pad]).

    Phase layout: slice the local grad into one flat chunk per fsdp
    destination, add the shard-local residual to the OWNED chunk,
    quantize, all_to_all the int8 payload (+scales) over ``fsdp`` so
    each owner receives every fsdp-peer's copy of its chunk, dequantize
    + sum, then a (1/nf-sized) fp32 psum over the pure-data axes folds
    in the cross-data replicas.  int8 (or bf16) is what crosses the
    fsdp wire; nothing is ever all-gathered back — the updated PARAMS
    are what return to the replicas (build_param_gather)."""
    orig_dtype, shape = g.dtype, g.shape
    shard_len = shape[dim] // nf
    m = jnp.moveaxis(g.astype(jnp.float32), dim, 0)
    rest_shape = m.shape[1:]
    chunk, chunk_pad = _fsdp_chunk_elems(shape, dim, nf, cfg)
    m = m.reshape(nf, chunk)
    if chunk_pad != chunk:
        m = jnp.pad(m, ((0, 0), (0, chunk_pad - chunk)))
    own = jax.lax.axis_index(mesh_lib.FSDP_AXIS)
    # residual add and error extraction touch ONLY the owned chunk:
    # indexed update/reads lower to dynamic slices, O(chunk) instead of
    # the O(nf*chunk) a full onehot mask (or full dequantize) would cost
    # in the hot step
    m = m.at[own].add(r)
    own_m = m[own]
    if cfg.mode == "bf16":
        c = m.astype(jnp.bfloat16)
        own_dq = c[own].astype(jnp.float32)
        recv = jax.lax.all_to_all(c, mesh_lib.FSDP_AXIS, split_axis=0,
                                  concat_axis=0, tiled=True)
        summed = recv.astype(jnp.float32).reshape(nf, chunk_pad).sum(0)
    else:
        bpc = chunk_pad // cfg.block   # blocks never span chunks
        q, s = quantize_blocks(m.reshape(-1), cfg.block)
        own_dq = dequantize_blocks(q.reshape(nf, bpc, cfg.block)[own],
                                   s.reshape(nf, bpc)[own])
        pq = jax.lax.all_to_all(q, mesh_lib.FSDP_AXIS, split_axis=0,
                                concat_axis=0, tiled=True)
        ps = jax.lax.all_to_all(s, mesh_lib.FSDP_AXIS, split_axis=0,
                                concat_axis=0, tiled=True)
        summed = dequantize_blocks(pq, ps).reshape(nf, chunk_pad).sum(0)
    new_r = own_m - own_dq
    red = jax.lax.psum(summed, data_axes) / n
    out = red[:chunk].reshape((shard_len,) + rest_shape)
    out = jnp.moveaxis(out, 0, dim).astype(orig_dtype)
    return out, new_r


def build_fsdp_exchange(mesh: Mesh, cfg: ExchangeConfig, param_shardings):
    """The jit-composable FSDP exchange: (stacked local grads
    [n, *shape], shard-local residuals) -> (grads in the PARAM layout —
    each owner holds its reduced shard — and new residuals).

    Per-leaf routing follows ``_leaf_regime``: fsdp-sharded compressible
    leaves reduce-scatter quantized into the owner; compressible leaves
    that stayed replicated ride the existing two-phase allreduce;
    everything else is an exact fp32 psum (sliced to the shard when the
    param is sharded).  Call inside the jitted train step."""
    all_axes = dp_axis_names(mesh)
    data_axes = tuple(a for a in all_axes if a != mesh_lib.FSDP_AXIS)
    n = dp_size(mesh)
    nf = mesh_lib.mesh_axis_size(mesh, mesh_lib.FSDP_AXIS)
    flat_sh, sh_treedef = jax.tree.flatten(param_shardings)
    dims = [fsdp_shard_dim(s) for s in flat_sh]

    def body(stacked_grads, stacked_res):
        flat_g, treedef = jax.tree.flatten(stacked_grads)
        flat_r = treedef.flatten_up_to(stacked_res)
        outs = []
        for g, r, dim in zip(flat_g, flat_r, dims):
            g2 = g.reshape(g.shape[1:])   # drop the [1, ...] replica axis
            r2 = r.reshape(r.shape[1:])
            if dim is not None and compressible(g2, cfg):
                outs.append(_rs_leaf_in_body(g2, r2, dim, nf, n,
                                             data_axes, cfg))
            elif dim is None:
                # replicated leaf: the existing two-phase allreduce (or
                # exact psum below threshold) — re-wraps the replica axis
                # _exchange_leaf_in_body expects
                outs.append(_exchange_leaf_in_body(g, r, all_axes, n, cfg))
            else:
                # fsdp-sharded but sub-threshold: exact psum, sliced to
                # the owned shard so the update still runs shard-local
                full = jax.lax.psum(g2.astype(jnp.float32), all_axes) / n
                own = jax.lax.axis_index(mesh_lib.FSDP_AXIS)
                shard_len = g2.shape[dim] // nf
                sl = jax.lax.dynamic_slice_in_dim(
                    full, own * shard_len, shard_len, axis=dim)
                outs.append((sl.astype(g2.dtype), r2))
        grads = treedef.unflatten([o[0] for o in outs])
        new_res = treedef.unflatten([o[1][None] for o in outs])
        return grads, new_res

    lead = P(mesh_lib.BATCH_AXES)
    out_grad_specs = sh_treedef.unflatten([s.spec for s in flat_sh])
    # graftlint: ok(retrace) — builder runs once at compile; reused
    return jax.shard_map(body, mesh=mesh, in_specs=(lead, lead),
                     out_specs=(out_grad_specs, lead), check_vma=False)


# dtype crossing the wire in the param all-gather: bf16 halves the
# all-gather bytes; the f32 master shards (the optimizer's view) are
# untouched, so this is standard mixed-precision, not a lossy state
PARAM_GATHER_DTYPE = jnp.bfloat16


def build_param_gather(mesh: Mesh, param_shardings):
    """The replicated-for-compute view of fsdp-sharded params: per leaf,
    cast the local shard to bf16, all_gather over the ``fsdp`` axis,
    cast back to the param dtype (bf16 is what crosses the wire; the f32
    master shards stay exact on their owners).  Replicated and
    non-float leaves pass through untouched.  Call inside the jitted
    train step — XLA overlaps the gathers with the forward."""
    flat_sh, sh_treedef = jax.tree.flatten(param_shardings)
    dims = [fsdp_shard_dim(s) for s in flat_sh]
    in_specs = sh_treedef.unflatten([s.spec for s in flat_sh])

    def body(params):
        flat_p, treedef = jax.tree.flatten(params)
        outs = []
        for p, dim in zip(flat_p, dims):
            if dim is None:
                outs.append(p)
                continue
            wire = (p.astype(PARAM_GATHER_DTYPE)
                    if jnp.issubdtype(p.dtype, jnp.floating) else p)
            g = jax.lax.all_gather(wire, mesh_lib.FSDP_AXIS, axis=dim,
                                   tiled=True)
            outs.append(g.astype(p.dtype))
        return treedef.unflatten(outs)

    # graftlint: ok(retrace) — builder runs once at compile; reused
    return jax.shard_map(body, mesh=mesh, in_specs=(in_specs,),
                     out_specs=P(), check_vma=False)


# --------------------------------------------------------------------- #
# Overlap-aware (scan) param gather: layer-wise all-gather in the scan   #
# --------------------------------------------------------------------- #
# The tree gather above assembles the WHOLE bf16 compute view before the
# forward: the all-gather latency serializes with compute and the full
# replicated tree stays live through the backward.  The scan gather
# instead keeps the stacked per-layer param leaves (the model's declared
# scanned subtrees, e.g. GPT's params["layers"]) fsdp-sharded as scan
# OPERANDS; each scan iteration all-gathers only its own layer's bf16
# shards through a hook the model applies at the top of its scan body,
# so XLA overlaps layer k+1's gather with layer k's matmuls.  The
# backward's transpose of that gather is a bf16 reduce-scatter
# (psum_scatter) straight into the shard owner — the gradient reduce
# over fsdp comes out of autodiff, per layer, overlapped — and under a
# remat policy that drops the gathered weights the backward re-gathers
# layer-by-layer instead of holding the replicated tree live.

# trace-time hook registry: build_scan_local_grads enters the scope
# around value_and_grad so the model's scan body picks up its gather
# hook DURING the train-step trace only — eval/predict traces (plain
# GSPMD jits, where a named-axis all_gather would not even bind) happen
# outside the scope and see None
_LAYER_GATHER_HOOKS: contextvars.ContextVar = contextvars.ContextVar(
    "rla_layer_gather_hooks", default=None)


@contextlib.contextmanager
def layer_gather_scope(hooks: Dict[str, Any]):
    token = _LAYER_GATHER_HOOKS.set(hooks)
    try:
        yield
    finally:
        _LAYER_GATHER_HOOKS.reset(token)


def current_layer_gather(key: str):
    """The in-scan gather hook for one scanned subtree (or None outside
    a scan-gather train-step trace)."""
    hooks = _LAYER_GATHER_HOOKS.get()
    return None if hooks is None else hooks.get(key)


def _split_scanned(tree: Dict[str, Any], scanned: Tuple[str, ...]):
    """(scanned subtrees, rest) of a top-level dict param tree."""
    sc = {k: v for k, v in tree.items() if k in scanned}
    rest = {k: v for k, v in tree.items() if k not in scanned}
    return sc, rest


def validate_scan_gather(param_shardings, scanned: Tuple[str, ...]) -> None:
    """Typed refusal of layouts the in-scan gather cannot handle: a
    scanned (stacked) leaf whose fsdp-sharded dim is dim 0 — the layer
    dim itself — cannot stay a scan operand (each device would hold only
    a slice of the LAYERS, not of a layer)."""
    if not isinstance(param_shardings, dict):
        raise TensorShardedParamsError(
            "gather_mode='scan' needs a dict param tree with the scanned "
            f"stacks as top-level keys; got {type(param_shardings).__name__}")
    missing = [k for k in scanned if k not in param_shardings]
    if missing:
        raise TensorShardedParamsError(
            f"gather_mode='scan': scanned subtree keys {missing} are not "
            f"top-level param keys {sorted(param_shardings)}")
    for k in scanned:
        for s in jax.tree.leaves(param_shardings[k]):
            if fsdp_shard_dim(s) == 0:
                raise TensorShardedParamsError(
                    f"gather_mode='scan': a leaf of scanned subtree {k!r} "
                    f"is fsdp-sharded on dim 0 (the stacked layer dim); "
                    f"the layer scan needs every device to hold ALL "
                    f"layers of its shard — shard a non-layer dim or use "
                    f"gather_mode='tree'")


def build_scan_param_gather(mesh: Mesh, param_shardings,
                            scanned: Tuple[str, ...]):
    """The scan-mode compute view: ``(prelude_fn, hooks)``.

    ``prelude_fn(params)`` bf16-all-gathers only the NON-scanned leaves
    (embeddings, final norm — weights every position touches before the
    first layer) exactly like ``build_param_gather`` and passes the
    scanned stacks through UNTOUCHED, still fsdp-sharded.

    ``hooks[key]`` is the per-layer gather the model applies inside its
    scan body (via ``current_layer_gather``): for each fsdp-sharded leaf
    of one layer SLICE, cast to bf16, ``all_gather`` over the fsdp axis
    (at the stacked dim minus the layer dim), cast back — so the gather
    of layer k+1 overlaps layer k's compute, and its autodiff transpose
    reduce-scatters the layer's gradient into the shard owner."""
    validate_scan_gather(param_shardings, scanned)
    sc_sh, rest_sh = _split_scanned(param_shardings, scanned)
    rest_gather = build_param_gather(mesh, rest_sh) if rest_sh else None

    def prelude(params):
        sc, rest = _split_scanned(params, scanned)
        out = dict(rest_gather(rest)) if rest_gather is not None else {}
        out.update(sc)
        return out

    hooks = {}
    for key in scanned:
        flat_sh, _ = jax.tree.flatten(sc_sh[key])
        # dim within one layer SLICE (the scan drops stacked dim 0)
        slice_dims = [None if fsdp_shard_dim(s) is None
                      else fsdp_shard_dim(s) - 1 for s in flat_sh]

        def hook(layer_slice, _dims=tuple(slice_dims)):
            flat, treedef = jax.tree.flatten(layer_slice)
            outs = []
            for leaf, d in zip(flat, _dims):
                if d is None:
                    outs.append(leaf)
                    continue
                wire = (leaf.astype(PARAM_GATHER_DTYPE)
                        if jnp.issubdtype(leaf.dtype, jnp.floating)
                        else leaf)
                g = jax.lax.all_gather(wire, mesh_lib.FSDP_AXIS, axis=d,
                                       tiled=True)
                outs.append(g.astype(leaf.dtype))
            return treedef.unflatten(outs)

        hooks[key] = hook
    return prelude, hooks


def build_scan_local_grads(mesh: Mesh, value_and_grad_fn, batch_spec,
                           param_shardings, scanned: Tuple[str, ...],
                           hooks, extra_metrics=None):
    """Per-replica gradients for the scan-gather step.  The params
    argument is the PRELUDE's mixed tree: non-scanned leaves replicated
    (gathered), scanned stacks still fsdp-sharded — they enter the
    shard_map body as local shards and the model's in-scan hook (bound
    via ``layer_gather_scope`` for exactly this trace) gathers each
    layer on use.

    Gradient layouts out of the body:

    - scanned fsdp-sharded leaves: the all-gather's transpose already
      reduce-scattered the bf16 cotangent into the shard owner (summed
      over the fsdp group, per layer, inside the overlapped backward);
      the body folds the pure-data replicas with an exact fp32 psum and
      divides by n — the finished MEAN gradient in the param layout,
      nothing left for the quantized exchange to move.
    - scanned replicated leaves (stacked norm scales): exact psum-mean
      over all axes (they are tiny).
    - everything else: raw local grads stacked ``[n, ...]`` — the
      caller routes them through the usual quantized exchange."""
    axes = dp_axis_names(mesh)
    data_axes = tuple(a for a in axes if a != mesh_lib.FSDP_AXIS)
    n = dp_size(mesh)
    flat_sh, sh_treedef = jax.tree.flatten(param_shardings)
    kind_tree = {
        k: jax.tree.map(
            (lambda s: "scan_rs" if fsdp_shard_dim(s) is not None
             else "scan_repl") if k in scanned else (lambda s: "rest"),
            sub)
        for k, sub in param_shardings.items()}
    kinds = jax.tree.leaves(kind_tree)  # congruent tree -> same order
    param_in_specs = sh_treedef.unflatten(
        [s.spec if k != "rest" else P()
         for s, k in zip(flat_sh, kinds)])
    grad_out_specs = sh_treedef.unflatten(
        [s.spec if k == "scan_rs" else
         (P() if k == "scan_repl" else P(mesh_lib.BATCH_AXES))
         for s, k in zip(flat_sh, kinds)])

    def body(params, batch, rng):
        # per-replica stochasticity: same fold_in as build_local_grads
        rng = jax.random.fold_in(rng, jax.lax.axis_index(axes))
        with layer_gather_scope(hooks):
            (_, metrics), grads = value_and_grad_fn(params, batch, rng)
        metrics = jax.tree.map(lambda m: jax.lax.pmean(m, axes), metrics)
        if extra_metrics is not None:
            metrics.update(extra_metrics(grads))
        flat_g, g_treedef = jax.tree.flatten(grads)
        outs = []
        for g, kind in zip(flat_g, kinds):
            if kind == "scan_rs":
                # already fsdp-reduced into the owner by the gather's
                # transpose; fold cross-data replicas, finish the mean
                dt = g.dtype
                g = g.astype(jnp.float32)
                if data_axes:
                    g = jax.lax.psum(g, data_axes)
                outs.append((g / n).astype(dt))
            elif kind == "scan_repl":
                outs.append(jax.lax.psum(g.astype(jnp.float32), axes) / n)
            else:
                outs.append(g[None])
        return metrics, g_treedef.unflatten(outs)

    # graftlint: ok(retrace) — builder runs once at compile; reused
    return jax.shard_map(
        body, mesh=mesh, in_specs=(param_in_specs, batch_spec, P()),
        out_specs=(P(), grad_out_specs), check_vma=False)


# --------------------------------------------------------------------- #
# ZeRO-1 optimizer-state sharding                                        #
# --------------------------------------------------------------------- #
def zero1_param_sharding(mesh: Mesh, leaf) -> NamedSharding:
    """ZeRO-1 layout for one param-shaped leaf; the layout decision is
    authored in ``plan.py`` (zero1_spec) — this wrapper survives for the
    exchange-side callers and tests."""
    from . import plan as plan_lib
    return plan_lib.zero1_sharding(mesh, leaf)


def zero1_opt_shardings(mesh: Mesh, tx, opt_state, params):
    """Sharding tree for the optimizer state under ZeRO-1: every
    param-shaped moment gets ``zero1_param_sharding``; counts and other
    non-param leaves replicate.  Returns None (with a warning) when the
    optimizer state cannot be mapped (exotic wrappers) — the caller keeps
    the replicated layout, which is correct, just not memory-sharded."""
    import optax
    from ..utils.logging import log
    repl = NamedSharding(mesh, P())
    try:
        return optax.tree_map_params(
            tx, lambda _s, p: zero1_param_sharding(mesh, p),
            opt_state, params, transform_non_params=lambda _s: repl)
    except Exception as e:
        log.warning(
            "shard_optimizer_state: could not map the optimizer state "
            "(%s: %s); optimizer moments stay REPLICATED (correct, but "
            "no ZeRO-1 memory saving)", type(e).__name__, e)
        return None


def zero1_update_shardings(mesh: Mesh, params):
    """Sharding constraints for the update tree (param-shaped): partition
    the update computation the same way the moments are stored."""
    return jax.tree.map(lambda p: zero1_param_sharding(mesh, p), params)


# --------------------------------------------------------------------- #
# Wire accounting                                                        #
# --------------------------------------------------------------------- #
def wire_bytes_per_step(params, n: int, cfg: ExchangeConfig,
                        param_shardings=None, gather_mode: str = "tree",
                        scanned: Tuple[str, ...] = ()) -> Dict[str, Any]:
    """Analytic per-device bytes-on-wire for one gradient exchange.

    Ring-allreduce fp32 moves ``2*(N-1)/N * 4 * size`` bytes per device;
    the two-phase compressed exchange moves ``2*(N-1)/N`` of the
    compressed payload (int8: 1 byte/elem + 4/block scale overhead; bf16:
    2 bytes/elem); sub-threshold leaves pay the fp32 rate in both columns.
    ``compressed_ratio`` is the reduction over compressed leaves only —
    the honest headline for "large leaves".

    ``param_shardings`` switches a leaf into the FSDP
    reduce-scatter/all-gather regime when it is fsdp-sharded: per step it
    moves one quantized reduce-scatter of the gradient over fsdp
    (``(nf-1)/nf`` of the compressed payload), one fp32 psum of the
    1/nf reduced shard over the pure-data axes, and one bf16 all-gather
    of the updated param (``(nf-1)/nf * 2 * size``).  The fp32 baseline
    column stays the ring allreduce — what replicated DP (or fp32 FSDP,
    whose RS+AG totals the same bytes) would move — so the ratio is the
    honest apples-to-apples headline.

    ``gather_mode="scan"`` + ``scanned``: overlap accounting.  Bytes a
    probe should price as latency are only the ones that SERIALIZE with
    compute — ``exposed_bytes_per_step``.  Leaves of the scanned
    subtrees move per layer inside the scan: a bf16 forward all-gather
    overlapped with the previous layer's matmuls, the bf16 cotangent
    reduce-scatter the gather's autodiff transpose emits inside the
    (equally overlapped) backward, and the fp32 cross-data psum of the
    1/nf reduced shard — all ``hidden_bytes_per_step``.  Everything
    else (the up-front gather of non-scanned leaves, the post-backward
    quantized exchange — and the WHOLE tree-mode exchange) is exposed.
    ``exchange_bytes_per_step`` remains exposed + hidden."""
    if gather_mode not in GATHER_MODES:
        raise ValueError(f"gather_mode must be one of {GATHER_MODES}, "
                         f"got {gather_mode!r}")
    if n <= 1:
        factor = 0.0
    else:
        factor = 2.0 * (n - 1) / n
    flat, treedef = jax.tree.flatten(params)
    in_scan = [False] * len(flat)
    if gather_mode == "scan" and scanned and isinstance(params, dict):
        in_scan = jax.tree.leaves({
            k: jax.tree.map(lambda _: k in scanned, sub)
            for k, sub in params.items()})
    if param_shardings is not None:
        flat_sh = treedef.flatten_up_to(param_shardings)
        mesh = flat_sh[0].mesh
        nf = mesh_lib.mesh_axis_size(mesh, mesh_lib.FSDP_AXIS)
        nd = max(1, n // max(nf, 1))
    else:
        flat_sh = [None] * len(flat)
        nf = nd = 1
    rs_factor = 0.0 if nf <= 1 else (nf - 1) / nf
    data_factor = 0.0 if nd <= 1 else 2.0 * (nd - 1) / nd
    base_total = comp_base = 0.0
    exch_total = comp_exch = 0.0
    rs_bytes = ag_bytes = hidden = 0.0
    n_comp = n_fp32 = n_rs = 0
    for leaf, sh, sc in zip(flat, flat_sh, in_scan):
        size = int(np.prod(leaf.shape))
        fp32 = factor * 4.0 * size
        base_total += fp32
        regime = ("allreduce" if sh is None
                  else _leaf_regime(leaf, sh, cfg))
        if regime == "rs" and sc:
            # in-scan leaf: bf16 fwd all-gather + bf16 cotangent RS (the
            # gather's transpose) — exact (no quantized exchange) and
            # overlapped with the scan's compute.  The fp32 cross-data
            # psum of the 1/nf shard runs in the shard_map body AFTER
            # the backward (build_scan_local_grads), not inside the
            # scan, so it serializes like the exposed exchange and is
            # priced as exposed.
            n_rs += 1
            data_psum = data_factor * 4.0 * (size / nf)
            rs = rs_factor * 2.0 * size + data_psum
            ag = rs_factor * 2.0 * size
            rs_bytes += rs
            ag_bytes += ag
            exch_total += rs + ag
            hidden += rs + ag - data_psum
            comp_base += fp32
            comp_exch += rs + ag
        elif regime == "rs":
            n_rs += 1
            _, chunk_pad = _fsdp_chunk_elems(leaf.shape,
                                             fsdp_shard_dim(sh), nf, cfg)
            payload = (chunk_pad * nf * 2.0 if cfg.mode == "bf16" else
                       chunk_pad * nf * 1.0 + (chunk_pad * nf //
                                               cfg.block) * 4.0)
            rs = rs_factor * payload + data_factor * 4.0 * (size / nf)
            ag = rs_factor * 2.0 * size
            rs_bytes += rs
            ag_bytes += ag
            exch_total += rs + ag
            comp_base += fp32
            comp_exch += rs + ag
        elif regime == "allreduce" and compressible(leaf, cfg):
            n_comp += 1
            if cfg.mode == "int8":
                padded = size + ((-size) % (max(n, 1) * cfg.block))
                payload = padded * 1.0 + (padded // cfg.block) * 4.0
            else:  # bf16
                payload = size * 2.0
            b = factor * payload
            exch_total += b
            comp_base += fp32
            comp_exch += b
        else:
            n_fp32 += 1
            exch_total += fp32
    ratio = base_total / exch_total if exch_total else 1.0
    comp_ratio = comp_base / comp_exch if comp_exch else 1.0
    report = {
        "mode": cfg.mode, "block": cfg.block, "devices": n,
        "regime": ("reduce_scatter_all_gather" if n_rs
                   else "allreduce"),
        "gather_mode": gather_mode if n_rs else None,
        "baseline_fp32_bytes_per_step": int(base_total),
        "exchange_bytes_per_step": int(exch_total),
        # derived from the two truncated fields so the documented
        # exposed + hidden == exchange invariant holds exactly
        "exposed_bytes_per_step": int(exch_total) - int(hidden),
        "hidden_bytes_per_step": int(hidden),
        "compression_ratio": round(ratio, 3),
        "compressed_ratio": round(comp_ratio, 3),
        "compressed_leaves": n_comp + n_rs, "fp32_leaves": n_fp32,
    }
    if n_rs:
        report.update({
            "fsdp": nf, "reduce_scattered_leaves": n_rs,
            "grad_reduce_scatter_bytes_per_step": int(rs_bytes),
            "param_allgather_bytes_per_step": int(ag_bytes),
        })
    return report
