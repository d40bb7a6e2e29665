"""Pipeline parallelism: GPipe-style microbatch schedule over the `pipeline`
mesh axis.

No reference analog (DP-only reference, SURVEY.md §2.4).  Design:

- layer-stacked parameters (leading dim L) are sharded over the `pipeline`
  axis, so each stage holds L/S contiguous layers in HBM;
- inside a **partial-manual shard_map** (only the pipeline axis is manual;
  data/fsdp/tensor/sequence shardings keep propagating through the stage
  body), the classic GPipe schedule runs M + S - 1 ticks: stage 0 feeds a
  fresh microbatch each tick, activations hop stage->stage+1 via
  ``jax.lax.ppermute`` (nearest-neighbor ICI traffic), the last stage
  accumulates outputs;
- the schedule is a ``lax.scan`` over ticks, so reverse-mode AD derives the
  symmetric backward pipeline automatically (ppermute transposes to the
  reverse shift);
- bubble ticks compute on zero inputs and their outputs are masked out --
  the standard GPipe utilization cost of (S-1)/(M+S-1).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from . import mesh as mesh_lib


def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   stage_params: Any, x: jax.Array, mesh: Mesh,
                   num_microbatches: int) -> jax.Array:
    """Run x through all pipeline stages.

    stage_fn(params_local, x_mb): applies ONE stage's layer stack to a
    microbatch.  stage_params: pytree whose leaves have leading dim
    L (sharded over `pipeline`).  x: [B, ...] batch (B % num_microbatches
    == 0).  Returns [B, ...] outputs, replicated over the pipeline axis.
    """
    S = mesh_lib.mesh_axis_size(mesh, mesh_lib.PIPELINE_AXIS)
    if S == 1:
        return stage_fn(stage_params, x)
    M = num_microbatches
    b = x.shape[0]
    if b % M != 0:
        raise ValueError(f"batch {b} % microbatches {M} != 0")
    n_layers = jax.tree.leaves(stage_params)[0].shape[0]
    if n_layers % S != 0:
        raise ValueError(
            f"layer count {n_layers} not divisible by {S} pipeline stages; "
            f"choose n_layers as a multiple of the pipeline axis size")

    axis = mesh_lib.PIPELINE_AXIS
    fwd_perm = [(i, i + 1) for i in range(S - 1)]  # no wraparound

    def body(params_local, x_full):
        stage = jax.lax.axis_index(axis)
        x_mb = x_full.reshape(M, b // M, *x_full.shape[1:])

        def tick(carry, t):
            cur, outbuf = carry
            mb_idx = jnp.clip(t, 0, M - 1)
            fresh = jax.lax.dynamic_index_in_dim(x_mb, mb_idx, 0,
                                                 keepdims=False)
            inp = jnp.where(stage == 0, fresh, cur)
            y = stage_fn(params_local, inp)
            out_idx = t - (S - 1)
            updated = jax.lax.dynamic_update_index_in_dim(
                outbuf, y, jnp.clip(out_idx, 0, M - 1), 0)
            valid = jnp.logical_and(out_idx >= 0, stage == S - 1)
            outbuf = jnp.where(valid, updated, outbuf)
            cur_next = jax.lax.ppermute(y, axis, fwd_perm)
            return (cur_next, outbuf), None

        cur0 = jnp.zeros_like(x_mb[0])
        out0 = jnp.zeros_like(x_mb)
        (cur, outbuf), _ = jax.lax.scan(tick, (cur0, out0),
                                        jnp.arange(M + S - 1))
        # broadcast the last stage's buffer to every stage
        outbuf = jax.lax.psum(
            jnp.where(stage == S - 1, outbuf, jnp.zeros_like(outbuf)), axis)
        return outbuf.reshape(b, *x_full.shape[1:])

    return jax.shard_map(
        body, mesh=mesh, axis_names={axis},
        in_specs=(P(axis), P()),   # stage dim manual; rest auto-propagated
        out_specs=P(), check_vma=False)(stage_params, x)
