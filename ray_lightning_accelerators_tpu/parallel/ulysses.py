"""Ulysses sequence parallelism: all-to-all head/sequence re-sharding.

No reference analog (the reference is DP-only, SURVEY.md §2.4/§5.7); this is
the second first-class long-context strategy beside ring attention
(parallel/ring_attention.py).  Design follows DeepSpeed-Ulysses: the
activations arrive sequence-sharded; one ``all_to_all`` re-shards them so
each device holds ALL sequence positions for a slice of the heads, local
(flash) attention runs unchanged on its full sequence, and a second
``all_to_all`` restores sequence sharding.

Trade-off vs ring attention, in ICI terms: Ulysses moves each Q/K/V/O
element exactly once (4 all-to-alls of the per-device activation volume,
bandwidth independent of the device count along the axis) and keeps the
attention kernel completely local — so the Pallas flash kernel applies
as-is.  Ring attention instead streams K/V around the ring (P-1 neighbor
hops overlapped with compute) and never needs the head dim to be divisible
by the axis size.  Ulysses requires ``heads % axis_size == 0``; prefer ring
when heads are few or the sequence axis is large.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
from jax.sharding import Mesh, PartitionSpec as P

from . import mesh as mesh_lib
from ..ops.attention import flash_attention


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      axis_name: str, causal: bool = True,
                      scale: Optional[float] = None) -> jax.Array:
    """Per-device body (call under shard_map).

    q, k, v: [batch, heads, seq_local, head_dim] — this device's sequence
    shard with the FULL head dim.  Returns local-shard output, exactly equal
    to full attention over the global sequence.
    """
    axis_size = jax.lax.psum(1, axis_name)
    h = q.shape[1]
    if h % axis_size != 0:
        raise ValueError(
            f"ulysses needs heads ({h}) divisible by the sequence axis "
            f"size ({axis_size}); use ring attention instead")

    def seq_to_heads(x):
        # [b, h, s/P, d] -> [b, h/P, s, d]: scatter head groups, gather seq
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    def heads_to_seq(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    out = flash_attention(qh, kh, vh, causal, scale)
    return heads_to_seq(out)


def ulysses_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array,
                              mesh: Mesh, causal: bool = True,
                              scale: Optional[float] = None) -> jax.Array:
    """Mesh-level entry: q,k,v are [batch, heads, seq, head_dim] GLOBAL
    arrays (possibly traced under jit); sequence dim sharded over the
    `sequence` axis, heads over `tensor`, batch over (data, fsdp)."""
    seq_size = mesh_lib.mesh_axis_size(mesh, mesh_lib.SEQUENCE_AXIS)
    if seq_size == 1:
        return flash_attention(q, k, v, causal, scale)
    if q.shape[2] % seq_size != 0:
        raise ValueError(
            f"ulysses needs the sequence length ({q.shape[2]}) divisible by "
            f"the sequence axis size ({seq_size}); pad the sequence or "
            f"change the mesh")
    if q.shape[1] % seq_size != 0:
        raise ValueError(
            f"ulysses needs heads ({q.shape[1]}) divisible by the sequence "
            f"axis size ({seq_size}); use ring attention instead")
    spec = P(mesh_lib.BATCH_AXES, mesh_lib.TENSOR_AXIS,
             mesh_lib.SEQUENCE_AXIS, None)
    body = functools.partial(ulysses_attention,
                             axis_name=mesh_lib.SEQUENCE_AXIS,
                             causal=causal, scale=scale)
    return jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False)(q, k, v)
