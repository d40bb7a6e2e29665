"""Ring attention: exact attention over sequence shards via ICI neighbor
exchange (context parallelism).

No reference analog (the reference is DP-only, SURVEY.md §2.4/§5.7); this is
a first-class requirement of the TPU framework.  Design follows the blockwise
/ ring formulation (Liu et al.): each device holds a sequence shard of
Q, K, V; K/V chunks rotate around the ring with ``jax.lax.ppermute`` while
each device folds every visiting chunk into an **online-softmax accumulator**
(running max m, denominator l, weighted accumulator acc) -- the same math as
the flash kernel, lifted to the mesh level.  Communication is
nearest-neighbor only, so it rides ICI links, overlapping with the local
block compute under XLA's scheduler.

Usage is via shard_map over a mesh with a `sequence` axis; see
``ring_attention_sharded``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from . import mesh as mesh_lib

_NEG_INF = -1e30


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str, causal: bool = True,
                   scale: Optional[float] = None) -> jax.Array:
    """Per-device body (call under shard_map).

    q, k, v: [batch, heads, seq_local, head_dim] -- this device's sequence
    shard.  Returns the attention output for the local queries, exactly equal
    to full attention over the global sequence.
    """
    b, h, s_local, d = q.shape
    scale_v = scale if scale is not None else d ** -0.5
    axis_size = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    # each step ships our current KV chunk to the next rank, so after step i
    # we hold the chunk originally owned by (my_idx - i) % P
    perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]

    q32 = q.astype(jnp.float32) * scale_v
    rows = jax.lax.broadcasted_iota(jnp.int32, (s_local, s_local), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (s_local, s_local), 1)

    def step(i, carry):
        k_cur, v_cur, m, l, acc = carry
        src = (my_idx - i) % axis_size  # owner rank of the visiting chunk
        s = jnp.einsum("bhqd,bhkd->bhqk", q32,
                       k_cur.astype(jnp.float32),
                       preferred_element_type=jnp.float32)
        if causal:
            # global causal mask: query my_idx*s_local+r vs key src*s_local+c
            mask = (my_idx * s_local + rows) >= (src * s_local + cols)
            s = jnp.where(mask[None, None], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        pv = jnp.einsum("bhqk,bhkd->bhqd", p,
                        v_cur.astype(jnp.float32),
                        preferred_element_type=jnp.float32)
        acc_new = acc * alpha + pv
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, m_new, l_new, acc_new)

    m0 = jnp.full((b, h, s_local, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_local, 1), jnp.float32)
    acc0 = jnp.zeros((b, h, s_local, d), jnp.float32)
    _, _, m, l, acc = jax.lax.fori_loop(
        0, axis_size, step, (k, v, m0, l0, acc0))
    l = jnp.where(l == 0.0, 1.0, l)  # rows with no visible keys
    return (acc / l).astype(q.dtype)


def ring_attention_sharded(q: jax.Array, k: jax.Array, v: jax.Array,
                           mesh: Mesh, causal: bool = True,
                           scale: Optional[float] = None) -> jax.Array:
    """Mesh-level entry: q,k,v are [batch, heads, seq, head_dim] GLOBAL
    arrays (possibly traced under jit); sequence dim is sharded over the
    `sequence` axis, heads over `tensor`, batch over (data, fsdp)."""
    seq_size = mesh_lib.mesh_axis_size(mesh, mesh_lib.SEQUENCE_AXIS)
    if seq_size == 1:
        from ..ops.attention import flash_attention
        return flash_attention(q, k, v, causal, scale)
    if q.shape[2] % seq_size != 0:
        raise ValueError(
            f"ring attention needs the sequence length ({q.shape[2]}) "
            f"divisible by the sequence axis size ({seq_size}); pad the "
            f"sequence or change the mesh")
    spec = P(mesh_lib.BATCH_AXES, mesh_lib.TENSOR_AXIS,
             mesh_lib.SEQUENCE_AXIS, None)
    body = functools.partial(ring_attention,
                             axis_name=mesh_lib.SEQUENCE_AXIS,
                             causal=causal, scale=scale)
    return jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False)(q, k, v)
