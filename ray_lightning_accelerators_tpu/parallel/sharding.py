"""Sharding rules: map logical parameter axes to mesh axes.

The reference relied on torch DDP to replicate parameters and allreduce
gradients (reference: ray_lightning/ray_ddp.py:222-237 supplies the process
group; the DDP wrapper does the rest).  The TPU-native design instead
annotates every parameter with *logical axis names* and translates them to
mesh ``PartitionSpec``s through a rules table -- the pattern used by
flax.linen.with_partitioning / MaxText-style codebases.  XLA then emits the
all-gathers / reduce-scatters that DDP's bucketed allreduce performed.
"""

from __future__ import annotations

from typing import Any, Mapping, Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import mesh as mesh_lib

# Default logical->mesh rules.  A logical axis may map to a mesh axis name, a
# tuple of mesh axes, or None (replicated).
DEFAULT_RULES: Tuple[Tuple[str, Any], ...] = (
    ("batch", mesh_lib.BATCH_AXES),
    ("seq", mesh_lib.SEQUENCE_AXIS),
    ("embed", mesh_lib.FSDP_AXIS),          # ZeRO-3: shard params on fsdp axis
    ("mlp", mesh_lib.TENSOR_AXIS),          # megatron column/row split
    ("heads", mesh_lib.TENSOR_AXIS),
    ("kv", None),
    ("vocab", mesh_lib.TENSOR_AXIS),
    ("expert", mesh_lib.EXPERT_AXIS),
    ("stage", mesh_lib.PIPELINE_AXIS),
    ("layers", mesh_lib.PIPELINE_AXIS),   # stacked layer dim = stage dim
    (None, None),
)


def logical_to_spec(logical_axes: Sequence[Optional[str]],
                    rules: Sequence[Tuple[str, Any]] = DEFAULT_RULES) -> P:
    """Translate a tuple of logical axis names into a PartitionSpec."""
    table = dict(rules)
    entries = []
    used = set()
    for name in logical_axes:
        target = table.get(name)
        # A mesh axis can shard at most one dim of a given array; later dims
        # that would reuse it fall back to replication.
        key = tuple(target) if isinstance(target, (list, tuple)) else target
        if key is not None and key in used:
            target = None
        if key is not None:
            used.add(key)
        entries.append(tuple(target) if isinstance(target, list) else target)
    return P(*entries)


def tree_logical_to_shardings(mesh: Mesh, logical_tree: Any,
                              rules: Sequence[Tuple[str, Any]] = DEFAULT_RULES):
    """Map a pytree of logical-axis tuples to a pytree of NamedShardings."""

    def one(axes):
        if axes is None:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, logical_to_spec(axes, rules))

    return jax.tree.map(one, logical_tree,
                        is_leaf=lambda x: x is None or isinstance(x, tuple))


def validate_shardings(params, shardings, mesh: Mesh) -> None:
    """Raise a readable error when a param dim doesn't divide by its mesh
    axes (the raw device_put failure is impenetrable).

    Structure-checked: tree_map_with_path raises on any params/shardings
    tree mismatch instead of silently misaligning leaves.
    """

    def check(path, leaf, sh):
        spec = getattr(sh, "spec", None)
        if spec is None or not hasattr(leaf, "shape"):
            return leaf
        for d, axes in enumerate(spec):
            if axes is None:
                continue
            axes = axes if isinstance(axes, tuple) else (axes,)
            size = 1
            for a in axes:
                size *= mesh.shape.get(a, 1)
            if leaf.shape[d] % size != 0:
                name = jax.tree_util.keystr(path)
                raise ValueError(
                    f"parameter {name} dim {d} (size {leaf.shape[d]}) is not "
                    f"divisible by mesh axes {axes} (size {size}); adjust the "
                    f"model dims or the mesh (e.g. n_layers % pipeline == 0)")
        return leaf

    jax.tree_util.tree_map_with_path(check, params, shardings)


def manual_axes() -> frozenset:
    """Mesh axes that are Manual in the current trace: non-empty only
    inside a ``jax.shard_map`` body."""
    ambient = jax.sharding.get_abstract_mesh()
    return frozenset() if ambient.empty else frozenset(ambient.manual_axes)


def _drop_axes(spec: P, axes: frozenset) -> P:
    """``spec`` with every mention of ``axes`` removed."""
    entries = []
    for entry in spec:
        if isinstance(entry, tuple):
            kept = tuple(a for a in entry if a not in axes)
            entries.append(kept or None)
        else:
            entries.append(None if entry in axes else entry)
    return P(*entries)


def shard_constraint(x, mesh: Mesh, spec: P):
    """with_sharding_constraint that adapts to the tracing context.

    Three call contexts exist in this package:

    - plain jit (every forward, eval, decode): a concrete NamedSharding.
    - a FULL-manual ``shard_map`` body (the compressed gradient exchange,
      parallel/collectives.py ``build_local_grads`` /
      ``build_scan_local_grads``, runs the whole model in one): values
      are device-local and a sharding constraint has no meaning --
      identity.
    - a PARTIAL-manual body (parallel/pipeline.py: only ``pipeline`` is
      manual): the remaining Auto axes still propagate, and jax accepts
      only a bare PartitionSpec over them -- the Manual axes are
      dropped from the spec.
    """
    ambient = jax.sharding.get_abstract_mesh()
    if ambient.empty or not ambient.manual_axes:
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    if ambient.are_all_axes_manual:
        return x
    return jax.lax.with_sharding_constraint(
        x, _drop_axes(spec, frozenset(ambient.manual_axes)))


def shard_local(fn, mesh: Optional[Mesh], in_axes, out_axes):
    """``fn`` applied to each device's shard of its operands, whose
    layouts are given as tuples of LOGICAL axis names (one per operand /
    for the single result), translated by ``logical_to_spec``.

    Pallas (Mosaic) kernels carry no GSPMD partitioning rule: under
    plain jit on a multi-device mesh the TPU compiler refuses them
    ("Mosaic kernels cannot be automatically partitioned. Please wrap
    the call in a shard_map").  Ops that may dispatch to such a kernel
    (ops/attention.py, ops/norms.py) are row- or head-local, so models
    run them through this wrapper with the layout their operands already
    have; replicated operands (norm scales) get their cotangents summed
    over the unmentioned axes by shard_map's transpose.

    Returns ``fn`` itself where nothing is partitioned: no mesh, a
    one-device mesh, or a trace already inside a shard_map body (the
    compressed gradient exchange and ring/ulysses attention run the op
    on device-local values)."""
    if mesh is None or mesh.size == 1 or manual_axes():
        return fn
    return jax.shard_map(
        fn, mesh=mesh,
        in_specs=tuple(logical_to_spec(axes) for axes in in_axes),
        out_specs=logical_to_spec(out_axes), check_vma=False)


def replicate_tree(tree, mesh: Mesh):
    sharding = NamedSharding(mesh, P())
    return jax.device_put(tree, sharding)


def infer_fsdp_shardings(params, mesh: Mesh, min_size: int = 2 ** 12,
                         on_fallback=None):
    """Heuristic FSDP sharding for models without logical annotations.

    Shards the largest dimension of each sufficiently-large leaf over the
    `fsdp` axis when divisible; small leaves stay replicated.  This gives
    user models ZeRO-style memory scaling with zero annotation work.

    ``on_fallback(name, leaf)`` fires for each leaf LARGE enough to want
    sharding whose dims all fail to divide the fsdp axis — the silent
    loss-of-FSDP-savings case observability wants surfaced (the
    accelerator routes it into a telemetry event + profiler counter).

    The per-leaf layout choice is authored in ``plan.py``
    (fsdp_leaf_spec) — this function is the tree-mapping + fallback
    plumbing around it.
    """
    from . import plan as plan_lib

    def one(path, leaf):
        spec = plan_lib.fsdp_leaf_spec(mesh, leaf, min_size=min_size)
        if spec is None:  # wanted sharding, nothing divides
            if on_fallback is not None:
                on_fallback(jax.tree_util.keystr(path), leaf)
            spec = plan_lib.replicated_spec()
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params)
