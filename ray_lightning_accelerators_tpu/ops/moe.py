"""Mixture-of-Experts feed-forward with expert parallelism.

No reference analog (the reference implements only data parallelism,
SURVEY.md §2.4); this exists because the TPU framework treats expert
parallelism (the ``expert`` mesh axis, parallel/mesh.py:39) as first-class.

Design is GShard/Switch-style and deliberately XLA-shaped:

- routing, dispatch and combine are **static-shape einsums** over a
  ``[batch, seq, experts, capacity]`` dispatch tensor — no gather/scatter
  with data-dependent shapes, so the whole layer tiles onto the MXU and
  jit-compiles once;
- expert weights carry a leading ``experts`` dim annotated with the
  ``expert`` logical axis; when the mesh has ``expert > 1`` XLA partitions
  the expert einsums and inserts the all-to-alls itself;
- tokens over capacity are *dropped* (their combine weight is zero) and
  ride the residual connection — the standard Switch behavior;
- the load-balancing auxiliary loss (Switch eq. 4) is returned alongside
  the output so the caller can add ``aux_weight * aux`` to the task loss.

Two expert paths live here (ROADMAP Queue 3 names the pair as a debt).
The capacity path above is the one the ``expert`` mesh axis partitions.
The dropless path (``dropless_moe``, below) is told which experts it
holds: sigmoid scores with a selection bias, the (token, choice) pairs
sorted by expert, one grouped matrix product per projection over the rows
routed to the held experts, nothing dropped at any imbalance.  It runs
without an exchange: what absent experts would add is left out.  The
sorted pairs are walked in windows (``window_rows``: the held experts'
nominal share of the pairs and half as much again), so the buffers of
the sorted side follow the share held, not ``tokens x top_k``; window 0
holds every routed row unless the load is far over nominal, the loop
runs only as many windows as the rows reach, and ``stats["rounds"]``
(logged as ``moe_rounds``) says how many ran.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from ..analysis import knobs
from ..parallel import mesh as mesh_lib
from ..parallel import sharding as sharding_lib


def expert_capacity(seq_len: int, num_experts: int, top_k: int,
                    capacity_factor: float) -> int:
    """Per-expert token budget; static (derived from trace-time shapes)."""
    cap = int(math.ceil(seq_len * top_k * capacity_factor / num_experts))
    return max(cap, 1)


def top_k_routing(router_logits: jax.Array, top_k: int, capacity: int
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Compute dispatch/combine tensors from router logits.

    Args:
      router_logits: ``[b, s, e]`` float32 logits.
      top_k: experts per token.
      capacity: per-expert slot count ``c``.

    Returns:
      ``dispatch`` ``[b, s, e, c]`` 0/1 — token (b,s) occupies slot c of
      expert e; ``combine`` ``[b, s, e, c]`` — dispatch weighted by the
      renormalized gate probability; ``aux`` scalar load-balance loss.
    """
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    b, s, e = probs.shape
    if top_k > e:
        raise ValueError(f"moe top_k={top_k} exceeds num_experts={e}; a "
                         "token cannot route to more experts than exist")

    masks = []      # one-hot chosen expert per routing round
    gates = []      # chosen-expert probability per round
    remaining = probs
    for _ in range(top_k):
        idx = jnp.argmax(remaining, axis=-1)
        m = jax.nn.one_hot(idx, e, dtype=probs.dtype)          # [b, s, e]
        masks.append(m)
        gates.append(jnp.sum(probs * m, axis=-1))              # [b, s]
        remaining = remaining * (1.0 - m)

    # Switch aux loss uses the first-choice assignment fractions.
    frac_tokens = jnp.mean(masks[0], axis=(0, 1))              # [e]
    frac_probs = jnp.mean(probs, axis=(0, 1))                  # [e]
    aux = e * jnp.sum(frac_tokens * frac_probs)

    # top_k > 1: renormalize so combine weights sum to 1 per token.
    # top_k == 1 keeps the raw gate probability (Switch Transformer): a
    # renormalized single gate is constant ~1 and would starve the router
    # of task-loss gradient.
    if top_k > 1:
        gate_sum = sum(gates) + 1e-9
        gates = [g / gate_sum for g in gates]

    # Assign capacity slots: earlier routing rounds and earlier sequence
    # positions win; a cumulative per-expert count carries across rounds.
    counts = jnp.zeros((b, e), probs.dtype)
    dispatch = jnp.zeros((b, s, e, capacity), probs.dtype)
    combine = jnp.zeros((b, s, e, capacity), probs.dtype)
    for m, g in zip(masks, gates):
        pos = counts[:, None, :] + jnp.cumsum(m, axis=1) - m   # [b, s, e]
        keep = m * (pos < capacity)
        counts = counts + jnp.sum(keep, axis=1)
        slots = jax.nn.one_hot(pos.astype(jnp.int32), capacity,
                               dtype=probs.dtype) * keep[..., None]
        dispatch = dispatch + slots
        combine = combine + g[..., None, None] * slots
    return dispatch, combine, aux


def moe_mlp(x: jax.Array, params: Dict[str, jax.Array], *,
            top_k: int = 2, capacity_factor: float = 1.25,
            compute_dtype=jnp.bfloat16,
            mesh: Optional[jax.sharding.Mesh] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """MoE FFN block: route -> dispatch -> per-expert GELU MLP -> combine.

    Args:
      x: ``[b, s, d]`` activations.
      params: ``router`` ``[d, e]``, ``wi`` ``[e, d, f]``, ``wo`` ``[e, f, d]``.

    Returns: ``(y [b, s, d], aux_loss scalar)``.
    """
    e = params["wi"].shape[0]
    s = x.shape[1]
    cap = expert_capacity(s, e, top_k, capacity_factor)
    dt = compute_dtype

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router"].astype(jnp.float32))
    dispatch, combine, aux = top_k_routing(logits, top_k, cap)

    def constrain(arr, *spec):
        if mesh is None:
            return arr
        return sharding_lib.shard_constraint(
            # constraint shim over mesh-axis names from parallel/mesh.py
            # constants; expert layout consolidation belongs to the
            # graftlint: ok(sharding-inventory) — ShardingPlan refactor
            arr, mesh, jax.sharding.PartitionSpec(*spec))

    # [b, e, c, d] — expert dim explicit so XLA partitions the expert matmuls
    # over the `expert` axis (the dispatch einsum lowers to an all-to-all).
    xe = jnp.einsum("bsec,bsd->becd", dispatch.astype(dt), x.astype(dt))
    xe = constrain(xe, mesh_lib.BATCH_AXES, mesh_lib.EXPERT_AXIS, None, None)
    h = jax.nn.gelu(jnp.einsum("becd,edf->becf", xe, params["wi"].astype(dt)))
    h = constrain(h, mesh_lib.BATCH_AXES, mesh_lib.EXPERT_AXIS, None,
                  mesh_lib.TENSOR_AXIS)
    ye = jnp.einsum("becf,efd->becd", h, params["wo"].astype(dt))
    ye = constrain(ye, mesh_lib.BATCH_AXES, mesh_lib.EXPERT_AXIS, None, None)
    y = jnp.einsum("becd,bsec->bsd", ye, combine.astype(dt))
    return y.astype(x.dtype), aux


def init_moe_params(rng, d_model: int, d_ff: int, num_experts: int
                    ) -> Dict[str, jax.Array]:
    kr, ki, ko = jax.random.split(rng, 3)
    return {
        "router": jax.random.normal(kr, (d_model, num_experts), jnp.float32)
                  * (d_model ** -0.5),
        "wi": jax.random.normal(ki, (num_experts, d_model, d_ff), jnp.float32)
              * (d_model ** -0.5),
        "wo": jax.random.normal(ko, (num_experts, d_ff, d_model), jnp.float32)
              * (d_ff ** -0.5),
    }


def moe_logical_axes() -> Dict[str, Any]:
    """Logical axis names for an `init_moe_params` tree (one layer)."""
    return {
        "router": (None, None),               # tiny; replicate
        "wi": ("expert", "embed", "mlp"),
        "wo": ("expert", "mlp", "embed"),
    }


# --------------------------------------------------------------------- #
# Dropless path: the experts held here                                   #
# --------------------------------------------------------------------- #
def sigmoid_routing(x: jax.Array, router: jax.Array, bias: jax.Array, *,
                    top_k: int, norm_topk: bool, scale: float,
                    eps: float = 1e-6) -> Tuple[jax.Array, jax.Array]:
    """``s = sigmoid(x W_g)`` in float32 at full matmul precision (a
    near-tied choice flips on a rounded score and costs a whole expert);
    ``sel = top_k(s + bias)``, the bias a buffer that only steers the
    selection; weights ``s[sel]``, normalised over ALL chosen experts
    (held or not; ``eps`` beside their sum is the published model's) and
    scaled.  x: [t, d] -> (ids [t, k] int32, weights [t, k] float32)."""
    s = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, ids = jax.lax.top_k(s + jax.lax.stop_gradient(
        bias.astype(jnp.float32)), top_k)
    # the chosen scores by comparison, not by gather: the transpose of a
    # gather is a scatter-add, and a select is cheaper on this chip
    chosen = ids[..., None] == jnp.arange(s.shape[-1])
    w = jnp.sum(jnp.where(chosen, s[..., None, :], 0.0), axis=-1)
    if norm_topk:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
    return ids.astype(jnp.int32), w * scale


def _rows_to_tokens(rows, pairs, top_k: int, t: int):
    """``out[token] = sum of rows[i] over the window's sorted positions i
    of that token's pairs``: a scatter-add of the WINDOW's rows in
    float32 (a row no group owns arrives as zeros and adds nothing)."""
    out = jnp.zeros((t, rows.shape[1]), jnp.float32)
    return out.at[pairs // top_k].add(rows.astype(jnp.float32)).astype(
        rows.dtype)


def _rows_of_pairs(ys, pos, top_k: int):
    """[top_k, t, d]: the row at every pair's (clipped) position, the
    choices on the LEADING axis (``pos`` is choice-major).  Each choice
    is a slab of ``t`` whole rows, so the split of the gathered
    ``[top_k * t, d]`` moves nothing and the sum over the choices reads
    the buffer as it was written; with the choices second-minor
    (``[t, top_k, d]``: 4 rows against an 8- or 16-row tile) the chip
    copies it into another tiling first."""
    return ys[pos].reshape(top_k, -1, ys.shape[1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _dispatch_rows(x, pairs, pos, live, top_k, scatter):
    """Row ``pairs[i] // top_k`` of ``x`` for the sorted pairs of one
    window.  Every (token, choice) pair sits at one sorted position, so
    the transpose is a gather by that position (``pos`` ``[top_k * t]``,
    choice-major and clipped to the window; ``live`` where the window
    holds the pair) and a sum over the choices, not a scatter-add --
    unless ``scatter`` says the window is a small part of the pairs
    (``_token_side_by_scatter``): then the window's rows are added into
    their tokens."""
    return x[pairs // top_k]


def _dispatch_fwd(x, pairs, pos, live, top_k, scatter):
    return x[pairs // top_k], (pairs, pos, live, x.shape[0])


def _dispatch_bwd(top_k, scatter, res, g):
    pairs, pos, live, t = res
    if scatter:
        return _rows_to_tokens(g, pairs, top_k, t), None, None, None
    g = jnp.where(live.reshape(top_k, t, 1), _rows_of_pairs(g, pos, top_k),
                  0)
    return g.sum(0).astype(g.dtype), None, None, None


_dispatch_rows.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _combine_rows(ys, w, pairs, pos, scatter):
    """``y[i] = sum_k w[k, i] * ys[pos[k * t + i]]``: the window's
    output rows back at their ``t`` tokens (``w`` ``[top_k, t]`` and
    ``pos`` ``[top_k * t]``, choice-major; ``w`` is zero where the
    window does not hold the pair), gathered by the token side, or with
    ``scatter`` weighted on the sorted side and added into their tokens.
    Transposed on the sorted side, which keeps token-major pair ids:
    ``d ys[i] = w[pair i] * g[token of pair i]``, a gather of the
    window's rows from ``[t, d]``."""
    if scatter:
        return _rows_to_tokens(w.T.reshape(-1)[pairs][:, None] * ys, pairs,
                               *w.shape)
    return jnp.einsum("kt,ktd->td", w, _rows_of_pairs(ys, pos, len(w)))


def _combine_fwd(ys, w, pairs, pos, scatter):
    return _combine_rows(ys, w, pairs, pos, scatter), (ys, w, pairs, pos)


def _combine_bwd(scatter, res, g):
    ys, w, pairs, pos = res
    g_rows = g[pairs // w.shape[0]]
    d_ys = w.T.reshape(-1)[pairs][:, None] * g_rows
    if scatter:     # each pair's product on the sorted side, then home
        d_w = jnp.zeros((w.size,), w.dtype).at[pairs].add(jnp.sum(
            g_rows.astype(w.dtype) * ys, -1)).reshape(w.shape[::-1]).T
    else:
        d_w = jnp.einsum("td,ktd->kt", g, _rows_of_pairs(ys, pos, len(w)))
    return d_ys, d_w, None, None


_combine_rows.defvjp(_combine_fwd, _combine_bwd)

# m, k, n tile of the grouped-matmul kernel; the row tile is also what a
# group's ragged end is padded to, so `moe_load_max_over_mean` explains
# the padding.  Chosen on the v5e (PERF.md section 6, PR 27).
GMM_ROW_TILE = 512
# A window of the sorted pairs holds this many times the held experts'
# nominal share of them (PERF.md section 6, PR 28: the largest
# rows-a-layer seen on the chip against it).
WINDOW_SPARE = 1.5
# checkpoint name of what ``dropless_moe``'s windows read of the routing
MOE_PLAN = "moe_plan"


def _tile(extent: int, limit: int = 1024) -> int:
    """The largest multiple of 128 that divides ``extent``, at most
    ``limit``."""
    for cand in range(limit, 0, -128):
        if extent % cand == 0:
            return cand
    return extent


def _use_gmm_kernel(lhs: jax.Array, rhs: jax.Array, mesh) -> bool:
    """The Pallas grouped matmul of ``jax.experimental.pallas.ops.tpu
    .megablox`` (its grid over row tiles is as long as the group sizes
    need, so work follows the routed rows) on a TPU where nothing is
    partitioned; ``jax.lax.ragged_dot`` elsewhere (a Mosaic kernel
    carries no GSPMD rule).  Both follow the rows on the v5e; at the
    benchmark cell's shapes the kernel runs at 151-165 TFLOP/s and
    ``ragged_dot`` at 94 (PERF.md section 6, PR 27)."""
    if knobs.get_flag("RLA_TPU_DISABLE_PALLAS") \
            or jax.default_backend() != "tpu":
        return False
    if not (mesh is None or mesh.size == 1 or sharding_lib.manual_axes()):
        return False
    return (lhs.shape[0] % GMM_ROW_TILE == 0 and lhs.shape[1] % 128 == 0
            and rhs.shape[2] % 128 == 0)


def _megablox():
    # the package's ``gmm`` attribute is its differentiable function (one
    # tiling for all three products); the kernels are in the module
    import importlib
    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _gmm_call(lhs, rhs, group_sizes, transpose_rhs: bool = False):
    """One grouped matmul through the kernel, its k and n tiles taken
    from THIS product's extents (the backward's products swap them)."""
    backend = _megablox()
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    with jax.named_scope("kernel/moe_gmm"):
        return backend.gmm(
            lhs, rhs, group_sizes, lhs.dtype,
            (GMM_ROW_TILE, _tile(lhs.shape[1]), _tile(n)),
            transpose_rhs=transpose_rhs)


@jax.custom_vjp
def _gmm(lhs, rhs, group_sizes):
    return _gmm_call(lhs, rhs, group_sizes)


def _gmm_fwd(lhs, rhs, group_sizes):
    return _gmm_call(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _gmm_bwd(res, g):
    """d lhs = g @ rhs^T by group; d rhs[group] = lhs[rows]^T @ g[rows]
    (the kernel masks the rows of a tile that belong to another group by
    select, so rows no group owns are never read as numbers)."""
    backend = _megablox()
    lhs, rhs, group_sizes = res
    d_lhs = _gmm_call(g, rhs, group_sizes, transpose_rhs=True)
    with jax.named_scope("kernel/moe_gmm"):
        d_rhs = backend.tgmm(
            lhs.swapaxes(0, 1), g, group_sizes, rhs.dtype,
            (GMM_ROW_TILE, _tile(lhs.shape[1]), _tile(g.shape[1])))
    return d_lhs, d_rhs, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   mesh=None) -> jax.Array:
    """``lhs[rows of group g] @ rhs[g]``: lhs [m, k] with its rows sorted
    by group, rhs [g, k, n], group_sizes [g] int32 summing to at most m.
    Rows past the last group are unspecified (the kernel never visits
    them): the caller masks them."""
    if _use_gmm_kernel(lhs, rhs, mesh):
        return _gmm(lhs, rhs, group_sizes)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes)


def _token_side_by_scatter(m: int, pairs: int) -> bool:
    """Whether a window of ``m`` sorted rows meets the tokens by a
    scatter-add of its own rows rather than by a gather of every pair's
    row: under a quarter of the pairs.  The gather reads ``pairs`` rows
    whatever the window holds; with 8 of 512 experts held and 22 choices
    a token a window is 4,608 of an 8k sequence's 180,224 pairs and two
    such gathers a layer are 369 MB each."""
    return 4 * m <= pairs


def window_rows(pairs: int, n_held: int, num_experts: int) -> int:
    """Rows of one window of the sorted (token, choice) pairs: the held
    experts' nominal share of them with ``WINDOW_SPARE`` over it, in
    whole row tiles; all the pairs where that is no fewer."""
    spare = math.ceil(WINDOW_SPARE * pairs * n_held / num_experts)
    return min(pairs, -(-spare // GMM_ROW_TILE) * GMM_ROW_TILE)


def _window(diff, ints, start, *, m: int, top_k: int, mesh):
    """The held experts over sorted positions ``[start, start + m)``
    and what those rows add to every token: ``(y [t, d], pairs whose
    output row came back)``.

    diff: ``rows`` [t, d], ``w`` [top_k, t] (zero for a pair whose
    expert is absent), then the experts' weights, all in the compute
    dtype but ``w``: ``w1`` / ``w3`` / ``w2`` are SwiGLU's three
    products, ``w1`` / ``w2`` alone ReLU squared's two.  ints:
    ``order`` (the pairs, ``token * top_k + choice``, by sorted
    position, at least ``start + m`` long), ``inverse`` [top_k * t] (the
    sorted position of every pair, pair ``(token, choice)`` at ``choice
    * t + token``), ``first`` / ``last`` (each held expert's interval of
    sorted positions), ``n_rows`` (pairs whose expert is held: they sort
    first).  What is indexed by token (``w``, ``inverse`` and the
    ``pos`` / ``live`` made of it) is choice-major; the sorted side
    keeps the token-major pair ids."""
    rows, w, w_up, *w_gate, w_down = diff
    order, inverse, first, last, n_rows = ints
    t, dt = rows.shape[0], rows.dtype
    scatter = _token_side_by_scatter(m, t * top_k)
    with jax.named_scope("gpt/moe_dispatch"):
        group_sizes = (jnp.clip(last, start, start + m)
                       - jnp.clip(first, start, start + m))
        pairs = jax.lax.dynamic_slice_in_dim(order, start, m)
        pos = inverse - start
        live = (pos >= 0) & (pos < jnp.minimum(m, n_rows - start))
        pos = jnp.clip(pos, 0, m - 1)
        xs = _dispatch_rows(rows, pairs, pos, live, top_k, scatter)
        pad = -m % GMM_ROW_TILE
        if pad:
            xs = jnp.pad(xs, ((0, pad), (0, 0)))
        # rows past the window's last group (the absent experts' pairs,
        # the pad) are never visited by the grouped matmuls: whatever
        # the buffer held is masked on the way out, and by the mask's
        # transpose on the way back, so neither direction ever reads it
        n_computed = jnp.sum(group_sizes)
        computed = (jnp.arange(m + pad) < n_computed)[:, None]

        def masked(a):
            return jnp.where(computed, a, 0)

        xs = masked(xs)
    with jax.named_scope("gpt/moe_experts"):
        act = masked(grouped_matmul(xs, w_up, group_sizes, mesh))
        if w_gate:      # SwiGLU: w_up is the gate's w1, w_gate the w3
            act = jax.nn.silu(act) * masked(
                grouped_matmul(xs, w_gate[0], group_sizes, mesh))
        else:
            act = jnp.square(jax.nn.relu(act))
        ys = masked(grouped_matmul(act.astype(dt), w_down, group_sizes,
                                   mesh))[:m]
    with jax.named_scope("gpt/moe_combine"):
        y = _combine_rows(
            ys, jnp.where(live.reshape(top_k, t), w, 0.0).astype(dt),
            pairs, pos, scatter)
        # live: from the selection's count; computed: from the groups
        # the matmuls were given.  They agree unless a window is cut.
        # ``computed`` is a prefix of the window, so what it holds at a
        # pair's position is a comparison, not a gather of every pair
        came_back = jnp.sum(live & (pos < n_computed), dtype=jnp.int32)
    return y, came_back


def _rounds(n_rows, m: int):
    """Windows of ``m`` sorted rows that hold a routed row; one at
    least."""
    return jnp.maximum(1, -(-n_rows // m))


def _sum_windows(one, m, n_rows, like):
    """``one(start)`` summed over the windows of ``m`` sorted rows that
    hold a routed row (one at least), from zeros shaped ``like``."""
    def body(i, acc):
        return jax.tree.map(jnp.add, acc, one(i * m))

    return jax.lax.fori_loop(0, _rounds(n_rows, m), body,
                             jax.tree.map(jnp.zeros_like, like))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _walk_windows(window, m, diff, ints):
    """``window(diff, ints, start)`` summed over the windows that hold a
    routed row: ``(y, came_back, rounds)``.  Window 0 holds them all
    unless the load is far over nominal; the loop runs as many as the
    count asks for, so nothing is ever dropped, and a window that does
    not run writes no buffer in either direction: the backward is the
    same loop over each window's transpose, its forward computed again
    there, one window's residuals alive at a time."""
    y, came_back = _sum_windows(
        lambda start: window(diff, ints, start), m, ints[-1],
        jax.eval_shape(window, diff, ints, 0))
    return y, came_back, _rounds(ints[-1], m)


def _walk_fwd(window, m, diff, ints):
    return _walk_windows(window, m, diff, ints), (diff, ints)


def _walk_bwd(window, m, res, g):
    diff, ints = res

    def transposed(start):
        _, vjp, _ = jax.vjp(lambda *d: window(d, ints, start), *diff,
                            has_aux=True)
        return vjp(g[0])

    return _sum_windows(transposed, m, ints[-1], diff), None


_walk_windows.defvjp(_walk_fwd, _walk_bwd)


def dropless_moe(x: jax.Array, params: Dict[str, jax.Array], *,
                 top_k: int, held: Sequence[int], num_experts: int,
                 norm_topk: bool = True, scale: float = 1.0,
                 compute_dtype=jnp.bfloat16,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 router_x: Optional[jax.Array] = None,
                 norm_eps: float = 1e-6
                 ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Sparse block over the experts held here.

    Args:
      x: ``[b, s, d]`` activations, of whatever width the experts read.
      params: ``router`` ``[router width, num_experts]``, ``expert_bias``
        ``[num_experts]``, ``w1`` ``[len(held), d, f]``, ``w2``
        ``[len(held), f, d]`` (slot i is expert ``held[i]``), and
        SwiGLU's ``w3`` like ``w1``: with it an expert is
        ``(silu(x w1) * (x w3)) w2``, without it ``relu(x w1)^2 w2``.
      router_x: the rows the router reads where they are not the
        experts' (a latent expert layer routes on the full-width rows);
        None = ``x``.

    The router keeps its full width and its ``top_k`` whatever is held;
    ``out = sum_{e in sel, e held} w_e * expert_e(x)``.  Where only some
    of the experts are held the weights carry no gradient: the router's
    gradient is a sum over every chip's share (the exchange brings it),
    and one share alone teaches the router to prefer the experts that
    answer here, so the load would grow step by step.

    The (token, choice) pairs are sorted by expert, the held experts'
    first, and walked in windows of ``window_rows`` sorted rows: every
    buffer on the sorted side has a window's rows, not ``tokens x
    top_k``.  With every expert held (or tiny shapes) one window is all
    the pairs and the program has no loop.

    Returns ``(y, stats)`` with ``rows_routed`` (pairs whose expert is
    held, counted from the selection), ``rows_computed`` (pairs whose
    output row came back from the grouped matmuls: the pairs a window
    holds whose position is under the count of rows its groups own, by
    comparison (the mask of the visited rows is a prefix of the window),
    on the same clipped positions that gather their result, summed over
    the windows that ran; fewer than ``rows_routed`` if a window or a
    group were ever cut short),
    ``rounds`` (the windows that ran: 1 unless the load was over
    ``WINDOW_SPARE`` times nominal), ``load_max_over_mean`` (the fullest
    held expert's rows over the mean) and ``selected`` ``[b, s,
    top_k]``, the chosen expert ids.
    """
    if mesh is not None and mesh_lib.mesh_axis_size(
            mesh, mesh_lib.EXPERT_AXIS) > 1:
        raise NotImplementedError(
            "dropless_moe (TransformerConfig.moe_router='sigmoid') runs "
            "the experts held on one chip and has no exchange over the "
            "`expert` mesh axis yet; set expert=1")
    b, s, d = x.shape
    t, n_held, dt = b * s, len(held), compute_dtype
    rows = x.reshape(t, d)
    with jax.named_scope("gpt/moe_route"):
        ids, w = sigmoid_routing(
            rows if router_x is None else router_x.reshape(t, -1),
            params["router"], params["expert_bias"], top_k=top_k,
            norm_topk=norm_topk, scale=scale, eps=norm_eps)
        # slot of each chosen expert among the held ones; n_held = absent
        slot_of = np.full((num_experts,), n_held, np.int32)
        slot_of[list(held)] = np.arange(n_held)
        slots = jnp.asarray(slot_of)[ids].reshape(-1)          # [t * k]
        is_held = slots < n_held
        w = jnp.where(is_held.reshape(t, top_k), w, 0.0)
        if n_held < num_experts:
            w = jax.lax.stop_gradient(w)
        w = w.T             # the token side is choice-major: ``_window``
    m = window_rows(t * top_k, n_held, num_experts)
    n_windows = -(-t * top_k // m)
    with jax.named_scope("gpt/moe_dispatch"):
        order = jnp.argsort(slots, stable=True).astype(jnp.int32)
        # choice-major, and flat: one transposition a layer, and no
        # axis of ``top_k`` for a tiling to pad
        inverse = jnp.argsort(order).astype(jnp.int32).reshape(
            t, top_k).T.reshape(-1)
        group_sizes = jnp.sum(
            slots[:, None] == jnp.arange(n_held, dtype=jnp.int32),
            axis=0, dtype=jnp.int32)
        last = jnp.cumsum(group_sizes)
        n_rows = last[-1]
        # the last window may reach past the pairs: it reads pair 0
        # there, on rows no group owns
        order = jnp.pad(order, (0, n_windows * m - t * top_k))
    window = functools.partial(_window, m=m, top_k=top_k, mesh=mesh)
    # the plan: all the windows read of the routing, forward and
    # backward.  Named, so a layer's remat keeps it (a few integers a
    # pair; models/transformer.py ``_KEPT_UNDER_REMAT``) and its
    # backward runs neither the router nor the sorts again.  A name is
    # the identity on the primal and on the tangent: where ``w`` carries
    # a gradient it still does
    w, *ints = (checkpoint_name(a, MOE_PLAN) for a in (
        w, order, inverse, last - group_sizes, last, n_rows))
    diff = (rows.astype(dt), w) + tuple(
        params[name].astype(dt) for name in ("w1", "w3", "w2")
        if name in params)
    ints = tuple(ints)
    if n_windows == 1:
        y, came_back = window(diff, ints, 0)
        rounds = jnp.ones((), jnp.int32)
    else:
        y, came_back, rounds = _walk_windows(window, m, diff, ints)
    stats = {
        "rows_routed": jnp.sum(is_held).astype(jnp.float32),
        "rows_computed": came_back.astype(jnp.float32),
        "rounds": rounds.astype(jnp.float32),
        "load_max_over_mean": jnp.max(group_sizes) * n_held
        / jnp.maximum(n_rows, 1).astype(jnp.float32),
        "selected": ids.reshape(b, s, top_k),
    }
    return y.reshape(b, s, d).astype(x.dtype), stats


def init_dropless_params(rng, d_model: int, d_ff: int, num_experts: int,
                         n_held: int, *, gated: bool = True,
                         router_dim: Optional[int] = None
                         ) -> Dict[str, jax.Array]:
    """One layer's router (reading rows of ``router_dim``; None =
    ``d_model``), selection bias and held experts (SwiGLU, or without
    ``w3`` when not ``gated``).  The bias is
    a buffer (no gradient; ``GPT.configure_optimizers`` gives it no
    optimizer state); it is drawn small so that it moves some selections
    (the published models start it at zero and steer it by a balancing
    rule the config gives no rate for)."""
    kr, kb, k1, k3, k2 = jax.random.split(rng, 5)

    def dense(key, shape, fan_in):
        return jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5

    router_dim = router_dim or d_model
    out = {
        "router": dense(kr, (router_dim, num_experts), router_dim),
        "expert_bias": 0.01 * jax.random.normal(kb, (num_experts,),
                                                jnp.float32),
        "w1": dense(k1, (n_held, d_model, d_ff), d_model),
        "w3": dense(k3, (n_held, d_model, d_ff), d_model),
        "w2": dense(k2, (n_held, d_ff, d_model), d_ff),
    }
    if not gated:
        del out["w3"]
    return out


def dropless_logical_axes(gated: bool = True) -> Dict[str, Any]:
    """Logical axis names for an `init_dropless_params` tree (one layer)."""
    axes = {
        "router": (None, None),
        "expert_bias": (None,),
        "w1": ("expert", "embed", "mlp"),
        "w3": ("expert", "embed", "mlp"),
        "w2": ("expert", "mlp", "embed"),
    }
    if not gated:
        del axes["w3"]
    return axes


# --------------------------------------------------------------------- #
# Latent expert layer: the dropless path inside a narrower space         #
# --------------------------------------------------------------------- #
def latent_moe(x: jax.Array, params: Dict[str, Any], *, top_k: int,
               held: Sequence[int], num_experts: int, norm_topk: bool,
               scale: float, compute_dtype=jnp.bfloat16, mesh=None
               ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """``(r W_fc2 + relu(x U_s)^2 V_s, stats)`` with ``r = sum_{e in sel,
    e held} w_e * relu(l U_e)^2 V_e`` and ``l = x W_fc1``: the routed
    experts live in a latent of width ``W_fc1.shape[1]`` (down- and
    up-projection under scope ``gpt/moe_latent``), the router and the
    shared expert (``gpt/moe_shared``) read the full-width rows.  The
    routed part is ``dropless_moe`` itself, ungated ReLU squared, on the
    latent rows; ``stats`` are its counters.  Router, projections and
    shared expert are the same on every chip that shares the layer: a
    sum over the shares counts them once."""
    dt = compute_dtype
    with jax.named_scope("gpt/moe_latent"):
        latent = jnp.einsum("bsd,dl->bsl", x, params["fc1"].astype(dt))
    routed, stats = dropless_moe(
        latent, params["experts"], top_k=top_k, held=held,
        num_experts=num_experts, norm_topk=norm_topk, scale=scale,
        compute_dtype=dt, mesh=mesh, router_x=x, norm_eps=1e-20)
    with jax.named_scope("gpt/moe_latent"):
        y = jnp.einsum("bsl,ld->bsd", routed, params["fc2"].astype(dt))
    with jax.named_scope("gpt/moe_shared"):
        up = jnp.einsum("bsd,df->bsf", x, params["shared_w1"].astype(dt))
        y = y + jnp.einsum("bsf,fd->bsd", jnp.square(jax.nn.relu(up)),
                           params["shared_w2"].astype(dt))
    return y, stats


def init_latent_moe_params(rng, d_model: int, latent: int, d_ff: int,
                           shared_d_ff: int, num_experts: int, n_held: int
                           ) -> Dict[str, Any]:
    """One latent expert layer: ``experts`` is ``init_dropless_params``
    at the latent width, ungated, its router reading ``d_model`` rows."""
    k_e, k1, k2, k3, k4 = jax.random.split(rng, 5)

    def dense(key, shape, fan_in):
        return jax.random.normal(key, shape, jnp.float32) * fan_in ** -0.5

    return {"experts": init_dropless_params(
                k_e, latent, d_ff, num_experts, n_held, gated=False,
                router_dim=d_model),
            "fc1": dense(k1, (d_model, latent), d_model),
            "fc2": dense(k2, (latent, d_model), latent),
            "shared_w1": dense(k3, (d_model, shared_d_ff), d_model),
            "shared_w2": dense(k4, (shared_d_ff, d_model), shared_d_ff)}


def latent_moe_logical_axes() -> Dict[str, Any]:
    """Logical axis names for an ``init_latent_moe_params`` tree."""
    return {"experts": dropless_logical_axes(gated=False),
            "fc1": ("embed", None), "fc2": (None, "embed"),
            "shared_w1": ("embed", "mlp"), "shared_w2": ("mlp", "embed")}
