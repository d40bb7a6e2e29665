"""A layer stack of several kinds (gated short convolution, GQA with
QK-norm, dense and sparse SwiGLU) against its plain float32 reference
(``models/reference_lfm2.py``), at a small size with every kind of layer
present, on the suite's CPU mesh: logits, loss and gradients; the share
test (what the chips that share a layer compute adds up to the uncut
layer); the dropless expert layer under an adversarial router; the
uniform block's parameter tree and its one ``lax.scan`` left as they
were; every walker of ``params["layers"]`` refusing a mixed stack by
name; the scanned epoch carrying the expert layer's counters."""

import hashlib
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_lightning_accelerators_tpu import (ArrayDataset, DataLoader,
                                            RayTPUAccelerator, Trainer)
from ray_lightning_accelerators_tpu.models import reference_lfm2 as ref
from ray_lightning_accelerators_tpu.models.transformer import (
    GPT, TransformerConfig, _rope)
from ray_lightning_accelerators_tpu.ops import moe
from ray_lightning_accelerators_tpu.ops.conv import gated_short_conv
from ray_lightning_accelerators_tpu.parallel import mesh as mesh_lib

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, SEQ = 256, 32
# published layers 1-5 of the pattern, at toy widths: conv + dense, then
# a whole period (attention + sparse, 3 x conv + sparse)
MODEL = dict(
    vocab_size=VOCAB, d_model=64, n_heads=4, n_kv_heads=2, d_ff=160,
    n_layers=5, max_seq_len=64, tie_embeddings=True, rope_theta=1e6,
    layer_types=["conv", "full_attention", "conv", "conv", "conv"],
    conv_kernel=3, moe_router="sigmoid", num_dense_layers=1, num_experts=8,
    moe_top_k=2, moe_d_ff=48, moe_experts_held=[0, 1, 2, 3],
    moe_norm_topk=True, moe_routed_scale=1.0, gated_mlp=True, qk_norm=True,
    rope_style="half", norm_eps=1e-5)


def _gpt(**over):
    model = GPT(TransformerConfig(**{**MODEL, **over}), lr=1e-3)
    model.compute_dtype = jnp.float32
    return model


@pytest.fixture(scope="module")
def system():
    model = _gpt()
    params = model.init_params(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, SEQ), 0, VOCAB)
    return model, params, tokens


@pytest.fixture(scope="module", params=["share", "all"])
def both_grads(request, system):
    """The system's and the reference's loss and gradients, on a chip
    that holds a share of the experts (4 of 8) and on one that holds
    them all."""
    model, params, tokens = system
    cfg = MODEL
    if request.param == "all":
        cfg = {**MODEL, "moe_experts_held": None}
        model = _gpt(moe_experts_held=None)
        params = model.init_params(jax.random.PRNGKey(0))
    (loss, _), grads = jax.jit(jax.value_and_grad(
        lambda p: model.training_step(p, tokens, None), has_aux=True))(
            params)
    ref_loss, ref_grads = ref.loss_and_grads(params, tokens, cfg)
    return float(loss), grads, float(ref_loss), ref_grads, request.param


# --------------------------------------------------------------------- #
# the stack as runs                                                      #
# --------------------------------------------------------------------- #
def test_runs_group_consecutive_layers_of_one_kind():
    assert TransformerConfig(**MODEL).layer_runs() == (
        ("conv", "dense", 1), ("attn", "sparse", 1), ("conv", "sparse", 3))
    published = ["conv", "conv", "full_attention"] + [
        "conv", "conv", "conv", "full_attention"] * 4 + [
        "conv", "conv", "full_attention", "conv", "conv"]
    whole = TransformerConfig(**{**MODEL, "n_layers": 24,
                                 "layer_types": published,
                                 "num_dense_layers": 2})
    runs = whole.layer_runs()
    assert len(runs) == 13 and sum(n for _, _, n in runs) == 24
    assert runs[0] == ("conv", "dense", 2)
    assert GPT(whole).scanned_param_subtrees() == tuple(
        f"layers_{i}" for i in range(13))


def test_logits_and_routing_match_the_reference(system):
    model, params, tokens = system
    logits, aux = jax.jit(
        lambda p, t: model.forward(p, t, return_aux=True))(params, tokens)
    ref_logits, routing = ref.forward(params, tokens, MODEL)
    assert float(jnp.max(jnp.abs(logits - ref_logits))) < 1e-4 * float(
        ref_logits.std())
    assert routing["selected"].shape == (4, 2, SEQ, 2)      # 4 sparse layers
    assert bool(jnp.all(jnp.sort(aux["moe_selected"], -1)
                        == routing["selected"]))
    assert float(aux["moe_rows_routed"]) == float(aux["moe_rows_computed"])
    assert float(routing["margin"].min()) >= 0.0


def test_loss_matches_the_reference(both_grads):
    loss, _, ref_loss, _, _ = both_grads
    assert abs(loss - ref_loss) < 1e-5 * abs(ref_loss)


@pytest.mark.parametrize("group", ref.GROUPS)
def test_gradients_match_the_reference(both_grads, group):
    _, grads, _, ref_grads, held = both_grads
    norms, ref_norms = (ref.grad_group_norms(grads),
                        ref.grad_group_norms(ref_grads))
    if group == "router" and held == "share":
        # the combine weights carry no gradient where only some experts
        # are held: one share's router gradient prefers the experts that
        # answer here (the whole gradient is a sum over the shares)
        assert norms[group] == ref_norms[group] == 0.0
        return
    assert ref_norms[group] > 0
    assert abs(norms[group] - ref_norms[group]) < 1e-4 * ref_norms[group]
    # leaf by leaf, for the leaves the group holds
    holds = {"router": ("router",), "experts": ("w1", "w3", "w2"),
             "conv": ("conv",), "attention": ("attn",),
             "dense_mlp": ("layers_0", "mlp"), "embedding": ("embed",)}
    for (path, g), (_, r) in zip(
            jax.tree_util.tree_flatten_with_path(grads)[0],
            jax.tree_util.tree_flatten_with_path(ref_grads)[0]):
        names = [getattr(k, "key", None) for k in path]
        if all(n in names for n in holds[group]):
            assert float(jnp.max(jnp.abs(g - r))) <= 1e-4 * float(
                jnp.max(jnp.abs(r)) + 1e-12), jax.tree_util.keystr(path)


def test_selection_bias_is_a_buffer(both_grads, system):
    """No gradient, and -- through ``configure_optimizers`` -- no update
    and no optimizer state."""
    model, params, _ = system
    _, grads, _, _, _ = both_grads
    bias = grads["layers_2"]["mlp"]["expert_bias"]
    assert float(jnp.max(jnp.abs(bias))) == 0.0
    tx = model.configure_optimizers()
    state = tx.init(params)
    n_params = len(jax.tree.leaves(params))
    n_bias = sum("expert_bias" in jax.tree_util.keystr(p) for p, _ in
                 jax.tree_util.tree_flatten_with_path(params)[0])
    moments = [x for x in jax.tree.leaves(state) if getattr(x, "ndim", 0)]
    assert n_bias == 2 and len(moments) == 2 * (n_params - n_bias)
    updates, _ = tx.update(jax.tree.map(jnp.ones_like, params), state,
                           params)
    assert float(jnp.max(jnp.abs(
        updates["layers_1"]["mlp"]["expert_bias"]))) == 0.0


# --------------------------------------------------------------------- #
# the expert layer that is told which experts it holds                   #
# --------------------------------------------------------------------- #
def _layer_params(num_experts=8, d=64, f=48, seed=3):
    return moe.init_dropless_params(jax.random.PRNGKey(seed), d, f,
                                    num_experts, num_experts)


def _slice_experts(p, held):
    idx = jnp.asarray(held)
    return {**p, **{w: p[w][idx] for w in ("w1", "w3", "w2")}}


@pytest.mark.parametrize("top_k", [2, 4])
def test_shares_add_up_to_the_uncut_reference_layer(top_k):
    """Four chips share the layer, two experts each: the partial results
    of the four shares add up to what the uncut reference gives for the
    whole layer (nothing is computed alike on every chip: no shared
    expert), and the shares' rows add up to top_k a token."""
    p = _layer_params()
    x = jax.random.normal(jax.random.PRNGKey(4), (2, SEQ, 64), jnp.float32)
    model = {**MODEL, "moe_top_k": top_k}
    whole, _, _ = ref.sparse_block(x, p, model, tuple(range(8)))
    total, rows = jnp.zeros_like(x), 0.0
    for chip in range(4):
        held = (2 * chip, 2 * chip + 1)
        y, stats = moe.dropless_moe(
            x, _slice_experts(p, held), top_k=top_k, held=held,
            num_experts=8, compute_dtype=jnp.float32)
        part, _, _ = ref.sparse_block(x, _slice_experts(p, held), model,
                                      held)
        assert float(jnp.max(jnp.abs(y - part))) < 1e-5
        total, rows = total + y, rows + float(stats["rows_computed"])
    assert float(jnp.max(jnp.abs(total - whole))) < 1e-5
    assert rows == 2 * SEQ * top_k


@pytest.mark.parametrize("favourite", [0, 3])
def test_dropless_under_an_adversarial_router(favourite):
    """Every token's first choice is ONE held expert: the capacity path
    would drop all but its budget; here the counter says every routed
    row was computed, and the result is the reference's."""
    held = (0, 1, 2, 3)
    p = _slice_experts(_layer_params(), held)
    # a bias can only steer the selection; make the router itself adore
    # one expert: a large score whatever the token
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, 64)))
    p["router"] = p["router"].at[:, favourite].set(1.0)
    y, stats = moe.dropless_moe(x, p, top_k=2, held=held, num_experts=8,
                                compute_dtype=jnp.float32)
    assert bool(jnp.all(jnp.any(stats["selected"] == favourite, -1)))
    assert float(stats["rows_routed"]) == float(stats["rows_computed"])
    assert float(stats["rows_computed"]) >= 2 * SEQ     # one row a token
    assert float(stats["load_max_over_mean"]) > 2.0
    want, _, _ = ref.sparse_block(x, p, {**MODEL, "moe_top_k": 2}, held)
    assert float(jnp.max(jnp.abs(y - want))) < 1e-4 * float(
        jnp.max(jnp.abs(want)))
    # the capacity path at the same load keeps its budget and no more
    cap = moe.expert_capacity(SEQ, 8, 2, 1.25)
    assert cap < SEQ


def _full_buffer_layer(x, p, top_k, held, num_experts):
    """The layer over ONE buffer of all ``tokens x top_k`` sorted pairs,
    plain ``jnp`` under ordinary AD: what the windows must add up to."""
    t, n_held = x.shape[0] * x.shape[1], len(held)
    rows = x.reshape(t, -1)
    ids, w = moe.sigmoid_routing(rows, p["router"], p["expert_bias"],
                                 top_k=top_k, norm_topk=True, scale=1.0)
    slot_of = np.full((num_experts,), n_held, np.int32)
    slot_of[list(held)] = np.arange(n_held)
    slots = jnp.asarray(slot_of)[ids].reshape(-1)
    w = jnp.where((slots < n_held).reshape(t, top_k), w, 0.0)
    if n_held < num_experts:
        w = jax.lax.stop_gradient(w)
    order = jnp.argsort(slots, stable=True)
    sizes = jnp.sum(slots[:, None] == jnp.arange(n_held), 0, jnp.int32)
    routed = (jnp.arange(t * top_k) < jnp.sum(sizes))[:, None]
    xs = rows[order // top_k]
    act = jnp.where(routed, jax.nn.silu(
        jax.lax.ragged_dot(xs, p["w1"], sizes))
        * jax.lax.ragged_dot(xs, p["w3"], sizes), 0.0)
    ys = jnp.where(routed, jax.lax.ragged_dot(act, p["w2"], sizes), 0.0)
    pairs = ys[jnp.argsort(order)].reshape(t, top_k, -1)
    return jnp.einsum("tk,tkd->td", w, pairs).reshape(x.shape)


# 1024 tokens, top-2, 2 of 16 experts held: 2,048 pairs in windows of 512
# rows (1.5 x the nominal 256, in whole row tiles), four windows at most
WINDOWED = dict(tokens=1024, top_k=2, num_experts=16, held=(9, 3))


def _steered(both: int, one: int):
    """Tokens and a router that send ``both`` tokens to the two held
    experts, ``one`` to one held and one absent expert and the rest to
    two absent ones: ``2 * both + one`` routed rows, whatever the
    noise."""
    t, (h0, h1) = WINDOWED["tokens"], WINDOWED["held"]
    kind = np.full((t,), 2)
    kind[:both], kind[both:both + one] = 0, 1
    kind = np.random.default_rng(8).permutation(kind)
    x = 0.3 * jax.random.normal(jax.random.PRNGKey(9), (1, t, 64))
    x = x.at[0, :, :3].set(8.0 * jnp.asarray(np.eye(3)[kind]))
    router = 0.05 * jax.random.normal(jax.random.PRNGKey(10), (64, 16))
    router = router.at[:3].set(0.0)
    for feature, experts in enumerate([(h0, h1), (h1, 5), (5, 12)]):
        router = router.at[feature, list(experts)].set(1.0)
    return x, router


def plan_of(selected, held, num_experts):
    """The layer's plan from the chosen ids, in numpy and token-major:
    ``inverse`` (the sorted position of pair ``token * top_k + choice``),
    each held expert's ``first`` / ``last`` sorted position, and the
    count of pairs whose expert is held."""
    n_held = len(held)
    slot_of = np.full((num_experts,), n_held, np.int64)
    slot_of[list(held)] = np.arange(n_held)
    slots = slot_of[np.asarray(selected).reshape(-1)]
    inverse = np.argsort(np.argsort(slots, kind="stable"))
    last = np.cumsum(np.bincount(slots, minlength=n_held + 1)[:n_held])
    first = np.concatenate([[0], last[:-1]])
    return inverse, first, last, int(last[-1])


def came_back_by_gather(inverse, first, last, n_rows, m):
    """``rows_computed`` as the layer counted it until PR 36: in every
    window of ``m`` sorted rows that holds a routed row (one at least),
    ``sum(live & computed[pos])``: the mask of the rows the grouped
    matmuls visit, GATHERED at every pair's clipped position."""
    total = 0
    for start in range(0, max(n_rows, 1), m):
        sizes = (np.clip(last, start, start + m)
                 - np.clip(first, start, start + m))
        computed = np.arange(m) < sizes.sum()
        pos = inverse - start
        live = (pos >= 0) & (pos < min(m, n_rows - start))
        total += int(np.sum(live & computed[np.clip(pos, 0, m - 1)]))
    return total


_LOADS = [("balanced", None, 1), ("window_full", (156, 200), 1),
          ("one_over", (156, 201), 2), ("adversarial", (1024, 0), 4)]


@pytest.mark.parametrize("case,steer,rounds,check", [
    *[(*load, "layer") for load in _LOADS],
    ("all_held", None, 1, "layer"), ("all_held_top_4", None, 1, "layer"),
    *[(*load, "counter") for load in _LOADS]])
def test_windows_add_up_to_the_full_buffer_layer(case, steer, rounds, check):
    """The expert layer walks its sorted pairs in windows; whatever the
    load, output and every gradient are those of the one-buffer
    formulation and of the reference's layer, every routed pair's row
    comes back, and ``rounds`` says how many windows ran: one at a
    balanced load and at exactly a window's rows, two at one row more,
    all four under a router that sends every pair to the held experts
    (the capacity path would drop 7 of 8 there).  With every expert
    held the window is the buffer and the program has no loop, and the
    combine weights carry the router's gradient (``d_w`` over the
    choice-major rows; at top-2 and at the cell's top-4).  ``counter``:
    ``rows_computed``, which the layer makes by comparing positions, is
    the count the mask of visited rows gives when gathered at them."""
    all_held = case.startswith("all_held")
    top_k = 4 if case == "all_held_top_4" else WINDOWED["top_k"]
    num_experts = WINDOWED["num_experts"]
    held = tuple(range(16)) if all_held else WINDOWED["held"]
    p = _slice_experts(_layer_params(num_experts), held)
    x = jax.random.normal(jax.random.PRNGKey(11), (1, 1024, 64))
    if steer is not None:
        x, p["router"] = _steered(*steer)
    m = moe.window_rows(1024 * top_k, len(held), num_experts)
    assert m == (1024 * top_k if all_held else 512)
    kw = dict(top_k=top_k, held=held, num_experts=num_experts)
    model = {**MODEL, "moe_top_k": top_k, "num_experts": num_experts}
    g = jax.random.normal(jax.random.PRNGKey(12), x.shape)

    def system(p_, x_):
        y, stats = moe.dropless_moe(x_, p_, compute_dtype=jnp.float32, **kw)
        return y, stats

    y, vjp, stats = jax.vjp(system, p, x, has_aux=True)
    assert float(stats["rows_routed"]) == float(stats["rows_computed"])
    if steer is not None:
        assert float(stats["rows_routed"]) == 2 * steer[0] + steer[1]
    assert float(stats["rounds"]) == rounds
    if check == "counter":
        assert float(stats["rows_computed"]) == came_back_by_gather(
            *plan_of(stats["selected"], held, num_experts), m)
        return
    loops = [w for w in ("while", "cond")
             if w + "[" in str(jax.make_jaxpr(system)(p, x))]
    assert loops == ([] if all_held else ["while"])
    for name, plain in [
            ("full_buffer", lambda p_, x_: _full_buffer_layer(x_, p_, **kw)),
            ("reference", lambda p_, x_: ref.sparse_block(
                x_, p_, model, held)[0])]:
        want, want_vjp = jax.vjp(plain, p, x)
        assert float(jnp.max(jnp.abs(y - want))) < 1e-5 * float(
            jnp.max(jnp.abs(want))), (name, "y")
        (dp, dx), (want_dp, want_dx) = vjp(g), want_vjp(g)
        names = ["w1", "w3", "w2"] + ["router"] * all_held
        for leaf, a, b in [("x", dx, want_dx)] + [
                (n, dp[n], want_dp[n]) for n in names]:
            assert float(jnp.max(jnp.abs(b))) > 0, (name, leaf)
            assert float(jnp.max(jnp.abs(a - b))) < 1e-4 * float(
                jnp.max(jnp.abs(b))), (name, leaf)


def test_rows_computed_falls_short_where_a_group_is_cut():
    """The counter has two witnesses: ``live`` from the selection's
    count, ``computed`` from the groups the matmuls are given.  A window
    handed a last group that stops three rows short counts three pairs
    fewer than were routed, by comparison as by gather."""
    top_k, num_experts, held = (WINDOWED[k] for k in (
        "top_k", "num_experts", "held"))
    p = _slice_experts(_layer_params(num_experts), held)
    x, p["router"] = _steered(100, 50)
    _, stats = moe.dropless_moe(x, p, compute_dtype=jnp.float32,
                                top_k=top_k, held=held,
                                num_experts=num_experts)
    inverse, first, last, n_rows = plan_of(stats["selected"], held,
                                           num_experts)
    assert n_rows == 250 == float(stats["rows_computed"])
    last[-1] -= 3
    t, m = x.shape[1], moe.window_rows(2048, len(held), num_experts)
    ints = tuple(jnp.asarray(a, jnp.int32) for a in (
        np.argsort(inverse), inverse.reshape(t, top_k).T.reshape(-1),
        first, last, n_rows))
    diff = (x.reshape(t, -1), jnp.full((top_k, t), 0.5), p["w1"], p["w3"],
            p["w2"])
    _, came_back = moe._window(diff, ints, 0, m=m, top_k=top_k, mesh=None)
    assert int(came_back) == n_rows - 3 == came_back_by_gather(
        inverse, first, last, n_rows, m)


@pytest.mark.parametrize("mutation", ["no_bias", "unnormalised_topk",
                                      "scaled"])
def test_routing_options_change_the_result(mutation):
    """The reference comparison can tell a missing bias, an unnormalised
    top-k and a routed scale apart: each moves the layer's output."""
    held = tuple(range(8))
    p = _layer_params()
    p["expert_bias"] = p["expert_bias"] * 30.0   # moves many selections
    x = jax.random.normal(jax.random.PRNGKey(6), (2, SEQ, 64), jnp.float32)
    kw = dict(top_k=2, held=held, num_experts=8, compute_dtype=jnp.float32)
    base, _ = moe.dropless_moe(x, p, **kw)
    want, _, _ = ref.sparse_block(x, p, {**MODEL, "moe_top_k": 2}, held)
    assert float(jnp.max(jnp.abs(base - want))) < 1e-5
    if mutation == "no_bias":
        other, _ = moe.dropless_moe(
            x, {**p, "expert_bias": jnp.zeros(8)}, **kw)
    elif mutation == "unnormalised_topk":
        other, _ = moe.dropless_moe(x, p, norm_topk=False, **kw)
    else:
        other, _ = moe.dropless_moe(x, p, scale=2.0, **kw)
    assert float(jnp.max(jnp.abs(other - want))) > 1e-2


def test_expert_axis_is_refused_by_name():
    from jax.sharding import Mesh
    devices = np.asarray(jax.devices()[:2]).reshape(1, 1, 2, 1, 1, 1)
    names = (mesh_lib.DATA_AXIS, mesh_lib.FSDP_AXIS, mesh_lib.EXPERT_AXIS,
             mesh_lib.TENSOR_AXIS, mesh_lib.SEQUENCE_AXIS,
             mesh_lib.PIPELINE_AXIS)
    mesh = Mesh(devices, names)
    x = jnp.zeros((2, SEQ, 64))
    with pytest.raises(NotImplementedError, match="expert"):
        moe.dropless_moe(x, _layer_params(), top_k=2, held=tuple(range(8)),
                         num_experts=8, mesh=mesh)


# --------------------------------------------------------------------- #
# the short convolution                                                  #
# --------------------------------------------------------------------- #
def _conv_params(d=64, taps=3):
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    return {"w_in": jax.random.normal(ks[0], (d, 3 * d)) * d ** -0.5,
            "conv_w": jax.random.normal(ks[1], (d, taps)),
            "w_out": jax.random.normal(ks[2], (d, d)) * d ** -0.5}


@pytest.mark.parametrize("taps", [3, 4])
def test_short_conv_matches_the_reference_and_is_causal(taps):
    p = _conv_params(taps=taps)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, SEQ, 64))
    y = gated_short_conv(x, p["w_in"], p["conv_w"], p["w_out"])
    assert float(jnp.max(jnp.abs(y - ref.conv_operator(x, p)))) < 1e-5
    # a change at position t leaves every earlier output as it was
    t = 11
    y2 = gated_short_conv(x.at[:, t].add(1.0), p["w_in"], p["conv_w"],
                          p["w_out"])
    assert float(jnp.max(jnp.abs((y2 - y)[:, :t]))) == 0.0
    assert float(jnp.max(jnp.abs((y2 - y)[:, t]))) > 0.0
    # the last tap sits on the current position: t + taps - 1 is the
    # last position the change reaches
    assert float(jnp.max(jnp.abs((y2 - y)[:, t + taps - 1]))) > 0.0
    assert float(jnp.max(jnp.abs((y2 - y)[:, t + taps:]))) == 0.0


def test_short_conv_backward_holds_at_8k_tokens():
    p = _conv_params(d=16)
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 8192, 16))

    def loss(fn, x_, p_):
        return jnp.sum(fn(x_, p_) ** 2)

    got = jax.grad(lambda x_, p_: loss(lambda a, b: gated_short_conv(
        a, b["w_in"], b["conv_w"], b["w_out"]), x_, p_), (0, 1))(x, p)
    want = jax.grad(lambda x_, p_: loss(ref.conv_operator, x_, p_),
                    (0, 1))(x, p)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert float(jnp.max(jnp.abs(g - w))) <= 1e-4 * float(
            jnp.max(jnp.abs(w)))


# --------------------------------------------------------------------- #
# a config that sets none of the new fields is today's block             #
# --------------------------------------------------------------------- #
def _count_scans(jaxpr) -> list:
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "scan":
            found.append(eqn.params["length"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _count_scans(sub)
    return found


TODAY = {
    "dense": (dict(), {
        "embed": (VOCAB, 64), "ln_f": (64,),
        "layers/attn/wq": (3, 64, 4, 16), "layers/attn/wk": (3, 64, 4, 16),
        "layers/attn/wv": (3, 64, 4, 16), "layers/attn/wo": (3, 4, 16, 64),
        "layers/mlp/wi": (3, 64, 128), "layers/mlp/wo": (3, 128, 64),
        "layers/ln1": (3, 64), "layers/ln2": (3, 64)}),
    "gqa_capacity_moe": (dict(n_kv_heads=2, num_experts=4), {
        "embed": (VOCAB, 64), "ln_f": (64,),
        "layers/attn/wq": (3, 64, 4, 16), "layers/attn/wk": (3, 64, 2, 16),
        "layers/attn/wv": (3, 64, 2, 16), "layers/attn/wo": (3, 4, 16, 64),
        "layers/mlp/router": (3, 64, 4), "layers/mlp/wi": (3, 4, 64, 128),
        "layers/mlp/wo": (3, 4, 128, 64),
        "layers/ln1": (3, 64), "layers/ln2": (3, 64)}),
}


@pytest.mark.parametrize("case", sorted(TODAY))
def test_config_without_the_new_fields_builds_todays_tree(case):
    over, pinned = TODAY[case]
    cfg = TransformerConfig(vocab_size=VOCAB, d_model=64, n_heads=4,
                            d_ff=128, n_layers=3, max_seq_len=64, **over)
    # one run of attention blocks, in the subtree today's checkpoints hold
    assert cfg.layer_runs() == (
        ("attn", "capacity" if over.get("num_experts") else "dense", 3),)
    assert cfg.run_keys() == ("layers",)
    model = GPT(cfg)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    got = {"/".join(k.key for k in path): leaf.shape for path, leaf in
           jax.tree_util.tree_flatten_with_path(params)[0]}
    assert got == pinned
    assert model.scanned_param_subtrees() == ("layers",)
    assert jax.tree.structure(model.param_logical_axes(),
                              is_leaf=lambda x: isinstance(x, tuple)
                              ) == jax.tree.structure(params)
    tokens = jnp.zeros((2, SEQ), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda p: model.forward(p, tokens))(params)
    assert _count_scans(jaxpr.jaxpr) == [3]     # one scan over the stack
    # and the optimizer is the plain one: no partition for a buffer
    assert len(jax.tree.leaves(model.configure_optimizers().init(
        {"w": jnp.zeros(3)}))) == 3


@pytest.mark.parametrize("field", [
    "num_dense_layers", "moe_d_ff", "moe_experts_held", "gated_mlp",
    "qk_norm", "rope_style", "norm_eps"])
def test_fields_of_the_mixed_stack_are_refused_on_the_uniform_block(field):
    """Only ``layer_types`` or ``moe_router='sigmoid'`` turns the mixed
    stack on; a field that only it reads does not rename
    ``params["layers"]`` by itself, it is refused by name."""
    with pytest.raises(ValueError, match=field):
        TransformerConfig(vocab_size=VOCAB, d_model=64, n_heads=4, d_ff=128,
                          n_layers=3, max_seq_len=64, **{field: MODEL[field]})


@pytest.mark.parametrize("field,value", [
    ("gated_mlp", False), ("qk_norm", False), ("rope_style", "interleaved")])
def test_mixed_stack_runs_one_block_and_refuses_the_rest(field, value):
    with pytest.raises(NotImplementedError, match=field):
        TransformerConfig(**{**MODEL, field: value})


def test_mixed_stack_is_one_scan_a_run(system):
    model, params, tokens = system
    jaxpr = jax.make_jaxpr(lambda p: model.forward(p, tokens))(params)
    assert sorted(_count_scans(jaxpr.jaxpr)) == [1, 1, 3]
    assert jax.tree.structure(
        model.param_logical_axes(), is_leaf=lambda x: isinstance(x, tuple)
    ) == jax.tree.structure(params)


# --------------------------------------------------------------------- #
# one layer stack: the three cells' kinds build the trees and run the    #
# arithmetic they did when two blocks built them                         #
# --------------------------------------------------------------------- #
_SMALL = dict(vocab_size=VOCAB, d_model=64, n_heads=4, d_ff=128, n_layers=3,
              max_seq_len=64)
# name: (config, digest of init_params(key) eager / under jit, loss and
# gradient norm of one training_step as float.hex(), scan lengths of its
# jaxpr (the fused loss is the scan of 1)), all read off the tree that
# still had the uniform block beside the mixed one (PR 28).  PR 30 left
# every digest and loss as read and moved the gradient norm of the two
# interleaved-rotary kinds by one unit in the last place (..a0 -> ..a2,
# ..68 -> ..66): ``_rope`` and its transpose are spelled over the whole
# head, and jitted on the CPU any respelling (a lane rotation and a
# select too) rounds 13 % of the cotangent's elements the other way
# (the compiler contracts a * c + b * s into another fused multiply-add;
# op by op the gradient is the pairwise one bit for bit).  PR 34 left
# every digest and loss again and moved the gradient norm of the dense
# and the LFM2 kind by one unit in the last place (..a2 -> ..a0, ..74 ->
# ..76): the fused loss multiplies dh and dw by 1 / valid rows AFTER
# their products where it multiplied the softmax's gradient before them
ONE_STACK = {
    "dense": (_SMALL, "bae5bc0b854c20e6", "c5cf35fbf2e6ace9",
              "0x1.60f5180000000p+2", "0x1.cc19a00000000p+0", [1, 3]),
    "gqa_capacity_moe": (
        {**_SMALL, **TODAY["gqa_capacity_moe"][0]},
        "8af981d53c36461b", "a49323c3c14de7fc",
        "0x1.63e1400000000p+2", "0x1.17a4660000000p+1", [1, 3]),
    "lfm2": (MODEL, "9728f03cda70c1b3", "af66504f958d5f44",
             "0x1.62ecf40000000p+2", "0x1.9c87760000000p+2", [1, 1, 1, 3]),
}


def _digest(params) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        h.update("/".join(k.key for k in path).encode())
        h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("case", sorted(ONE_STACK))
def test_one_stack_keeps_each_kind_to_the_last_bit(case):
    """The parameter tree is a format (a checkpoint, the benchmark's
    ``weights_seed``: the routed rows of the LFM2 cell follow the
    weights), and on the CPU the same operations in the same order give
    the same float32 bits."""
    kw, eager, jitted, loss_hex, norm_hex, scans = ONE_STACK[case]
    model = GPT(TransformerConfig(**kw), lr=1e-3)
    params = model.init_params(jax.random.PRNGKey(0))
    assert _digest(params) == eager
    assert _digest(jax.jit(model.init_params)(jax.random.PRNGKey(0))
                   ) == jitted
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, SEQ), 0, VOCAB)

    def step(p):
        return model.training_step(p, tokens, None)

    (loss, _), grads = jax.jit(jax.value_and_grad(step, has_aux=True))(
        params)
    norm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                        for g in jax.tree.leaves(grads)))
    assert (float(loss).hex(), float(norm).hex()) == (loss_hex, norm_hex)
    assert sorted(_count_scans(jax.make_jaxpr(step)(params).jaxpr)) == scans
    # the trunk's second result has one meaning, whatever the stack
    assert isinstance(model.forward(params, tokens, return_aux=True)[1],
                      dict)


# --------------------------------------------------------------------- #
# walkers of params["layers"] refuse a mixed stack by name               #
# --------------------------------------------------------------------- #
def _walkers():
    prompt = jnp.zeros((1, 4), jnp.int32)
    tok = jnp.zeros((1,), jnp.int32)

    def speculative(m, p):
        from ray_lightning_accelerators_tpu.models.speculative import (
            speculative_generate)
        return speculative_generate(m, p, m, p, prompt, 4)

    def serve(m, p):
        from ray_lightning_accelerators_tpu.serve import ServeEngine
        return ServeEngine(m, p, max_slots=2)

    return {
        "generate": lambda m, p: m.generate(p, prompt, 4),
        "generate_beam": lambda m, p: m.generate_beam(p, prompt, 4),
        "_prefill": lambda m, p: m._prefill(p, prompt, 8),
        "_decode_chunk": lambda m, p: m._decode_chunk(p, None, prompt, 0),
        "_decode_token": lambda m, p: m._decode_token(p, None, tok, 0),
        "decode_cache_alloc": lambda m, p: m.decode_cache_alloc(2, 16),
        "decode_step_rows": lambda m, p: m.decode_step_rows(
            p, None, tok, tok),
        "paged_cache_alloc": lambda m, p: m.paged_cache_alloc(4, 16),
        "decode_step_rows_paged": lambda m, p: m.decode_step_rows_paged(
            p, None, None, tok, tok),
        "decode_chunk_paged": lambda m, p: m.decode_chunk_paged(
            p, None, None, prompt, 0),
        "quantize_weights": lambda m, p: GPT.quantize_weights(p),
        "speculative_generate": speculative,
        "ServeEngine": serve,
    }


@pytest.mark.parametrize("walker", sorted(_walkers()))
def test_walkers_of_the_uniform_stack_refuse_by_name(system, walker):
    model, params, _ = system
    with pytest.raises(NotImplementedError, match="layer_types"):
        _walkers()[walker](model, params)


def test_pipeline_and_dropout_refuse_a_mixed_stack(system):
    from jax.sharding import Mesh
    model, params, tokens = system
    with pytest.raises(NotImplementedError, match="dropout"):
        _gpt(dropout=0.1).training_step(params, tokens,
                                        jax.random.PRNGKey(0))
    names = (mesh_lib.DATA_AXIS, mesh_lib.FSDP_AXIS, mesh_lib.EXPERT_AXIS,
             mesh_lib.TENSOR_AXIS, mesh_lib.SEQUENCE_AXIS,
             mesh_lib.PIPELINE_AXIS)
    piped = _gpt()
    piped.mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(
        1, 1, 1, 1, 1, 2), names)
    with pytest.raises(NotImplementedError, match="pipeline"):
        piped.forward(params, tokens)
    with pytest.raises(NotImplementedError, match="moe_router"):
        TransformerConfig(**{**MODEL, "moe_router": "softmax"})


# --------------------------------------------------------------------- #
# the normal path: Trainer.fit, the scanned epoch                        #
# --------------------------------------------------------------------- #
def _tokens(n=32):
    p = np.arange(1, VOCAB + 1, dtype=np.float64) ** -1.0
    return np.random.default_rng(0).choice(
        VOCAB, p=p / p.sum(), size=(n, SEQ)).astype(np.int32)


def _fit(tmpdir, model, **accel):
    trainer = Trainer(max_epochs=3, precision="f32", seed=0,
                      enable_checkpointing=False, log_every_n_steps=1,
                      default_root_dir=str(tmpdir),
                      accelerator=RayTPUAccelerator(**accel))
    trainer.fit(model, DataLoader(ArrayDataset(_tokens()), batch_size=8,
                                  shuffle=False))
    return trainer


@pytest.fixture
def device_cache(monkeypatch):
    """The device-resident data set (and with it the scanned epoch) is
    off on the CPU unless asked for."""
    monkeypatch.setattr(Trainer, "_CACHE_AUTO_ON_CPU", True)


def test_scanned_epoch_carries_the_counters_out_with_the_loss(
        tmpdir, device_cache):
    model = _gpt(remat=True)
    start = jax.device_get(model.init_params(jax.random.PRNGKey(0)))
    model.params = start
    trainer = _fit(tmpdir, model, num_workers=1)
    # the whole-epoch scan ran (a Program keeps its arguments' shapes
    # from its first call) and the per-step program never did
    assert trainer._epoch_scan_fn.args is not None
    assert trainer._train_step_cached_fn.args is None
    rows = [r for r in trainer.logger.history if "moe_rows_routed" in r]
    assert len(rows) == 12 and all("train_loss" in r for r in rows)
    assert all(r["moe_rows_routed"] == r["moe_rows_computed"] > 0
               for r in rows)
    assert all(r["moe_load_max_over_mean"] >= 1.0 for r in rows)
    # 128 pairs a layer are one window: every sparse layer ran one
    assert all(r["moe_rounds"] == 1.0 for r in rows)
    losses = [r["train_loss"] for r in rows]
    assert sum(losses[-4:]) < sum(losses[:4])
    end = jax.device_get(trainer._state.params)
    # the buffer stayed; its neighbours moved
    assert np.array_equal(end["layers_2"]["mlp"]["expert_bias"],
                          start["layers_2"]["mlp"]["expert_bias"])
    assert not np.array_equal(end["layers_2"]["mlp"]["w1"],
                              start["layers_2"]["mlp"]["w1"])


def test_train_step_carries_the_new_scopes(tmpdir, device_cache):
    """``gpt/conv`` and the four ``gpt/moe_*`` scopes, forward, remat's
    second forward and backward, from the compiled text alone."""
    from ray_lightning_accelerators_tpu.telemetry import scopes

    model = _gpt(remat=True)
    model.params = jax.device_get(model.init_params(jax.random.PRNGKey(0)))
    trainer = _fit(tmpdir, model, num_workers=1)
    trainer.teardown()
    names = set(scopes.scope_table("epoch_scan").values())
    for scope in ("conv", "moe_route", "moe_dispatch", "moe_experts",
                  "moe_combine", "attn", "mlp", "layers"):
        passes = {("bwd" if "transpose(" in n else "fwd")
                  if "rematted_computation" not in n else "recompute"
                  for n in names if f"/gpt/{scope}/" in f"/{n}/"
                  or f"(gpt/{scope})" in n}
        # the scan itself is not rematted; on a chip that holds a share
        # of the experts the combine weights carry no gradient, so the
        # route has no backward and the backward needs no second combine;
        # remat keeps the windows' plan by name, so the route runs once
        want = {"layers": {"fwd", "bwd"},
                "moe_route": {"fwd"},
                "moe_combine": {"fwd", "bwd"}}.get(
                    scope, {"fwd", "bwd", "recompute"})
        assert passes >= want, (scope, passes)
        assert scope != "moe_route" or passes == want


@pytest.mark.parametrize("gather_mode", ["tree", "scan"])
def test_fsdp_places_every_run_and_trains(tmpdir, device_cache,
                                          gather_mode):
    """FSDP sees more than one stacked subtree: each run's large leaves
    are sharded, the in-scan gather takes each run by its key, and the
    loss is the one-device loss."""
    model = _gpt()
    model.params = jax.device_get(model.init_params(jax.random.PRNGKey(0)))
    one = _fit(tmpdir.join("one"), model, num_workers=1)
    model = _gpt()
    model.params = jax.device_get(model.init_params(jax.random.PRNGKey(0)))
    trainer = Trainer(max_epochs=3, precision="f32", seed=0,
                      enable_checkpointing=False, log_every_n_steps=1,
                      default_root_dir=str(tmpdir.join("four")),
                      gather_mode=gather_mode,
                      grad_compression="int8" if gather_mode == "scan"
                      else None,
                      accelerator=RayTPUAccelerator(num_workers=4,
                                                    use_fsdp=True))
    trainer.fit(model, DataLoader(ArrayDataset(_tokens()), batch_size=8,
                                  shuffle=False))
    params = trainer._state.params
    for key in ("layers_0", "layers_1", "layers_2"):
        assert any(not leaf.sharding.is_fully_replicated
                   for leaf in jax.tree.leaves(params[key])), key
    a = float(one.callback_metrics["train_loss"])
    b = float(trainer.callback_metrics["train_loss"])
    assert abs(a - b) < (5e-2 if gather_mode == "scan" else 1e-3) * a


# --------------------------------------------------------------------- #
# rotary embeddings: the whole head on the lanes against the pairwise    #
# definition                                                             #
# --------------------------------------------------------------------- #
def _rope_pairwise(x, positions, theta, style="interleaved"):
    """The definition, as ``_rope`` spelled it until PR 30: split the
    head into the two members of each pair, rotate, put them back."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = (positions[..., None].astype(jnp.float32)
              * freqs[(None,) * positions.ndim])            # [.., d/2]
    if angles.ndim == 3:
        angles = angles[:, None]                    # the head axis
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    if style == "half":
        x1, x2 = x[..., :d // 2], x[..., d // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                               axis=-1).astype(x.dtype)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    rx1 = x1 * cos - x2 * sin
    rx2 = x2 * cos + x1 * sin
    return jnp.stack([rx1, rx2], axis=-1).reshape(x.shape).astype(x.dtype)


def _bf16_steps(a, b):
    """How many representable bfloat16 values lie between a and b."""
    def rank(t):
        bits = np.asarray(t).view(np.int16).astype(np.int32)
        return np.where(bits < 0, -(bits & 0x7fff), bits)
    return np.abs(rank(a) - rank(b))


def _assert_same_rotation(got, want):
    assert got.dtype == want.dtype and got.shape == want.shape
    if got.dtype == jnp.float32:
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0, atol=1e-6)
        return
    steps = _bf16_steps(got, want)
    assert steps.max() <= 1             # none further than one ulp
    assert (steps == 0).mean() >= 0.999


_ROPE_B, _ROPE_H, _ROPE_S, _ROPE_D = 3, 4, 24, 64
_ROPE_POSITIONS = {
    "[s]": lambda: jnp.arange(_ROPE_S),
    "[b,s]": lambda: (jnp.arange(_ROPE_S)[None]
                      + jnp.array([[0], [7], [1000]])),
    "[b,1]": lambda: jnp.array([[5], [0], [4093]]),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("positions", sorted(_ROPE_POSITIONS))
@pytest.mark.parametrize("style", ["interleaved", "half"])
def test_rope_equals_the_pairwise_definition(style, positions, dtype):
    """``_rope`` never splits the head (a strided lane slice is a gather
    on the TPU): value and gradient equal the pairwise definition, for
    both pairings, every rank of ``positions`` and both dtypes.  The
    definition is evaluated in float32 on the same values and cast once,
    which is what ``_rope`` does with a bfloat16 head and with its
    cotangent (the rotation back, one rounding; differentiating the
    pairwise spelling in bfloat16 rounds each member's two cotangents
    apart and adds them in bfloat16)."""
    dtype = jnp.dtype(dtype)
    pos = _ROPE_POSITIONS[positions]()
    theta = 1e6 if style == "half" else 1e4
    kx, kw = jax.random.split(jax.random.PRNGKey(7))
    shape = (_ROPE_B, _ROPE_H, pos.shape[-1], _ROPE_D)
    x = jax.random.normal(kx, shape, dtype)
    w = jax.random.normal(kw, shape, dtype)     # exact in either dtype

    def value_and_pullback(rope, t):
        out, pull = jax.vjp(lambda u: rope(u, pos, theta, style), t)
        return out, pull(w.astype(out.dtype))[0]

    got = value_and_pullback(_rope, x)
    want = value_and_pullback(_rope_pairwise, x.astype(jnp.float32))
    for g, r in zip(got, want):
        _assert_same_rotation(g, r.astype(dtype))


@pytest.mark.parametrize("style", ["interleaved", "half"])
def test_rope_gives_one_query_one_value_on_every_path(style):
    """A query at position p through the ``[s]`` call (training,
    prefill) and the ``[b, 1]`` call (a decode step, every row at its
    own position): identical, which token identity of the decode paths
    rests on."""
    x = jax.random.normal(jax.random.PRNGKey(3),
                          (_ROPE_B, _ROPE_H, _ROPE_S, _ROPE_D), jnp.float32)
    whole = _rope(x, jnp.arange(_ROPE_S), 1e4, style)
    at = jnp.array([[17], [0], [23]])
    rows = jnp.take_along_axis(x, at[:, None, :, None], axis=2)
    one = _rope(rows, at, 1e4, style)
    want = jnp.take_along_axis(whole, at[:, None, :, None], axis=2)
    assert np.array_equal(np.asarray(one), np.asarray(want))
    grid = _rope(x, jnp.broadcast_to(jnp.arange(_ROPE_S),
                                     (_ROPE_B, _ROPE_S)), 1e4, style)
    assert np.array_equal(np.asarray(grid), np.asarray(whole))


# --------------------------------------------------------------------- #
# the benchmark's copy of the reference                                  #
# --------------------------------------------------------------------- #
def test_the_two_reference_files_are_one_text():
    with open(os.path.join(ROOT, "ray_lightning_accelerators_tpu", "models",
                           "reference_lfm2.py")) as f:
        ours = f.read()
    with open(os.path.join(ROOT, "benchmark", "lib",
                           "reference_lfm2.py")) as f:
        assert f.read() == ours
