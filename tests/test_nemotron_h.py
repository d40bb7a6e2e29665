"""A hybrid stack of single-part layers (Mamba-2 mixers, a latent expert
layer with a shared expert, GQA at a head size given as a value, an
untied head) against its plain float32 reference
(``models/reference_nemotron_h.py``: the recurrence one position at a
time), at a small size with every kind of layer present, on the suite's
CPU mesh: the chunked scan's values and gradients; each layer kind and
the whole stack; the share test over Mamba-2 groups, KV heads and
experts; the dropless walk under a skewed router at top-22; what
``TransformerConfig`` and the serving walkers refuse by name; a
``Trainer.fit`` on the normal path."""

import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_lightning_accelerators_tpu import (ArrayDataset, DataLoader,
                                            RayTPUAccelerator, Trainer)
from ray_lightning_accelerators_tpu.models import reference_nemotron_h as ref
from ray_lightning_accelerators_tpu.models.transformer import (
    GPT, TransformerConfig)
from ray_lightning_accelerators_tpu.ops import moe, ssm
from tests.test_lfm2 import came_back_by_gather, plan_of

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
VOCAB, SEQ = 256, 40        # 40 positions: two chunks of 16 and a tail
PUBLISHED = ("MEMEMEM*EMEMEMEM*EMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*EMEMEMEMEM*"
             "EMEMEMEMEM*EMEMEMEM*EMEMEMEME")
# published layers 26-36 of the pattern at toy widths; the head size (8)
# is not d_model / n_heads (16)
WHOLE = dict(
    vocab_size=VOCAB, d_model=64, n_heads=4, n_kv_heads=2, attn_head_dim=8,
    d_ff=64, n_layers=11, max_seq_len=64, tie_embeddings=False,
    hybrid_pattern="EMEMEMEMEM*", conv_kernel=4, moe_router="sigmoid",
    num_experts=16, moe_top_k=4, moe_d_ff=24, moe_latent_dim=32,
    moe_shared_d_ff=48, moe_norm_topk=True, moe_routed_scale=5.0,
    rope_style="none", norm_eps=1e-5, ssm_heads=8, ssm_head_dim=8,
    ssm_groups=4, ssm_state=16, ssm_chunk=16)
# one chip's share: 4 of 16 experts, 2 of 4 Mamba-2 groups, the two
# query heads of KV head 0
SHARE = dict(WHOLE, moe_experts_held=[0, 1, 2, 3], ssm_groups_held=[0, 1],
             attn_heads_held=[0, 1])


def _gpt(model=SHARE, dtype=jnp.float32, **over):
    gpt = GPT(TransformerConfig(**{**model, **over}), lr=1e-3)
    gpt.compute_dtype = dtype
    return gpt


def _randomised(params, seed=7):
    """Norm scales, biases and skips off their initial ones and zeros, so
    that a part that ignored them would show."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        a + 0.1 * jax.random.normal(k, a.shape, a.dtype) if a.ndim <= 2
        else a for a, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, SEQ), 0, VOCAB)


@pytest.fixture(scope="module", params=["share", "all"])
def both_grads(request, tokens):
    """The system's and the reference's logits, loss and gradients, on a
    chip that holds a share of every layer and on one that holds all."""
    cfg = SHARE if request.param == "share" else WHOLE
    model = _gpt(cfg)
    params = _randomised(model.init_params(jax.random.PRNGKey(0)))

    def system(p):      # one program: the step's loss, and the logits
        return model.training_step(p, tokens, None)[0], model.forward(
            p, tokens, return_aux=True)

    def reference(p):
        out = ref.forward(p, tokens, cfg, remat=True)
        return ref.lm_loss(out[0], tokens), out

    (loss, (logits, aux)), grads = jax.jit(
        jax.value_and_grad(system, has_aux=True))(params)
    (ref_loss, (ref_logits, routing)), ref_grads = jax.value_and_grad(
        reference, has_aux=True)(params)
    return dict(logits=logits, aux=aux, loss=float(loss), grads=grads,
                ref_logits=ref_logits, routing=routing,
                ref_loss=float(ref_loss), ref_grads=ref_grads,
                held=request.param, params=params, model=model)


# --------------------------------------------------------------------- #
# the pattern as runs of blocks                                          #
# --------------------------------------------------------------------- #
def test_letters_pair_into_blocks_and_blocks_into_runs():
    assert PUBLISHED[26:37] == WHOLE["hybrid_pattern"]
    cfg = TransformerConfig(**SHARE)
    assert cfg.layer_runs() == (
        ("none", "latent", 1), ("mamba", "latent", 4), ("mamba", "none", 1),
        ("attn", "none", 1))
    assert cfg.run_keys() == tuple(f"layers_{i}" for i in range(4))
    whole = TransformerConfig(**{**WHOLE, "n_layers": 88,
                                 "hybrid_pattern": PUBLISHED})
    runs = whole.layer_runs()
    # 40 + 40 + 8 letters; a mixer takes the expert layer behind it
    assert sum(n * ((op != "none") + (ff != "none"))
               for op, ff, n in runs) == 88
    assert sum(n for op, _, n in runs if op == "mamba") == 40
    assert sum(n for op, _, n in runs if op == "attn") == 8
    assert sum(n for _, ff, n in runs if ff == "latent") == 40
    assert GPT(whole).scanned_param_subtrees() == whole.run_keys()


def test_a_block_holds_only_the_parts_it_has():
    model = _gpt()
    shapes = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    assert set(shapes["layers_0"]) == {"ln2", "mlp"}
    assert set(shapes["layers_1"]) == {"ln1", "ssm", "ln2", "mlp"}
    assert set(shapes["layers_2"]) == {"ln1", "ssm"}
    assert set(shapes["layers_3"]) == {"ln1", "attn"}
    # the share is in the shapes: 2 of 4 groups = 4 of 8 heads of 8, two
    # query heads of 8 and their one KV head, 4 of 16 experts
    s, a, e = (shapes["layers_2"]["ssm"], shapes["layers_3"]["attn"],
               shapes["layers_0"]["mlp"])
    assert s["w_in"].shape == (1, 64, 32 + (32 + 2 * 2 * 16) + 4)
    assert s["conv_w"].shape == (1, 96, 4) and s["a_log"].shape == (1, 4)
    assert a["wq"].shape == (1, 64, 2, 8) and a["wk"].shape == (1, 64, 1, 8)
    assert e["experts"]["w1"].shape == (1, 4, 32, 24)
    assert e["experts"]["router"].shape == (1, 64, 16)
    assert "w3" not in e["experts"] and e["shared_w1"].shape == (1, 64, 48)
    assert shapes["unembed"].shape == (64, VOCAB)
    axes = model.param_logical_axes()
    assert jax.tree.structure(
        axes, is_leaf=lambda a: isinstance(a, tuple)) == jax.tree.structure(
            shapes)


# --------------------------------------------------------------------- #
# the chunked scan against the recurrence, one position at a time        #
# --------------------------------------------------------------------- #
def _scan_inputs(s, groups, heads=4, p=8, n=16, b=2):
    k = jax.random.split(jax.random.PRNGKey(s), 5)
    return (jax.random.normal(k[0], (b, s, heads, p)),
            jax.nn.softplus(jax.random.normal(k[1], (b, s, heads))),
            -jnp.exp(jax.random.normal(k[2], (heads,))),
            jax.random.normal(k[3], (b, s, groups, n)),
            jax.random.normal(k[4], (b, s, groups, n)))


def _stepwise(x, dt, a, b_in, c_in):
    per_group = x.shape[2] // b_in.shape[2]
    return ref._recurrence(x, dt, a, jnp.repeat(b_in, per_group, 2),
                           jnp.repeat(c_in, per_group, 2))


@pytest.mark.parametrize("s,groups", [(32, 1), (37, 2), (8, 4), (128, 2)])
def test_chunked_scan_equals_the_stepwise_recurrence(s, groups):
    """Values and every gradient, at lengths that are and are not
    multiples of the chunk (16), shorter than one chunk, and (128) with
    the reference's own checkpointed segments."""
    args = _scan_inputs(s, groups)

    def both(fn):
        return jax.jit(jax.value_and_grad(
            lambda *t: jnp.sum(jnp.sin(fn(*t))), argnums=(0, 1, 2, 3, 4),
            has_aux=False))(*args), jax.jit(fn)(*args)

    with jax.default_matmul_precision("highest"):
        (_, got_grads), got = both(lambda *t: ssm.ssd_scan(*t, 16))
        (_, want_grads), want = both(_stepwise)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5 * float(
        jnp.max(jnp.abs(want)))
    for g, w in zip(got_grads, want_grads):
        assert float(jnp.max(jnp.abs(g - w))) < 5e-5 * float(
            jnp.max(jnp.abs(w)))


def test_chunked_scan_is_causal_and_carries_state_across_chunks():
    x, dt, a, b_in, c_in = _scan_inputs(48, 2)
    scan = jax.jit(lambda x: ssm.ssd_scan(x, dt, a, b_in, c_in, 16))
    base, moved = scan(x), scan(x.at[:, 20].add(1.0))
    changed = jnp.any(jnp.abs(moved - base) > 1e-6, axis=(0, 2, 3))
    assert not bool(changed[:20].any())
    # position 20 is in the second chunk: the third feels it too
    assert bool(changed[20]) and bool(changed[32:].any())


# --------------------------------------------------------------------- #
# each layer kind, and its shares, against the uncut reference           #
# --------------------------------------------------------------------- #
def _mixer_params(seed=3):
    return _randomised(ssm.init_mamba2_params(
        jax.random.PRNGKey(seed), 64, 8, 8, 4, 16, 4), seed)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _mixer(u, p, groups, dtype=jnp.float32):
    return ssm.mamba2_mixer(
        u.astype(dtype), p, heads=2 * groups, head_dim=8, groups=groups,
        state=16, chunk=16, eps=1e-5, compute_dtype=dtype)


@jax.jit
def _ref_mixer(u, p):
    return ref.mamba_mixer(u, p, WHOLE)


def _group_of_mixer(p, g, heads=8, hd=8, groups=4, n=16):
    """The leaves of group ``g`` alone: every leaf is laid out group by
    group along its wide axis."""
    inner, per = heads * hd, heads // groups
    ch = np.arange(g * per * hd, (g + 1) * per * hd)       # its channels
    hs = np.arange(g * per, (g + 1) * per)                 # its heads
    st = np.arange(g * n, (g + 1) * n)                     # its B (or C)
    conv = np.concatenate([ch, inner + st, inner + groups * n + st])
    cols = np.concatenate([ch, inner + conv,
                           2 * inner + 2 * groups * n + hs])
    return {"w_in": p["w_in"][:, cols], "conv_w": p["conv_w"][conv],
            "conv_b": p["conv_b"][conv], "a_log": p["a_log"][hs],
            "dt_bias": p["dt_bias"][hs], "d_skip": p["d_skip"][hs],
            "norm": p["norm"][ch], "w_out": p["w_out"][ch]}


def test_mixer_matches_the_reference_and_its_groups_add_up():
    """The whole mixer is the reference's; the four groups' partial
    results (a group: its heads, its B and C, its gated norm, its rows
    of the out-projection) add up to the uncut reference's layer."""
    p = _mixer_params()
    u = jax.random.normal(jax.random.PRNGKey(4), (2, SEQ, 64))
    want = _ref_mixer(u, p)
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(_mixer(u, p, 4) - want))) < 1e-4 * scale
    total = jnp.zeros_like(u)
    for g in range(4):
        share = _group_of_mixer(p, g)
        part = _mixer(u, share, 1)
        assert float(jnp.max(jnp.abs(
            part - _ref_mixer(u, share)))) < 1e-4 * scale
        total = total + part
    assert float(jnp.max(jnp.abs(total - want))) < 1e-4 * scale


def test_mixer_in_bfloat16_stays_near_the_reference():
    """bfloat16 operands, float32 decays, state and accumulation: the
    largest error of an element is under 3 % of the output's largest
    (8 bits of mantissa through two projections, the chunk products and
    a norm; a float16-free 8-bit format would be ten times that)."""
    p = _mixer_params()
    u = jax.random.normal(jax.random.PRNGKey(4), (2, SEQ, 64))
    want = _ref_mixer(u, p)
    got = _mixer(u, p, 4, jnp.bfloat16).astype(jnp.float32)
    assert float(jnp.max(jnp.abs(got - want))) < 0.03 * float(
        jnp.max(jnp.abs(want)))


def test_attention_heads_add_up_by_kv_head():
    """Head size 8 at d_model 64 with 4 heads; the two shares (a KV head
    and the two query heads that read it) add up to the uncut layer."""
    whole = _gpt(WHOLE)
    a = jax.tree.map(
        lambda w: w[0], whole.init_params(jax.random.PRNGKey(0))[
            "layers_3"]["attn"])
    x = jax.random.normal(jax.random.PRNGKey(5), (2, SEQ, 64))
    pos = jnp.arange(SEQ)
    want = jax.jit(ref.attention_operator)(x, a)
    got, _ = jax.jit(whole._self_attention)(x, a, pos)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    total = jnp.zeros_like(x)
    for kv in range(2):
        heads = [2 * kv, 2 * kv + 1]
        share = {"wq": a["wq"][:, 2 * kv:2 * kv + 2],
                 "wk": a["wk"][:, kv:kv + 1], "wv": a["wv"][:, kv:kv + 1],
                 "wo": a["wo"][2 * kv:2 * kv + 2]}
        part, _ = jax.jit(_gpt(
            WHOLE, attn_heads_held=heads)._self_attention)(x, share, pos)
        total = total + part
    assert float(jnp.max(jnp.abs(total - want))) < 1e-5


def _latent_params(num_experts=16, seed=6):
    return moe.init_latent_moe_params(jax.random.PRNGKey(seed), 64, 32, 24,
                                      48, num_experts, num_experts)


def _slice_experts(p, held):
    idx = jnp.asarray(held)
    experts = {**p["experts"], "w1": p["experts"]["w1"][idx],
               "w2": p["experts"]["w2"][idx]}
    return {**p, "experts": experts}


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5))
def _latent(x, p, held, num_experts=16, top_k=4, dtype=jnp.float32):
    return moe.latent_moe(x.astype(dtype), p, top_k=top_k, held=held,
                          num_experts=num_experts, norm_topk=True,
                          scale=5.0, compute_dtype=dtype)


def test_expert_shares_add_up_in_the_latent():
    """Four chips share the layer, four experts each.  A chip's result is
    the reference's for its share; the shares' routed parts summed IN THE
    LATENT, then the up-projection, the shared expert and the router
    counted once, are the uncut reference's layer; the shares' rows add
    up to top_k a token."""
    p = _latent_params()
    x = jax.random.normal(jax.random.PRNGKey(7), (2, SEQ, 64))
    block = jax.jit(lambda x, p, held: ref.latent_block(x, p, WHOLE, held),
                    static_argnums=2)
    whole, _, _ = block(x, p, tuple(range(16)))
    shared = jnp.square(jax.nn.relu(x @ p["shared_w1"])) @ p["shared_w2"]
    routed, rows = jnp.zeros((2, SEQ, 32)), 0.0
    for chip in range(4):
        held = tuple(range(4 * chip, 4 * chip + 4))
        share = _slice_experts(p, held)
        y, stats = _latent(x, share, held)
        part, _, _ = block(x, share, held)
        assert float(jnp.max(jnp.abs(y - part))) < 1e-4
        r, _ = jax.jit(functools.partial(
            moe.dropless_moe, top_k=4, held=held, num_experts=16,
            scale=5.0, compute_dtype=jnp.float32, norm_eps=1e-20))(
                x @ p["fc1"], share["experts"], router_x=x)
        routed, rows = routed + r, rows + float(stats["rows_computed"])
    total = routed @ p["fc2"] + shared
    assert float(jnp.max(jnp.abs(total - whole))) < 1e-4
    assert rows == 2 * SEQ * 4


# 256 tokens, top-22 of 64: 5,632 pairs.  Six experts held: windows of
# 1,024 sorted rows (1.5 x the nominal 528, in whole row tiles), under a
# quarter of the pairs, so the token side is the scatter-add; eight held:
# 1,536 rows, the gather; all 64 held: one window of all the pairs, and
# the combine weights carry the router's gradient
_LOADS = [(6, 0, 1), (6, 5, 2), (8, 0, 1), (8, 8, 2)]


@pytest.mark.parametrize("n_held,favoured,rounds,check", [
    *[(*load, "layer") for load in _LOADS], (64, 0, 1, "layer"),
    *[(*load, "counter") for load in _LOADS]])
def test_no_token_dropped_at_top_22_under_a_skewed_router(
        n_held, favoured, rounds, check):
    """``favoured`` held experts are every token's choice whatever the
    token: more rows than one window holds, as many windows as they
    need, every routed row computed, the result and its gradients the
    reference's (the router's among them where every expert is held:
    ``d_w`` over the choice-major rows).  ``counter``: ``rows_computed``,
    made by comparing positions, is the count the mask of visited rows
    gives when gathered at them, on either token side."""
    held = tuple(range(64) if n_held == 64 else range(0, 2 * n_held, 2))
    p = _slice_experts(_latent_params(64), held)
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(8), (1, 256, 64)))
    router = p["experts"]["router"]
    for e in held[:favoured]:
        router = router.at[:, e].set(1.0)
    p["experts"]["router"] = router
    model = {**WHOLE, "num_experts": 64, "moe_top_k": 22}
    m = moe.window_rows(256 * 22, n_held, 64)
    assert moe._token_side_by_scatter(m, 256 * 22) == (n_held == 6)

    def system(x, p):
        y, stats = _latent(x, p, held, 64, 22)
        return jnp.sum(jnp.sin(y)), (y, stats)

    def reference(x, p):
        y, _, _ = ref.latent_block(x, p, model, held)
        return jnp.sum(jnp.sin(y)), y

    (_, (y, stats)), grads = jax.jit(jax.value_and_grad(
        system, argnums=(0, 1), has_aux=True))(x, p)
    assert float(stats["rows_routed"]) == float(stats["rows_computed"])
    assert float(stats["rows_computed"]) >= 256 * favoured
    assert float(stats["rounds"]) == rounds
    if check == "counter":
        assert float(stats["rows_computed"]) == came_back_by_gather(
            *plan_of(stats["selected"], held, 64), m)
        return
    (_, want), ref_grads = jax.jit(jax.value_and_grad(
        reference, argnums=(0, 1), has_aux=True))(x, p)
    assert float(jnp.max(jnp.abs(y - want))) < 1e-4 * float(
        jnp.max(jnp.abs(want)))
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0],
                            jax.tree.leaves(ref_grads)):
        assert float(jnp.max(jnp.abs(g - r))) <= 2e-4 * float(
            jnp.max(jnp.abs(r)) + 1e-12), jax.tree_util.keystr(path)
    taught = float(jnp.max(jnp.abs(grads[1]["experts"]["router"])))
    assert (taught > 0) == (n_held == 64)


# --------------------------------------------------------------------- #
# the whole stack                                                        #
# --------------------------------------------------------------------- #
def test_logits_and_routing_match_the_reference(both_grads):
    got = both_grads
    assert float(jnp.max(jnp.abs(got["logits"] - got["ref_logits"]))) \
        < 2e-4 * float(got["ref_logits"].std())
    routing, aux = got["routing"], got["aux"]
    assert routing["selected"].shape == (5, 2, SEQ, 4)  # 5 expert layers
    assert bool(jnp.all(jnp.sort(aux["moe_selected"], -1)
                        == routing["selected"]))
    assert float(aux["moe_rows_routed"]) == float(aux["moe_rows_computed"])
    assert float(routing["margin"].min()) >= 0.0
    if got["held"] == "all":
        assert float(aux["moe_rows_routed"]) == 5 * 2 * SEQ * 4


def test_loss_matches_the_reference(both_grads):
    assert abs(both_grads["loss"] - both_grads["ref_loss"]) \
        < 1e-5 * abs(both_grads["ref_loss"])


@pytest.mark.parametrize("group", ref.GROUPS)
def test_gradients_match_the_reference(both_grads, group):
    grads, ref_grads = both_grads["grads"], both_grads["ref_grads"]
    norms, ref_norms = (ref.grad_group_norms(grads),
                        ref.grad_group_norms(ref_grads))
    if group == "router" and both_grads["held"] == "share":
        # the combine weights carry no gradient where only some experts
        # are held (the whole gradient is a sum over the shares)
        assert norms[group] == ref_norms[group] == 0.0
        return
    assert ref_norms[group] > 0
    assert abs(norms[group] - ref_norms[group]) < 2e-4 * ref_norms[group]
    holds = {"router": ("router",), "experts": ("experts", "w1"),
             "latent": ("fc1",), "shared": ("shared_w2",), "ssm": ("ssm",),
             "attention": ("attn",), "embedding": ("embed",),
             "head": ("unembed",)}
    for (path, g), r in zip(
            jax.tree_util.tree_flatten_with_path(grads)[0],
            jax.tree.leaves(ref_grads)):
        names = [getattr(k, "key", None) for k in path]
        if all(n in names for n in holds[group]):
            assert float(jnp.max(jnp.abs(g - r))) <= 5e-4 * float(
                jnp.max(jnp.abs(r)) + 1e-12), jax.tree_util.keystr(path)


def test_stack_in_bfloat16_stays_near_the_reference(both_grads, tokens):
    """The system in bfloat16 over the same float32 weights, all experts
    held (no routing share to flip a whole expert away): the loss within
    5e-3 relative (a mean over 78 positions only; 2.4e-3 read), the
    median row error under 0.1 row deviations (a near-tied choice flips
    on a rounded hidden state and moves a row by a whole expert: the
    median, not the maximum)."""
    if both_grads["held"] != "all":
        pytest.skip("one precision comparison, on the whole model")
    model = _gpt(WHOLE, jnp.bfloat16)
    loss, logits = jax.jit(lambda p, t: (
        model.training_step(p, t, None)[0], model.forward(p, t)))(
            both_grads["params"], tokens)
    ref_logits = both_grads["ref_logits"]
    assert abs(float(loss) - both_grads["ref_loss"]) < 5e-3 * both_grads[
        "ref_loss"]
    err = jnp.max(jnp.abs(logits - ref_logits), -1) / ref_logits.std(-1)
    assert float(jnp.median(err)) < 0.1


def test_buffers_and_rates_are_no_weights(both_grads):
    """The selection bias: no gradient, no update, no optimizer state.
    ``a_log``, ``dt_bias``, ``d_skip``: trained, without weight decay."""
    if both_grads["held"] != "share":
        pytest.skip("one optimizer, on the share")
    model, params = both_grads["model"], both_grads["params"]
    bias = both_grads["grads"]["layers_1"]["mlp"]["experts"]["expert_bias"]
    assert float(jnp.max(jnp.abs(bias))) == 0.0
    tx = model.configure_optimizers()
    state = tx.init(params)
    zero = jax.tree.map(jnp.zeros_like, params)
    updates, _ = jax.jit(tx.update)(zero, state, params)
    run = updates["layers_1"]
    assert float(jnp.max(jnp.abs(run["mlp"]["experts"]["expert_bias"]))) == 0
    for name in ("a_log", "dt_bias", "d_skip"):     # no gradient, no decay
        assert float(jnp.max(jnp.abs(run["ssm"][name]))) == 0.0
    assert float(jnp.max(jnp.abs(run["ssm"]["w_in"]))) > 0.0    # decayed


# --------------------------------------------------------------------- #
# what still has no reference is refused by name                         #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("over,match", [
    (dict(hybrid_pattern="EMEMEMEMEM-"), "dense '-' layer"),
    (dict(hybrid_pattern="EMEM"), "n_layers=11"),
    (dict(gated_mlp=True), "gated_mlp"),
    (dict(qk_norm=True), "qk_norm"),
    (dict(rope_style="half"), "rope_style"),
    (dict(layer_types=["conv"] * 11), "layer_types"),
    (dict(moe_router="softmax"), "moe_router"),
    (dict(num_dense_layers=1), "num_dense_layers"),
    (dict(moe_latent_dim=None), "moe_latent_dim"),
    (dict(moe_shared_d_ff=None), "moe_shared_d_ff"),
    (dict(attn_heads_held=[0, 2, 3]), "attn_heads_held"),
    (dict(attn_heads_held=[1, 0]), "attn_heads_held"),
    (dict(ssm_heads=6), "ssm_heads"),
])
def test_hybrid_stack_runs_one_block_and_refuses_the_rest(over, match):
    with pytest.raises((NotImplementedError, ValueError), match=match):
        TransformerConfig(**{**SHARE, **over})


@pytest.mark.parametrize("field,value", [
    ("attn_head_dim", 8), ("attn_heads_held", [0, 1]), ("ssm_heads", 8),
    ("ssm_groups_held", [0]), ("moe_latent_dim", 32),
    ("moe_shared_d_ff", 48)])
@pytest.mark.parametrize("stack", ["uniform", "mixed"])
def test_fields_of_the_hybrid_stack_are_refused_without_a_pattern(
        stack, field, value):
    base = dict(vocab_size=VOCAB, d_model=64, n_heads=4, d_ff=64, n_layers=2)
    if stack == "mixed":
        base.update(layer_types=["conv", "full_attention"], gated_mlp=True,
                    qk_norm=True, rope_style="half")
    with pytest.raises(ValueError, match=f"{field} belongs to a hybrid"):
        TransformerConfig(**base, **{field: value})
    with pytest.raises((ValueError, NotImplementedError),
                       match="rope_style"):
        TransformerConfig(**{**base, "rope_style": "none"})


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
def test_serving_walkers_refuse_the_hybrid_stack_by_name(walker):
    model = _gpt()
    params = model.init_params(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="hybrid_pattern"):
        _walkers()[walker](model, params)


def test_pipeline_and_dropout_refuse_the_hybrid_stack(tokens):
    from jax.sharding import Mesh

    from ray_lightning_accelerators_tpu.parallel import mesh as mesh_lib
    model = _gpt(dropout=0.1)
    params = model.init_params(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError, match="dropout"):
        model.training_step(params, tokens, jax.random.PRNGKey(0))
    names = (mesh_lib.DATA_AXIS, mesh_lib.FSDP_AXIS, mesh_lib.EXPERT_AXIS,
             mesh_lib.TENSOR_AXIS, mesh_lib.SEQUENCE_AXIS,
             mesh_lib.PIPELINE_AXIS)
    piped = _gpt()
    piped.mesh = Mesh(np.asarray(jax.devices()[:2]).reshape(
        1, 1, 1, 1, 1, 2), names)
    with pytest.raises(NotImplementedError, match="pipeline"):
        piped.forward(params, tokens)


# --------------------------------------------------------------------- #
# the normal path: Trainer.fit, the scanned epoch                        #
# --------------------------------------------------------------------- #
def test_trainer_fit_carries_the_counters_and_the_new_scopes(
        tmpdir, monkeypatch):
    """Two scanned epochs of a tiny preset on the normal path: a finite,
    falling loss, the expert layer's counters in every logged step, the
    rates trained, the buffer left alone, and the new scopes in the
    compiled text."""
    from ray_lightning_accelerators_tpu.telemetry import scopes

    monkeypatch.setattr(Trainer, "_CACHE_AUTO_ON_CPU", True)
    p = np.arange(1, VOCAB + 1, dtype=np.float64) ** -1.0
    data = np.random.default_rng(0).choice(
        VOCAB, p=p / p.sum(), size=(32, SEQ)).astype(np.int32)
    model = _gpt(remat=True)
    start = jax.device_get(model.init_params(jax.random.PRNGKey(0)))
    model.params = start
    trainer = Trainer(max_epochs=2, precision="f32", seed=0,
                      enable_checkpointing=False, log_every_n_steps=1,
                      default_root_dir=str(tmpdir),
                      accelerator=RayTPUAccelerator(num_workers=1))
    trainer.fit(model, DataLoader(ArrayDataset(data), batch_size=8,
                                  shuffle=False))
    assert trainer._epoch_scan_fn.args is not None     # the scanned epoch
    rows = [r for r in trainer.logger.history if "moe_rows_routed" in r]
    assert len(rows) == 8 and all(np.isfinite(r["train_loss"]) for r in rows)
    assert all(r["moe_rows_routed"] == r["moe_rows_computed"] > 0
               for r in rows)
    assert all(r["moe_rounds"] == 1.0 for r in rows)
    losses = [r["train_loss"] for r in rows]
    assert sum(losses[-4:]) < sum(losses[:4])
    end = jax.device_get(trainer._state.params)
    bias = ("layers_1", "mlp", "experts", "expert_bias")
    rate = ("layers_1", "ssm", "a_log")

    def leaf(tree, path):
        for key in path:
            tree = tree[key]
        return tree

    assert np.array_equal(leaf(end, bias), leaf(start, bias))
    assert not np.array_equal(leaf(end, rate), leaf(start, rate))
    trainer.teardown()
    names = set(scopes.scope_table("epoch_scan").values())
    for scope in ("ssm", "ssm_scan", "moe_latent", "moe_shared",
                  "moe_experts", "attn"):
        assert any(f"gpt/{scope}" in n.replace("(", "/").replace(")", "/")
                   for n in names), scope


def test_the_two_reference_files_are_one_text():
    with open(os.path.join(ROOT, "ray_lightning_accelerators_tpu", "models",
                           "reference_nemotron_h.py")) as f:
        package = f.read()
    with open(os.path.join(ROOT, "benchmark", "lib",
                           "reference_nemotron_h.py")) as f:
        assert f.read() == package
