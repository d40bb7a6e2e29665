"""The looped stack (``TransformerConfig.post_norms`` / ``loop_passes`` /
``exit_gate``: ``model_type: ouro``) on the train path, at a small size
on the CPU: the program against ``models/reference_ouro.py`` in float32
(objective, every pass's loss, the last pass's logits, the exit
distribution, every leaf's gradient), the shared weights' gradient
against an unrolled stack, the exit distribution's own arithmetic, the
weighted fused loss against the materialised one, remat and the named
residuals per application, one layer body whatever the number of
passes, every refusal by name -- and, with the new fields at their
defaults, the parent's program for the stacks the benchmark runs."""

import hashlib
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_lightning_accelerators_tpu.models import reference_ouro as ref
from ray_lightning_accelerators_tpu.models.transformer import (
    GPT, TransformerConfig)
from ray_lightning_accelerators_tpu.ops import losses
from ray_lightning_accelerators_tpu.parallel import mesh as mesh_lib
from tests.test_lfm2 import MODEL as LFM2
from tests.test_named_residuals import _per_scan, kernel_branch  # noqa: F401
from tests.test_nemotron_h import SHARE as LATENT

VOCAB, SEQ = 256, 48
MODEL = dict(
    vocab_size=VOCAB, d_model=64, n_heads=4, attn_head_dim=24, d_ff=160,
    n_layers=2, max_seq_len=64, tie_embeddings=False, rope_theta=1e6,
    gated_mlp=True, rope_style="half", norm_eps=1e-6, post_norms=True,
    loop_passes=3, exit_gate=True, exit_beta=0.05)
GPT2 = dict(vocab_size=VOCAB, d_model=64, n_heads=4, d_ff=128, n_layers=2,
            max_seq_len=64, loss_chunk_rows=32)


def _gpt(**over):
    model = GPT(TransformerConfig(**{**MODEL, "loss_chunk_rows": 32,
                                     **over}), lr=1e-3)
    model.compute_dtype = jnp.float32
    return model


def _params(model, key=0):
    """Seeded weights with every norm scale and the gate off their
    initial 1 and 0, so that each of them matters."""
    params = jax.jit(model.init_params)(jax.random.PRNGKey(key))
    return jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(
            jax.random.PRNGKey(a.size), a.shape), params)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(1), (2, SEQ), 0, VOCAB)


def _step(model, params, tokens):
    return jax.jit(jax.value_and_grad(
        lambda p: model.training_step(p, tokens, None), has_aux=True))(params)


def _reference(params, tokens, model):
    """One program: application by application the reference dispatches
    every layer alone."""
    return jax.jit(lambda p: ref.loss_and_grads(p, tokens, model))(params)


# --------------------------------------------------------------------- #
# program against reference                                              #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
@pytest.mark.parametrize("fused", [True, False],
                         ids=["fused", "materialised"])
def test_program_agrees_with_the_reference_in_float32(tokens, fused, remat):
    model = _gpt(fused_loss=fused, remat=remat)
    params = _params(model)
    (loss, metrics), grads = _step(model, params, tokens)
    want, want_grads = _reference(params, tokens, MODEL)
    logits, aux = model.forward(params, tokens, return_aux=True)
    want_logits, out = ref.forward(params, tokens, MODEL)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    assert float(out["loss"]) == pytest.approx(float(want), rel=1e-6)
    assert float(metrics["loss"]) == float(loss)
    np.testing.assert_allclose(
        [float(metrics[f"loop_loss_pass_{t + 1}"]) for t in range(3)],
        np.asarray(out["pass_logits_loss"]), rtol=2e-6)
    np.testing.assert_allclose(logits, want_logits, atol=5e-5)
    p = aux["loop_exit_p"]
    assert p.shape == (3, 2, SEQ)
    np.testing.assert_allclose(p[:, :, :-1], out["exit_p"], atol=2e-6)
    # the counters are the reference's exit distribution's
    assert float(metrics["loop_exit_mean_pass"]) == pytest.approx(float(
        jnp.mean(jnp.sum(out["exit_p"] * jnp.arange(1, 4)[:, None, None],
                         0))), rel=1e-5)
    entropy = -jnp.sum(out["exit_p"] * jnp.log(out["exit_p"]), 0)
    assert float(metrics["loop_exit_entropy"]) == pytest.approx(
        float(jnp.mean(entropy)) / np.log(3), rel=1e-5)
    assert jax.tree.structure(grads) == jax.tree.structure(want_grads)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want_grads)):
        assert float(jnp.max(jnp.abs(b))) > 0, jax.tree_util.keystr(path)
        # the floor: a gate bias's gradient is a sum of ten thousand
        # terms that cancel to a thousandth of their size
        np.testing.assert_allclose(
            a, b, atol=2e-7 + 1e-5 * float(jnp.max(jnp.abs(b))), rtol=1e-4,
            err_msg=jax.tree_util.keystr(path))


def test_one_pass_has_no_gate_and_the_plain_loss(tokens):
    """``loop_passes=1``: the sandwich block once, no gate in the tree,
    the loss the one pass's cross-entropy."""
    model = _gpt(loop_passes=1, exit_gate=False, exit_beta=0.0)
    params = _params(model)
    assert "exit_gate" not in params
    one = dict(MODEL, loop_passes=1, exit_gate=False, exit_beta=0.0)
    (loss, metrics), grads = _step(model, params, tokens)
    want, want_grads = _reference(params, tokens, one)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)
    assert float(metrics["loop_loss_pass_1"]) == pytest.approx(float(loss),
                                                               rel=1e-6)
    assert float(metrics["loop_exit_entropy"]) == 0.0
    assert float(metrics["loop_exit_mean_pass"]) == pytest.approx(1.0)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(
            a, b, atol=2e-7 + 1e-5 * float(jnp.max(jnp.abs(b))), rtol=1e-4)


# --------------------------------------------------------------------- #
# shared weights                                                         #
# --------------------------------------------------------------------- #
def test_shared_weights_gradient_is_the_sum_over_an_unrolled_stack(tokens):
    """The same blocks stood ``loop_passes`` times in a Python loop, each
    pass on a COPY of the layers: the looped program's gradient of the
    shared layers is the sum of the copies' gradients, and no copy's
    alone."""
    model = _gpt()
    params = _params(model)

    def unrolled(copies):
        h = model._embed_lookup(params, tokens)
        pos, states = jnp.arange(SEQ), []
        for layers in copies:
            for i in range(MODEL["n_layers"]):
                h, _, _ = model._block(
                    h, jax.tree.map(lambda a: a[i], layers), pos, "attn",
                    "dense")
            h = model._rms_norm(h, params["ln_f"])
            states.append(h)
        return model._loop_loss(params, jnp.stack(states), tokens)[0]

    copies = [params["layers"]] * MODEL["loop_passes"]
    loss, per_copy = jax.jit(jax.value_and_grad(unrolled))(copies)
    (want, _), grads = _step(model, params, tokens)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    total = jax.tree.map(lambda *g: sum(g), *per_copy)
    for (path, a), b, first in zip(
            jax.tree_util.tree_leaves_with_path(grads["layers"]),
            jax.tree.leaves(total), jax.tree.leaves(per_copy[0])):
        scale = float(jnp.max(jnp.abs(b)))
        np.testing.assert_allclose(a, b, atol=2e-5 * scale, rtol=1e-4,
                                   err_msg=jax.tree_util.keystr(path))
        assert float(jnp.max(jnp.abs(a - first))) > 1e-2 * scale


# --------------------------------------------------------------------- #
# the exit distribution                                                  #
# --------------------------------------------------------------------- #
def test_exit_distribution_sums_to_one_and_ends_in_the_survival(tokens):
    model = _gpt(loop_passes=4)
    params = _params(model)
    _, aux = model.forward(params, tokens, return_aux=True)
    p = aux["loop_exit_p"]
    assert p.shape == (4, 2, SEQ) and float(p.min()) > 0
    np.testing.assert_allclose(jnp.sum(p, 0), 1.0, atol=1e-6)
    # the gates back out of p: g_t = p_t / prod_{j<t} (1 - g_j)
    stay, gates = jnp.ones_like(p[0]), []
    for t in range(3):
        gates.append(p[t] / stay)
        stay = stay * (1.0 - gates[-1])
    np.testing.assert_allclose(p[3], stay, atol=1e-6)
    assert all(0 < float(g.min()) and float(g.max()) < 1 for g in gates)
    ref_p, ref_log_p = ref.exit_distribution(
        [jnp.log(g) - jnp.log1p(-g) for g in gates])
    np.testing.assert_allclose(ref_p, p, atol=1e-6)
    np.testing.assert_allclose(jnp.exp(ref_log_p), p, atol=1e-6)


@pytest.mark.parametrize("bias,taken", [(-40.0, 3), (40.0, 1)],
                         ids=["gates-shut", "gates-open"])
def test_pinned_gates_leave_one_passes_loss(tokens, bias, taken):
    """Gates pinned shut: nothing exits early, the loss is the last
    pass's cross-entropy alone and the entropy term is gone; pinned
    open, the first pass's."""
    model = _gpt()
    params = _params(model)
    params["exit_gate"] = {"w": jnp.zeros_like(params["exit_gate"]["w"]),
                           "b": jnp.full((1,), bias)}
    loss, metrics = model.training_step(params, tokens, None)
    assert float(loss) == pytest.approx(
        float(metrics[f"loop_loss_pass_{taken}"]), rel=1e-6)
    assert float(metrics["loop_exit_mean_pass"]) == pytest.approx(taken)
    assert float(metrics["loop_exit_entropy"]) == pytest.approx(0, abs=1e-6)
    want = jax.jit(lambda p: ref.lm_loss(p, tokens, MODEL))(params)
    assert float(loss) == pytest.approx(float(want), rel=2e-6)


# --------------------------------------------------------------------- #
# the weighted fused loss                                                #
# --------------------------------------------------------------------- #
def _loss_operands(rows, d=32, vocab=96):
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    h = jax.random.normal(keys[0], (rows, d), jnp.float32)
    w = jax.random.normal(keys[1], (d, vocab), jnp.float32) * d ** -0.5
    targets = jax.random.randint(keys[2], (rows,), 0, vocab)
    targets = targets.at[::7].set(-1)               # masked rows
    weights = jax.random.uniform(keys[3], (rows,), jnp.float32)
    return h, w, targets, weights


def _materialised(h, w, targets, weights):
    valid = targets >= 0
    logits = h @ w
    row = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
        logits, jnp.where(valid, targets, 0)[:, None], -1)[:, 0]
    row = jnp.where(valid, row, 0.0)
    return jnp.sum(weights * row), row, jnp.where(
        valid, jnp.argmax(logits, -1) == targets, False)


@pytest.mark.parametrize("rows,chunk", [(64, 16), (50, 16), (40, 64)],
                         ids=["whole-chunks", "padded", "one-chunk"])
def test_weighted_fused_loss_against_the_materialised_one(rows, chunk):
    """Value, the rows' own losses and hits, and the gradient with
    respect to rows, head AND weights (the row's own loss)."""
    h, w, targets, weights = _loss_operands(rows)

    def fused(h_, w_, r_):
        total, row, hit = losses.fused_linear_cross_entropy(
            h_, w_, targets, chunk, row_weights=r_)
        return total, (row, hit)

    def plain(h_, w_, r_):
        total, row, hit = _materialised(h_, w_, targets, r_)
        return total, (row, hit)

    (total, (row, hit)), grads = jax.jit(jax.value_and_grad(
        fused, argnums=(0, 1, 2), has_aux=True))(h, w, weights)
    (want, (want_row, want_hit)), want_grads = jax.value_and_grad(
        plain, argnums=(0, 1, 2), has_aux=True)(h, w, weights)
    assert float(total) == pytest.approx(float(want), rel=1e-6)
    np.testing.assert_allclose(row, want_row, atol=1e-5)
    assert np.array_equal(np.asarray(hit) > 0, np.asarray(want_hit))
    for a, b in zip(grads, want_grads):
        np.testing.assert_allclose(a, b, atol=2e-5)
    np.testing.assert_allclose(grads[2], want_row, atol=1e-5)
    assert float(jnp.max(jnp.abs(grads[2][::7]))) == 0.0    # masked rows


def test_unit_weights_give_the_unweighted_loss():
    h, w, targets, _ = _loss_operands(64)
    mean, acc = losses.fused_linear_cross_entropy(h, w, targets, 16)
    total, row, hit = losses.fused_linear_cross_entropy(
        h, w, targets, 16, row_weights=jnp.ones((64,), jnp.float32))
    n = float(jnp.sum(targets >= 0))
    assert float(total) / n == pytest.approx(float(mean), rel=1e-6)
    assert float(jnp.sum(hit)) / n == pytest.approx(float(acc), rel=1e-6)
    want_mean, _ = losses.linear_cross_entropy_reference(h, w, targets)
    assert float(mean) == pytest.approx(float(want_mean), rel=1e-6)


def test_weighted_fused_loss_over_a_data_sharded_batch(tokens):
    """Rows sharded over the data axis: each device streams its own rows
    and the sums are psum'd; the looped model trains through it."""
    mesh = mesh_lib.build_mesh(mesh_lib.MeshConfig(data=2),
                               devices=jax.devices()[:2])
    h, w, targets, weights = _loss_operands(64)

    def fused(h_, w_, r_, mesh_):
        return losses.fused_linear_cross_entropy(
            h_, w_, targets, 16, mesh=mesh_, row_weights=r_)[0]

    got = jax.jit(jax.value_and_grad(
        lambda *a: fused(*a, mesh), argnums=(0, 1, 2)))(h, w, weights)
    want = jax.value_and_grad(
        lambda *a: fused(*a, None), argnums=(0, 1, 2))(h, w, weights)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-6)
    for a, b in zip(got[1], want[1]):
        np.testing.assert_allclose(a, b, atol=2e-5)
    model, plain = _gpt(), _gpt()
    model.mesh = mesh
    params = _params(plain)
    loss = jax.jit(lambda p: model.training_step(p, tokens, None)[0])(params)
    assert float(loss) == pytest.approx(
        float(plain.training_step(params, tokens, None)[0]), rel=1e-5)


# --------------------------------------------------------------------- #
# remat and the named residuals                                          #
# --------------------------------------------------------------------- #
_KWALK = dict(MODEL, d_model=128, n_heads=2, attn_head_dim=None, d_ff=256,
              max_seq_len=256, flash_block_q=128, flash_block_k=128)


def test_named_residuals_are_kept_per_application(kernel_branch):
    """One forward kernel in the program's forward (the layer body, under
    the pass loop), none in the backward: the k-walk's output is kept
    for every one of the passes x layers applications and no pass runs
    it again."""
    forward, backward = _per_scan(_KWALK, jnp.zeros((1, 256), jnp.int32),
                                  "flash_fwd")
    assert sum(n for n, in forward) == 1
    assert sum(n for n, in backward) == 0


@pytest.mark.parametrize("passes", [2, 4])
def test_residuals_stack_over_passes_and_layers(passes):
    """What the backward keeps of the layers is stacked [passes, layers,
    ...]: each application's input, by remat."""
    model = _gpt(remat=True, loop_passes=passes)
    params = _params(model)
    batch = jnp.zeros((2, 32), jnp.int32)
    _, vjp = jax.vjp(lambda p: model.training_step(p, batch, None)[0],
                     params)
    kept = [x.shape for x in jax.tree.leaves(vjp)
            if hasattr(x, "shape") and x.shape[:2] == (passes, 2)]
    assert (passes, 2, 2, 32, 64) in kept       # [T, L, b, s, d]


# --------------------------------------------------------------------- #
# one layer body, and the scopes                                         #
# --------------------------------------------------------------------- #
def _compiled_text(passes):
    model = _gpt(remat=True, loop_passes=passes)
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    batch = jnp.zeros((2, 32), jnp.int32)
    return jax.jit(jax.grad(
        lambda p: model.training_step(p, batch, None)[0])).lower(
            params).compile().as_text()


def test_compiled_step_holds_one_layer_body_whatever_the_passes():
    """The pass loop is a loop in the program: the matmuls under
    ``gpt/layers`` stand as often at four passes as at two, and the
    scopes of the contract are in the op names."""
    texts = {passes: _compiled_text(passes) for passes in (2, 4)}

    def layer_dots(text):
        return sum(" dot(" in line.split("metadata")[0]
                   and "gpt/layers" in line for line in text.splitlines())

    assert layer_dots(texts[2]) == layer_dots(texts[4]) > 0
    for text in texts.values():
        names = set(re.findall(r'op_name="([^"]*)"', text))
        assert any(re.search(r"gpt/loop\b.*gpt/layers", n) for n in names)
        assert any("gpt/loop_exit" in n for n in names)
        assert any("gpt/loss" in n for n in names)
        assert not any("gpt/loss" in n and "gpt/loop_exit" in n
                       for n in names)
    assert len(texts[4]) < 1.15 * len(texts[2])


# --------------------------------------------------------------------- #
# refusals, by name                                                      #
# --------------------------------------------------------------------- #
def test_every_walker_of_one_uniform_stack_refuses_a_looped_one(tokens):
    model = _gpt()
    assert model.cfg.run_keys() == ("layers",)      # the old guard's test
    params = jax.jit(model.init_params)(jax.random.PRNGKey(0))
    cache = {"k": jnp.zeros((2, 1, 4, 8, 24)), "v": jnp.zeros((2, 1, 4, 8, 24))}
    one = jnp.zeros((1,), jnp.int32)
    calls = {
        "_prefill": lambda: model._prefill(params, tokens, SEQ),
        "_decode_token": lambda: model._decode_token(params, cache, one, 0),
        "_decode_chunk": lambda: model._decode_chunk(
            params, cache, tokens[:1, :4], 0),
        "decode_cache_alloc": lambda: model.decode_cache_alloc(1, 8),
        "decode_step_rows": lambda: model.decode_step_rows(
            params, cache, one, one),
        "paged_cache_alloc": lambda: model.paged_cache_alloc(4, 8),
        "decode_step_rows_paged": lambda: model.decode_step_rows_paged(
            params, cache, jnp.zeros((1, 1), jnp.int32), one, one),
        "decode_chunk_paged": lambda: model.decode_chunk_paged(
            params, cache, jnp.zeros((1,), jnp.int32), tokens[:1, :4], 0, 4),
        "generate": lambda: model.generate(params, tokens[:, :4], 2),
        "generate_beam": lambda: model.generate_beam(
            params, tokens[:1, :4], 2),
    }
    for name, call in calls.items():
        with pytest.raises(NotImplementedError, match="looped stack"):
            call()
    with pytest.raises(NotImplementedError, match="looped stack"):
        GPT.quantize_weights(params)
    with pytest.raises(NotImplementedError, match="looped stack"):
        model._uniform_stack_only("a walker")
    # a block with sandwich norms run ONCE is no uniform stack either
    once = _gpt(loop_passes=1, exit_gate=False, exit_beta=0.0)
    with pytest.raises(NotImplementedError, match="looped stack"):
        once._prefill(once.init_params(jax.random.PRNGKey(0)), tokens, SEQ)


@pytest.mark.parametrize("axis", ["pipeline", "tensor", "sequence"])
def test_model_axes_are_refused_by_name(tokens, axis):
    model = _gpt()
    model.mesh = mesh_lib.build_mesh(
        mesh_lib.MeshConfig(data=1, **{axis: 2}), devices=jax.devices()[:2])
    params = model.init_params(jax.random.PRNGKey(0))
    with pytest.raises(NotImplementedError,
                       match="looped stack|pipeline parallelism"):
        model.training_step(params, tokens, None)


@pytest.mark.parametrize("over,message", [
    (dict(gated_mlp=False), "gated_mlp=True"),
    (dict(qk_norm=True), "qk_norm=False"),
    (dict(rope_style="interleaved"), "rope_style='half'"),
    (dict(n_kv_heads=2), "n_kv_heads=None"),
    (dict(num_experts=4), "num_experts=1"),
    (dict(sliding_window=16), "sliding_window=None"),
    (dict(layer_types=["full_attention"] * 2), "layer_types=None"),
    (dict(exit_gate=False), "exit gate"),
    (dict(loop_passes=1), "exit gate"),
    (dict(dropout=0.1), "dropout"),
    (dict(z_loss=1e-4), "plain cross-entropy"),
    (dict(label_smoothing=0.1), "plain cross-entropy"),
], ids=lambda x: "-".join(x) if isinstance(x, dict) else None)
def test_a_looped_block_without_a_reference_is_refused(over, message):
    with pytest.raises(NotImplementedError, match=message):
        TransformerConfig(**{**MODEL, **over})


@pytest.mark.parametrize("over", [
    dict(loop_passes=2), dict(exit_gate=True), dict(exit_beta=0.05)],
    ids=lambda x: "-".join(x))
def test_loop_fields_need_the_looped_block(over):
    with pytest.raises(ValueError, match="belongs to the looped stack"):
        TransformerConfig(**{**GPT2, **over})
    with pytest.raises(ValueError, match="belongs to the looped stack"):
        TransformerConfig(**{**LFM2, **over})


def test_loop_passes_below_one_and_a_stray_beta_are_value_errors():
    with pytest.raises(ValueError, match="at least 1"):
        TransformerConfig(**{**MODEL, "loop_passes": 0})
    with pytest.raises(ValueError, match="exit_beta belongs"):
        TransformerConfig(**{**MODEL, "loop_passes": 1, "exit_gate": False})


def test_weight_decay_spares_norm_scales_and_the_gates_bias():
    model = _gpt()
    params = jax.tree.map(
        lambda a: jnp.ones(a.shape, a.dtype),
        jax.eval_shape(model.init_params, jax.random.PRNGKey(0)))
    tx = model.configure_optimizers()
    updates, _ = tx.update(jax.tree.map(jnp.zeros_like, params),
                           tx.init(params), params)
    moved = {jax.tree_util.keystr(path): float(jnp.max(jnp.abs(u)))
             for path, u in jax.tree_util.tree_leaves_with_path(updates)}
    still = {k for k, v in moved.items() if v == 0.0}
    assert still == {
        "['ln_f']", "['exit_gate']['b']", "['layers']['ln1']",
        "['layers']['ln1_post']", "['layers']['ln2']",
        "['layers']['ln2_post']"}


# --------------------------------------------------------------------- #
# with the new fields off: the parent's program                          #
# --------------------------------------------------------------------- #
# sha256 of the traced program's text (jaxpr, object addresses blanked),
# taken on the PARENT of the PR that brought the looped stack (commit
# abb630f) with a copy of this very function: a stack that sets none of
# the new fields traces the program it traced before them, equation for
# equation (the GPT-2-shaped stacks: loss and gradient; the two mixed
# stacks: the loss, whose gradient takes a quarter of a minute to
# trace).  A PR that changes one of these programs on purpose pins its
# own digest here: PR 34 the two that trace the gradient (the fused
# loss's forward rule makes dh and dw; 47f5cf62c04ddcd1 and
# 1d456fc52e8cb887 before it), PR 36 the two mixed stacks (the expert
# layer's token side is choice-major and ``rows_computed`` compares
# positions; 06993cb687eb30cc and d2e2caeaf8ac545a before it).  The
# materialised one kept its own: the call nobody differentiates is the
# program it was.
_PARENTS = {
    "gpt2": (GPT2, True, "623b250208429e4a"),
    "gpt2-remat": (dict(GPT2, remat=True), True, "ae04d61271fdf68a"),
    "gpt2-materialised": (dict(GPT2, fused_loss=False), True,
                          "3b040f274eb296f1"),
    "lfm2": (dict(LFM2, remat=True), False, "654fcc1d6699bcc8"),
    "latent": (dict(LATENT, remat=True), False, "936b251e67dd224f"),
}


@pytest.mark.parametrize("stack", sorted(_PARENTS))
def test_with_the_new_fields_off_the_traced_step_is_the_parents(stack):
    cfg, with_gradient, digest = _PARENTS[stack]
    model = GPT(TransformerConfig(**cfg), lr=1e-3)
    model.compute_dtype = jnp.float32
    params = jax.eval_shape(model.init_params, jax.random.PRNGKey(0))
    batch = jnp.zeros((2, 32), jnp.int32)

    def loss(p):
        return model.training_step(p, batch, None)[0]

    text = str(jax.make_jaxpr(
        jax.value_and_grad(loss) if with_gradient else loss)(params))
    text = re.sub(r"0x[0-9a-f]+", "0x", text)
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == digest
    assert (model.cfg.post_norms, model.cfg.loop_passes,
            model.cfg.exit_gate, model.cfg.exit_beta) == (False, 1, False, 0)
    assert "exit_gate" not in params and not any(
        "post" in jax.tree_util.keystr(path)
        for path, _ in jax.tree_util.tree_leaves_with_path(params))
