"""Fused linear cross-entropy: numerics + grads vs the materialized path.

The reference has no loss ops of its own (losses live in the user's torch
module, reference: ray_lightning/tests/utils.py:33-37); these tests pin the
framework's streaming LM-head op against optax / the naive matmul path.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_lightning_accelerators_tpu.ops.losses import (
    fused_linear_cross_entropy, linear_cross_entropy_reference)


def _case(rows=100, d=32, v=257, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    h = jnp.asarray(rng.normal(size=(rows, d)), dtype)
    w = jnp.asarray(rng.normal(size=(d, v)) * d ** -0.5, dtype)
    t = jnp.asarray(rng.integers(0, v, size=(rows,)), jnp.int32)
    return h, w, t


def test_matches_reference_loss_and_acc():
    h, w, t = _case()
    loss_f, acc_f = fused_linear_cross_entropy(h, w, t, 32)
    loss_r, acc_r = linear_cross_entropy_reference(h, w, t)
    np.testing.assert_allclose(loss_f, loss_r, rtol=1e-5)
    np.testing.assert_allclose(acc_f, acc_r, rtol=1e-6)


def test_matches_optax():
    h, w, t = _case(rows=64)
    loss_f, _ = fused_linear_cross_entropy(h, w, t, 64)
    logits = h @ w
    loss_o = optax.softmax_cross_entropy_with_integer_labels(logits, t).mean()
    np.testing.assert_allclose(loss_f, loss_o, rtol=1e-5)


@pytest.mark.parametrize("chunk", [16, 100, 128])
def test_chunking_invariance(chunk):
    h, w, t = _case(rows=100)
    loss_f, acc_f = fused_linear_cross_entropy(h, w, t, chunk)
    loss_r, acc_r = linear_cross_entropy_reference(h, w, t)
    np.testing.assert_allclose(loss_f, loss_r, rtol=1e-5)
    np.testing.assert_allclose(acc_f, acc_r, rtol=1e-6)


def test_grads_match_naive():
    h, w, t = _case(rows=96, d=16, v=99)

    def fused(h_, w_):
        return fused_linear_cross_entropy(h_, w_, t, 32)[0]

    def naive(h_, w_):
        return optax.softmax_cross_entropy_with_integer_labels(
            h_ @ w_, t).mean()

    gh_f, gw_f = jax.grad(fused, argnums=(0, 1))(h, w)
    gh_n, gw_n = jax.grad(naive, argnums=(0, 1))(h, w)
    np.testing.assert_allclose(gh_f, gh_n, atol=1e-6)
    np.testing.assert_allclose(gw_f, gw_n, atol=1e-6)


def test_masked_targets_ignored():
    h, w, t = _case(rows=64)
    t_masked = t.at[10:20].set(-1)
    loss_f, acc_f = fused_linear_cross_entropy(h, w, t_masked, 16)
    keep = np.r_[0:10, 20:64]
    loss_r, acc_r = linear_cross_entropy_reference(h[keep], w, t[keep])
    np.testing.assert_allclose(loss_f, loss_r, rtol=1e-5)
    np.testing.assert_allclose(acc_f, acc_r, rtol=1e-6)
    # masked rows get zero grad
    gh = jax.grad(
        lambda h_: fused_linear_cross_entropy(h_, w, t_masked, 16)[0])(h)
    np.testing.assert_allclose(gh[10:20], np.zeros((10, h.shape[1])))


def test_bf16_inputs_close_to_f32():
    h, w, t = _case(dtype=jnp.bfloat16)
    loss_f, _ = fused_linear_cross_entropy(h, w, t, 32)
    loss_r, _ = linear_cross_entropy_reference(
        h.astype(jnp.float32), w.astype(jnp.float32), t)
    np.testing.assert_allclose(float(loss_f), float(loss_r), rtol=2e-2)


def test_sharded_matches_unsharded():
    from ray_lightning_accelerators_tpu.parallel.mesh import (MeshConfig,
                                                              build_mesh)
    if jax.device_count() < 4:
        pytest.skip("needs >=4 devices")
    mesh = build_mesh(MeshConfig(data=-1, fsdp=2))
    h, w, t = _case(rows=64, d=16, v=99)

    def sharded(h_, w_):
        return fused_linear_cross_entropy(h_, w_, t, 8, mesh=mesh)[0]

    def local(h_, w_):
        return fused_linear_cross_entropy(h_, w_, t, 8)[0]

    P = jax.sharding.PartitionSpec
    hs = jax.device_put(h, jax.sharding.NamedSharding(
        mesh, P(("data", "fsdp"), None)))
    loss_s, acc_s = jax.jit(
        lambda h_, w_: fused_linear_cross_entropy(h_, w_, t, 8, mesh=mesh)
    )(hs, w)
    loss_l, acc_l = fused_linear_cross_entropy(h, w, t, 8)
    np.testing.assert_allclose(loss_s, loss_l, rtol=1e-5)
    np.testing.assert_allclose(acc_s, acc_l, rtol=1e-6)
    gh_s, gw_s = jax.jit(jax.grad(sharded, argnums=(0, 1)))(hs, w)
    gh_l, gw_l = jax.grad(local, argnums=(0, 1))(h, w)
    np.testing.assert_allclose(jax.device_get(gh_s), gh_l, atol=1e-6)
    np.testing.assert_allclose(jax.device_get(gw_s), gw_l, atol=1e-6)


def test_gpt_fused_vs_naive_loss():
    from ray_lightning_accelerators_tpu.models.transformer import (
        GPT, TransformerConfig)
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, 128, size=(2, 32)), jnp.int32)
    outs = {}
    for fused in (True, False):
        cfg = TransformerConfig(vocab_size=128, d_model=64, n_heads=2,
                                d_ff=128, n_layers=2, max_seq_len=32,
                                fused_loss=fused)
        model = GPT(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        loss, metrics = model.training_step(params, toks,
                                            jax.random.PRNGKey(1))
        grads = jax.grad(
            lambda p: model.training_step(p, toks, jax.random.PRNGKey(1))[0]
        )(params)
        outs[fused] = (float(loss), float(metrics["accuracy"]), grads)
    assert outs[True][0] == pytest.approx(outs[False][0], rel=1e-4)
    assert outs[True][1] == pytest.approx(outs[False][1], abs=1e-6)
    for a, b in zip(jax.tree.leaves(outs[True][2]),
                    jax.tree.leaves(outs[False][2])):
        np.testing.assert_allclose(a, b, atol=2e-5)


def test_label_smoothing_and_z_loss_values():
    """Fused op with eps/z matches the explicit formula on f32 inputs."""
    h, w, t = _case(rows=64, d=16, v=99)
    eps, zl = 0.1, 1e-3
    loss_f, _ = fused_linear_cross_entropy(h, w, t, 16,
                                           label_smoothing=eps, z_loss=zl)
    logits = np.asarray(h @ w, np.float64)
    lse = np.log(np.exp(logits - logits.max(-1, keepdims=True)).sum(-1)) \
        + logits.max(-1)
    tgt = logits[np.arange(64), np.asarray(t)]
    expect = (lse - (1 - eps) * tgt - (eps / 99) * logits.sum(-1)
              + zl * lse ** 2).mean()
    np.testing.assert_allclose(float(loss_f), expect, rtol=1e-5)
    # eps=z=0 reproduces the plain path exactly
    plain, _ = fused_linear_cross_entropy(h, w, t, 16)
    ref, _ = linear_cross_entropy_reference(h, w, t)
    np.testing.assert_allclose(plain, ref, rtol=1e-5)


def test_label_smoothing_z_loss_grads_match_autodiff():
    h, w, t = _case(rows=48, d=16, v=53)
    eps, zl = 0.05, 1e-2

    def fused(h_, w_):
        return fused_linear_cross_entropy(h_, w_, t, 16,
                                          label_smoothing=eps, z_loss=zl)[0]

    def naive(h_, w_):
        logits = h_ @ w_
        lse = jax.nn.logsumexp(logits, -1)
        tgt = jnp.take_along_axis(logits, t[:, None], -1)[:, 0]
        return (lse - (1 - eps) * tgt - (eps / 53) * logits.sum(-1)
                + zl * lse ** 2).mean()

    gh_f, gw_f = jax.grad(fused, argnums=(0, 1))(h, w)
    gh_n, gw_n = jax.grad(naive, argnums=(0, 1))(h, w)
    np.testing.assert_allclose(gh_f, gh_n, atol=1e-6)
    np.testing.assert_allclose(gw_f, gw_n, atol=1e-6)


def test_gpt_loss_shaping_fused_matches_naive():
    from ray_lightning_accelerators_tpu.models.transformer import (
        GPT, TransformerConfig)
    toks = jnp.asarray(
        np.random.default_rng(4).integers(0, 128, size=(2, 32)), jnp.int32)
    losses = {}
    for fused in (True, False):
        cfg = TransformerConfig(vocab_size=128, d_model=64, n_heads=2,
                                d_ff=128, n_layers=2, max_seq_len=32,
                                fused_loss=fused, label_smoothing=0.1,
                                z_loss=1e-3)
        model = GPT(cfg)
        params = model.init_params(jax.random.PRNGKey(0))
        loss, _ = model.training_step(params, toks, jax.random.PRNGKey(1))
        losses[fused] = float(loss)
    assert losses[True] == pytest.approx(losses[False], rel=1e-4)


# --------------------------------------------------------------------- #
# The forward rule makes the gradient: dh and dw are residuals, the      #
# backward a scale                                                       #
# --------------------------------------------------------------------- #
def _materialised(h, w, t, weights, eps, zl):
    """The fused op's first return from whole logits, for autodiff."""
    valid = t >= 0
    logits = h @ w
    lse = jax.nn.logsumexp(logits, -1)
    tgt = jnp.take_along_axis(logits, jnp.where(valid, t, 0)[:, None],
                              -1)[:, 0]
    row = jnp.where(valid, lse - (1 - eps) * tgt
                    - (eps / w.shape[1]) * logits.sum(-1) + zl * lse ** 2,
                    0.0)
    if weights is None:
        return row.sum() / jnp.maximum(valid.sum(), 1)
    return (weights * row).sum()


def _paths(t, chunk, eps=0.0, zl=0.0):
    """{path: (fused, reference, number of differentiated operands)}"""
    kw = dict(label_smoothing=eps, z_loss=zl)
    return {
        "mean": (lambda h, w, r: fused_linear_cross_entropy(
            h, w, t, chunk, **kw)[0],
            lambda h, w, r: _materialised(h, w, t, None, eps, zl), 2),
        "row-weights": (lambda h, w, r: fused_linear_cross_entropy(
            h, w, t, chunk, row_weights=r, **kw)[0],
            lambda h, w, r: _materialised(h, w, t, r, eps, zl), 3),
    }


def _masked_case(dtype=jnp.float32):
    """100 rows in chunks of 32 (the last one padded by 28), ten rows
    masked, a weight a row."""
    h, w, t = _case(rows=100, d=32, v=257, seed=3, dtype=dtype)
    r = jnp.asarray(np.random.default_rng(4).uniform(0.1, 1.0, 100),
                    jnp.float32)
    return h, w, t.at[40:50].set(-1), r


@pytest.mark.parametrize("shaping", [(0.0, 0.0), (0.1, 1e-2)],
                         ids=["plain", "smoothing+z"])
@pytest.mark.parametrize("path", ["mean", "row-weights"])
def test_vjp_with_a_cotangent_that_is_not_one_matches_autodiff(path,
                                                               shaping):
    h, w, t, r = _masked_case()
    fused, reference, n = _paths(t, 32, *shaping)[path]
    out_f, vjp_f = jax.vjp(fused, h, w, r)
    out_r, vjp_r = jax.vjp(reference, h, w, r)
    np.testing.assert_allclose(out_f, out_r, rtol=1e-5)
    grads = vjp_f(jnp.float32(0.37))[:n]
    for got, want in zip(grads, vjp_r(jnp.float32(0.37))):
        assert got.dtype == want.dtype and np.abs(want).max() > 1e-4
        np.testing.assert_allclose(got, want, atol=1e-5)
    np.testing.assert_allclose(grads[0][40:50], 0.0)


def _vocab_products(jaxpr, v):
    """The ``dot_general``s of a jaxpr, its sub-jaxprs included, that
    have a ``v``-sized dimension among their operands or their result."""
    return sum(
        (e.primitive.name == "dot_general" and any(
            v in x.aval.shape for x in (*e.invars, *e.outvars)))
        + sum(_vocab_products(j, v)
              for j in jax.core.jaxprs_in_params(e.params))
        for e in jaxpr.eqns)


@pytest.mark.parametrize("path", ["mean", "row-weights"])
def test_training_traces_three_vocabulary_products_and_a_call_one(path):
    """Logits, dh and dw: no second forward under differentiation, and no
    gradient work in a call nobody differentiates."""
    h, w, t, r = _masked_case(jnp.bfloat16)
    fused, _, n = _paths(t, 32, 0.1, 1e-2)[path]
    trained = jax.make_jaxpr(jax.value_and_grad(
        fused, argnums=tuple(range(n))))(h, w, r)
    assert _vocab_products(trained.jaxpr, 257) == 3
    assert _vocab_products(jax.make_jaxpr(fused)(h, w, r).jaxpr, 257) == 1


@pytest.mark.parametrize("path", ["mean", "row-weights"])
def test_residuals_are_dh_and_dw_not_the_operands(path):
    """What crosses from the forward to the backward: one float32
    ``[rows, d]`` (dh) and one float32 ``[d, V]`` (dw); neither the
    bfloat16 ``h`` nor ``w``, nor a chunk's logits."""
    h, w, t, r = _masked_case(jnp.bfloat16)
    _, vjp = jax.vjp(_paths(t, 32)[path][0], h, w, r)
    held = sorted((x.shape, str(x.dtype)) for x in jax.tree.leaves(vjp)
                  if getattr(x, "ndim", 0) >= 2)
    assert held == [((32, 257), "float32"), ((100, 32), "float32")]
    dh, dw = vjp(jnp.float32(0.37))[:2]
    assert (dh.dtype, dw.dtype) == (jnp.bfloat16, jnp.bfloat16)
