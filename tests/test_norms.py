"""Pallas RMSNorm/LayerNorm kernels vs jnp references (interpret mode on
CPU; the real kernel path when run with RLA_TPU_TEST_PLATFORM on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_accelerators_tpu.ops.norms import (
    layer_norm, layer_norm_interpret, layer_norm_reference, rms_norm,
    rms_norm_interpret, rms_norm_reference)

_ON_CPU = jax.default_backend() == "cpu"
_TOL = (dict(atol=1e-6, rtol=1e-6) if _ON_CPU
        else dict(atol=1e-2, rtol=2e-2))


def _x(shape=(4, 96, 256), seed=0, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape, dtype) * 3.0


@pytest.mark.parametrize("shape", [(4, 96, 256), (8, 128), (2, 7, 384)])
def test_rms_interpret_matches_reference(shape):
    x = _x(shape)
    scale = jnp.linspace(0.5, 1.5, shape[-1])
    out = rms_norm_interpret(x, scale)
    ref = rms_norm_reference(x, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-6, rtol=1e-6)


@pytest.mark.parametrize("shape", [(4, 96, 256), (8, 128)])
def test_ln_interpret_matches_reference(shape):
    x = _x(shape)
    scale = jnp.linspace(0.5, 1.5, shape[-1])
    bias = jnp.linspace(-1.0, 1.0, shape[-1])
    out = layer_norm_interpret(x, scale, bias)
    ref = layer_norm_reference(x, scale, bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-6, rtol=1e-6)


def test_public_entries_match_reference():
    """On CPU the public ops fall back to the reference; on TPU they run
    the Pallas kernels — either way values must agree."""
    x = _x((4, 64, 256))
    scale = jnp.ones((256,)) * 1.2
    bias = jnp.zeros((256,))
    np.testing.assert_allclose(
        np.asarray(rms_norm(x, scale)),
        np.asarray(rms_norm_reference(x, scale)), **_TOL)
    np.testing.assert_allclose(
        np.asarray(layer_norm(x, scale, bias)),
        np.asarray(layer_norm_reference(x, scale, bias)), **_TOL)


def test_rms_gradients_match():
    x = _x((2, 32, 256))
    scale = jnp.linspace(0.5, 1.5, 256)

    gx, gs = jax.grad(lambda x_, s_: jnp.sum(rms_norm(x_, s_) ** 2),
                      argnums=(0, 1))(x, scale)
    rx, rs = jax.grad(
        lambda x_, s_: jnp.sum(rms_norm_reference(x_, s_) ** 2),
        argnums=(0, 1))(x, scale)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx), **_TOL)
    np.testing.assert_allclose(np.asarray(gs), np.asarray(rs), **_TOL)


def test_bf16_stays_bf16():
    x = _x((4, 128), dtype=jnp.bfloat16)
    scale = jnp.ones((128,), jnp.bfloat16)
    assert rms_norm(x, scale).dtype == jnp.bfloat16
    assert rms_norm_interpret(x, scale).dtype == jnp.bfloat16


def test_row_block_is_a_sublane_multiple_or_the_whole_array():
    """The TPU lowering refuses any other row block (a 57-token prompt
    used to become 57 one-row blocks and failed on the chip)."""
    from ray_lightning_accelerators_tpu.ops.norms import _row_block
    assert _row_block(8192) == 512
    assert _row_block(24) == 8
    assert _row_block(57) == 57 and _row_block(4) == 4
    assert _row_block(1031) is None  # long and odd: the jnp reference
    # a block is at most 512 rows of 2048: at d_model 4096 the kernel's
    # VMEM holds 256 rows (the described compile refused 512, PR 31)
    assert _row_block(8192, 1024) == _row_block(8192, 2048) == 512
    assert _row_block(8192, 4096) == 256 and _row_block(8192, 8192) == 128
    for rows in range(1, 1100):
        br = _row_block(rows)
        assert br is None or (rows % br == 0
                              and (br % 8 == 0 or br == rows))

