"""Kernel correctness: pallas flash attention (interpreter mode) vs the XLA
reference, including causal masking and the custom-vjp gradient path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_accelerators_tpu.ops import attention
from ray_lightning_accelerators_tpu.ops.attention import (
    attention_reference, causal_tiles, flash_attention,
    flash_attention_grads_interpret, flash_attention_interpret,
    kwalk_fused)


# CPU runs both paths in strict f32; on real TPU the MXU's default matmul
# precision (bf16-grade passes) plus the online-softmax accumulation order
# shifts values by up to ~1e-2 absolute on O(1) outputs
_ON_CPU = jax.default_backend() == "cpu"
_TOL = (dict(atol=2e-5, rtol=2e-5) if _ON_CPU
        else dict(atol=2e-2, rtol=5e-2))
_GRAD_TOL = (dict(atol=1e-4, rtol=1e-4) if _ON_CPU
             else dict(atol=5e-2, rtol=1e-1))


def _qkv(b=2, h=2, s=256, d=64, seed=0, dtype=jnp.float32):
    rng = jax.random.PRNGKey(seed)
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (b, h, s, d), dtype)
    k = jax.random.normal(kk, (b, h, s, d), dtype)
    v = jax.random.normal(kv, (b, h, s, d), dtype)
    return q, k, v


# (seq, block) pairs: a k-walk of 128-blocks; one block that holds the whole
# causal square, which the kernels walk as a triangle of 2, 2 and 4 strips
# (causal_tiles: 3/4, 3/4, 10/16); a 2x2 grid whose diagonal blocks would
# tile but run as masked squares
_ONE_BLOCK = [(256, 256), (512, 512), (1024, 1024)]
_GRIDS = [(256, 128)] + _ONE_BLOCK + [(512, 256)]


@pytest.mark.parametrize("causal,seq,block",
                         [(False, 256, 128), (False, 512, 512)]
                         + [(True, s, b) for s, b in _GRIDS])
def test_flash_matches_reference(causal, seq, block):
    q, k, v = _qkv(b=1, s=seq)
    ref = attention_reference(q, k, v, causal=causal)
    out = flash_attention_interpret(q, k, v, causal=causal,
                                    block_q=block, block_k=block)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **_TOL)


@pytest.mark.parametrize("shape,causal,window,want", [
    ((1024, 1024, 1024, 1024), True, None, (10, 16)),   # both cells: t 256
    ((512, 512, 512, 512), True, None, (3, 4)),
    ((256, 256, 256, 256), True, None, (3, 4)),
    ((384, 384, 384, 384), True, None, (6, 9)),         # 128 divides, 192 no
    ((128, 128, 128, 128), True, None, (1, 1)),         # a single tile
    ((1024, 1024, 1024, 1024), False, None, (1, 1)),
    ((1024, 1024, 1024, 1024), True, 48, (1, 1)),       # window: the square
    ((1024, 1024, 512, 512), True, None, (3, 4)),       # whole-block skip
    ((1024, 1024, 512, 512), False, None, (4, 4)),
    ((1024, 1024, 512, 1024), True, None, (2, 2)),
    ((1024, 1024, 128, 128), True, 128, (15, 64)),      # diagonal + one left
])
def test_causal_tiles_counts_what_the_kernels_visit(shape, causal, window,
                                                    want):
    assert causal_tiles(*shape, causal, window) == want


def test_flash_uneven_blocks():
    q, k, v = _qkv(s=384)
    ref = attention_reference(q, k, v, causal=True)
    out = flash_attention_interpret(q, k, v, causal=True,
                                    block_q=128, block_k=128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **_TOL)


def test_flash_gradients_match():
    q, k, v = _qkv(b=1, h=2, s=128, d=64)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   **_GRAD_TOL)


def test_cpu_dispatch_falls_back():
    """On the CPU test backend the public entry must route to XLA."""
    q, k, v = _qkv(s=64)
    out = flash_attention(q, k, v, False)
    ref = attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def _brute_window(q, k, v, window):
    ql = q.shape[2]
    qi = np.arange(ql)[:, None]
    ki = np.arange(ql)[None, :]
    mask = (qi >= ki) & (qi - ki < window)
    logits = np.einsum("bhqd,bhkd->bhqk", np.asarray(q), np.asarray(k)) \
        * q.shape[-1] ** -0.5
    logits = np.where(mask[None, None], logits, -1e30)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,bhkd->bhqd", p, np.asarray(v))


@pytest.mark.parametrize("window", [64, 100, 256])
def test_sliding_window_reference(window):
    q, k, v = _qkv(s=256)
    out = attention_reference(q, k, v, causal=True, window=window)
    ref = _brute_window(q, k, v, window)
    np.testing.assert_allclose(np.asarray(out), ref, **_TOL)


@pytest.mark.parametrize("window,seq,block", [
    (64, 256, 128), (100, 256, 128),
    (48, 512, 512), (300, 512, 512)])   # one block; 300 is wider than a tile
def test_sliding_window_kernel_matches(window, seq, block):
    q, k, v = _qkv(b=1, s=seq)
    out = flash_attention_interpret(q, k, v, causal=True, block_q=block,
                                    block_k=block, window=window)
    ref = attention_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **_TOL)


def test_sliding_window_gradients():
    q, k, v = _qkv(b=1, h=2, s=128, d=64)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, True, window=48) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(attention_reference(q, k, v, causal=True,
                                           window=48) ** 2)

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   **_GRAD_TOL)


# (seq, block_q, block_k, causal, window) of the k-walk (several key
# blocks): two blocks a side, none masked / causal / a window inside the
# diagonal's neighbour; four key blocks; q blocks smaller and larger than
# the key blocks; a window of 96 that skips whole blocks on the left
_KWALK = [(256, 128, bk, c, w) for bk in (128, 256)
          for c, w in ((False, None), (True, None), (True, 96))
          if bk < 256] + [
    (1024, 256, 256, True, None), (512, 128, 256, True, None),
    (512, 256, 128, True, None), (512, 128, 128, True, 96)]


def _grads_match(seq, block_q, block_k, causal, window):
    q, k, v = _qkv(b=1, h=2, s=seq, d=64)
    g = jax.random.normal(jax.random.PRNGKey(7), q.shape, q.dtype)

    def ref(q_, k_, v_):
        return attention_reference(q_, k_, v_, causal=causal, window=window)

    _, vjp = jax.vjp(ref, q, k, v)
    want = vjp(g)
    got = flash_attention_grads_interpret(q, k, v, g, causal=causal,
                                          block_q=block_q, block_k=block_k,
                                          window=window)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), **_GRAD_TOL)


@pytest.mark.parametrize("seq,block_q,block_k,causal,window", _KWALK + [
    (256, 128, 256, c, w)
    for c, w in ((False, None), (True, None), (True, 96))
] + [(s, b, b, True, None) for s, b in _ONE_BLOCK + [(512, 256)]] + [
    (512, 512, 512, False, None), (512, 512, 512, True, 48),
    (512, 512, 512, True, 300)])
def test_flash_backward_kernels_match(seq, block_q, block_k, causal, window):
    """The hand-written backward kernels must reproduce XLA autodiff of
    the reference: block_k < seq exercises the one-pass k-walk kernel
    (dq held for the whole head, dk/dv over the q walk), block_k == seq
    the FUSED single-k-block kernel that shares the score recompute --
    as one masked square (block_q < block_k, a window, non-causal) and,
    where one block holds the causal square, as the triangle of
    strips."""
    _grads_match(seq, block_q, block_k, causal, window)


@pytest.mark.parametrize("seq,block_q,block_k,causal,window", _KWALK)
def test_flash_backward_split_pair_matches(monkeypatch, seq, block_q,
                                           block_k, causal, window):
    """Past the k-walk kernel's VMEM budget a dq pass and a dk/dv pass
    each recompute the blocks: the same walks through that pair."""
    monkeypatch.setattr(attention, "kwalk_fused", lambda q_len, d: False)
    _grads_match(seq, block_q, block_k, causal, window)


@pytest.mark.parametrize("q_len,d,fused", [
    (8192, 64, True),       # train-lfm2-moe-8k: 64 lanes pad to 128, 4 MiB
    (8192, 128, True),      # train-ouro-loop-8k, train-nemotron3-ssm-8k
    (1024, 64, True),
    (16384, 128, False),    # 8 MiB of dq: the split pair
    (16384, 64, False),
    (8192, 256, False),
])
def test_kwalk_backward_is_one_pass_while_a_heads_dq_fits_vmem(q_len, d,
                                                                fused):
    assert kwalk_fused(q_len, d) is fused
