"""What a layer's remat keeps by name (``models/transformer.py``
``_KEPT_UNDER_REMAT``): the flash k-walk's output and log-sum-exp and the
plan the dropless expert layer's windows read.  The mechanism is decided
while the step is traced, so these tests count operations in the
gradient's jaxpr (the router's ``top_k`` and the two ``sort``s once a
sparse run, in the forward scan; the forward kernel once an attention run
at several k blocks, twice at one), and compare loss and gradients with
the same model under the stock policies, where every name is inert."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_lightning_accelerators_tpu.models import transformer
from ray_lightning_accelerators_tpu.models.transformer import (
    GPT, TransformerConfig)
from ray_lightning_accelerators_tpu.ops import attention, moe
from tests.test_lfm2 import MODEL as LFM2, _layer_params
from tests.test_nemotron_h import SHARE as LATENT

STOCK = {
    "nothing": jax.checkpoint_policies.nothing_saveable,
    "dots": jax.checkpoint_policies.dots_saveable,
    "dots_with_no_batch_dims":
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
    "everything": jax.checkpoint_policies.everything_saveable,
}
# the two small stacks, each with two runs that hold an expert layer
STACKS = {"lfm2": LFM2, "latent": LATENT}


@pytest.fixture
def inert(monkeypatch):
    """The stock policy of each name, passed straight in: no policy
    keeps a name (``everything`` keeps every value, named or not)."""
    monkeypatch.setattr(transformer, "_remat_policy", STOCK.__getitem__)


def _count(jaxpr, matches) -> int:
    """Equations ``matches`` takes, at any depth."""
    return sum(bool(matches(e)) + sum(
        _count(j, matches) for j in jax.core.jaxprs_in_params(e.params))
        for e in jaxpr.eqns)


def _scan_bodies(jaxpr, reverse: bool) -> list:
    """The bodies of the scans that run in the given direction,
    outermost first.  Only a layer run's backward runs in reverse."""
    out = []
    for e in jaxpr.eqns:
        if e.primitive.name == "scan":
            if e.params["reverse"] is reverse:
                out.append(e.params["jaxpr"].jaxpr)
        else:
            for j in jax.core.jaxprs_in_params(e.params):
                out.extend(_scan_bodies(j, reverse))
    return out


def _loss_and_grads(cfg, tokens, **over):
    """A fresh model and a fresh function every call: jax caches a trace
    by the function it was made from, and the policy is read in it."""
    model = GPT(TransformerConfig(**{**cfg, "remat": True, **over}), lr=1e-3)
    model.compute_dtype = jnp.float32
    params = model.init_params(jax.random.PRNGKey(0))
    return jax.value_and_grad(
        lambda p: model.training_step(p, tokens, None)[0]), params


def _per_scan(cfg, tokens, *names, **over) -> tuple:
    """For the forward scans and for the backward's, of the gradient's
    jaxpr: in each scan's body, how often each of ``names`` stands (a
    primitive's name, or a Pallas kernel's)."""
    fn, params = _loss_and_grads(cfg, tokens, **over)
    jaxpr = jax.make_jaxpr(fn)(params).jaxpr
    return tuple([tuple(_count(body, lambda e: name in (
                      e.primitive.name, e.params.get("name")))
                        for name in names)
                  for body in _scan_bodies(jaxpr, reverse)]
                 for reverse in (False, True))


def _routing_ops(cfg, policy) -> tuple:
    """``(top_k, sort)`` counts a scan, forward scans then backward."""
    return _per_scan(cfg, jnp.zeros((2, 32), jnp.int32), "top_k", "sort",
                     remat_policy=policy)


@pytest.mark.parametrize("policy", sorted(STOCK))
@pytest.mark.parametrize("stack", sorted(STACKS))
def test_backward_runs_neither_the_router_nor_the_sorts(stack, policy):
    cfg = STACKS[stack]
    forward, backward = _routing_ops(cfg, policy)
    assert [n for n in forward if n != (0, 0)] == [(1, 2)] * 2
    assert backward == [(0, 0)] * len(TransformerConfig(**cfg).layer_runs())


def test_stock_nothing_policy_runs_them_twice(inert):
    """What the names save: under the stock policy the backward scan of
    every sparse run holds the router's ``top_k`` and both sorts."""
    forward, backward = _routing_ops(LFM2, "nothing")
    # the loss's one scan (two until its forward rule made the gradient)
    assert sorted(forward) == sorted(backward + [(0, 0)])
    assert backward.count((1, 2)) == 2


@pytest.mark.parametrize("policy", sorted(STOCK))
@pytest.mark.parametrize("stack", ["lfm2", "latent", "lfm2_all_held"])
def test_named_residuals_change_no_bit(stack, policy, monkeypatch):
    """Loss and every gradient equal those under the stock policy bit
    for bit: a kept value is the value a second run would have produced.
    Op by op: compiled whole, the CPU's compiler fuses the two programs
    differently and some gradients round one unit in the last place
    apart.  With every expert held the combine weights carry the
    router's gradient through their name."""
    cfg = (dict(LFM2, moe_experts_held=None) if stack == "lfm2_all_held"
           else STACKS[stack])
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                cfg["vocab_size"])

    def run():
        fn, params = _loss_and_grads(cfg, tokens, remat_policy=policy)
        with jax.disable_jit():
            return fn(params)

    loss, grads = run()
    monkeypatch.setattr(transformer, "_remat_policy", STOCK.__getitem__)
    want_loss, want_grads = run()
    assert float(loss) == float(want_loss)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(want_grads)):
        assert np.array_equal(a, b), jax.tree_util.keystr(path)
    if stack == "lfm2_all_held":
        assert float(jnp.max(jnp.abs(
            grads["layers_2"]["mlp"]["router"]))) > 0


def test_router_gradient_passes_through_the_named_weights():
    """All experts held: the layer's own gradient to the router under
    ``jax.checkpoint`` with the plan kept equals the one with nothing
    kept and the one without remat."""
    held = tuple(range(8))
    p = _layer_params()
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 32, 64), jnp.float32)

    def loss(p_, x_):
        y, _ = moe.dropless_moe(x_, p_, top_k=2, held=held, num_experts=8,
                                compute_dtype=jnp.float32)
        return jnp.sum(jnp.square(y))

    def grads(policy):
        fn = loss if policy is None else jax.checkpoint(loss, policy=policy)
        with jax.disable_jit():
            return jax.grad(fn, argnums=(0, 1))(p, x)

    kept = grads(jax.checkpoint_policies.save_only_these_names(moe.MOE_PLAN))
    assert float(jnp.max(jnp.abs(kept[0]["router"]))) > 0
    for other in (grads(STOCK["nothing"]), grads(None)):
        for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(other)):
            assert np.array_equal(a, b)


# --------------------------------------------------------------------- #
# the flash kernel's residuals                                           #
# --------------------------------------------------------------------- #
_ATTN = dict(vocab_size=256, d_model=128, n_heads=2, d_ff=256, n_layers=2,
             max_seq_len=256, remat=True)


def _flash_fwd_calls(block, policy) -> tuple:
    """Forward kernels in the forward scans and in the backward's."""
    forward, backward = _per_scan(
        _ATTN, jnp.zeros((1, 256), jnp.int32), "flash_fwd",
        remat_policy=policy, flash_block_q=block, flash_block_k=block)
    return sum(n for n, in forward), sum(n for n, in backward)


@pytest.fixture
def kernel_branch(monkeypatch):
    """``flash_attention`` on its kernel branch, as on the chip; only
    traced here."""
    monkeypatch.setattr(
        attention, "_use_pallas",
        lambda q, bq, bk: bq is not None and bk is not None)


@pytest.mark.parametrize("policy", sorted(STOCK))
def test_k_walk_runs_once_and_one_block_twice(kernel_branch, policy):
    """Several k blocks: output and log-sum-exp are named and the
    backward scan holds no forward kernel.  One block a sequence: no
    name, and every policy but ``everything`` runs it again."""
    assert _flash_fwd_calls(128, policy) == (1, 0)
    assert _flash_fwd_calls(256, policy) == (
        1, 0 if policy == "everything" else 1)


def test_stock_nothing_policy_runs_the_k_walk_twice(kernel_branch, inert):
    assert _flash_fwd_calls(128, "nothing") == (1, 1)


def test_kept_flash_residuals_change_no_bit(monkeypatch):
    """The kernels in interpret mode under a checkpointed attention
    call: with output and log-sum-exp kept the gradients are those with
    nothing kept."""
    for name in ("_flash_forward", "_flash_backward"):
        real = getattr(attention, name)
        monkeypatch.setattr(attention, name, functools.partial(
            lambda real, *a, interpret, **kw: real(*a, interpret=True, **kw),
            real))
    monkeypatch.setattr(attention, "_use_pallas", lambda q, bq, bk: True)
    q, k, v = (jax.random.normal(key, (1, 2, 256, 64), jnp.float32)
               for key in jax.random.split(jax.random.PRNGKey(2), 3))

    def loss(q_, k_, v_):
        out = attention.flash_attention(q_ * 1.5, k_, v_, True, None,
                                        128, 128)
        return jnp.sum(jnp.square(out))

    def grads(policy):
        return jax.jit(jax.grad(jax.checkpoint(loss, policy=policy),
                                argnums=(0, 1, 2)))(q, k, v)

    kept = grads(jax.checkpoint_policies.save_only_these_names(
        *attention.FLASH_RESIDUALS))
    for a, b in zip(kept, grads(STOCK["nothing"])):
        assert float(jnp.max(jnp.abs(b))) > 0
        assert np.array_equal(a, b)
