"""Pipeline parallelism: GPipe schedule must equal sequential layer apply,
forward and backward, standalone and inside the GPT model."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_accelerators_tpu import (Accelerator, DataLoader,
                                            MeshConfig, Trainer)
from ray_lightning_accelerators_tpu.parallel import mesh as mesh_lib
from ray_lightning_accelerators_tpu.parallel.pipeline import pipeline_apply

from .test_transformer import TokenDataset, _fit, tiny_cfg


def _layers_params(n_layers=4, d=16, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), n_layers)
    return {"w": jax.vmap(lambda kk: jax.random.normal(kk, (d, d)) * 0.3)(k),
            "b": jnp.zeros((n_layers, d))}


def _stage_fn(params, x):
    def one(carry, lp):
        return jnp.tanh(carry @ lp["w"] + lp["b"]), None

    out, _ = jax.lax.scan(one, x, params)
    return out


@pytest.mark.parametrize("stages,microbatches", [(2, 4), (4, 2), (4, 8)])
def test_pipeline_matches_sequential(stages, microbatches):
    mesh = Accelerator(MeshConfig(data=1, pipeline=stages)).build_mesh()
    params = _layers_params()
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 16))
    ref = _stage_fn(params, x)
    out = jax.jit(lambda p, x: pipeline_apply(
        _stage_fn, p, x, mesh, microbatches))(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


def test_pipeline_gradients_match():
    mesh = Accelerator(MeshConfig(data=1, pipeline=4)).build_mesh()
    params = _layers_params()
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16))

    def loss_pp(p):
        return jnp.sum(pipeline_apply(_stage_fn, p, x, mesh, 4) ** 2)

    def loss_seq(p):
        return jnp.sum(_stage_fn(p, x) ** 2)

    g1 = jax.jit(jax.grad(loss_pp))(params)
    g2 = jax.grad(loss_seq)(params)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_gpt_trains_with_pipeline(tmpdir):
    """Full model under dp2 x pp2: trains below chance loss; stage params
    actually sharded over the pipeline axis."""
    trainer, model = _fit(tmpdir, MeshConfig(data=2, pipeline=2),
                          batch_size=16, max_epochs=2,
                          n_layers=2, pipeline_microbatches=4)
    assert trainer.callback_metrics["val_loss"] < jnp.log(128)
    wq = trainer._state.params["layers"]["attn"]["wq"]
    assert wq.sharding.spec[0] == "pipeline"


def test_gpt_pipeline_matches_plain(tmpdir):
    """pp2 and plain dp give the same learning trajectory on the same data
    (same global batches, same init)."""
    t1, m1 = _fit(tmpdir, MeshConfig(data=1, pipeline=2), batch_size=8,
                  max_epochs=1, n_layers=2, pipeline_microbatches=2)
    t2, m2 = _fit(tmpdir, MeshConfig(data=1), batch_size=8,
                  max_epochs=1, n_layers=2)
    for a, b in zip(jax.tree.leaves(m1.params), jax.tree.leaves(m2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-3)


def test_dp_times_pp_matches_sequential():
    """dp>1 x pp>1: the partial-manual shard_map (only ``pipeline``
    manual, the data axis left to the partitioner) must give the
    sequential result on a batch sharded over ``data``."""
    mesh = Accelerator(MeshConfig(data=2, pipeline=2)).build_mesh()
    params = _layers_params(n_layers=4)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16))
    out = jax.jit(lambda p, xx: pipeline_apply(
        _stage_fn, p, xx, mesh, 4))(params, x)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(_stage_fn(params, x)),
                               atol=1e-5, rtol=1e-5)
