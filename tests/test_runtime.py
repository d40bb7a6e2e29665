"""Actor runtime tests — behavioral port of the reference's actor-lifecycle
assertions (reference: tests/test_ddp.py:29-42 actor counts + DEAD-after-fit;
ray_ddp.py:21-27 env RPC; util.py:96-109 result pump) on the from-scratch
multiprocessing actor system, plus a real 2-process jax.distributed
all-reduce."""

import os
import time

import numpy as np
import pytest

from ray_lightning_accelerators_tpu.runtime.actors import (ActorPool,
                                                           RemoteError,
                                                           Worker)
from ray_lightning_accelerators_tpu.runtime.queue import (TrampolineQueue,
                                                          process_results)


def _sq(x):
    return x * x


def _getenv(k):
    return os.environ.get(k)


def _boom():
    raise ValueError("worker exploded")


def _pid():
    return os.getpid()


def _echo_big(arr):
    return arr * 2


def test_large_payloads_do_not_deadlock():
    """Requests/results far beyond the OS pipe buffer (~64KiB) must flow
    while earlier results are still in flight (regression: a single lock held
    across a blocking send could three-way-deadlock sender/collector/worker).
    """
    big = np.ones(1_000_000, dtype=np.float32)  # ~4MB each way
    with ActorPool(1) as pool:
        w = pool.workers[0]
        futs = [w.execute(_echo_big, big) for _ in range(4)]
        for f in futs:
            np.testing.assert_array_equal(f.result(timeout=60), big * 2)


def test_pool_executes_in_parallel_processes():
    with ActorPool(2) as pool:
        futs = pool.execute_all(_pid)
        pids = [f.result(timeout=60) for f in futs]
    assert len(set(pids)) == 2
    assert all(p != os.getpid() for p in pids)


def test_execute_returns_results_in_order():
    with ActorPool(1) as pool:
        futs = [pool.workers[0].execute(_sq, i) for i in range(5)]
        assert [f.result(timeout=60) for f in futs] == [0, 1, 4, 9, 16]


def test_env_propagation_prefork_and_rpc():
    """Env must be settable pre-fork (TPU topology vars) and via RPC
    (reference: ray_ddp.py:21-23,154-159)."""
    with ActorPool(2, env_per_worker=[{"RLA_T": "a"}, {"RLA_T": "b"}]) as pool:
        vals = [f.result(timeout=60)
                for f in pool.execute_all(_getenv, "RLA_T")]
        assert vals == ["a", "b"]
        pool.set_env_vars({"RLA_T2": "77"})
        vals = [f.result(timeout=60)
                for f in pool.execute_all(_getenv, "RLA_T2")]
        assert vals == ["77", "77"]


def test_remote_exception_carries_traceback():
    with ActorPool(1) as pool:
        fut = pool.workers[0].execute(_boom)
        with pytest.raises(RemoteError, match="worker exploded"):
            fut.result(timeout=60)


def test_closures_ship_via_cloudpickle():
    factor = 7
    with ActorPool(1) as pool:
        fut = pool.workers[0].execute(lambda x: x * factor, 6)
        assert fut.result(timeout=60) == 42


def test_local_ranks_census():
    with ActorPool(3) as pool:
        assert pool.local_ranks() == [0, 1, 2]  # same node -> 0,1,2


def test_workers_dead_after_shutdown():
    pool = ActorPool(2)
    procs = [w._proc for w in pool.workers]
    pool.shutdown()
    deadline = time.time() + 10
    while time.time() < deadline and any(p.is_alive() for p in procs):
        time.sleep(0.1)
    assert not any(p.is_alive() for p in procs)


def test_queue_shutdown_idempotent_drains_and_rejects():
    """TrampolineQueue.shutdown(): safe with requests still enqueued —
    drains them unexecuted (the caller cancels them typed), rejects later
    put()s with QueueShutdown, and is idempotent.  The serve engine's
    cancellation path rides this."""
    from ray_lightning_accelerators_tpu.runtime.queue import QueueShutdown

    ran = []
    q = TrampolineQueue()
    q.put((0, lambda: ran.append("a")))
    q.put((1, lambda: ran.append("b")))
    drained = q.shutdown()
    assert [r for r, _ in drained] == [0, 1]
    assert ran == []                      # drained, never executed
    assert q.closed
    assert q.get_nowait() is None
    assert q.shutdown() == []             # idempotent no-op
    with pytest.raises(QueueShutdown):
        q.put((2, lambda: ran.append("c")))
    assert ran == []


def test_process_results_pumps_queue_during_run():
    q = TrampolineQueue()
    seen = []
    q.put((0, lambda: seen.append("early")))
    with ActorPool(1) as pool:
        futs = pool.execute_all(time.sleep, 0.3)
        q.put((0, lambda: seen.append("mid")))
        process_results(futs, q)
    assert seen == ["early", "mid"]


def _distributed_psum(process_id, coord, nprocs):
    from ray_lightning_accelerators_tpu.runtime.bootstrap import (
        initialize_worker)
    initialize_worker(coord, nprocs, process_id, platform="cpu",
                      cpu_devices_per_process=1)
    import jax
    import jax.numpy as jnp

    assert jax.process_count() == nprocs
    out = jax.shard_map(
        lambda x: jax.lax.psum(x, "i"),
        mesh=jax.sharding.Mesh(jax.devices(), ("i",)),
        in_specs=jax.sharding.PartitionSpec("i"),
        out_specs=jax.sharding.PartitionSpec())(
            jnp.arange(float(nprocs)))
    return float(np.asarray(out)[0])


@pytest.mark.slow
def test_two_process_jax_distributed_allreduce():
    """The L1 bootstrap really forms a 2-process world whose psum crosses
    process boundaries (the reference's init_process_group analog,
    ray_ddp.py:222-237)."""
    from ray_lightning_accelerators_tpu.runtime.bootstrap import (
        pick_coordinator_address)

    coord = pick_coordinator_address()
    env = {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""}
    with ActorPool(2, env_per_worker=[dict(env), dict(env)]) as pool:
        futs = pool.execute_per_worker(
            _distributed_psum, [(0, coord, 2), (1, coord, 2)])
        results = [f.result(timeout=180) for f in futs]
    assert results == [1.0, 1.0]  # 0 + 1 summed across processes


def _distributed_fit(process_id, coord, nprocs):
    from ray_lightning_accelerators_tpu.runtime.bootstrap import (
        initialize_worker)
    initialize_worker(coord, nprocs, process_id, platform="cpu",
                      cpu_devices_per_process=2)
    import jax
    import numpy as np

    # device-binding contract (reference pins the device/env mapping,
    # reference: tests/test_ddp_gpu.py:89-95): each process sees exactly
    # its cpu_devices_per_process devices, the global mesh spans all
    # processes' devices, and the rank mapping holds
    assert len(jax.local_devices()) == 2
    assert jax.device_count() == 2 * nprocs
    assert jax.process_index() == process_id
    assert {d.process_index for d in jax.devices()} == set(range(nprocs))
    from ray_lightning_accelerators_tpu import DataLoader, Trainer
    from ray_lightning_accelerators_tpu.data.loader import ArrayDataset
    from tests.utils import BoringModel

    x = np.random.default_rng(0).normal(size=(64, 32)).astype("float32")
    model = BoringModel()
    trainer = Trainer(max_epochs=2, precision="f32", seed=0,
                      enable_checkpointing=False,
                      default_root_dir=f"/tmp/dist_fit_{process_id}")
    trainer.fit(model, DataLoader(ArrayDataset(x), batch_size=8))
    leaf = np.asarray(jax.tree.leaves(model.params)[0], dtype=np.float64)
    return (trainer.global_step, float(leaf.sum()),
            float(trainer.callback_metrics["loss"]))


@pytest.mark.slow
def test_two_process_full_training():
    """End-to-end Trainer.fit across a REAL 2-process jax.distributed world
    (2 procs x 2 cpu devices = 4-device mesh): per-process sampler shards,
    cross-process batch assembly, gradient psum via sharding.  Both ranks
    must agree on step count and final (SPMD-replicated) weights -- the
    multi-host analog of the reference's DDP weight-sync guarantee."""
    from ray_lightning_accelerators_tpu.runtime.bootstrap import (
        pick_coordinator_address)

    coord = pick_coordinator_address()
    env = {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""}
    with ActorPool(2, env_per_worker=[dict(env), dict(env)]) as pool:
        futs = pool.execute_per_worker(
            _distributed_fit, [(0, coord, 2), (1, coord, 2)])
        results = [f.result(timeout=300) for f in futs]
    steps0, wsum0, loss0 = results[0]
    steps1, wsum1, loss1 = results[1]
    # 64 samples / 2 replicas / batch 8 = 4 steps/epoch x 2 epochs
    assert steps0 == steps1 == 8
    assert wsum0 == pytest.approx(wsum1, rel=1e-6)
    assert loss0 == pytest.approx(loss1, rel=1e-5)
