"""Multi-machine launch path: per-host agents + RemoteWorkers.

Behavioral analog of the reference's multi-node capability (reference:
README.md:57-62 -- cluster fan-out; ray_lightning/ray_ddp.py:92-97 actor
placement on remote nodes; tests/test_ddp_gpu.py:106-117 the opt-in
multi-node test).  Two HostAgents on localhost stand in for two machines:
every byte between driver and worker crosses a real TCP socket, so the
same code path serves genuinely remote hosts.
"""

import os
import time

import numpy as np
import pytest

from ray_lightning_accelerators_tpu.runtime.actors import (ActorPool,
                                                           RemoteError)
from ray_lightning_accelerators_tpu.runtime.agent import (HostAgent,
                                                          RemoteWorker,
                                                          assign_agents,
                                                          coordinator_address_on)
from ray_lightning_accelerators_tpu.runtime.queue import (QueueClient,
                                                          QueueServer,
                                                          TrampolineQueue)


@pytest.fixture()
def two_agents():
    agents = [HostAgent(port=0, bind="127.0.0.1") for _ in range(2)]
    for a in agents:
        a.serve_in_background()
    yield [f"127.0.0.1:{a.port}" for a in agents]
    for a in agents:
        a.shutdown()


def _pid():
    return os.getpid()


def _sq(x):
    return x * x


def _getenv(k):
    return os.environ.get(k)


def _boom():
    raise ValueError("remote worker exploded")


def _die():
    os._exit(13)


def test_remote_worker_executes(two_agents):
    w = RemoteWorker(two_agents[0], rank=0, env={"RLA_AGENT_T": "x"})
    try:
        assert w.execute(_sq, 6).result(timeout=60) == 36
        assert w.execute(_getenv, "RLA_AGENT_T").result(timeout=60) == "x"
        assert w.is_alive
        assert w.get_node_ip()  # resolves without error
    finally:
        w.shutdown()


def test_remote_error_carries_traceback(two_agents):
    w = RemoteWorker(two_agents[0], rank=0)
    try:
        with pytest.raises(RemoteError, match="remote worker exploded"):
            w.execute(_boom).result(timeout=60)
        # the worker survives an exception and keeps serving
        assert w.execute(_sq, 3).result(timeout=60) == 9
    finally:
        w.shutdown()


def test_remote_worker_death_fails_future_and_restarts(two_agents):
    w = RemoteWorker(two_agents[0], rank=0)
    try:
        with pytest.raises(RuntimeError, match="died"):
            w.execute(_die).result(timeout=60)
        deadline = time.time() + 10
        while w.is_alive and time.time() < deadline:
            time.sleep(0.05)
        assert not w.is_alive
        w.restart()
        assert w.execute(_sq, 4).result(timeout=60) == 16
    finally:
        w.shutdown()


def test_pool_over_agents_places_block_per_agent(two_agents):
    with ActorPool(4, agents=two_agents) as pool:
        pids = [f.result(timeout=60) for f in pool.execute_all(_pid)]
        assert len(set(pids)) == 4
        assert all(p != os.getpid() for p in pids)
        # contiguous block assignment: workers 0,1 -> agent 0; 2,3 -> agent 1
        addrs = [w.address for w in pool.workers]
        assert addrs == [two_agents[0], two_agents[0],
                         two_agents[1], two_agents[1]]
        assert pool.local_ranks() == [0, 1, 2, 3]  # same IP on localhost


def test_assign_agents_uneven_balanced():
    # heterogeneous layouts place like the reference's resource-driven
    # scheduling (reference: ray_ddp.py:92-97): 3 over 2 hosts -> 2+1
    assert assign_agents(["a:1", "b:2"], 3) == ["a:1", "a:1", "b:2"]
    assert assign_agents(["a:1", "b:2", "c:3"], 1) == ["a:1"]
    assert assign_agents(["a:1", "b:2"], 4) == ["a:1", "a:1", "b:2", "b:2"]


def test_assign_agents_explicit_counts():
    assert assign_agents(["a:1*1", "b:2*3"], 4) == \
        ["a:1", "b:2", "b:2", "b:2"]
    with pytest.raises(ValueError, match="sum to"):
        assign_agents(["a:1*1", "b:2*1"], 4)
    with pytest.raises(ValueError, match="mix"):
        assign_agents(["a:1*1", "b:2"], 2)


def test_agent_auth_handshake(monkeypatch):
    from ray_lightning_accelerators_tpu.runtime.agent import (
        TOKEN_ENV, AgentConnection)

    monkeypatch.delenv(TOKEN_ENV, raising=False)
    agent = HostAgent(port=0, bind="127.0.0.1", token="s3cret")
    agent.serve_in_background()
    addr = f"127.0.0.1:{agent.port}"
    try:
        # no token: the connection is dropped BEFORE the agent unpickles
        # anything (unpickling an untrusted frame would itself be the
        # RCE).  Depending on when the RST lands relative to the first
        # op's send, the drop surfaces as "lost connection", "connection
        # closed", or a plain socket error -- all are the refusal.
        refusal = ("lost connection|connection closed|unreachable|"
                   "Broken pipe|reset")
        with pytest.raises(Exception, match=refusal):
            RemoteWorker(addr, rank=0)
        # wrong token: dropped the same way; surfaces on the first op
        with pytest.raises(Exception, match=refusal):
            AgentConnection(addr, token="wrong").call("ping", timeout=10)
        # right token (picked up from the env like `rla-tpu launch` does)
        monkeypatch.setenv(TOKEN_ENV, "s3cret")
        w = RemoteWorker(addr, rank=0)
        try:
            assert w.execute(_sq, 5).result(timeout=60) == 25
        finally:
            w.shutdown()
    finally:
        agent.shutdown()


def test_tokened_client_talks_to_open_agent(two_agents, monkeypatch):
    # a driver with RLA_TPU_AGENT_TOKEN exported must still work against
    # an agent that requires none (the auth frame is accepted + ignored)
    from ray_lightning_accelerators_tpu.runtime.agent import TOKEN_ENV

    monkeypatch.setenv(TOKEN_ENV, "extra")
    w = RemoteWorker(two_agents[0], rank=0)
    try:
        assert w.execute(_sq, 7).result(timeout=60) == 49
    finally:
        w.shutdown()


def test_queue_server_auth(monkeypatch):
    from ray_lightning_accelerators_tpu.runtime.agent import TOKEN_ENV

    monkeypatch.setenv(TOKEN_ENV, "qtok")
    q = TrampolineQueue()
    server = QueueServer(q)
    _SEEN.clear()
    try:
        client = QueueClient(server.address)  # env token -> accepted
        client.put((1, _remote_mark))
        client.flush()
        rank, thunk = q.get_nowait()
        thunk()
        assert rank == 1 and _SEEN == ["remote"]
        client.shutdown()

        monkeypatch.setenv(TOKEN_ENV, "wrong")
        bad = QueueClient(server.address)
        with pytest.raises((ConnectionError, OSError)):
            bad.put((2, _remote_mark))
            bad.flush()  # server dropped the connection; the ack never comes
        bad.shutdown()
        assert q.empty()
    finally:
        server.close()


def test_queue_server_without_token_skips_auth_frame(monkeypatch):
    # workers inherit the agent host's token env even when the driver has
    # none; the token-less server must skip (not enqueue!) the auth frame
    from ray_lightning_accelerators_tpu.runtime.agent import TOKEN_ENV

    monkeypatch.delenv(TOKEN_ENV, raising=False)
    q = TrampolineQueue()
    server = QueueServer(q)
    _SEEN.clear()
    try:
        monkeypatch.setenv(TOKEN_ENV, "worker-side-token")
        client = QueueClient(server.address)  # sends the auth frame
        client.put((4, _remote_mark))
        client.flush()
        rank, thunk = q.get_nowait()
        thunk()
        assert rank == 4 and _SEEN == ["remote"]
        client.shutdown()
    finally:
        server.close()


def test_coordinator_address_on_agent_host(two_agents):
    coord = coordinator_address_on(two_agents[0])
    host, port = coord.rsplit(":", 1)
    assert host and 0 < int(port) < 65536


def _remote_mark():
    # module-global so the cloudpickled thunk resolves it by reference in
    # the receiving process (a closed-over local would arrive as a copy)
    _SEEN.append("remote")


def test_queue_crosses_the_network():
    q = TrampolineQueue()
    server = QueueServer(q)
    _SEEN.clear()
    try:
        client = QueueClient(server.address)
        client.put((3, _remote_mark))
        deadline = time.time() + 10
        while q.empty() and time.time() < deadline:
            time.sleep(0.01)
        rank, thunk = q.get_nowait()
        thunk()
        assert rank == 3 and _SEEN == ["remote"]
        client.shutdown()
    finally:
        server.close()


def test_pool_env_and_health_over_agents(two_agents):
    with ActorPool(2, env_per_worker=[{"RLA_HOSTV": "h0"},
                                      {"RLA_HOSTV": "h1"}],
                   agents=two_agents) as pool:
        vals = [f.result(timeout=60)
                for f in pool.execute_all(_getenv, "RLA_HOSTV")]
        assert vals == ["h0", "h1"]
        assert pool.health_check() == [True, True]


# ------------------------------------------------------------------ #
# End-to-end distributed launches through agents (slow)               #
# ------------------------------------------------------------------ #
def _distributed_psum_agent(process_id):
    import jax
    import jax.numpy as jnp

    assert jax.process_count() == 2
    out = jax.shard_map(
        lambda x: jax.lax.psum(x, "i"),
        mesh=jax.sharding.Mesh(jax.devices(), ("i",)),
        in_specs=jax.sharding.PartitionSpec("i"),
        out_specs=jax.sharding.PartitionSpec())(jnp.arange(2.0))
    return float(np.asarray(out)[0])


@pytest.mark.slow
def test_launch_distributed_through_agents(two_agents):
    """launch_distributed(agents=...) forms a REAL 2-process
    jax.distributed world with one worker per 'host'."""
    from ray_lightning_accelerators_tpu.runtime.bootstrap import (
        launch_distributed)

    results = launch_distributed(
        _distributed_psum_agent, num_processes=2, platform="cpu",
        cpu_devices_per_process=1,
        env={"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""},
        agents=two_agents)
    assert results == [1.0, 1.0]


def _distributed_fit_agent(process_id):
    import jax
    import numpy as np
    from ray_lightning_accelerators_tpu import DataLoader, Trainer
    from ray_lightning_accelerators_tpu.data.loader import ArrayDataset
    from ray_lightning_accelerators_tpu.runtime import session as session_lib
    from tests.utils import BoringModel

    # device-binding contract (the reference pins the device/env mapping,
    # reference: tests/test_ddp_gpu.py:89-95): each process sees exactly
    # its devices, and the global view spans both processes
    assert len(jax.local_devices()) == 2
    assert jax.device_count() == 4
    assert jax.process_index() == process_id

    # the trampoline session reaches the driver over the network; a
    # partial of a module-level function pickles BY REFERENCE, so the
    # executed thunk mutates the DRIVER's module globals (a lambda would
    # pickle by value and mutate a copy)
    import functools
    session_lib.put_queue(functools.partial(_mark_rank, process_id))

    x = np.random.default_rng(0).normal(size=(64, 32)).astype("float32")
    model = BoringModel()
    trainer = Trainer(max_epochs=2, precision="f32", seed=0,
                      enable_checkpointing=False,
                      default_root_dir=f"/tmp/agent_fit_{process_id}")
    trainer.fit(model, DataLoader(ArrayDataset(x), batch_size=8))
    leaf = np.asarray(jax.tree.leaves(model.params)[0], dtype=np.float64)
    return (trainer.global_step, float(leaf.sum()),
            float(trainer.callback_metrics["loss"]))


_SEEN: list = []  # driver-side sink for trampolined thunks


def _mark_rank(pid):
    _SEEN.append(pid)


@pytest.mark.slow
def test_full_fit_through_agents(two_agents):
    """A complete Trainer.fit across two agent-hosted processes: sampler
    shards per process, gradient psum crosses the (local) network, both
    ranks agree on steps and final weights, and worker thunks reach the
    driver queue."""
    from ray_lightning_accelerators_tpu.runtime.bootstrap import (
        launch_distributed)

    _SEEN.clear()
    q = TrampolineQueue()
    results = launch_distributed(
        _distributed_fit_agent, num_processes=2, platform="cpu",
        cpu_devices_per_process=2,
        env={"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""},
        agents=two_agents, queue=q)
    steps0, wsum0, loss0 = results[0]
    steps1, wsum1, loss1 = results[1]
    assert steps0 == steps1 == 8  # 64 / 2 replicas / batch 8 x 2 epochs
    assert wsum0 == pytest.approx(wsum1, rel=1e-6)
    assert loss0 == pytest.approx(loss1, rel=1e-5)
    assert sorted(_SEEN) == [0, 1]  # one thunk per rank reached the driver


def _distributed_cached_fit_agent(cache, process_id):
    import jax
    import numpy as np
    from ray_lightning_accelerators_tpu import DataLoader, Trainer
    from ray_lightning_accelerators_tpu.data.loader import ArrayDataset
    from tests.utils import BoringModel

    x = np.random.default_rng(3).standard_normal((64, 32)).astype("float32")
    model = BoringModel()
    trainer = Trainer(max_epochs=2, precision="f32", seed=0,
                      enable_checkpointing=False,
                      cache_dataset_on_device=cache,
                      log_every_n_steps=10 ** 9,
                      default_root_dir=f"/tmp/cached_fit_{cache}_{process_id}")
    trainer.fit(model, DataLoader(ArrayDataset(x), batch_size=8,
                                  shuffle=True))
    used_cache = trainer._device_cache is not None
    used_scan = trainer._epoch_scan_fn is not None
    leaf = np.asarray(jax.tree.leaves(model.params)[0], dtype=np.float64)
    return (used_cache, used_scan, trainer.global_step, float(leaf.sum()))


@pytest.mark.slow
def test_cached_fit_matches_host_fed_through_agents(two_agents):
    """The device cache + whole-epoch scan run under a REAL 2-process world
    (round-2 gap: the fast path and the multi-host path were disjoint
    code); the cached multi-process fit must match the host-fed one."""
    import functools

    from ray_lightning_accelerators_tpu.runtime.bootstrap import (
        launch_distributed)

    env = {"JAX_PLATFORMS": "cpu", "XLA_FLAGS": ""}
    host = launch_distributed(
        functools.partial(_distributed_cached_fit_agent, False),
        num_processes=2, platform="cpu", cpu_devices_per_process=2,
        env=env, agents=two_agents)
    cached = launch_distributed(
        functools.partial(_distributed_cached_fit_agent, True),
        num_processes=2, platform="cpu", cpu_devices_per_process=2,
        env=env, agents=two_agents)
    assert [r[0] for r in host] == [False, False]
    assert [r[0] for r in cached] == [True, True]
    assert [r[1] for r in cached] == [True, True]  # epoch scan compiled
    assert cached[0][2] == host[0][2] == 8  # same step count
    # both ranks agree, and cached == host-fed on final weights
    assert cached[0][3] == pytest.approx(cached[1][3], rel=1e-6)
    assert cached[0][3] == pytest.approx(host[0][3], rel=1e-5)


def _worker_topology_probe(process_id):
    """Inside a 2-process world, a mismatched num_hosts must raise."""
    from ray_lightning_accelerators_tpu import (HorovodRayAccelerator,
                                                Trainer, DataLoader)
    from ray_lightning_accelerators_tpu.data.loader import ArrayDataset
    from tests.utils import BoringModel
    import numpy as np
    import pytest as pt

    x = np.zeros((16, 32), dtype="float32")
    trainer = Trainer(max_epochs=1, precision="f32", seed=0,
                      enable_checkpointing=False,
                      accelerator=HorovodRayAccelerator(num_hosts=3,
                                                        num_slots=1),
                      default_root_dir=f"/tmp/topo_probe_{process_id}")
    with pt.raises(ValueError, match="num_hosts=3"):
        trainer.fit(BoringModel(),
                    DataLoader(ArrayDataset(x), batch_size=8))
    return "raised"


@pytest.mark.slow
def test_num_hosts_mismatch_raises_in_distributed_world(two_agents):
    from ray_lightning_accelerators_tpu.runtime.bootstrap import (
        launch_distributed)

    results = launch_distributed(
        _worker_topology_probe, num_processes=2, platform="cpu",
        cpu_devices_per_process=1,
        env={"JAX_PLATFORMS": "cpu", "XLA_FLAGS": "",
             "RLA_TPU_INSIDE_WORKER": "1"},
        agents=two_agents)
    assert results == ["raised", "raised"]


@pytest.mark.slow
def test_driver_mode_fit_through_agents(two_agents, tmp_path):
    """The reference's headline flow, multi-machine: the DRIVER calls
    trainer.fit once; the framework fans out one process per host agent,
    trains SPMD across them, and re-hydrates rank-0 weights + metrics into
    the driver's module (reference: ray_lightning/ray_ddp.py:169-193)."""
    import numpy as np
    from ray_lightning_accelerators_tpu import (HorovodRayAccelerator,
                                                Trainer, DataLoader)
    from ray_lightning_accelerators_tpu.data.loader import ArrayDataset
    from tests.utils import BoringModel

    x = np.random.default_rng(0).normal(size=(64, 32)).astype("float32")
    model = BoringModel()
    assert model.params is None
    trainer = Trainer(max_epochs=4, precision="f32", seed=0,
                      enable_checkpointing=False,
                      accelerator=HorovodRayAccelerator(
                          num_hosts=2, num_slots=2, agents=two_agents),
                      default_root_dir=str(tmp_path))
    trainer.fit(model, DataLoader(ArrayDataset(x), batch_size=8))

    # rank-0 state re-hydrated into the driver's objects
    assert trainer.global_step == 16  # 64 / 2 procs / batch 8 x 4 epochs
    assert trainer.epochs_completed == 4
    assert "loss" in trainer.callback_metrics
    assert model.params is not None
    # weights really trained: loss at re-hydrated params beats init,
    # and the model is directly usable driver-side
    out = np.asarray(model.forward(model.params, x[:4]))
    assert out.shape == (4, 2)
    assert float(np.mean((out - 1.0) ** 2)) < 1.0  # moved toward target


@pytest.mark.slow
def test_distributed_eval_through_agents(two_agents, tmp_path):
    """trainer.test / predict with num_hosts=2 fan out through the agents
    (the reference's fit/test multi-call contract, reference:
    README.md:34-36) and match a single-process run on the SAME params."""
    import jax
    from ray_lightning_accelerators_tpu import (HorovodRayAccelerator,
                                                Trainer, DataLoader)
    from ray_lightning_accelerators_tpu.data.loader import ArrayDataset
    from tests.utils import BoringModel

    x = np.random.default_rng(1).normal(size=(64, 32)).astype("float32")

    def loader():
        return DataLoader(ArrayDataset(x), batch_size=8, shuffle=False)

    # single-process baseline on fixed params
    model = BoringModel()
    model.params = jax.tree.map(np.asarray,
                                model.init_params(jax.random.key(7)))
    t_local = Trainer(max_epochs=1, precision="f32", seed=0,
                      enable_checkpointing=False,
                      default_root_dir=str(tmp_path / "local"))
    local_metrics = t_local.test(model, loader())[0]
    local_preds = np.concatenate(
        [np.asarray(o) for o in t_local.predict(model, loader())])

    # the same params, evaluated through two agent-hosted processes
    model2 = BoringModel()
    model2.params = jax.tree.map(np.asarray,
                                 model.init_params(jax.random.key(7)))
    t_dist = Trainer(max_epochs=1, precision="f32", seed=0,
                     enable_checkpointing=False,
                     accelerator=HorovodRayAccelerator(
                         num_hosts=2, num_slots=2, agents=two_agents),
                     default_root_dir=str(tmp_path / "dist"))
    dist_metrics = t_dist.test(model2, loader())[0]
    assert set(dist_metrics) == set(local_metrics)
    for k, v in local_metrics.items():
        assert dist_metrics[k] == pytest.approx(v, rel=1e-5), k
    # metrics re-hydrated driver-side (BoringModel.test_step logs "y")
    assert t_dist.callback_metrics["y"] == pytest.approx(
        local_metrics["y"], rel=1e-5)

    dist_preds = np.concatenate(
        [np.asarray(o) for o in t_dist.predict(model2, loader())])
    np.testing.assert_allclose(dist_preds, local_preds, rtol=1e-5,
                               atol=1e-6)


@pytest.mark.slow
def test_world_persists_across_entry_points(tmp_path):
    """fit -> test -> fit through the same agents reuses ONE persistent
    world: each agent spawns its worker exactly once for the whole span
    (the reference's actors live setup -> teardown and serve every stage,
    reference: ray_lightning/ray_ddp.py:99-121), and the same worker
    process (same pid) serves every entry point."""
    from ray_lightning_accelerators_tpu import (Callback, DataLoader,
                                                HorovodRayAccelerator,
                                                Trainer)
    from ray_lightning_accelerators_tpu.data.loader import ArrayDataset
    from tests.utils import BoringModel

    class PidCb(Callback):
        def on_fit_end(self, trainer, module):
            trainer.callback_metrics["worker_pid"] = float(os.getpid())

        def on_test_end(self, trainer, module):
            trainer.callback_metrics["worker_pid"] = float(os.getpid())

    agents = [HostAgent(port=0, bind="127.0.0.1") for _ in range(2)]
    for a in agents:
        a.serve_in_background()
    addrs = [f"127.0.0.1:{a.port}" for a in agents]
    try:
        x = np.random.default_rng(0).normal(size=(64, 32)).astype("float32")

        def loader():
            return DataLoader(ArrayDataset(x), batch_size=8, shuffle=False)

        model = BoringModel()
        trainer = Trainer(max_epochs=1, precision="f32", seed=0,
                          enable_checkpointing=False, callbacks=[PidCb()],
                          accelerator=HorovodRayAccelerator(
                              num_hosts=2, num_slots=1, agents=addrs),
                          default_root_dir=str(tmp_path))
        trainer.fit(model, loader())
        fit_pid = trainer.callback_metrics["worker_pid"]
        trainer.test(model, loader())
        test_pid = trainer.callback_metrics["worker_pid"]
        trainer.fit(model, loader())  # refit reuses the world too
        refit_pid = trainer.callback_metrics["worker_pid"]

        assert fit_pid == test_pid == refit_pid  # same rank-0 process
        # one spawn per rank EVER, not per entry point
        assert sum(a.spawn_count for a in agents) == 2
        assert [a.spawn_count for a in agents] == [1, 1]
        # the dataset shipped ONCE: later entry points over byte-identical
        # loaders hit the worker-side content cache
        stats = trainer._world.ship_stats
        assert stats["sent"] >= 1
        assert stats["reused"] >= 1, stats

        # full teardown() ends the world too (the reference's teardown
        # ends its actors, ray_ddp.py:109-121); a fresh entry point after
        # it builds a new world rather than dispatching into a dead one
        world = trainer._world
        trainer.teardown()
        assert trainer._world is None
        assert world.pool is None  # shut down, not leaked
    finally:
        for a in agents:
            a.shutdown()


@pytest.mark.slow
def test_unreachable_agent_leaves_driver_intact(tmp_path, monkeypatch):
    """An unreachable agent fails the fan-out BEFORE the driver's
    module/trainer are stripped for shipment: the module stays bound and
    trainable locally afterwards (round-3 weak #3)."""
    import socket as socket_mod

    from ray_lightning_accelerators_tpu import (DataLoader,
                                                HorovodRayAccelerator,
                                                Trainer)
    from ray_lightning_accelerators_tpu.data.loader import ArrayDataset
    from tests.utils import BoringModel

    monkeypatch.setenv("RLA_TPU_AGENT_CONNECT_TIMEOUT", "2")
    live = HostAgent(port=0, bind="127.0.0.1")
    live.serve_in_background()
    # a port with no listener: refused instantly, retried ~2s, then raises
    probe = socket_mod.socket()
    probe.bind(("127.0.0.1", 0))
    dead_port = probe.getsockname()[1]
    probe.close()
    addrs = [f"127.0.0.1:{live.port}", f"127.0.0.1:{dead_port}"]
    try:
        x = np.random.default_rng(0).normal(size=(64, 32)).astype("float32")

        def loader():
            return DataLoader(ArrayDataset(x), batch_size=8, shuffle=False)

        model = BoringModel()
        trainer = Trainer(max_epochs=1, precision="f32", seed=0,
                          enable_checkpointing=False,
                          accelerator=HorovodRayAccelerator(
                              num_hosts=2, num_slots=1, agents=addrs),
                          default_root_dir=str(tmp_path / "dist"))
        with pytest.raises(Exception):
            trainer.fit(model, loader())

        # nothing was stripped mid-flight: a plain local fit on the same
        # module works
        local = Trainer(max_epochs=1, precision="f32", seed=0,
                        enable_checkpointing=False,
                        default_root_dir=str(tmp_path / "local"))
        local.fit(model, loader())
        assert local.global_step > 0
        assert model.params is not None
    finally:
        live.shutdown()


@pytest.mark.slow
def test_dead_world_respawns_on_next_entry_point(two_agents, tmp_path):
    """A worker process dying between entry points poisons the world; the
    next entry point detects it (world.alive() False) and respawns a
    fresh one instead of dispatching into dead processes."""
    from ray_lightning_accelerators_tpu import (DataLoader,
                                                HorovodRayAccelerator,
                                                Trainer)
    from ray_lightning_accelerators_tpu.data.loader import ArrayDataset
    from tests.utils import BoringModel

    x = np.random.default_rng(0).normal(size=(64, 32)).astype("float32")

    def loader():
        return DataLoader(ArrayDataset(x), batch_size=8, shuffle=False)

    model = BoringModel()
    trainer = Trainer(max_epochs=1, precision="f32", seed=0,
                      enable_checkpointing=False,
                      accelerator=HorovodRayAccelerator(
                          num_hosts=2, num_slots=1, agents=two_agents),
                      default_root_dir=str(tmp_path))
    trainer.fit(model, loader())
    world = trainer._world
    assert world is not None and world.alive()
    world.pool.workers[1].kill()  # simulate a crash between entry points
    deadline = time.time() + 10
    while world.alive() and time.time() < deadline:
        time.sleep(0.05)
    assert not world.alive()

    metrics = trainer.test(model, loader())[0]  # respawns transparently
    assert metrics
    assert trainer._world is not world and trainer._world.alive()
    trainer.shutdown_workers()


def test_single_host_agent_fans_out(tmp_path):
    """num_hosts=1 WITH an agent configured still fans out -- "run my
    training on that one (possibly remote, chip-holding) host" is the
    single-host analog of the reference placing its one actor wherever
    the resources are (reference: ray_ddp.py:92-97).  Previously
    launch_spec() silently ignored explicit agents when num_hosts <= 1.
    This is also the exact layout of the on-chip world gate
    (test_tpu_world.py) with a CPU worker standing in for the chip."""
    from ray_lightning_accelerators_tpu import (Callback, DataLoader,
                                                HorovodRayAccelerator,
                                                Trainer)
    from ray_lightning_accelerators_tpu.data.loader import ArrayDataset
    from tests.utils import BoringModel

    class PidCb(Callback):
        def on_fit_end(self, trainer, module):
            trainer.callback_metrics["worker_pid"] = float(os.getpid())

    agent = HostAgent(port=0, bind="127.0.0.1")
    agent.serve_in_background()
    try:
        x = np.random.default_rng(0).normal(size=(64, 32)).astype(
            "float32")

        def loader():
            return DataLoader(ArrayDataset(x), batch_size=8,
                              shuffle=False)

        model = BoringModel()
        trainer = Trainer(max_epochs=1, precision="f32", seed=0,
                          enable_checkpointing=False, callbacks=[PidCb()],
                          accelerator=HorovodRayAccelerator(
                              num_hosts=1, num_slots=1,
                              agents=[f"127.0.0.1:{agent.port}"]),
                          default_root_dir=str(tmp_path))
        trainer.fit(model, loader())
        assert trainer.callback_metrics["worker_pid"] != float(os.getpid())
        assert model.params is not None
        preds = trainer.predict(model, loader())
        assert sum(np.shape(p)[0] for p in preds) == len(x)
        assert agent.spawn_count == 1  # one persistent worker, reused
        trainer.teardown()
    finally:
        agent.shutdown()


def test_fan_out_decision_leaves_driver_backend_untouched(
        cpu_mesh_subprocess):
    """One process per chip: a driver that initialises its backend takes
    the chip its same-host worker needs.  The fan-out decision
    (``_launch_plan`` + ``_spawn_platform``) must therefore read
    configuration only.  Proven with a platform that cannot initialise:
    any backend touch in the driver raises."""
    cpu_mesh_subprocess("""
import jax
from ray_lightning_accelerators_tpu import HorovodRayAccelerator, Trainer

trainer = Trainer(max_epochs=1, enable_checkpointing=False,
                  accelerator=HorovodRayAccelerator(
                      num_hosts=1, num_slots=1, agents=["127.0.0.1:1"]))
plan = trainer._launch_plan()
assert plan is not None and plan["num_processes"] == 1, plan
env, platform, cpu_per = trainer._spawn_platform(plan)
# nothing but the unknown platform is configured: workers detect their own
assert (platform, cpu_per) == (None, None), (platform, cpu_per)
assert env["RLA_TPU_INSIDE_WORKER"] == "1"
try:
    jax.devices()
except RuntimeError:
    pass  # the driver's backend was still uninitialised until here
else:
    raise AssertionError("no_such_platform initialised?")
""", env_extra={"JAX_PLATFORMS": "no_such_platform"})


def test_queue_server_binds_loopback_by_default():
    """Without remote agents in play the trampoline endpoint must not
    open a network-reachable port (round-3 advisor finding: thunks
    EXECUTE driver-side)."""
    q = TrampolineQueue()
    server = QueueServer(q)
    try:
        assert server.address.startswith("127.0.0.1:")
    finally:
        server.close()


def test_host_agent_refuses_tokenless_wide_bind(monkeypatch):
    """Agents execute arbitrary thunks as this user -- the QueueServer's
    tokenless-wide-bind refusal applies to them identically."""
    monkeypatch.delenv("RLA_TPU_AGENT_TOKEN", raising=False)
    monkeypatch.delenv("RLA_TPU_ALLOW_TOKENLESS_BIND", raising=False)
    with pytest.raises(RuntimeError, match="RLA_TPU_AGENT_TOKEN"):
        HostAgent(port=0, bind="0.0.0.0")
    # a token makes the wide bind legitimate
    agent = HostAgent(port=0, bind="0.0.0.0", token="s3cret")
    agent.shutdown()
    # ... as does the explicit opt-out
    monkeypatch.setenv("RLA_TPU_ALLOW_TOKENLESS_BIND", "1")
    agent = HostAgent(port=0, bind="0.0.0.0")
    agent.shutdown()


def test_queue_server_refuses_tokenless_wide_bind(monkeypatch):
    """An unauthenticated 0.0.0.0 bind is an RCE surface (queued frames
    are unpickled and executed driver-side): without RLA_TPU_AGENT_TOKEN
    the server must refuse, not warn-and-proceed (round-4 advisor
    finding) -- unless the explicit opt-out is set."""
    monkeypatch.delenv("RLA_TPU_AGENT_TOKEN", raising=False)
    monkeypatch.delenv("RLA_TPU_ALLOW_TOKENLESS_BIND", raising=False)
    with pytest.raises(RuntimeError, match="RLA_TPU_AGENT_TOKEN"):
        QueueServer(TrampolineQueue(), bind="0.0.0.0")
    monkeypatch.setenv("RLA_TPU_ALLOW_TOKENLESS_BIND", "1")
    server = QueueServer(TrampolineQueue(), bind="0.0.0.0")
    server.close()


def test_queue_bind_for_agents_stays_loopback_for_local_agents():
    """Single-machine agent setups (every agent on 127.x) keep the
    trampoline on loopback; any non-loopback agent needs the wide bind
    (and then the tokenless refusal above applies)."""
    from ray_lightning_accelerators_tpu.runtime.agent import \
        queue_bind_for_agents
    assert queue_bind_for_agents(None) is None
    assert queue_bind_for_agents([]) is None
    assert queue_bind_for_agents(["127.0.0.1:7777", "localhost:7778*2"]) \
        is None
    assert queue_bind_for_agents(["127.0.0.1:7777", "10.0.0.5:7777"]) \
        == "0.0.0.0"


def _hang_remote():
    import time
    time.sleep(10_000)


def test_remote_worker_heartbeat_and_wedge_reap(two_agents):
    """Watchdog parity over the wire: heartbeat snapshots are taken
    agent-side (only ages cross the network), a wedged remote rank is
    reaped through the agent, and its future fails with the TYPED
    WorkerWedged -- diagnosis intact -- after crossing the relay as
    (name, message, tb)."""
    from ray_lightning_accelerators_tpu.runtime.watchdog import (Watchdog,
                                                                 WorkerWedged)
    w = RemoteWorker(two_agents[0], rank=0,
                     env={"RLA_TPU_WORKER_HEARTBEAT_S": "0.05"})
    wd = None
    try:
        assert w.execute(_sq, 3).result(timeout=60) == 9
        snap = w.heartbeat.snapshot()
        assert snap is not None
        assert snap["started"]
        assert snap["dispatches"] == 1
        fut = w.execute(_hang_remote)
        wd = Watchdog([w], wedge_timeout_s=30.0, dispatch_deadline_s=0.4,
                      poll_s=0.05).start()
        with pytest.raises(WorkerWedged) as ei:
            fut.result(timeout=120)
        assert ei.value.rank == 0
        assert "deadline" in ei.value.diagnosis["detail"]
        # the slot stays restartable through the same agent connection
        w.restart()
        assert w.execute(_sq, 4).result(timeout=60) == 16
    finally:
        if wd is not None:
            wd.stop()
        w.kill()


def test_is_loopback_classification():
    """Round-5 advisor fix: the RCE gate must not be foolable by the old
    startswith('127.') prefix check, and IPv6 loopback must count."""
    from ray_lightning_accelerators_tpu.runtime.agent import is_loopback
    assert is_loopback("127.0.0.1")
    assert is_loopback("127.9.9.9")
    assert is_loopback("localhost")
    assert is_loopback("::1")
    assert is_loopback("[::1]")
    assert not is_loopback("10.0.0.5")
    assert not is_loopback("::2")
    assert not is_loopback("0.0.0.0")
    # a '127.'-PREFIXED hostname is not an address: it must resolve (and
    # be loopback) or be refused -- unresolvable fails closed
    assert not is_loopback("127.evil.example.invalid")
