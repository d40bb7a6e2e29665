"""Multi-host bootstrap: coordinator discovery + jax.distributed init.

Capability analog of the reference's process-group rendezvous
(reference: ray_lightning/ray_ddp.py:162-163 -- rank-0 actor computes a
``tcp://ip:port`` init string; :222-237 -- every worker joins the NCCL/Gloo
group).  TPU-native redesign: there is no per-gradient process group to
manage -- workers call ``jax.distributed.initialize(coordinator, N, i)``
once, PjRt forms the global device view, and XLA emits collectives from
shardings.  The ``launch_distributed`` helper reproduces the full driver
flow: pick a coordinator address, fan a trainable out over actor workers
with the right env, pump the trampoline queue, and return every rank's
result (rank-0 first -- normalizing the result-tuple inconsistency SURVEY.md
§3.2 flags between the reference's two accelerators).

Multi-MACHINE launches pass ``agents`` -- per-host `runtime.agent.HostAgent`
addresses (the reference's multi-node Ray cluster analog,
reference: README.md:57-62).  The coordinator is then picked on agent[0]'s
host (rank-0 placement, reference: ray_ddp.py:162-163), and the trampoline
queue crosses the network through a `runtime.queue.QueueServer`.
"""

from __future__ import annotations

import os
import socket
from typing import Any, Callable, Dict, List, Optional, Sequence

from .actors import ActorPool, RemoteError
from .queue import QueueServer, TrampolineQueue, process_results


def pick_coordinator_address(port: Optional[int] = None) -> str:
    """ip:port rendezvous string (reference setup_address analog,
    ray_ddp.py:10,162-163)."""
    from .net import node_ip
    ip = node_ip()
    if port is None:
        with socket.socket() as s:
            s.bind(("", 0))
            port = s.getsockname()[1]
    return f"{ip}:{port}"


def initialize_worker(coordinator_address: str, num_processes: int,
                      process_id: int,
                      platform: Optional[str] = None,
                      cpu_devices_per_process: Optional[int] = None) -> None:
    """Run INSIDE each worker before any jax use."""
    import jax

    if platform is not None:
        jax.config.update("jax_platforms", platform)
        if platform == "cpu":
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
            if cpu_devices_per_process:
                jax.config.update("jax_num_cpu_devices",
                                  cpu_devices_per_process)
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)


def _nested_query_handler() -> Optional[Callable[[str, Any], Any]]:
    """Query handler for a fit-level QueueServer: workers inside THIS fit
    may poll tune state ("should_stop", synchronous "report"/"checkpoint")
    that lives one level up -- with the fit nested in a tune process
    trial, the decision is on the TUNE driver, reachable through this
    process's own session QueueClient.  Forwards those queries upward,
    re-stamping the inner worker's fit rank with this process's trial
    rank; answers directly when a tune trial session lives right here
    (sequential thread-executor trials).  Returns None (no handler) when
    there is nothing to answer from this process."""
    def handler(name: str, payload: Any) -> Any:
        try:
            from ..tune import run as tune_run
            s = tune_run._current_session()
        except Exception:
            s = None
        if s is not None:
            # one dispatch shared with the tune driver's own QueueServer;
            # inner fit ranks all resolve to THIS process's trial session
            return tune_run.dispatch_trial_query(name, payload,
                                                 lambda _rank: s)
        from . import session as session_lib
        if not session_lib.session_exists():
            return None
        sess = session_lib.get_session()
        q = getattr(sess, "_queue", None)
        if not hasattr(q, "query"):
            return None
        if name in ("report", "checkpoint"):
            return q.query(name, (sess.rank,) + tuple(payload[1:]))
        return q.query(name, sess.rank)
    return handler


# Ship-once store: content-keyed pickled blobs written to the worker
# HOST's tmpdir (one copy per machine, shared by every worker process on
# it), namespaced per world.  Resolution unpickles a FRESH object per use
# -- runs mutate loaders (sampler injection etc.), so caching live
# objects would leak one run's mutations into the next.


def _ship_dir(ns: str) -> str:
    import tempfile
    return os.path.join(tempfile.gettempdir(), f"rla_ship_{ns}")


class ShippedRef:
    """Handle to a payload cached on every host of a DistributedWorld
    (see ``DistributedWorld.ship_value``)."""

    __slots__ = ("ns", "key")

    def __init__(self, ns: str, key: str):
        self.ns = ns
        self.key = key


def _store_shipped(ns: str, key: str, blob: bytes) -> None:
    d = _ship_dir(ns)
    os.makedirs(d, exist_ok=True)
    tmp = os.path.join(d, f".{key}.tmp.{os.getpid()}")
    with open(tmp, "wb") as f:
        f.write(blob)
    os.replace(tmp, os.path.join(d, key))  # atomic: readers see all or none


def _cleanup_shipped(ns: str) -> None:
    import shutil
    shutil.rmtree(_ship_dir(ns), ignore_errors=True)


def resolve_shipped(obj):
    """Materialize a ShippedRef from this host's store (fresh copy);
    pass anything else through."""
    if isinstance(obj, ShippedRef):
        import cloudpickle
        path = os.path.join(_ship_dir(obj.ns), obj.key)
        try:
            with open(path, "rb") as f:
                return cloudpickle.loads(f.read())
        except FileNotFoundError:
            raise KeyError(
                f"shipped payload {obj.key[:12]} not cached on this host "
                "(world respawned without re-shipping?)") from None
    return obj


def _run_world_body(process_id: int, trainable, queue_address, init_hook):
    """One entry-point run inside a (persistent) worker: fresh session
    bound to this run's queue, trainable, flush barrier."""
    from . import session as session_lib

    # persistent workers run many bodies; each run binds a fresh session
    # to ITS driver queue (and a queue-less run must not inherit a stale
    # client from the previous one)
    session_lib.shutdown_session()
    client = None
    if queue_address is not None:
        from .queue import QueueClient
        client = QueueClient(queue_address)
        session_lib.init_session(process_id, client)
    try:
        if init_hook is not None:
            init_hook()
        return trainable(process_id)
    finally:
        # the result travels the worker pipe while queued thunks travel a
        # separate TCP connection: without this barrier the driver's final
        # drain can run before the server enqueues the last thunks,
        # dropping tune reports (mirrors _process_trial_main in
        # tune/run.py).  A dead driver/queue here must not mask the body's
        # real exception (e.g. a crashed peer already tore the server
        # down).
        if client is not None:
            try:
                client.flush()
            except (ConnectionError, OSError):
                pass
            client.shutdown()


class DistributedWorld:
    """Persistent fan-out world: spawned worker processes with a formed
    ``jax.distributed`` world, reusable across entry points
    (fit -> validate -> test -> predict) without respawning workers,
    re-forming the world, or recompiling from a cold runtime.

    The reference keeps its Ray actors alive for the accelerator's whole
    ``setup()`` -> ``teardown()`` span and routes every stage through them
    (reference: ray_lightning/ray_ddp.py:99-121); this is that lifecycle
    for agent workers.  Construction spawns the pool and forms the world
    (so an unreachable agent fails HERE, before any driver state is
    mutated); ``run`` executes one trainable over the live world; a failed
    run poisons the collectives, so the world kills itself and ``alive``
    turns False.
    """

    def __init__(self, num_processes: int,
                 platform: Optional[str] = None,
                 cpu_devices_per_process: Optional[int] = None,
                 env: Optional[Dict[str, str]] = None,
                 agents: Optional[Sequence[str]] = None):
        self.num_processes = num_processes
        self.agents = list(agents) if agents else None
        self.spec = (num_processes, platform, cpu_devices_per_process,
                     tuple(sorted((env or {}).items())),
                     tuple(self.agents or ()))
        self.pool: Optional[ActorPool] = None
        # ship-once bookkeeping: content digests already cached on every
        # HOST of this world (per-world tmpdir namespace), plus counters
        # tests/users can read
        import secrets
        self._ship_ns = secrets.token_hex(8)
        self._shipped: set = set()
        self.ship_stats = {"sent": 0, "reused": 0}
        # the probe-then-close port pick has an inherent reuse window
        # (another process can claim the freed port before rank 0's
        # coordinator binds it); bind failures retry with a fresh port
        # rather than surfacing as an unattributable rendezvous hang
        for attempt in range(3):
            if self.agents:
                from .agent import coordinator_address_on, parse_agent_spec
                coord = coordinator_address_on(
                    parse_agent_spec(self.agents[0])[0])
            else:
                coord = pick_coordinator_address()
            pool: Optional[ActorPool] = None
            try:
                # inside try: a partially-constructed multi-machine pool
                # (one agent down) must still tear down the workers it DID
                # spawn
                pool = ActorPool(num_processes,
                                 env_per_worker=[dict(env or {})
                                                 for _ in
                                                 range(num_processes)],
                                 agents=self.agents)
                futures = pool.execute_per_worker(
                    initialize_worker,
                    [(coord, num_processes, i, platform,
                      cpu_devices_per_process)
                     for i in range(num_processes)])
                for f in futures:
                    f.result()
                self.pool = pool
                # a world left open at interpreter exit must die BEFORE
                # multiprocessing's exit handler joins children:
                # jax.distributed workers catch SIGTERM (preemption
                # notifier), so the default terminate-and-join hangs.
                # The closure holds the POOL strongly -- a world dropped
                # without shutdown() (e.g. a GC'd trainer) still gets its
                # worker processes killed at exit
                import atexit

                def _reap(p=pool):
                    try:
                        p.kill()
                    except Exception:
                        pass  # agents already gone; processes die with us

                self._atexit_cb = _reap
                atexit.register(_reap)
                return
            except RemoteError as e:
                if pool is None:
                    raise  # pool construction itself failed: no retry
                pool.kill()
                pool.shutdown()
                bindy = any(tok in str(e).lower()
                            for tok in ("bind", "address already in use"))
                if not (bindy and attempt < 2):
                    raise
            except BaseException:
                if pool is not None:
                    pool.kill()
                    pool.shutdown()
                raise

    def alive(self) -> bool:
        return (self.pool is not None
                and all(w.is_alive for w in self.pool.workers))

    def _one_worker_per_host(self) -> List[Any]:
        """One representative worker per distinct placement: the store is
        host-shared (tmpdir), so the blob crosses the wire once per
        machine, not once per worker slot."""
        seen = set()
        reps = []
        for w in self.pool.workers:
            addr = getattr(w, "address", None)  # None = local subprocess
            host = None if addr is None else addr.split(":")[0]
            if host not in seen:
                seen.add(host)
                reps.append(w)
        return reps

    def ship_value(self, obj):
        """Cache ``obj`` on every HOST of this world ONCE,
        content-addressed; returns a ShippedRef later runs reference
        instead of re-shipping the bytes (on real TPU hosts a dataset
        crossing the wire per entry point is the dominant fit->test cost;
        the reference ships its trainer to the object store once,
        ray_ddp.py:169).  Workers unpickle a FRESH copy per resolve, so
        one run's mutations never leak into the next.  ``None`` passes
        through un-shipped."""
        if obj is None:
            return None
        import hashlib

        import cloudpickle
        blob = cloudpickle.dumps(obj)
        key = hashlib.sha256(blob).hexdigest()
        if key in self._shipped:
            self.ship_stats["reused"] += 1
            return ShippedRef(self._ship_ns, key)
        for f in [w.execute(_store_shipped, self._ship_ns, key, blob)
                  for w in self._one_worker_per_host()]:
            f.result()
        self._shipped.add(key)
        self.ship_stats["sent"] += 1
        return ShippedRef(self._ship_ns, key)

    def run(self, trainable: Callable[[int], Any],
            queue: Optional[TrampolineQueue] = None,
            init_hook: Optional[Callable[[], None]] = None,
            deadline_s: Optional[float] = None,
            wedge_timeout_s: Optional[float] = None) -> List[Any]:
        """Fan ``trainable(process_id)`` over the live world.  Returns
        per-rank results, rank 0 first.  With a ``queue``, every worker
        gets a session whose trampoline reaches this driver over TCP, so
        tune callbacks work unchanged through remote workers.

        Hang-aware supervision (`runtime.watchdog`) runs when
        ``deadline_s`` (per-attempt budget for this run's dispatch),
        ``wedge_timeout_s`` (stale-heartbeat threshold), or the
        ``RLA_TPU_WEDGE_TIMEOUT_S`` env is set: a rank that stops making
        progress is reaped and fails the run with ``WorkerWedged``
        (retryable) instead of hanging the driver forever.  A padded
        driver-side ``process_results`` deadline backstops the case where
        the supervision channel itself is broken."""
        # liveness was checked by the caller (_acquire_world) moments ago;
        # re-probing here would cost another N agent round-trips per entry
        # point, and a racing death still surfaces as a dispatch failure
        if self.pool is None:
            raise RuntimeError("DistributedWorld is not alive (a prior run "
                               "failed or it was shut down)")
        qserver: Optional[QueueServer] = None
        queue_address: Optional[str] = None
        if queue is not None:
            # loopback unless workers live on other machines; the query
            # handler lets worker-side stop-polls/reports cross THIS fit
            # and reach an enclosing tune driver (nested process trials)
            from .agent import queue_bind_for_agents
            qserver = QueueServer(queue,
                                  bind=queue_bind_for_agents(self.agents),
                                  query_handler=_nested_query_handler())
            queue_address = qserver.address
        from .watchdog import Watchdog, wedge_timeout_from_env
        if wedge_timeout_s is None:
            wedge_timeout_s = wedge_timeout_from_env()
        watchdog: Optional[Watchdog] = None
        self.last_stall: List[Dict[str, Any]] = []
        try:
            futures = self.pool.execute_per_worker(
                _run_world_body,
                [(i, trainable, queue_address, init_hook)
                 for i in range(self.num_processes)])
            if deadline_s is not None or wedge_timeout_s is not None:
                watchdog = Watchdog(
                    self.pool, wedge_timeout_s=wedge_timeout_s,
                    dispatch_deadline_s=deadline_s).start()
            # backstop deadline, padded past the watchdog's trigger so
            # the typed WorkerWedged (with diagnosis) wins when possible
            hard_deadline = (deadline_s + max(30.0, wedge_timeout_s or 0.0)
                             if deadline_s is not None else None)
            return process_results(futures, queue,
                                   deadline_s=hard_deadline)
        except BaseException as e:
            # a crashed rank leaves its peers blocked in the distributed
            # barrier; they will never drain a shutdown sentinel -- kill
            # the whole world (callers respawn a fresh one)
            self.kill()
            from .preemption import as_preempted, is_preemption
            if is_preemption(e):
                # a graceful drain crossed the worker pipe as a generic
                # RemoteError; hand the caller the TYPED outcome (step +
                # emergency checkpoint path) so fit(ckpt_path="last")
                # resumes instead of counting a failure
                raise as_preempted(e) from e
            raise
        finally:
            if watchdog is not None:
                watchdog.stop()
                self.last_stall = list(watchdog.reaped)
            if qserver is not None:
                qserver.close()

    def _drop_atexit(self) -> None:
        cb = getattr(self, "_atexit_cb", None)
        if cb is not None:
            import atexit
            atexit.unregister(cb)
            self._atexit_cb = None

    def kill(self) -> None:
        self._drop_atexit()
        if self.pool is not None:
            self.pool.kill()
            self.pool = None

    def shutdown(self) -> None:
        self._drop_atexit()
        if self.pool is not None:
            if self._shipped:
                # best-effort: clear the per-world host caches while the
                # workers are still alive (kill() paths leave the files
                # to the OS tmp reaper)
                try:
                    for f in [w.execute(_cleanup_shipped, self._ship_ns)
                              for w in self._one_worker_per_host()]:
                        f.result(timeout=10)
                except Exception:
                    pass
            self.pool.shutdown()
            self.pool = None

    def __enter__(self) -> "DistributedWorld":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()


def launch_distributed(trainable: Callable[[int], Any], num_processes: int,
                       platform: Optional[str] = None,
                       cpu_devices_per_process: Optional[int] = None,
                       env: Optional[Dict[str, str]] = None,
                       init_hook: Optional[Callable[[], None]] = None,
                       queue: Optional[TrampolineQueue] = None,
                       agents: Optional[Sequence[str]] = None) -> List[Any]:
    """Fan `trainable(process_id)` over num_processes fresh processes, each
    with a jax.distributed world formed first.  Returns per-rank results,
    rank 0 first.  One-shot wrapper over ``DistributedWorld`` (the
    persistent form the Trainer uses across entry points).

    ``agents``: HostAgent addresses for a multi-machine launch (one worker
    process per address slot, contiguous blocks).  With a ``queue``, every
    worker gets a session whose trampoline reaches the driver over TCP, so
    tune callbacks work unchanged through remote workers.
    """
    world = DistributedWorld(num_processes, platform,
                             cpu_devices_per_process, env, agents)
    try:
        return world.run(trainable, queue=queue, init_hook=init_hook)
    finally:
        world.shutdown()
