"""Actor runtime: persistent worker processes with remote-execute futures.

Capability analog of the reference's Ray-actor control plane
(reference: ray_lightning/ray_ddp.py -- `RayExecutor` actor :17-31, actor
creation :92-97,105, env propagation :21-23,154-159, init_hook :106-107,
fan-out :178-182, teardown/kill :109-121, node-IP census :25-27,132-143).

Without Ray in the image, this is a from-scratch actor system on
``multiprocessing`` spawn workers:

- each **Worker** is a long-lived subprocess running a request loop; work
  arrives as cloudpickled (fn, args, kwargs) so closures/lambdas ship like
  they do through Ray;
- ``execute()`` returns a ``concurrent.futures.Future`` resolved by a
  driver-side collector thread -- the ObjectRef analog that
  ``runtime.queue.process_results`` polls;
- env vars can be set pre-fork (TPU topology variables such as
  ``TPU_PROCESS_BOUNDS`` / coordinator addresses must exist before the
  child's XLA backend initializes -- the TPU twist on the reference's
  `set_env_var` RPC);
- ``kill()``/``shutdown()`` terminate workers (`no_restart` semantics,
  reference: ray_ddp.py:119).

The TPU multi-host bootstrap built on top lives in `runtime/bootstrap.py`.

Note: scripts creating pools must guard pool construction with
``if __name__ == "__main__":`` -- spawn children re-import the main module
(standard multiprocessing semantics; Ray's driver/worker split hid this).
"""

from __future__ import annotations

import multiprocessing as mp
import os
import socket
import threading
import traceback
from concurrent.futures import Future
from typing import Any, Callable, Dict, List, Optional, Sequence

import cloudpickle

from ..analysis import knobs
from ..utils.logging import log
from .watchdog import (HeartbeatChannel, WorkerBeat, WorkerWedged,
                       heartbeat_interval_s)

_SENTINEL = b"__shutdown__"

# worker-process side: this process's beat thread, installed by
# _worker_main so in-process layers (the replica-level chaos seam in
# serve/replicas.py) can freeze it without plumbing the object through
# every dispatch signature
_CURRENT_BEAT: Optional["WorkerBeat"] = None


def freeze_current_heartbeat() -> None:
    """Freeze THIS worker process's heartbeat thread (no-op on the
    driver / when heartbeats are disabled).  A chaos ``hang`` injected
    above the dispatch loop — e.g. inside a replica's serve-chunk path —
    calls this so the hang reads as a frozen process to the watchdog,
    not as a long-running dispatch."""
    if _CURRENT_BEAT is not None:
        _CURRENT_BEAT.freeze()


def _worker_main(conn, env: Dict[str, str], rank: int = 0,
                 heartbeat: Optional[HeartbeatChannel] = None,
                 heartbeat_s: float = 0.0) -> None:
    os.environ.update(env)
    # flight recorder (telemetry/recorder.py): rank-keyed so the spill
    # file and every event carry this worker's identity; the trace id /
    # telemetry dir come from the per-worker env overlay.  A failure
    # here must not take the worker down — telemetry observes, never
    # gates.
    try:
        from ..telemetry import recorder as telemetry
        telemetry.configure(rank=rank, env=env)
    except Exception:
        telemetry = None
    # live telemetry plane (telemetry/live.py): with RLA_TPU_METRICS_PORT
    # in the overlay this rank serves /metrics + /statusz + /healthz on
    # an ephemeral loopback port published via its portfile — /healthz
    # classifies from THIS rank's own heartbeat channel, so a hung
    # dispatch flips it to wedged before the driver watchdog reaps.
    # Observes, never gates: a bind failure leaves the worker running.
    try:
        from ..telemetry import live as live_telemetry
        live_telemetry.maybe_start_from_env(
            rank=rank, env=env,
            beat_snapshot_fn=(heartbeat.snapshot
                              if heartbeat is not None else None))
    except Exception:
        pass
    # opt-in SPMD collective sanitizer (testing/spmd_sanitizer.py):
    # when RLA_TPU_SPMD_SANITIZER is in the overlay, every collective
    # this worker traces is recorded + spilled rank-keyed so the driver
    # can diff sequences across ranks.  Observes, never gates.
    try:
        from ..testing.spmd_sanitizer import maybe_install_from_env
        maybe_install_from_env(rank=rank, env=env)
    except Exception:
        pass
    try:
        # the package logger was configured at import, BEFORE the
        # per-worker overlay landed in os.environ — re-read
        # RLA_TPU_LOG_JSON / RLA_TPU_LOG_LEVEL so overlays are honored
        from ..utils.logging import configure_logging
        configure_logging()
    except Exception:
        pass
    # jax was imported (and read JAX_PLATFORMS) before the per-worker
    # overlay landed in os.environ: re-apply the overlay's choice through
    # the config, or a CPU-pinned trial/worker on a chip host would try
    # to claim the TPU its driver holds
    platforms = env.get("JAX_PLATFORMS") or os.environ.get("JAX_PLATFORMS")
    if platforms:
        try:
            import jax
            jax.config.update("jax_platforms", platforms)
        except Exception:
            pass
    # persistent compile cache placed before this worker's first compile
    from ..utils import compile_cache
    compile_cache.enable()
    beat = None
    if heartbeat is not None and heartbeat_s > 0:
        beat = WorkerBeat(heartbeat, heartbeat_s)
        beat.start()
        global _CURRENT_BEAT
        _CURRENT_BEAT = beat
    # preemption notice handler (runtime/preemption.py), installed only
    # when a grace budget is configured: SIGTERM then flips a drain flag
    # the dispatched body polls (busy) or exits immediately (idle), so
    # spot notices drain gracefully while pool teardown stays fast
    notice = None
    try:
        from .preemption import install_from_env
        notice = install_from_env(worker_mode=True)
    except Exception:
        pass
    # deterministic fault injection (testing/chaos.py), imported ONLY when
    # requested -- the test harness must not be a production dependency.
    # A broken spec surfaces on the first dispatch's future, not by
    # killing the worker silently.
    chaos = chaos_error = None
    if knobs.get_raw("RLA_TPU_CHAOS"):
        try:
            from ..testing.chaos import ChaosInjector
            chaos = ChaosInjector.from_env(
                rank, freeze_heartbeat=beat.freeze if beat else None)
        except BaseException as e:
            chaos_error = e
    n_dispatch = 0
    while True:
        try:
            blob = conn.recv_bytes()
        except EOFError:
            return
        if blob == _SENTINEL:
            conn.close()
            return
        n_dispatch += 1
        if telemetry is not None:
            # emitted BEFORE chaos/user code runs, and the recorder's
            # first emit spills eagerly: a rank that hangs or dies inside
            # this dispatch leaves "it entered dispatch N" on disk — the
            # tail the watchdog embeds into WorkerWedged.diagnosis
            telemetry.emit("dispatch_begin", n=n_dispatch)
        try:
            if chaos_error is not None:
                raise chaos_error
            fn, args, kwargs = cloudpickle.loads(blob)
            # Ray-style call-site deref: top-level ObjectRef args resolve
            # from the shared-memory store (reference: ray.put'd trainer_ref
            # arriving deserialized at ray_ddp.py:179,201)
            from .object_store import resolve
            args = tuple(resolve(a) for a in args)
            kwargs = {k: resolve(v) for k, v in kwargs.items()}
            # busy marker brackets the USER work only: deserialization
            # above imports the fn's module graph, and counting that
            # cold-start cost against a dispatch deadline would wedge
            # every freshly restarted (healthy) worker on its first
            # dispatch -- retries could then never converge.  A hung
            # loads is still bounded by the driver-side deadline
            # backstops (queue.process_results / world.run).
            if beat is not None:
                beat.begin_dispatch()
            if notice is not None:
                # busy bracket: a SIGTERM landing mid-dispatch drains at
                # the body's next boundary instead of killing the process
                notice.busy = True
            if chaos is not None:
                chaos.on_dispatch()
            result = fn(*args, **kwargs)
            payload = ("ok", cloudpickle.dumps(result))
        except BaseException as e:  # ship the traceback home
            payload = ("err", cloudpickle.dumps(
                (type(e).__name__, str(e), traceback.format_exc())))
        if notice is not None:
            notice.busy = False
        if beat is not None:
            beat.end_dispatch()
        if telemetry is not None:
            telemetry.emit("dispatch_end", n=n_dispatch,
                           ok=payload[0] == "ok")
        conn.send_bytes(cloudpickle.dumps(payload))


class RemoteError(RuntimeError):
    """A worker-side exception, carrying the remote traceback."""

    def __init__(self, name: str, message: str, remote_traceback: str):
        super().__init__(f"{name}: {message}\n--- remote traceback ---\n"
                         f"{remote_traceback}")
        self.remote_traceback = remote_traceback


class Worker:
    """One persistent subprocess executing shipped callables in order."""

    def __init__(self, rank: int, env: Optional[Dict[str, str]] = None,
                 ctx: Optional[Any] = None,
                 heartbeat_s: Optional[float] = None):
        self.rank = rank
        self._env = dict(env or {})  # kept for restart()
        self._ctx = ctx or mp.get_context("spawn")
        # with a preemption grace budget configured the worker installs a
        # SIGTERM *notice* handler (runtime/preemption.py) -- SIGTERM no
        # longer means "die", it means "drain".  Driver-initiated
        # kill/restart must therefore go straight to SIGKILL: a swallowed
        # terminate() would cost the full join timeout per worker AND
        # write a bogus preemption flag into the shared run dir
        from .preemption import PREEMPT_GRACE_ENV
        self._sigterm_is_notice = bool(
            knobs.get_raw(PREEMPT_GRACE_ENV, env=self._env))
        # liveness channel interval: explicit arg > per-worker env >
        # process env > default; <= 0 disables the channel entirely
        self._heartbeat_s = (heartbeat_s if heartbeat_s is not None
                             else heartbeat_interval_s(self._env))
        # Two locks: _state_lock guards _pending (held only for list ops, so
        # the collector can always drain the pipe even while a sender is
        # blocked on a full pipe buffer -- holding one lock across a blocking
        # send_bytes can three-way-deadlock driver sender / collector /
        # worker); _send_lock serializes senders so _pending order matches
        # wire order.
        self._state_lock = threading.Lock()
        self._send_lock = threading.Lock()
        self._spawn()

    def _spawn(self) -> None:
        self._conn, child_conn = self._ctx.Pipe()
        # fresh heartbeat channel per generation: a restarted worker starts
        # with a clean beat (watchdog state resets with the process)
        self.heartbeat = (HeartbeatChannel(self._ctx)
                          if self._heartbeat_s > 0 else None)
        self._proc = self._ctx.Process(
            target=_worker_main,
            args=(child_conn, self._env, self.rank, self.heartbeat,
                  self._heartbeat_s),
            daemon=True, name=f"rla-tpu-worker-{self.rank}")
        self._proc.start()
        child_conn.close()
        self._pending: List[Future] = []
        # per-generation metadata shared with THIS generation's collector:
        # a watchdog reap marks the wedge diagnosis here so the collector
        # fails the generation's futures with WorkerWedged, not 'died'
        self._meta: Dict[str, Any] = {"wedge": None}
        # the collector binds ITS generation's pipe/pending/process: after a
        # restart() swaps them on self, the old thread must keep draining the
        # old pipe (to fail the old futures), not the new one
        self._collector = threading.Thread(
            target=self._collect,
            args=(self._conn, self._proc, self._pending, self._meta),
            daemon=True)
        self._collector.start()

    @property
    def is_alive(self) -> bool:
        return self._proc.is_alive()

    @property
    def exitcode(self) -> Optional[int]:
        return self._proc.exitcode

    def restart(self) -> None:
        """Respawn a dead (or wedged) worker process with the same rank/env.

        The reference is fail-fast by explicit design (no_restart actors,
        SURVEY.md §5.3 / reference: ray_ddp.py:119); this is the recovery
        primitive it deliberately lacks.  Pending futures on the old process
        fail with 'worker died'; the new process starts with a clean slate —
        callers re-dispatch work (resuming from checkpoints, see
        runtime/elastic.py)."""
        with self._send_lock:
            if self._proc.is_alive():
                if self._sigterm_is_notice:
                    # SIGTERM is a drain request in this worker, not a
                    # kill -- a busy rank would swallow it, cost the full
                    # join timeout, and stamp a bogus preemption flag
                    self._proc.kill()
                else:
                    self._proc.terminate()
            self._proc.join(timeout=10)
            if self._proc.is_alive():
                # SIGTERM blocked/ignored (wedged in uninterruptible work):
                # escalate, or we'd leak a duplicate-rank process whose open
                # pipe end keeps the old collector (and its futures) hanging
                self._proc.kill()
                self._proc.join(timeout=10)
            self._conn.close()  # unblocks the old collector via EOF/OSError
            self._spawn()

    # ------------------------------------------------------------------ #
    def execute(self, fn: Callable, *args, **kwargs) -> Future:
        """Ship fn to the worker; returns a Future (ObjectRef analog)."""
        return self.execute_blob(cloudpickle.dumps((fn, args, kwargs)))

    def execute_blob(self, blob: bytes, raw: bool = False) -> Future:
        """Ship an already-cloudpickled (fn, args, kwargs) blob.

        ``raw=True`` resolves the Future with the wire tuple
        ``(status, payload_bytes)`` without deserializing -- the host
        agent relays results to a remote driver this way, so classes only
        importable driver-side never unpickle on the agent."""
        fut: Future = Future()
        with self._send_lock:
            if not self._proc.is_alive():
                fut.set_exception(RuntimeError(
                    f"worker {self.rank} is dead"))
                return fut
            with self._state_lock:
                self._pending.append((fut, raw))
            try:
                self._conn.send_bytes(blob)  # may block; collector still runs
            except (BrokenPipeError, OSError) as e:
                # worker died between the liveness check and the send
                with self._state_lock:
                    if (fut, raw) in self._pending:
                        self._pending.remove((fut, raw))
                fut.set_exception(RuntimeError(
                    f"worker {self.rank} died before accepting work: {e}"))
        return fut

    def _collect(self, conn, proc, pending_list, meta=None) -> None:
        from .wire import rebuild_remote

        while True:
            try:
                blob = conn.recv_bytes()
            except (EOFError, OSError):
                with self._state_lock:
                    pending = list(pending_list)
                    pending_list.clear()
                    wedge = (meta or {}).get("wedge")
                for fut, _raw in pending:
                    if fut.done():
                        continue
                    if wedge is not None:
                        # deliberate watchdog kill of an alive-but-stuck
                        # process: callers must see a wedge, not a death
                        fut.set_exception(
                            WorkerWedged.for_rank(self.rank, wedge))
                    else:
                        fut.set_exception(RuntimeError(
                            f"worker {self.rank} died "
                            f"(exitcode={proc.exitcode})"))
                return
            with self._state_lock:
                fut, raw = pending_list.pop(0)
            try:
                status, payload = cloudpickle.loads(blob)
                if raw:
                    fut.set_result((status, payload))
                elif status == "ok":
                    fut.set_result(cloudpickle.loads(payload))
                else:
                    # same typed-rebuild registry as the agent relay
                    # (runtime/wire.py): a Preempted/WorkerWedged raised
                    # INSIDE dispatched work crosses the local pipe as
                    # typed as it crosses the relay
                    name, msg, tb = cloudpickle.loads(payload)
                    fut.set_exception(rebuild_remote(name, msg, tb))
            except BaseException as e:
                # a result that can't unpickle driver-side (e.g. a class only
                # importable in the worker) must fail ITS future, not kill
                # this collector thread and strand every later future
                if not fut.done():
                    fut.set_exception(RuntimeError(
                        f"failed to deserialize result from worker "
                        f"{self.rank}: {type(e).__name__}: {e}"))

    def telemetry_tail(self) -> Optional[Dict[str, Any]]:
        """This rank's spilled flight-recorder snapshot (telemetry/
        recorder.py), read from the shared ``RLA_TPU_TELEMETRY_DIR``
        spill file — works even when the worker is wedged or dead,
        which is exactly when the watchdog asks.  None when no
        telemetry dir is configured or the rank never spilled."""
        from ..telemetry.recorder import read_spill, spill_path_for
        path = spill_path_for(self.rank, env=self._env)
        return read_spill(path) if path else None

    def live_snapshot(self) -> Optional[Dict[str, Any]]:
        """This rank's LIVE telemetry snapshot (telemetry/live.py),
        scraped from its portfile-published loopback endpoint — the
        ClusterView seam.  None when the live plane is disabled, the
        rank never bound, or it stopped answering (a wedged rank's
        last snapshot survives in the ClusterView's view, not here)."""
        from ..telemetry.live import scrape_rank
        try:
            return scrape_rank(self.rank, env=self._env)
        except Exception:
            return None

    # parity surface (reference: ray_ddp.py:21-27)
    def set_env_var(self, key: str, value: str) -> Future:
        return self.execute(_set_env, key, value)

    def get_node_ip(self) -> str:
        return self.execute(_node_ip).result()

    def reap(self, diagnosis: Optional[Dict[str, Any]] = None) -> None:
        """Deliberate SIGTERM-then-SIGKILL of an alive-but-stuck worker
        (the watchdog's kill path).  Unlike a spontaneous death, pending
        futures fail with ``WorkerWedged`` carrying the diagnosis, so
        retry layers can tell a wedge from a crash.  The worker stays
        restartable (``restart()`` respawns with rank/env intact)."""
        with self._state_lock:
            self._meta["wedge"] = dict(diagnosis or {})
        self.kill()

    def kill(self) -> None:
        if self._proc.is_alive():
            if self._sigterm_is_notice:
                # SIGTERM means "drain" in this worker (see __init__);
                # a deliberate kill goes straight to SIGKILL
                self._proc.kill()
            else:
                self._proc.terminate()
            self._proc.join(timeout=5)
        if self._proc.is_alive():
            # SIGTERM isn't fatal to every worker: jax.distributed installs
            # a preemption notifier that CATCHES it (and gloo-wedged ranks
            # sit in C++), so escalate -- a surviving child would hang the
            # interpreter's exit join forever (mp joins daemons at exit)
            self._proc.kill()
            self._proc.join(timeout=5)

    def shutdown(self, timeout: float = 10.0) -> None:
        try:
            with self._send_lock:
                self._conn.send_bytes(_SENTINEL)
            self._proc.join(timeout=timeout)
        except (BrokenPipeError, OSError):
            pass
        if self._proc.is_alive():
            self.kill()


def _set_env(key: str, value: str) -> None:
    os.environ[key] = value


def _probe_ok() -> bool:
    return True


def _node_ip() -> str:
    from .net import node_ip
    return node_ip()


class ActorPool:
    """N workers + fan-out helpers (the reference's actor list + fan-out loop,
    ray_ddp.py:105,178-182).

    ``agents``: HostAgent addresses ("host:port") for multi-machine pools --
    workers become `agent.RemoteWorker`s spread in contiguous blocks over
    the agents (the reference's multi-node actor placement,
    ray_ddp.py:92-97).  None = local subprocesses."""

    def __init__(self, num_workers: int,
                 env_per_worker: Optional[Sequence[Dict[str, str]]] = None,
                 init_hook: Optional[Callable[[], None]] = None,
                 agents: Optional[Sequence[str]] = None):
        envs = env_per_worker or [{} for _ in range(num_workers)]
        assert len(envs) == num_workers
        self.workers: List[Any] = []
        # env overlays of ranks removed by drop(), kept so revive() can
        # re-place a host that came back (the elastic grow path)
        self._dropped_envs: Dict[int, Dict[str, str]] = {}
        try:
            if agents:
                from .agent import RemoteWorker, assign_agents
                assignment = assign_agents(list(agents), num_workers)
                for i in range(num_workers):
                    self.workers.append(
                        RemoteWorker(assignment[i], i, envs[i]))
            else:
                ctx = mp.get_context("spawn")
                for i in range(num_workers):
                    self.workers.append(Worker(i, envs[i], ctx))
        except BaseException:
            # one unreachable agent must not orphan the workers already
            # spawned on the healthy ones
            self.kill()
            raise
        if init_hook is not None:
            for f in self.execute_all(init_hook):
                f.result()

    def __len__(self) -> int:
        return len(self.workers)

    def execute_all(self, fn: Callable, *args, **kwargs) -> List[Future]:
        return [w.execute(fn, *args, **kwargs) for w in self.workers]

    def execute_per_worker(self, fn: Callable,
                           args_per_worker: Sequence[tuple]) -> List[Future]:
        return [w.execute(fn, *args)
                for w, args in zip(self.workers, args_per_worker)]

    def set_env_vars(self, env: Dict[str, str]) -> None:
        futs = []
        for k, v in env.items():
            futs += [w.set_env_var(k, str(v)) for w in self.workers]
        for f in futs:
            f.result()

    def node_ips(self) -> List[str]:
        return [w.get_node_ip() for w in self.workers]

    def local_ranks(self) -> List[int]:
        """Global->local rank map from the node-IP census
        (reference: ray_ddp.py:132-143)."""
        counts: Dict[str, int] = {}
        ranks = []
        for ip in self.node_ips():
            ranks.append(counts.get(ip, 0))
            counts[ip] = counts.get(ip, 0) + 1
        return ranks

    # ------------------------------------------------------------------ #
    # failure detection / recovery (absent-by-design in the reference,
    # SURVEY.md §5.3; first-class here)                                  #
    # ------------------------------------------------------------------ #
    def health_check(self) -> List[bool]:
        """Liveness per rank, detected from the OS process state.  Note
        this only sees DEAD workers; a wedged (alive-but-stuck) rank needs
        progress-based supervision -- see ``watch()``."""
        return [w.is_alive for w in self.workers]

    def watch(self, **kwargs) -> "Any":
        """A started ``runtime.watchdog.Watchdog`` over this pool: per-rank
        ``ok | slow | wedged | dead`` classification from heartbeats, with
        wedged ranks reaped so their futures fail ``WorkerWedged``."""
        from .watchdog import Watchdog
        return Watchdog(self, **kwargs).start()

    def add_worker(self, env: Optional[Dict[str, str]] = None,
                   rank: Optional[int] = None) -> Worker:
        """Grow the pool by one LOCAL worker (the serve tier's scale-up
        primitive, serve/controller.py).  The new worker gets the next
        free rank (max existing + 1 — ranks are identity, so a rank
        freed by ``drop`` is never reused within one pool lifetime) and
        its own env overlay.  Agent-backed pools are not supported: a
        remote scale-up needs placement the agent protocol doesn't
        express yet."""
        if self.workers and not isinstance(self.workers[0], Worker):
            raise RuntimeError(
                "add_worker supports local subprocess pools only "
                "(agent-backed pools cannot place new workers)")
        if rank is None:
            rank = max((w.rank for w in self.workers), default=-1) + 1
        w = Worker(rank, dict(env or {}), mp.get_context("spawn"))
        self.workers.append(w)
        log.warning("added worker rank %d; pool now %d rank(s) %s",
                    rank, len(self.workers),
                    [x.rank for x in self.workers])
        return w

    def restart_dead(self, init_hook: Optional[Callable[[], None]] = None) \
            -> List[int]:
        """Respawn every dead worker; returns the restarted ranks."""
        restarted = []
        for w in self.workers:
            if not w.is_alive:
                w.restart()
                restarted.append(w.rank)
        if restarted and init_hook is not None:
            for rank in restarted:
                self.workers[rank].execute(init_hook).result()
        if restarted:
            log.warning("restarted dead workers: %s", restarted)
        return restarted

    def _probe_sweep(self, workers, timeout_s: float) -> List[int]:
        """Parallel round-trip probes; returns the ranks that failed.
        The timeout is shared across the whole sweep (the dispatches run
        in parallel)."""
        import time as _time
        futs = [(w.rank, w.execute(_probe_ok)) for w in workers]
        deadline = _time.monotonic() + timeout_s
        lost = []
        for rank, f in futs:
            try:
                f.result(timeout=max(0.1, deadline - _time.monotonic()))
            except BaseException as e:
                log.warning("probe of worker %d failed: %s", rank, e)
                lost.append(rank)
        return lost

    def find_lost(self, timeout_s: float = 120.0, classify: bool = False):
        """Ranks that fail a trivial round-trip dispatch within
        ``timeout_s`` — the "is this host actually back?" probe run after
        a restart.  A permanently lost rank (host gone; chaos
        ``lost@rankN``) respawns and immediately dies, failing its probe
        future fast via the collector's EOF path; healthy ranks answer as
        soon as their interpreter finishes booting.

        ``classify=True`` distinguishes a REVIVABLE rank from a gone one
        (the elastic grow path): each failed rank gets one restart + one
        re-probe — a host that came back mid-sweep (chaos ``rejoin``
        clearing its ``lost`` marker) lands in ``"revived"`` and stays
        in the pool; the rest are ``"gone"``.  Returns
        ``{"gone": [...], "revived": [...]}`` instead of the flat
        list."""
        lost = self._probe_sweep(self.workers, timeout_s)
        if not classify:
            return lost
        if not lost:
            return {"gone": [], "revived": []}
        retry = [w for w in self.workers if w.rank in set(lost)]
        for w in retry:
            try:
                w.restart()
            except BaseException as e:
                log.warning("classify restart of worker %d failed: %s",
                            w.rank, e)
        still_lost = set(self._probe_sweep(retry, timeout_s))
        revived = sorted(set(lost) - still_lost)
        if revived:
            log.warning("lost rank(s) %s answered their re-probe; "
                        "keeping them in the pool", revived)
        return {"gone": sorted(still_lost), "revived": revived}

    def drop(self, ranks: Sequence[int]) -> List[int]:
        """Remove ``ranks`` from the pool (the elastic scale-down
        primitive): the named workers are killed and forgotten; survivors
        KEEP their original rank identity — rank is placement (which
        host/slot a worker is), not position, so a surviving rank 2 stays
        rank 2 while callers dispatch with logical ranks derived from
        list position (``ElasticRunner`` passes the new world size to
        ``args_per_worker``)."""
        gone = set(ranks)
        dropping = [w for w in self.workers if w.rank in gone]
        for w in dropping:
            # remember the env overlay: a dropped host that comes back
            # can be re-placed at its old rank via revive()
            self._dropped_envs[w.rank] = dict(getattr(w, "_env", {}) or {})
            try:
                w.kill()
            except BaseException:
                pass
        self.workers = [w for w in self.workers if w.rank not in gone]
        dropped = [w.rank for w in dropping]
        if dropped:
            log.warning("dropped lost workers %s; pool now %d rank(s) %s",
                        dropped, len(self.workers),
                        [w.rank for w in self.workers])
        return dropped

    def dropped_ranks(self) -> List[int]:
        """Ranks removed by ``drop`` whose env overlay is remembered —
        the revival candidates the elastic grow path retries."""
        return sorted(self._dropped_envs)

    def revive(self, rank: int,
               probe_timeout_s: float = 30.0) -> Optional[Worker]:
        """Re-place a previously dropped rank (the elastic grow
        primitive): spawn a fresh Worker at the SAME rank with its
        remembered env overlay and probe it.  Returns the worker (now
        back in the pool, inserted in rank order so logical-rank
        dispatch stays deterministic) on success; None when the rank was
        never dropped, the pool is agent-backed, or the host is still
        gone (the probe failed — the spawn is killed and the rank stays
        dropped for a later retry)."""
        env = self._dropped_envs.get(rank)
        if env is None:
            return None
        if self.workers and not isinstance(self.workers[0], Worker):
            log.warning("revive(%d): agent-backed pools cannot re-place "
                        "workers", rank)
            return None
        w = Worker(rank, dict(env), mp.get_context("spawn"))
        if self._probe_sweep([w], probe_timeout_s):
            try:
                w.kill()
            except BaseException:
                pass
            log.warning("revive(%d): host still gone (probe failed)",
                        rank)
            return None
        del self._dropped_envs[rank]
        self.workers.append(w)
        self.workers.sort(key=lambda x: x.rank)
        log.warning("revived worker rank %d; pool now %d rank(s) %s",
                    rank, len(self.workers),
                    [x.rank for x in self.workers])
        return w

    def restart_all(self, init_hook: Optional[Callable[[], None]] = None) \
            -> List[int]:
        """Respawn EVERY worker, alive or not.

        The recovery primitive for collective (SPMD) work: when one rank
        dies mid-collective its peers stay alive-but-wedged in the broken
        collective, so restarting only the dead rank would re-dispatch into
        workers that never dequeue again.  All ranks restart together."""
        for w in self.workers:
            w.restart()
        ranks = [w.rank for w in self.workers]
        if init_hook is not None:
            for f in self.execute_all(init_hook):
                f.result()
        log.warning("restarted all workers: %s", ranks)
        return ranks

    def shutdown(self) -> None:
        # reverse rank order: rank 0 hosts the jax.distributed
        # coordination service, and a peer outliving it by milliseconds
        # logs a FATAL "leader died" before being reaped
        for w in reversed(self.workers):
            w.shutdown()

    def kill(self) -> None:
        for w in reversed(self.workers):
            w.kill()

    def __enter__(self) -> "ActorPool":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()
