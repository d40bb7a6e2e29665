"""tune.run: multi-trial hyperparameter search with the reference's shape.

Capability analog of Ray Tune as the reference consumes it
(reference: examples/ray_ddp_example.py:94-113 -- tune.run over a train
function, metric/mode, num_samples, analysis.best_config; tests at
ray_lightning/tests/test_tune.py:33-75 -- results_df iteration counts and
best_checkpoint existence).

TPU-native redesign: trials run **sequentially in-process by default** --
on TPU, one process owns the chips, so concurrent trials would fight over
them; multi-trial parallelism across hosts is the actor runtime's job.  Each
trial's trainable runs in a worker thread while the driver thread drains the
callable-trampoline queue (the reference's process_results loop,
reference: util.py:96-109), preserving the exact report/checkpoint
architecture so the same callbacks work over the subprocess/actor executors.
"""

from __future__ import annotations

import math
import os
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..runtime import session as session_lib
from ..runtime.queue import TrampolineQueue, process_results
from ..utils import checkpoint as ckpt_lib
from ..utils.logging import log
from .search import generate_trial_configs


class Trial:
    def __init__(self, trial_id: str, config: Dict[str, Any], local_dir: str):
        self.trial_id = trial_id
        self.config = config
        self.logdir = os.path.join(local_dir, trial_id)
        os.makedirs(self.logdir, exist_ok=True)
        self.results: List[Dict[str, Any]] = []
        self.checkpoints: List[Tuple[int, str]] = []  # (step, path)
        self.status = "PENDING"
        self.error: Optional[BaseException] = None
        self.should_stop = False  # set by a scheduler's STOP decision

    @property
    def last_result(self) -> Dict[str, Any]:
        return self.results[-1] if self.results else {}

    @property
    def training_iteration(self) -> int:
        return len(self.results)

    def report(self, metrics: Dict[str, Any]) -> None:
        row = dict(metrics)
        row["training_iteration"] = self.training_iteration + 1
        row["trial_id"] = self.trial_id
        self.results.append(row)

    def create_checkpoint(self, payload: Dict[str, Any], step: int,
                          filename: str) -> str:
        cdir = os.path.join(self.logdir, f"checkpoint_{step:06d}")
        path = os.path.join(cdir, filename)
        ckpt_lib.atomic_save(payload, path)
        self.checkpoints.append((step, path))
        return path

    def best_checkpoint_path(self) -> Optional[str]:
        return self.checkpoints[-1][1] if self.checkpoints else None


class _TrialSession:
    """Driver-side marker that a trial is active in this process (the analog
    of a Ray Tune session; probed via is_session_enabled,
    reference: ray_lightning/tune.py:10-22)."""

    def __init__(self, trial: Trial, scheduler=None, devices=None):
        self.trial = trial
        self.scheduler = scheduler
        self.devices = devices  # this trial's device partition (or None)
        self._lock = threading.Lock()

    def report(self, **metrics) -> None:
        with self._lock:
            self.trial.report(metrics)
            if self.scheduler is not None and not self.trial.should_stop:
                # schedulers hold cross-trial state (ASHA brackets, median
                # histories); serialize their decisions across concurrent
                # trials
                with _scheduler_lock:
                    decision = self.scheduler.on_result(
                        self.trial, self.trial.last_result)
                if decision == self.scheduler.STOP:
                    self.trial.should_stop = True


_scheduler_lock = threading.Lock()


_trial_session: Optional[_TrialSession] = None
# thread-local overlay for concurrent trials (each trial's driver +
# trainable threads bind their own session; sequential mode keeps using
# the process-global)
_tls = threading.local()


def _current_session() -> Optional[_TrialSession]:
    return getattr(_tls, "session", None) or _trial_session


def _bind_trial_session(session: Optional[_TrialSession]) -> None:
    _tls.session = session


def is_session_enabled() -> bool:
    return _current_session() is not None


def get_trial_session() -> _TrialSession:
    s = _current_session()
    if s is None:
        raise RuntimeError("tune.report()/checkpointing used outside a "
                           "tune.run() trial")
    return s


def trial_should_stop() -> bool:
    """True when the active trial was STOPped by a scheduler; the Tune
    callbacks poll this and end training cleanly via trainer.should_stop.

    Inside a PROCESS-isolated trial there is no local trial session; the
    scheduler's decision lives driver-side, so the poll crosses the
    network queue's query channel (the stop analog of the report
    trampoline)."""
    s = _current_session()
    if s is not None:
        return s.trial.should_stop
    if session_lib.session_exists():
        sess = session_lib.get_session()
        q = getattr(sess, "_queue", None)
        if hasattr(q, "query"):
            try:
                return bool(q.query("should_stop", sess.rank))
            except BaseException:
                return False  # driver gone; the trial will fail on its own
    return False


def dispatch_trial_query(name: str, payload,
                         lookup: Callable[[int], Optional[_TrialSession]]):
    """Driver-side dispatch for the queue query channel, shared by the
    tune driver's QueueServer and the fit-level nested forwarder
    (runtime/bootstrap._nested_query_handler).  ``lookup(rank)`` resolves
    the owning trial session.  Returns None for anything unresolvable --
    callers treat None as "unhandled" and fall back to the thunk path."""
    if name == "should_stop":
        s = lookup(payload)
        return bool(s is not None and s.trial.should_stop)
    if name == "report":
        rank, metrics = payload
        s = lookup(rank)
        if s is None:
            return None
        s.report(**metrics)
        return bool(s.trial.should_stop)
    if name == "checkpoint":
        rank, pl, step, filename = payload
        s = lookup(rank)
        if s is None:
            return None
        return s.trial.create_checkpoint(pl, step, filename)
    return None


def trial_devices() -> Optional[list]:
    """The device partition assigned to the current trial, or None when
    trials own all devices (sequential mode).  Pass to an accelerator:
    ``RayTPUAccelerator(devices=tune.trial_devices())``."""
    s = _current_session()
    return None if s is None else s.devices


def report(**metrics) -> None:
    """Report metrics for the current trial.

    Callable from the driver thread (via trampoline thunks, the reference
    path) or directly from the trial thread (convenience the reference
    lacked -- its workers had no session and HAD to trampoline,
    reference: tune.py:97-101).  Inside a PROCESS trial there is no local
    trial session; the call trampolines itself to the driver through the
    runtime session's queue (exactly the reference's worker->trial-process
    report flow, reference: tune.py:101 -> session.py:61-63).
    """
    if _current_session() is None:
        from ..runtime import session as rt_session
        if rt_session.session_exists():
            sess = rt_session.get_session()
            q = getattr(sess, "_queue", None)
            if hasattr(q, "query"):
                # synchronous: the driver records the report AND runs the
                # scheduler before this returns, so a following
                # trial_should_stop() deterministically sees the decision
                handled = q.query("report", (sess.rank, dict(metrics)))
                if handled is not None:
                    return
                # None = no query handler up the chain could resolve the
                # trial (e.g. concurrent thread trials, whose sessions are
                # thread-bound and invisible to reader threads): the thunk
                # path still works -- the drain runs with the session bound
            rt_session.put_queue(lambda: report(**metrics))
            return
    get_trial_session().report(**metrics)


def checkpoint_payload(payload: Dict[str, Any], step: int,
                       filename: str = "checkpoint") -> str:
    """Write ``payload`` as the current trial's checkpoint.  Routed like
    ``report``: direct with a local trial session, synchronous query from
    a process trial (keeping the checkpoint-before-report registration
    order the reference documents, reference: tune.py:197-199)."""
    if _current_session() is None:
        from ..runtime import session as rt_session
        if rt_session.session_exists():
            sess = rt_session.get_session()
            q = getattr(sess, "_queue", None)
            if hasattr(q, "query"):
                path = q.query("checkpoint",
                               (sess.rank, payload, step, filename))
                if path is not None:
                    return path
            # unhandled up the chain: thunk fallback (see report())
            rt_session.put_queue(
                lambda: checkpoint_payload(payload, step, filename))
            return ""
    return get_trial_session().trial.create_checkpoint(payload, step, filename)


class ExperimentAnalysis:
    """Results container (reference surface: analysis.best_config at
    README.md:107, results_df / best_checkpoint at tests/test_tune.py:42-75)."""

    def __init__(self, trials: List[Trial], metric: Optional[str],
                 mode: str = "min"):
        self.trials = trials
        self.metric = metric
        self.mode = mode

    def _score(self, trial: Trial) -> Optional[float]:
        if self.metric is None or self.metric not in trial.last_result:
            return None
        return float(trial.last_result[self.metric])

    @property
    def best_trial(self) -> Trial:
        scored = [(self._score(t), t) for t in self.trials
                  if self._score(t) is not None]
        if not scored:
            if self.metric is not None:
                raise ValueError(
                    f"no trial reported metric {self.metric!r}")
            return self.trials[0]
        pick = min if self.mode == "min" else max
        return pick(scored, key=lambda st: st[0])[1]

    @property
    def best_config(self) -> Dict[str, Any]:
        return self.best_trial.config

    @property
    def best_result(self) -> Dict[str, Any]:
        return self.best_trial.last_result

    @property
    def best_checkpoint(self) -> Optional[str]:
        return self.best_trial.best_checkpoint_path()

    @property
    def results_df(self):
        """pandas DataFrame of final results, one row per trial, with
        config.* columns (shape matched to the reference's assertions,
        tests/test_tune.py:42-44)."""
        import pandas as pd
        rows = []
        for t in self.trials:
            row = dict(t.last_result)
            for k, v in t.config.items():
                row[f"config.{k}"] = v
            rows.append(row)
        return pd.DataFrame(rows)


def _execute_trial(trainable, trial: Trial, scheduler, devices,
                   raise_on_failed_trial: bool, verbose: int,
                   set_global: bool) -> None:
    """Run one trial on the CURRENT thread: bind sessions (thread-local,
    plus the process-global in sequential mode), fan the trainable out to a
    worker thread, and drain the trampoline queue until it finishes."""
    global _trial_session
    q = TrampolineQueue()
    tsess = _TrialSession(trial, scheduler, devices=devices)
    rt = session_lib.TpuSession(0, q)
    _bind_trial_session(tsess)
    session_lib.bind_session_to_thread(rt)
    if set_global:
        _trial_session = tsess
        session_lib.install_session(rt)

    def _bind_worker():  # runs on the pool's worker thread
        _bind_trial_session(tsess)
        session_lib.bind_session_to_thread(rt)

    trial.status = "RUNNING"
    try:
        with ThreadPoolExecutor(max_workers=1,
                                initializer=_bind_worker) as pool:
            fut = pool.submit(trainable, trial.config)
            process_results([fut], q)
        trial.status = "STOPPED" if trial.should_stop else "TERMINATED"
    except BaseException as e:  # noqa: BLE001 - fail-fast like ray.get
        trial.status = "ERROR"
        trial.error = e
        log.warning("trial %s failed: %s", trial.trial_id, e)
        if raise_on_failed_trial:
            raise
    finally:
        _bind_trial_session(None)
        session_lib.bind_session_to_thread(None)
        if set_global:
            session_lib.shutdown_session()
            _trial_session = None
    if verbose:
        log.warning("trial %s finished: %s", trial.trial_id,
                    trial.last_result)


def _process_trial_main(trainable, config, queue_address, trial_rank):
    """Body of a PROCESS-isolated trial: runs inside a fresh worker
    subprocess; report/checkpoint thunks reach the driver through the
    network queue under this trial's rank."""
    from ..runtime import session as session_lib
    from ..runtime.queue import QueueClient

    client = QueueClient(queue_address)
    session_lib.init_session(trial_rank, client)
    try:
        return trainable(config)
    finally:
        # barrier: the trial's result races its last reports (different
        # channels); flush guarantees the driver enqueued them first.  A
        # dead driver must not mask the trainable's real exception.
        try:
            client.flush()
        except (ConnectionError, OSError):
            pass


def _run_trials_in_processes(trainable, trials, scheduler,
                             max_concurrent: int,
                             raise_on_failed_trial: bool, verbose: int,
                             trial_env: Optional[Dict[str, str]],
                             agents: Optional[List[str]] = None):
    """One fresh worker subprocess per trial (the reference's trial
    isolation: Tune trials are separate processes,
    examples/ray_ddp_example.py:101-113).  A trial that hard-crashes
    (os._exit, fatal XLA error) is recorded as ERROR; the experiment
    continues.  Thunks carry the trial's rank, and the drain binds that
    trial's session before executing, so concurrent trials can't
    cross-report.

    ``agents``: HostAgent addresses -- trial subprocesses place
    round-robin across the hosts (the reference's trials-anywhere-on-the-
    cluster placement, reference: examples/ray_ddp_example.py:101-113),
    with reports/checkpoints/stop-polls riding the network queue."""
    import time as time_mod

    from ..runtime.actors import Worker
    from ..runtime.queue import QueueServer, TrampolineQueue

    sessions = {i: _TrialSession(t, scheduler) for i, t in enumerate(trials)}

    def _query(name, payload):
        # worker-side trial_should_stop() polls land here (reader thread);
        # reading the bool the drain thread sets is atomic under the GIL.
        # report/checkpoint are synchronous: handled before the query
        # returns, so the scheduler's decision for report k is visible to
        # the trial's very next should_stop poll -- no drain-timing race
        # (_TrialSession.report serializes itself and the scheduler)
        return dispatch_trial_query(name, payload,
                                    lambda rank: sessions.get(rank))

    from ..runtime.agent import queue_bind_for_agents
    q = TrampolineQueue()
    server = QueueServer(q, bind=queue_bind_for_agents(agents),
                         query_handler=_query)

    def _spawn_worker(i: int):
        if agents:
            from ..runtime.agent import RemoteWorker, parse_agent_spec
            addr = parse_agent_spec(agents[i % len(agents)])[0]
            return RemoteWorker(addr, i, dict(trial_env or {}))
        return Worker(i, dict(trial_env or {}))

    def drain() -> None:
        while True:
            item = q.get_nowait()
            if item is None:
                return
            rank, thunk = item
            _bind_trial_session(sessions.get(rank))
            try:
                thunk()
            except Exception as e:
                # a failing thunk (checkpoint write, scheduler decision)
                # must not abort the whole experiment when failures are
                # tolerated; record it on the owning trial
                if rank in sessions:
                    sessions[rank].trial.error = e
                log.warning("trial thunk failed (trial %s): %s",
                            sessions[rank].trial.trial_id
                            if rank in sessions else rank, e)
                if raise_on_failed_trial:
                    failures.append(e)
            finally:
                _bind_trial_session(None)

    pending: Dict[int, tuple] = {}  # idx -> (worker, future)
    queue_idx = list(range(len(trials)))
    failures: List[BaseException] = []
    try:
        while queue_idx or pending:
            while queue_idx and len(pending) < max_concurrent:
                i = queue_idx.pop(0)
                trials[i].status = "RUNNING"
                try:
                    w = _spawn_worker(i)
                except BaseException as e:
                    # an unreachable agent fails THIS trial, not the whole
                    # experiment (same containment as a trial crash)
                    trials[i].status = "ERROR"
                    trials[i].error = e
                    log.warning("trial %s failed to place: %s",
                                trials[i].trial_id, e)
                    if raise_on_failed_trial:
                        failures.append(e)
                        queue_idx.clear()
                    continue
                fut = w.execute(_process_trial_main, trainable,
                                trials[i].config, server.address, i)
                pending[i] = (w, fut)
            drain()
            for i, (w, fut) in list(pending.items()):
                if not fut.done():
                    continue
                drain()  # results enqueued before completion land first
                trial = trials[i]
                err = fut.exception()
                if err is not None:
                    trial.status = "ERROR"
                    trial.error = err
                    log.warning("trial %s failed: %s", trial.trial_id, err)
                    if raise_on_failed_trial:
                        failures.append(err)
                else:
                    trial.status = ("STOPPED" if trial.should_stop
                                    else "TERMINATED")
                    if verbose:
                        log.warning("trial %s finished: %s", trial.trial_id,
                                    trial.last_result)
                w.kill()
                del pending[i]
                if failures:
                    queue_idx.clear()
            if failures:
                break
            time_mod.sleep(0.01)
        drain()
    finally:
        for w, _f in pending.values():
            w.kill()
        server.close()
    if failures:
        raise failures[0]


# default train-step autotuning space: the three step-shape knobs the
# MFU ladder (scripts/mfu_sweep.py) showed move step time on real
# hardware — what the rematerialized backward may keep, the
# flash-attention tile shape, and (new) how the FSDP compute view is
# assembled (whole-tree up-front vs overlapped layer-wise in the scan)
def default_step_space() -> Dict[str, Any]:
    from .search import choice
    return {
        "remat_policy": choice(["none", "nothing", "dots",
                                "dots_with_no_batch_dims"]),
        "flash_block_q": choice([128, 256, 512, 1024]),
        "flash_block_k": choice([128, 256, 512, 1024]),
        "gather_mode": choice(["tree", "scan"]),
    }


def autotune_step(measure: Callable[[Dict[str, Any]], float],
                  space: Optional[Dict[str, Any]] = None,
                  default_config: Optional[Dict[str, Any]] = None,
                  n_trials: int = 12,
                  searcher=None,
                  seed: int = 0,
                  verbose: int = 0) -> Dict[str, Any]:
    """Closed-loop train-step autotuning: the repo's own TPE searcher
    (tune/search.py) drives the step-shape knobs — remat policy, flash
    block sizes, FSDP gather mode — against a MEASURED step time.

    ``measure(config) -> step_time_seconds`` runs one short, honest
    measurement of a train step under ``config`` (scripts/mfu_sweep.py's
    variant machinery is the intended implementation: same timed-window
    / sync discipline as the driver bench).  A measurement that raises
    records ``inf`` for that trial and the search moves on (a config can
    legitimately be un-compilable — e.g. a flash block exceeding the
    sequence length).

    The DEFAULT config is measured first and enters the history as trial
    0, so the returned ``best_config`` can never be slower than the
    default — the search can only refine it.  Returns::

        {"best_config", "best_step_time_s", "default_step_time_s",
         "n_trials", "trials": [{"config", "step_time_s"}, ...]}
    """
    from .search import TPESearcher

    space = dict(space or default_step_space())
    default_config = dict(default_config or {
        "remat_policy": "none", "flash_block_q": 512,
        "flash_block_k": 512, "gather_mode": "tree"})
    searcher = searcher or TPESearcher(
        n_startup=max(2, min(8, n_trials // 2)), seed=seed)
    searcher.set_search_properties("step_time_s", "min")

    trials: List[Dict[str, Any]] = []

    def one(config: Dict[str, Any]) -> float:
        try:
            dt = float(measure(dict(config)))
        except Exception as e:  # an untunable config is a data point,
            log.warning("autotune_step: config %s failed (%s: %s)",
                        config, type(e).__name__, e)  # not an abort
            dt = float("inf")
        trials.append({"config": dict(config), "step_time_s": dt})
        if math.isfinite(dt):
            searcher.record(config, dt)
        if verbose:
            log.warning("autotune_step trial %d: %.2f ms  %s",
                        len(trials), dt * 1e3, config)
        return dt

    default_dt = one(default_config)
    for _ in range(max(0, n_trials - 1)):
        one(searcher.suggest(dict(space)))
    best = min(trials, key=lambda t: t["step_time_s"])
    # None (JSON null) rather than inf/NaN when either side failed to
    # measure: inf/inf is NaN, and json.dumps would emit the
    # non-standard Infinity/NaN tokens strict consumers reject
    speedup = (default_dt / best["step_time_s"]
               if math.isfinite(default_dt)
               and math.isfinite(best["step_time_s"])
               and best["step_time_s"] > 0 else None)
    return {
        "best_config": dict(best["config"]),
        "best_step_time_s": best["step_time_s"],
        "default_step_time_s": default_dt,
        "speedup_vs_default": speedup,
        "n_trials": len(trials),
        "trials": trials,
    }


def run(trainable: Callable[[Dict[str, Any]], Any],
        config: Optional[Dict[str, Any]] = None,
        num_samples: int = 1,
        metric: Optional[str] = None,
        mode: str = "min",
        name: Optional[str] = None,
        local_dir: Optional[str] = None,
        resources_per_trial: Optional[Dict[str, Any]] = None,
        seed: int = 0,
        raise_on_failed_trial: bool = True,
        verbose: int = 0,
        scheduler=None,
        search_alg=None,
        max_concurrent_trials: int = 1,
        devices_per_trial: Optional[int] = None,
        trial_executor: str = "thread",
        trial_env: Optional[Dict[str, str]] = None,
        agents: Optional[List[str]] = None,
        **_compat_kwargs) -> ExperimentAnalysis:
    """Run `trainable(config)` for every sampled/grid config.

    ``trial_executor``: "thread" (default -- trials share this process and
    its devices; on TPU one process owns the chips) or "process" -- each
    trial runs in a FRESH subprocess (the reference's isolation: Tune
    trials are separate processes, examples/ray_ddp_example.py:101-113), so
    a hard crash (OOM, fatal XLA error, os._exit) marks that trial ERROR
    while the experiment completes.  ``trial_env`` sets env vars in trial
    subprocesses pre-fork (e.g. JAX_PLATFORMS / XLA device counts).

    `resources_per_trial` (the reference's cpu/extra_cpu bookkeeping,
    examples/ray_ddp_example.py:107-112) caps process-executor concurrency
    so trials never oversubscribe the host: at most
    ``os.cpu_count() // (cpu + extra_cpu)`` trials run at once.
    `scheduler` is a tune.schedulers.TrialScheduler (e.g. ASHAScheduler)
    consulted on every reported result; its STOP decisions end trials
    early and mark them STOPPED (process trials poll the decision over
    the network queue's query channel and stop at the next report
    boundary).

    ``agents``: with ``trial_executor="process"``, HostAgent addresses
    (defaults to ``RLA_TPU_AGENTS``) to place trial subprocesses
    round-robin across cluster hosts -- the reference's
    trials-anywhere-on-the-cluster placement
    (examples/ray_ddp_example.py:101-113).

    ``max_concurrent_trials > 1`` runs trials in parallel over disjoint
    device partitions — the trials x workers-per-trial parallelism the
    reference gets from Ray Tune's placement
    (examples/ray_ddp_example.py:101-113).  Each concurrent trial leases a
    partition of ``devices_per_trial`` devices (default: an equal split);
    the trainable claims it via ``tune.trial_devices()``:
    ``RayTPUAccelerator(devices=tune.trial_devices())``.
    """
    name = name or f"tune_{int(time.time())}"
    local_dir = local_dir or os.path.join(os.getcwd(), "rla_tpu_results")
    exp_dir = os.path.join(local_dir, name)
    os.makedirs(exp_dir, exist_ok=True)

    if trial_executor not in ("thread", "process"):
        raise ValueError(f"trial_executor must be 'thread' or 'process', "
                         f"got {trial_executor!r}")
    if scheduler is not None:
        scheduler.set_search_properties(metric, mode)
    if search_alg is not None:
        if max_concurrent_trials > 1 or trial_executor == "process":
            raise ValueError(
                "search_alg suggests each trial from completed-trial "
                "history and requires sequential in-process trials "
                "(max_concurrent_trials=1, trial_executor='thread')")
        # model-based sequential search: each config is suggested from the
        # history of completed trials instead of sampled up front
        search_alg.set_search_properties(metric, mode)
        configs = [None] * num_samples
    else:
        configs = generate_trial_configs(config, num_samples, seed)

    if trial_executor == "process":
        if agents is None:
            from ..runtime.agent import agents_from_env
            agents = agents_from_env()
        trials = [Trial(f"trial_{i:05d}", cfg, exp_dir)
                  for i, cfg in enumerate(configs)]
        concurrent = max(1, max_concurrent_trials)
        if resources_per_trial:
            per = (int(resources_per_trial.get("cpu", 1))
                   + int(resources_per_trial.get("extra_cpu", 0)))
            cap = max(1, (os.cpu_count() or 1) // max(1, per))
            if cap < concurrent:
                log.warning("resources_per_trial caps concurrency at %d "
                            "(%d host cpus / %d per trial)", cap,
                            os.cpu_count() or 1, per)
            concurrent = min(concurrent, cap)
        _run_trials_in_processes(trainable, trials, scheduler, concurrent,
                                 raise_on_failed_trial, verbose, trial_env,
                                 agents=agents)
        return ExperimentAnalysis(trials, metric, mode)

    if max_concurrent_trials > 1:
        import queue as queue_mod

        import jax
        devs = list(jax.devices())
        per = devices_per_trial or max(1, len(devs) // max_concurrent_trials)
        n_groups = min(max_concurrent_trials, len(devs) // per)
        if n_groups < 1:
            raise ValueError(
                f"devices_per_trial={per} exceeds the {len(devs)} visible "
                f"devices")
        free: "queue_mod.Queue" = queue_mod.Queue()
        for g in range(n_groups):
            free.put(devs[g * per:(g + 1) * per])
        trials = [Trial(f"trial_{i:05d}", cfg, exp_dir)
                  for i, cfg in enumerate(configs)]

        def _leased(trial):
            group = free.get()
            try:
                _execute_trial(trainable, trial, scheduler, group,
                               raise_on_failed_trial, verbose,
                               set_global=False)
            finally:
                free.put(group)

        outer = ThreadPoolExecutor(max_workers=n_groups)
        try:
            futures = [outer.submit(_leased, t) for t in trials]
            for f in futures:
                f.result()  # propagate raise_on_failed_trial errors
        except BaseException:
            # fail-fast parity with sequential mode: un-started trials are
            # cancelled (already-running ones finish their lease)
            outer.shutdown(wait=True, cancel_futures=True)
            raise
        outer.shutdown(wait=True)
        return ExperimentAnalysis(trials, metric, mode)

    trials = []
    for i, cfg in enumerate(configs):
        if search_alg is not None:
            cfg = search_alg.suggest(dict(config or {}))
        trial = Trial(f"trial_{i:05d}", cfg, exp_dir)
        trials.append(trial)
        _execute_trial(trainable, trial, scheduler, None,
                       raise_on_failed_trial, verbose, set_global=True)
        if search_alg is not None and metric is not None and \
                trial.last_result.get(metric) is not None:
            search_alg.record(cfg, float(trial.last_result[metric]))
    return ExperimentAnalysis(trials, metric, mode)
