"""Progress-based liveness: heartbeat channel, ok/slow/wedged/dead
classification, wedge reaping, and elastic recovery from hangs.

The actor runtime's original failure detection was process-liveness only
(SURVEY.md §5.3: the reference has none at all); these tests pin the
upgrade from "process exited" to "process stopped making progress" --
the failure mode that burns a run's whole timeout in silence.  All assertions are event- or monotonic-deadline
based (future results, condition-signaled watchdog states): no
sleep-poll flakes, no TPU, no jax computation.
"""

import time
from concurrent.futures import Future

import pytest

from ray_lightning_accelerators_tpu.runtime.actors import ActorPool, Worker
from ray_lightning_accelerators_tpu.runtime.elastic import ElasticRunner
from ray_lightning_accelerators_tpu.runtime.queue import process_results
from ray_lightning_accelerators_tpu.runtime.watchdog import (
    STATE_DEAD, STATE_OK, STATE_SLOW, STATE_WEDGED, HeartbeatChannel,
    Watchdog, WorkerWedged, stall_record)

HB = 0.05  # fast heartbeat for tests


def _ok(x=1):
    return x * 2


def _crash(code=3):
    import os
    os._exit(code)


def _sleep_forever():
    import time
    time.sleep(10_000)


def _sleep(s):
    import time
    time.sleep(s)
    return s


# --------------------------------------------------------------------- #
# channel + record shapes (pure, no subprocesses)                        #
# --------------------------------------------------------------------- #
def test_heartbeat_channel_semantics():
    ch = HeartbeatChannel()
    snap = ch.snapshot()
    assert snap["busy_s"] is None
    assert snap["dispatches"] == 0
    assert not snap["started"]  # no worker has stamped yet
    ch.stamp()
    assert ch.snapshot()["started"]
    ch.begin_dispatch()
    snap = ch.snapshot()
    assert snap["dispatches"] == 1
    assert snap["busy_s"] is not None
    ch.end_dispatch()
    snap = ch.snapshot()
    assert snap["busy_s"] is None
    assert snap["beat_age_s"] < 5.0


def test_worker_wedged_message_roundtrip():
    diag = {"detail": "heartbeat stale 1.20s > wedge timeout 1.00s",
            "beat_age_s": 1.2, "busy_s": None, "dispatches": 4}
    e = WorkerWedged.for_rank(3, diag)
    assert e.rank == 3
    assert e.diagnosis["dispatches"] == 4
    # the agent relay ships exceptions as (name, str, tb): the message
    # alone must reconstruct the typed wedge with its diagnosis
    back = WorkerWedged.from_message(str(e))
    assert back.rank == 3
    assert back.diagnosis["beat_age_s"] == 1.2
    assert "stale" in back.diagnosis["detail"]


def test_stall_record_mirrors_death_record_shape():
    e = WorkerWedged.for_rank(1, {"detail": "dispatch busy 9s > deadline",
                                  "busy_s": 9.0})
    rec = stall_record(e, "fit")
    assert rec["metric"] == "worker_stall"
    assert rec["error"] == "worker wedged"
    assert rec["stage"] == "fit"
    assert rec["rank"] == 1
    assert rec["stall_busy_s"] == 9.0
    assert len(rec["detail"]) <= 500
    rec = stall_record(TimeoutError("5 of 8 futures unresolved"), "test")
    assert rec["error"] == "attempt deadline exceeded"


def test_process_results_deadline_backstop():
    # driver-side hard stop for when supervision itself is broken: a
    # never-resolving future must raise, not hang the driver forever
    with pytest.raises(TimeoutError, match="unresolved"):
        process_results([Future()], None, poll_s=0.01, deadline_s=0.2)


# --------------------------------------------------------------------- #
# live workers                                                           #
# --------------------------------------------------------------------- #
def test_worker_heartbeat_stamps_and_counts_dispatches():
    w = Worker(0, heartbeat_s=HB)
    try:
        assert w.execute(_ok, 21).result(timeout=60) == 42
        snap = w.heartbeat.snapshot()
        assert snap["started"]
        assert snap["dispatches"] == 1
        assert snap["busy_s"] is None  # idle between dispatches
    finally:
        w.kill()


def test_busy_marker_while_dispatch_runs():
    w = Worker(0, heartbeat_s=HB)
    try:
        assert w.execute(_ok).result(timeout=60) == 2  # worker fully up
        w.execute(_sleep_forever)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            snap = w.heartbeat.snapshot()
            if snap["busy_s"] is not None and snap["dispatches"] == 2:
                break
            time.sleep(0.02)
        else:
            pytest.fail(f"busy marker never appeared: "
                        f"{w.heartbeat.snapshot()}")
    finally:
        w.kill()


def test_watchdog_classifies_dead_worker():
    w = Worker(0, heartbeat_s=HB)
    try:
        with pytest.raises(RuntimeError, match="died"):
            w.execute(_crash).result(timeout=60)
        w._proc.join(timeout=30)
        wd = Watchdog([w], wedge_timeout_s=5.0, auto_reap=False)
        state, info = wd.classify(w)
        assert state == STATE_DEAD
        assert "exitcode" in info["detail"]
    finally:
        w.kill()


def test_watchdog_reaps_hung_dispatch_as_wedged():
    w = Worker(0, heartbeat_s=HB)
    wd = None
    try:
        assert w.execute(_ok).result(timeout=60) == 2
        fut = w.execute(_sleep_forever)
        wd = Watchdog([w], wedge_timeout_s=10.0, dispatch_deadline_s=0.4,
                      poll_s=HB).start()
        with pytest.raises(WorkerWedged) as ei:
            fut.result(timeout=60)
        e = ei.value
        assert e.rank == 0
        assert "deadline" in e.diagnosis["detail"]
        assert e.diagnosis["busy_s"] > 0.4
        assert len(wd.reaped) == 1
        assert wd.reaped[0]["error"] == "worker wedged"
        # after the reap the process is gone
        assert wd.wait_for_state(0, STATE_DEAD, timeout=30)
    finally:
        if wd is not None:
            wd.stop()
        w.kill()


def test_watchdog_slow_straggler_not_killed():
    w = Worker(0, heartbeat_s=HB)
    wd = None
    try:
        assert w.execute(_ok).result(timeout=60) == 2
        fut = w.execute(_sleep, 1.0)
        wd = Watchdog([w], wedge_timeout_s=60.0, dispatch_deadline_s=60.0,
                      slow_after_s=0.15, poll_s=HB).start()
        assert wd.wait_for_state(0, STATE_SLOW, timeout=30)
        assert fut.result(timeout=60) == 1.0  # completed, never reaped
        assert wd.wait_for_state(0, STATE_OK, timeout=30)
        assert wd.reaped == []
    finally:
        if wd is not None:
            wd.stop()
        w.kill()


def test_watchdog_boot_grace_no_false_positive_kill():
    # a freshly spawned worker spends seconds importing before its first
    # beat; a tiny wedge timeout must not reap it during boot
    w = Worker(0, heartbeat_s=HB)
    wd = None
    try:
        wd = Watchdog([w], wedge_timeout_s=0.2, poll_s=0.05).start()
        assert w.execute(_ok, 5).result(timeout=120) == 10
        assert wd.reaped == []
    finally:
        if wd is not None:
            wd.stop()
        w.kill()


def test_heartbeat_survives_worker_restart():
    w = Worker(0, heartbeat_s=HB)
    try:
        assert w.execute(_ok).result(timeout=60) == 2
        old_hb = w.heartbeat
        w.restart()
        assert w.heartbeat is not old_hb  # fresh channel per generation
        assert w.execute(_ok, 3).result(timeout=60) == 6
        snap = w.heartbeat.snapshot()
        assert snap["started"]
        assert snap["dispatches"] == 1  # counter reset with the process
    finally:
        w.shutdown()


def test_pool_watch_helper_states():
    pool = ActorPool(2)
    wd = None
    try:
        for f in pool.execute_all(_ok):
            f.result(timeout=60)
        wd = pool.watch(wedge_timeout_s=30.0, poll_s=0.05)
        states = wd.poll_once()
        assert states == {0: STATE_OK, 1: STATE_OK}
    finally:
        if wd is not None:
            wd.stop()
        pool.shutdown()


def _hang_on_first_attempt(attempt, rank):
    if attempt == 0 and rank == 1:
        import time
        time.sleep(10_000)
    return (attempt, rank)


def test_elastic_runner_recovers_from_wedged_rank():
    """Wedge -> WorkerWedged -> restart_all -> clean retry: hangs retry
    exactly like crashes instead of hanging the driver forever."""
    pool = ActorPool(2, env_per_worker=[
        {"RLA_TPU_WORKER_HEARTBEAT_S": str(HB)} for _ in range(2)])
    failures = []
    try:
        runner = ElasticRunner(
            pool, max_failures=2, dispatch_deadline_s=0.5,
            watchdog_poll_s=HB,
            on_failure=lambda a, e: failures.append(e))
        out = runner.run(_hang_on_first_attempt,
                         args_per_worker=lambda a: [(a, r)
                                                    for r in range(2)])
        assert out == [(1, 0), (1, 1)]
        assert runner.attempts_used == 2
        assert len(failures) == 1
        assert isinstance(failures[0], WorkerWedged)
        assert runner.wedge_events
        assert runner.wedge_events[0]["rank"] == 1
    finally:
        pool.shutdown()
