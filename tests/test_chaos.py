"""Deterministic fault injection (testing/chaos.py) and the full
hang-recovery loop it exists to prove.

The acceptance loop for the watchdog subsystem: inject ``hang@rank1``,
the watchdog classifies the rank wedged within the configured timeout,
pending futures fail with ``WorkerWedged``, ``ElasticRunner`` restarts
every rank, and the retry completes from checkpoint -- all on CPU, no
TPU, no timing races.  Chaos specs are passed through ``env_per_worker``
(never the driver's environment), so injection cannot leak into other
tests; conftest guards the driver env regardless.
"""

import json
import os
import time

import pytest

from ray_lightning_accelerators_tpu.runtime.actors import ActorPool, Worker
from ray_lightning_accelerators_tpu.runtime.elastic import ElasticRunner
from ray_lightning_accelerators_tpu.runtime.watchdog import (Watchdog,
                                                             WorkerWedged)
from ray_lightning_accelerators_tpu.testing.chaos import (CHAOS_EXIT_CODE,
                                                          ChaosFault,
                                                          ChaosInjector,
                                                          parse_chaos)

HB = 0.05


def _ok(x=1):
    return x * 2


# --------------------------------------------------------------------- #
# spec parsing (pure)                                                    #
# --------------------------------------------------------------------- #
def test_parse_full_spec():
    faults = parse_chaos("crash@rank1:step3,hang@rank0,slow@all:2.5")
    assert faults == [
        ChaosFault("crash", 1, 3, None, False),
        ChaosFault("hang", 0, None, None, False),
        ChaosFault("slow", None, None, 2.5, False),
    ]


def test_parse_once_and_step_qualifiers():
    (f,) = parse_chaos("hang@rank1:once")
    assert f.once and f.rank == 1 and f.step is None
    (f,) = parse_chaos("slow@rank2:1.5:step2")
    assert f.delay_s == 1.5 and f.step == 2 and f.rank == 2


def test_parse_rejects_malformed_specs():
    for bad in ("explode@rank0",       # unknown kind
                "crash@node1",          # bad target
                "slow@all",             # slow without delay
                "crash@rank0:2.5",      # delay on non-slow
                "hang@rank0:stepx",     # unknown qualifier
                "crash"):               # no target at all
        with pytest.raises(ValueError):
            parse_chaos(bad)


def test_fault_matching_defaults():
    crash = parse_chaos("crash@rank1:step3")[0]
    assert crash.matches(rank=1, step=3)
    assert not crash.matches(rank=1, step=2)
    assert not crash.matches(rank=0, step=3)
    hang = parse_chaos("hang@rank0")[0]  # crash/hang default: first dispatch
    assert hang.matches(rank=0, step=1)
    assert not hang.matches(rank=0, step=2)
    slow = parse_chaos("slow@all:0.5")[0]  # slow default: every dispatch
    assert slow.matches(rank=7, step=1) and slow.matches(rank=7, step=9)


def test_once_requires_namespace_dir():
    with pytest.raises(ValueError, match="RLA_TPU_CHAOS_NS"):
        ChaosInjector(parse_chaos("hang@rank1:once"), rank=1, ns_dir=None)


def test_once_claim_is_exclusive(tmp_path):
    faults = parse_chaos("crash@rank0:once")
    inj = ChaosInjector(faults, rank=0, ns_dir=str(tmp_path))
    assert inj._claim_once(faults[0])       # first claim fires
    assert not inj._claim_once(faults[0])   # replays (restarts) skip
    # a different rank's claim is independent
    inj2 = ChaosInjector(parse_chaos("hang@all:once"), rank=1,
                         ns_dir=str(tmp_path))
    assert inj2._claim_once(inj2.faults[0])


# --------------------------------------------------------------------- #
# live injection                                                         #
# --------------------------------------------------------------------- #
@pytest.mark.chaos
def test_chaos_crash_at_step():
    w = Worker(0, env={"RLA_TPU_CHAOS": "crash@rank0:step2"},
               heartbeat_s=HB)
    try:
        assert w.execute(_ok, 21).result(timeout=60) == 42  # step 1: fine
        with pytest.raises(RuntimeError, match="died"):
            w.execute(_ok).result(timeout=60)               # step 2: boom
        w._proc.join(timeout=30)
        assert w.exitcode == CHAOS_EXIT_CODE
    finally:
        w.kill()


@pytest.mark.chaos
def test_chaos_bad_spec_surfaces_on_future():
    # a broken spec must fail the dispatch visibly, not vanish worker-side
    w = Worker(0, env={"RLA_TPU_CHAOS": "explode@rank0"}, heartbeat_s=HB)
    try:
        with pytest.raises(Exception, match="chaos fault"):
            w.execute(_ok).result(timeout=60)
    finally:
        w.kill()


@pytest.mark.chaos
def test_chaos_slow_straggler_completes_without_kill():
    # a straggler is SLOW, never wedged: it must finish and return its
    # result -- the false-positive guard for the reaping path
    w = Worker(0, env={"RLA_TPU_CHAOS": "slow@all:1.0"}, heartbeat_s=HB)
    wd = None
    try:
        fut = w.execute(_ok, 4)
        wd = Watchdog([w], wedge_timeout_s=60.0, dispatch_deadline_s=60.0,
                      slow_after_s=0.2, poll_s=HB).start()
        assert wd.wait_for_state(0, "slow", timeout=60)
        assert fut.result(timeout=60) == 8
        assert wd.reaped == []
    finally:
        if wd is not None:
            wd.stop()
        w.kill()


@pytest.mark.chaos
def test_chaos_hang_freezes_heartbeat_and_watchdog_reaps():
    # 'hang' freezes the beat thread too: the stale-heartbeat path (a
    # fully frozen process) fires even with no dispatch deadline set
    w = Worker(0, env={"RLA_TPU_CHAOS": "hang@rank0",
                       "RLA_TPU_WORKER_HEARTBEAT_S": str(HB)})
    wd = None
    try:
        fut = w.execute(_ok)
        wd = Watchdog([w], wedge_timeout_s=0.6, poll_s=HB).start()
        with pytest.raises(WorkerWedged) as ei:
            fut.result(timeout=120)
        assert "stale" in ei.value.diagnosis["detail"]
        assert wd.reaped and wd.reaped[0]["rank"] == 0
    finally:
        if wd is not None:
            wd.stop()
        w.kill()


def _ckpt_train_body(rank, ckpt_dir, total_steps):
    """A checkpointing trainable: rank 0 persists progress per step; every
    rank resumes from the latest checkpoint (the Trainer.fit(ckpt_path=
    "last") analog, minus jax so the loop stays tier-1 fast)."""
    import json
    import os
    path = os.path.join(ckpt_dir, "state.json")
    start = 0
    if os.path.exists(path):
        with open(path) as f:
            start = json.load(f)["step"]
    for step in range(start, total_steps):
        if rank == 0:
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump({"step": step + 1}, f)
            os.replace(tmp, path)  # atomic: a mid-write kill can't corrupt
    return (rank, start, total_steps)


@pytest.mark.chaos
def test_chaos_hang_elastic_restart_resumes_from_checkpoint(tmp_path):
    """The acceptance loop, end to end on CPU: inject ``hang@rank1:once``,
    the watchdog classifies rank 1 wedged within the configured timeout,
    its pending future fails with WorkerWedged, ElasticRunner restarts
    every rank, and the retry completes from the checkpoint rank 0 wrote
    before the restart."""
    ns = str(tmp_path / "chaos_ns")
    ckpt = str(tmp_path / "ckpt")
    os.makedirs(ckpt)
    env = {"RLA_TPU_CHAOS": "hang@rank1:once",
           "RLA_TPU_CHAOS_NS": ns,
           "RLA_TPU_WORKER_HEARTBEAT_S": str(HB)}
    pool = ActorPool(2, env_per_worker=[dict(env), dict(env)])
    failures = []

    def healthy_rank_drained(attempt, exc):
        # The restart of every rank waits here for rank 0's checkpoint to
        # show its last step: ordered by the file, not by the clock.  Rank
        # 1 freezes at its dispatch, and the watchdog calls it wedged one
        # wedge timeout later, while on a loaded host rank 0's process may
        # still be importing: its six writes then raced the reap, and the
        # retry's two ranks read a checkpoint that rank 0 was still
        # writing ({0, 6} for one resume point).
        failures.append(exc)
        deadline = time.monotonic() + 120.0
        state = os.path.join(ckpt, "state.json")
        while time.monotonic() < deadline:
            if os.path.exists(state):
                with open(state) as f:
                    if json.load(f)["step"] == 6:
                        return
            time.sleep(HB)
        raise AssertionError("rank 0 never finished its steps")

    try:
        # the wedge timeout scales from the heartbeat with room for a
        # healthy rank's beat thread to be starved a while on a busy host
        runner = ElasticRunner(
            pool, max_failures=2, wedge_timeout_s=20 * HB,
            watchdog_poll_s=HB, on_failure=healthy_rank_drained)
        out = runner.run(
            _ckpt_train_body,
            args_per_worker=lambda a: [(r, ckpt, 6) for r in range(2)])

        # one wedged attempt, one clean retry
        assert runner.attempts_used == 2
        assert len(failures) == 1
        assert isinstance(failures[0], WorkerWedged)
        assert failures[0].rank == 1
        # the watchdog's wedge classification, machine-readable
        (reap,) = runner.wedge_events
        assert reap["rank"] == 1
        assert reap["state"] == "wedged"
        assert "stale" in reap["detail"]
        # the retry COMPLETED and resumed from checkpoint: rank 0 finished
        # its steps during attempt 1 (the hang wedged only rank 1), so the
        # retry started past step 0 instead of redoing the work
        by_rank = {r[0]: r for r in out}
        assert set(by_rank) == {0, 1}
        starts = {by_rank[0][1], by_rank[1][1]}
        assert len(starts) == 1  # both ranks agreed on the resume point
        assert starts.pop() >= 1
        with open(os.path.join(ckpt, "state.json")) as f:
            assert json.load(f)["step"] == 6  # training ran to completion
    finally:
        pool.shutdown()
