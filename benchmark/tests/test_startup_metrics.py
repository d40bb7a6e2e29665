"""The five start-up metrics (PR 35): the two readers on a hand-made
flight recorder and compile ledger, and one traced CPU rehearsal.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_startup_metrics.py -q
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import cells  # noqa: E402
from benchmark.readers import setup_fit, setup_ledger  # noqa: E402

NEW = ("setup_fit_s.train", "setup_first_epoch_s.train",
       "setup_compile_s.train", "setup_programs_built.train",
       "setup_cache_loaded.train")
REAL = ["train-medium-1k", "train-xl-fsdp4", "train-lfm2-moe-8k",
        "train-nemotron3-ssm-8k", "train-ouro-loop-8k"]


class Recorder:
    """As much of the program's FlightRecorder as the readers use."""

    def __init__(self, events, pinned):
        self._events, self._pinned = events, pinned

    def events(self):
        return list(self._events)

    def last(self, kind):
        return self._pinned.get(kind)


def _epoch_end(ts, epoch, **fields):
    return {"ts": ts, "kind": "epoch_end", "rank": None, "trace": "t",
            "data": dict(epoch=epoch, step=4 * epoch, **fields)}


FIVE = dict(plan_s=0.01, dispatch_s=0.02, readback_s=0.9, log_s=0.03,
            callbacks_s=0.04)
READY = {"ts": 107.001, "kind": "fit_ready", "rank": None, "trace": "t",
         "data": {"fit_start": 100.0, "setup_init_s": 3.0,
                  "setup_data_s": 0.5, "setup_build_s": 0.01,
                  "setup_place_s": 0.4, "first_epoch_s": 3.0,
                  "compile": {}}}


def _row(name, end, cache, trace_s=0.1, lower_s=0.2, backend_s=0.4):
    return {"name": name, "trace_s": trace_s, "lower_s": lower_s,
            "backend_s": backend_s, "cache": cache, "retrieval_s": 0.0,
            "saved_s": 0.0, "start": end - backend_s, "end": end,
            "thread": "MainThread", "phase": None}


LEDGER = [_row("jit(reference)", 95.0, "hit", backend_s=0.004),
          _row("jit(add)", 101.0, "small"),
          _row("jit(multiply)", 101.5, "uncached"),
          _row("jit(scanned_epoch)", 106.0, "hit", backend_s=0.5),
          _row("jit(eval_only)", 106.5, None, backend_s=0.0),
          _row("jit(second_warm)", 107.5, "miss", backend_s=2.0),
          _row("jit(scanned_epoch)", 140.0, "hit")]   # lowered after the fit


@pytest.fixture
def program(monkeypatch):
    """Put a hand-made recorder and ledger where the readers look."""
    from ray_lightning_accelerators_tpu import telemetry
    from ray_lightning_accelerators_tpu.analysis import compile_guard

    def install(events, pinned=None):
        rec = Recorder(events, {"fit_ready": READY} if pinned is None
                       else pinned)
        monkeypatch.setattr(telemetry, "get_recorder", lambda: rec)

    monkeypatch.setattr(
        compile_guard, "ledger", lambda since=None, until=None: [
            dict(r) for r in LEDGER
            if (since is None or r["end"] >= since)
            and (until is None or r["end"] < until)])
    return install


def _context(n_window_epochs):
    return {"counters": {"epoch_s": [1.0] * n_window_epochs}}


def test_readers_on_a_hand_made_recorder_and_ledger(program):
    # one warm epoch, three in the window: set-up ends at epoch 1's end
    ends = [_epoch_end(107.0, 1, **FIVE)] + [
        _epoch_end(107.0 + k, 1 + k, **FIVE) for k in (1, 2, 3)]
    program(ends)
    ctx = _context(3)
    assert setup_fit.read(ctx, "fit") == pytest.approx(7.0)
    assert setup_fit.read(ctx, "first_epoch") == pytest.approx(3.0)
    # rows that end before 107.0: reference, add, multiply, the step
    # program's load, and the program that was only traced
    assert setup_ledger.read(ctx, "compile_s") == pytest.approx(
        5 * 0.3 + 0.004 + 0.4 + 0.4 + 0.5)
    assert setup_ledger.read(ctx, "built") == 2
    assert setup_ledger.read(ctx, "loaded") == 2
    # two warm epochs: the second's five fields join the first epoch, and
    # the program compiled in it now lies before the window
    ctx = _context(2)
    assert setup_fit.read(ctx, "fit") == pytest.approx(8.0)
    assert setup_fit.read(ctx, "first_epoch") == pytest.approx(
        3.0 + sum(FIVE.values()))
    assert setup_ledger.read(ctx, "built") == 3
    assert setup_ledger.read(ctx, "compile_s") == pytest.approx(
        6 * 0.3 + 0.004 + 0.4 + 0.4 + 0.5 + 2.0)
    with pytest.raises(ValueError):
        setup_fit.read(ctx, "nothing")


def test_readers_when_the_ring_has_rolled_or_the_program_has_no_ledger(
        program, monkeypatch):
    window = [_epoch_end(107.0 + k, 1 + k, **FIVE) for k in (1, 2, 3)]
    # the ring rolled over the first epoch_end: fit_ready (pinned, and
    # microseconds after it) says where set-up ended
    program(window)
    assert setup_fit.read(_context(3), "fit") == pytest.approx(7.001)
    assert setup_ledger.read(_context(3), "loaded") == 2
    # ... but not where a later warm epoch's event is gone too
    program(window[1:])
    assert setup_fit.read(_context(1), "fit") is None
    assert setup_ledger.read(_context(1), "built") is None
    # no fit_ready (the parent's program), no window, no events
    program([_epoch_end(107.0, 1, **FIVE)] + window, pinned={})
    for what in ("fit", "first_epoch"):
        assert setup_fit.read(_context(3), what) is None
    assert setup_ledger.read(_context(3), "compile_s") is None
    program(window)
    assert setup_fit.read(_context(0), "fit") is None
    program([])
    assert setup_fit.read(_context(3), "fit") is None
    # a recorder without last(), a compile_guard without ledger()
    from ray_lightning_accelerators_tpu import telemetry
    from ray_lightning_accelerators_tpu.analysis import compile_guard
    monkeypatch.setattr(telemetry, "get_recorder", lambda: object())
    assert setup_fit.read(_context(3), "fit") is None
    program([_epoch_end(107.0, 1, **FIVE)] + window)
    monkeypatch.delattr(compile_guard, "ledger")
    assert setup_fit.read(_context(3), "fit") == pytest.approx(7.0)
    assert setup_ledger.read(_context(3), "built") is None


def test_the_five_metric_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"]][-5:] == list(NEW)
    for name in NEW:
        on_disk = cells._load("layer_metrics", name)
        assert on_disk["moves"] == entries[name]["moves"] == "setup_s"
        assert entries[name]["workloads"] == REAL
        assert on_disk["cells"][:5] == REAL
        rehearsed = [c for c in on_disk["cells"] if c.startswith("rehearsal")]
        # a CPU rehearsal loads nothing from the cache, and an accepted
        # test holds every rehearsed metric above 0
        assert rehearsed == ([] if name == "setup_cache_loaded.train" else [
            "rehearsal-train", "rehearsal-train-arch",
            "rehearsal-train-fsdp4", "rehearsal-train-nemotron",
            "rehearsal-train-ouro"])


def test_a_traced_rehearsal_prints_the_metrics_that_list_it(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_REHEARSAL="1",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    env.pop("BENCH_RUN", None)
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "rehearsal-train",
         "--seed", "3000000035", "--seconds", "1", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(l) for l in done.stdout.strip().splitlines()]
    line, summary = lines[-1], [l for l in lines
                                if l.get("info") == "summary"][0]
    assert line["rehearsal"] is True    # (`correct` is test_benchmark's)
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW[:4]) <= set(m) and NEW[4] not in m
    assert all(m[k] > 0 for k in NEW[:4])
    assert line["metrics"][NEW[3]]["unit"] == "programs"
    setup_s = summary["end_to_end"]["setup_s"]["value"]
    # the fit is part of set-up, its first epoch part of the fit, and
    # jax's pipeline (the reference's programs too) part of set-up
    assert m[NEW[1]] < m[NEW[0]] < setup_s
    assert m[NEW[2]] < setup_s
    # every backend event of set-up is a built program here: the ledger
    # and the benchmark's own count (the drivers' lowering after the fit
    # is in `compiled` alone) agree to within it
    assert 0 <= summary["compiled"] - m[NEW[3]] <= 2
