"""Telemetry subsystem (telemetry/): flight recorder, trace IDs, the
unified MetricsRegistry export, and crash postmortem reports.

The acceptance loops:

- an induced ``hang@rank1`` chaos run produces a ``run_report.json``
  with per-rank event timelines sharing one trace id across
  driver -> worker, and the raised ``WorkerWedged.diagnosis`` embeds the
  wedged rank's flight-recorder tail — across BOTH wire rebuild paths
  (local pipe and agent relay, runtime/wire.py);
- one run's MetricsRegistry export (Prometheus text + JSON) carries
  trainer, prefetch, comms, serve and compile-count metrics together;
- the recorder adds zero retraces to a trainer run (compile-guard) and
  bounded step-time overhead.
"""

import json
import logging
import os
import time

import numpy as np
import pytest

from ray_lightning_accelerators_tpu.telemetry import recorder as R
from ray_lightning_accelerators_tpu.telemetry import registry as REG
from ray_lightning_accelerators_tpu.utils.profiler import Profiler

pytestmark = pytest.mark.telemetry

HB = 0.05


@pytest.fixture(autouse=True)
def _fresh_recorder():
    """Each test gets a clean process recorder (and leaves one behind)."""
    R._reset_for_tests()
    yield
    R._reset_for_tests()


def _ok(x=1):
    return x * 2


# --------------------------------------------------------------------- #
# Flight recorder (ring, traces, spill)                                  #
# --------------------------------------------------------------------- #
def test_ring_is_bounded_ordered_and_traced():
    rec = R.FlightRecorder(capacity=8, rank=2, trace_id="t0")
    for i in range(20):
        rec.emit("train_step", step=i)
    evts = rec.events()
    assert len(evts) == 8  # bounded: oldest 12 dropped
    assert [e["data"]["step"] for e in evts] == list(range(12, 20))
    assert all(e["rank"] == 2 and e["trace"] == "t0" for e in evts)
    assert [e["ts"] for e in evts] == sorted(e["ts"] for e in evts)
    # per-event trace override (serve's per-request ids)
    rec.emit("serve_admit", trace="req-1", request=7)
    last = rec.events()[-1]
    assert last["trace"] == "req-1" and last["data"]["request"] == 7
    assert rec.events(last_n=2)[-1] == last
    rec.clear()
    assert rec.events() == []


def test_disabled_recorder_is_a_noop(monkeypatch):
    rec = R.FlightRecorder(enabled=False)
    rec.emit("train_step", step=1)
    assert rec.events() == []
    # the knob path: RLA_TPU_TELEMETRY=0 disables the process recorder
    monkeypatch.setenv("RLA_TPU_TELEMETRY", "0")
    R.configure()
    R.emit("train_step", step=1)
    assert R.get_recorder().events() == []


def test_trace_mint_set_and_ambient():
    t1, t2 = R.mint_trace_id(), R.mint_trace_id()
    assert t1 != t2 and len(t1) == 16
    R.set_trace_id(t1)
    assert R.current_trace_id() == t1
    R.emit("fit_start")
    assert R.get_recorder().events()[-1]["trace"] == t1


def test_spill_roundtrip_and_torn_files(tmp_path, monkeypatch):
    monkeypatch.setenv("RLA_TPU_TELEMETRY_DIR", str(tmp_path))
    rec = R.configure(rank=5, trace_id="tr")
    rec.emit("dispatch_begin", n=1)  # first emit spills eagerly
    path = R.spill_path_for(5)
    assert path == str(tmp_path / "rank5.events.json")
    snap = R.read_spill(path)
    assert snap["rank"] == 5 and snap["trace_id"] == "tr"
    (tail,) = R.tail_events(snap, 1)
    assert tail["kind"] == "dispatch_begin" and tail["trace"] == "tr"
    # missing and torn files read as None, never raise
    assert R.read_spill(str(tmp_path / "nope.json")) is None
    torn = tmp_path / "rank9.events.json"
    torn.write_text("{not json")
    assert R.read_spill(str(torn)) is None
    # dir-wide gather skips the torn file, keys by rank
    tails = REG.gather_spill_dir(str(tmp_path))
    assert list(tails) == ["5"]


# --------------------------------------------------------------------- #
# Profiler.merge (reservoir/max/count semantics)                         #
# --------------------------------------------------------------------- #
def test_profiler_merge_exact_when_under_cap():
    p1, p2 = Profiler(), Profiler()
    for _ in range(10):
        p1.observe("s", 1.0)
    for _ in range(5):
        p2.observe("s", 3.0)
    p1.incr("c", 1)
    p2.incr("c", 2)
    p1.gauge("g", 5)
    p2.gauge("g", 9)
    p2.record_comms({"mode": "int8", "compression_ratio": 3.9})
    p1.merge(p2)  # live-object form
    s = p1.summary()["s"]
    assert s["count"] == 15
    assert abs(s["total_s"] - 25.0) < 1e-9
    assert s["max_s"] == 3.0
    assert s["p95_s"] == 3.0  # 5/15 of the union is 3.0
    assert p1.counters()["c"] == 3
    g = p1.gauges()["g"]
    assert (g["count"], g["min"], g["max"], g["last"]) == (2, 5.0, 9.0, 9.0)
    assert p1.comms()["compression_ratio"] == 3.9
    # export dict form merges identically
    p3 = Profiler()
    p3.merge(p1.export_state())
    assert p3.summary()["s"]["count"] == 15


def test_profiler_merge_reservoir_is_count_weighted():
    # one side summarizes 100k spans at ~1.0 with a full (capped)
    # reservoir; the other 10 spans at 100.0.  A naive concat would give
    # the tiny side ~0.25% of the sample; correct weighting keeps the
    # big side's median AND the exact global max.
    big = {"stats": {"x": {"count": 100_000, "total": 100_000.0,
                           "samples": [1.0] * 4096, "max": 1.0}},
           "counters": {}, "gauges": {}, "comms": None}
    small = {"stats": {"x": {"count": 10, "total": 1_000.0,
                             "samples": [100.0] * 10, "max": 100.0}},
             "counters": {}, "gauges": {}, "comms": None}
    p = Profiler()
    p.merge(big)
    p.merge(small)
    s = p.summary()["x"]
    assert s["count"] == 100_010
    assert abs(s["total_s"] - 101_000.0) < 1e-6
    assert s["max_s"] == 100.0  # exact max survives the reservoir
    assert s["p50_s"] == 1.0    # dominant population wins the median
    assert len(p.export_state()["stats"]["x"]["samples"]) <= 4096


# --------------------------------------------------------------------- #
# MetricsRegistry exports                                                #
# --------------------------------------------------------------------- #
def _populated_registry():
    prof = Profiler()
    for _ in range(4):
        prof.observe("train_step", 0.01)
    prof.incr("prefetch_starved_steps", 2)
    prof.gauge("prefetch_depth", 1)
    prof.record_comms({"mode": "int8", "compression_ratio": 3.9,
                       "exchange_bytes_per_step": 1000,
                       "baseline_fp32_bytes_per_step": 3900})
    reg = REG.MetricsRegistry(trace_id="abc")
    reg.add_profiler(prof, rank="driver")
    reg.add_serve({"completed": 4, "failed": 0, "queue_depth": 0,
                   "throughput_tok_s": 12.5}, rank=0)
    reg.add_compile_count(7, rank="driver")
    reg.add_events([{"kind": "train_step", "trace": "abc"},
                    {"kind": "train_step", "trace": "abc"},
                    {"kind": "serve_admit", "trace": "r1"}], rank="driver")
    return reg


def test_registry_json_export():
    j = _populated_registry().to_json()
    assert j["trace_id"] == "abc"
    assert j["spans"]["train_step"]["count"] == 4
    assert j["counters"]["prefetch_starved_steps"] == 2
    assert j["gauges"]["prefetch_depth"]["last"] == 1
    assert j["comms"]["compression_ratio"] == 3.9
    assert j["serve"]["0"]["completed"] == 4
    assert j["compile"]["total_backend_compiles"] == 7
    assert j["events"] == {"train_step": 2, "serve_admit": 1}
    json.dumps(j)  # the export is JSON-able end to end


def test_registry_prometheus_export():
    txt = _populated_registry().prometheus_text()
    assert 'rla_tpu_span_seconds{span="train_step",quantile="0.5"}' in txt
    assert "rla_tpu_span_seconds_count" in txt
    assert "rla_tpu_prefetch_starved_steps_total 2" in txt
    assert "rla_tpu_prefetch_depth 1" in txt
    assert "rla_tpu_comms_compression_ratio 3.9" in txt
    assert 'rla_tpu_serve_completed_total{rank="0"} 4' in txt
    assert 'rla_tpu_serve_throughput_tok_s{rank="0"} 12.5' in txt
    assert "rla_tpu_backend_compiles_total 7" in txt
    assert 'rla_tpu_events_total{kind="train_step"} 2' in txt
    # exposition-format sanity: every sample line is name{labels} value
    # (shared validator — test_live applies the SAME one to live scrapes)
    from tests.utils import assert_prometheus_exposition
    assert_prometheus_exposition(txt)


def test_serve_metrics_reset_clears_every_structure():
    # the PR 3/PR 4 lesson as a test: reset must miss NOTHING
    from ray_lightning_accelerators_tpu.serve.metrics import ServeMetrics
    m = ServeMetrics()
    m.inc("submitted")
    m.observe_ttft(0.1)
    m.observe_queue_wait(0.04)
    m.observe_prefill(0.05)
    m.observe_step(0.01, active=3)
    m.observe_token_latency(0.002)
    before = m.snapshot()
    assert before["submitted"] == 1 and before["max_batch"] == 3 \
        and before["busy_s"] > 0 and before["ttft_s"] is not None \
        and before["queue_wait_s"] is not None
    m.reset()
    snap = m.snapshot()
    for k in ServeMetrics._COUNTERS:
        assert snap[k] == 0, f"reset missed counter {k!r}"
    assert snap["max_batch"] == 0
    assert snap["busy_s"] == 0.0 and snap["throughput_tok_s"] == 0.0
    for fam in ("ttft_s", "queue_wait_s", "token_latency_s",
                "decode_step_s", "prefill_s"):
        assert snap[fam] is None, f"reset missed reservoir {fam!r}"
    assert m.profiler.summary() == {}


def test_run_report_write_and_schema(tmp_path):
    R.configure(trace_id="tr-77")
    R.emit("fit_start", step=0)
    err = RuntimeError("boom")
    err.rank = 1
    err.diagnosis = {"detail": "stale", "events": [{"kind": "x"}]}
    path = REG.write_run_report(
        str(tmp_path), error=err,
        rank_events={"1": {"events": [{"kind": "dispatch_begin",
                                       "trace": "tr-77"}]}},
        stall_diagnosis={"error": "worker wedged"},
        extra={"attempt": 2})
    assert path == str(tmp_path / "run_report.json")
    rep = json.load(open(path))
    assert rep["schema"] == REG.REPORT_SCHEMA
    assert rep["kind"] == "run_report" and rep["trace_id"] == "tr-77"
    assert rep["error"] == {"type": "RuntimeError", "message": "boom",
                            "rank": 1,
                            "diagnosis": err.diagnosis}
    assert rep["stall_diagnosis"]["error"] == "worker wedged"
    assert rep["extra"]["attempt"] == 2
    # driver timeline included automatically; named ranks preserved
    assert rep["ranks"]["driver"]["events"][0]["kind"] == "fit_start"
    assert rep["ranks"]["1"]["events"][0]["trace"] == "tr-77"
    assert "written_unix" in rep and "compile" in rep


# --------------------------------------------------------------------- #
# Logging satellite (rank/pid formatter + JSON mode)                     #
# --------------------------------------------------------------------- #
def test_log_formatter_rank_pid_and_json_mode(monkeypatch):
    from ray_lightning_accelerators_tpu.utils import logging as ulog
    record = ulog.log.makeRecord("ray_lightning_accelerators_tpu",
                                 logging.WARNING, "f.py", 1,
                                 "hello %s", ("world",), None)
    plain = ulog._RankFormatter(json_mode=False)
    s = plain.format(record)
    assert f"driver:{os.getpid()}" in s and "hello world" in s
    R.configure(rank=3)
    assert f" 3:{os.getpid()}" in plain.format(record)
    row = json.loads(ulog._RankFormatter(json_mode=True).format(record))
    assert row["rank"] == "3" and row["pid"] == os.getpid()
    assert row["level"] == "WARNING" and row["msg"] == "hello world"
    # the knob wires through configure_logging; restore afterwards
    try:
        monkeypatch.setenv("RLA_TPU_LOG_JSON", "1")
        ulog.configure_logging()
        h = next(h for h in ulog.log.handlers
                 if isinstance(h, logging.StreamHandler))
        assert h.formatter.json_mode is True
    finally:
        ulog.configure_logging(json_mode=False)


# --------------------------------------------------------------------- #
# Cross-process: worker events, tails, wedge diagnosis                   #
# --------------------------------------------------------------------- #
def test_worker_dispatch_events_reach_the_driver_tail(tmp_path):
    from ray_lightning_accelerators_tpu.runtime.actors import Worker
    env = {"RLA_TPU_TELEMETRY_DIR": str(tmp_path),
           "RLA_TPU_TRACE_ID": "tid-1",
           "RLA_TPU_WORKER_HEARTBEAT_S": str(HB)}
    w = Worker(0, env=env)
    try:
        assert w.execute(_ok, 21).result(timeout=60) == 42
        deadline = time.monotonic() + 10
        snap = None
        while time.monotonic() < deadline:  # dispatch_end spill is gated
            snap = w.telemetry_tail()
            if snap and len(snap.get("events", [])) >= 1:
                break
            time.sleep(0.05)
        assert snap is not None and snap["rank"] == 0
        kinds = [e["kind"] for e in snap["events"]]
        assert "dispatch_begin" in kinds
        assert all(e["trace"] == "tid-1" for e in snap["events"])
    finally:
        w.kill()


@pytest.mark.chaos
def test_wedged_diagnosis_embeds_events_local_pipe(tmp_path):
    """hang@rank0 -> watchdog reap -> the WorkerWedged that crosses the
    LOCAL pipe carries the wedged rank's flight-recorder tail."""
    from ray_lightning_accelerators_tpu.runtime.actors import Worker
    from ray_lightning_accelerators_tpu.runtime.watchdog import (
        Watchdog, WorkerWedged)
    from ray_lightning_accelerators_tpu.runtime.wire import rebuild_remote
    env = {"RLA_TPU_CHAOS": "hang@rank0",
           "RLA_TPU_WORKER_HEARTBEAT_S": str(HB),
           "RLA_TPU_TELEMETRY_DIR": str(tmp_path),
           "RLA_TPU_TRACE_ID": "tid-wedge"}
    w = Worker(0, env=env)
    wd = None
    try:
        fut = w.execute(_ok)
        wd = Watchdog([w], wedge_timeout_s=0.6, poll_s=HB).start()
        with pytest.raises(WorkerWedged) as ei:
            fut.result(timeout=120)
        diag = ei.value.diagnosis
        kinds = [e["kind"] for e in diag["events"]]
        assert "dispatch_begin" in kinds  # it entered the dispatch
        assert diag["trace_id"] == "tid-wedge"
        # the SAME payload survives the (name, message, tb) wire rebuild
        # used by the agent relay — both paths via runtime/wire.py
        rebuilt = rebuild_remote("WorkerWedged", str(ei.value), "")
        assert isinstance(rebuilt, WorkerWedged)
        assert [e["kind"] for e in rebuilt.diagnosis["events"]] == kinds
        assert rebuilt.diagnosis["trace_id"] == "tid-wedge"
    finally:
        if wd is not None:
            wd.stop()
        w.kill()


@pytest.mark.chaos
def test_wedged_diagnosis_crosses_agent_relay(tmp_path):
    """Same acceptance over the REAL agent relay: the HostAgent reads the
    wedged rank's spill file host-side (the ``telemetry`` wire op), the
    reap-built WorkerWedged relays as (name, message, tb), and the
    driver rebuild recovers the embedded events."""
    from ray_lightning_accelerators_tpu.runtime.agent import (HostAgent,
                                                              RemoteWorker)
    from ray_lightning_accelerators_tpu.runtime.watchdog import (
        Watchdog, WorkerWedged)
    agent = HostAgent(port=0, bind="127.0.0.1")
    agent.serve_in_background()
    env = {"RLA_TPU_CHAOS": "hang@rank1",
           "RLA_TPU_WORKER_HEARTBEAT_S": str(HB),
           "RLA_TPU_TELEMETRY_DIR": str(tmp_path),
           "RLA_TPU_TRACE_ID": "tid-relay"}
    w = wd = None
    try:
        w = RemoteWorker(f"127.0.0.1:{agent.port}", rank=1, env=env)
        fut = w.execute(_ok)
        wd = Watchdog([w], wedge_timeout_s=0.8, poll_s=HB).start()
        with pytest.raises(WorkerWedged) as ei:
            fut.result(timeout=120)
        diag = ei.value.diagnosis
        assert ei.value.rank == 1
        assert getattr(ei.value, "remote_typed", False) or diag["events"]
        assert "dispatch_begin" in [e["kind"] for e in diag["events"]]
        assert diag["trace_id"] == "tid-relay"
        # the wire op alone also answers (wedged rank, live agent)
        snap = w.telemetry_tail()
        assert snap and snap["trace_id"] == "tid-relay"
    finally:
        if wd is not None:
            wd.stop()
        if w is not None:
            w.kill()
        agent.shutdown()


def _report_body(rank):
    return rank * 10


@pytest.mark.chaos
def test_hang_chaos_run_writes_run_report(tmp_path):
    """THE acceptance loop: induced ``hang@rank1`` under ElasticRunner
    (report_dir set) produces a ``run_report.json`` whose per-rank
    timelines share ONE trace id spanning driver -> worker, whose error
    is the typed WorkerWedged with the wedged rank's events embedded —
    and the run still completes on the retry."""
    from ray_lightning_accelerators_tpu.runtime.actors import ActorPool
    from ray_lightning_accelerators_tpu.runtime.elastic import ElasticRunner
    from ray_lightning_accelerators_tpu.runtime.watchdog import WorkerWedged
    ns = str(tmp_path / "chaos_ns")
    tdir = str(tmp_path / "telemetry")
    report_dir = str(tmp_path / "reports")
    trace = R.mint_trace_id()
    R.set_trace_id(trace)  # driver side of the shared trace
    env = {"RLA_TPU_CHAOS": "hang@rank1:once",
           "RLA_TPU_CHAOS_NS": ns,
           "RLA_TPU_WORKER_HEARTBEAT_S": str(HB),
           "RLA_TPU_TELEMETRY_DIR": tdir,
           "RLA_TPU_TRACE_ID": trace}
    pool = ActorPool(2, env_per_worker=[dict(env), dict(env)])
    try:
        runner = ElasticRunner(pool, max_failures=2, wedge_timeout_s=0.6,
                               watchdog_poll_s=HB, report_dir=report_dir)
        out = runner.run(_report_body,
                         args_per_worker=lambda a: [(r,) for r in
                                                    range(2)])
        assert sorted(out) == [0, 10]
        assert runner.attempts_used == 2  # wedged attempt + clean retry
        rep = json.load(open(os.path.join(report_dir,
                                          "run_report.json")))
        # typed failure with the wedged rank's embedded tail
        assert rep["error"]["type"] == "WorkerWedged"
        assert rep["error"]["rank"] == 1
        diag = rep["error"]["diagnosis"]
        assert "dispatch_begin" in [e["kind"] for e in diag["events"]]
        # per-rank timelines with the SHARED trace id
        assert rep["trace_id"] == trace
        driver_events = rep["ranks"]["driver"]["events"]
        assert any(e["kind"] == "elastic_attempt" and e["trace"] == trace
                   for e in driver_events)
        assert any(e["kind"] == "watchdog_transition"
                   for e in driver_events)
        rank1 = rep["ranks"]["1"]["events"]
        assert rank1 and all(e["trace"] == trace for e in rank1)
        assert rep["stall_diagnosis"]["rank"] == 1
    finally:
        pool.shutdown()


# --------------------------------------------------------------------- #
# Trainer integration: one run -> one unified export; zero retraces      #
# --------------------------------------------------------------------- #
def _tiny_trainer(tmp_path, profiler=None, **kw):
    from ray_lightning_accelerators_tpu import Trainer
    return Trainer(max_steps=kw.pop("max_steps", 8), precision="f32",
                   enable_checkpointing=False, seed=0, profiler=profiler,
                   default_root_dir=str(tmp_path),
                   log_every_n_steps=10 ** 9, **kw)


def test_unified_registry_spans_trainer_prefetch_comms_serve_compile(
        tmp_path):
    """Acceptance: ONE MetricsRegistry export (JSON + Prometheus) holds
    trainer spans, prefetch accounting, comms wire records, serve
    metrics and compile counts from a single run."""
    import jax
    from ray_lightning_accelerators_tpu import DataLoader
    from ray_lightning_accelerators_tpu.analysis import compile_guard as cg
    from ray_lightning_accelerators_tpu.data.loader import RandomDataset
    from ray_lightning_accelerators_tpu.models.transformer import (
        GPT, TransformerConfig)
    from ray_lightning_accelerators_tpu.serve import ServeEngine
    from tests.utils import BoringModel

    cg.install()  # count compiles from before the run's first trace
    profiler = Profiler()
    trainer = _tiny_trainer(tmp_path, profiler=profiler,
                            prefetch_batches=2, grad_compression="bf16",
                            cache_dataset_on_device=False)
    trainer.fit(BoringModel(),
                DataLoader(RandomDataset(32, 64), batch_size=8))
    assert trainer.trace_id

    cfg = TransformerConfig(vocab_size=61, d_model=32, n_heads=2,
                            d_ff=64, n_layers=2, max_seq_len=64)
    model = GPT(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    with ServeEngine(model, params, max_slots=2) as engine:
        handles = [engine.submit(rng.integers(0, 61, size=(5,))
                                 .astype(np.int32), 4) for _ in range(3)]
        for h in handles:
            h.result(timeout=120)
        reg = trainer.build_metrics_registry()
        reg.add_serve(engine.metrics, rank="serve0")

    j = reg.to_json()
    assert j["trace_id"] == trainer.trace_id
    assert j["spans"]["train_step"]["count"] >= 8          # trainer
    assert "h2d_wait" in j["spans"]                        # prefetch
    assert "prefetch_depth" in j["gauges"]                 # prefetch
    assert j["comms"]["mode"] == "bf16"                    # comms
    assert j["serve"]["serve0"]["completed"] == 3          # serve
    assert j["compile"]["total_backend_compiles"] >= 1     # compile
    assert j["events"].get("train_step", 0) >= 8
    assert j["events"].get("serve_respond", 0) == 3
    txt = reg.prometheus_text()
    for needle in ('rla_tpu_span_seconds{span="train_step"',
                   "rla_tpu_prefetch_depth",
                   "rla_tpu_comms_compression_ratio",
                   'rla_tpu_serve_completed_total{rank="serve0"} 3',
                   "rla_tpu_backend_compiles_total",
                   'rla_tpu_events_total{kind="serve_respond"} 3'):
        assert needle in txt, f"{needle!r} missing from:\n{txt}"


def test_recorder_on_zero_retraces_and_bounded_overhead(tmp_path):
    """Acceptance: a recorder-ON trainer run compiles once and never
    retraces after warmup (compile-guard), and the per-step overhead of
    emitting events is bounded.  The bound is deliberately generous —
    shared-CPU wall clocks are noisy — because the emit cost itself is
    microseconds (pinned separately below)."""
    from ray_lightning_accelerators_tpu import Callback, DataLoader
    from ray_lightning_accelerators_tpu.analysis import compile_guard as cg
    from ray_lightning_accelerators_tpu.data.loader import RandomDataset
    from tests.utils import BoringModel

    class StepClock(Callback):
        def __init__(self):
            self.t = []
            self.compiles = []

        def on_train_batch_end(self, trainer, module, metrics, idx):
            self.t.append(time.perf_counter())
            self.compiles.append(cg.compile_count())

    def run(enabled):
        R.configure(enabled=enabled)
        clock = StepClock()
        tr = _tiny_trainer(tmp_path / f"run{enabled}", max_steps=12,
                           prefetch_batches=0,
                           cache_dataset_on_device=False,
                           callbacks=[clock])
        tr.fit(BoringModel(),
               DataLoader(RandomDataset(32, 96), batch_size=8))
        # steady state = steps 3.. (step 1 compiles, 2 settles)
        steps = np.diff(clock.t[2:])
        return clock, float(np.mean(steps))

    clock_on, mean_on = run(True)
    # zero retraces with the recorder ON: compile count frozen after the
    # first step's warmup across the remaining 11 steps
    assert clock_on.compiles[-1] == clock_on.compiles[0], (
        f"recorder-ON run retraced: {clock_on.compiles}")
    _, mean_off = run(False)
    assert mean_on <= mean_off * 3 + 0.02, (
        f"recorder overhead too high: on={mean_on:.5f}s "
        f"off={mean_off:.5f}s per step")
    # and the emit itself is cheap in absolute terms
    rec = R.FlightRecorder(capacity=256)
    t0 = time.perf_counter()
    for i in range(20_000):
        rec.emit("train_step", step=i)
    per_emit = (time.perf_counter() - t0) / 20_000
    assert per_emit < 5e-5, f"emit costs {per_emit * 1e6:.1f}us"


def _scanned_fit(tmp_path, **kw):
    """A two-epoch fit on the scanned-epoch path (device-resident data,
    epoch hooks only), no Profiler attached."""
    from ray_lightning_accelerators_tpu import DataLoader, Trainer
    from ray_lightning_accelerators_tpu.data.loader import RandomDataset
    from tests.utils import BoringModel

    trainer = Trainer(max_epochs=2, precision="f32", seed=0,
                      enable_checkpointing=False, log_every_n_steps=1,
                      default_root_dir=str(tmp_path),
                      cache_dataset_on_device=True, **kw)
    trainer.fit(BoringModel(),
                DataLoader(RandomDataset(32, 64), batch_size=8))
    assert trainer._can_scan_epoch()
    return trainer


def test_fit_ready_is_the_start_up_ledger_of_a_fit(tmp_path):
    """One ``fit_ready`` a fit, where its first epoch ends: the four
    start-up phases and the first epoch, positive, inside fit_start ->
    the first ``epoch_end`` on the recorder's clock; the first epoch
    names the programs jax compiled in it, a healthy later one none;
    and the event outlives the ring."""
    _scanned_fit(tmp_path)
    rec = R.get_recorder()
    events = rec.events()
    ready = [e for e in events if e["kind"] == "fit_ready"]
    ends = [e for e in events if e["kind"] == "epoch_end"]
    assert len(ready) == 1 and len(ends) == 2
    assert "fit_ready" in R.EVENT_KINDS
    data = ready[0]["data"]
    phases = ("setup_init_s", "setup_data_s", "setup_build_s",
              "setup_place_s", "first_epoch_s")
    assert all(isinstance(data[k], float) and data[k] > 0 for k in phases)
    start = [e for e in events if e["kind"] == "fit_start"][0]
    assert data["fit_start"] <= start["ts"] <= ends[0]["ts"]
    assert ends[0]["ts"] <= ready[0]["ts"] < ends[1]["ts"]
    # the phases are disjoint stretches of fit's entry -> first epoch_end
    assert sum(data[k] for k in phases) <= (
        ends[0]["ts"] - data["fit_start"]) + 1e-3
    # the step program is MADE in setup_build and compiled in the first
    # epoch's dispatch: that is what the ledger's phases say
    assert data["setup_build_s"] < data["first_epoch_s"]
    compiled = ends[0]["data"]["compiled"]
    assert "jit(scanned_epoch)" in compiled
    assert "compiled" not in ends[1]["data"]
    by_phase = data["compile"]
    assert by_phase["epoch_dispatch"]["built"] >= 1
    assert by_phase["epoch_dispatch"]["backend_s"] > 0
    # (a process that has fitted before compiles nothing in setup_init)
    assert set(by_phase) <= {
        "setup_init", "setup_data", "setup_build", "setup_place",
        "epoch_plan", "epoch_dispatch", "epoch_readback", "log_replay",
        "callbacks", "other"}, by_phase
    assert set(by_phase["epoch_dispatch"]) == {
        "programs", "built", "loaded", "missed", "trace_s", "lower_s",
        "backend_s", "retrieval_s"}
    json.dumps(events)      # host floats, strings, lists and dicts only
    for i in range(300):    # the ring (256) rolls over both
        rec.emit("train_step", step=i)
    assert not [e for e in rec.events()
                if e["kind"] in ("fit_start", "fit_ready")]
    assert rec.last("fit_ready") == ready[0]
    assert rec.last("fit_start") == start
    assert rec.last("train_step")["data"] == {"step": 299}    # from the ring
    assert rec.last("validation") is None
    rec.clear()
    assert rec.last("fit_ready") is None


def test_host_spans_reach_a_trace_without_a_profiler(tmp_path):
    """A trace taken of the process by ANYONE (here: jax.profiler, as
    the benchmark does) holds the fit's phases as ``rla:fit/*`` on the
    host plane, with no ``Profiler`` attached to the Trainer."""
    import glob

    import jax
    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace"),
                             profiler_options=options)
    try:
        trainer = _scanned_fit(tmp_path / "fit")
    finally:
        jax.profiler.stop_trace()
    assert trainer.profiler is None
    found = glob.glob(str(tmp_path / "trace" / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    assert found
    host = [p for p in ProfileData.from_file(found[-1]).planes
            if p.name == "/host:CPU"]
    if not host:
        pytest.skip("this jax's CPU profiler writes no /host:CPU plane")
    names = {}
    for line in host[0].lines:
        for e in line.events:
            if e.name.startswith("rla:"):
                names[e.name] = names.get(e.name, 0) + 1
    assert names.get("rla:fit/epoch_dispatch") == 2, names
    for phase in ("setup_init", "setup_data", "setup_build", "setup_place"):
        assert names.get("rla:fit/" + phase) == 1, names
    for phase in ("epoch_plan", "log_replay", "callbacks"):
        assert names.get("rla:fit/" + phase) == 2, names
    assert names.get("rla:fit/epoch_readback", 0) >= 2, names


def test_fit_failure_writes_run_report(tmp_path):
    """Any uncaught fit exception leaves a run_report.json under the run
    dir — with the typed error and the driver timeline — and re-raises
    the original exception untouched."""
    from ray_lightning_accelerators_tpu import DataLoader
    from ray_lightning_accelerators_tpu.data.loader import RandomDataset
    from tests.utils import BoringModel

    class Poison(Exception):
        pass

    class Bomb:
        def __init__(self, inner):
            self.inner = inner

        def __iter__(self):
            yield from list(self.inner)[:2]
            raise Poison("poisoned batch 3")

        def __len__(self):
            return len(self.inner)

    trainer = _tiny_trainer(tmp_path, prefetch_batches=0,
                            cache_dataset_on_device=False)
    loader = Bomb(DataLoader(RandomDataset(32, 64), batch_size=8))
    with pytest.raises(Poison):
        trainer.fit(BoringModel(), loader)
    rep = json.load(open(os.path.join(str(tmp_path), "run_report.json")))
    assert rep["error"]["type"] == "Poison"
    assert rep["trace_id"] == trainer.trace_id
    kinds = [e["kind"] for e in rep["ranks"]["driver"]["events"]]
    assert "fit_start" in kinds and "train_step" in kinds
    assert rep["metrics"] is not None  # registry snapshot rode along


def test_eval_fanout_ships_rank_telemetry_under_fresh_trace(tmp_path):
    """A fanned-out validate is a run of its own: it mints a FRESH trace
    id (not the fit's), makes it ambient inside the eval workers, and
    ships every rank's telemetry home so build_metrics_registry() covers
    the eval ranks too (review finding: the eval path used to neither
    propagate the trace nor repopulate _rank_telemetry)."""
    from ray_lightning_accelerators_tpu import (DataLoader,
                                                HorovodRayAccelerator,
                                                Trainer)
    from ray_lightning_accelerators_tpu.data.loader import ArrayDataset
    from ray_lightning_accelerators_tpu.runtime.agent import HostAgent
    from tests.utils import BoringModel

    agent = HostAgent(port=0, bind="127.0.0.1")
    agent.serve_in_background()
    trainer = None
    try:
        x = np.random.default_rng(0).normal(size=(32, 32)).astype(
            "float32")

        def loader():
            return DataLoader(ArrayDataset(x), batch_size=8,
                              shuffle=False)

        model = BoringModel()
        trainer = Trainer(max_epochs=1, precision="f32", seed=0,
                          enable_checkpointing=False,
                          accelerator=HorovodRayAccelerator(
                              num_hosts=1, num_slots=1,
                              agents=[f"127.0.0.1:{agent.port}"]),
                          default_root_dir=str(tmp_path))
        trainer.fit(model, loader())
        fit_trace = trainer.trace_id
        assert fit_trace
        assert any(trainer._rank_telemetry.values())  # fit home-ship

        trainer.validate(model, loader())
        assert trainer.trace_id and trainer.trace_id != fit_trace
        snap = trainer._rank_telemetry.get(0)
        assert snap and snap["events"], "eval rank shipped no telemetry"
        val_events = [e for e in snap["events"]
                      if e["kind"] == "validation"]
        assert val_events, "worker validate left no timeline event"
        # the eval trace id crossed the pickle into the worker's events
        assert all(e["trace"] == trainer.trace_id for e in val_events)
        reg = trainer.build_metrics_registry()
        j = reg.to_json()
        assert j["trace_id"] == trainer.trace_id
        assert j["events"].get("validation", 0) >= 1
    finally:
        if trainer is not None:
            trainer.teardown()
        agent.shutdown()
