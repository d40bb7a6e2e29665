"""bench.py backend handling: the pre-flight backend probe must turn a
dead/hung backend into ONE machine-readable record in bounded time, and a
window whose backend does not come up must exit non-zero with that record
and nothing else -- no CPU-mesh line printed as if it were a result.  The
probe child is faked by monkeypatching the probe source -- the logic
under test is the parent's subprocess handling, not JAX."""

import json
import subprocess
import sys

import pytest

import bench


def test_probe_passes_on_healthy_child(monkeypatch):
    monkeypatch.setattr(bench, "_PROBE_SRC",
                        "print('PROBE_OK 1.0 fake-devices')")
    assert bench.probe_backend(timeout_s=30) is None


def test_probe_reports_failing_child(monkeypatch):
    monkeypatch.setattr(
        bench, "_PROBE_SRC",
        "import sys; sys.stderr.write('Unable to initialize backend "
        "tpu: UNAVAILABLE');\nraise SystemExit(1)")
    err = bench.probe_backend(timeout_s=30)
    assert err is not None
    assert err["error"] == "backend unavailable"
    assert "Unable to initialize" in err["detail"]
    assert err["probe_seconds"] < 30


def test_probe_kills_hung_child_within_timeout(monkeypatch):
    monkeypatch.setattr(bench, "_PROBE_SRC",
                        "import time; time.sleep(600)")
    err = bench.probe_backend(timeout_s=2)
    assert err is not None
    assert "hung" in err["detail"]
    # bounded: the whole point is not burning the driver's window
    assert err["probe_seconds"] < 30


def test_probe_stall_classification_hung_vs_dead(monkeypatch):
    """The hung-vs-dead triage embedded in the probe record: only a
    child that ran out its TIMEOUT with no output reads as hung inside
    backend init — a fast silent death (segfault on import) and a noisy
    timeout are both dead-backend (review finding: presence-of-output
    alone misdiagnosed fast crashes as hangs)."""
    monkeypatch.setattr(bench, "_PROBE_SRC",
                        "import time; time.sleep(600)")
    err = bench.probe_backend(timeout_s=2)
    assert err["stall"]["classification"] == "hung-init"
    # fast silent exit: dead backend, NOT a hang (no timeout occurred)
    monkeypatch.setattr(bench, "_PROBE_SRC", "raise SystemExit(1)")
    err = bench.probe_backend(timeout_s=30)
    assert err["stall"]["classification"] == "dead-backend"
    assert err["probe_seconds"] < 30
    # noisy timeout: the backend answered, then died
    assert bench._flight_diagnosis("partial output", "",
                                   timed_out=True)["stall"][
        "classification"] == "dead-backend"
    # spill tails ride along when a telemetry dir holds rank files
    tails = bench._flight_diagnosis("", "", timed_out=True)
    assert "flight_tail" not in tails  # no dir configured -> absent


def test_probe_rejects_child_without_marker(monkeypatch):
    # a child that exits 0 but never ran the device op must NOT pass
    monkeypatch.setattr(bench, "_PROBE_SRC", "print('something else')")
    assert bench.probe_backend(timeout_s=30) is not None


def _fail_if_run(name):
    def bench_fn():
        raise AssertionError(f"{name} ran although the backend is dead")
    return bench_fn


def test_dead_backend_emits_death_record_and_nothing_else(monkeypatch,
                                                          capsys):
    """main() with a dead backend: the death record is the window's ONLY
    line, no bench ever runs — not the requested accelerator bench, not a
    CPU-mesh probe standing in for it — and the exit code is non-zero.
    (The window used to print the CPU-mesh probe metrics next to the
    record and exit 0, which read as a result.)"""
    monkeypatch.setattr(bench, "_PROBE_SRC", "raise SystemExit(1)")
    monkeypatch.setattr(sys, "argv",
                        ["bench.py", "--benches", "mnist,gradexchange",
                         "--probe-timeout", "5"])
    for name in bench.BENCHES:
        monkeypatch.setitem(bench.BENCHES, name, _fail_if_run(name))
    monkeypatch.setattr(bench, "_run_cpu_probe",
                        lambda script, label: _fail_if_run(label)())
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code == 2
    lines = [json.loads(ln) for ln
             in capsys.readouterr().out.splitlines() if ln.strip()]
    assert len(lines) == 1
    assert lines[0]["metric"] == "backend_probe"
    assert lines[0]["error"] == "backend unavailable"
    assert lines[0]["value"] == 0
    assert not hasattr(bench, "_emit_cpu_fallbacks")


def test_backend_death_mid_run_stops_remaining_benches(monkeypatch,
                                                       capsys):
    """A bench raising a CERTAIN backend-death marker aborts the rest
    with a machine-readable record (no probe needed) and exits 2: the
    death record is the last line, no later bench runs, and no CPU-mesh
    probe line follows it."""
    monkeypatch.setattr(sys, "argv",
                        ["bench.py", "--benches", "ok,a,b",
                         "--probe-timeout", "0", "--no-isolate"])

    def dead():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setitem(
        bench.BENCHES, "ok",
        lambda: {"metric": "ok", "value": 1, "unit": "x",
                 "vs_baseline": 1})
    monkeypatch.setitem(bench.BENCHES, "a", dead)
    monkeypatch.setitem(bench.BENCHES, "b", _fail_if_run("b"))
    monkeypatch.setattr(bench, "_run_cpu_probe",
                        lambda script, label: _fail_if_run(label)())
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code == 2
    lines = [json.loads(ln) for ln
             in capsys.readouterr().out.splitlines() if ln.strip()]
    # what ran before the death stays on record; the death record ends
    # the window
    assert [r["metric"] for r in lines] == ["ok", "backend_probe"]
    assert lines[-1]["error"] == "backend died mid-run"
    assert lines[-1]["failed_bench"] == "a"

    # an EARLIER genuinely-failed bench does not soften the death: the
    # window still ends on the record with exit 2
    monkeypatch.setattr(sys, "argv",
                        ["bench.py", "--benches", "plain,a,b",
                         "--probe-timeout", "0", "--no-isolate"])
    monkeypatch.setitem(bench.BENCHES, "plain",
                        lambda: (_ for _ in ()).throw(RuntimeError("oops")))
    with pytest.raises(SystemExit) as e2:
        bench.main()
    assert e2.value.code == 2
    lines2 = [json.loads(ln) for ln
              in capsys.readouterr().out.splitlines() if ln.strip()]
    assert [r["metric"] for r in lines2] == ["backend_probe"]


def test_suspect_marker_with_probe_disabled_continues(monkeypatch,
                                                      capsys):
    """A transient-looking gRPC 'UNAVAILABLE' with probing disabled
    must NOT kill the remaining benches."""
    monkeypatch.setattr(sys, "argv",
                        ["bench.py", "--benches", "a,b",
                         "--probe-timeout", "0", "--no-isolate"])

    def flaky():
        raise RuntimeError("DEADLINE_EXCEEDED then UNAVAILABLE retry")

    ran = []
    monkeypatch.setitem(bench.BENCHES, "a", flaky)
    monkeypatch.setitem(
        bench.BENCHES, "b",
        lambda: ran.append(1) or {"metric": "b", "value": 1,
                                  "unit": "x", "vs_baseline": 1})
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code == 1  # a failed, but b still ran
    assert ran
    out = capsys.readouterr().out
    assert '"metric": "b"' in out


def test_isolated_mode_survives_a_hung_bench(monkeypatch, capsys):
    """Default (isolated) mode: a bench that HANGS -- the failure mode
    no in-process machinery can interrupt -- costs its own timeout,
    becomes an error record, and when the backend probe still passes,
    the remaining benches run."""
    monkeypatch.setenv("RLA_TPU_BENCH_SELFTEST", "1")
    monkeypatch.setattr(bench, "_PROBE_SRC",
                        "print('PROBE_OK 1.0 fake')")  # probe stays alive
    monkeypatch.setattr(sys, "argv",
                        ["bench.py", "--benches",
                         "selftest-hang,selftest",
                         "--probe-timeout", "5", "--bench-timeout", "3"])
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code == 1  # hang recorded as failure; selftest ran
    lines = [json.loads(ln) for ln
             in capsys.readouterr().out.splitlines() if ln.strip()]
    by_metric = {r["metric"]: r for r in lines}
    assert by_metric["selftest-hang"]["error"] == "bench timed out"
    assert by_metric["selftest"]["value"] == 1


def test_isolated_mode_death_exits_nonzero_without_fallback(monkeypatch,
                                                           capsys):
    """Mid-run backend death in the DEFAULT (isolated) mode: the child's
    death record passes through, later benches stop, nothing is printed
    after the record and the window exits 2 (a pre-flight probe alone
    does not protect a backend that dies after it passed)."""
    monkeypatch.setenv("RLA_TPU_BENCH_SELFTEST", "1")
    monkeypatch.setattr(bench, "_PROBE_SRC",
                        "print('PROBE_OK 1.0 fake')")  # pre-flight passes
    monkeypatch.setattr(bench, "_run_cpu_probe",
                        lambda script, label: _fail_if_run(label)())
    monkeypatch.setattr(sys, "argv",
                        ["bench.py", "--benches", "selftest-dead,selftest",
                         "--probe-timeout", "5"])
    with pytest.raises(SystemExit) as e:
        bench.main()
    assert e.value.code == 2
    lines = [json.loads(ln) for ln
             in capsys.readouterr().out.splitlines() if ln.strip()]
    assert len(lines) == 1  # death record only: no CPU-mesh metric line
    assert lines[0]["error"] == "backend died mid-run"
    assert lines[0]["failed_bench"] == "selftest-dead"


def test_isolated_mode_passes_through_child_records(monkeypatch,
                                                    capsys):
    monkeypatch.setenv("RLA_TPU_BENCH_SELFTEST", "1")
    monkeypatch.setattr(bench, "_PROBE_SRC",
                        "print('PROBE_OK 1.0 fake')")
    monkeypatch.setattr(sys, "argv",
                        ["bench.py", "--benches", "selftest",
                         "--probe-timeout", "5"])
    try:
        bench.main()
        code = 0
    except SystemExit as e:
        code = e.code
    assert code == 0
    rec = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert rec == {"metric": "selftest", "value": 1, "unit": "ok",
                   "vs_baseline": 1.0}


def test_last_metric_record_skips_compile_count_lines():
    # probes print a bench-honesty compile-count record alongside the
    # metric; whichever order they land in, the bench result must be the
    # record that actually carries a value
    metric = {"metric": "wire_bytes", "value": 3.9, "unit": "x",
              "vs_baseline": 0.98}
    compile_rec = {"probe": "gradexchange", "kind": "compile_count",
                   "total_compiles": 7}
    out = "\n".join(["warmup chatter",
                     json.dumps(metric),
                     json.dumps(compile_rec)])
    assert bench._last_metric_record(out) == metric
    out = "\n".join([json.dumps(compile_rec), json.dumps(metric)])
    assert bench._last_metric_record(out) == metric
    # no metric record at all: newest JSON line still surfaces (error
    # records), and pure chatter yields None
    assert bench._last_metric_record(
        json.dumps(compile_rec))["kind"] == "compile_count"
    assert bench._last_metric_record("no json here") is None


def test_last_metric_record_survives_telemetry_snapshot_line():
    """Probes now end with a ``kind="telemetry"`` MetricsRegistry
    snapshot (PR 7).  It is value-less by contract, so the newest
    VALUE-BEARING line — the real metric — still wins the parse in
    either print order (the PR 6 contract, re-pinned against the new
    line)."""
    metric = {"metric": "serve_throughput", "value": 120.5, "unit":
              "tok/s", "vs_baseline": 1.1}
    compile_rec = {"probe": "serve", "kind": "compile_count",
                   "total_backend_compiles": 9}
    telemetry_rec = {"probe": "serve", "kind": "telemetry",
                     "snapshot": {"spans": {}, "counters": {"x": 1},
                                  "compile": {"total_backend_compiles": 9}}}
    # value-bearing metric: it wins regardless of print order
    out = "\n".join(json.dumps(r) for r in
                    (metric, compile_rec, telemetry_rec))
    assert bench._last_metric_record(out) == metric
    # gradexchange-style order: bookkeeping first, metric last
    out = "\n".join(json.dumps(r) for r in
                    (compile_rec, telemetry_rec, metric))
    assert bench._last_metric_record(out) == metric
    # the REAL serve metric record has no "value" key — it only wins by
    # POSITION, which is why serve_probe prints it last (pinned here
    # with the actual record shape, not a value-bearing stand-in)
    serve_metric = {"probe": "serve", "requests": 16,
                    "throughput_tok_s": 120.5, "steps": 40}
    out = "\n".join(json.dumps(r) for r in
                    (compile_rec, telemetry_rec, serve_metric))
    assert bench._last_metric_record(out) == serve_metric
    # a window that died before the metric: the telemetry record may be
    # the fallback surfaced, never mistaken for a value
    rec = bench._last_metric_record(json.dumps(telemetry_rec))
    assert rec["kind"] == "telemetry" and "value" not in rec
