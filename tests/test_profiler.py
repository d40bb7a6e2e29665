"""Profiler subsystem: spans, sync mode, summaries, trainer integration,
device traces (the first-class tracing subsystem SURVEY.md §5.1 calls for —
the reference has none)."""

import glob
import os
import time

import jax
import jax.numpy as jnp
import pytest

from ray_lightning_accelerators_tpu import (Profiler, RayTPUAccelerator,
                                            Trainer, device_memory_stats)
from tests.utils import BoringModel, boring_loaders


def test_spans_nest_and_count():
    prof = Profiler()
    for _ in range(3):
        with prof.span("outer"):
            with prof.span("inner"):
                time.sleep(0.001)
    s = prof.summary()
    assert s["outer"]["count"] == 3
    assert s["outer/inner"]["count"] == 3
    assert s["outer"]["total_s"] >= s["outer/inner"]["total_s"] > 0
    for k in ("count", "total_s", "mean_s", "p50_s", "p95_s", "p99_s",
              "max_s"):
        assert k in s["outer"]
    assert "outer/inner" in prof.describe()
    prof.reset()
    assert prof.summary() == {}


def test_tail_percentiles_and_exact_max():
    """p99 sits in the tail of the reservoir and max_s is the EXACT
    maximum (it must survive even when the reservoir would evict it)."""
    prof = Profiler()
    for i in range(1, 101):           # 1ms..100ms, deterministic
        prof.observe("op", i / 1000.0)
    s = prof.summary()["op"]
    assert s["count"] == 100
    assert s["p50_s"] == pytest.approx(0.050, abs=0.002)
    assert s["p95_s"] == pytest.approx(0.095, abs=0.002)
    assert s["p99_s"] == pytest.approx(0.099, abs=0.002)
    assert s["p99_s"] >= s["p95_s"] >= s["p50_s"]
    assert s["max_s"] == pytest.approx(0.100)
    # beyond the reservoir cap the exact max still survives
    prof.observe("op", 9.9)
    for _ in range(5000):
        prof.observe("op", 0.001)
    assert prof.summary()["op"]["max_s"] == pytest.approx(9.9)
    # describe() renders the new tail columns
    head = prof.describe().splitlines()[0]
    assert "p99" in head and "max" in head


def test_sync_span_blocks_on_device_outputs():
    prof = Profiler(sync=True)

    @jax.jit
    def work(x):
        for _ in range(20):
            x = x @ x
        return x

    x = jnp.ones((512, 512)) * 0.001
    work(x).block_until_ready()  # compile outside the span
    with prof.span("dispatch_only"):
        y = work(x)
    y.block_until_ready()
    with prof.span("synced") as h:
        h.set(work(x))
    s = prof.summary()
    # the synced span includes device compute; dispatch-only does not
    assert s["synced"]["total_s"] >= s["dispatch_only"]["total_s"]


def test_trainer_profiler_integration():
    # SYNCHRONOUS host-fed path (cache off, prefetch off): fetch/h2d/step
    # spans per batch.  The async-pipeline span shape (h2d_wait /
    # prefetch_depth / starvation) is pinned in test_prefetch.py.
    prof = Profiler()
    train, val = boring_loaders()
    trainer = Trainer(max_epochs=2, accelerator=RayTPUAccelerator(),
                      precision="f32", enable_checkpointing=False,
                      profiler=prof, log_every_n_steps=10 ** 9, seed=0,
                      cache_dataset_on_device=False, prefetch_batches=0)
    trainer.fit(BoringModel(), train, val)
    s = prof.summary()
    assert s["train_step"]["count"] == trainer.global_step > 0
    assert s["data_fetch"]["count"] >= trainer.global_step
    assert s["h2d"]["count"] == trainer.global_step
    assert s["validation"]["count"] == 2


def test_trainer_profiler_integration_cached_path():
    # device-cached path: a profiler never changes which program runs --
    # the whole-epoch scan stays, with one span per host phase of the
    # epoch instead of per-step spans
    prof = Profiler()
    train, val = boring_loaders()
    trainer = Trainer(max_epochs=2, accelerator=RayTPUAccelerator(),
                      precision="f32", enable_checkpointing=False,
                      profiler=prof, log_every_n_steps=10 ** 9, seed=0,
                      cache_dataset_on_device=True)
    trainer.fit(BoringModel(), train, val)
    assert trainer._can_scan_epoch()
    s = prof.summary()
    assert s["fit/epoch_dispatch"]["count"] == 2    # once per epoch
    assert s["fit/epoch_plan"]["count"] == 2
    assert s["fit/callbacks"]["count"] == 2
    assert s["validation"]["count"] == 2
    assert trainer.global_step > 0
    assert "train_step" not in s and "h2d" not in s


def test_device_trace_roundtrip(tmp_path):
    prof = Profiler()
    log_dir = str(tmp_path / "trace")
    with prof.trace(log_dir):
        jnp.ones((64, 64)).sum().block_until_ready()
    produced = glob.glob(os.path.join(log_dir, "**", "*"), recursive=True)
    assert any(os.path.isfile(p) for p in produced), produced
    # a second trace works after the first closed
    with prof.trace(str(tmp_path / "trace2")):
        pass


def test_trace_double_start_raises(tmp_path):
    prof = Profiler()
    prof.start_trace(str(tmp_path / "t"))
    try:
        with pytest.raises(RuntimeError, match="already running"):
            prof.start_trace(str(tmp_path / "t2"))
    finally:
        prof.stop_trace()
    assert prof.stop_trace() is None  # idempotent


def test_device_memory_stats_shape():
    stats = device_memory_stats()
    assert len(stats) == len(jax.local_devices())
    assert all(isinstance(d, dict) for d in stats)


def test_flops_estimate_and_mfu():
    import jax.numpy as jnp
    from ray_lightning_accelerators_tpu.utils.profiler import (flops_estimate,
                                                               mfu)

    def f(a, b):
        return a @ b

    a = jnp.ones((128, 256), jnp.float32)
    b = jnp.ones((256, 64), jnp.float32)
    fl = flops_estimate(f, a, b)
    if fl is not None:  # cpu backend may omit cost analysis
        # matmul flops = 2*M*N*K
        assert fl == pytest.approx(2 * 128 * 256 * 64, rel=0.5)
    # explicit peak: 1 TFLOP/s peak, 1e9 flops in 1ms = 100% MFU
    assert mfu(1e9, 1e-3, peak_flops=1e12) == pytest.approx(1.0)


def test_unknown_device_has_no_peak():
    """A utilization against an unknown peak is not a number: a device
    missing from the published-peak table raises (it used to read as
    MFU 0.0), and the table names where its figures come from."""
    from ray_lightning_accelerators_tpu.utils import profiler as prof

    assert prof.peak_bf16_flops("TPU v5 lite") == 197e12
    assert "Google Cloud" in prof.PEAK_BF16_FLOPS_SOURCE
    with pytest.raises(ValueError, match="no published bf16 peak"):
        prof.peak_bf16_flops("cpu")
    with pytest.raises(ValueError, match="no published bf16 peak"):
        prof.mfu(1e9, 1e-3)  # the forced-CPU suite's own device


def test_trace_op_summary_parses_device_events(tmp_path):
    """trace_op_summary reads an XPlane-exported trace.json.gz, keeps only
    device-clock events, resolves nesting (a scan's children don't
    double-count against it), and reports achieved GB/s / TF/s."""
    import gzip
    import json

    from ray_lightning_accelerators_tpu.utils.profiler import (
        trace_events, trace_op_summary)

    # synthetic trace: one while(0..1000us) containing two fusions
    # (400us @ 1GB read, 500us of matmul flops), plus a host event that
    # must be ignored (no device_duration_ps)
    def dev(name, cat, off_us, dur_us, nbytes=0, flops=0):
        return {"ph": "X", "name": name, "pid": 3, "ts": off_us,
                "dur": dur_us,
                "args": {"device_offset_ps": str(int(off_us * 1e6)),
                         "device_duration_ps": str(int(dur_us * 1e6)),
                         "hlo_category": cat,
                         "raw_bytes_accessed": str(nbytes),
                         "model_flops": str(flops)}}

    trace = {"traceEvents": [
        dev("while.1", "while", 0, 1000),
        dev("fusion.1", "loop fusion", 10, 400, nbytes=10 ** 9),
        dev("fusion.2", "convolution fusion", 450, 500,
            flops=50 * 10 ** 12 * 500 // 10 ** 6),
        {"ph": "X", "name": "host_thing", "pid": 701, "ts": 0, "dur": 5},
        # a SECOND device timeline overlapping the first: concurrent
        # chips must not read as parent/child of chip 0's while
        {**dev("other_chip_op", "data formatting", 100, 300), "pid": 4},
    ]}
    d = tmp_path / "plugins" / "profile" / "run1"
    d.mkdir(parents=True)
    with gzip.open(d / "vm.trace.json.gz", "wt") as f:
        json.dump(trace, f)

    evs = trace_events(str(tmp_path))
    assert [e["name"] for e in evs] == ["while.1", "fusion.1",
                                       "other_chip_op", "fusion.2"]

    s = trace_op_summary(str(tmp_path))
    # chip 0's 1000us + chip 1's 300us, nothing double-counted
    assert s["total_ms"] == pytest.approx(1.3, rel=1e-6)
    by = s["by_category"]
    # while self time = 1000 - 900 nested on ITS OWN timeline = 100us
    # (the other chip's overlapping 300us op must not subtract)
    assert by["while"]["self_ms"] == pytest.approx(0.1, rel=1e-6)
    # 1 GB in 400us = 2500 GB/s
    assert by["loop fusion"]["gbps"] == pytest.approx(2500.0, rel=1e-3)
    assert by["convolution fusion"]["tfs"] == pytest.approx(50.0, rel=1e-3)
    names = [o["name"] for o in s["ops"]]
    assert "host_thing" not in names
