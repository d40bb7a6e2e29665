"""Live-telemetry-plane probe on a forced-host-platform 8-device CPU mesh.

Self-contained: forces ``JAX_PLATFORMS=cpu`` with 8 virtual devices
BEFORE importing jax, so it runs the same way on any machine and never
touches the chip.

Four measurements, one run (telemetry/live.py + serve/slo.py):

1. **Scrape-under-load validity + overhead** — a training fit with the
   live plane enabled while a scraper thread hammers ``/metrics`` +
   ``/statusz`` (~20 Hz).  EVERY ``/metrics`` body is validated against
   the Prometheus exposition grammar (the same validator
   tests/test_telemetry.py applies to the end-of-run export); the
   headline value is the fraction of scrapes that came back valid
   (bar: 1.0 — a live scrape that tears or 500s under load is a
   correctness bug, not noise).  A/B against an identical unscraped fit
   reports the step-wall overhead fraction (reported, not gated: CPU
   shared-host noise swamps the <1% bar the StepTimeline shows).
2. **Compile discipline** — the steady-state window compiles with the
   plane enabled (scraping included) must be 0.
3. **Serve SLO burn rate** — a mixed serve workload under an engine
   with deliberately tight targets reports a NONZERO burn rate + typed
   deadline sheds; the same workload under generous targets reports
   exactly zero (the signal has no false floor).
4. **ClusterView** — 2 spawned workers publish live endpoints via
   portfiles; the driver's ClusterView collects both and the merged
   driver ``/metrics`` carries rank-labeled samples.

Emits one bench.py-shaped JSON line on stdout, with the bench-honesty
compile-count record and the telemetry snapshot printed BEFORE it (the
parser takes the newest value-bearing line)."""

from __future__ import annotations

import json
import os
import re
import sys
import tempfile
import threading
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# the exposition grammar check tests/test_telemetry.py pins, applied to
# every LIVE scrape here
_SAMPLE_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
                        r'(\{[a-zA-Z0-9_]+="[^"]*"'
                        r'(,[a-zA-Z0-9_]+="[^"]*")*\})? '
                        r"-?[0-9.eE+-]+(inf|nan)?$")


def exposition_valid(text: str) -> bool:
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        if not _SAMPLE_RE.match(line):
            return False
    return bool(text.strip())


class _Scraper:
    """Background /metrics + /statusz poller with validity accounting."""

    def __init__(self, url: str, hz: float = 20.0):
        self.url = url
        self.period = 1.0 / hz
        self.scrapes = 0
        self.valid = 0
        self.statusz_ok = 0
        self.latencies = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        from urllib.request import urlopen
        while not self._stop.wait(self.period):
            t0 = time.perf_counter()
            try:
                with urlopen(self.url + "/metrics", timeout=5) as r:
                    body = r.read().decode()
                self.scrapes += 1
                if exposition_valid(body):
                    self.valid += 1
                with urlopen(self.url + "/statusz", timeout=5) as r:
                    json.loads(r.read().decode())
                self.statusz_ok += 1
            except Exception:
                self.scrapes += 1
            self.latencies.append(time.perf_counter() - t0)

    def __enter__(self):
        self._thread.start()
        return self

    def __exit__(self, *exc):
        self._stop.set()
        self._thread.join(timeout=10)


def _fit_once(workdir: str, tag: str, clock_cb):
    from ray_lightning_accelerators_tpu import (DataLoader,
                                                RayTPUAccelerator,
                                                Trainer)
    from ray_lightning_accelerators_tpu.data.loader import ArrayDataset
    from ray_lightning_accelerators_tpu.models.mnist import (
        MNISTClassifier, synthetic_mnist)
    from ray_lightning_accelerators_tpu.utils.profiler import Profiler
    x, y = synthetic_mnist(1024, seed=0)
    loader = DataLoader(ArrayDataset(x, y), batch_size=128, shuffle=True)
    model = MNISTClassifier({"layer_1": 128, "layer_2": 128, "lr": 1e-3,
                             "batch_size": 128})
    trainer = Trainer(max_epochs=3, precision="f32", seed=0,
                      accelerator=RayTPUAccelerator(),
                      enable_checkpointing=False,
                      log_every_n_steps=10 ** 9,
                      profiler=Profiler(sync=True),
                      perf_observatory=True,
                      prefetch_batches=2,
                      cache_dataset_on_device=False,
                      callbacks=[clock_cb],
                      default_root_dir=os.path.join(workdir, tag))
    trainer.fit(model, loader)
    return trainer


def _make_clock():
    from ray_lightning_accelerators_tpu import Callback
    from ray_lightning_accelerators_tpu.analysis import compile_guard as cg

    class Clock(Callback):
        def __init__(self):
            self.starts, self.ends = [], []
            self.c_start, self.c_end = [], []

        def on_train_epoch_start(self, trainer, module):
            self.starts.append(time.perf_counter())
            self.c_start.append(cg.compile_count())

        def on_train_epoch_end(self, trainer, module):
            self.ends.append(time.perf_counter())
            self.c_end.append(cg.compile_count())

        def steady_s(self):
            return self.ends[-1] - self.starts[1]

        def window_compiles(self):
            return self.c_end[-1] - self.c_start[1]

    return Clock()


def _serve_slo(overloaded: bool):
    """One mixed serve workload; returns the engine's final snapshot +
    deadline-shed count.  ``overloaded``: microsecond targets (every
    observation violates) and a deliberately stale queued request for a
    typed shed; else second-scale targets (nothing violates)."""
    import numpy as np

    import jax
    from ray_lightning_accelerators_tpu.models.transformer import (
        GPT, TransformerConfig)
    from ray_lightning_accelerators_tpu.serve import (DeadlineExceeded,
                                                      ServeEngine,
                                                      SloPolicy)
    cfg = TransformerConfig(vocab_size=61, d_model=32, n_heads=2,
                            d_ff=64, n_layers=2, max_seq_len=64)
    model = GPT(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    if overloaded:
        pol = SloPolicy(ttft_target_s=1e-6, token_cadence_target_s=1e-6,
                        deadline_s=0.02)
    else:
        pol = SloPolicy(ttft_target_s=300.0,
                        token_cadence_target_s=300.0, deadline_s=300.0)
    engine = ServeEngine(model, params, max_slots=2, slo=pol)
    sheds = 0
    if overloaded:
        # a request that ages past its deadline while the engine is not
        # yet draining the queue -> shed typed before prefill
        stale = engine.submit(rng.integers(0, 61, size=(4,))
                              .astype(np.int32), 4)
        time.sleep(0.06)
    engine.start()
    try:
        from ray_lightning_accelerators_tpu.serve import QueueFull

        def submit_retry(prompt, n):
            # typed backpressure (QueueFull/PoolExhausted) is the
            # documented client contract: shed and retry after drain
            deadline = time.monotonic() + 120
            while True:
                try:
                    return engine.submit(prompt, n)
                except QueueFull:
                    if time.monotonic() > deadline:
                        raise
                    time.sleep(0.01)

        lens = rng.lognormal(1.5, 0.6, size=12).astype(int).clip(2, 24)
        handles = [submit_retry(rng.integers(0, 61, size=(int(n),))
                                .astype(np.int32),
                                int(rng.integers(2, 8)))
                   for n in lens]
        if overloaded:
            handles.append(stale)
        for h in handles:
            try:
                h.result(timeout=300)
            except DeadlineExceeded:
                # under the overloaded 20ms deadline, queue waits
                # legitimately shed requests typed — that IS the signal
                sheds += 1
        return engine.metrics.snapshot(), sheds
    finally:
        engine.stop()


def _cluster_rank_body(step_count):
    """Worker-side body: emit a few flight events so the live snapshot
    has something to show."""
    from ray_lightning_accelerators_tpu.telemetry import emit
    for i in range(step_count):
        emit("train_step", step=i)
        import time as _t
        _t.sleep(0.02)
    return step_count


def _run_cluster(tdir: str):
    """2 local workers with live endpoints; returns (ranks collected,
    driver /metrics rank-label check, merged families)."""
    from urllib.request import urlopen

    from ray_lightning_accelerators_tpu.runtime.actors import ActorPool
    from ray_lightning_accelerators_tpu.telemetry import live
    env = {"RLA_TPU_TELEMETRY_DIR": tdir, "RLA_TPU_METRICS_PORT": "0",
           "RLA_TPU_WORKER_HEARTBEAT_S": "0.1"}
    pool = ActorPool(2, env_per_worker=[dict(env), dict(env)])
    try:
        for f in pool.execute_all(_cluster_rank_body, 10):
            f.result(timeout=180)
        cv = live.ClusterView(workers=list(pool.workers), refresh_s=0.2)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline and len(cv.view()) < 2:
            cv.refresh()
            time.sleep(0.2)
        srv = live.get_server()
        srv.sources.bind_cluster_view(cv)
        with urlopen(srv.url + "/metrics", timeout=10) as r:
            body = r.read().decode()
        labeled = ('rla_tpu_rank_healthy{rank="0"}' in body
                   and 'rla_tpu_rank_healthy{rank="1"}' in body)
        return len(cv.view()), labeled and exposition_valid(body)
    finally:
        pool.shutdown()


def main() -> None:
    workdir = tempfile.mkdtemp(prefix="rla_live_plane_")
    tdir = os.path.join(workdir, "telemetry")
    os.makedirs(tdir)
    os.environ["RLA_TPU_TELEMETRY_DIR"] = tdir
    os.environ["RLA_TPU_METRICS_PORT"] = "0"

    from ray_lightning_accelerators_tpu.analysis import compile_guard as cg
    from ray_lightning_accelerators_tpu.telemetry import live
    cg.install()

    # -- 1+2: scrape-under-load fit, then the unscraped A/B twin -------
    clock_a = _make_clock()
    trainer = None
    scraper = None

    # the server starts inside fit; poll for it from a side thread
    def attach_scraper():
        nonlocal scraper
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            srv = live.get_server()
            if srv is not None and srv.url:
                scraper = _Scraper(srv.url).__enter__()
                return
            time.sleep(0.05)

    attach_thread = threading.Thread(target=attach_scraper, daemon=True)
    attach_thread.start()
    trainer = _fit_once(workdir, "scraped", clock_a)
    attach_thread.join(timeout=5)
    if scraper is not None:
        scraper.__exit__()
    scraped_step_s = clock_a.steady_s()
    window_compiles = clock_a.window_compiles()

    clock_b = _make_clock()
    _fit_once(workdir, "plain", clock_b)
    plain_step_s = clock_b.steady_s()
    overhead = (scraped_step_s - plain_step_s) / plain_step_s \
        if plain_step_s > 0 else 0.0

    scrapes = scraper.scrapes if scraper else 0
    valid = scraper.valid if scraper else 0
    validity = (valid / scrapes) if scrapes else 0.0
    lat = sorted(scraper.latencies) if scraper else []
    lat_p50_ms = round(lat[len(lat) // 2] * 1e3, 2) if lat else None

    # -- 3: serve SLO burn rates ---------------------------------------
    hot, sheds = _serve_slo(overloaded=True)
    cold, _ = _serve_slo(overloaded=False)

    # -- 4: cluster view over 2 live worker endpoints ------------------
    cluster_ranks, cluster_labeled = _run_cluster(tdir)

    record = {
        "metric": "live_plane_scrape_validity",
        "value": round(validity, 4),
        "unit": "fraction",
        "scrapes": scrapes,
        "statusz_ok": scraper.statusz_ok if scraper else 0,
        "scrape_latency_p50_ms": lat_p50_ms,
        "scrape_overhead_fraction": round(overhead, 4),
        "scraped_steady_s": round(scraped_step_s, 3),
        "plain_steady_s": round(plain_step_s, 3),
        "measured_window_compiles": window_compiles,
        "slo_burn_rate_overloaded": hot.get("slo_burn_rate"),
        "slo_violations_overloaded": hot.get("slo_violations"),
        "slo_deadline_sheds": hot.get("slo_deadline_shed"),
        "deadline_shed_typed": sheds,
        "slo_burn_rate_light": cold.get("slo_burn_rate"),
        "slo_violations_light": cold.get("slo_violations"),
        "cluster_ranks_collected": cluster_ranks,
        "cluster_metrics_rank_labeled": cluster_labeled,
        "platform": "cpu-forced-host",
        "note": "value = fraction of live /metrics scrapes (~20Hz under "
                "a training fit) that parsed exposition-valid; overhead "
                "is the scraped-vs-plain steady-state A/B (reported, "
                "not gated — shared-CPU noise; the in-run StepTimeline "
                "is the <1% witness)",
        "vs_baseline": round(validity, 4),
    }
    compile_rec = cg.compile_count_record("live_plane")
    print(json.dumps(compile_rec), flush=True)
    from ray_lightning_accelerators_tpu.telemetry import (
        probe_snapshot_record)
    print(json.dumps(probe_snapshot_record(
        "live_plane", profiler=trainer.profiler)), flush=True)
    print(json.dumps(record), flush=True)


if __name__ == "__main__":
    main()
