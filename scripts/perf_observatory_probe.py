"""Perf-observatory probe on a forced-host-platform 8-device CPU mesh.

Self-contained: forces ``JAX_PLATFORMS=cpu`` with 8 virtual devices
BEFORE importing jax, so it runs the same way on any machine and never
touches the chip.

One training run + one elastic run exercise all three ledgers
(telemetry/perf.py), and everything lands in a ``run_report.json`` and
a Prometheus export:

1. **StepTimeline** — a compressed-FSDP fit (int8 reduce-scatter +
   bf16 all-gather over fsdp=8) with the observatory attached: per-step
   wall partitioned into h2d / compile / compute / ckpt / other.  The
   headline value is the NAMED-phase coverage of measured step wall
   (the `other` remainder is exported, not hidden) — the acceptance bar
   is phases summing to within 10% of step wall.
2. **HbmLedger** — params / opt_state / exchange-buffer / device-cache
   / prefetch pools vs the live placed-array total; the probe reports
   the attributed fraction and the pool table.
3. **GoodputLedger** — an ``ElasticRunner`` run over a 2-worker pool
   with ONE injected preemption (chaos ``preempt@rank0:step1:once``):
   the drained attempt resumes from its checkpoint, the runner accounts
   restart/boot, the workers report their productive/checkpoint split,
   and one goodput fraction comes out.

Emits one bench.py-shaped JSON line on stdout, with the bench-honesty
compile-count record and the telemetry snapshot printed BEFORE it (the
parser takes the newest value-bearing line)."""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _goodput_train_body(rank, ckpt_dir, total_steps):
    """Checkpointing trainable honoring the preemption contract (the
    test_preemption shape, jax-free so worker boot stays cheap): poll
    the notice each step boundary, persist the step, return the rank's
    measured productive/checkpoint seconds for the goodput ledger."""
    import json as _json
    import os as _os
    import time as _time
    from ray_lightning_accelerators_tpu.runtime import preemption
    notice = preemption.get_notice()
    path = _os.path.join(ckpt_dir, "state.json")
    start = 0
    if _os.path.exists(path):
        with open(path) as f:
            start = _json.load(f)["step"]
    productive = ckpt = 0.0
    for step in range(start, total_steps):
        if notice.requested():
            raise preemption.Preempted.at_step(step, path,
                                               source=notice.source)
        t0 = _time.monotonic()
        _time.sleep(0.04)  # the "step"
        productive += _time.monotonic() - t0
        if rank == 0:
            t0 = _time.monotonic()
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                _json.dump({"step": step + 1}, f)
            _os.replace(tmp, path)
            ckpt += _time.monotonic() - t0
    return {"rank": rank, "productive_s": productive,
            "checkpoint_s": ckpt}


def _run_goodput(workdir: str):
    """ElasticRunner over 2 workers with one injected preemption;
    returns (runner, per-rank breakdowns)."""
    from ray_lightning_accelerators_tpu.runtime.actors import ActorPool
    from ray_lightning_accelerators_tpu.runtime.elastic import \
        ElasticRunner
    ckpt = os.path.join(workdir, "goodput-ckpt")
    ns = os.path.join(workdir, "chaos-ns")
    os.makedirs(ckpt)
    os.makedirs(ns)
    env = {"RLA_TPU_CHAOS": "preempt@rank0:step2:once",
           "RLA_TPU_CHAOS_NS": ns,
           "RLA_TPU_PREEMPT_GRACE_S": "60"}
    pool = ActorPool(2, env_per_worker=[dict(env), dict(env)])
    try:
        # warm-up dispatch (chaos step 1 skipped by the :step2 spec):
        # worker-process boot lands OUTSIDE the goodput wall, so the
        # fraction measures the run, not the spawn
        for f in pool.execute_all(lambda: None):
            f.result(timeout=120)
        runner = ElasticRunner(pool, max_failures=0, max_preemptions=2)
        out = runner.run(_goodput_train_body,
                         args_per_worker=lambda a: [(r, ckpt, 30)
                                                    for r in range(2)])
        # the interior split: ONE rank's breakdown (absorbing all ranks
        # would double-count seconds against one driver wall)
        r0 = next(o for o in out if o["rank"] == 0)
        runner.goodput.account("productive", r0["productive_s"])
        runner.goodput.account("checkpoint", r0["checkpoint_s"])
        from ray_lightning_accelerators_tpu.telemetry import get_recorder
        runner.goodput.absorb_events(get_recorder().events())
        return runner, out
    finally:
        pool.shutdown()


def main() -> None:
    import numpy as np  # noqa: F401  (keeps the mesh import order tidy)

    from ray_lightning_accelerators_tpu import (DataLoader,
                                                RayTPUAccelerator,
                                                Trainer)
    from ray_lightning_accelerators_tpu.analysis import compile_guard as cg
    from ray_lightning_accelerators_tpu.data.loader import ArrayDataset
    from ray_lightning_accelerators_tpu.models.mnist import (
        MNISTClassifier, synthetic_mnist)
    from ray_lightning_accelerators_tpu.telemetry import (HbmLedger,
                                                          PerfObservatory,
                                                          registry as treg)
    from ray_lightning_accelerators_tpu.utils.profiler import Profiler

    cg.install()
    workdir = tempfile.mkdtemp(prefix="rla_perf_observatory_")

    # -- ledgers 1+2: compressed-FSDP fit with the observatory attached -
    perf = PerfObservatory(hbm=HbmLedger(sample_min_s=0.0))
    x, y = synthetic_mnist(1024, seed=0)
    loader = DataLoader(ArrayDataset(x, y), batch_size=128, shuffle=True)
    model = MNISTClassifier({"layer_1": 128, "layer_2": 128, "lr": 1e-3,
                             "batch_size": 128})
    trainer = Trainer(max_epochs=3, precision="f32", seed=0,
                      accelerator=RayTPUAccelerator(use_fsdp=True),
                      grad_compression="int8",
                      enable_checkpointing=True,
                      checkpoint_format="sharded",
                      log_every_n_steps=10 ** 9,
                      profiler=Profiler(sync=True),
                      perf_observatory=perf,
                      # force the HBM-resident dataset cache (auto skips
                      # it on CPU): the dominant placed pool becomes an
                      # attributed one, and the cached-gather step path
                      # gets timeline coverage too
                      cache_dataset_on_device=True,
                      default_root_dir=os.path.join(workdir, "fit"))
    t_fit = time.perf_counter()
    trainer.fit(model, loader)
    fit_wall = time.perf_counter() - t_fit

    tl = perf.timeline.snapshot()
    hbm = perf.hbm.snapshot()
    phase_coverage = tl["phase_sum_over_wall"]   # == 1.0 by construction
    named_coverage = tl["attributed_fraction"]   # the non-`other` share

    # -- ledger 3: goodput across an elastic run with one preemption ----
    runner, _ = _run_goodput(workdir)
    # driver-side context the runner cannot see: the run's own fit phase
    # split feeds productive/compile/checkpoint for the TRAINING run too
    gp = runner.goodput.snapshot()

    # -- unified export + run report ------------------------------------
    reg = trainer.build_metrics_registry()
    reg.add_goodput(runner.goodput)   # the elastic run's ledger
    prom_lines = reg.prometheus_text().splitlines()
    report_path = treg.write_run_report(
        os.path.join(workdir, "run_report.json"),
        trace_id=trainer.trace_id, registry=reg,
        extra={"probe": "perf_observatory", "fit_wall_s": fit_wall})
    with open(report_path) as f:
        report = json.load(f)
    ledgers = set((report.get("metrics") or {}).get("perf") or {})

    record = {
        "metric": "perf_observatory_phase_coverage",
        "value": round(named_coverage, 4),
        "unit": "fraction",
        "steps": tl["steps"],
        "mean_step_ms": tl["mean_step_ms"],
        "phase_sum_over_wall": phase_coverage,
        "phases_ms": {k: round(v["total_s"] * 1e3, 2)
                      for k, v in tl["phases"].items()},
        "between_step_phases_ms": {
            k: round(v["total_s"] * 1e3, 2)
            for k, v in tl["between_step_phases"].items()},
        "hbm_attributed_fraction": hbm["attributed_fraction"],
        "hbm_total_bytes": hbm["total_bytes"],
        "hbm_pools_bytes": {k: v["bytes"]
                            for k, v in hbm["pools"].items()},
        "hbm_samples": hbm["samples"],
        "goodput_fraction": gp["goodput_fraction"],
        "goodput_seconds": gp["seconds"],
        "goodput_wall_s": gp["wall_s"],
        "elastic_attempts": gp["attempts"],
        "preemptions_injected": 1,
        "preemptions_observed": len(runner.preempt_events),
        "run_report": report_path,
        "run_report_ledgers": sorted(ledgers),
        "prometheus_lines": len(prom_lines),
        "platform": "cpu-forced-host",
        "note": "value = named-phase coverage of measured step wall "
                "(the `other` remainder is exported, not hidden); "
                "in-step phases sum to wall by construction "
                "(phase_sum_over_wall)",
        # the bar: named phases cover >= ~0.86 of step wall (the
        # within-10% acceptance criterion, PERF_BASELINE.json floor)
        "vs_baseline": round(named_coverage / 0.855, 3),
    }
    compile_rec = cg.compile_count_record("perf_observatory")
    print(json.dumps(compile_rec), flush=True)
    from ray_lightning_accelerators_tpu.telemetry import (
        probe_snapshot_record)
    print(json.dumps(probe_snapshot_record("perf_observatory")),
          flush=True)
    print(json.dumps(record), flush=True)


if __name__ == "__main__":
    main()
