"""Input-pipeline microbench on a forced-host-platform CPU mesh.

Self-contained (the gradexchange_probe.py pattern): forces
``JAX_PLATFORMS=cpu`` with 8 virtual devices BEFORE importing jax, so it runs the same way on any machine and never
touches the chip.

What it measures: steps/s through the full Trainer fit loop on a
synthetic INPUT-BOUND loader (a collate_fn that sleeps a configurable
per-batch host latency — the stand-in for decode/augment/tokenize cost;
a custom collate also keeps the device cache and the native engine out
of the way, so this is the honest host-fed hot loop), with
``prefetch_batches=0`` (fully synchronous: collate -> H2D -> dispatch)
vs ``prefetch_batches=2`` (data/prefetch.py overlaps collate + H2D with
compute).  The host latency is CALIBRATED to the measured compute step
time of this machine — overlap hides ``min(host, compute)``, so pinning
host ≈ compute makes the ~2x ideal portable instead of
machine-dependent.  Env overrides: ``RLA_TPU_INPUT_LATENCY_MS`` (skip
calibration), ``RLA_TPU_INPUT_STEPS`` (steps per epoch, default 12).

Emits one bench.py-shaped JSON line on stdout.
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

STEPS_PER_EPOCH = int(os.environ.get("RLA_TPU_INPUT_STEPS", "12"))
EPOCHS = 3  # epoch 1 absorbs compile; epochs 2..N are the timed window


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from ray_lightning_accelerators_tpu import (Callback, DataLoader,
                                                RayTPUAccelerator, Trainer,
                                                TpuModule)
    from ray_lightning_accelerators_tpu.analysis import compile_guard as cg
    from ray_lightning_accelerators_tpu.data.loader import ArrayDataset
    from ray_lightning_accelerators_tpu.utils.profiler import Profiler

    cg.install()  # count XLA compiles across the whole probe
    n_devices = jax.device_count()
    batch = 64 * n_devices
    dim, hidden, classes = 256, 1024, 10

    class _MLP(TpuModule):
        def init_params(self, rng):
            k1, k2, k3 = jax.random.split(rng, 3)
            s = 0.02
            return {"w1": jax.random.normal(k1, (dim, hidden)) * s,
                    "w2": jax.random.normal(k2, (hidden, hidden)) * s,
                    "w3": jax.random.normal(k3, (hidden, classes)) * s}

        def training_step(self, params, batch_, rng):
            x, y = batch_
            h = jnp.tanh(x @ params["w1"])
            h = jnp.tanh(h @ params["w2"])
            logits = h @ params["w3"]
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
            return loss, {"train_loss": loss}

        def configure_optimizers(self):
            return optax.sgd(0.01)

    # PRE-BATCHED samples: each dataset element is one whole (batch, dim)
    # step batch and the collate just sleeps and unwraps it.  The host
    # latency is then pure sleep (GIL-free, needs no CPU), so on a
    # forced-CPU mesh — where a real collate would contend with XLA's
    # compute threads for the same cores and inflate under overlap, a
    # contention a real accelerator's host loop doesn't have — the
    # measured ratio isolates what the bench claims: overlap of host
    # latency with compute.
    rng = np.random.default_rng(0)
    x = rng.standard_normal(
        (STEPS_PER_EPOCH, batch, dim)).astype(np.float32)
    y = rng.integers(0, classes,
                     size=(STEPS_PER_EPOCH, batch)).astype(np.int32)

    class _Clock(Callback):
        """Device-synced wall time at epoch boundaries (bench.py's
        _EpochClock discipline: epoch 1 absorbs compile)."""

        def __init__(self):
            self.starts, self.ends = [], []

        def _sync(self, trainer):
            if trainer._state is not None:
                int(np.asarray(jax.device_get(trainer._state.step)))
            return time.perf_counter()

        def on_train_epoch_start(self, trainer, module):
            self.starts.append(self._sync(trainer))

        def on_train_epoch_end(self, trainer, module):
            self.ends.append(self._sync(trainer))

    def run(latency_s: float, prefetch: int, profiler=None) -> float:
        """One fit; returns steady-state steps/s."""

        def slow_collate(samples):
            if latency_s:
                time.sleep(latency_s)
            return samples[0]  # pre-batched: one element IS the batch

        loader = DataLoader(ArrayDataset(x, y), batch_size=1,
                            shuffle=False, collate_fn=slow_collate)
        clock = _Clock()
        trainer = Trainer(max_epochs=EPOCHS,
                          accelerator=RayTPUAccelerator(),
                          precision="f32", enable_checkpointing=False,
                          log_every_n_steps=10 ** 9, seed=0,
                          callbacks=[clock], profiler=profiler,
                          cache_dataset_on_device=False,
                          prefetch_batches=prefetch,
                          default_root_dir="/tmp/rla_tpu_bench_input")
        trainer.fit(_MLP(), loader)
        dt = clock.ends[-1] - clock.starts[1]
        return STEPS_PER_EPOCH * (EPOCHS - 1) / dt

    latency_ms = os.environ.get("RLA_TPU_INPUT_LATENCY_MS")
    if latency_ms is not None:
        latency_s = float(latency_ms) / 1e3
        calibrated_ms = None
    else:
        # calibrate: host latency = this machine's compute step time, so
        # overlap has an honest ~2x to win.  Calibration runs with a
        # fixed sleep INTERLEAVED (and subtracts it) rather than
        # back-to-back: a saturated all-core burn throttles/queues
        # differently than the sleep-interleaved regime the timed runs
        # actually operate in, and overestimates compute by up to 2x
        cal_sleep_ms = 60.0
        cal_sps = run(cal_sleep_ms / 1e3, 0)
        calibrated_ms = max(1e3 / cal_sps - cal_sleep_ms, 1.0)
        # 1.4x: host strictly dominating compute keeps the overlapped
        # loop host-bound, so prefetch=2 throughput is the (exact) sleep
        # rate and the measured ratio survives a +-30% compute swing
        # between calibration and the timed runs
        latency_s = min(max(1.4 * calibrated_ms, 15.0), 200.0) / 1e3

    sps0 = run(latency_s, 0)
    prof = Profiler()
    sps2 = run(latency_s, 2, profiler=prof)
    ratio = sps2 / sps0
    starved = prof.counters().get("prefetch_starved_steps", 0)
    h2d_wait = prof.summary().get("h2d_wait", {})
    record = {
        "metric": "input_pipeline_prefetch_speedup",
        "value": round(ratio, 3),
        "unit": "x",
        "steps_per_sec_prefetch0": round(sps0, 2),
        "steps_per_sec_prefetch2": round(sps2, 2),
        "host_latency_ms": round(latency_s * 1e3, 2),
        "calibrated_step_ms": (round(calibrated_ms, 2)
                               if calibrated_ms is not None else None),
        "starved_steps_prefetch2": int(starved),
        "h2d_wait_mean_ms": round(h2d_wait.get("mean_s", 0.0) * 1e3, 3),
        "devices": n_devices,
        "platform": "cpu-forced-host",
        "note": "synthetic input-bound loader (collate sleeps "
                "host_latency per pre-batched element); overlap hides "
                "min(host, compute), latency calibrated ~= compute",
        # the driver bar: >= 1.5x steps/s from prefetch on this loader
        "vs_baseline": round(ratio / 1.5, 3),
    }
    # both timed runs share shapes: compile totals drifting up across
    # bench rounds means the fit loop started retracing.  Printed BEFORE
    # the metric record: bench.py takes the LAST JSON line of probe
    # stdout as the bench result.
    print(json.dumps(cg.compile_count_record("input_pipeline")),
          flush=True)
    # unified telemetry snapshot (telemetry/registry.py): the prefetch
    # run's profiler (h2d_wait span, starvation counter, depth gauge) +
    # recorder events + compile count in one registry export — value-
    # less and kind-tagged, so the metric line below stays the result
    from ray_lightning_accelerators_tpu.telemetry import (
        probe_snapshot_record)
    print(json.dumps(probe_snapshot_record("input_pipeline",
                                           profiler=prof)), flush=True)
    print(json.dumps(record), flush=True)


if __name__ == "__main__":
    main()
