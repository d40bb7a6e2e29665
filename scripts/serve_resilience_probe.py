"""Serve-resilience probe: completed-request fraction + p99 TTFT across
a replica chaos window, on a forced host-platform CPU mesh.

Self-contained: forces ``JAX_PLATFORMS=cpu`` with 8 virtual devices
BEFORE importing jax (matching the other CPU-mesh probes), so it
runs the same way on any machine and never touches the chip.

Two phases over the same mixed-length sustained workload:

1. **No-chaos baseline**: a 2-replica ``ServeReplicas`` tier serves the
   stream; p99 TTFT and completed fraction recorded.
2. **Chaos window**: a 3-replica tier with one replica KILLED
   (``crash@replica1:chunk3:once``) and one HUNG
   (``hang@replica2:chunk3:once``) mid-run.  The controller
   (serve/controller.py) requeues the lost chunks head-of-line with
   retry backoff, opens the failed replicas' circuits, auto-revives
   them through the half-open probe, and the headline is the fraction
   of admitted requests that still resolved — the driver bar is 1.0
   (zero lost requests), with the chaos-vs-baseline p99 TTFT ratio
   reported as the recovery-latency evidence.

Output (compile-count line, telemetry line, metric line LAST —
the bench parser contract)::

    {"probe": "serve_resilience", "kind": "compile_count", ...}
    {"probe": "serve_resilience", "kind": "telemetry", ...}
    {"metric": "serve_resilience_completed_fraction", "value": ...,
     "unit": "fraction", "vs_baseline": ..., "p99_ttft_ratio": ..., ...}
"""

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

N_REQUESTS = 24
WAVES = 6
WAVE_SLEEP_S = 0.25
HEARTBEAT_S = 0.1
WEDGE_TIMEOUT_S = 1.5
COMPLETED_BAR = 1.0

_MODEL_CFG = dict(vocab_size=61, d_model=32, n_heads=2, d_ff=64,
                  n_layers=2, max_seq_len=48)


def _engine_factory(np_params):
    def make():
        from ray_lightning_accelerators_tpu.models.transformer import (
            GPT, TransformerConfig)
        from ray_lightning_accelerators_tpu.serve import ServeEngine
        model = GPT(TransformerConfig(**_MODEL_CFG))
        return ServeEngine(model, np_params, max_slots=4, queue_depth=64)
    return make


def _requests(rng, n):
    import numpy as np
    out = []
    for _ in range(n):
        s0 = int(rng.integers(3, 13))
        out.append((rng.integers(0, _MODEL_CFG["vocab_size"],
                                 size=(s0,)).astype(np.int32),
                    int(rng.integers(3, 7))))
    return out


def _drive(group, reqs):
    """Sustained mixed load: waves of submissions across the window so
    the chaos faults land while requests are genuinely in flight."""
    import numpy as np
    handles = []
    per_wave = -(-len(reqs) // WAVES)
    for w in range(WAVES):
        for p, n in reqs[w * per_wave:(w + 1) * per_wave]:
            handles.append(group.submit(p, n))
        time.sleep(WAVE_SLEEP_S)
    done = failed = 0
    for h in handles:
        try:
            np.asarray(h.result(timeout=300))
            done += 1
        except Exception:
            failed += 1
    return done, failed, [h.ttft_s for h in handles
                          if h.ttft_s is not None]


def _p99(values):
    import numpy as np
    return float(np.percentile(np.asarray(values), 99)) if values else 0.0


def probe(seed: int) -> tuple:
    import jax
    import numpy as np

    from ray_lightning_accelerators_tpu.analysis import compile_guard as cg
    from ray_lightning_accelerators_tpu.models.transformer import (
        GPT, TransformerConfig)
    from ray_lightning_accelerators_tpu.serve import ServeReplicas

    cg.install()
    model = GPT(TransformerConfig(**_MODEL_CFG))
    params = model.init_params(jax.random.PRNGKey(seed))
    np_params = jax.tree.map(np.asarray, params)
    factory = _engine_factory(np_params)
    rng = np.random.default_rng(seed)
    reqs = _requests(rng, N_REQUESTS)
    hb = {"RLA_TPU_WORKER_HEARTBEAT_S": str(HEARTBEAT_S)}

    # -- phase 1: no-chaos baseline ------------------------------------ #
    with ServeReplicas(factory, num_replicas=2, chunk_size=2,
                       heartbeat_s=HEARTBEAT_S,
                       wedge_timeout_s=WEDGE_TIMEOUT_S) as base:
        # warm every replica's compile path before the timed window
        for p, _ in reqs[:4]:
            base.submit(p, 2).result(timeout=300)
        base.metrics.reset()
        window_start = cg.compile_count()
        b_done, b_failed, b_ttfts = _drive(base, reqs)
        base_snap = base.stats()
    compile_rec = cg.compile_count_record("serve_resilience",
                                          window_start)

    # -- phase 2: chaos window (1 killed + 1 hung mid-run) ------------- #
    ns = tempfile.mkdtemp(prefix="rla-serve-resilience-chaos-")
    envs = [
        dict(hb),
        dict(hb, RLA_TPU_CHAOS="crash@replica1:chunk3:once",
             RLA_TPU_CHAOS_NS=ns),
        dict(hb, RLA_TPU_CHAOS="hang@replica2:chunk3:once",
             RLA_TPU_CHAOS_NS=ns),
    ]
    with ServeReplicas(factory, num_replicas=3, chunk_size=2,
                       heartbeat_s=HEARTBEAT_S,
                       wedge_timeout_s=WEDGE_TIMEOUT_S,
                       env_per_worker=envs) as tier:
        for p, _ in reqs[:4]:
            tier.submit(p, 2).result(timeout=300)
        tier.metrics.reset()
        c_done, c_failed, c_ttfts = _drive(tier, reqs)
        # bounded recovery: both faulted replicas must rejoin rotation
        # through the circuit breaker before teardown
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:
            if tier.metrics.snapshot()["revived"] >= 2:
                break
            time.sleep(0.2)
        chaos_snap = tier.stats()

    from ray_lightning_accelerators_tpu.telemetry import (
        probe_snapshot_record)
    telemetry_rec = probe_snapshot_record("serve_resilience",
                                          serve=chaos_snap)

    submitted = chaos_snap["submitted"]
    fraction = c_done / submitted if submitted else 0.0
    b_p99, c_p99 = _p99(b_ttfts), _p99(c_ttfts)
    return compile_rec, telemetry_rec, {
        "metric": "serve_resilience_completed_fraction",
        "value": round(fraction, 4),
        "unit": "fraction",
        "vs_baseline": round(fraction / COMPLETED_BAR, 4),
        "requests": N_REQUESTS,
        "chaos": "crash@replica1:chunk3:once,hang@replica2:chunk3:once",
        "completed_chaos": int(c_done),
        "failed_chaos": int(c_failed),
        "completed_baseline": int(b_done),
        "failed_baseline": int(b_failed),
        "p99_ttft_ms_baseline": round(1e3 * b_p99, 3),
        "p99_ttft_ms_chaos": round(1e3 * c_p99, 3),
        "p99_ttft_ratio": round(c_p99 / b_p99, 3) if b_p99 else 0.0,
        "requeued": int(chaos_snap["requeued"]),
        "wedge_events": int(chaos_snap["wedge_events"]),
        "revived": int(chaos_snap["revived"]),
        "hedged": int(chaos_snap["hedged"]),
        "baseline_accounting_exact": bool(
            base_snap["completed"] + base_snap["failed"]
            + base_snap["cancelled"] == base_snap["submitted"]),
        "chaos_accounting_exact": bool(
            chaos_snap["completed"] + chaos_snap["failed"]
            + chaos_snap["cancelled"] == chaos_snap["submitted"]),
    }


def main() -> None:
    compile_rec = telemetry_rec = None
    try:
        compile_rec, telemetry_rec, rec = probe(
            int(sys.argv[sys.argv.index("--seed") + 1])
            if "--seed" in sys.argv else 0)
    except Exception as e:
        rec = {"metric": "serve_resilience_completed_fraction",
               "value": 0, "unit": "fraction", "vs_baseline": 0.0,
               "error": f"{type(e).__name__}: {e}"[:400]}
    if compile_rec is not None:
        print(json.dumps(compile_rec), flush=True)
    if telemetry_rec is not None:
        print(json.dumps(telemetry_rec), flush=True)
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
