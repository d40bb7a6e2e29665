"""Prefix-affinity probe: affinity-vs-least-loaded p99 TTFT A/B plus a
disaggregated-lane decode-cadence window, on a forced host-platform CPU
mesh.

Self-contained: forces ``JAX_PLATFORMS=cpu`` with 8 virtual devices
BEFORE importing jax (matching the other CPU-mesh probes), so it
runs the same way on any machine and never touches the chip.

Two parts:

1. **Affinity A/B (lanes off)**: the SAME skewed shared-prefix workload
   (4 hot 384-token prefix families x repeated suffix variants, each
   wave's arrival order shuffled the way real traffic interleaves) is
   served twice by a 3-replica tier whose block pools hold roughly two
   families each — once with affinity routing disabled (pure
   least-loaded spray: families wander across replicas with the
   shuffled arrivals, so the bounded prefix caches keep evicting and
   re-prefilling whole families under LRU) and once enabled (each
   family converges on the replica whose cache already holds its
   blocks, so repeats prefill only the suffix).  Requests route
   one-per-chunk so the comparison is pure routing policy, not chunk
   grouping.  The first two waves are a routing/cache warmup excluded
   from BOTH arms' windows — the A/B measures steady state, where a
   production tier lives.  The headline is the steady-state p99 TTFT
   ratio least-loaded/affinity (>1 = affinity faster) with the tier
   prefix-route hit rate as the mechanism evidence (driver bar:
   >= 0.5).

2. **Disaggregated lanes**: 1 prefill + 2 decode replicas; the same
   long-prompt stream prefills in the prefill lane and hands each KV
   block span to a decode replica (block-id remap + wave-bounded
   object-store copy).  Reported: decode-cadence p99 while the long
   prefill stream runs, and the KV handoff count (>= 1 proves the lane
   path served).

Output (compile-count line, telemetry line, metric line LAST —
the bench parser contract)::

    {"probe": "prefix_affinity", "kind": "compile_count", ...}
    {"probe": "prefix_affinity", "kind": "telemetry", ...}
    {"metric": "prefix_affinity_ttft_ratio", "value": ...,
     "unit": "ratio", "vs_baseline": ..., "prefix_hit_rate": ...,
     "decode_cadence_p99_ms": ..., "kv_handoffs": ..., ...}
"""

from __future__ import annotations

import json
import os
import sys
import time

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

N_FAMILIES = 4
REPEATS = 10                 # waves; one request per family per wave
WARMUP_WAVES = 2             # excluded from both arms' TTFT windows
PREFIX_LEN = 384             # 48 full blocks at block_len 8
BLOCK_LEN = 8
HEARTBEAT_S = 0.1
TTFT_RATIO_BAR = 1.0         # affinity must not lose to least-loaded
HIT_RATE_BAR = 0.5

_MODEL_CFG = dict(vocab_size=61, d_model=64, n_heads=4, d_ff=256,
                  n_layers=3, max_seq_len=512)


def _engine_factory(np_params, n_blocks):
    def make():
        from ray_lightning_accelerators_tpu.models.transformer import (
            GPT, TransformerConfig)
        from ray_lightning_accelerators_tpu.serve import ServeEngine
        model = GPT(TransformerConfig(**_MODEL_CFG))
        return ServeEngine(model, np_params, max_slots=4,
                           queue_depth=64, block_len=BLOCK_LEN,
                           n_blocks=n_blocks, idle_poll_s=0.005,
                           slo=None)
    return make


def _skewed_requests(rng):
    """Shared-prefix workload: each request is one of N_FAMILIES hot
    384-token prefixes + a short random suffix — the shape prefix
    routing exists for.  One request per family per wave, with each
    wave's arrival order shuffled: real traffic interleaves families
    arbitrarily, and a fixed arrival order would let least-loaded
    routing degenerate into an accidental stable family->replica
    assignment (queue order decides placement), hiding the re-prefill
    cost affinity exists to avoid."""
    import numpy as np
    prefixes = [rng.integers(1, 60, size=PREFIX_LEN).astype(np.int32)
                for _ in range(N_FAMILIES)]
    reqs = []
    for _ in range(REPEATS):
        for fam in rng.permutation(N_FAMILIES):
            suffix = rng.integers(1, 60, size=int(
                rng.integers(4, 9))).astype(np.int32)
            reqs.append((np.concatenate([prefixes[fam], suffix]), 2))
    return reqs


def _drive(group, reqs):
    """One wave of N_FAMILIES requests in flight at a time, so TTFT
    measures routing + prefill, not an ever-deepening queue.  The first
    WARMUP_WAVES waves run but are excluded from the returned TTFT
    window (routing + caches converge there in both A/B arms)."""
    import numpy as np
    ttfts, cadences = [], []
    for i in range(0, len(reqs), N_FAMILIES):
        handles = [(group.submit(p, n), n, time.monotonic())
                   for p, n in reqs[i:i + N_FAMILIES]]
        for h, n, t0 in handles:
            np.asarray(h.result(timeout=300))
            t_done = time.monotonic()
            if h.ttft_s is None or i < WARMUP_WAVES * N_FAMILIES:
                continue
            ttfts.append(h.ttft_s)
            if n > 1:
                cadences.append((t_done - t0 - h.ttft_s) / (n - 1))
    return ttfts, cadences


def _p99(values):
    import numpy as np
    return float(np.percentile(np.asarray(values), 99)) if values else 0.0


def _tier(factory, **cfg_overrides):
    from ray_lightning_accelerators_tpu.serve import (ControllerConfig,
                                                      ServeReplicas)
    cfg = ControllerConfig(hedge=False, poll_s=0.05, **cfg_overrides)
    return ServeReplicas(factory, num_replicas=3, chunk_size=1,
                         heartbeat_s=HEARTBEAT_S, queue_depth=64,
                         controller=cfg, affinity_block_len=BLOCK_LEN)


def _warm(group):
    """Warm every replica's compile path with a prompt DISJOINT from
    the measured families (vocab-0 filler never appears in the
    workload), so both A/B arms start with hot programs."""
    import numpy as np
    for _ in group.pool.workers:
        p = np.zeros(PREFIX_LEN + 4, np.int32)
        group.submit(p, 2).result(timeout=300)
    group.metrics.reset()


def probe(seed: int) -> tuple:
    import jax
    import numpy as np

    from ray_lightning_accelerators_tpu.analysis import compile_guard as cg
    from ray_lightning_accelerators_tpu.models.transformer import (
        GPT, TransformerConfig)

    cg.install()
    model = GPT(TransformerConfig(**_MODEL_CFG))
    params = model.init_params(jax.random.PRNGKey(seed))
    np_params = jax.tree.map(np.asarray, params)
    rng = np.random.default_rng(seed)
    reqs = _skewed_requests(rng)
    # A/B pool: ~2 families' worth of cache per replica (48 blocks per
    # family + in-flight reservations) — under least-loaded spray the
    # shuffled arrivals walk every family across every replica and the
    # LRU prefix cache keeps evicting whole families; under affinity
    # each replica's 1-2 resident families fit stably
    ab_factory = _engine_factory(np_params, n_blocks=120)

    # -- part 1a: least-loaded spray (affinity off) -------------------- #
    with _tier(ab_factory, affinity=False) as spray:
        _warm(spray)
        window_start = cg.compile_count()
        ll_ttfts, _ = _drive(spray, reqs)
    # -- part 1b: the same workload under affinity routing ------------- #
    with _tier(ab_factory, affinity=True) as aff:
        _warm(aff)
        af_ttfts, _ = _drive(aff, reqs)
        aff_snap = aff.stats()
    compile_rec = cg.compile_count_record("prefix_affinity",
                                          window_start)
    hits = aff_snap["prefix_route_hits"]
    misses = aff_snap["prefix_route_misses"]
    hit_rate = hits / (hits + misses) if hits + misses else 0.0

    # -- part 2: disaggregated lanes (1 prefill + 2 decode) ------------ #
    # much bigger pool: the single prefill-lane replica carries every
    # in-flight request's export reservation PLUS the source holds of
    # already-handed-off requests (released asynchronously after the
    # decode side finishes), so a couple of waves of 48-block prompts
    # can be committed at once
    lane_factory = _engine_factory(np_params, n_blocks=640)
    with _tier(lane_factory, affinity=True, prefill_replicas=1,
               handoff_min_blocks=1) as lanes:
        _warm(lanes)
        _, lane_cadences = _drive(lanes, reqs)
        lanes_snap = lanes.stats()

    from ray_lightning_accelerators_tpu.telemetry import (
        probe_snapshot_record)
    telemetry_rec = probe_snapshot_record("prefix_affinity",
                                          serve=lanes_snap)

    ll_p99, af_p99 = _p99(ll_ttfts), _p99(af_ttfts)
    ratio = ll_p99 / af_p99 if af_p99 else 0.0
    return compile_rec, telemetry_rec, {
        "metric": "prefix_affinity_ttft_ratio",
        "value": round(ratio, 4),
        "unit": "ratio",
        "vs_baseline": round(ratio / TTFT_RATIO_BAR, 4),
        "requests": len(reqs),
        "families": N_FAMILIES,
        "prefix_len": PREFIX_LEN,
        "warmup_waves": WARMUP_WAVES,
        "p99_ttft_ms_least_loaded": round(1e3 * ll_p99, 3),
        "p99_ttft_ms_affinity": round(1e3 * af_p99, 3),
        "prefix_hit_rate": round(hit_rate, 4),
        "prefix_route_hits": int(hits),
        "prefix_route_misses": int(misses),
        "hit_rate_bar": HIT_RATE_BAR,
        "decode_cadence_p99_ms": round(1e3 * _p99(lane_cadences), 3),
        "kv_handoffs": int(lanes_snap["kv_handoffs"]),
        "kv_handoff_bytes": int(lanes_snap["kv_handoff_bytes"]),
        "lanes_completed": int(lanes_snap["completed"]),
        "lanes_failed": int(lanes_snap["failed"]),
        "affinity_accounting_exact": bool(
            aff_snap["completed"] + aff_snap["failed"]
            + aff_snap["cancelled"] == aff_snap["submitted"]),
        "lanes_accounting_exact": bool(
            lanes_snap["completed"] + lanes_snap["failed"]
            + lanes_snap["cancelled"] == lanes_snap["submitted"]),
    }


def main() -> None:
    compile_rec = telemetry_rec = None
    try:
        compile_rec, telemetry_rec, rec = probe(
            int(sys.argv[sys.argv.index("--seed") + 1])
            if "--seed" in sys.argv else 0)
    except Exception as e:
        rec = {"metric": "prefix_affinity_ttft_ratio",
               "value": 0, "unit": "ratio", "vs_baseline": 0.0,
               "error": f"{type(e).__name__}: {e}"[:400]}
    if compile_rec is not None:
        print(json.dumps(compile_rec), flush=True)
    if telemetry_rec is not None:
        print(json.dumps(telemetry_rec), flush=True)
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
