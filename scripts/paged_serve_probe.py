"""Paged-serve probe: concurrency per HBM byte, measured, on a forced
host-platform CPU mesh.

Self-contained: forces ``JAX_PLATFORMS=cpu`` with 8 virtual devices
BEFORE importing jax (matching the other CPU-mesh probes), so it
runs the same way on any machine and never touches the chip.

Two measured phases, both over the production-shaped mixed-length
workload (lognormal prompt lengths):

1. **Concurrency per placed byte**: the SAME request stream is driven
   through the dense allocator (``paged=False``, one full
   ``max_total_len`` row per slot) and through a paged pool holding the
   equivalent block capacity, and the headline is
   ``(paged peak concurrent / paged placed bytes) / (dense peak
   concurrent / dense placed bytes)`` — placed bytes read off the real
   cache arrays, peak concurrency off the engines' own watermarks.
   ``vs_baseline`` is against the 1.5x driver bar.
2. **Prefix TTFT**: a shared-system-prompt workload with the prefix
   index ON vs OFF (cold request excluded from both means) — the
   measured TTFT reduction prefix reuse buys.

Output (compile-count line, telemetry line, metric line LAST —
the bench parser contract)::

    {"probe": "paged_serve", "kind": "compile_count", ...}
    {"probe": "paged_serve", "kind": "telemetry", ...}
    {"metric": "paged_serve_concurrency_per_hbm_ratio", "value": ...,
     "unit": "x", "vs_baseline": ..., "ttft_prefix_reduction": ..., ...}
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

MAX_TOTAL_LEN = 192
BLOCK_LEN = 16
DENSE_SLOTS = 4
PAGED_SLOTS = 16
N_REQUESTS = 20
PREFIX_LEN = 96
PREFIX_REQUESTS = 8
CONCURRENCY_BAR = 1.5


def _build_model(seed: int):
    import jax

    from ray_lightning_accelerators_tpu.models.transformer import (
        GPT, TransformerConfig)

    cfg = TransformerConfig(vocab_size=512, d_model=128, n_heads=4,
                            d_ff=256, n_layers=4, max_seq_len=256)
    model = GPT(cfg)
    return model, model.init_params(jax.random.PRNGKey(seed))


def _drive(engine, reqs):
    handles = [engine.submit(p, n) for p, n in reqs]
    for h in handles:
        h.result(timeout=600)
    return [h for h in handles]


def _warm(engine, rng, vocab, lengths, budget=2):
    import numpy as np
    for s0 in lengths:
        p = rng.integers(0, vocab, size=(s0,)).astype(np.int32)
        engine.submit(p, budget).result(timeout=600)


def probe(seed: int) -> tuple:
    import numpy as np

    from ray_lightning_accelerators_tpu.analysis import compile_guard as cg
    from ray_lightning_accelerators_tpu.serve import ServeEngine

    cg.install()
    model, params = _build_model(seed)
    vocab = model.cfg.vocab_size
    rng = np.random.default_rng(seed)

    from serve_probe import mixed_prompts  # shared workload shape
    reqs = [(p, int(rng.integers(8, 17)))
            for p in mixed_prompts(rng, N_REQUESTS, vocab, 120)]

    # -- phase 1: concurrency per placed byte, dense vs paged ---------- #
    # dense: 4 full-length rows.  paged: the same block capacity split
    # over 16 slots (4 slots x 12 blocks + the reserved garbage block).
    per_slot_blocks = -(-MAX_TOTAL_LEN // BLOCK_LEN)
    n_blocks = DENSE_SLOTS * per_slot_blocks + 1
    with ServeEngine(model, params, max_slots=DENSE_SLOTS,
                     queue_depth=2 * N_REQUESTS, paged=False,
                     max_total_len=MAX_TOTAL_LEN) as dense:
        _warm(dense, rng, vocab, range(7, 121, 8))
        dense.metrics.reset()
        _drive(dense, reqs)
        dense_snap = dense.stats()
        dense_bytes = dense._pool_bytes
        dense_peak = dense_snap["max_batch"]

    with ServeEngine(model, params, max_slots=PAGED_SLOTS,
                     queue_depth=2 * N_REQUESTS,
                     max_total_len=MAX_TOTAL_LEN, block_len=BLOCK_LEN,
                     n_blocks=n_blocks, pool_overcommit=2.0) as paged:
        _warm(paged, rng, vocab, range(7, 121, 16))
        paged.metrics.reset()
        window_start = cg.compile_count()
        _drive(paged, reqs)
        paged_snap = paged.stats()
        paged_bytes = paged._pool_bytes
        paged_peak = paged_snap["peak_concurrent"]
        compile_rec = cg.compile_count_record("paged_serve", window_start)

    ratio = ((paged_peak / paged_bytes) / (dense_peak / dense_bytes)
             if dense_peak and paged_bytes else 0.0)

    # -- phase 2: prefix-reuse TTFT, index ON vs OFF ------------------- #
    shared = rng.integers(0, vocab, size=(PREFIX_LEN,)).astype(np.int32)
    pre_reqs = [(p, 4) for p in mixed_prompts(
        rng, PREFIX_REQUESTS, vocab, 120, shared=shared)]

    def ttft_mean(prefix_cache):
        eng = ServeEngine(model, params, max_slots=2,
                          queue_depth=2 * PREFIX_REQUESTS,
                          max_total_len=MAX_TOTAL_LEN,
                          block_len=BLOCK_LEN,
                          prefix_cache=prefix_cache)
        with eng:
            # warm every bucket this workload hits: full-prompt buckets
            # (cold/off path) AND, with the index on, the hit path's
            # suffix buckets (seed request + one hit per suffix edge)
            for sfx in (2, 16):
                for _ in range(2 if prefix_cache else 1):
                    p = np.concatenate([shared, rng.integers(
                        0, vocab, size=(sfx,)).astype(np.int32)])
                    eng.submit(p, 2).result(timeout=600)
            eng.metrics.reset()
            # serialized submissions: TTFT must measure prefill, not
            # queue wait behind the previous request
            ttfts = []
            for p, n in pre_reqs:
                r = eng.submit(p, n)
                r.result(timeout=600)
                ttfts.append(r.ttft_s)
            snap = eng.stats()
        return float(np.mean(ttfts)), snap

    ttft_off, _ = ttft_mean(False)
    ttft_on, on_snap = ttft_mean(True)
    reduction = ttft_off / ttft_on if ttft_on > 0 else 0.0

    from ray_lightning_accelerators_tpu.telemetry import (
        probe_snapshot_record)
    telemetry_rec = probe_snapshot_record("paged_serve", serve=on_snap)

    return compile_rec, telemetry_rec, {
        "metric": "paged_serve_concurrency_per_hbm_ratio",
        "value": round(ratio, 3),
        "unit": "x",
        "vs_baseline": round(ratio / CONCURRENCY_BAR, 3),
        "dense_peak_concurrent": int(dense_peak),
        "paged_peak_concurrent": int(paged_peak),
        "dense_cache_bytes": int(dense_bytes),
        "paged_cache_bytes": int(paged_bytes),
        "requests": N_REQUESTS,
        "block_len": BLOCK_LEN,
        "peak_used_blocks": int(paged_snap["peak_used_blocks"]),
        "cache_waste_ratio": round(
            1.0 - paged_snap["peak_used_blocks"]
            / (paged_peak * per_slot_blocks), 4) if paged_peak else 0.0,
        "ttft_prefix_off_ms": round(1e3 * ttft_off, 3),
        "ttft_prefix_on_ms": round(1e3 * ttft_on, 3),
        "ttft_prefix_reduction": round(reduction, 3),
        "prefix_hits": int(on_snap["prefix_hits"]),
        "prefix_hit_blocks": int(on_snap["prefix_hit_blocks"]),
    }


def main() -> None:
    compile_rec = telemetry_rec = None
    try:
        compile_rec, telemetry_rec, rec = probe(
            int(sys.argv[sys.argv.index("--seed") + 1])
            if "--seed" in sys.argv else 0)
    except Exception as e:
        rec = {"metric": "paged_serve_concurrency_per_hbm_ratio",
               "value": 0, "unit": "x", "vs_baseline": 0.0,
               "error": f"{type(e).__name__}: {e}"[:400]}
    if compile_rec is not None:
        print(json.dumps(compile_rec), flush=True)
    if telemetry_rec is not None:
        print(json.dumps(telemetry_rec), flush=True)
    print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
