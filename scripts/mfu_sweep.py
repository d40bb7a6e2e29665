"""MFU sweep harness for the GPT flagship bench config.

Runs one bench-shaped GPT training measurement per requested variant and
prints a JSON line each, so the tuned ladder can be re-measured (and
extended) on hardware in one command:

    python scripts/mfu_sweep.py tuned remat-dots gather-scan

Variants (all deltas are against the tuned r4 config: flash 1024x1024,
loss_chunk 2048, 24-step epochs, per-chip batch 16, seq 1024):

- ``r3``            the round-3 conservative config (512 blocks, chunk
                    4096, 12-step epochs) -- the cross-round anchor
- ``tuned``         the r4 tuned config exactly
- ``remat-dots``    + per-layer jax.checkpoint, dots_saveable: keeps
                    matmul outputs, recomputes elementwise (norm/rope/
                    gelu) in the backward -- trades recompute VPU time
                    for the residual-stacking HBM traffic
- ``remat-dots-nbd``+ dots_with_no_batch_dims_saveable (keeps only
                    batch-free dots; more recompute, less traffic)
- ``b20`` / ``b24`` per-chip batch 20 / 24 (b24 OOMed by 0.85 GB on the
                    no-remat config; remat variants may fit -- a bigger
                    batch amortizes fixed per-step costs)
- ``chunk1024`` / ``chunk4096``  loss-chunk pipeline re-check

Overlap-aware FSDP (compressed-FSDP step, parallel/collectives.py):

- ``gather-tree`` / ``gather-scan``   fsdp + int8 reduce-scatter with
                    the whole-tree up-front bf16 param gather vs the
                    layer-wise gather INSIDE the transformer scan
                    (overlaps layer k+1's gather with layer k's
                    matmuls; backward re-gathers under remat).  Both
                    run under remat so the schedules are compared on
                    the composition the scan gather exists for.
- ``gather-*-smoke``  the same A/B at the CPU-mesh-measurable ``small``
                    size -- what scripts/mfu_overlap_probe.py runs on
                    the forced 8-device host mesh.
- ``int8-matmul``   tuned config + int8 forward MLP matmuls with
                    straight-through gradients (ops/quant.py)
- ``autotuned`` / ``autotuned-smoke``  the closed loop: the in-repo TPE
                    searcher (tune.autotune_step) drives remat_policy x
                    flash blocks x gather_mode against measured step
                    time, then the record reports best-vs-default.

Each variant is measured through the same public-API fit + epoch-clock
discipline as bench.py (epoch 1 absorbs compile; scalar-readback sync),
and every record carries ``measured_window_compiles`` (0 = no retrace
landed inside the timed window).
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_FSDP_SWEEP = dict(loss_chunk=2048, flash_block=1024, steps_per_epoch=24,
                   use_fsdp=True, grad_compression="int8", remat=True,
                   remat_policy="nothing")
# CPU-mesh-measurable size: 4 epochs x 6 steps keeps one variant under
# ~a minute on an 8-device host mesh while the steady-state window still
# spans 18 steps
_FSDP_SMOKE = dict(loss_chunk=256, flash_block=128, steps_per_epoch=6,
                   epochs=4, small=True, precision="f32", use_fsdp=True,
                   grad_compression="int8", remat=True,
                   remat_policy="nothing")

VARIANTS = {
    # CPU-runnable plumbing check (tiny model; MFU meaningless)
    "smoke": dict(loss_chunk=256, flash_block=128, steps_per_epoch=2,
                  tiny=True),
    "smoke-remat": dict(loss_chunk=256, flash_block=128,
                        steps_per_epoch=2, tiny=True, remat=True,
                        remat_policy="dots"),
    "r3": dict(loss_chunk=4096, flash_block=512, steps_per_epoch=12),
    "tuned": dict(loss_chunk=2048, flash_block=1024, steps_per_epoch=24),
    "remat-dots": dict(loss_chunk=2048, flash_block=1024,
                       steps_per_epoch=24, remat=True,
                       remat_policy="dots"),
    "remat-dots-nbd": dict(loss_chunk=2048, flash_block=1024,
                           steps_per_epoch=24, remat=True,
                           remat_policy="dots_with_no_batch_dims"),
    "b20": dict(loss_chunk=2048, flash_block=1024, steps_per_epoch=24,
                per_chip_batch=20),
    "b24": dict(loss_chunk=2048, flash_block=1024, steps_per_epoch=24,
                per_chip_batch=24),
    "b20-remat-dots": dict(loss_chunk=2048, flash_block=1024,
                           steps_per_epoch=24, per_chip_batch=20,
                           remat=True, remat_policy="dots"),
    "chunk1024": dict(loss_chunk=1024, flash_block=1024,
                      steps_per_epoch=24),
    "chunk4096": dict(loss_chunk=4096, flash_block=1024,
                      steps_per_epoch=24),
    # overlap-aware FSDP A/B (compressed-FSDP step)
    "gather-tree": dict(_FSDP_SWEEP, gather_mode="tree"),
    "gather-scan": dict(_FSDP_SWEEP, gather_mode="scan"),
    "gather-tree-smoke": dict(_FSDP_SMOKE, gather_mode="tree"),
    "gather-scan-smoke": dict(_FSDP_SMOKE, gather_mode="scan"),
    # int8 forward matmuls in the train step (MLP projections)
    "int8-matmul": dict(loss_chunk=2048, flash_block=1024,
                        steps_per_epoch=24, int8_matmul=True),
    "int8-matmul-smoke": dict(loss_chunk=256, flash_block=128,
                              steps_per_epoch=2, tiny=True,
                              int8_matmul=True),
    # closed-loop step autotuning (special-cased in run_variant)
    "autotuned": dict(autotune=True, smoke=False),
    "autotuned-smoke": dict(autotune=True, smoke=True),
}


def _autotune_measure(smoke: bool):
    """measure(config) -> step seconds for tune.autotune_step, produced
    by the same _bench_gpt timed-window discipline as every other sweep
    number (reduced budget: trials are search probes, not headlines)."""
    from bench import _bench_gpt

    def measure(config):
        remat_policy = config.get("remat_policy", "none")
        base = (dict(loss_chunk=256, flash_block=128, steps_per_epoch=4,
                     epochs=3, small=True, precision="f32",
                     use_fsdp=True, grad_compression="int8")
                if smoke else
                dict(loss_chunk=2048, flash_block=1024,
                     steps_per_epoch=8, epochs=3, use_fsdp=True,
                     grad_compression="int8"))
        base["flash_block"] = int(config.get("flash_block_q",
                                             base["flash_block"]))
        rec = _bench_gpt(**dict(
            base,
            remat=remat_policy != "none",
            remat_policy=(remat_policy if remat_policy != "none"
                          else "nothing"),
            gather_mode=config.get("gather_mode", "tree")))
        return rec["step_ms"] / 1e3

    return measure


def _run_autotuned(name: str, smoke: bool) -> tuple:
    from ray_lightning_accelerators_tpu import tune
    from ray_lightning_accelerators_tpu.analysis import compile_guard as cg

    space = {
        "remat_policy": tune.choice(["none", "nothing", "dots"]),
        "flash_block_q": tune.choice([64, 128] if smoke
                                     else [256, 512, 1024]),
        "gather_mode": tune.choice(["tree", "scan"]),
    }
    default = {"remat_policy": "none",
               "flash_block_q": 128 if smoke else 1024,
               "gather_mode": "tree"}
    c0 = cg.compile_count()
    result = tune.autotune_step(_autotune_measure(smoke), space=space,
                                default_config=default,
                                n_trials=6 if smoke else 10)
    compile_rec = dict(cg.compile_count_record(f"mfu_sweep:{name}"),
                       variant_new_compiles=cg.compile_count() - c0)

    def ms(v):
        # failed measurements are inf; keep the record strict JSON
        # (json.dumps would emit the non-standard Infinity token)
        import math
        return round(v * 1e3, 1) if math.isfinite(v) else None

    return ({"variant": name,
             "step_ms": ms(result["best_step_time_s"]),
             "default_step_ms": ms(result["default_step_time_s"]),
             "speedup_vs_default": (
                 None if result["speedup_vs_default"] is None
                 else round(result["speedup_vs_default"], 3)),
             "best_config": result["best_config"],
             "n_trials": result["n_trials"]},
            compile_rec)


def run_variant(name: str, spec: dict) -> tuple:
    # the measurement itself lives in bench.py so every sweep number is
    # produced under exactly the timed-window/sync discipline the
    # driver's bench uses (bench-honesty: one shared implementation)
    from ray_lightning_accelerators_tpu.analysis import compile_guard as cg

    if spec.get("autotune"):
        return _run_autotuned(name, spec.get("smoke", False))

    from bench import _bench_gpt

    c0 = cg.compile_count()
    rec = _bench_gpt(**spec)
    # compile-count alongside the metric (bench-honesty tie-in): the
    # train step must compile a FIXED program count per variant — a
    # growing number across bench rounds is a retrace regression even
    # when step_ms still looks plausible.  measured_window_compiles in
    # the metric record pins the stronger claim: ZERO of them landed
    # inside the timed window.
    compile_rec = dict(cg.compile_count_record(f"mfu_sweep:{name}"),
                       variant_new_compiles=cg.compile_count() - c0)
    out = {"variant": name, "step_ms": rec["step_ms"], "mfu": rec["mfu"],
           "tokens_per_sec_per_chip": rec["value"],
           "measured_window_compiles": rec["measured_window_compiles"],
           **spec}
    for k in ("gather_mode", "exposed_bytes_per_step",
              "hidden_bytes_per_step"):
        if k in rec:
            out[k] = rec[k]
    return out, compile_rec


def main() -> None:
    names = sys.argv[1:] or ["tuned", "remat-dots"]
    for name in names:
        try:
            metric_rec, compile_rec = run_variant(name, VARIANTS[name])
            print(json.dumps(metric_rec), flush=True)
            print(json.dumps(compile_rec), flush=True)
        except Exception as e:
            print(json.dumps({"variant": name, "error":
                              f"{type(e).__name__}: {e}"[:500]}),
                  flush=True)


if __name__ == "__main__":
    main()
