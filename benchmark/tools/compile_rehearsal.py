#!/usr/bin/env python3
"""Compile a cell's programs for a DESCRIBED v5e:2x2 (no chip attached).

    JAX_PLATFORMS=cpu python benchmark/tools/compile_rehearsal.py train \
        --config gpt2-medium --chips 1 --batch 4 8
    JAX_PLATFORMS=cpu python benchmark/tools/compile_rehearsal.py train \
        --config gpt2-xl --chips 4 --fsdp --remat --batch 2 4 8
    JAX_PLATFORMS=cpu python benchmark/tools/compile_rehearsal.py serve \
        --config gpt2-medium --slots 32

Prints what the TPU compiler reports per device (argument, temporary and
aliased bytes; the collectives and Pallas kernels in the program): sizes
and names only, never a time.  It reaches into the Trainer the way
``tests/test_tpu_compile.py`` does -- a described device cannot hold an
array, so every operand is a shape.  A rehearsal aid, not part of any
measurement.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def _report(what, lowered):
    text = lowered.as_text()
    kernels = sorted(set(re.findall(r'kernel_name = "([^"]+)"', text)))
    try:
        compiled = lowered.compile()
    except Exception as e:  # the compiler's refusal is the result
        print(json.dumps({"what": what, "compiles": False,
                          "error": str(e).splitlines()[0][:400]}), flush=True)
        return None
    m = compiled.memory_analysis()
    hlo = compiled.as_text()
    collectives = {op: len(re.findall(rf"\b{op}(?:-start)?\(", hlo))
                   for op in ("all-gather", "reduce-scatter", "all-reduce",
                              "all-to-all", "collective-permute")}
    print(json.dumps({
        "what": what, "compiles": True, "kernels": kernels,
        "argument_bytes": m.argument_size_in_bytes,
        "temp_bytes": m.temp_size_in_bytes,
        "alias_bytes": m.alias_size_in_bytes,
        "output_bytes": m.output_size_in_bytes,
        "per_device_bytes": (m.argument_size_in_bytes + m.output_size_in_bytes
                             + m.temp_size_in_bytes - m.alias_size_in_bytes),
        "collectives": {k: v for k, v in collectives.items() if v},
    }), flush=True)
    return compiled


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("what", choices=("train", "serve"))
    ap.add_argument("--config", required=True)
    ap.add_argument("--chips", type=int, default=1)
    ap.add_argument("--batch", type=int, nargs="+", default=[4])
    ap.add_argument("--fsdp", action="store_true")
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--slots", type=int, default=32)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark.lib import cells

    jax.config.update("jax_enable_compilation_cache", False)
    jax.default_backend = lambda: "tpu"  # steer the package's dispatch
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    devices = list(topo.devices[:args.chips])
    config = cells.load_config(args.config)

    def sds(s, sharding):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)

    if args.what == "train":
        from ray_lightning_accelerators_tpu import RayTPUAccelerator, Trainer
        from ray_lightning_accelerators_tpu.core.state import TrainState
        from ray_lightning_accelerators_tpu.runtime import guardian
        from ray_lightning_accelerators_tpu.utils.seed import rng_from_seed

        for per_chip in args.batch:
            module = cells.build_model(config, {
                "remat": args.remat, "flash_block": 1024, "loss_chunk_rows": 2048})
            trainer = Trainer(
                max_epochs=1, precision="bf16", enable_checkpointing=False,
                seed=0, accelerator=RayTPUAccelerator(
                    num_workers=len(devices), use_fsdp=args.fsdp,
                    devices=devices))
            trainer.module, module.trainer = module, trainer
            module.compute_dtype = trainer.compute_dtype
            trainer._mesh = trainer.accelerator.build_mesh()
            trainer._tx = trainer._build_tx(module)

            def make_state():
                init_rng, state_rng = jax.random.split(rng_from_seed(0))
                state = TrainState.create(module.init_params(init_rng),
                                          trainer._tx, state_rng)
                return state.replace(
                    guard_ema=jnp.asarray(guardian.fresh_state()))

            state = jax.eval_shape(make_state)
            seq = config["model"]["max_seq_len"]
            batch = jax.ShapeDtypeStruct((per_chip * len(devices), seq),
                                         jnp.int32)
            trainer._compile(module, state, batch)
            state = jax.tree.map(sds, state, trainer._state_shardings)
            batch = sds(batch, trainer._batch_sharding)
            _report(f"train_step {args.config} chips={len(devices)} "
                    f"per_chip_batch={per_chip} fsdp={args.fsdp} "
                    f"remat={args.remat}",
                    trainer._train_step_fn.lower(state, batch))
    else:
        from ray_lightning_accelerators_tpu.serve import ServeEngine

        one = SingleDeviceSharding(devices[0])
        model = cells.build_model(config, {})
        model.compute_dtype = jnp.bfloat16
        shapes = jax.eval_shape(lambda: jax.tree.map(
            lambda p: p.astype(jnp.bfloat16),
            model.init_params(jax.random.PRNGKey(0))))
        engine = ServeEngine(model, jax.tree.map(
            lambda s: np.zeros((1,), s.dtype), shapes), max_slots=args.slots,
            max_total_len=config["model"]["max_seq_len"])
        params = jax.tree.map(lambda s: sds(s, one), shapes)
        pool = jax.tree.map(lambda s: sds(s, one), jax.eval_shape(
            lambda: model.paged_cache_alloc(engine.n_blocks, engine.block_len)))

        def i32(*shape):
            return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one)

        B, M = engine.max_slots, engine.table_blocks
        _report(f"decode_step {args.config} slots={B}",
                engine._step.lower(params, pool, i32(B, M), i32(B), i32(B)))
        for bucket in (engine.block_len, engine._chunk_blocks * engine.block_len):
            _report(f"prefill_chunk {args.config} bucket={bucket}",
                    engine._chunk_prefill_fn(bucket).lower(
                        params, pool, i32(M), i32(1, bucket), i32(), i32()))


if __name__ == "__main__":
    main()
