#!/usr/bin/env python3
"""The second reading behind the limits of ``drivers/train_nemotron_h.py``:
the float32 reference computed with its weights rounded to a lower
precision -- an 8-bit float (e4m3), the nearest below the bfloat16 the
configuration states, and bfloat16 itself for scale -- compared with the
true float32 reference exactly as a run's system is.  The 8-bit reading
has to come out as NOT correct by at least one of the cell's limits.

    chiprun -- python3 benchmark/tools/lowprec_nemotron_h.py <cell> <seed>

A measuring aid on the chip (the reference at the cell's sizes does not
fit a CPU run's patience); no part of any run.
"""
from __future__ import annotations

import importlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(workload: str, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from benchmark.drivers import train_nemotron_h as driver
    from benchmark.drivers.train import CHECK_SEQUENCES
    from benchmark.lib import cells, traffic

    w = cells.load_workload(workload)
    config, mix = cells.load_config(w["config"]), cells.load_traffic(
        w["traffic"])
    model_cfg, settings = config["model"], w["settings"]
    reference = importlib.import_module("benchmark.lib." + config["reference"])
    seq = int(mix["sequence_tokens"])
    n = int(settings["per_chip_batch"]) * int(settings["steps_per_epoch"])
    tokens = jnp.asarray(traffic.train_tokens(
        mix, seed, n + CHECK_SEQUENCES, seq,
        model_cfg["vocab_size"])[-CHECK_SEQUENCES:])
    model = cells.build_model(config, settings)
    params = jax.jit(model.init_params)(jax.random.PRNGKey(
        int(settings.get("weights_seed", seed % (2 ** 31 - 1)))))
    ref_logits, routing = reference.forward(params, tokens, model_cfg)
    ref_loss = reference.lm_loss(ref_logits, tokens)
    grads = jax.jit(lambda p, t: reference.loss_and_grads(
        p, t, model_cfg)[1])
    ref_norms = reference.grad_group_norms(grads(params, tokens[:1]))
    for name, dtype in (("float8_e4m3fn", jnp.float8_e4m3fn),
                        ("bfloat16", jnp.bfloat16)):
        low = jax.tree.map(lambda a: a.astype(dtype).astype(a.dtype), params)
        logits, low_routing = reference.forward(low, tokens, model_cfg)
        check = driver.compare(
            reference.lm_loss(logits, tokens), logits,
            low_routing["selected"], ref_loss, ref_logits, routing,
            held=tuple(model_cfg["moe_experts_held"]))
        norms = reference.grad_group_norms(grads(low, tokens[:1]))
        check["grad_norm_rel_err"] = {
            k: abs(norms[k] - ref_norms[k]) / ref_norms[k]
            if ref_norms[k] > 0 else float(norms[k] != 0) for k in norms}
        check["ok"] = driver.passes(check)
        print(json.dumps({"weights_rounded_to": name, **check}), flush=True)
        del low, logits, low_routing


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]))
