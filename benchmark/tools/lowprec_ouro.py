#!/usr/bin/env python3
"""The second reading behind the limits of ``drivers/train_looped.py``:
the float32 reference computed with its weights rounded to a lower
precision -- an 8-bit float (e4m3), the nearest below the bfloat16 the
configuration states, and bfloat16 itself for scale -- compared with the
true float32 reference exactly as a run's system is.  The 8-bit reading
has to come out as NOT correct by at least one of the cell's limits.

    chiprun -- python3 benchmark/tools/lowprec_ouro.py <cell> <seed>

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

    from benchmark.drivers import train_looped as driver
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
        seed % (2 ** 31 - 1)))
    def grads(p, t):    # application by application, as the driver
        return reference.loss_and_grads(p, t, model_cfg)[1]

    ref_norms = reference.grad_group_norms(grads(params, tokens[:1]))
    for name, dtype in (("float8_e4m3fn", jnp.float8_e4m3fn),
                        ("bfloat16", jnp.bfloat16)):
        low = jax.tree.map(lambda a: a.astype(dtype).astype(a.dtype), params)
        checks = []
        for i in range(tokens.shape[0]):    # a sequence at a time, as a run
            one = tokens[i:i + 1]
            ref_logits, ref = reference.forward(params, one, model_cfg)
            logits, ours = reference.forward(low, one, model_cfg)
            checks.append(driver.compare(
                ours["loss"], ours["pass_logits_loss"], logits,
                ours["exit_p"], ref_logits, ref))
            del ref_logits, ref, logits, ours
        check = driver.merge(checks)
        check["grad_norm_rel_err"] = driver.grad_norm_errors(
            reference.grad_group_norms(grads(low, tokens[:1])), ref_norms)
        check["ok"] = driver.passes(check)
        print(json.dumps({"weights_rounded_to": name, **check}), flush=True)
        del low


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]))
