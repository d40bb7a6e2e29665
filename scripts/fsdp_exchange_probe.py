"""Compressed-FSDP exchange microbench on a forced-host-platform CPU mesh.

Self-contained: forces ``JAX_PLATFORMS=cpu`` with 8 virtual devices
BEFORE importing jax (the XLA_FLAGS override must land before backend
init), so it runs the same way on any machine and never touches the
chip.  The numbers are honest about what they are: CPU "collectives"
are memcpys, so the headlines are the
analytic BYTES-ON-WIRE reduction of the int8 reduce-scatter + bf16
param all-gather regime vs the fp32 allreduce (the quantity that
transfers to real interconnects) and the MEASURED per-shard peak state
bytes vs a replicated layout (params + Adam moments + error-feedback
residuals, read off the actual device arrays), with fp32/int8/bf16
exchange step times as supporting fields.

Emits one bench.py-shaped JSON line on stdout, with the bench-honesty
compile-count record and the telemetry snapshot printed BEFORE it (the
parser takes the newest value-bearing line).
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

N_REPS = 20


def _per_device_bytes(tree) -> int:
    """Peak state bytes ONE device holds for a pytree of placed arrays
    (sum of its addressable shard sizes — the memory claim FSDP makes)."""
    import jax
    total = 0
    for leaf in jax.tree.leaves(tree):
        shard = leaf.addressable_shards[0]
        total += shard.data.size * shard.data.dtype.itemsize
    return total


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_lightning_accelerators_tpu.parallel import collectives as C
    from ray_lightning_accelerators_tpu.parallel import mesh as mesh_lib
    from ray_lightning_accelerators_tpu.parallel import (
        sharding as sharding_lib)

    mesh = mesh_lib.build_mesh(mesh_lib.MeshConfig(data=1, fsdp=8))
    n = C.dp_size(mesh)
    rng = np.random.default_rng(0)
    # one transformer-block-sized leaf + one bias-sized leaf (the fp32
    # threshold path), stacked per-replica like the train step's local
    # grads
    params = {"w": rng.normal(size=(1024, 1024)).astype(np.float32),
              "b": rng.normal(size=(64,)).astype(np.float32)}
    param_sh = sharding_lib.infer_fsdp_shardings(params, mesh)
    grads = {"w": rng.normal(size=(n, 1024, 1024)).astype(np.float32),
             "b": rng.normal(size=(n, 64)).astype(np.float32)}
    lead = NamedSharding(mesh, P(mesh_lib.BATCH_AXES))
    gd = jax.tree.map(lambda a: jax.device_put(jnp.asarray(a), lead), grads)

    from ray_lightning_accelerators_tpu.analysis import compile_guard as cg

    cg.install()  # count from before the first exchange compiles
    window_compiles = [0]  # compiles landing inside the timed reps

    def timed(fn, *args):
        out = fn(*args)
        jax.block_until_ready(out)  # compile + warmup
        w0 = cg.compile_count()
        t0 = time.perf_counter()
        for _ in range(N_REPS):
            out = fn(*args)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / N_REPS
        window_compiles[0] += cg.compile_count() - w0
        return dt

    results = {}
    for name in ("fp32", "int8", "bf16"):
        cfg = C.ExchangeConfig(mode=None if name == "fp32" else name)
        res = jax.tree.map(lambda a: jax.device_put(a, lead),
                           C.fsdp_residual_zeros(params, param_sh, cfg))
        ex = jax.jit(C.build_fsdp_exchange(mesh, cfg, param_sh))
        results[name] = timed(ex, gd, res)

    # per-shard peak state bytes, measured off REAL placed arrays:
    # sharded params + Adam moments + shard-local residuals vs the same
    # state fully replicated
    cfg8 = C.ExchangeConfig(mode="int8")
    repl = NamedSharding(mesh, P())
    tx = optax.adam(1e-3)
    pd = jax.tree.map(lambda a, s: jax.device_put(jnp.asarray(a), s),
                      params, param_sh)
    opt = optax.tree_map_params(
        tx, lambda s, p_sh: jax.device_put(s, p_sh), tx.init(params),
        param_sh, transform_non_params=lambda s: jax.device_put(s, repl))
    res8 = jax.tree.map(lambda a: jax.device_put(a, lead),
                        C.fsdp_residual_zeros(params, param_sh, cfg8))
    sharded_bytes = (_per_device_bytes(pd) + _per_device_bytes(opt)
                     + _per_device_bytes(res8))
    pr = jax.tree.map(lambda a: jax.device_put(jnp.asarray(a), repl),
                      params)
    opt_r = optax.adam(1e-3).init(pr)
    res_r = jax.tree.map(
        lambda a: jax.device_put(a, lead),
        C.residual_zeros(params, n, cfg8))
    replicated_bytes = (_per_device_bytes(pr) + _per_device_bytes(opt_r)
                        + _per_device_bytes(res_r))

    wire = C.wire_bytes_per_step(params, n, cfg8, param_shardings=param_sh)
    record = {
        "metric": "fsdp_exchange_int8_wire_bytes_reduction",
        "value": wire["compression_ratio"],
        "unit": "x",
        "regime": wire["regime"],
        "fp32_step_ms": round(results["fp32"] * 1e3, 2),
        "int8_step_ms": round(results["int8"] * 1e3, 2),
        "bf16_step_ms": round(results["bf16"] * 1e3, 2),
        "bytes_fp32_per_step": wire["baseline_fp32_bytes_per_step"],
        "bytes_int8_per_step": wire["exchange_bytes_per_step"],
        "grad_reduce_scatter_bytes": wire[
            "grad_reduce_scatter_bytes_per_step"],
        "param_allgather_bytes": wire["param_allgather_bytes_per_step"],
        "per_shard_state_bytes": sharded_bytes,
        "replicated_state_bytes": replicated_bytes,
        "per_shard_state_fraction": round(
            sharded_bytes / replicated_bytes, 4),
        "devices": n,
        "fsdp": wire.get("fsdp"),
        "platform": "cpu-forced-host",
        "note": "CPU collectives are memcpys; wire-bytes ratio and "
                "per-shard peak bytes are the transferable claims, step "
                "times are CPU-local context",
        # fp32 RS+AG moves the same bytes as a ring allreduce; report
        # the achieved fraction of the ~2.65x int8-RS + bf16-AG ideal
        "vs_baseline": round(wire["compression_ratio"] / 2.65, 3),
    }
    # bench-honesty tie-in: nonzero timed-window compiles = a retrace
    # landed inside a measured rep and the step times above are polluted.
    # Printed BEFORE the metric record: bench.py takes the newest
    # value-bearing JSON line of probe stdout as the bench result.
    compile_rec = dict(cg.compile_count_record("fsdp_exchange"),
                       measured_window_compiles=window_compiles[0])
    print(json.dumps(compile_rec), flush=True)
    # unified telemetry snapshot (telemetry/registry.py): value-less and
    # kind-tagged, printed before the metric so the newest value-bearing
    # line stays the bench result either way
    from ray_lightning_accelerators_tpu.telemetry import (
        probe_snapshot_record)
    print(json.dumps(probe_snapshot_record("fsdp_exchange")), flush=True)
    print(json.dumps(record), flush=True)


if __name__ == "__main__":
    main()
