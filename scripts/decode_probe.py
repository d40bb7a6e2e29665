"""Decode int8 kernel microbench: isolates WHERE the int8 speedup lives
(or dies) on the real chip, one JSON line per probe.

bench.py's decode `int8_ratio` measures the whole generate loop; when it
lands near 1.0 this script says why, by timing the two layers of the
stack separately on the exact bench decode shapes:

1. ``kernel``  — `ops/quant.int8_matmul` vs the XLA dequant dot vs a
   plain bf16 dot on one [16, 768] @ [768, 768] decode matmul (the
   qkv/out shape) and the [16, 768] @ [768, 50304] unembed: pure
   kernel-vs-XLA, no scan.
2. ``scanned`` — the same matmuls inside a `lax.scan` over a 12-layer
   stacked weight tree (decode's actual access pattern: a stream of
   weight matrices through one small activation block).  Its
   ``kernel_int8_gbps`` / ``bf16_gbps`` fields ARE the per-dtype
   effective stream rates on this pattern -- if the int8 rate matches
   bf16's BYTE rate, the kernel pipeline is the bottleneck, not HBM.

Sync discipline per bench-honesty rules: chain reps, one scalar
readback at the end; per-call sync would bill dispatch latency to
bandwidth.
"""

import functools
import json
import sys
import time

import os

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _timed(fn, *args, reps=20):
    import jax
    import numpy as np

    out = fn(*args)
    jax.tree.leaves(out)[0].block_until_ready()
    np.asarray(jax.tree.leaves(out)[0])  # honest sync: host readback
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        acc = None
        for _ in range(reps):
            acc = fn(*args)
        np.asarray(jax.tree.leaves(acc)[0])
        best = min(best, (time.perf_counter() - t0) / reps)
    return best


def probe_kernel(m, k, n, interpret=False) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_lightning_accelerators_tpu.ops import quant

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(m, k)), jnp.bfloat16)
    wq = jnp.asarray(rng.integers(-127, 128, size=(k, n)), jnp.int8)
    scale = jnp.asarray(rng.uniform(0.01, 0.1, size=(n,)), jnp.float32)

    kern = jax.jit(functools.partial(quant.int8_matmul,
                                     interpret=interpret))
    deq = jax.jit(lambda x, wq, s:
                  x @ (wq.astype(jnp.bfloat16) * s[None, :].astype(
                      jnp.bfloat16)))
    bf16_w = (wq.astype(jnp.bfloat16) * scale[None, :].astype(
        jnp.bfloat16))
    plain = jax.jit(lambda x, w: x @ w)

    t_kernel = _timed(kern, x, wq, scale)
    t_dequant = _timed(deq, x, wq, scale)
    t_bf16 = _timed(plain, x, bf16_w)
    return {"probe": "kernel", "shape": [m, k, n],
            "kernel_us": round(t_kernel * 1e6, 1),
            "xla_dequant_us": round(t_dequant * 1e6, 1),
            "bf16_us": round(t_bf16 * 1e6, 1),
            "kernel_vs_bf16": round(t_bf16 / t_kernel, 3),
            "int8_bytes_over_bf16": 0.5}


def probe_scanned(m=16, d=768, layers=12, interpret=False) -> dict:
    """Decode's real pattern: scan one activation block through a
    stacked weight tree, q8-kernel vs XLA dequant vs plain bf16."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_lightning_accelerators_tpu.ops import quant

    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(m, d)), jnp.bfloat16)
    wq_stack = jnp.asarray(rng.integers(-127, 128, size=(layers, d, d)),
                           jnp.int8)
    sc_stack = jnp.asarray(rng.uniform(0.01, 0.1, size=(layers, d)),
                           jnp.float32)
    wbf_stack = (wq_stack.astype(jnp.bfloat16)
                 * sc_stack[:, None, :].astype(jnp.bfloat16))

    @jax.jit
    def scan_kernel(x, wq, sc):
        def body(h, ws):
            w, s = ws
            return quant.int8_matmul(h, w, s,
                                     interpret=interpret), ()
        out, _ = jax.lax.scan(body, x, (wq, sc))
        return out.astype(jnp.float32).sum()

    @jax.jit
    def scan_dequant(x, wq, sc):
        def body(h, ws):
            w, s = ws
            wf = w.astype(jnp.bfloat16) * s[None, :].astype(jnp.bfloat16)
            return (h @ wf).astype(jnp.bfloat16), ()
        out, _ = jax.lax.scan(body, x, (wq, sc))
        return out.astype(jnp.float32).sum()

    @jax.jit
    def scan_bf16(x, w):
        def body(h, wl):
            return (h @ wl).astype(jnp.bfloat16), ()
        out, _ = jax.lax.scan(body, x, w)
        return out.astype(jnp.float32).sum()

    t_kernel = _timed(scan_kernel, x, wq_stack, sc_stack)
    t_dequant = _timed(scan_dequant, x, wq_stack, sc_stack)
    t_bf16 = _timed(scan_bf16, x, wbf_stack)
    int8_bytes = wq_stack.nbytes
    return {"probe": "scanned", "layers": layers, "d": d, "m": m,
            "kernel_ms": round(t_kernel * 1e3, 2),
            "xla_dequant_ms": round(t_dequant * 1e3, 2),
            "bf16_ms": round(t_bf16 * 1e3, 2),
            "kernel_vs_bf16": round(t_bf16 / t_kernel, 3),
            "kernel_int8_gbps": round(int8_bytes / t_kernel / 1e9, 1),
            "bf16_gbps": round(2 * int8_bytes / t_bf16 / 1e9, 1)}


def main() -> None:
    from ray_lightning_accelerators_tpu.analysis import compile_guard as cg

    cg.install()
    interpret = "--interpret" in sys.argv
    for fn in (lambda: probe_kernel(16, 768, 768, interpret),
               lambda: probe_kernel(16, 768, 50304, interpret),
               lambda: probe_scanned(interpret=interpret)):
        try:
            c0 = cg.compile_count()
            rec = fn()
            print(json.dumps(rec), flush=True)
            print(json.dumps(dict(
                cg.compile_count_record("decode"),
                probe_new_compiles=cg.compile_count() - c0)), flush=True)
        except Exception as e:
            print(json.dumps({"error": f"{type(e).__name__}: {e}"[:400]}),
                  flush=True)


if __name__ == "__main__":
    main()
