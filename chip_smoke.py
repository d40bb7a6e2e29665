#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls, at the
full width of the 124M GPT (``bench.py``'s model: vocab 50304, d_model
768, 12 heads, d_ff 3072, 12 layers, seq 1024, bf16, fused loss, flash
attention), with random weights from ``--seed``:

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # four chips: only the sharded fits

One chip, in order -- a failed phase raises, the run exits non-zero and
prints no result:

1. *device*   a TPU or nothing: there is no CPU branch.
2. *kernels*  the compiled Pallas kernels (flash attention forward and
              ``jax.grad`` through it, rms/layer norm, the decode int8
              matmuls) against their references at the model's widths;
              the lowered public op must hold the kernel's TPU custom
              call, so a dispatch that slid to the reference cannot pass.
              Then the benchmark cells' own flash shape (64 heads of
              [1024, 64], one 1024^2 causal block): checked, and the
              forward's and backward's microseconds per head printed.
              Then the ``train-lfm2-moe-8k`` cell's two operators at its
              shapes (the dropless expert layer: 32,768 rows, 8 of 32
              experts held, 2048 x 1792; the gated short convolution at
              [4, 8192, 2048]) against the float32 reference, forward
              and backward, rows and milliseconds per call printed.
              Then the ``train-nemotron3-ssm-8k`` cell's two (the Mamba-2
              mixer, 16 heads of 64 with state 128 in chunks of 128, and
              the latent expert layer, 8 of 512 experts held, top-22, at
              [1, 8192, 4096]) the same way.  Then the
              ``train-ouro-loop-8k`` cell's looped stack (6 sandwich-norm
              layers run 4 times on the same weights at [1, 8192, 2048]).
3. *train*    ``Trainer.fit(GPT, DataLoader)``: finite, falling loss,
              zero compiles in the second epoch, the flash forward and
              backward kernels in the train step's lowering, peak HBM.
4. *generate* greedy ``generate`` at batch 16 with bf16 and with
              ``quantize_weights`` int8 weights on the compiled q8 path.
5. *serve*    the paged ``ServeEngine`` (donation on) answers mixed-length
              requests, half sharing a system prompt, submitted while
              others decode; every response against standalone
              ``generate``.

``--chips 4`` runs only the same GPT fitted on one device, on a
four-device data-parallel mesh, under FSDP and under the int8 +
scan-gather compressed FSDP exchange, comparing loss trajectories and
where the shards sit.

One process: a chip belongs to one process at a time.  Earlier lines are
JSON records of whatever is useful (errors, step time, tokens/s, HBM) --
observed, not benchmarked; the LAST line of stdout is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import functools
import json
import os
import re
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")

# normalized max error |a - ref|_max / |ref|_max of a bf16-output kernel
# against its f32 reference: bf16 rounds at 2^-8 = 3.9e-3, so a working
# kernel sits well under these and a broken one is O(1)
TOL_FLASH_FWD = 2e-2
TOL_FLASH_GRAD = 4e-2
TOL_NORM = 1e-2
TOL_INT8 = 1e-2
# the LFM2 cell's operators, bf16 against float32, forward and VJP: three
# chained matmuls and a gate round like the flash gradients do
TOL_LFM2 = 4e-2
# the looped stack of the Ouro cell, bf16 against float32: 24 layer
# applications compound what one rounds (on the chip at the cell's
# shapes: forward 0.019, the weights' gradients 0.023-0.040; PR 33)
TOL_OURO = 1e-1
# two correct bf16 programs for the same greedy decode (cached vs
# re-forward, paged vs dense) can disagree where the top two logits
# nearly tie; a chosen token may trail the re-forward argmax by at most
# this many standard deviations of its logit row (a wrong cache or
# position is off by whole deviations)
TIE_TOL = 0.1
# per-step loss, sharded fit vs the one-device fit (bf16 compute, other
# reduction order); and the repo's int8-exchange bound on the final loss
# (tests/test_fsdp_exchange.py, tests/test_overlap_gather.py)
TOL_TRAJECTORY = 1e-2
TOL_INT8_EXCHANGE = 2e-2


@dataclasses.dataclass(frozen=True)
class Size:
    """Model widths and workload.  The default is the real thing; tests
    rehearse the control flow on the CPU mesh with a tiny one."""
    vocab_size: int = 50304
    d_model: int = 768
    n_heads: int = 12
    d_ff: int = 3072
    n_layers: int = 12
    seq: int = 1024
    batch: int = 16             # global train batch == generate batch
    steps_per_epoch: int = 8
    flash_block: int = 1024     # bench_gpt's tuned step shape
    loss_chunk: int = 2048
    gen_prompt: int = 128
    gen_new: int = 32
    serve_system: int = 64      # shared system prompt (whole blocks)
    serve_family: tuple = (16, 16, 40, 40)   # suffixes after it
    serve_others: tuple = (24, 57, 150, 57)  # 150 streams chunk by chunk
    serve_new: int = 24


class SmokeFailure(AssertionError):
    pass


def require(cond, message: str) -> None:
    if not cond:
        raise SmokeFailure(message)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def kernels_in(lowered) -> list:
    """Pallas kernels whose TPU custom call a lowering holds."""
    text = lowered.as_text()
    names = sorted(set(re.findall(r'kernel_name = "([^"]+)"', text)))
    require(not names or "tpu_custom_call" in text,
            "kernel names without a tpu_custom_call in the lowering")
    return names


def _rel_err(out, ref) -> float:
    import numpy as np
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    require(bool(np.isfinite(out).all()), "non-finite kernel output")
    return float(np.max(np.abs(out - ref)) / max(np.max(np.abs(ref)), 1e-30))


def _model_config(size: Size):
    from ray_lightning_accelerators_tpu.models.transformer import (
        TransformerConfig)
    return TransformerConfig(
        vocab_size=size.vocab_size, d_model=size.d_model,
        n_heads=size.n_heads, d_ff=size.d_ff, n_layers=size.n_layers,
        max_seq_len=size.seq, fused_loss=True,
        loss_chunk_rows=size.loss_chunk, flash_block_q=size.flash_block,
        flash_block_k=size.flash_block)


def _tokens(size: Size, seed: int):
    """Seeded synthetic tokens with Zipf-like unigram statistics: there
    is something to learn in a few steps, so the loss visibly falls
    (uniform tokens sit at ln(vocab) from the first step)."""
    import numpy as np
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, size.vocab_size + 1)
    return rng.choice(size.vocab_size, p=p / p.sum(),
                      size=(size.batch * size.steps_per_epoch, size.seq)
                      ).astype(np.int32)


# --------------------------------------------------------------------- #
# 1. device                                                              #
# --------------------------------------------------------------------- #
def phase_device(chips: int) -> dict:
    import importlib.metadata as md

    import jax

    from ray_lightning_accelerators_tpu import native
    from ray_lightning_accelerators_tpu.utils import compile_cache

    cache_dir = compile_cache.enable()
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        sys.exit(f"chip_smoke: no TPU (jax.devices()[0] is {devices[0]!r}); "
                 "this script has no CPU branch")
    if chips == 4 and device["count"] != 4:
        sys.exit(f"chip_smoke: --chips 4 needs four chips, found "
                 f"{device['count']}")
    emit("device", **device, jax=jax.__version__,
         jaxlib=md.version("jaxlib"), libtpu=md.version("libtpu"),
         compile_cache_dir=cache_dir,
         compile_cache_from_env=bool(os.environ.get(compile_cache.ENV_VAR)),
         native_engine=native.available())
    return device


def _check_flash(shape, blocks, keys):
    """Compiled causal flash attention, forward and ``jax.grad``, against
    the f32 reference at ``shape`` under each grid block.  Returns the
    bf16 (q, k, v) and the f32 cotangent it drew."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_accelerators_tpu.ops.attention import (
        attention_reference, flash_attention)

    q, k, v = (jax.random.normal(next(keys), shape, jnp.bfloat16)
               for _ in range(3))
    g = jax.random.normal(next(keys), shape, jnp.float32)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]

    def ref_loss(q, k, v):
        return (attention_reference(q, k, v, causal=True) * g).sum()

    with jax.default_matmul_precision("highest"):
        ref_out = jax.jit(functools.partial(attention_reference,
                                            causal=True))(*f32)
        ref_grads = jax.jit(jax.grad(ref_loss, argnums=(0, 1, 2)))(*f32)

    for block in blocks:
        fwd = jax.jit(functools.partial(flash_attention, causal=True,
                                        block_q=block, block_k=block))

        def loss(q, k, v):
            out = flash_attention(q, k, v, True, None, block, block)
            return (out.astype(jnp.float32) * g).sum()

        bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        fwd_kernels = kernels_in(fwd.lower(q, k, v))
        bwd_kernels = kernels_in(bwd.lower(q, k, v))
        require(fwd_kernels == ["flash_fwd"],
                f"flash forward dispatched to {fwd_kernels or 'reference'}")
        require(any(n.startswith("flash_bwd") for n in bwd_kernels),
                f"flash backward dispatched to {bwd_kernels}")
        err_fwd = _rel_err(fwd(q, k, v), ref_out)
        err_bwd = [_rel_err(a, b) for a, b in zip(bwd(q, k, v), ref_grads)]
        emit("kernels", kernel="flash_attention", shape=list(shape),
             block=block, kernels=bwd_kernels, err_fwd=err_fwd,
             err_dq_dk_dv=err_bwd)
        require(err_fwd <= TOL_FLASH_FWD, f"flash fwd error {err_fwd}")
        require(max(err_bwd) <= TOL_FLASH_GRAD,
                f"flash grad error {err_bwd}")
    return q, k, v, g


# --------------------------------------------------------------------- #
# 2. kernels                                                             #
# --------------------------------------------------------------------- #
def phase_kernels(size: Size, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from ray_lightning_accelerators_tpu.ops import quant
    from ray_lightning_accelerators_tpu.ops.norms import (
        layer_norm, layer_norm_reference, rms_norm, rms_norm_reference)

    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    head_dim = size.d_model // size.n_heads
    _check_flash((max(1, size.batch // 2), size.n_heads, size.seq, head_dim),
                 sorted({min(512, size.seq), size.flash_block}), keys)

    rows = size.batch * size.seq // 2
    x = jax.random.normal(next(keys), (rows, size.d_model),
                          jnp.bfloat16) * 3.0
    scale = jnp.linspace(0.5, 1.5, size.d_model)
    bias = jnp.linspace(-1.0, 1.0, size.d_model)
    for name, op, ref, args in (
            ("rms_norm", rms_norm, rms_norm_reference, (x, scale)),
            ("layer_norm", layer_norm, layer_norm_reference,
             (x, scale, bias))):
        fn = jax.jit(op)
        names = kernels_in(fn.lower(*args))
        require(len(names) == 1, f"{name} dispatched to {names}")
        ref_out = ref(args[0].astype(jnp.float32), *args[1:])
        err = _rel_err(fn(*args), ref_out)
        emit("kernels", kernel=name, shape=[rows, size.d_model],
             kernels=names, err=err)
        require(err <= TOL_NORM, f"{name} error {err}")

    d, f, m = size.d_model, size.d_ff, size.batch
    for kk, n in ((d, d), (d, f), (f, d)):
        xr = jax.random.normal(next(keys), (m, kk), jnp.bfloat16)
        wq = jax.random.randint(next(keys), (kk, n), -127, 128, jnp.int8)
        sc = jax.random.uniform(next(keys), (n,), jnp.float32, 0.002, 0.02)
        names = kernels_in(quant.int8_matmul.lower(xr, wq, sc))
        require(names == ["q8_matmul"], f"int8_matmul lowered {names}")
        with jax.default_matmul_precision("highest"):
            ref_out = xr.astype(jnp.float32) @ (
                wq.astype(jnp.float32) * sc[None, :])
        err = _rel_err(quant.int8_matmul(xr, wq, sc), ref_out)
        emit("kernels", kernel="int8_matmul", shape=[m, kk, n], err=err)
        require(err <= TOL_INT8, f"int8_matmul {m}x{kk}x{n} error {err}")
    xr = jax.random.normal(next(keys), (m, d), jnp.bfloat16)
    wq = jax.random.randint(next(keys), (size.vocab_size, d), -127, 128,
                            jnp.int8)
    names = kernels_in(quant.int8_matmul_nt.lower(xr, wq))
    require(names == ["q8_matmul_nt"], f"int8_matmul_nt lowered {names}")
    with jax.default_matmul_precision("highest"):
        ref_out = xr.astype(jnp.float32) @ wq.astype(jnp.float32).T
    err = _rel_err(quant.int8_matmul_nt(xr, wq), ref_out)
    emit("kernels", kernel="int8_matmul_nt", shape=[m, d, size.vocab_size],
         err=err)
    require(err <= TOL_INT8, f"int8_matmul_nt error {err}")


def phase_flash_cell_shape(seed: int, iters: int = 100) -> None:
    """The benchmark cells' kernel: batch x heads = 64 (``gpt2-medium``
    at batch 4) heads of [1024, 64] in bf16 under one 1024^2 causal
    block.  Checked like the others, then timed: device time per head of
    the forward kernel and of the backward (delta pre-pass and fused
    kernel), from ``iters`` chained calls in one program."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_accelerators_tpu.ops.attention import (
        causal_tiles, flash_attention)

    shape, block = (4, 16, 1024, 64), 1024
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 4))
    q, k, v, g = _check_flash(shape, [block], keys)
    attend = functools.partial(flash_attention, causal=True, block_q=block,
                               block_k=block)

    g = g.astype(q.dtype)
    # each call's output is the next call's q: nothing hoists or folds
    chains = {
        "fwd": lambda q: attend(q, k, v),
        "fwd_bwd": lambda q: jax.vjp(attend, q, k, v)[1](g)[0],
    }
    seconds = {}
    for name, step in chains.items():
        chain = jax.jit(lambda q, step=step: jax.lax.fori_loop(
            0, iters, lambda _, x: step(x), q))
        chain(q).block_until_ready()
        runs = []
        for _ in range(5):
            t0 = time.perf_counter()
            chain(q).block_until_ready()
            runs.append(time.perf_counter() - t0)
        seconds[name] = min(runs)
    per_head = 1e6 / (iters * shape[0] * shape[1])
    visited, total = causal_tiles(shape[2], shape[2], block, block, True)
    emit("flash_cell_shape", shape=list(shape), block=block,
         tiles_visited=visited, tiles_total=total,
         fwd_us_per_head=seconds["fwd"] * per_head,
         bwd_us_per_head=(seconds["fwd_bwd"] - seconds["fwd"]) * per_head)


def _operator_check(phase, name, system, reference, params, *, x, g, iters,
                    on_chip, tol=TOL_LFM2, **fields):
    """``system`` / ``reference``: (params, x) -> y.  Compared with their
    VJPs at cotangent ``g`` on the first sequence, then the system timed
    on the batch; one record under ``phase``."""
    import jax
    import jax.numpy as jnp

    def timed(fn, *args):
        jax.block_until_ready(fn(*args))
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters * 1e3

    fwd = jax.jit(system)
    both = jax.jit(lambda p, x_, g_: jax.vjp(system, p, x_)[1](g_))
    if on_chip:
        names = kernels_in(fwd.lower(params, x))
        require("moe" not in name or names,
                "the expert layer lowered without its kernel")
        fields["kernels"] = names
    y, vjp = jax.vjp(reference, params, x[:1])
    errs = {"fwd": _rel_err(fwd(params, x[:1]), y)}
    got, want = both(params, x[:1], g[:1]), vjp(g[:1])
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(
            got)[0], jax.tree.leaves(want)):
        if float(jnp.max(jnp.abs(b))) > 0:      # a buffer has none
            errs["d" + jax.tree_util.keystr(path)] = _rel_err(a, b)
    emit(phase, op=name, shape=list(x.shape), errs=errs,
         fwd_ms=timed(fwd, params, x),
         fwd_bwd_ms=timed(both, params, x, g), **fields)
    worst = max(errs.values())
    require(worst <= tol, f"{name}: error {worst} ({errs})")


def phase_lfm2_cell_shapes(seed: int, *, batch: int = 4, seq: int = 8192,
                           d_model: int = 2048, expert_width: int = 1792,
                           experts: int = 32, held: int = 8, top_k: int = 4,
                           iters: int = 5, on_chip: bool = True) -> None:
    """The ``train-lfm2-moe-8k`` cell's two new operators at its shapes
    (32,768 rows into 8 held experts of 32, each 2048 x 1792, top-4; the
    gated short convolution at [4, 8192, 2048]) against the plain
    float32 reference, forward and backward.  The comparison runs on one
    sequence (the reference's float32 intermediates of four do not fit
    beside it), the timing on the whole batch; both get float32 inputs,
    so the router (float32, full precision on both sides) chooses alike
    and the difference is bfloat16 arithmetic alone.  The expert layer
    runs twice: as drawn (one window of its sorted pairs holds every
    routed row) and crowded onto two held experts (two windows).  Prints
    rows and milliseconds per call."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_accelerators_tpu.models import reference_lfm2 as ref
    from ray_lightning_accelerators_tpu.ops import moe
    from ray_lightning_accelerators_tpu.ops.conv import gated_short_conv

    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 2), 8))
    ids = tuple(range(held))
    model = {"num_experts": experts, "moe_top_k": top_k,
             "moe_norm_topk": True, "moe_routed_scale": 1.0}
    x = jax.random.normal(next(keys), (batch, seq, d_model), jnp.float32)
    g = jax.random.normal(next(keys), x.shape, jnp.float32)
    check = functools.partial(_operator_check, "lfm2_cell_shapes", x=x, g=g,
                              iters=iters, on_chip=on_chip)

    p = moe.init_dropless_params(next(keys), d_model, expert_width, experts,
                                 held)
    # a selection bias that sends every token to two held experts: the
    # rows pass one window of the sorted pairs, so the loop behind
    # window 0 compiles and runs on the chip too
    crowded = {**p, "expert_bias": p["expert_bias"].at[:2].add(2.0)}
    window = moe.window_rows(batch * seq * top_k, held, experts)

    def layer(p_, x_):
        return moe.dropless_moe(x_, p_, top_k=top_k, held=ids,
                                num_experts=experts)

    counters = jax.jit(lambda p_, x_: layer(p_, x_)[1])
    for name, p_, at_the_cell in (("dropless_moe", p, 1),
                                  ("dropless_moe_overflow", crowded, 2)):
        stats = counters(p_, x)
        rounds = max(1, -(-int(stats["rows_routed"]) // window))
        require(float(stats["rows_routed"]) == float(stats["rows_computed"])
                and float(stats["rounds"]) == rounds,
                f"{name}: a routed row was not computed, or not in "
                f"{rounds} window(s): {stats}")
        require(not on_chip or rounds == at_the_cell,
                f"{name}: {rounds} window(s) at the cell's shapes")
        check(name, lambda p_, x_: layer(p_, x_)[0],
              lambda p_, x_: ref.sparse_block(x_, p_, model, ids)[0], p_,
              rows_in=batch * seq, rows_routed=float(stats["rows_routed"]),
              rounds=float(stats["rounds"]),
              load_max_over_mean=float(stats["load_max_over_mean"]))

    c = {"w_in": jax.random.normal(next(keys), (d_model, 3 * d_model))
         * d_model ** -0.5,
         "conv_w": jax.random.normal(next(keys), (d_model, 3)) * 3 ** -0.5,
         "w_out": jax.random.normal(next(keys), (d_model, d_model))
         * d_model ** -0.5}
    check("gated_short_conv",
          lambda c_, x_: gated_short_conv(
              x_.astype(jnp.bfloat16), c_["w_in"].astype(jnp.bfloat16),
              c_["conv_w"], c_["w_out"].astype(jnp.bfloat16)
              ).astype(jnp.float32),
          lambda c_, x_: ref.conv_operator(x_, c_), c)


def phase_nemotron_cell_shapes(seed: int, *, batch: int = 1, seq: int = 8192,
                               d_model: int = 4096, ssm_heads: int = 16,
                               ssm_head_dim: int = 64, ssm_state: int = 128,
                               chunk: int = 128, latent: int = 1024,
                               expert_width: int = 2688,
                               shared_width: int = 5376, experts: int = 512,
                               held: int = 8, top_k: int = 22,
                               iters: int = 5, on_chip: bool = True) -> None:
    """The ``train-nemotron3-ssm-8k`` cell's two new operators at its
    shapes (the Mamba-2 mixer: 16 heads of 64 = one of 8 groups held,
    state 128, chunks of 128, at [1, 8192, 4096]; the latent expert
    layer: 8 of 512 experts held, top-22, ReLU^2 experts of 2688 in a
    latent of 1024, a shared expert of 5376) in bfloat16 against the
    plain float32 reference (the recurrence one position at a time),
    forward and VJP; rows and milliseconds per call printed."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_accelerators_tpu.models import (
        reference_nemotron_h as ref)
    from ray_lightning_accelerators_tpu.ops import moe, ssm

    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 3), 4))
    ids = tuple(range(held))
    model = {"num_experts": experts, "moe_top_k": top_k,
             "moe_norm_topk": True, "moe_routed_scale": 5.0,
             "norm_eps": 1e-5, "ssm_heads": ssm_heads, "ssm_groups": 1,
             "ssm_head_dim": ssm_head_dim, "ssm_state": ssm_state}
    x = jax.random.normal(next(keys), (batch, seq, d_model), jnp.float32)
    g = jax.random.normal(next(keys), x.shape, jnp.float32)
    check = functools.partial(_operator_check, "nemotron_cell_shapes", x=x,
                              g=g, iters=iters, on_chip=on_chip)

    check("mamba2_mixer",
          lambda p_, x_: ssm.mamba2_mixer(
              x_.astype(jnp.bfloat16), p_, heads=ssm_heads,
              head_dim=ssm_head_dim, groups=1, state=ssm_state, chunk=chunk,
              eps=1e-5).astype(jnp.float32),
          lambda p_, x_: ref.mamba_mixer(x_, p_, model),
          ssm.init_mamba2_params(next(keys), d_model, ssm_heads,
                                 ssm_head_dim, 1, ssm_state, 4))

    def layer(p_, x_):
        return moe.latent_moe(x_, p_, top_k=top_k, held=ids,
                              num_experts=experts, norm_topk=True,
                              scale=5.0)

    p = moe.init_latent_moe_params(next(keys), d_model, latent,
                                   expert_width, shared_width, experts,
                                   held)
    stats = jax.jit(lambda p_, x_: layer(p_, x_)[1])(p, x)
    require(float(stats["rows_routed"]) == float(stats["rows_computed"])
            and float(stats["rounds"]) == 1.0,
            f"latent_moe: a routed row was not computed, or not in one "
            f"window: {stats}")
    check("latent_moe", lambda p_, x_: layer(p_, x_)[0],
          lambda p_, x_: ref.latent_block(x_, p_, model, ids)[0], p,
          rows_in=batch * seq, rows_routed=float(stats["rows_routed"]),
          load_max_over_mean=float(stats["load_max_over_mean"]))


def phase_ouro_cell_shapes(seed: int, *, batch: int = 1, seq: int = 8192,
                           d_model: int = 2048, heads: int = 16,
                           head_dim: int = 128, d_ff: int = 5632,
                           layers: int = 6, passes: int = 4,
                           block: int = 1024, iters: int = 3,
                           on_chip: bool = True) -> None:
    """The ``train-ouro-loop-8k`` cell's layer stack at its shapes (6
    sandwich-norm layers of 16 heads of 128 and a SwiGLU of 5632, run 4
    times on the same weights with the final norm after every pass, at
    [1, 8192, 2048], remat on, flash blocks of 1024) in bfloat16 against
    the plain float32 reference, forward and VJP with respect to every
    shared weight; the mean of the four passes' states is compared, so
    that each pass answers.  Milliseconds per call printed."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_accelerators_tpu.models import reference_ouro as ref
    from ray_lightning_accelerators_tpu.models.transformer import (
        GPT, TransformerConfig)

    model = GPT(TransformerConfig(
        vocab_size=256, d_model=d_model, n_heads=heads,
        attn_head_dim=head_dim, d_ff=d_ff, n_layers=layers,
        max_seq_len=seq, tie_embeddings=False, rope_theta=1e6,
        gated_mlp=True, rope_style="half", post_norms=True,
        loop_passes=passes, exit_gate=passes > 1,
        exit_beta=0.05 if passes > 1 else 0.0, remat=True,
        flash_block_q=block, flash_block_k=block))
    model.compute_dtype = jnp.bfloat16
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 4), 3))
    params = jax.jit(model.init_params)(next(keys))
    params = {"layers": params["layers"], "ln_f": params["ln_f"]}
    # an embedding's rows are small: deviation 0.02
    x = 0.02 * jax.random.normal(next(keys), (batch, seq, d_model),
                                 jnp.float32)
    g = jax.random.normal(next(keys), x.shape, jnp.float32)
    about = {"rope_theta": 1e6, "norm_eps": 1e-6, "loop_passes": passes}

    def system(p_, x_):
        _, aux = model._run_stacks(p_, x_.astype(jnp.bfloat16))
        return jnp.mean(aux["loop_hidden"].astype(jnp.float32), 0)

    def reference(p_, x_):
        return sum(ref.run_passes(x_, p_, about, remat=True)) / passes

    _operator_check("ouro_cell_shapes", "looped_stack", system, reference,
                    params, x=x, g=g, iters=iters, on_chip=on_chip,
                    tol=TOL_OURO, layer_applications=passes * layers)


# --------------------------------------------------------------------- #
# 3. train                                                               #
# --------------------------------------------------------------------- #
def _fit(size: Size, seed: int, name: str, accelerator, epochs: int,
         **trainer_kw):
    """One seeded ``Trainer.fit`` of the GPT; returns (trainer, model,
    epoch clock, per-step losses)."""
    import numpy as np

    import bench
    from ray_lightning_accelerators_tpu import (Callback, DataLoader,
                                                Trainer)
    from ray_lightning_accelerators_tpu.data.loader import ArrayDataset
    from ray_lightning_accelerators_tpu.models.transformer import GPT

    model = GPT(_model_config(size), lr=3e-4)
    loader = DataLoader(ArrayDataset(_tokens(size, seed)),
                        batch_size=size.batch, shuffle=False)
    clock = bench._EpochClock(Callback)
    trainer = Trainer(max_epochs=epochs, accelerator=accelerator,
                      precision="bf16", enable_checkpointing=False,
                      log_every_n_steps=1, seed=seed, callbacks=[clock.cb],
                      default_root_dir=os.path.join(OUT_DIR, name),
                      **trainer_kw)
    trainer.fit(model, loader)
    losses = [row["train_loss"] for row in trainer.logger.history
              if "train_loss" in row]
    require(len(losses) == epochs * size.steps_per_epoch,
            f"{name}: {len(losses)} logged steps, expected "
            f"{epochs * size.steps_per_epoch}")
    require(bool(np.isfinite(losses).all()),
            f"{name}: non-finite loss in {losses}")
    return trainer, model, clock, losses


def _train_step_lowering(trainer, size: Size):
    """The trainer's own jitted train step, lowered for the live state's
    shapes and shardings (nothing runs, nothing is donated)."""
    import jax
    import jax.numpy as jnp

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)

    batch = jax.ShapeDtypeStruct((size.batch, size.seq), jnp.int32,
                                 sharding=trainer._batch_sharding)
    return trainer._train_step_fn.lower(jax.tree.map(sds, trainer._state),
                                        batch)


def phase_train(size: Size, seed: int, on_chip: bool = True):
    import jax

    from ray_lightning_accelerators_tpu import RayTPUAccelerator

    trainer, _, clock, losses = _fit(size, seed, "train",
                                     RayTPUAccelerator(), epochs=2)
    require(losses[-1] < losses[0],
            f"loss did not fall: {losses[0]} -> {losses[-1]}")
    require(clock.window_compiles() == 0,
            f"{clock.window_compiles()} compiles in the second epoch")
    names = kernels_in(_train_step_lowering(trainer, size))
    step_s = clock.steady_state_seconds() / size.steps_per_epoch
    record = dict(losses=losses,
                  second_epoch_compiles=clock.window_compiles(),
                  step_kernels=names, observed_step_s=step_s,
                  observed_tokens_per_s=size.batch * size.seq / step_s,
                  scanned_epoch=trainer._epoch_scan_fn is not None)
    if on_chip:
        require("flash_fwd" in names
                and any(n.startswith("flash_bwd") for n in names),
                f"train step lowering holds {names}: no flash fwd+bwd")
        stats = jax.devices()[0].memory_stats()
        record.update(peak_hbm_bytes=stats["peak_bytes_in_use"],
                      hbm_limit_bytes=stats.get("bytes_limit"))
    emit("train", **record)
    trainer.teardown()


# --------------------------------------------------------------------- #
# 4. generate                                                            #
# --------------------------------------------------------------------- #
def _tie_margins(model, params, seqs, n_prompt: int):
    """Teacher-forced re-forward of finished greedy sequences: for every
    generated position, how far the chosen token's logit trails the
    row's maximum, in standard deviations of the row (0 = it is the
    argmax).  Returns [batch, generated] float32."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def margins(params, seqs):
        # the barrier makes max and gather read ONE materialized array:
        # fused into the unembed matmul, the max is taken before the
        # logits are rounded to bf16 and never equals a gathered value
        logits = jax.lax.optimization_barrier(
            model.forward(params, seqs[:, :-1])[:, n_prompt - 1:])
        chosen = jnp.take_along_axis(
            logits, seqs[:, n_prompt:, None], axis=-1)[..., 0]
        return (logits.max(-1) - chosen) / logits.std(-1)

    return np.asarray(jax.jit(margins)(params, jnp.asarray(seqs)))


def _check_greedy(what: str, model, params, seqs, prompt) -> dict:
    import numpy as np
    seqs = np.asarray(seqs)
    n_prompt = prompt.shape[1]
    require(seqs.shape[0] == prompt.shape[0]
            and seqs.shape[1] > n_prompt, f"{what}: shape {seqs.shape}")
    require(np.array_equal(seqs[:, :n_prompt], prompt),
            f"{what}: prompt not preserved")
    require(int(seqs.min()) >= 0 and int(seqs.max()) < model.cfg.vocab_size,
            f"{what}: token out of range")
    m = _tie_margins(model, params, seqs, n_prompt)
    require(bool(np.isfinite(m).all()), f"{what}: non-finite logits")
    require(float(m.max()) <= TIE_TOL,
            f"{what}: a generated token trails the re-forward argmax by "
            f"{float(m.max()):.3f} logit deviations (> {TIE_TOL})")
    return {"argmax_fraction": float((m == 0).mean()),
            "max_tie_margin": float(m.max()),
            "distinct_new_tokens": int(np.unique(seqs[:, n_prompt:]).size)}


def phase_generate(size: Size, seed: int, on_chip: bool = True):
    """Returns (model, bf16 params) for the serve phase.  The weights
    are fresh from ``seed``, not the few-step fit's: a random-weight
    transformer's next token depends on its whole context, which is what
    makes agreement with a re-forward a test of the KV cache."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_lightning_accelerators_tpu.models.transformer import GPT

    model = GPT(_model_config(size))
    model.compute_dtype = jnp.bfloat16
    params = jax.jit(lambda key: jax.tree.map(
        lambda p: p.astype(jnp.bfloat16), model.init_params(key)))(
            jax.random.PRNGKey(seed))
    prompt = np.random.default_rng(seed + 1).integers(
        0, size.vocab_size, (size.batch, size.gen_prompt)).astype(np.int32)
    gen = jax.jit(functools.partial(model.generate,
                                    max_new_tokens=size.gen_new,
                                    temperature=0.0))

    def run(p):
        out = np.asarray(gen(p, prompt))  # compiles
        t0 = time.perf_counter()
        np.asarray(gen(p, prompt))
        return out, size.batch * size.gen_new / (time.perf_counter() - t0)

    out_bf16, tps_bf16 = run(params)
    emit("generate", weights="bf16", observed_tokens_per_s=tps_bf16,
         **_check_greedy("generate bf16", model, params, out_bf16, prompt))

    q8 = GPT.quantize_weights(params)
    declined = set(GPT._q8_declined_shapes)
    out_q8, tps_q8 = run(q8)
    mode = model._q8_kernel_mode()
    if on_chip:
        require(mode == "compiled", f"q8 kernel mode is {mode!r}")
        require(GPT._q8_declined_shapes == declined,
                f"q8 kernels declined model shapes "
                f"{sorted(GPT._q8_declined_shapes - declined)}")
    emit("generate", weights="int8", q8_kernel_mode=mode,
         observed_tokens_per_s=tps_q8,
         agrees_with_bf16=float((out_q8 == out_bf16)[:, size.gen_prompt:]
                                .mean()),
         **_check_greedy("generate int8", model, q8, out_q8, prompt))
    return model, params


# --------------------------------------------------------------------- #
# 5. serve                                                               #
# --------------------------------------------------------------------- #
def phase_serve(size: Size, seed: int, model, params,
                on_chip: bool = True) -> None:
    import jax
    import numpy as np

    from ray_lightning_accelerators_tpu.analysis import (
        compile_guard as cg)
    from ray_lightning_accelerators_tpu.serve import ServeEngine

    rng = np.random.default_rng(seed + 2)

    def toks(n):
        return rng.integers(0, size.vocab_size, (n,)).astype(np.int32)

    system = toks(size.serve_system)
    family = [np.concatenate([system, toks(n)]) for n in size.serve_family]
    others = [toks(n) for n in size.serve_others]
    # the first four are submitted together, the rest while those
    # decode; the family's head goes first so that its system-prompt
    # blocks are there to be shared
    prompts = [family[0]] + others[:3] + family[1:] + others[3:]

    gen = jax.jit(functools.partial(model.generate,
                                    max_new_tokens=size.serve_new,
                                    temperature=0.0))
    refs = [np.asarray(gen(params, p[None]))[0] for p in prompts]

    engine = ServeEngine(model, params)
    require(engine.paged, "the default engine is paged")
    if on_chip:
        require(engine._donate, "pool donation is off on the chip")
    engine.start()
    try:
        # warm-up: every prefill bucket (block multiples up to the chunk
        # quantum) and the decode step, one request at a time
        for blocks in range(1, engine._chunk_blocks + 1):
            n = blocks * engine.block_len
            if n + 2 <= engine.max_total_len:
                engine.submit(toks(n), 2).result(timeout=600)
        engine.metrics.reset()
        compiles_before = cg.compile_count()

        t0 = time.perf_counter()
        pending = [engine.submit(p, size.serve_new) for p in prompts[:4]]
        deadline = time.monotonic() + 600
        while pending[0].ttft_s is None and not pending[0].done():
            require(time.monotonic() < deadline, "no first token in 600s")
            time.sleep(0.005)
        pending += [engine.submit(p, size.serve_new) for p in prompts[4:]]
        outs = [np.asarray(r.result(timeout=600)) for r in pending]
        wall = time.perf_counter() - t0
        new_compiles = cg.compile_count() - compiles_before
    finally:
        engine.stop()
    stats = engine.stats()

    identical, near_ties, worst = 0, 0, 0.0
    for p, ref, out in zip(prompts, refs, outs):
        if np.array_equal(out, ref):
            identical += 1
            continue
        # not token-identical: admissible only as a bf16 near-tie
        first = int(np.argmax(out != ref))
        margin = float(_tie_margins(model, params, out[None], p.size).max())
        require(margin <= TIE_TOL,
                f"serve response (prompt {p.size}) leaves generate() at "
                f"token {first} and trails the re-forward argmax by "
                f"{margin:.3f} logit deviations (> {TIE_TOL})")
        near_ties += 1
        worst = max(worst, margin)
    emit("serve", requests=len(prompts), token_identical=identical,
         near_tie_divergent=near_ties, max_tie_margin=worst,
         compiles_after_warmup=new_compiles, wall_s=wall,
         observed_tokens_per_s=len(prompts) * size.serve_new / wall,
         donation=engine._donate,
         **{k: stats.get(k) for k in (
             "submitted", "completed", "failed", "prefix_hits",
             "prefix_hit_blocks", "peak_concurrent", "max_batch",
             "prefill_chunks", "block_pool_used", "block_pool_total",
             "hbm_cache_bytes")})
    require(stats["completed"] == len(prompts) and not stats["failed"],
            f"completed {stats['completed']} of {len(prompts)}")
    require(new_compiles == 0, f"{new_compiles} compiles after warm-up")
    require(stats["prefix_hits"] > 0, "no prefix hit")
    require(stats["peak_concurrent"] >= 2, "requests never overlapped")
    require(stats["block_pool_used"] == 0,
            f"{stats['block_pool_used']} blocks still held after stop()")


# --------------------------------------------------------------------- #
# --chips 4                                                              #
# --------------------------------------------------------------------- #
@contextlib.contextmanager
def _stderr_to(path: str):
    """XLA's C++ warnings go to fd 2, past ``sys.stderr``: point the fd
    at a file for the duration, then replay it."""
    os.makedirs(os.path.dirname(path), exist_ok=True)
    sys.stderr.flush()
    saved = os.dup(2)
    try:
        with open(path, "wb") as f:
            os.dup2(f.fileno(), 2)
            try:
                yield
            finally:
                sys.stderr.flush()
                os.dup2(saved, 2)
    finally:
        os.close(saved)
        with open(path, "rb") as f:
            os.write(2, f.read())


def _placement(tree, n_devices: int) -> dict:
    """Where a state tree's bytes sit: every leaf on ``n_devices``
    distinct devices; the share of bytes in leaves that are split
    ``1/n_devices`` per device."""
    import jax
    total = split = 0
    for leaf in jax.tree.leaves(tree):
        shards = leaf.addressable_shards
        devices = {s.device for s in shards}
        require(len(devices) == n_devices,
                f"leaf {leaf.shape} sits on {len(devices)} devices")
        total += leaf.nbytes
        if not leaf.sharding.is_fully_replicated:
            require(all(s.data.size * n_devices == leaf.size
                        for s in shards),
                    f"leaf {leaf.shape} is not split 1/{n_devices}")
            split += leaf.nbytes
    return {"bytes": total, "split_fraction": split / total}


def phase_four_chip(size: Size, seed: int) -> None:
    import numpy as np

    from ray_lightning_accelerators_tpu import RayTPUAccelerator

    runs = (
        ("one_device", dict(num_workers=1), {}),
        ("dp4", dict(num_workers=4), {}),
        ("fsdp4", dict(num_workers=4, use_fsdp=True), {}),
        ("fsdp4_int8_scan", dict(num_workers=4, use_fsdp=True),
         dict(grad_compression="int8", gather_mode="scan")),
    )
    log = os.path.join(OUT_DIR, "four_chip.stderr")
    trajectories = {}
    with _stderr_to(log):
        for name, accel_kw, trainer_kw in runs:
            trainer, _, clock, losses = _fit(
                size, seed, name, RayTPUAccelerator(**accel_kw), epochs=1,
                **trainer_kw)
            trajectories[name] = np.asarray(losses)
            record = dict(run=name, losses=losses)
            n = accel_kw["num_workers"]
            if n > 1:
                state = trainer._state
                record["params"] = _placement(state.params, n)
                record["opt_state"] = _placement(state.opt_state, n)
                if accel_kw.get("use_fsdp"):
                    # the weight matrices and their Adam moments -- all
                    # but the norm scales -- are 1/4 per chip
                    for part in ("params", "opt_state"):
                        require(record[part]["split_fraction"] > 0.99,
                                f"{name}: only "
                                f"{record[part]['split_fraction']:.3f} of "
                                f"{part} bytes are sharded")
                if trainer_kw:
                    require(trainer._gather_mode_eff == "scan",
                            "gather_mode='scan' fell back to 'tree'")
            emit("four_chip", **record)
            trainer.teardown()
    with open(log, errors="replace") as f:
        require("nvoluntary full rematerialization" not in f.read(),
                f"the partitioner rematerialized a tensor in full "
                f"(see {log})")

    ref = trajectories["one_device"]
    drift = {name: float(np.max(np.abs(t - ref) / ref))
             for name, t in trajectories.items() if name != "one_device"}
    final = {name: float(abs(t[-1] - ref[-1]) / ref[-1])
             for name, t in trajectories.items() if name != "one_device"}
    emit("four_chip", max_step_loss_drift=drift, final_loss_drift=final)
    for name in ("dp4", "fsdp4"):
        require(drift[name] <= TOL_TRAJECTORY,
                f"{name} loss trajectory drifts {drift[name]:.4f} from "
                f"the one-device run (> {TOL_TRAJECTORY})")
    require(final["fsdp4_int8_scan"] <= TOL_INT8_EXCHANGE,
            f"int8 + scan-gather final loss is "
            f"{final['fsdp4_int8_scan']:.4f} off the one-device run "
            f"(> {TOL_INT8_EXCHANGE})")


# --------------------------------------------------------------------- #
def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    t0 = time.perf_counter()
    device = phase_device(args.chips)
    size = Size()
    if args.chips == 4:
        phase_four_chip(size, args.seed)
    else:
        phase_kernels(size, args.seed)
        phase_flash_cell_shape(args.seed)
        phase_lfm2_cell_shapes(args.seed)
        phase_nemotron_cell_shapes(args.seed)
        phase_ouro_cell_shapes(args.seed)
        phase_train(size, args.seed)
        model, params = phase_generate(size, args.seed)
        phase_serve(size, args.seed, model, params)
    # the program's own compile ledger (one listener for the process)
    from ray_lightning_accelerators_tpu.analysis import compile_guard
    programs = compile_guard.summary(compile_guard.ledger())
    emit("done", seconds=time.perf_counter() - t0,
         compile_cache_hits=programs["loaded"],
         compile_cache_misses=programs["missed"],
         built=programs["built"])
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
