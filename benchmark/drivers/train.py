"""Training cells: ``Trainer.fit`` of the configuration's GPT on seeded
sequences, timed between device-synced epoch boundaries.

The cell file's ``settings``: ``per_chip_batch``, ``steps_per_epoch``,
``fsdp``, ``remat``, ``flash_block``, ``loss_chunk_rows``, ``lr``,
``guard`` (the Trainer's numeric guardian: ``"auto"``, its default, or
``null`` for off), ``warm_epochs`` and, for the traced run,
``trace_epochs``.
"""
from __future__ import annotations

import contextlib
import math
import shutil
import tempfile
import time

import numpy as np

from benchmark.lib import cells, flops, reference, stats, traffic
from benchmark.lib import trace as trace_lib

# The system computes in bfloat16 over float32 weights; the reference in
# float32.  Measured on the chip at initialization (gpt2-medium, 15 runs,
# PERF.md PR 24): the loss differs by at most 5e-5 relative and the
# last-position logits by 0.049-0.051 standard deviations of their row.
# The bounds sit 10x and 3x above that (the deeper gpt2-xl shares them):
# a compute type with fewer mantissa bits than bfloat16's 8 doubles both
# errors per bit lost, so an 8-bit float (3 bits) is 32x off and fails; a
# wrong mask, position or weight is off by whole deviations.
TOL_LOSS = 5e-4
TOL_LOGITS = 0.15
CHECK_SEQUENCES = 2


def _sync(trainer) -> float:
    """Drain the device (a 4-byte readback of the step counter, produced
    by the epoch's last step), then read the host clock."""
    import jax
    state = getattr(trainer, "_state", None)
    if state is not None:
        int(np.asarray(jax.device_get(state.step)))
    return time.perf_counter()


def _spread_over(params, devices):
    """Reshard a parameter tree made on one chip over all of them, so
    that the optimizer state the Trainer creates from it (eagerly, with
    the parameters' own placement) never sits whole on one chip."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.asarray(devices), ("all",))
    n = len(devices)

    def place(x):
        spec = [None] * x.ndim
        for axis in sorted(range(x.ndim), key=lambda a: -x.shape[a]):
            if x.shape[axis] % n == 0 and x.shape[axis] >= n:
                spec[axis] = "all"
                break
        return jax.device_put(x, NamedSharding(mesh, P(*spec)))

    out = jax.tree.map(place, params)
    jax.block_until_ready(out)
    for leaf in jax.tree.leaves(params):
        leaf.delete()
    return out


def _program_temp_bytes(trainer, steps: int, global_batch: int) -> int:
    """Temporaries of the window's program (the scanned epoch) on one
    chip, by the compiler's own account.  This runtime's allocator
    counter holds live buffers only (PERF.md, PR 22), so the peak of the
    window is the buffers live after it plus these.  The program is in the compile
    cache, so asking again costs a lowering, not a compile.  It reaches
    into the Trainer, and comes after the measurement: whatever goes
    wrong here (the attributes moved, the lowering is refused) gives 0,
    and the allocator's figure then stands alone."""
    import jax
    import jax.numpy as jnp
    try:
        idx = jax.ShapeDtypeStruct((steps, global_batch), jnp.int32,
                                   sharding=trainer._idx_mat_sharding)
        compiled = trainer._epoch_scan_fn.lower(
            trainer._state, trainer._device_cache, idx).compile()
        return int(compiled.memory_analysis().temp_size_in_bytes)
    except Exception:   # never fail a measured run over its memory note
        return 0


def _reference_check(model, params, tokens) -> dict:
    """Loss and last-position logits of a few seeded sequences: the
    system's own forward (bfloat16, kernels, fused loss) against the
    plain float32 reference."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def system(p, t):
        loss, _ = model.training_step(p, t, None)
        return loss, model.forward(p, t)[:, -1]

    tokens = jnp.asarray(tokens)
    loss, last = system(params, tokens)
    ref_logits = reference.logits(params, tokens)
    ref_loss = float(reference.lm_loss(ref_logits, tokens))
    ref_last = ref_logits[:, -1]
    err = float(jnp.max(jnp.abs(last - ref_last)
                        / ref_last.std(-1, keepdims=True)))
    loss = float(loss)
    return {"loss": loss, "reference_loss": ref_loss,
            "loss_rel_err": abs(loss - ref_loss) / abs(ref_loss),
            "logit_err_deviations": err,
            "ok": bool(math.isfinite(loss)
                       and abs(loss - ref_loss) <= TOL_LOSS * abs(ref_loss)
                       and err <= TOL_LOGITS)}


def run(cell, *, devices, seed, seconds, trace: bool, t_process, compiles,
        emit) -> dict:
    import jax
    import jax.numpy as jnp

    from ray_lightning_accelerators_tpu import (Callback, DataLoader,
                                                RayTPUAccelerator, Trainer)
    from ray_lightning_accelerators_tpu.data.loader import ArrayDataset


    settings = cell["workload"]["settings"]
    model_cfg = cell["config"]["model"]
    chips = len(devices)
    seq = int(cell["traffic"]["sequence_tokens"])
    steps = int(settings["steps_per_epoch"])
    global_batch = int(settings["per_chip_batch"]) * chips
    warm_epochs = int(settings.get("warm_epochs", 1))
    trace_epochs = int(settings.get("trace_epochs", 2))
    seed32 = seed % (2 ** 31 - 1)

    tokens = traffic.train_tokens(cell["traffic"], seed,
                                  global_batch * steps + CHECK_SEQUENCES, seq,
                                  model_cfg["vocab_size"])
    model = cells.build_model(cell["config"], settings)
    model.compute_dtype = jnp.bfloat16
    # weights on the device, in one jitted call, from the seed
    params = jax.jit(model.init_params)(jax.random.PRNGKey(seed32))
    check = _reference_check(model, params, tokens[-CHECK_SEQUENCES:])
    emit(info="reference_check", **check)
    if chips > 1:
        params = _spread_over(params, devices)
    model.params = params
    del params

    class Window(Callback):
        def __init__(self):
            self.starts, self.ends, self.gaps = [], [], []
            self.t0 = self.t1 = None
            self.compiles_at_t0 = self.compiles_at_t1 = 0
            self.tracing = self.captured = self.gap_span = None
            self.traced = 0
            self.gap_from = None    # when this callback gave the epoch end back

        def on_fit_start(self, trainer, module):
            # the state is placed: drop the last reference to the initial
            # weights, or a copy of them stays on the chip for the whole fit
            module.params = None

        def on_train_epoch_start(self, trainer, module):
            now = _sync(trainer)
            if self.gap_span is not None:
                self.gap_span.__exit__(None, None, None)
                self.gap_span = None
            if self.gap_from is not None:
                self.gaps.append(now - self.gap_from)
            if len(self.starts) == warm_epochs:
                self.t0, self.compiles_at_t0 = now, compiles.count()
            if (trace and self.captured is None
                    and len(self.starts) == warm_epochs + 1):
                self.tracing = contextlib.ExitStack()
                self.captured = self.tracing.enter_context(
                    trace_lib.capture())
                self.tracing.enter_context(trace_lib.annotate("window"))
                now = time.perf_counter()
            self.starts.append(now)

        def on_train_epoch_end(self, trainer, module):
            now = _sync(trainer)
            self.ends.append(now)
            if self.tracing is not None:
                self.traced += 1
                if self.traced == trace_epochs:
                    self.tracing.close()    # window span, then the trace
                    self.tracing = None
            if self.t0 is not None and now - self.t0 >= seconds \
                    and self.tracing is None:
                self.t1, self.compiles_at_t1 = now, compiles.count()
                trainer.should_stop = True
            else:
                self.gap_span = trace_lib.annotate("epoch_boundary")
                self.gap_span.__enter__()
            # stamped last: the profiler's stop above is no part of the gap
            self.gap_from = time.perf_counter()

    window = Window()
    root = tempfile.mkdtemp(prefix="bench-train-")
    try:
        trainer = Trainer(
            max_epochs=10 ** 9, precision="bf16", enable_checkpointing=False,
            log_every_n_steps=1, seed=seed32, callbacks=[window],
            default_root_dir=root, guard=settings.get("guard", "auto"),
            accelerator=RayTPUAccelerator(
                num_workers=chips, use_fsdp=bool(settings.get("fsdp")),
                devices=list(devices)))
        loader = DataLoader(ArrayDataset(tokens[:global_batch * steps]),
                            batch_size=global_batch, shuffle=False)
        trainer.fit(model, loader)
        history = [(row["step"], row["train_loss"])
                   for row in trainer.logger.history if "train_loss" in row]
        t_after = time.perf_counter()
        temp_bytes = _program_temp_bytes(trainer, steps, global_batch)
        live_bytes = max((d.memory_stats() or {}).get("bytes_in_use", 0)
                         for d in devices)
        emit(info="program_memory", temp_bytes=temp_bytes,
             live_bytes=live_bytes,
             seconds_to_ask=time.perf_counter() - t_after)
        trainer.teardown()
    finally:
        shutil.rmtree(root, ignore_errors=True)

    n_epochs = len(window.ends) - warm_epochs
    n_steps = n_epochs * steps
    losses = [loss for step, loss in history if step > warm_epochs * steps]
    window_s = window.t1 - window.t0
    window_compiles = window.compiles_at_t1 - window.compiles_at_t0
    epoch_s = [e - s for s, e in zip(window.starts[warm_epochs:],
                                     window.ends[warm_epochs:])]
    tokens = n_steps * global_batch * seq
    tok_s_chip = tokens / window_s / chips
    flops_per_token = flops.train_flops_per_token(model_cfg, seq)
    checks = {
        "reference": check["ok"],
        "losses_finite": bool(losses) and all(map(math.isfinite, losses)),
        # every epoch holds the same sequences, so epoch means compare
        # like with like (single steps differ by their batch)
        "loss_fell": len(losses) >= 2 * steps and (
            sum(losses[-steps:]) < sum(losses[:steps])),
        "no_compile_in_window": window_compiles == 0,
        "every_step_logged": len(losses) == n_steps,
    }
    emit(info="train", epochs=n_epochs, steps=n_steps, window_s=window_s,
         epoch_s_median=stats.percentile(epoch_s, 50),
         # where a stall sits, should a run lose time: in an epoch (device
         # or dispatch) or between two (the Trainer's host code)
         epoch_s_max=max(epoch_s),
         epoch_gap_max_ms=max(window.gaps[warm_epochs:], default=0.0) * 1e3,
         first_loss=losses[0] if losses else None,
         last_loss=losses[-1] if losses else None,
         window_compiles=window_compiles, scanned_epoch=temp_bytes > 0,
         global_batch=global_batch, n_params=flops.n_params(model_cfg))
    return {
        "correct": all(checks.values()), "checks": checks,
        "attempted": n_steps,
        "failed": sum(not math.isfinite(x) for x in losses),
        "end_to_end": {"train_tok_s_chip": tok_s_chip,
                       "setup_s": window.t0 - t_process},
        "units": {"train_tok_s_chip": "tokens/s/chip", "setup_s": "s"},
        "counters": {
            "chips": chips, "steps": n_steps, "steps_per_epoch": steps,
            "global_batch": global_batch, "sequence_tokens": seq,
            "epoch_s": epoch_s, "epoch_gap_s": window.gaps[warm_epochs:],
            # over the epochs' own time: a traced run's window also holds
            # the profiler's start and stop
            "tokens_per_s": tokens / sum(epoch_s),
            "flops_per_token": flops_per_token,
            "trace_steps": trace_epochs * steps,
        },
        "window_peak_bytes": live_bytes + temp_bytes if temp_bytes else 0,
        "trace": (trace_lib.reduce(window.captured[0])
                  if window.captured else None),
    }
