"""Training cells of a LOOPED stack (``TransformerConfig.post_norms`` /
``loop_passes`` / ``exit_gate``): ``Trainer.fit`` of the configuration's
GPT on seeded sequences, timed between device-synced epoch boundaries as
``drivers/train.py`` and ``drivers/train_arch.py`` do it (the same
set-up, window, checks and counters: their exported helpers are imported
and neither file is edited; ``train_arch.run`` fails a model without
expert counters at ``no_token_dropped``, so the window is repeated here;
PERF.md section 7 asks the next ``benchmark`` issue to fold the three).

The configuration's file names two modules of ``benchmark/lib``:
``"reference"`` (``forward(params, tokens, model) -> (logits_T,
{"pass_logits_loss", "exit_p", "loss"})``, ``loss_and_grads``,
``grad_group_norms``) and ``"flops"`` (``n_params``,
``train_flops_per_token(model, seq)``, ``causal_attention_flops``).

Nothing in a looped dense stack is a discontinuity, so the comparison
with the float32 reference is over every position: the objective (the
exit-weighted loss less its entropy term) and each pass's own
cross-entropy, relative; the last pass's logits by row deviation (the
largest difference in a row over the row's deviation: median and 99th
percentile, the maximum printed); the exit distribution by its largest
absolute difference; gradient norms by group on one check sequence.
The program's logged scalars ``loop_loss_pass_<t>``,
``loop_exit_mean_pass`` and ``loop_exit_entropy`` ride the logged
metrics and come back as counters.
"""
from __future__ import annotations

import contextlib
import importlib
import math
import shutil
import tempfile
import time

import numpy as np

from benchmark.drivers.train import (CHECK_SEQUENCES, _program_temp_bytes,
                                     _spread_over, _sync)
from benchmark.lib import cells, stats, traffic
from benchmark.lib import trace as trace_lib

# Each limit lies between two readings on the chip at the cell's sizes
# (my chip runs, PR 33; PERF.md section 6): what bfloat16 over float32
# weights gave over fifteen seeds, and what the float32 reference itself
# gave with its weights rounded to an 8-bit float (e4m3), the nearest
# precision below (``benchmark/tools/lowprec_ouro.py``).  24 layer
# applications compound the rounding, so the limits were measured here
# and not copied from another cell.  A pass left out, the final norm
# left out between passes, a gate without its bias or a loss without
# its entropy term is off by whole percents of the objective or tenths
# of the exit distribution (``benchmark/tests/test_ouro_cell.py`` shows
# each failing at the tiny size).
TOL_LOSS = 4.0e-4       # relative, the objective: 0.16-0.95e-4 over fifteen
#                         seeds | 2.38e-3
TOL_PASS_LOSS = 1.2e-3  # relative, the worst pass's cross-entropy:
#                         1.4-3.9e-4 | 3.20e-3
TOL_LOGITS_P50 = 0.4    # row deviations, the last pass, median:
#                         0.0919-0.1052 | 1.635
TOL_LOGITS_P99 = 0.5    # the same, 99th percentile: 0.126-0.141 | 1.944
#                         (the maximum, printed: 0.167-0.234 | 2.103)
TOL_EXIT_P = 0.035      # the exit distribution, largest difference over
#                         passes and positions: 0.0096-0.0152 | 0.112
TOL_GRAD_NORM = 0.012   # relative, each group but the gate: the largest
#                         group's 0.2-4.1e-3 | 0.033 (embedding; attention
#                         0.013; norms, head and mlp under the limit)
TOL_GRAD_NORM_GATE = 0.1    # the gate's 2,049 parameters, whose gradient
#                         is what is left of a sum that cancels:
#                         0.6e-3-0.025, and 0.019 with the reference's own
#                         weights rounded to bfloat16 | 0.79


def compare(sys_loss, sys_pass_loss, sys_logits, sys_p, ref_logits,
            ref) -> dict:
    """The numbers of the forward comparison (arrays in, floats out):
    ``sys_p`` and ``ref["exit_p"]`` are [passes, b, s - 1]."""
    import jax.numpy as jnp

    out = {"loss": float(sys_loss), "reference_loss": float(ref["loss"])}
    out["loss_rel_err"] = abs(out["loss"] - out["reference_loss"]) / abs(
        out["reference_loss"])
    ours, theirs = (np.asarray(x, np.float64) for x in (
        sys_pass_loss, ref["pass_logits_loss"]))
    out.update(pass_loss=ours.tolist(), reference_pass_loss=theirs.tolist(),
               pass_loss_rel_err=float(np.max(np.abs(ours - theirs)
                                              / np.abs(theirs))))
    err = np.asarray(jnp.max(jnp.abs(sys_logits - ref_logits), -1)
                     / ref_logits.std(-1))                      # [b, s]
    out.update(logit_err_p50=float(np.percentile(err, 50)),
               logit_err_p99=float(np.percentile(err, 99)),
               logit_err_max=float(err.max()),
               exit_p_err_max=float(jnp.max(jnp.abs(sys_p - ref["exit_p"]))),
               exit_mean_pass=float(jnp.mean(jnp.sum(
                   ref["exit_p"] * jnp.arange(1, len(theirs) + 1)[
                       :, None, None], 0))))
    return out


def merge(checks: list) -> dict:
    """The comparison of several check sequences taken one at a time (a
    float32 ``[8192, 49152]`` array a side is all one chip holds beside
    the weights): means of the losses, the worst of every error but the
    median."""
    out = {}
    for key in checks[0]:
        values = [c[key] for c in checks]
        if key in ("loss", "reference_loss", "exit_mean_pass",
                   "logit_err_p50"):
            out[key] = float(np.mean(values))
        elif key in ("pass_loss", "reference_pass_loss"):
            out[key] = np.mean(values, 0).tolist()
        else:
            out[key] = float(np.max(values))
    return out


def passes(check: dict) -> bool:
    grads = check.get("grad_norm_rel_err", {})
    return bool(
        math.isfinite(check["loss"])
        and check["loss_rel_err"] <= TOL_LOSS
        and check["pass_loss_rel_err"] <= TOL_PASS_LOSS
        and check["logit_err_p50"] <= TOL_LOGITS_P50
        and check["logit_err_p99"] <= TOL_LOGITS_P99
        and check["exit_p_err_max"] <= TOL_EXIT_P
        and all(v <= (TOL_GRAD_NORM_GATE if k == "gate" else TOL_GRAD_NORM)
                for k, v in grads.items()))


def grad_norm_errors(norms: dict, ref_norms: dict) -> dict:
    return {k: abs(norms[k] - ref_norms[k]) / ref_norms[k]
            if ref_norms[k] > 0 else (0.0 if norms[k] == 0 else math.inf)
            for k in norms}


def _reference_check(model, params, tokens, reference, model_cfg) -> dict:
    """The system's own ``training_step`` and ``forward`` (bfloat16,
    kernels, the pass loop, the fused weighted loss) against the plain
    float32 reference, a check sequence at a time; then gradient norms
    by group on the first."""
    import jax
    import jax.numpy as jnp

    n_passes = int(model_cfg.get("loop_passes", 1))

    @jax.jit
    def system(p, t):
        loss, metrics = model.training_step(p, t, None)
        logits, aux = model.forward(p, t, return_aux=True)
        return (loss, jnp.stack([metrics[f"loop_loss_pass_{i + 1}"]
                                 for i in range(n_passes)]),
                logits, aux["loop_exit_p"][:, :, :-1])

    @jax.jit
    def system_grads(p, t):
        return jax.grad(lambda q: model.training_step(q, t, None)[0])(p)

    tokens = jnp.asarray(tokens)
    checks = []
    for i in range(tokens.shape[0]):
        one = tokens[i:i + 1]
        loss, pass_loss, logits, p = system(params, one)
        ref_logits, ref = reference.forward(params, one, model_cfg)
        checks.append(compare(loss, pass_loss, logits, p, ref_logits, ref))
        del logits, ref_logits, ref, p
    check = merge(checks)
    one = tokens[:1]
    norms = reference.grad_group_norms(system_grads(params, one))
    # application by application, not as one program: every application
    # is checkpointed, so between two of them only their inputs live;
    # given all 24 in one program the chip's compiler held 14 GB of
    # float32 temporaries (my chip runs, PR 33)
    ref_norms = reference.grad_group_norms(
        reference.loss_and_grads(params, one, model_cfg)[1])
    check.update(grad_norms=norms, reference_grad_norms=ref_norms,
                 grad_norm_rel_err=grad_norm_errors(norms, ref_norms))
    check["ok"] = passes(check)
    return check


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
    reference = importlib.import_module(
        "benchmark.lib." + cell["config"]["reference"])
    flops = importlib.import_module(
        "benchmark.lib." + cell["config"]["flops"])

    tokens = traffic.train_tokens(cell["traffic"], seed,
                                  global_batch * steps + CHECK_SEQUENCES, seq,
                                  model_cfg["vocab_size"])
    model = cells.build_model(cell["config"], settings)
    model.compute_dtype = jnp.bfloat16
    # weights on the device, in one jitted call, from the seed
    params = jax.jit(model.init_params)(jax.random.PRNGKey(seed32))
    check = _reference_check(model, params, tokens[-CHECK_SEQUENCES:],
                             reference, model_cfg)
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
            self.gap_from = None    # when this callback gave the end back

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
        logged = [row for row in trainer.logger.history
                  if "train_loss" in row
                  and row["step"] > warm_epochs * steps]
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
    losses = [row["train_loss"] for row in logged]
    window_s = window.t1 - window.t0
    window_compiles = window.compiles_at_t1 - window.compiles_at_t0
    epoch_s = [e - s for s, e in zip(window.starts[warm_epochs:],
                                     window.ends[warm_epochs:])]
    # the pass loop's logged scalars, mean over the window's steps
    loop = {key: sum(row[key] for row in logged) / len(logged)
            for key in sorted(logged[0]) if key.startswith("loop_")} \
        if logged else {}
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
         global_batch=global_batch, n_params=flops.n_params(model_cfg),
         flops_per_token=flops_per_token, **loop)
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
            **loop,
            "loop_exit_entropy_pct": (
                100.0 * loop["loop_exit_entropy"]
                if "loop_exit_entropy" in loop else None),
        },
        "window_peak_bytes": live_bytes + temp_bytes if temp_bytes else 0,
        "trace": (trace_lib.reduce(window.captured[0])
                  if window.captured else None),
    }
