"""Training cells of an architecture that brings its own reference and
operation count: ``Trainer.fit`` of the configuration's GPT on seeded
sequences, timed between device-synced epoch boundaries, as
``drivers/train.py`` does it (the same set-up, window, checks and
counters: its helpers are imported, its ``run`` is repeated here because
it binds ``lib/reference.py`` and ``lib/flops.py`` inside; PERF.md
section 7 asks the next ``benchmark`` issue to fold the two).

The configuration's file names two modules of ``benchmark/lib``:
``"reference"`` (``forward(params, tokens, model) -> (logits, routing)``,
``lm_loss``, ``loss_and_grads``, ``grad_group_norms``) and ``"flops"``
(``n_params``, ``train_flops_per_token(model, seq, expert_rows_per_token)``,
``expert_matmul_flops``).  The cell file's ``settings`` are the train
driver's, and optionally ``weights_seed`` (the weights from a seed fixed
in the cell; the tokens stay on ``--seed``).

Routing is a discontinuity: a bfloat16 hidden state flips a near-tied
last choice, and that token's output changes by a whole expert.  So the
comparison with the float32 reference is: the loss over all positions;
the logits at the positions whose routing margin (the last chosen minus
the first rejected biased score) exceeds ``MARGIN`` in every sparse
layer, by their median and 99th percentile; the share of (position,
layer) pairs whose chosen set equals the reference's; gradient norms by
group on one check sequence (the router's is zero on both sides where
only some experts are held: the combine weights carry no gradient
there); and the expert layer's own counters (every pair routed to a held
expert got its row back from the grouped matmuls, in every step).
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
# (my chip runs, PR 27; PERF.md section 6): what bfloat16 over float32
# weights gave over six seeds, and what the float32 reference itself gave
# with its weights rounded to an 8-bit float (e4m3), the nearest
# precision below (``benchmark/tools/lowprec_lfm2.py``).  The 8-bit
# reading fails four of them; a missing bias or a dropped token fails the
# choice share or the counters, an unnormalised top-k or a non-causal tap
# the medians.
MARGIN = 0.002          # biased-score gap under which a choice may flip:
#                         75.6-76.1 % of the positions clear it in every
#                         sparse layer; of the (position, layer) pairs
#                         over it 4.5 % still differ (7.1 % of all)
MIN_COMPARED = 0.5      # share of positions that must clear MARGIN
TOL_LOSS = 1.3e-4       # relative, all positions: 0.3-6.7e-5 over 12
#                         seeds (rms 3.1e-5) | 1.86e-4
TOL_LOGITS_P50 = 0.3    # row deviations, compared positions, median:
#                         0.087-0.089 | 1.15
TOL_LOGITS_P99 = 1.05   # the same, 99th percentile (a flipped choice
#                         moves a row by a whole expert): 0.66-0.69 | 1.64
MIN_SAME_CHOICE = 0.8   # share of (position, layer) pairs with the
#                         reference's set: 0.929-0.932 | 0.47
TOL_GRAD_NORM = 0.02    # relative, each group: at most 0.0034 (router).
#                         No precision limit -- the 8-bit reading is 0.0047
#                         -- but a wrong router, tap or dropped row is off
#                         by whole tenths


def compare(sys_loss, sys_logits, sys_selected, ref_loss, ref_logits,
            routing) -> dict:
    """The numbers of the forward comparison (arrays in, floats out).
    A position is compared when its choice clears ``MARGIN`` in every
    sparse layer.  The logit error of a position is the largest
    difference in its row over the row's deviation; a flipped choice
    (its own under the margin's noise, or a neighbour's carried on by
    the conv layers' taps) moves a row by a whole expert, so the
    statistics are the median and the 99th percentile of the compared
    positions, and the maximum is printed beside them."""
    import jax.numpy as jnp

    out = {"loss": float(sys_loss), "reference_loss": float(ref_loss)}
    out["loss_rel_err"] = abs(out["loss"] - out["reference_loss"]) / abs(
        out["reference_loss"])
    err = np.asarray(jnp.max(jnp.abs(sys_logits - ref_logits), -1)
                     / ref_logits.std(-1))                      # [b, s]
    if routing is None:
        clear = np.ones(err.shape, bool)
        out.update(compared_share=1.0, same_choice_share=1.0)
    else:
        margin = np.asarray(routing["margin"])              # [L, b, s]
        same = np.asarray(jnp.all(
            jnp.sort(sys_selected, -1) == routing["selected"], -1))
        clear = (margin > MARGIN).all(0)
        out.update(
            compared_share=float(clear.mean()),
            same_choice_share=float(same.mean()),
            # by margin: the share of (position, layer) pairs over it,
            # and the share of those whose choice differs all the same
            flips_over_margin={
                str(m): [float((margin > m).mean()),
                         float(1.0 - same[margin > m].mean())]
                for m in (0.0, 0.001, 0.002, 0.004, 0.008, 0.016)})
    picked = err[clear] if clear.any() else np.zeros(1)
    out.update(logit_err_p50=float(np.percentile(picked, 50)),
               logit_err_p99=float(np.percentile(picked, 99)),
               logit_err_max=float(picked.max()),
               logit_err_p50_all=float(np.percentile(err, 50)),
               logit_err_p99_all=float(np.percentile(err, 99)),
               logit_err_max_all=float(err.max()))
    return out


def passes(check: dict) -> bool:
    grads = check.get("grad_norm_rel_err", {})
    return bool(
        math.isfinite(check["loss"])
        and check["loss_rel_err"] <= TOL_LOSS
        and check["compared_share"] >= MIN_COMPARED
        and check["logit_err_p50"] <= TOL_LOGITS_P50
        and check["logit_err_p99"] <= TOL_LOGITS_P99
        and check["same_choice_share"] >= MIN_SAME_CHOICE
        and all(v <= TOL_GRAD_NORM for v in grads.values()))


def _reference_check(model, params, tokens, reference, model_cfg) -> dict:
    """The system's own forward and gradient (bfloat16, kernels, fused
    loss, the dropless expert layer) against the plain float32
    reference: every number of ``compare``, then gradient norms by group
    on the first check sequence."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def system(p, t):
        loss, _ = model.training_step(p, t, None)
        logits, aux = model.forward(p, t, return_aux=True)
        return loss, logits, aux.get("moe_selected")

    @jax.jit
    def system_grads(p, t):
        return jax.grad(lambda q: model.training_step(q, t, None)[0])(p)

    tokens = jnp.asarray(tokens)
    loss, logits, selected = system(params, tokens)
    ref_logits, routing = reference.forward(params, tokens, model_cfg)
    check = compare(loss, logits, selected,
                    reference.lm_loss(ref_logits, tokens), ref_logits,
                    routing)
    del logits, ref_logits, routing, selected
    one = tokens[:1]
    norms = reference.grad_group_norms(system_grads(params, one))
    # one program, so that the compiler schedules the float32 gradient's
    # memory (layer by layer it keeps every layer's intermediates)
    ref_norms = reference.grad_group_norms(jax.jit(
        lambda p, t: reference.loss_and_grads(p, t, model_cfg)[1])(
            params, one))
    check["grad_norms"] = norms
    check["reference_grad_norms"] = ref_norms
    # a group without a gradient in the reference (the router, where only
    # some experts are held) must have none in the system either
    check["grad_norm_rel_err"] = {
        k: abs(norms[k] - ref_norms[k]) / ref_norms[k] if ref_norms[k] > 0
        else (0.0 if norms[k] == 0 else math.inf) for k in norms}
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
    weights_seed = int(settings.get("weights_seed", seed32))
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
    params = jax.jit(model.init_params)(jax.random.PRNGKey(weights_seed))
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
        history = [(row["step"], row["train_loss"])
                   for row in trainer.logger.history if "train_loss" in row]
        # the expert layer's counters ride the logged metrics
        moe = [(row["step"], row["moe_rows_routed"],
                row["moe_rows_computed"], row["moe_load_max_over_mean"])
               for row in trainer.logger.history if "moe_rows_routed" in row]
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
    moe = [m for m in moe if m[0] > warm_epochs * steps]
    rows_per_step = sum(m[2] for m in moe) / max(len(moe), 1)
    # the traced epochs' own steps (the window's second epoch on): a
    # share of a roofline divides THEIR rows by THEIR time
    first_traced = (warm_epochs + 1) * steps
    rows_traced = sum(m[2] for m in moe if first_traced < m[0]
                      <= first_traced + trace_epochs * steps)
    tokens = n_steps * global_batch * seq
    tok_s_chip = tokens / window_s / chips
    flops_per_token = flops.train_flops_per_token(
        model_cfg, seq, rows_per_step / (global_batch * seq))
    checks = {
        "reference": check["ok"],
        "losses_finite": bool(losses) and all(map(math.isfinite, losses)),
        # every epoch holds the same sequences, so epoch means compare
        # like with like (single steps differ by their batch)
        "loss_fell": len(losses) >= 2 * steps and (
            sum(losses[-steps:]) < sum(losses[:steps])),
        "no_compile_in_window": window_compiles == 0,
        "every_step_logged": len(losses) == n_steps,
        # dropless: every pair routed to a held expert got its row back
        "no_token_dropped": len(moe) == n_steps and all(
            routed == computed for _, routed, computed, _ in moe),
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
         moe_rows_per_step=rows_per_step,
         moe_rows_per_step_min=min((m[2] for m in moe), default=0),
         moe_rows_per_step_max=max((m[2] for m in moe), default=0),
         moe_rows_per_token=rows_per_step / (global_batch * seq),
         moe_rows_by_step=[m[2] for m in moe],
         flops_per_token=flops_per_token)
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
            "moe_rows_per_step": rows_per_step,
            "moe_rows_traced": rows_traced if window.captured else None,
            "moe_load_max_over_mean": (
                sum(m[3] for m in moe) / len(moe) if moe else None),
        },
        "window_peak_bytes": live_bytes + temp_bytes if temp_bytes else 0,
        "trace": (trace_lib.reduce(window.captured[0])
                  if window.captured else None),
    }
