"""Benchmarks through the full framework.  One JSON line per metric:
{"metric", "value", "unit", "vs_baseline", ...}.

- ``mnist``  (headline, BASELINE.json north star): imgs/sec/chip training
  the MNISTClassifier example end-to-end through Trainer +
  RayTPUAccelerator.  Baseline constant: 25_000 imgs/sec -- a single-A100
  PTL+DDP run of this 3-layer-MLP example is input-pipeline-bound in that
  regime (BASELINE.json target: ">= single-A100 DDP throughput").
- ``gpt``    (flagship compute bench): tokens/sec/chip + MFU training a
  GPT-2-small-class model (124M params, seq 1024, bf16, fused LM-head
  loss, flash attention).  FLOPs/token uses the PaLM-appendix formula
  6*N + 12*L*d_model*S (matmul params + attention); peak FLOP/s comes
  from utils.profiler's published-peak table keyed by device_kind (an
  unknown device is an error).  vs_baseline is MFU against the 0.35
  driver bar.
- ``cifar``  (BASELINE.md config #3, single-chip): ResNet18 imgs/sec/chip
  + val_acc.
- ``decode`` (inference): GPT-2-small greedy KV-cache decode tokens/sec
  (bf16 headline, int8 weight-only ratio), with vs_baseline measured
  against this chip's own weight-streaming roofline probed with a
  matmul-shaped read (the access pattern decode actually has).

- ``gradexchange`` / ``input_pipeline`` / ``fsdp_exchange`` /
  ``paged_serve`` / ``mfu_overlap`` / ``perf_observatory`` /
  ``live_plane`` / ``serve_resilience`` / ``long_context``
  (CPU-mesh subprocess benches):
  quantized-allreduce wire-bytes reduction, async-input-pipeline
  prefetch speedup, compressed-FSDP exchange, paged-KV-cache
  concurrency-per-HBM, the overlap-aware scan-gather + step autotune
  loop, the perf-observatory ledgers, the live telemetry plane, and
  the serve-tier chaos-resilience window, each measured by a
  self-contained probe script that forces an 8-device host-platform
  CPU mesh before backend init.  They check counts and parity; they
  never stand in for an accelerator bench: a window whose backend does
  not come up prints its death record and exits non-zero.

Each timed region is the steady state of a single public-API ``fit`` --
epoch 1 absorbs compile + the one-time device-cache shipment, later epochs
measure the loop the way a user runs it (device-resident gather feeding a
donated, jitted train step).

The reference publishes no numbers anywhere (BASELINE.md); baselines here
are the driver-defined bars.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

BASELINE_MNIST_IMGS_PER_SEC = 25_000.0
GPT_MFU_TARGET = 0.35
BASELINE_CIFAR_IMGS_PER_SEC = 2_500.0  # single-A100 PTL+DDP ResNet18/CIFAR

# Backend-death markers: one bench failing this way means every later
# bench would re-attempt the same dead init.  _CERTAIN are init-phase
# failures (the backend never came up); _SUSPECT strings also appear in
# transient bench-local errors, so they abort only after a re-probe
# confirms the backend is really gone.
_BACKEND_DEAD_CERTAIN = ("Unable to initialize backend",
                         "failed to initialize backend")
_BACKEND_DEAD_SUSPECT = ("No visible devices", "UNAVAILABLE")

_PROBE_SRC = """
import jax, numpy as np
x = jax.numpy.ones((128, 128))
v = float(np.asarray(jax.device_get((x @ x).sum())))
print("PROBE_OK", v, [str(d) for d in jax.devices()], flush=True)
"""


def _terminate(proc) -> str:
    """SIGTERM-first kill: give the child a grace period to run its
    handlers (and release the chip it may hold) before the hard kill.
    Returns whatever stdout the child produced."""
    proc.terminate()
    try:
        out, _ = proc.communicate(timeout=10)
    except subprocess.TimeoutExpired:
        proc.kill()
        out, _ = proc.communicate()
    return out or ""


def _flight_diagnosis(child_out: str, child_err: str,
                      timed_out: bool = False) -> dict:
    """Hung-vs-dead triage embedded in the ``backend_probe`` record, so
    the record alone says which way the backend failed.  Stdlib-only by
    design: it reads the flight-recorder SPILL FILES
    (``RLA_TPU_TELEMETRY_DIR``) directly -- this very record is written
    precisely when importing/initializing jax is what failed.

    - ``stall``: classification from the probe child's own output -- a
      child that printed NOTHING before the timeout hung inside backend
      init (``hung-init``: e.g. the chip is held by another process and
      the claim never returns); one that produced output reached python
      and then stalled/failed (``dead-backend``).
    - ``flight_tail``: the last events of every rank's spill file from
      the most recent run on this machine (empty when no telemetry dir
      is configured) -- the driver-side breadcrumb trail of whatever ran
      last against this backend."""
    produced = bool((child_out or "").strip() or (child_err or "").strip())
    # the hung verdict needs BOTH signals: only a child that ran out
    # its whole timeout without producing anything looks like a device
    # claim that never returned -- a fast silent death (segfault/OOM on
    # import) is a dead backend, not a hang
    if timed_out and not produced:
        cls, detail = "hung-init", (
            "probe child produced no output before the timeout: hung "
            "inside backend init (device claim never returned)")
    elif timed_out:
        cls, detail = "dead-backend", (
            "probe child reached python and produced output before "
            "stalling past the timeout: backend answered, then died")
    else:
        cls, detail = "dead-backend", (
            "probe child exited promptly"
            + ("" if produced else " with no output (killed during "
               "init? segfault/OOM)")
            + ": backend failed rather than hung")
    diag: dict = {"stall": {
        "classification": cls,
        "detail": detail,
        "child_output_tail": ((child_err or "") + (child_out or ""))[-300:],
    }}
    tdir = os.environ.get("RLA_TPU_TELEMETRY_DIR")
    tails = {}
    if tdir and os.path.isdir(tdir):
        for fname in sorted(os.listdir(tdir)):
            if not fname.endswith(".events.json"):
                continue
            try:
                with open(os.path.join(tdir, fname)) as f:
                    snap = json.load(f)
            except (OSError, ValueError):
                continue  # torn mid-write: expected near a crash
            if isinstance(snap, dict):
                label = fname[:-len(".events.json")]
                tails[label] = (snap.get("events") or [])[-8:]
    if tails:
        diag["flight_tail"] = tails
    return diag


def _death_record(detail: str, failed_bench: str, probe_err: dict) -> str:
    return json.dumps(
        {"metric": "backend_probe", "value": 0, "unit": "alive",
         "vs_baseline": 0.0, "error": "backend died mid-run",
         "detail": detail[-500:], "failed_bench": failed_bench,
         **{"probe_" + k: v for k, v in probe_err.items()}})


def probe_backend(timeout_s: float) -> dict | None:
    """Bounded-time liveness check of the JAX backend, in a subprocess.

    A chip belongs to one process at a time, and a claim against a chip
    another process holds can hang instead of failing.  Touching the
    device from a child process first means a hang costs ``timeout_s``
    seconds, after which the parent -- which never imports jax, so it
    never holds the chip its bench children need -- can still emit
    machine-readable output.  Returns None when the backend is live,
    else an error record ready to print as JSON."""
    t0 = time.perf_counter()
    proc = subprocess.Popen([sys.executable, "-c", _PROBE_SRC],
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True)
    try:
        out, err = proc.communicate(timeout=timeout_s)
    except subprocess.TimeoutExpired:
        partial = _terminate(proc)
        # hung-vs-dead triage + flight-recorder tail, embedded so the
        # record alone says WHICH failure mode this window hit
        return {"error": "backend unavailable",
                "detail": f"device probe hung > {timeout_s:.0f}s",
                "probe_seconds": round(time.perf_counter() - t0, 1),
                **_flight_diagnosis(partial, "", timed_out=True)}
    if proc.returncode != 0 or "PROBE_OK" not in out:
        tail = (err or out).strip().splitlines()[-3:]
        return {"error": "backend unavailable",
                "detail": " | ".join(tail)[-500:],
                "probe_seconds": round(time.perf_counter() - t0, 1),
                **_flight_diagnosis(out, err)}
    return None


class _EpochClock:
    """Wall time at train-epoch boundaries, device-synced.

    The sync is a 4-byte host readback of the step counter -- the scalar
    is produced by the epoch's last dispatched step, so reading it drains
    the device queue.  Marks at epoch start AND end keep the timed window
    free of fit()'s final full-parameter download.

    Also snapshots the compile-guard counter at every boundary, so the
    steady-state window carries its own bench-honesty record: a nonzero
    ``window_compiles()`` means a retrace landed inside the timed epochs
    and the step time is polluted."""

    def __init__(self, base):
        import jax
        import numpy as np

        from ray_lightning_accelerators_tpu.analysis import (
            compile_guard as cg)

        class _CB(base):
            def __init__(cb_self):
                cb_self.starts = []
                cb_self.ends = []
                cb_self.compiles_at_start = []
                cb_self.compiles_at_end = []

            def _sync(cb_self, trainer):
                if trainer._state is not None:
                    int(np.asarray(jax.device_get(trainer._state.step)))
                return time.perf_counter()

            def on_train_epoch_start(cb_self, trainer, module):
                cb_self.starts.append(cb_self._sync(trainer))
                cb_self.compiles_at_start.append(cg.compile_count())

            def on_train_epoch_end(cb_self, trainer, module):
                cb_self.ends.append(cb_self._sync(trainer))
                cb_self.compiles_at_end.append(cg.compile_count())

        self.cb = _CB()

    def steady_state_seconds(self) -> float:
        """Epoch-2-start .. last-epoch-end (epoch 1 absorbs compile)."""
        return self.cb.ends[-1] - self.cb.starts[1]

    def window_compiles(self) -> int:
        """Backend compiles landing inside the timed window (0 = clean)."""
        return self.cb.compiles_at_end[-1] - self.cb.compiles_at_start[1]


def bench_mnist() -> dict:
    import jax
    import numpy as np

    from ray_lightning_accelerators_tpu import (Callback, DataLoader,
                                                RayTPUAccelerator, Trainer)
    from ray_lightning_accelerators_tpu.data.loader import ArrayDataset
    from ray_lightning_accelerators_tpu.models.mnist import (MNISTClassifier,
                                                             synthetic_mnist)

    import os

    n_devices = jax.device_count()
    batch_size = 1024 * n_devices
    n_images = batch_size * 24
    # real data source order: a mounted dir (RLA_TPU_DATA_DIR), then the
    # committed 1024-image real-MNIST IDX subset under tests/data/mnist
    # (the no-mount fallback, tiled to bench size below) -- the throughput
    # number should say "real" wherever real pixels are available, like
    # the reference's real-MNIST accuracy gate
    # (/root/reference/ray_lightning/tests/utils.py:137-152)
    from ray_lightning_accelerators_tpu.data import vision
    real = None
    source = None
    data_dir = os.environ.get("RLA_TPU_DATA_DIR")
    if data_dir:
        real = vision.load_mnist(data_dir, "train")
        source = "real"
    if real is None:
        bundled = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "tests", "data", "mnist")
        real = vision.load_mnist(bundled, "train")
        if real is not None:
            # distinct label: real pixels, but a small committed subset
            # tiled to bench size -- cross-round comparisons must be able
            # to tell this regime from a full mounted dataset
            source = f"real-tiled-{len(real[0])}"
    if real is not None:
        x, y = real
        reps = -(-n_images // len(x))  # tile up to the bench size
        x = np.tile(x, (reps, 1, 1))[:n_images]
        y = np.tile(y, reps)[:n_images]
    else:
        x, y = synthetic_mnist(n_images, seed=0)
        source = "synthetic"
    loader = DataLoader(ArrayDataset(x, y), batch_size=batch_size,
                        shuffle=True)

    model = MNISTClassifier({"layer_1": 128, "layer_2": 256, "lr": 1e-3,
                             "batch_size": batch_size})
    clock = _EpochClock(Callback)
    epochs = 5
    trainer = Trainer(max_epochs=epochs, accelerator=RayTPUAccelerator(),
                      precision="bf16", enable_checkpointing=False,
                      log_every_n_steps=10 ** 9, seed=0,
                      callbacks=[clock.cb],
                      default_root_dir="/tmp/rla_tpu_bench")
    trainer.fit(model, loader)

    steps_per_epoch = len(loader)
    dt = clock.steady_state_seconds()
    imgs = batch_size * steps_per_epoch * (epochs - 1)
    per_chip = imgs / dt / n_devices
    return {
        "metric": "mnist_mlp_train_imgs_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "imgs/sec/chip",
        "data": source,
        "vs_baseline": round(per_chip / BASELINE_MNIST_IMGS_PER_SEC, 3),
    }


def bench_gpt() -> dict:
    # 1024x1024 flash blocks amortize per-grid-cell overhead at seq 1024;
    # 2048-row loss chunks pipeline the LM-head scan; 24 steps/epoch
    # amortizes the one dispatch+sync each scanned epoch pays.  A config
    # the chip's compiler refuses fails the bench: there is no second
    # config to retry with.
    return _bench_gpt(loss_chunk=2048, flash_block=1024,
                      steps_per_epoch=24)


def _bench_gpt(loss_chunk: int, flash_block: int,
               steps_per_epoch: int, per_chip_batch: int = 16,
               remat: bool = False, remat_policy: str = "nothing",
               tiny: bool = False, small: bool = False, epochs: int = 3,
               use_fsdp: bool = False, gather_mode: str = "tree",
               grad_compression: str | None = None,
               int8_matmul: bool = False,
               precision: str = "bf16") -> dict:
    """One bench-shaped GPT training measurement.  The extra knobs serve
    scripts/mfu_sweep.py's variant ladder; keeping them HERE means every
    sweep number is produced under exactly the timed-window/sync
    discipline the driver's bench uses (``tiny`` shrinks the model for
    CPU plumbing smokes; ``small`` is the CPU-mesh-measurable middle
    size the overlap probe uses — enough layers/params for the gather
    schedule to matter, small enough for an 8-device host CPU mesh;
    MFU is meaningless for both).

    ``use_fsdp``/``gather_mode``/``grad_compression`` engage the
    compressed-FSDP step (parallel/collectives.py): "tree" all-gathers
    the whole bf16 param tree before the forward, "scan" overlaps a
    layer-wise gather inside the transformer scan.  ``int8_matmul``
    routes the MLP projections through int8 forward matmuls with
    straight-through gradients (ops/quant.py)."""
    import jax
    import numpy as np

    from ray_lightning_accelerators_tpu import (Callback, DataLoader,
                                                RayTPUAccelerator, Trainer)
    from ray_lightning_accelerators_tpu.data.loader import ArrayDataset
    from ray_lightning_accelerators_tpu.models.transformer import (
        GPT, TransformerConfig)
    from ray_lightning_accelerators_tpu.utils import profiler as prof

    n_devices = jax.device_count()
    seq = 256 if tiny else (128 if small else 1024)
    if tiny:
        per_chip_batch = min(per_chip_batch, 2)
    if small:
        per_chip_batch = min(per_chip_batch, 4)
    batch = per_chip_batch * n_devices
    if tiny:
        dims = dict(vocab_size=512, d_model=128, n_heads=4, d_ff=512,
                    n_layers=2)
    elif small:
        dims = dict(vocab_size=2048, d_model=192, n_heads=6, d_ff=768,
                    n_layers=6)
    else:
        dims = dict(vocab_size=50304, d_model=768, n_heads=12, d_ff=3072,
                    n_layers=12)
    cfg = TransformerConfig(**dims, max_seq_len=seq,
                            fused_loss=True, loss_chunk_rows=loss_chunk,
                            flash_block_q=flash_block,
                            flash_block_k=flash_block,
                            remat=remat, remat_policy=remat_policy)
    model = GPT(cfg, lr=3e-4)
    n_seqs = batch * steps_per_epoch
    tokens = np.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size,
                                          size=(n_seqs, seq)),
        dtype=np.int32)
    loader = DataLoader(ArrayDataset(tokens), batch_size=batch,
                        shuffle=False)

    clock = _EpochClock(Callback)
    trainer = Trainer(max_epochs=epochs,
                      accelerator=RayTPUAccelerator(use_fsdp=use_fsdp),
                      precision=precision, enable_checkpointing=False,
                      log_every_n_steps=10 ** 9, seed=0,
                      callbacks=[clock.cb],
                      grad_compression=grad_compression,
                      gather_mode=gather_mode, int8_matmul=int8_matmul,
                      default_root_dir="/tmp/rla_tpu_bench_gpt")
    trainer.fit(model, loader)

    dt = clock.steady_state_seconds()
    timed_steps = steps_per_epoch * (epochs - 1)
    tokens_done = batch * seq * timed_steps
    tok_per_sec_chip = tokens_done / dt / n_devices
    step_time = dt / timed_steps

    # PaLM-appendix train FLOPs: 6*N per matmul param-touch (fwd + 2x bwd)
    # + 12*L*d_model*S attention per token.  N counts matmul params (norm
    # scales are negligible; the tied embedding is counted once, covering
    # the unembedding matmul).
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(model.params))
    flops_per_token = 6 * n_params + 12 * cfg.n_layers * cfg.d_model * seq
    flops_per_step = flops_per_token * batch * seq
    rec = {
        "metric": "gpt2s_124m_train_tokens_per_sec_per_chip",
        "value": round(tok_per_sec_chip, 1),
        "unit": "tokens/sec/chip",
        "step_ms": round(step_time * 1e3, 1),
        "params": n_params,
        "seq_len": seq,
        "measured_window_compiles": clock.window_compiles(),
    }
    if tiny or small:
        # CPU-mesh plumbing sizes: there is no peak to divide by
        rec.update(mfu=None, vs_baseline=None)
    else:
        device_kind = jax.devices()[0].device_kind
        peak = prof.peak_bf16_flops(device_kind)  # raises if unknown
        mfu = prof.mfu(flops_per_step / n_devices, step_time, peak)
        rec.update(mfu=round(mfu, 4), device_kind=device_kind,
                   peak_flops=peak,
                   peak_flops_source=prof.PEAK_BF16_FLOPS_SOURCE,
                   vs_baseline=round(mfu / GPT_MFU_TARGET, 3))
    if use_fsdp and grad_compression is not None:
        # the exposed-vs-hidden wire split for THIS step's gather mode
        # (collectives.wire_bytes_per_step via the trainer's record)
        comms = trainer.comms_per_step or {}
        for k in ("gather_mode", "exposed_bytes_per_step",
                  "hidden_bytes_per_step"):
            if k in comms:
                rec[k] = comms[k]
    return rec


def bench_cifar() -> dict:
    import jax
    import numpy as np

    from ray_lightning_accelerators_tpu import (Callback, DataLoader,
                                                RayTPUAccelerator, Trainer)
    from ray_lightning_accelerators_tpu.data.loader import ArrayDataset
    from ray_lightning_accelerators_tpu.models.resnet import (
        CIFAR10DataModule, ResNet18)

    import os

    n_devices = jax.device_count()
    batch = 256 * n_devices
    dm = CIFAR10DataModule(batch_size=batch, n_train=batch * 12,
                           n_val=batch * 2,
                           data_dir=os.environ.get("RLA_TPU_DATA_DIR"))
    dm.setup("fit")

    # lr 0.01: stable convergence on this short synthetic run -- higher
    # rates sit in a chaotic regime where val_acc depends on rounding
    # noise (verified: at 0.02-0.05 both executor paths land anywhere in
    # [0.09, 0.93] run to run)
    model = ResNet18({"lr": 0.01, "batch_size": batch})
    clock = _EpochClock(Callback)
    epochs = 4
    trainer = Trainer(max_epochs=epochs, accelerator=RayTPUAccelerator(),
                      precision="bf16", enable_checkpointing=False,
                      log_every_n_steps=10 ** 9, seed=0,
                      callbacks=[clock.cb],
                      default_root_dir="/tmp/rla_tpu_bench_cifar")
    # train-only fit so the timed window holds pure training steps;
    # validation runs once afterwards for the accuracy gate
    train_loader = dm.train_dataloader()
    trainer.fit(model, train_loader)
    steps_per_epoch = len(train_loader)
    dt = clock.steady_state_seconds()
    imgs = batch * steps_per_epoch * (epochs - 1)
    per_chip = imgs / dt / n_devices
    val_metrics = trainer.validate(model, dm.val_dataloader())[0]
    val_acc = float(val_metrics.get("val_accuracy", 0.0))
    return {
        "metric": "cifar_resnet18_train_imgs_per_sec_per_chip",
        "value": round(per_chip, 1),
        "unit": "imgs/sec/chip",
        "val_acc": round(val_acc, 4),
        # CIFAR10DataModule.source: "real" when local CIFAR-10 binaries
        # were found, "synthetic" otherwise
        "data": getattr(dm, "source", "synthetic"),
        "vs_baseline": round(per_chip / BASELINE_CIFAR_IMGS_PER_SEC, 3),
    }


def bench_decode() -> dict:
    """Autoregressive decode throughput on the GPT-2-small class model:
    batch-16 greedy generation through the single-scan KV-cache decode
    path, bf16 weights (headline) and int8 weight-only (ratio field).
    vs_baseline is decode efficiency against THIS chip's own
    weight-streaming roofline, measured in-bench: ideal tokens/sec =
    batch * HBM_GB/s / bf16_param_bytes (every token re-reads every
    weight) -- self-contained, no invented external bar."""
    import time as time_mod

    import jax
    import numpy as np

    from ray_lightning_accelerators_tpu.models.transformer import (
        GPT, TransformerConfig)
    from ray_lightning_accelerators_tpu.utils import compile_cache

    import functools

    import jax.numpy as jnp

    cfg = TransformerConfig(vocab_size=50304, d_model=768, n_heads=12,
                            d_ff=3072, n_layers=12, max_seq_len=512)
    compile_cache.enable()  # no Trainer/ServeEngine here to place it
    model = GPT(cfg, lr=3e-4)
    model.compute_dtype = jnp.bfloat16
    # bf16 STORAGE too (the deployment layout the headline claims; init
    # builds f32 masters)
    params = jax.device_put(jax.tree.map(
        lambda p: p.astype(jnp.bfloat16), model.init_params(
            jax.random.PRNGKey(0))))
    prompt = np.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab_size, (16, 128)),
        dtype=np.int32)
    new_tokens = 128

    # one compiled program per params-structure: jit the whole generate so
    # repetitions skip tracing and eager per-op dispatch
    gen = jax.jit(functools.partial(model.generate,
                                    max_new_tokens=new_tokens,
                                    temperature=0.0))

    def timed(p, n=3):
        np.asarray(gen(p, prompt))  # compile + warmup
        t0 = time_mod.perf_counter()
        for _ in range(n):
            out = gen(p, prompt)
        np.asarray(out)  # host readback = honest sync
        return (time_mod.perf_counter() - t0) / n

    dt_bf16 = timed(params)
    q8 = GPT.quantize_weights(params)
    # the int8 ratio is only a statement about the Pallas kernels
    # (ops/quant.py) if they are what ran.  Wherever they are expected
    # -- on the chip, unless RLA_TPU_DISABLE_Q8_KERNEL asks for the XLA
    # dequant path -- a kernel that fails to compile raises out of
    # timed(), and a shape it silently declined is an error here.
    q8_config = ("q8-kernel" if model._q8_kernel_mode() == "compiled"
                 else "xla-dequant")
    declined_before = set(GPT._q8_declined_shapes)
    dt_q8 = timed(q8)
    declines = GPT._q8_declined_shapes - declined_before
    if q8_config == "q8-kernel" and declines:
        raise RuntimeError(
            f"int8 kernel declined {len(declines)} of the model's own "
            f"matmul shapes (M, K, N): {sorted(declines)}; those ran the "
            "XLA dequant path, so the int8 ratio would not measure the "
            "kernels")
    tps_bf16 = prompt.shape[0] * new_tokens / dt_bf16
    tps_q8 = prompt.shape[0] * new_tokens / dt_q8

    # this chip's own weight-streaming roofline, measured with a
    # MATMUL-shaped probe -- decode's actual access pattern is a small
    # activation block multiplying a stream of weight matrices into the
    # MXU, which this chip moves faster than a reduce-style read (round
    # 2's reduce probe under-read at 27 GB/s and made decode "beat" its
    # own roofline by 52%; a ratio > 1 against a physical ceiling is a
    # probe bug, not a win).  Chain several passes and sync ONCE at the
    # end -- a per-call sync would bill dispatch latency to bandwidth.
    L, d = 48, 2048
    w_stack = jnp.ones((L, d, d), jnp.bfloat16) / d  # 384 MB
    xact = jnp.ones((prompt.shape[0], d), jnp.bfloat16)

    def stream(x, s):
        def body(carry, w):
            return (carry @ w).astype(jnp.bfloat16), ()
        out, _ = jax.lax.scan(body, x, w_stack)
        return out.astype(jnp.float32).sum() + s

    reader = jax.jit(stream)
    float(reader(xact, jnp.float32(0)))  # warmup/compile
    reps = 12
    best = float("inf")
    for _ in range(3):
        t0 = time_mod.perf_counter()
        acc = jnp.float32(0)
        for _ in range(reps):
            acc = reader(xact, acc)
        float(acc)
        best = min(best, time_mod.perf_counter() - t0)
    stream_bps = reps * w_stack.nbytes / best
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(params))
    # ideal decode: every token re-reads every bf16 weight byte at the
    # measured matmul-stream rate (KV-cache traffic ignored -- it only
    # LOWERS attainable tokens/sec, keeping this a true ceiling)
    roofline_tps = prompt.shape[0] * stream_bps / (2 * n_params)
    return {
        "metric": "gpt2s_124m_decode_tokens_per_sec_per_chip",
        "value": round(tps_bf16, 1),
        "unit": "tokens/sec/chip",
        "int8_ratio": round(tps_q8 / tps_bf16, 3),
        "int8_config": q8_config,
        "batch": prompt.shape[0],
        "weight_stream_gbps_measured": round(stream_bps / 1e9, 1),
        "vs_baseline": round(tps_bf16 / roofline_tps, 3),
    }


def _last_metric_record(stdout: str):
    """Newest JSON line of probe stdout that is an actual METRIC record
    (has a ``value`` key) -- probes also emit bench-honesty compile-count
    records, which must never displace the metric.  Falls back to the
    newest JSON line of any kind so probe error records still surface."""
    fallback = None
    for line in reversed(stdout.strip().splitlines()):
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            rec = json.loads(line)
        except ValueError:
            continue
        if isinstance(rec, dict) and "value" in rec:
            return rec
        if fallback is None:
            fallback = rec
    return fallback


def _run_cpu_probe(script_name: str, label: str) -> dict:
    """Run one of the forced-host-platform CPU-mesh probe scripts in a
    FRESH subprocess and return its newest value-bearing JSON line.  The
    probes force ``JAX_PLATFORMS=cpu`` before backend init: they check
    counts and parity on virtual devices and never touch the chip."""
    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "scripts", script_name)
    proc = subprocess.run([sys.executable, script], capture_output=True,
                          text=True, timeout=600)
    if proc.returncode != 0:
        tail = (proc.stderr or proc.stdout).strip().splitlines()[-3:]
        raise RuntimeError(
            f"{label} probe failed (rc {proc.returncode}): "
            + " | ".join(tail))
    rec = _last_metric_record(proc.stdout)
    if rec is None:
        raise RuntimeError(f"{label} probe produced no JSON record")
    return rec


def bench_gradexchange() -> dict:
    """Gradient-exchange microbench (fp32 implicit-psum vs int8/bf16
    quantized allreduce, parallel/collectives.py): step time + bytes
    moved on a forced-host-platform 8-device CPU mesh (see
    ``_run_cpu_probe``)."""
    return _run_cpu_probe("gradexchange_probe.py", "gradexchange")


def bench_input_pipeline() -> dict:
    """Async-input-pipeline bench (prefetch_batches=2 vs 0 steps/s on a
    synthetic input-bound loader, data/prefetch.py): see
    ``_run_cpu_probe``."""
    return _run_cpu_probe("input_pipeline_probe.py", "input_pipeline")


def bench_fsdp_exchange() -> dict:
    """Compressed-FSDP exchange bench (int8 reduce-scatter into the shard
    owner + bf16 param all-gather vs fp32, parallel/collectives.py):
    wire-bytes ratio + measured per-shard peak state bytes vs a
    replicated layout, on a forced-host-platform 8-device CPU mesh (see
    ``_run_cpu_probe``)."""
    return _run_cpu_probe("fsdp_exchange_probe.py", "fsdp_exchange")


def bench_paged_serve() -> dict:
    """Paged-KV-cache serve bench (block pool + prefix reuse,
    serve/engine.py): concurrent sequences per placed cache byte vs the
    dense allocator on a mixed-length lognormal workload, plus the
    measured TTFT reduction prefix hits buy — on a forced-host-platform
    8-device CPU mesh (see ``_run_cpu_probe``)."""
    return _run_cpu_probe("paged_serve_probe.py", "paged_serve")


def bench_mfu_overlap() -> dict:
    """Overlap-aware FSDP gather bench (layer-wise param all-gather
    inside the transformer scan vs whole-tree up-front,
    parallel/collectives.py + the tune.autotune_step closed loop):
    scan/tree step-time ratio under remat + the analytic exposed-comm
    reduction AND the measured exposed-comm crosscheck, on a
    forced-host-platform 8-device CPU mesh (see ``_run_cpu_probe``)."""
    return _run_cpu_probe("mfu_overlap_probe.py", "mfu_overlap")


def bench_live_plane() -> dict:
    """Live-telemetry-plane bench (telemetry/live.py + serve/slo.py):
    a training fit scraped at ~20Hz through the live /metrics+/statusz
    endpoints (every scrape exposition-validated; overhead A/B'd), a
    serve SLO burn-rate contrast (overloaded nonzero, light zero, typed
    deadline sheds), and a 2-worker ClusterView rank-labeled merge —
    on a forced-host-platform 8-device CPU mesh (see
    ``_run_cpu_probe``)."""
    return _run_cpu_probe("live_plane_probe.py", "live_plane")


def bench_serve_resilience() -> dict:
    """Serve-tier resilience bench (serve/controller.py + replicas):
    completed-request fraction and p99 TTFT across a replica chaos
    window (1 replica killed + 1 hung mid-run, circuit-breaker
    auto-revival, head-of-line requeue with retry backoff) vs a
    no-chaos baseline — on a forced-host-platform 8-device CPU mesh
    (see ``_run_cpu_probe``)."""
    return _run_cpu_probe("serve_resilience_probe.py",
                          "serve_resilience")


def bench_perf_observatory() -> dict:
    """Perf-observatory bench (telemetry/perf.py): one 8-dev CPU-mesh
    training run whose per-step phase timeline, HBM pool ledger and
    goodput fraction (over an ElasticRunner run with one injected
    preemption) all land in a ``run_report.json`` + Prometheus export;
    the headline value is the named-phase coverage of measured step
    wall time (see ``_run_cpu_probe``)."""
    return _run_cpu_probe("perf_observatory_probe.py", "perf_observatory")


def bench_resize() -> dict:
    """Live-resize downtime bench (parallel/plan.py +
    parallel/redistribute.py + Trainer.resize_in_memory): one dp=8 fit
    interrupted at step 2 is recovered into a dp=4 world both ways —
    checkpoint round-trip vs in-memory redistribution — and the value is
    the downtime ratio (recovery entry → first completed dp=4 step;
    must be strictly > 1), on a forced-host-platform 8-device CPU mesh
    (see ``_run_cpu_probe``)."""
    return _run_cpu_probe("resize_probe.py", "resize")


def bench_pipeline() -> dict:
    """MPMD pipeline-bubble bench (parallel/mpmd/): one 1F1B fit over 2
    stage groups x 4 microbatches on spawned CPU workers with compute
    sized to dominate the handoff cost; the value is the bubble accuracy
    1 - |measured - analytic| / analytic against the analytic 1F1B
    bubble (S-1)/(M+S-1), steady-state steps only (must be > 0.8 —
    within 20% of analytic; see ``_run_cpu_probe``)."""
    return _run_cpu_probe("pipeline_probe.py", "pipeline")


def bench_long_context() -> dict:
    """Long-context fast-path bench (serve/engine.py chunked prefill +
    core/trainer.py seq_parallel): inter-token p99 ratio
    blocking/chunked while two 40-block prompts join three live decode
    streams (must be strictly > 1 — chunking protects decode cadence),
    with token-identity and zero-measured-window-compile evidence, plus
    the seq_parallel=2 (ulysses) train-loss parity rel-err as a field —
    on a forced-host-platform 8-device CPU mesh (see
    ``_run_cpu_probe``)."""
    return _run_cpu_probe("long_context_probe.py", "long_context")


def bench_prefix_affinity() -> dict:
    """Prefix-affinity routing bench (serve/controller.py +
    serve/engine.py): a skewed shared-prefix workload (4 hot 384-token
    prefix families, shuffled arrivals) is served by a 3-replica tier
    twice — least-loaded spray vs prefix-affinity routing — and the
    value is the steady-state p99 TTFT ratio least-loaded/affinity
    (must be strictly > 1), plus a disaggregated 1-prefill/2-decode
    lane pass whose decode cadence and KV-handoff counts ride along as
    fields, on a forced-host-platform CPU mesh (see
    ``_run_cpu_probe``)."""
    return _run_cpu_probe("prefix_affinity_probe.py", "prefix_affinity")


def bench_anomaly_guard() -> dict:
    """Numeric-guard bench (runtime/guardian.py + core/trainer.py
    in-step hooks): steady-state epoch-time ratio guarded/unguarded of
    the same tiny-GPT fit on the 8-device CPU mesh (must stay <= 1.05 —
    detection rides the existing metrics readback with zero extra syncs
    and zero retraces, pinned by the measured-window compile count),
    plus one full badbatch trip -> data blame -> quarantine -> resumed
    skip recovery timed as ``recovery_s`` (see ``_run_cpu_probe``)."""
    return _run_cpu_probe("anomaly_guard_probe.py", "anomaly_guard")


BENCHES = {"mnist": bench_mnist, "gpt": bench_gpt, "cifar": bench_cifar,
           "decode": bench_decode, "gradexchange": bench_gradexchange,
           "input_pipeline": bench_input_pipeline,
           "fsdp_exchange": bench_fsdp_exchange,
           "paged_serve": bench_paged_serve,
           "mfu_overlap": bench_mfu_overlap,
           "perf_observatory": bench_perf_observatory,
           "live_plane": bench_live_plane,
           "serve_resilience": bench_serve_resilience,
           "resize": bench_resize, "pipeline": bench_pipeline,
           "prefix_affinity": bench_prefix_affinity,
           "long_context": bench_long_context,
           "anomaly_guard": bench_anomaly_guard}

if os.environ.get("RLA_TPU_BENCH_SELFTEST"):
    # jax-free fixtures for tests/test_bench_probe.py's isolation tests
    # (must exist in the CHILD processes too, hence env-gated, not
    # monkeypatched)
    BENCHES["selftest"] = lambda: {"metric": "selftest", "value": 1,
                                   "unit": "ok", "vs_baseline": 1.0}

    def _selftest_hang():
        time.sleep(600)

    BENCHES["selftest-hang"] = _selftest_hang

    def _selftest_dead():
        raise RuntimeError("Unable to initialize backend 'selftest'")

    BENCHES["selftest-dead"] = _selftest_dead


def _run_isolated(names, per_bench_timeout: float,
                  probe_timeout: float) -> int:
    """Run each bench in ITS OWN subprocess with a hard timeout.

    The parent never imports jax, so it never holds the chip: each child
    claims it, runs one bench and releases it on exit -- one process per
    chip at a time.  The pre-flight probe only protects the START of the
    window; a bench that hangs MID-run sits inside a jit dispatch that
    nothing in-process can interrupt.  Here a hung bench costs its own
    timeout, is killed SIGTERM-first, becomes one machine-readable error
    record, and the remaining benches still run (after a confirming
    re-probe).
    Exit code: 0 all pass, 1 some failed, 2 backend declared dead (the
    death record is the window's last line; nothing runs after it)."""
    failed = False
    for name in names:
        cmd = [sys.executable, os.path.abspath(__file__),
               "--benches", name, "--no-isolate", "--probe-timeout", "0"]
        proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True)
        timed_out = False
        try:
            out, _ = proc.communicate(timeout=per_bench_timeout)
        except subprocess.TimeoutExpired:
            timed_out = True
            out = _terminate(proc)
        for line in (out or "").splitlines():
            if line.strip():
                print(line, flush=True)  # child records pass through
        if timed_out:
            failed = True
            print(json.dumps(
                {"metric": name, "value": 0, "unit": "error",
                 "vs_baseline": 0.0, "error": "bench timed out",
                 "detail": f"no result within {per_bench_timeout:.0f}s"}),
                flush=True)
            # a hang suggests a dead backend: confirm before burning the
            # next bench's timeout on it too (probing disabled via
            # --probe-timeout 0 = keep going, same as the in-process
            # suspect-marker rule)
            if probe_timeout > 0:
                err = probe_backend(min(probe_timeout, 60))
                if err is not None:
                    print(_death_record("bench hang, probe confirmed",
                                        name, err), flush=True)
                    return 2
        elif proc.returncode == 2:
            return 2  # child already printed the death record
        elif proc.returncode != 0:
            failed = True
    return 1 if failed else 0


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument(
        "--benches",
        default="mnist,gpt,cifar,decode,gradexchange,input_pipeline,"
                "fsdp_exchange,paged_serve,mfu_overlap,perf_observatory,"
                "live_plane,serve_resilience,resize,pipeline,"
                "prefix_affinity,long_context",
        help=f"comma-separated subset of {sorted(BENCHES)}")
    parser.add_argument("--gate", action="store_true",
                        help="run no benches: gate a bench window "
                             "against PERF_BASELINE.json floors "
                             "(scripts/perf_gate.py) and exit 0 pass / "
                             "1 regression / 2 UNGATED (no numbers)")
    parser.add_argument("--gate-input", default=None,
                        help="window to gate: bench stdout capture or "
                             "BENCH_r*.json; '-' = stdin (default: "
                             "newest committed BENCH_r*.json)")
    parser.add_argument("--gate-baseline", default=None,
                        help="floors file (default: PERF_BASELINE.json)")
    parser.add_argument("--probe-timeout", type=float,
                        default=float(os.environ.get(
                            "RLA_TPU_PROBE_TIMEOUT", "120")),
                        help="seconds before the pre-flight backend probe "
                             "declares the backend dead (0 disables)")
    parser.add_argument("--no-isolate", action="store_true",
                        help="run benches in THIS process instead of one "
                             "subprocess each (isolation is the default "
                             "so a mid-run backend hang costs one "
                             "bench's timeout, not the whole window)")
    parser.add_argument("--bench-timeout", type=float,
                        default=float(os.environ.get(
                            "RLA_TPU_BENCH_TIMEOUT", "1200")),
                        help="per-bench wall-clock limit in isolated "
                             "mode (seconds)")
    args = parser.parse_args()
    if args.gate:
        # regression gate: stdlib-only (scripts/perf_gate.py never
        # imports jax — it must run on the machine whose backend died)
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "scripts"))
        import perf_gate
        sys.exit(perf_gate.run(
            args.gate_input,
            args.gate_baseline or perf_gate.DEFAULT_BASELINE))
    if args.probe_timeout > 0:
        err = probe_backend(args.probe_timeout)
        if err is not None:
            # no backend, no window: the death record is the only line.
            # The CPU-mesh probes are not a substitute result.
            print(json.dumps({"metric": "backend_probe", "value": 0,
                              "unit": "alive", "vs_baseline": 0.0, **err}),
                  flush=True)
            sys.exit(2)
    names = [b.strip() for b in args.benches.split(",") if b.strip()]
    if not args.no_isolate:
        sys.exit(_run_isolated(names, args.bench_timeout,
                               args.probe_timeout))
    failed = False
    for name in names:
        try:
            print(json.dumps(BENCHES[name]()), flush=True)
        except Exception as e:  # emit remaining benches; Ctrl-C still aborts
            msg = f"{type(e).__name__}: {e}"
            print(f"bench {name} failed: {msg}", file=sys.stderr,
                  flush=True)
            certain = any(m in str(e) for m in _BACKEND_DEAD_CERTAIN)
            suspect = any(m in str(e) for m in _BACKEND_DEAD_SUSPECT)
            if certain or suspect:
                # a certain init failure aborts outright; a suspect
                # marker ("UNAVAILABLE" can be a transient, bench-local
                # error) aborts only after a bounded re-probe confirms
                # the backend is really gone -- and with probing
                # disabled (--probe-timeout 0) a suspect marker just
                # moves on to the next bench
                err = {"detail": "init-phase failure, not re-probed"} \
                    if certain else (
                        probe_backend(min(args.probe_timeout, 60))
                        if args.probe_timeout > 0 else None)
                if err is not None:
                    print(_death_record(msg, name, err), flush=True)
                    sys.exit(2)
            failed = True
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
