"""Named scopes inside the jitted programs, the scope table
(telemetry/scopes.py) and the host side of the scanned epoch: spans,
``epoch_end`` fields, the step timeline's compute row.  All on the CPU
mesh: what a scope costs or shows on the chip is PERF.md's business."""

import gc
import json
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_accelerators_tpu import (ArrayDataset, DataLoader,
                                            Profiler, RayTPUAccelerator,
                                            Trainer, telemetry)
from ray_lightning_accelerators_tpu.models.transformer import (
    GPT, TransformerConfig)
from ray_lightning_accelerators_tpu.telemetry import (HbmLedger,
                                                      PerfObservatory,
                                                      scopes)

VOCAB = 256


def _gpt(**over):
    cfg = TransformerConfig(vocab_size=VOCAB, d_model=64, n_heads=4,
                            d_ff=128, n_layers=2, max_seq_len=32,
                            fused_loss=True, loss_chunk_rows=64, **over)
    return GPT(cfg, lr=1e-3)


def _loader(n=32, bs=8):
    toks = np.random.default_rng(0).integers(
        0, VOCAB, size=(n, 32)).astype(np.int32)
    return DataLoader(ArrayDataset(toks), batch_size=bs, shuffle=False)


def _fit(tmp_path, model=None, **kw):
    kw.setdefault("accelerator", RayTPUAccelerator(num_workers=2))
    trainer = Trainer(max_epochs=2, precision="f32", seed=0,
                      enable_checkpointing=False,
                      default_root_dir=str(tmp_path),
                      log_every_n_steps=1, cache_dataset_on_device=True,
                      **kw)
    trainer.fit(model or _gpt(), _loader())
    return trainer


def _op_names(program):
    return set(scopes.scope_table(program).values())


def _has(op_names, scope):
    return any(f"/{scope}/" in f"/{n}/" or f"({scope})" in n
               for n in op_names)


def _pass_of(op_name):
    """fwd / bwd / recompute, read from JAX's own wrappers."""
    if "transpose(" not in op_name:
        return "fwd"
    return "recompute" if "rematted_computation" in op_name else "bwd"


# --------------------------------------------------------------------- #
# (a) the scope contract, program by program                             #
# --------------------------------------------------------------------- #
TRAIN_SCOPES = ("gpt/embed", "gpt/layers", "gpt/attn", "gpt/mlp",
                "gpt/norm", "gpt/loss", "optimizer")


@pytest.mark.parametrize("kw,extra,remat", [
    ({}, ("guard",), False),
    ({"grad_compression": "int8"}, ("guard", "exchange"), False),
    ({"grad_compression": "int8", "gather_mode": "scan",
      "accelerator": "fsdp8"}, ("exchange",), True),
], ids=["replicated", "compressed", "fsdp-scan-gather-remat"])
def test_train_step_carries_every_scope(tmp_path, kw, extra, remat):
    """Every train-step builder names its parts; forward, backward,
    loss and optimizer are told apart from the compiled text alone, and
    a remat's second forward is ``recompute``, not a second ``fwd``."""
    kw = dict(kw)
    if kw.get("accelerator") == "fsdp8":
        kw["accelerator"] = RayTPUAccelerator(num_workers=8, use_fsdp=True)
    trainer = _fit(tmp_path, model=_gpt(remat=remat), **kw)
    assert "epoch_scan" in scopes.registered()
    trainer.teardown()          # the table outlives the trainer's state
    names = _op_names("epoch_scan")
    for scope in TRAIN_SCOPES + extra:
        assert _has(names, scope), scope
    model = {(_pass_of(n), scope) for n in names
             for scope in ("attn", "mlp", "norm", "layers", "embed")
             if _has([n], "gpt/" + scope) and not _has([n], "gpt/loss")}
    assert {("fwd", "attn"), ("fwd", "mlp"), ("fwd", "norm"),
            ("fwd", "layers"), ("bwd", "attn"), ("bwd", "mlp")} <= model
    assert (("recompute", "mlp") in model) == remat
    # the head is the loss's in a train step, forward and backward
    assert {_pass_of(n) for n in names if _has([n], "gpt/loss")} >= {
        "fwd", "bwd"}


def test_kernel_scopes_wrap_the_pallas_calls():
    from ray_lightning_accelerators_tpu.ops import attention, norms, quant

    q = jnp.ones((1, 2, 128, 64), jnp.float32)

    def flash(q):
        return attention.flash_attention_grads_interpret(
            q, q, q, q, causal=True, block_q=128, block_k=128)

    def rest(x, wq, s):
        return (norms.rms_norm_interpret(x, jnp.ones((128,))),
                quant.int8_matmul(x, wq, s, interpret=True))

    held = [scopes.Program("kernels_flash", jax.jit(flash)),
            scopes.Program("kernels_rest", jax.jit(rest))]
    held[0].register(q)
    held[1].register(jnp.ones((8, 128)), jnp.ones((128, 128), jnp.int8),
                     jnp.ones((128,)))
    names = _op_names("kernels_flash") | _op_names("kernels_rest")
    for scope in ("kernel/flash_fwd", "kernel/flash_bwd",
                  "kernel/rms_norm", "kernel/q8_matmul"):
        assert _has(names, scope), scope


def test_forward_alone_books_nothing_to_the_loss():
    """Inference and ``generate`` run the head too: ``gpt/loss`` is the
    train step's, set in ``_lm_loss`` and not in ``forward``."""
    model = _gpt()
    params = model.init_params(jax.random.PRNGKey(0))
    held = scopes.Program("forward", jax.jit(model.forward))
    held.register(params, jnp.zeros((2, 32), jnp.int32))
    names = _op_names("forward")
    for scope in ("gpt/embed", "gpt/layers", "gpt/attn", "gpt/mlp",
                  "gpt/norm"):
        assert _has(names, scope), scope
    assert not _has(names, "gpt/loss")


# --------------------------------------------------------------------- #
# the table and the join                                                 #
# --------------------------------------------------------------------- #
HLO = '''
HloModule jit_epoch
%fused.1 {
  ROOT %m.1 = f32[8]{0} multiply(%a, %a), metadata={op_name="jit(e)/optimizer/mul"}
}
ENTRY %main {
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_name="jit(e)/jvp(gpt/layers)/while/body/closed_call/gpt/attn/dot_general" source_file="x.py" source_line=3}
  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_type="mul" op_name="jit(e)/transpose(jvp(gpt/loss))/mul;jit(e)/optimizer/sub"}, backend_config={"x":1}
  ROOT %copy.9 = f32[8]{0} copy(%p)
}
'''


def test_scope_table_is_the_compiled_texts_op_names(monkeypatch):
    monkeypatch.setattr(scopes, "program_text", lambda name: HLO)
    assert scopes.scope_table("epoch") == {
        "%m.1": "jit(e)/optimizer/mul",
        "%fusion.1": "jit(e)/jvp(gpt/layers)/while/body/closed_call/"
                     "gpt/attn/dot_general",
        "%fusion.3": "jit(e)/transpose(jvp(gpt/loss))/mul;"
                     "jit(e)/optimizer/sub"}


def test_program_registers_once_keeps_shapes_not_buffers(monkeypatch):
    scopes.clear()
    f = jax.jit(lambda x, k=2: x * k)
    tiny = scopes.Program("tiny", f)
    assert scopes.registered() == ()        # nothing before a first call
    x = jnp.ones((4, 4))
    assert float(tiny(x, 3).sum()) == 48.0
    assert scopes.registered() == ("tiny",)
    assert scopes._get("tiny") is tiny and tiny.args[1] == 3
    assert isinstance(tiny.args[0], jax.ShapeDtypeStruct)
    monkeypatch.setattr(tiny, "register", None)     # a second would raise
    tiny(x, 3)
    assert "multiply" in scopes.program_text("tiny")
    assert tiny.lower(x, 3).as_text() == f.lower(x, 3).as_text()
    # one program per name: the last to make its first call
    other = scopes.Program("tiny", jax.jit(lambda x: x + 1))
    assert scopes._get("tiny") is tiny
    other(x)
    assert "add" in scopes.program_text("tiny")
    tiny.retire()                   # the name is no longer tiny's
    assert scopes._get("tiny") is other
    other.retire()
    del tiny, other                 # the registry alone keeps the text
    assert "add" in scopes.program_text("tiny")
    with pytest.raises(KeyError):
        scopes.program_text("never-registered")


def test_registry_does_not_keep_a_dropped_owner_alive():
    """Held weakly until retired: the callable's closure reaches its
    owner (a Trainer and its device state)."""
    scopes.clear()
    weights = jnp.ones((8, 8))
    dropped = scopes.Program("dropped", jax.jit(lambda x: x @ weights))
    dropped(weights)
    assert scopes.registered() == ("dropped",)
    del dropped
    gc.collect()
    assert scopes.registered() == ()
    with pytest.raises(KeyError):
        scopes.program_text("dropped")


def test_two_trainers_keep_their_own_programs(tmp_path):
    """A second owner of a name neither re-registers per call nor
    disturbs the first one's program; a Trainer dropped without
    teardown takes its programs and its device state with it, a torn
    down one leaves its programs' text behind."""
    first = _fit(tmp_path / "a")
    held = scopes._get("epoch_scan")
    assert held is first._epoch_scan_fn
    second = _fit(tmp_path / "b")
    assert scopes._get("epoch_scan") is second._epoch_scan_fn
    assert first._epoch_scan_fn is held and held.args is not None
    first.teardown()                # not its name any more: stays second's
    assert scopes._get("epoch_scan") is second._epoch_scan_fn
    gone = weakref.ref(first)
    del first, held
    gc.collect()
    assert gone() is None
    second.teardown()
    assert second._epoch_scan_fn is None
    del second                      # as the benchmark's readers find it
    gc.collect()
    assert "gpt/attn" in scopes.program_text("epoch_scan")
    third = weakref.ref(_fit(tmp_path / "c"))      # dropped, no teardown
    gc.collect()
    assert third() is None
    assert scopes.registered() == ()
    scopes.clear()


# --------------------------------------------------------------------- #
# (c, d, f) the host side of the scanned epoch                           #
# --------------------------------------------------------------------- #
def test_profiler_keeps_the_scanned_epoch_and_epoch_end_says_where(
        tmp_path):
    from ray_lightning_accelerators_tpu import Callback
    from ray_lightning_accelerators_tpu.analysis.compile_guard import (
        compile_count, install)
    install()
    telemetry.configure()
    compiles = []

    class Snap(Callback):       # epoch hooks only: the scan stays eligible
        def on_train_epoch_end(self, trainer, module):
            compiles.append(compile_count())

    prof = Profiler()
    perf = PerfObservatory(hbm=HbmLedger(sample_min_s=0.0))
    trainer = Trainer(max_epochs=4, precision="f32", seed=0,
                      enable_checkpointing=False,
                      default_root_dir=str(tmp_path), log_every_n_steps=1,
                      cache_dataset_on_device=True, profiler=prof,
                      perf_observatory=perf, callbacks=[Snap()],
                      accelerator=RayTPUAccelerator(num_workers=2))
    trainer.fit(_gpt(), _loader())
    assert trainer._can_scan_epoch()
    spans = prof.summary()
    for name in ("fit/epoch_plan", "fit/epoch_dispatch", "fit/log_replay",
                 "fit/callbacks"):
        assert spans[name]["count"] == 4, name
    assert spans["fit/epoch_readback"]["count"] == 8
    assert "train_step" not in spans            # the scan, not the loop
    events = [e["data"] for e in telemetry.get_recorder().events()
              if e["kind"] == "epoch_end"]
    assert len(events) == 4
    for data in events:
        assert {"plan_s", "dispatch_s", "readback_s", "log_s",
                "callbacks_s", "epoch", "step"} <= set(data)
        assert all(isinstance(data[k], float) and data[k] >= 0
                   for k in data if k.endswith("_s"))
    # the epochs after the first: the device's time shows in the
    # readback, and the timeline books it as compute
    assert all(d["readback_s"] > d["dispatch_s"] for d in events[1:])
    tl = perf.timeline.snapshot()
    assert tl["steps"] == 16
    assert tl["phases"]["compile"]["total_s"] > 0       # epoch 1's
    rows = tl["recent_steps"]
    assert [r["scanned_steps"] for r in rows] == [4] * 4
    # epoch 1's dispatch traced and compiled; the synced epochs after it
    assert all(r["phases"]["compute"] > r["phases"].get("other", 0.0)
               for r in rows[1:]), rows
    # (f) scopes, profiler and observatory attached: epoch 1 compiles,
    # epochs 2..4 retrace nothing
    assert compiles[1:] == [compiles[0]] * 3, compiles


def test_goodput_counts_the_scanned_epoch_as_productive():
    from ray_lightning_accelerators_tpu.telemetry import GoodputLedger

    prof = Profiler()
    prof.observe("fit/epoch_dispatch", 0.001)
    prof.observe("fit/epoch_readback", 0.9)
    prof.observe("fit/callbacks", 0.5)
    ledger = GoodputLedger()
    ledger.absorb_profiler(prof)
    assert ledger.snapshot()["productive_s"] == pytest.approx(0.901)


def test_scan_epoch_row_splits_compute_other_and_compile():
    from ray_lightning_accelerators_tpu.telemetry import StepTimeline

    compiled = [0.0]
    tl = StepTimeline(ring=4, compile_seconds_fn=lambda: compiled[0])
    compiled[0] = 2.0
    tl.observe_scan_epoch(3.5, 8, compute_s=1.0)    # first epoch
    tl.observe_scan_epoch(1.01, 8, compute_s=1.0)
    snap = tl.snapshot()
    phases = {k: v["total_s"] for k, v in snap["phases"].items()}
    assert phases == pytest.approx(
        {"compute": 2.0, "compile": 2.0, "other": 0.51})
    assert snap["steps"] == 16
    assert snap["step_wall_total_s"] == pytest.approx(4.51)
    assert snap["recent_steps"][-1]["phases"] == pytest.approx(
        {"compute": 1.0, "other": 0.01})


# --------------------------------------------------------------------- #
# the profiler's trace: one prefix, scopes.json, the per-scope table     #
# --------------------------------------------------------------------- #
def test_spans_annotate_with_one_prefix_and_the_nested_path(monkeypatch):
    seen = []

    class Annotation:
        def __init__(self, name):
            seen.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    prof = Profiler()
    with prof.span("fit/epoch_dispatch"):
        with prof.span("inner"):
            pass
    assert seen == ["rla:fit/epoch_dispatch",
                    "rla:fit/epoch_dispatch/inner"]
    assert set(prof.summary()) == {"fit/epoch_dispatch",
                                   "fit/epoch_dispatch/inner"}


def test_stop_trace_writes_scope_tables_and_the_summary_reads_them(
        tmp_path, capsys):
    import gzip

    from ray_lightning_accelerators_tpu import cli
    from ray_lightning_accelerators_tpu.utils.profiler import (
        trace_op_summary)

    @jax.jit
    def f(x):
        with jax.named_scope("optimizer"):
            return x * 2 + 1

    scopes.clear()
    held = scopes.Program("tiny", f)
    held.register(jnp.ones((8,)))
    prof = Profiler()
    log_dir = tmp_path / "trace"
    with prof.trace(str(log_dir)):
        f(jnp.ones((8,))).block_until_ready()
    tables = json.loads((log_dir / "scopes.json").read_text())
    assert set(tables) == {"tiny"}
    scoped = [k for k, v in tables["tiny"].items() if "optimizer" in v]
    assert scoped
    # a device trace as the chip writes it, naming one scoped op
    events = [{"ph": "X", "name": scoped[0].lstrip("%"), "pid": 1,
               "tid": 1, "args": {"device_offset_ps": 0,
                                  "device_duration_ps": 3_000_000,
                                  "hlo_category": "fusion"}},
              {"ph": "X", "name": "copy.77", "pid": 1, "tid": 1,
               "args": {"device_offset_ps": 4_000_000,
                        "device_duration_ps": 1_000_000,
                        "hlo_category": "copy"}}]
    for old in log_dir.rglob("*.trace.json.gz"):
        old.unlink()
    with gzip.open(log_dir / "vm.trace.json.gz", "wt") as fh:
        json.dump({"traceEvents": events}, fh)
    tables["other"] = {"%copy.77": "jit(g)/exchange/copy"}   # knows 25 %
    (log_dir / "scopes.json").write_text(json.dumps(tables))
    s = trace_op_summary(str(log_dir))
    assert s["scope_program"] == "tiny"
    by_name = {op["name"]: op for op in s["ops"]}
    assert "optimizer" in by_name[scoped[0].lstrip("%")]["scope"]
    assert by_name["copy.77"]["scope"] == ""
    cli.main(["trace", str(log_dir)])
    out = capsys.readouterr().out
    assert "scope in tiny" in out and "/optimizer/" in out
    # no scopes.json, no scope column
    (log_dir / "scopes.json").unlink()
    s = trace_op_summary(str(log_dir))
    assert "scope_program" not in s and "scope" not in s["ops"][0]
