"""Tests of the scope join and the readers PR 25 added (not of the
program):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import scopes  # noqa: E402
from benchmark.readers import (epoch_host, scope_ms,  # noqa: E402
                               scoped_share)

OP = 'metadata={op_name="jit(e)/while/body/closed_call/'
EPOCH = f'''
HloModule jit_scanned_epoch
%fused_computation.9 (p0: f32[8]) -> (f32[], f32[8]) {{
  %r.1 = f32[]{{:T(128)}} reduce(%p0, %c), dimensions={{0}}, to_apply=%add, {OP}guard/reduce_sum"}}
  %a.1 = f32[8]{{0}} add(%p0, %p0), {OP}optimizer/add"}}
  ROOT %tuple.1 = (f32[]{{:T(128)}}, f32[8]{{0}}) tuple(%r.1, %a.1)
}}
%fused_computation.10 (p0: bf16[4,8]) -> (bf16[4,8], bf16[24,4,8]) {{
  %conv.1 = bf16[4,8]{{1,0}} convolution(%p0, %w), dim_labels=bf_io->bf, {OP}jvp(gpt/layers)/while/body/closed_call/gpt/mlp/dot_general"}}
  %dus.1 = bf16[24,4,8]{{2,1,0}} dynamic-update-slice(%s, %conv.1, %i), {OP}jvp(gpt/layers)/while/body/dynamic_update_slice"}}
  ROOT %tuple.2 = (bf16[4,8]{{1,0}}, bf16[24,4,8]{{2,1,0}}) tuple(%conv.1, %dus.1)
}}
ENTRY %main {{
  %multiply_reduce_fusion.5 = (f32[]{{:T(128)}}, f32[8]{{0}}) fusion(%p.1), kind=kLoop, calls=%fused_computation.9, {OP}guard/reduce_sum"}}
  %fusion.20 = (bf16[4,8]{{1,0}}, bf16[24,4,8]{{2,1,0}}) fusion(%p.1), kind=kOutput, calls=%fused_computation.10, {OP}jvp(gpt/layers)/while/body/closed_call/gpt/mlp/dot_general"}}
  %fusion.3 = bf16[4,8]{{1,0:T(8,128)(2,1)}} fusion(%p.1), kind=kLoop, calls=%f.1, {OP}jvp(gpt/layers)/while/body/closed_call/gpt/attn/dot_general" source_file="t.py" source_line=3}}
  %flash_fwd.2 = (bf16[8,128,64]{{2,1,0}}, f32[8,1,128]{{2,1,0}}) custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", {OP}transpose(jvp(gpt/layers))/while/body/closed_call/checkpoint/rematted_computation/gpt/attn/shard_map/kernel/flash_fwd/flash_fwd/pallas_call"}}
  %fusion.7 = f32[8]{{0}} fusion(%p.1), kind=kLoop, calls=%f.2, {OP}transpose(jvp(gpt/layers))/while/body/dynamic_update_slice"}}
  %fusion.9 = f32[8]{{0}} fusion(%p.1), kind=kInput, calls=%f.3, {OP}transpose(jvp(gpt/loss))/mul;jit(e)/while/body/closed_call/optimizer/sub"}}, backend_config={{"a":1}}
  %fusion.11 = f32[8]{{0}} fusion(%p.1), kind=kLoop, calls=%f.4, {OP}optimizer/add"}}
  %all-gather-start.2 = (bf16[2,8]{{1,0}}, bf16[8,8]{{1,0}}) all-gather-start(%w), dimensions={{0}}, {OP}jvp(gpt/layers)/while/body/closed_call/gpt/mlp/dot_general"}}
  %fusion.12 = s32[4]{{0}} fusion(%i), kind=kLoop, calls=%f.5, {OP}jit(_take)/gather"}}
  %convert.381 = bf16[24,8]{{1,0}} convert(%w)
}}
'''
# another module of the process that also has a %fusion.3
OTHER = f'''
ENTRY %main {{
  %fusion.3 = f32[2]{{0}} fusion(%p.1), kind=kLoop, calls=%f.1, {OP}optimizer/mul"}}
}}
'''
# event names as the chip's trace gives them: operand types, no metadata
EXCLUSIVE = {
    "%fusion.3 = bf16[4,8]{1,0:T(8,128)(2,1)} fusion(bf16[4,8]{1,0} %p.1), "
    "kind=kLoop, calls=%f.1": 4.0,
    "%flash_fwd.2 = (bf16[8,128,64]{2,1,0}, f32[8,1,128]{2,1,0}) "
    "custom-call(bf16[8,128,64]{2,1,0} %q, bf16[8,128,64]{2,1,0} %k, "
    'bf16[8,128,64]{2,1,0} %v), custom_call_target="tpu_custom_call"': 2.0,
    "%fusion.7 = f32[8]{0} fusion(f32[8]{0} %p.1), kind=kLoop, "
    "calls=%f.2": 1.0,
    "%fusion.9 = f32[8]{0} fusion(f32[8]{0} %p.1), kind=kInput, "
    "calls=%f.3": 3.0,
    "%fusion.11 = f32[8]{0} fusion(f32[8]{0} %p.1), kind=kLoop, "
    "calls=%f.4": 0.5,
    "%all-gather-start.2 = (bf16[2,8]{1,0}, bf16[8,8]{1,0}) "
    "all-gather-start(bf16[2,8]{1,0} %w), dimensions={0}": 0.25,
    "%fusion.12 = s32[4]{0} fusion(s32[4]{0} %i), kind=kLoop, "
    "calls=%f.5": 0.125,
    "%convert.381 = bf16[24,8]{1,0} convert(f32[24,8]{1,0} %w)": 0.625,
    "%while.5 = (s32[]) while((s32[]) %t), body=%b": 0.5,
    "%multiply_reduce_fusion.5 = (f32[]{:T(128)}, f32[8]{0}) "
    "fusion(f32[8]{0} %p.1), kind=kLoop, calls=%fused_computation.9": 0.75,
    "%fusion.20 = (bf16[4,8]{1,0}, bf16[24,4,8]{2,1,0}) "
    "fusion(bf16[4,8]{1,0} %p.1), kind=kOutput, "
    "calls=%fused_computation.10": 1.25,
}


def test_buckets_from_op_names():
    assert [scopes.bucket(n) for n in (
        "jit(e)/jvp(gpt/layers)/while/body/closed_call/gpt/norm/mul",
        "jit(e)/jvp(gpt/layers)/while/body/dynamic_update_slice",
        "jit(e)/transpose(jvp(gpt/layers))/while/body/closed_call/"
        "gpt/attn/kernel/flash_bwd/flash_bwd_fused/pallas_call",
        "jit(e)/transpose(jvp(gpt/layers))/while/body/closed_call/"
        "checkpoint/rematted_computation/gpt/mlp/tanh",
        "jit(e)/jvp(gpt/embed)/gather",
        "jit(e)/jvp(gpt/loss)/gpt/norm/mul",
        "jit(e)/transpose(jvp(gpt/loss))/while/body/dot_general",
        "jit(e)/optimizer/mul", "jit(e)/guard/lt",
        "jit(e)/jvp(gpt/layers)/while/body/closed_call/exchange/"
        "all_gather",
        "jit(e)/while/body/add", "jit(e)/jit(guardian)/mul")] == [
        "fwd/norm", "fwd/layers", "bwd/kernel/flash_bwd", "recompute/mlp",
        "fwd/embed", "loss", "loss", "optimizer", "guard", "exchange",
        "unscoped", "unscoped"]


def test_join_on_a_hand_written_text_with_a_name_collision():
    joined = scopes.join(EXCLUSIVE, {"other": OTHER, "epoch_scan": EPOCH})
    assert joined["program"] == "epoch_scan"   # knows 13.5 s, "other" 0
    s = joined["seconds"]
    assert joined["total_s"] == 14.0
    assert s["fwd/attn"] == 4.0
    assert s["recompute"] == s["recompute/kernel/flash_fwd"] == 2.0
    assert s["bwd"] == s["bwd/layers"] == 1.0   # the scan's carry copies
    assert s["loss"] == 3.0 and joined["split_s"] == 3.0   # the ;-joined
    # AdamW's update with the guardian's norm riding along, named
    # guard/reduce_sum by the compiler: booked by its largest output
    assert s["optimizer"] == 0.5 + 0.75 and joined["renamed_s"] == 0.75
    assert "guard" not in s
    # a matmul that also writes the scan's stack stays the matmul's
    assert s["fwd/mlp"] == 1.25 and s["fwd"] == 4.0 + 1.25
    assert s["collective"] == 0.25       # scoped, but not booked twice
    # the index gather, the hoisted cast XLA made (no metadata) and the
    # while's own time: known or not, none has a scope
    assert s["unscoped"] == 0.125 + 0.625 + 0.5
    assert joined["scoped_s"] == 12.75
    assert [label for label, _ in joined["unscoped_top"]] == [
        "%convert.381 convert bf16[24,8]", "%while.5 while s32[]",
        "%fusion.12 fusion s32[4]"]
    assert sum(v for k, v in s.items() if "/" not in k) == 14.0
    # a name alone joins too (a trace that gives no more), and a program
    # that knows nothing of the trace gives nothing
    assert scopes.join({"%fusion.11": 1.0}, {"e": EPOCH})["seconds"][
        "optimizer"] == 1.0
    assert scopes.join(EXCLUSIVE, {}) is None


def _context(monkeypatch, texts, exclusive=EXCLUSIVE):
    from ray_lightning_accelerators_tpu.telemetry import scopes as program
    monkeypatch.setattr(program, "registered", lambda: tuple(texts))
    monkeypatch.setattr(program, "program_text", texts.__getitem__)
    return {"trace": {"exclusive": dict(exclusive)},
            "counters": {"trace_steps": 4, "steps_per_epoch": 2,
                         "epoch_s": [1.0] * 5}}


def test_scope_readers(monkeypatch, capsys):
    ctx = _context(monkeypatch, {"epoch_scan": EPOCH})
    assert scope_ms.read(ctx, "fwd") == pytest.approx(1312.5)
    assert scope_ms.read(ctx, "recompute") == pytest.approx(500.0)
    assert scope_ms.read(ctx, "loss") == pytest.approx(750.0)
    assert scope_ms.read(ctx, "guard") is None          # nothing there
    assert scoped_share.read(ctx) == pytest.approx(100 * 12.75 / 14)
    out = capsys.readouterr().out.strip().splitlines()
    assert len(out) == 1                   # asked of the program once
    info = json.loads(out[0])
    assert info["info"] == "scopes" and info["program"] == "epoch_scan"
    assert info["seconds"]["bwd/layers"] == 1.0
    # no trace: nothing; a scope-less text (a stale compile-cache entry):
    # nothing, and it says so
    assert scope_ms.read({"trace": None, "counters": {}}, "fwd") is None
    stale = _context(monkeypatch, {"epoch_scan": OTHER})
    assert scope_ms.read(stale, "optimizer") is None
    assert scoped_share.read(stale) is None
    assert "stale compile cache" in capsys.readouterr().err


def test_scope_readers_on_a_program_without_a_scope_table(monkeypatch,
                                                          capsys):
    from ray_lightning_accelerators_tpu.telemetry import scopes as program
    ctx = _context(monkeypatch, {})
    monkeypatch.delattr(program, "registered")    # the parent commit
    assert scope_ms.read(ctx, "fwd") is None
    assert scoped_share.read(ctx) is None
    assert "no scope table" in capsys.readouterr().err


def test_epoch_host_reads_the_windows_epoch_end_events(monkeypatch):
    from ray_lightning_accelerators_tpu import telemetry
    rec = telemetry.configure()
    host_ms = [9000.0, 1.0, 700.0, 800.0, 2.0, 5.0]   # warm-up first
    for i, ms in enumerate(host_ms):
        rec.emit("train_step", step=i)
        rec.emit("epoch_end", epoch=i, step=i, plan_s=ms / 4e3,
                 dispatch_s=ms / 4e3, readback_s=123.0, log_s=ms / 4e3,
                 callbacks_s=ms / 4e3)
    ctx = {"counters": {"epoch_s": [1.0] * 5, "trace_steps": 4,
                        "steps_per_epoch": 2}}
    # the window's five epochs less the two the profiler sat in
    assert epoch_host.read(ctx, 50) == pytest.approx(2.0)
    assert epoch_host.read(ctx, 100) == pytest.approx(5.0)
    rec.clear()
    rec.emit("epoch_end", epoch=1, step=4)       # the parent's event
    assert epoch_host.read(ctx, 50) is None
    assert epoch_host.read({"counters": {}}, 50) is None


@pytest.mark.parametrize("workload,flags", [
    ("rehearsal-train", ""),
    ("rehearsal-train-fsdp4", "--xla_force_host_platform_device_count=4"),
    ("rehearsal-serve", ""),
])
def test_rehearsal_cells_leave_out_the_new_metrics_without_failing(
        workload, flags):
    """On the CPU the Trainer keeps no device cache, so a rehearsal runs
    the step loop (its ``epoch_end`` has no plan / dispatch / log
    fields) and its trace has no device plane: every new reader runs and
    finds nothing to read."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_REHEARSAL="1",
               XLA_FLAGS=flags)
    env.pop("BENCH_RUN", None)
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload,
         "--seed", "3000000019", "--seconds", "1", "--trace", "1"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["metrics"]
    assert not set(line["metrics"]) & {
        "fwd_ms.train", "bwd_ms.train", "loss_ms.train",
        "optimizer_ms.train", "recompute_ms.train", "scoped_share.train",
        "epoch_host_ms.train", "epoch_host_max_ms.train"}
