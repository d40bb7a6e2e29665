"""Tests of what PR 27 added to the benchmark (the ``lfm2-8b-a1b``
configuration, its cell, ``drivers/train_arch.py``, ``lib/flops_lfm2.py``
and the new readers):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_lfm2_cell.py -q
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

from benchmark.lib import cells, flops_lfm2, scopes  # noqa: E402
from benchmark.readers import (counter, flash_roofline_arch,  # noqa: E402
                               moe_gmm_roofline, scope_sum_ms)

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "num_attention_heads", "num_key_value_heads", "conv_L_cache",
          "num_experts_per_tok")


def test_configuration_file_is_the_published_one_cut_as_it_says():
    config = cells.load_config("lfm2-8b-a1b")
    published, reduced = config["published"], set(config["reduced"])
    assert reduced == {"num_hidden_layers", "layer_types",
                       "num_dense_layers", "num_experts", "vocab_size"}
    for key, value in published.items():
        assert (config[key] == value) != (key in reduced), key
    assert not reduced & set(WIDTHS)
    assert (config["num_hidden_layers"], config["num_dense_layers"],
            config["num_experts"], config["vocab_size"]) == (5, 1, 8, 16384)
    assert config["layer_types"] == published["layer_types"][1:6]
    assert "4 chips share each layer" in config["deployment"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "LFM2-8B-A1B")
        assert published == row["config"]
        assert config["source"] == row["source_url"]
    # the model group states the same sizes to the program
    m = config["model"]
    assert (m["d_model"], m["d_ff"], m["moe_d_ff"], m["n_heads"],
            m["n_kv_heads"], m["conv_kernel"], m["moe_top_k"]) == tuple(
        published[k] for k in WIDTHS)
    assert m["num_experts"] == published["num_experts"]     # router width
    assert m["moe_experts_held"] == list(range(config["num_experts"]))
    assert (m["n_layers"], m["num_dense_layers"], m["vocab_size"],
            m["layer_types"], m["norm_eps"], m["rope_theta"]) == (
        5, 1, 16384, config["layer_types"], published["norm_eps"],
        published["rope_theta"])


def test_operation_counts_of_the_cut_model():
    model = cells.load_config("lfm2-8b-a1b")["model"]
    d, f, fe = 2048, 7168, 1792
    conv, attn = 4 * d * d + 3 * d, 2 * d * d + 2 * d * 8 * 64 + 128
    expert, norms = 3 * d * fe, 2 * d
    want = (16384 * d + d + (conv + 3 * d * f + norms)
            + (attn + d * 32 + 8 * expert + norms)
            + 3 * (conv + d * 32 + 8 * expert + norms))
    assert flops_lfm2.n_params(model) == want
    assert 507.5e6 < want < 508.5e6             # ISSUE 27: 507.8M
    # and the program holds as many (the selection bias is a buffer)
    import jax
    program = cells.build_model(cells.load_config("lfm2-8b-a1b"), {})
    shapes = jax.eval_shape(program.init_params, jax.random.PRNGKey(0))
    held = sum(leaf.size for path, leaf in
               jax.tree_util.tree_flatten_with_path(shapes)[0]
               if "expert_bias" not in jax.tree_util.keystr(path))
    assert held == want
    assert flops_lfm2.n_sparse_layers(model) == 4
    # one routed row a sparse layer a token: 6 x 199.5M + 12 x d x S
    per_token = flops_lfm2.train_flops_per_token(model, 8192, 4.0)
    active = (16384 * d + conv + 3 * d * f + attn + 3 * conv + 4 * d * 32
              + 4 * expert)
    assert per_token == pytest.approx(6 * active + 12 * d * 8192)
    assert 1.35e9 < per_token < 1.45e9          # ISSUE 27: ~1.4 GF
    assert flops_lfm2.expert_matmul_flops(model, 1000) == 18 * d * fe * 1000
    # attention in ONE of the five layers: 32 heads of 64 over 8192^2 / 2
    assert flops_lfm2.causal_attention_flops(
        model, 4, 8192, backward=True) == 3 * 2 * 8192 ** 2 * 64 * 32 * 4


def test_new_scopes_fall_into_buckets_without_an_edit():
    body = "jit(e)/transpose(jvp(gpt/layers))/while/body/closed_call/"
    assert [scopes.bucket(n) for n in (
        "jit(e)/jvp(gpt/layers)/while/body/closed_call/gpt/conv/mul",
        "jit(e)/jvp(gpt/layers)/while/body/closed_call/gpt/moe_route/top_k",
        body + "checkpoint/rematted_computation/gpt/moe_dispatch/sort",
        body + "gpt/moe_experts/kernel/moe_gmm/pallas_call",
        body + "gpt/moe_experts/mul",
        body + "gpt/moe_combine/dot_general")] == [
        "fwd/conv", "fwd/moe_route", "recompute/moe_dispatch",
        "bwd/kernel/moe_gmm", "bwd/moe_experts", "bwd/moe_combine"]


def test_new_readers_on_a_hand_made_join(monkeypatch):
    joined = {"seconds": {
        "fwd/conv": 0.5, "bwd/conv": 1.0, "recompute/conv": 0.5,
        "fwd/moe_experts": 0.25, "fwd/kernel/moe_gmm": 0.75,
        "bwd/kernel/moe_gmm": 2.0, "bwd/moe_route": 1.0, "fwd": 9.0},
        "total_s": 20.0, "scoped_s": 19.0}
    monkeypatch.setattr(scopes, "of", lambda context: joined)
    config = cells.load_config("lfm2-8b-a1b")
    context = {"cell": {"config": config}, "trace": object(),
               "device": {"kind": "TPU v5 lite"},
               "counters": {"trace_steps": 4, "moe_rows_per_step": 150000.0,
                            "moe_rows_traced": 4 * 131072.0,
                            "moe_load_max_over_mean": 1.25}}
    assert scope_sum_ms.read(context, ["conv"]) == pytest.approx(500.0)
    assert scope_sum_ms.read(context, [
        "moe_route", "moe_dispatch", "moe_experts", "moe_combine",
        "kernel/moe_gmm"]) == pytest.approx(1000.0)
    assert scope_sum_ms.read(context, ["attn"]) is None
    # the rows of the traced steps themselves, not the window's mean
    needed = 18 * 2048 * 1792 * 131072.0 * 4
    assert moe_gmm_roofline.read(context) == pytest.approx(
        100 * needed / 197e12 / 3.0)
    assert moe_gmm_roofline.read({**context, "counters": {
        "trace_steps": 4, "moe_rows_per_step": 150000.0}}) is None
    assert counter.read(context, "moe_load_max_over_mean") == 1.25
    assert counter.read(context, "nothing") is None
    # a program without the scopes or the counters (the parent commit)
    monkeypatch.setattr(scopes, "of", lambda context: None)
    bare = {**context, "counters": {}}
    assert scope_sum_ms.read(bare, ["conv"]) is None
    assert moe_gmm_roofline.read(bare) is None


def test_flash_share_counts_the_attention_layers_alone():
    config = cells.load_config("lfm2-8b-a1b")
    flash = ('%flash_fwd.25 = bf16[128,8192,64]{2,1,0} custom-call(%a), '
             'custom_call_target="tpu_custom_call"')
    gmm = ('%gmm.1 = bf16[131072,1792]{1,0} custom-call(%a), '
           'custom_call_target="tpu_custom_call"')
    context = {"cell": {"config": config}, "device": {"kind": "TPU v5 lite"},
               "trace": {"exclusive": {flash: 0.25, gmm: 1.0,
                                       "%fusion.1 = f32[8]": 2.0}},
               "counters": {"trace_steps": 4, "global_batch": 4, "chips": 1,
                            "sequence_tokens": 8192}}
    needed = 3 * 2 * 8192 ** 2 * 64 * 32 * 16       # ONE layer, 16 sequences
    assert flash_roofline_arch.read(context) == pytest.approx(
        100 * needed / 197e12 / 0.25)
    # no trace, no such kernel, or a configuration that names no count
    assert flash_roofline_arch.read({**context, "trace": None}) is None
    assert flash_roofline_arch.read(
        {**context, "trace": {"exclusive": {gmm: 1.0}}}) is None
    bare = {k: v for k, v in config.items() if k != "flops"}
    assert flash_roofline_arch.read(
        {**context, "cell": {"config": bare}}) is None


def test_comparison_on_hand_made_arrays():
    import jax.numpy as jnp
    from benchmark.drivers import train_arch
    ref_logits = jnp.asarray([[[0.0, 2.0], [0.0, 2.0], [0.0, 2.0]]])
    sys_logits = ref_logits.at[0, 1, 0].add(0.5).at[0, 2, 1].add(3.0)
    routing = {"margin": jnp.asarray([[[0.5, 0.5, 1e-4]], [[0.5, 0.5, 0.5]]]),
               "selected": jnp.asarray([[[[0, 1], [0, 1], [2, 3]]],
                                        [[[0, 1], [0, 1], [0, 1]]]])}
    chosen = jnp.asarray([[[[1, 0], [0, 1], [2, 4]]], [[[0, 1]] * 3]])
    out = train_arch.compare(1.0, sys_logits, chosen, 1.0, ref_logits,
                             routing)
    assert out["compared_share"] == pytest.approx(2 / 3)   # one near-tie
    assert out["same_choice_share"] == pytest.approx(5 / 6)
    assert out["logit_err_max"] == pytest.approx(0.5)       # rows' std is 1
    assert out["logit_err_max_all"] == pytest.approx(3.0)
    assert out["flips_over_margin"]["0.001"] == [pytest.approx(5 / 6), 0.0]
    assert out["loss_rel_err"] == 0.0


def test_cpu_rehearsal_of_the_driver_ends_in_a_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_REHEARSAL="1")
    env.pop("BENCH_RUN", None)
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "rehearsal-train-arch", "--seed", "3000000019", "--seconds", "1",
         "--trace", "1"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    records = [json.loads(x) for x in done.stdout.strip().splitlines()]
    line, check = records[-1], next(
        r for r in records if r.get("info") == "reference_check")
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"]["moe_load_max_over_mean.train"]["value"] >= 1.0
    summary = next(r for r in records if r.get("info") == "summary")
    # the toy's bfloat16 noise decides `reference`; everything else holds
    assert all(v for k, v in summary["checks"].items() if k != "reference")
    assert summary["checks"]["no_token_dropped"] is True
    assert set(check["grad_norm_rel_err"]) == {
        "router", "experts", "conv", "attention", "dense_mlp", "embedding"}
    assert 0 < check["compared_share"] <= 1
    assert check["logit_err_p50"] <= check["logit_err_p99"] <= check[
        "logit_err_max"]


def test_parent_without_the_cell_fails_at_once(tmp_path):
    """What the driver sees on the parent commit: no cell file, a
    non-zero exit before anything is imported that could hang."""
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "no-such-cell",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=120)
    assert done.returncode != 0 and "metrics" not in done.stdout
