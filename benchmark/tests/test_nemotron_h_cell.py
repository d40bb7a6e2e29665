"""Tests of what PR 31 added to the benchmark (the
``nemotron-3-super-120b-a12b`` configuration, its cell,
``drivers/train_nemotron_h.py``, ``lib/flops_nemotron_h.py``,
``readers/ssm_scan_roofline.py`` and five per-layer metrics):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_nemotron_h_cell.py -q
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

from benchmark.lib import cells, flops_nemotron_h, scopes  # noqa: E402
from benchmark.readers import (counter, flash_roofline_arch,  # noqa: E402
                               moe_gmm_roofline, scope_sum_ms,
                               ssm_scan_roofline)

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAME, CELL = "nemotron-3-super-120b-a12b", "train-nemotron3-ssm-8k"
# no width is cut: sizes of heads, states, latents and experts, the
# router's outputs and its experts a token
WIDTHS = ("hidden_size", "head_dim", "mamba_head_dim", "ssm_state_size",
          "conv_kernel", "chunk_size", "expand", "intermediate_size",
          "moe_intermediate_size", "moe_latent_size",
          "moe_shared_expert_intermediate_size", "num_experts_per_tok",
          "n_shared_experts", "routed_scaling_factor")
NEW_METRICS = ("ssm_ms.train", "ssm_scan_roofline.train",
               "latent_moe_ms.train", "latent_gmm_roofline.train",
               "latent_moe_load_max_over_mean.train")


def test_configuration_file_is_the_published_one_cut_as_it_says():
    config = cells.load_config(NAME)
    published, reduced = config["published"], set(config["reduced"])
    assert reduced == {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "mamba_num_heads", "n_groups", "num_attention_heads",
        "num_key_value_heads", "vocab_size", "num_nextn_predict_layers"}
    assert reduced == set(config["reduced_how"])
    for key, value in published.items():
        assert (config[key] == value) != (key in reduced), key
    assert not reduced & set(WIDTHS)
    assert config["hybrid_override_pattern"] == published[
        "hybrid_override_pattern"][26:37] == "EMEMEMEMEM*"
    assert "64 chips share each layer" in config["deployment"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
        assert published == row["config"]
        assert config["source"] == row["source_url"]
    # the model group states the same sizes to the program, and of each
    # layer the published count beside the share held
    m = config["model"]
    assert (m["d_model"], m["attn_head_dim"], m["ssm_head_dim"],
            m["ssm_state"], m["conv_kernel"], m["ssm_chunk"], m["moe_d_ff"],
            m["moe_latent_dim"], m["moe_shared_d_ff"], m["moe_top_k"],
            m["moe_routed_scale"], m["norm_eps"]) == tuple(published[k] for k in (
                "hidden_size", "head_dim", "mamba_head_dim", "ssm_state_size",
                "conv_kernel", "chunk_size", "moe_intermediate_size",
                "moe_latent_size", "moe_shared_expert_intermediate_size",
                "num_experts_per_tok", "routed_scaling_factor", "norm_eps"))
    assert m["ssm_heads"] * m["ssm_head_dim"] == published["expand"] * m[
        "d_model"]
    assert (m["num_experts"], m["n_heads"], m["n_kv_heads"], m["ssm_heads"],
            m["ssm_groups"]) == tuple(published[k] for k in (
                "n_routed_experts", "num_attention_heads",
                "num_key_value_heads", "mamba_num_heads", "n_groups"))
    assert m["moe_experts_held"] == list(range(config["n_routed_experts"]))
    assert m["attn_heads_held"] == list(range(config["num_attention_heads"]))
    assert len(m["ssm_groups_held"]) == config["n_groups"]
    assert len(m["ssm_groups_held"]) * m["ssm_heads"] // m[
        "ssm_groups"] == config["mamba_num_heads"]
    assert (m["n_layers"], m["hybrid_pattern"], m["vocab_size"],
            m["tie_embeddings"]) == (
        config["num_hidden_layers"], config["hybrid_override_pattern"],
        config["vocab_size"], published["tie_word_embeddings"])
    # the step size's initial range is the program's own constant
    from ray_lightning_accelerators_tpu.ops import ssm
    assert (ssm.DT_MIN, ssm.DT_MAX, ssm.DT_FLOOR) == tuple(
        published[k] for k in ("time_step_min", "time_step_max",
                               "time_step_floor"))


def test_files_agree_with_benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = cells.load_workload(CELL)
    listed = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (listed["config"], listed["traffic"], listed["chips"]) == (
        NAME, "pretrain-8k", 1) == (cell["config"], cell["traffic"],
                                    cell["chips"])
    assert cell["driver"] == "train_nemotron_h"
    settings = cell["settings"]
    assert (settings["steps_per_epoch"], settings["remat"],
            settings["flash_block"], settings["loss_chunk_rows"],
            settings["lr"], settings["guard"], settings["warm_epochs"],
            settings["trace_epochs"]) == (4, True, 1024, 2048, 3e-5, "auto",
                                          1, 1)
    assert "weights_seed" in settings
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    reported = {m["name"] for m in cells.load_layer_metrics(CELL, cell)}
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "train_tok_s_chip"
        assert name in reported
    # the twelve general metrics of the LFM2 cell's file, and the k-walk
    assert set(cells.load_workload("train-lfm2-moe-8k")["layer_metrics"]) \
        | {"flash_kwalk_roofline.train"} == set(cell["layer_metrics"])
    assert set(cell["layer_metrics"]) <= reported


def test_reference_copy_is_the_packages():
    with open(os.path.join(ROOT, "ray_lightning_accelerators_tpu", "models",
                           "reference_nemotron_h.py")) as f:
        package = f.read()
    with open(os.path.join(ROOT, "benchmark", "lib",
                           "reference_nemotron_h.py")) as f:
        assert f.read() == package


def test_operation_counts_against_a_hand_count():
    """At a toy size by hand, then the cut model against ISSUE 31's
    arithmetic and the program's own tree."""
    toy = cells.load_config("rehearsal-nemotron-tiny")["model"]
    d, inner, conv, heads = 64, 4 * 8, 4 * 8 + 2 * 2 * 16, 4
    mamba = d * (inner + conv + heads) + inner * d
    mamba_rest = conv * 4 + conv + 3 * heads + inner + d
    attn, latent = 2 * d * 2 * 8 + 2 * d * 8, d * 16 + 2 * d * 32 + 2 * d * 48
    expert = 2 * 32 * 24
    assert flops_nemotron_h.n_params(toy) == (
        2 * 512 * d + d + 5 * (mamba + mamba_rest) + (attn + d)
        + 5 * (latent + d + 4 * expert))
    rows = 1.25
    active = 512 * d + 5 * mamba + attn + 5 * latent + expert * rows
    scan = 3 * (2 * 16 * 16 + 2 * 16 * 8 + 4 * 16 * 8) * heads * 5
    assert flops_nemotron_h.train_flops_per_token(toy, 128, rows) == \
        pytest.approx(6 * active + 12 * 2 * 8 * 128 + scan)
    ops, nbytes = flops_nemotron_h.ssd_scan_work(toy, 1000)
    assert ops == scan * 1000
    assert nbytes == 2 * 2 * (2 * inner + 2 * 2 * 16 + heads) * 1000 * 5

    model = cells.load_config(NAME)["model"]
    assert flops_nemotron_h.n_params(model) == 700_862_960   # ISSUE 31: 700.9M
    import jax
    program = cells.build_model(cells.load_config(NAME), {})
    shapes = jax.eval_shape(program.init_params, jax.random.PRNGKey(0))
    held = sum(leaf.size for path, leaf in
               jax.tree_util.tree_flatten_with_path(shapes)[0]
               if "expert_bias" not in jax.tree_util.keystr(path))
    assert held == 700_862_960
    assert cells.load_config(NAME)["bytes"]["parameters_held"] == held
    # 22 of 512 chosen, 8 held: 0.34 rows a layer a token
    per_token = flops_nemotron_h.train_flops_per_token(model, 8192,
                                                       5 * 22 * 8 / 512)
    assert 2.55e9 < per_token < 2.65e9              # ISSUE 31: ~2.6 GF
    assert flops_nemotron_h.expert_matmul_flops(model, 1000) == \
        12 * 1024 * 2688 * 1000
    # one attention layer: 4 held heads of 128 over 8192^2 / 2
    assert flops_nemotron_h.causal_attention_flops(
        model, 4, 8192, backward=True) == 3 * 2 * 8192 ** 2 * 128 * 4 * 4
    # a token and head forward: 2 Q N + 2 Q P + 4 N P = 81,920
    ops, nbytes = flops_nemotron_h.ssd_scan_work(model, 1.0)
    assert ops == 3 * 81_920 * 16 * 5
    assert nbytes == 2 * 2 * (1024 + 1024 + 128 + 128 + 16) * 5
    assert ops / 197e12 > nbytes / 819e9            # compute is the larger


def test_new_scopes_fall_into_buckets_without_an_edit():
    body = "jit(e)/transpose(jvp(gpt/layers))/while/body/closed_call/"
    assert [scopes.bucket(n) for n in (
        "jit(e)/jvp(gpt/layers)/while/body/closed_call/gpt/ssm/dot_general",
        "jit(e)/jvp(gpt/layers)/while/body/gpt/ssm/gpt/ssm_scan/exp",
        body + "checkpoint/rematted_computation/gpt/moe_latent/dot_general",
        body + "gpt/moe_shared/mul",
        body + "gpt/ssm/gpt/ssm_scan/kernel/ssm_scan/pallas_call")] == [
        "fwd/ssm", "fwd/ssm_scan", "recompute/moe_latent", "bwd/moe_shared",
        "bwd/kernel/ssm_scan"]


def test_new_metrics_on_a_hand_made_join(monkeypatch):
    joined = {"seconds": {
        "fwd/ssm": 0.5, "bwd/ssm": 1.0, "fwd/ssm_scan": 0.25,
        "recompute/ssm_scan": 0.25, "bwd/ssm_scan": 0.5,
        "fwd/moe_latent": 0.5, "bwd/moe_shared": 1.5,
        "fwd/kernel/moe_gmm": 0.5, "bwd/moe_experts": 0.5, "fwd": 9.0},
        "total_s": 20.0, "scoped_s": 19.0}
    monkeypatch.setattr(scopes, "of", lambda context: joined)
    config = cells.load_config(NAME)
    context = {"cell": {"config": config}, "trace": object(),
               "device": {"kind": "TPU v5 lite"},
               "counters": {"trace_steps": 4, "global_batch": 1, "chips": 1,
                            "sequence_tokens": 8192,
                            "moe_rows_traced": 4 * 14080.0,
                            "moe_load_max_over_mean": 1.5}}
    by_name = {m["name"]: m for m in cells.load_layer_metrics(
        CELL, cells.load_workload(CELL))}

    def read(name):
        import importlib
        metric = by_name[name]
        return importlib.import_module(
            "benchmark.readers." + metric["reader"]).read(
                context, **metric.get("args", {}))

    assert read("ssm_ms.train") == pytest.approx(2500.0 / 4)
    assert read("latent_moe_ms.train") == pytest.approx(3000.0 / 4)
    assert read("latent_moe_load_max_over_mean.train") == 1.5
    assert read("latent_gmm_roofline.train") == pytest.approx(
        100 * 12 * 1024 * 2688 * 4 * 14080.0 / 197e12 / 1.0)
    # 4 steps of 8192 tokens, 5 layers, 16 heads: compute-bound
    assert read("ssm_scan_roofline.train") == pytest.approx(
        100 * 3 * 81_920 * 16 * 5 * 4 * 8192 / 197e12 / 1.0)
    # a program without the scopes or the counters (the parent commit),
    # a configuration whose count knows no scan (another cell)
    monkeypatch.setattr(scopes, "of", lambda context: None)
    assert ssm_scan_roofline.read({**context, "counters": {}}) is None
    assert scope_sum_ms.read(context, ["ssm"]) is None
    assert moe_gmm_roofline.read({**context, "counters": {}}) is None
    assert counter.read({**context, "counters": {}},
                        "moe_load_max_over_mean") is None
    monkeypatch.setattr(scopes, "of", lambda context: joined)
    lfm2 = {**context, "cell": {"config": cells.load_config("lfm2-8b-a1b")}}
    assert ssm_scan_roofline.read(lfm2) is None


def test_flash_share_reads_the_held_heads_at_the_published_head_size():
    """``readers/flash_roofline_arch.py`` takes the head size as d_model /
    n_heads: the model group keeps the published 32 heads (4 held), so
    that is the published 128."""
    config = cells.load_config(NAME)
    flash = ('%flash_fwd.25 = bf16[4,8192,128]{2,1,0} custom-call(%a), '
             'custom_call_target="tpu_custom_call"')
    context = {"cell": {"config": config}, "device": {"kind": "TPU v5 lite"},
               "trace": {"exclusive": {flash: 0.25, "%fusion.1 = f32[8]": 2}},
               "counters": {"trace_steps": 4, "global_batch": 1, "chips": 1,
                            "sequence_tokens": 8192}}
    needed = 3 * 2 * 8192 ** 2 * 128 * 4 * 4        # ONE layer, 4 sequences
    assert flash_roofline_arch.read(context) == pytest.approx(
        100 * needed / 197e12 / 0.25)


def test_comparison_on_hand_made_arrays():
    import jax.numpy as jnp
    from benchmark.drivers import train_nemotron_h as driver
    ref_logits = jnp.asarray([[[0.0, 2.0], [0.0, 2.0], [0.0, 2.0]]])
    sys_logits = ref_logits.at[0, 1, 0].add(0.3).at[0, 2, 1].add(3.0)
    # two layers, three positions, top-2 of 6; experts 0 and 1 held
    routing = {
        "held_margin": jnp.asarray([[[0.5, 0.5, 1e-4]], [[0.5, 0.5, 0.5]]]),
        "margin": jnp.zeros((2, 1, 3)),
        "selected": jnp.asarray([[[[0, 4], [2, 3], [1, 5]]],
                                 [[[0, 1], [0, 1], [4, 5]]]])}
    # position 2 of layer 0 loses held expert 1; an absent expert's swap
    # (4 for 3 at position 1) is no held expert's business
    chosen = jnp.asarray([[[[4, 0], [2, 4], [3, 5]]],
                          [[[1, 0], [0, 1], [5, 4]]]])
    out = driver.compare(1.0, sys_logits, chosen, 1.0, ref_logits, routing,
                         held=(0, 1))
    assert out["compared_share"] == pytest.approx(2 / 3)   # one near-tie
    assert out["held_choice_agreement"] == pytest.approx(11 / 12)
    assert out["held_choice_overlap"] == pytest.approx(5 / 6)
    assert out["reference_held_rows_per_token"] == pytest.approx(2.0)
    assert out["logit_err_max"] == pytest.approx(0.3)       # rows' std is 1
    assert out["logit_err_max_all"] == pytest.approx(3.0)
    assert out["loss_rel_err"] == 0.0
    good = {**out, "grad_norm_rel_err": {"ssm": 0.001, "router": 0.0}}
    assert driver.passes({**good, "held_choice_agreement": 1.0})
    assert not driver.passes(good)          # 11 of 12 is under the limit
    assert not driver.passes({**good, "held_choice_agreement": 1.0,
                              "grad_norm_rel_err": {"ssm": 0.5}})


def test_cpu_rehearsal_of_the_driver_ends_in_a_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_REHEARSAL="1")
    env.pop("BENCH_RUN", None)
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "rehearsal-train-nemotron", "--seed", "3000000019", "--seconds",
         "1", "--trace", "1"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    records = [json.loads(x) for x in done.stdout.strip().splitlines()]
    line, check = records[-1], next(
        r for r in records if r.get("info") == "reference_check")
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"]["latent_moe_load_max_over_mean.train"][
        "value"] >= 1.0
    summary = next(r for r in records if r.get("info") == "summary")
    # the toy's bfloat16 noise decides `reference`; everything else holds
    assert all(v for k, v in summary["checks"].items() if k != "reference")
    assert summary["checks"]["no_token_dropped"] is True
    assert set(check["grad_norm_rel_err"]) == {
        "router", "experts", "latent", "shared", "ssm", "attention",
        "embedding", "head"}
    assert 0 < check["compared_share"] <= 1
    assert 0.9 < check["held_choice_agreement"] <= 1
    assert check["logit_err_p50"] <= check["logit_err_p99"] <= check[
        "logit_err_max"]
    # the driver left train_arch's own comparison where it was
    from benchmark.drivers import train_arch
    assert train_arch.compare.__module__ == "benchmark.drivers.train_arch"
