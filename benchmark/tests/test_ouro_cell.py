"""Tests of what PR 33 added to the benchmark (the ``ouro-2.6b``
configuration, its cell, ``drivers/train_looped.py``,
``lib/flops_ouro.py``, ``lib/reference_ouro.py`` and three per-layer
metrics):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_ouro_cell.py -q
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

from benchmark.drivers import train_looped as driver  # noqa: E402
from benchmark.lib import cells, flops_ouro, scopes  # noqa: E402
from benchmark.readers import counter, scope_sum_ms  # noqa: E402

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NAME, CELL = "ouro-2.6b", "train-ouro-loop-8k"
# no width is cut: hidden, head and feed-forward sizes, the heads, the
# vocabulary and the number of passes are the published ones
WIDTHS = ("hidden_size", "head_dim", "intermediate_size",
          "num_attention_heads", "num_key_value_heads", "vocab_size",
          "total_ut_steps")
NEW_METRICS = ("loop_carry_ms.train", "loop_exit_ms.train",
               "loop_exit_entropy.train")


def test_configuration_file_is_the_published_one_cut_as_it_says():
    config = cells.load_config(NAME)
    published, reduced = config["published"], set(config["reduced"])
    assert reduced == {"num_hidden_layers", "layer_types"} == set(
        config["reduced_how"])
    for key, value in published.items():
        assert (config[key] == value) != (key in reduced), key
    assert not reduced & set(WIDTHS)
    layers = config["num_hidden_layers"]
    assert layers >= 4 and published["num_hidden_layers"] % layers == 0
    assert config["layer_types"] == published["layer_types"][:layers]
    assert f"one pipeline stage of {layers} layers a chip" in config[
        "deployment"]
    if os.path.exists(CATALOG):
        with open(CATALOG) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Ouro-2.6B")
        assert published == row["config"]
        assert config["source"] == row["source_url"]
    # the model group states the same sizes to the program
    m = config["model"]
    assert (m["d_model"], m["attn_head_dim"], m["d_ff"], m["n_heads"],
            m["vocab_size"], m["loop_passes"], m["norm_eps"],
            m["rope_theta"], m["tie_embeddings"]) == tuple(
        published[k] for k in (
            "hidden_size", "head_dim", "intermediate_size",
            "num_attention_heads", "vocab_size", "total_ut_steps",
            "rms_norm_eps", "rope_theta", "tie_word_embeddings"))
    assert published["num_key_value_heads"] == m["n_heads"]
    assert "n_kv_heads" not in m and m["n_layers"] == layers
    assert (m["post_norms"], m["exit_gate"], m["exit_beta"],
            m["gated_mlp"], m["rope_style"]) == (True, True, 0.05, True,
                                                 "half")
    assert published["hidden_act"] == "silu"
    assert m["max_seq_len"] <= published["max_position_embeddings"]
    for key in ("sandwich_norm", "final_norm_between_passes", "exit_gate",
                "loss", "attention_bias", "weight_decay", "init",
                "positions", "max_window_layers"):
        assert key in config["assumed"], key


def test_files_agree_with_benchmark_json():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = cells.load_workload(CELL)
    listed = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert bench["workloads"][-1] is listed       # appended, not inserted
    assert bench["configs"][-1]["name"] == NAME
    assert (listed["config"], listed["traffic"], listed["chips"]) == (
        NAME, "pretrain-8k", 1) == (cell["config"], cell["traffic"],
                                    cell["chips"])
    assert cell["driver"] == "train_looped" and len(cell["why"]) <= 200
    settings = cell["settings"]
    assert (settings["per_chip_batch"], settings["steps_per_epoch"],
            settings["remat"], settings["flash_block"],
            settings["loss_chunk_rows"], settings["lr"], settings["guard"],
            settings["warm_epochs"], settings["trace_epochs"]) == (
        1, 4, True, 1024, 2048, 3e-5, "auto", 1, 1)
    assert "weights_seed" not in settings       # weights from --seed
    per_layer = {m["name"]: m for m in bench["per_layer"]}
    assert [m["name"] for m in bench["per_layer"][-3:]] == list(NEW_METRICS)
    reported = {m["name"] for m in cells.load_layer_metrics(CELL, cell)}
    for name in NEW_METRICS:
        assert per_layer[name]["workloads"] == [CELL]
        assert per_layer[name]["moves"] == "train_tok_s_chip"
        assert per_layer[name]["layer"] == \
            "layer stack (models/transformer.py)"
        assert name in reported
    # the twelve general metrics and the k-walk's share, as the Nemotron
    # cell's own list has them
    assert cell["layer_metrics"] == cells.load_workload(
        "train-nemotron3-ssm-8k")["layer_metrics"]
    assert set(cell["layer_metrics"]) <= reported


def test_reference_copy_is_the_packages():
    with open(os.path.join(ROOT, "ray_lightning_accelerators_tpu", "models",
                           "reference_ouro.py")) as f:
        package = f.read()
    with open(os.path.join(ROOT, "benchmark", "lib",
                           "reference_ouro.py")) as f:
        assert f.read() == package


def test_operation_counts_against_a_hand_count():
    """At a toy size by hand, then the cut model against ISSUE 33's
    arithmetic and the program's own tree."""
    toy = cells.load_config("rehearsal-ouro-tiny")["model"]
    d, attn, ff, vocab, layers, passes = 64, 4 * 24, 160, 512, 2, 3
    layer = 4 * d * attn + 3 * d * ff
    assert flops_ouro.n_params(toy) == (
        layers * (layer + 4 * d) + 2 * vocab * d + d + d + 1)
    multiplied = passes * (layers * layer + vocab * d) + (passes - 1) * d
    assert flops_ouro.params_multiplied_per_token(toy) == multiplied
    assert flops_ouro.train_flops_per_token(toy, 128) == pytest.approx(
        6 * multiplied + 6 * passes * layers * attn * 128)
    fwd = flops_ouro.causal_attention_flops(toy, 2, 128, backward=False)
    assert fwd == 2 * 128 * 128 * 24 * 4 * 2 * passes * layers
    assert flops_ouro.causal_attention_flops(toy, 2, 128, True) == 3 * fwd

    config = cells.load_config(NAME)
    model = config["model"]
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632 + 4 * 2048
    assert layer == 51_388_416 == config["bytes"]["layer"]    # ISSUE 33
    held = model["n_layers"] * layer + 201_326_592 + 2048 + 2049
    assert flops_ouro.n_params(model) == held == config["bytes"][
        "parameters_held"]
    import jax
    program = cells.build_model(config, {})
    shapes = jax.eval_shape(program.init_params, jax.random.PRNGKey(0))
    assert sum(leaf.size for leaf in jax.tree.leaves(shapes)) == held
    # 6 x (4 passes x (layers + head) + 3 gates) + the causal triangle
    applications = 4 * model["n_layers"]
    assert flops_ouro.train_flops_per_token(model, 8192) == pytest.approx(
        6 * (applications * (layer - 4 * 2048) + 4 * 100_663_296 + 3 * 2048)
        + 6 * applications * 2048 * 8192)
    # the count of the reader as it stands: heads of d_model / n_heads
    assert model["d_model"] // model["n_heads"] == model["attn_head_dim"]
    assert flops_ouro.causal_attention_flops(
        model, 4, 8192, backward=True) == \
        3 * 2 * 8192 ** 2 * 128 * 16 * 4 * applications


def test_new_scopes_fall_into_buckets_without_an_edit():
    body = "jit(e)/jvp(gpt/loop)/while/body/closed_call/"
    back = "jit(e)/transpose(jvp(gpt/loop))/while/body/closed_call/"
    assert [scopes.bucket(n) for n in (
        "jit(e)/jvp(gpt/loop)/while/body/dynamic_update_slice",
        back + "add_any",
        body + "gpt/layers/while/body/dynamic_update_slice",
        body + "gpt/layers/while/body/closed_call/gpt/attn/dot_general",
        body + "gpt/norm/kernel/rms_norm/pallas_call",
        back + "gpt/layers/while/body/checkpoint/rematted_computation/"
               "gpt/mlp/dot_general",
        "jit(e)/jvp(gpt/loop_exit)/log_sigmoid",
        "jit(e)/transpose(jvp(gpt/loop_exit))/mul",
        "jit(e)/jvp(gpt/loss)/while/body/dot_general")] == [
        "fwd/loop", "bwd/loop", "fwd/layers", "fwd/attn",
        "fwd/kernel/rms_norm", "recompute/mlp", "fwd/loop_exit",
        "bwd/loop_exit", "loss"]


def test_new_metrics_on_a_hand_made_join(monkeypatch):
    joined = {"seconds": {
        "fwd/loop": 0.5, "bwd/loop": 1.5, "recompute/loop": 0.25,
        "fwd/loop_exit": 0.125, "bwd/loop_exit": 0.125,
        "fwd/layers": 3.0, "fwd": 9.0}, "total_s": 20.0, "scoped_s": 19.0}
    monkeypatch.setattr(scopes, "of", lambda context: joined)
    context = {"cell": {"config": cells.load_config(NAME)},
               "trace": object(), "device": {"kind": "TPU v5 lite"},
               "counters": {"trace_steps": 4, "loop_exit_entropy": 0.875,
                            "loop_exit_entropy_pct": 87.5}}
    by_name = {m["name"]: m for m in cells.load_layer_metrics(
        CELL, cells.load_workload(CELL))}

    def read(name, ctx=context):
        import importlib
        metric = by_name[name]
        return importlib.import_module(
            "benchmark.readers." + metric["reader"]).read(
                ctx, **metric.get("args", {}))

    assert read("loop_carry_ms.train") == pytest.approx(2250.0 / 4)
    assert read("loop_exit_ms.train") == pytest.approx(250.0 / 4)
    assert read("loop_exit_entropy.train") == 87.5
    # a program without the scopes or the counters (the parent commit)
    assert read("loop_exit_entropy.train",
                {**context, "counters": {}}) is None
    assert counter.read({**context, "counters": {}},
                        "loop_exit_entropy_pct") is None
    monkeypatch.setattr(scopes, "of", lambda context: {
        "seconds": {"fwd/layers": 3.0}, "total_s": 4.0, "scoped_s": 3.0})
    assert scope_sum_ms.read(context, ["loop"]) is None
    monkeypatch.setattr(scopes, "of", lambda context: None)
    assert read("loop_carry_ms.train") is None


def test_comparison_on_hand_made_arrays():
    import jax.numpy as jnp
    ref_logits = jnp.asarray([[[0.0, 2.0], [0.0, 2.0], [0.0, 2.0]]])
    sys_logits = ref_logits.at[0, 1, 0].add(0.3).at[0, 2, 1].add(3.0)
    ref = {"loss": 2.0, "pass_logits_loss": jnp.asarray([2.0, 4.0]),
           "exit_p": jnp.asarray([[[0.5, 0.25]], [[0.5, 0.75]]])}
    out = driver.compare(2.0002, jnp.asarray([2.0, 4.004]), sys_logits,
                         ref["exit_p"].at[0, 0, 1].add(0.01), ref_logits,
                         ref)
    assert out["loss_rel_err"] == pytest.approx(1e-4, rel=1e-3)
    assert out["pass_loss_rel_err"] == pytest.approx(1e-3, rel=1e-3)
    assert out["logit_err_max"] == pytest.approx(3.0)       # rows' std is 1
    assert out["logit_err_p50"] == pytest.approx(0.3)
    assert out["exit_p_err_max"] == pytest.approx(0.01, rel=1e-3)
    assert out["exit_mean_pass"] == pytest.approx(1.625)
    both = driver.merge([out, {**out, "loss": 4.0002, "logit_err_p99": 9.0,
                               "pass_loss": [4.0, 6.0]}])
    assert both["loss"] == pytest.approx(3.0002)
    assert both["logit_err_p99"] == 9.0
    assert both["pass_loss"] == pytest.approx([3.0, 5.002], rel=1e-6)
    good = {"loss": 2.0, "loss_rel_err": 0.0, "pass_loss_rel_err": 0.0,
            "logit_err_p50": 0.0, "logit_err_p99": 0.0,
            "exit_p_err_max": 0.0, "grad_norm_rel_err": {"mlp": 0.0}}
    assert driver.passes(good)
    for key, limit in (("loss_rel_err", driver.TOL_LOSS),
                       ("pass_loss_rel_err", driver.TOL_PASS_LOSS),
                       ("logit_err_p50", driver.TOL_LOGITS_P50),
                       ("logit_err_p99", driver.TOL_LOGITS_P99),
                       ("exit_p_err_max", driver.TOL_EXIT_P)):
        assert driver.passes({**good, key: limit})
        assert not driver.passes({**good, key: 1.01 * limit}), key
    assert not driver.passes({**good, "grad_norm_rel_err": {
        "mlp": 1.01 * driver.TOL_GRAD_NORM}})
    assert driver.passes({**good, "grad_norm_rel_err": {
        "gate": driver.TOL_GRAD_NORM_GATE}})
    assert not driver.passes({**good, "grad_norm_rel_err": {
        "gate": 1.01 * driver.TOL_GRAD_NORM_GATE}})
    assert not driver.passes({**good, "loss": float("nan")})
    assert driver.grad_norm_errors({"a": 1.1, "b": 0.0, "c": 1.0},
                                   {"a": 1.0, "b": 0.0, "c": 0.0}) == {
        "a": pytest.approx(0.1), "b": 0.0, "c": float("inf")}


# --------------------------------------------------------------------- #
# what the comparison catches, at the tiny size in float32               #
# --------------------------------------------------------------------- #
def _tiny_system(omission=None):
    """The rehearsal configuration's GPT in float32 with seeded weights
    off their initial values, and, under ``omission``, one part of the
    mathematics left out of the PROGRAM's side."""
    import jax
    import jax.numpy as jnp

    from ray_lightning_accelerators_tpu.models.transformer import (
        GPT, TransformerConfig)

    model_cfg = cells.load_config("rehearsal-ouro-tiny")["model"]
    kw = dict(model_cfg, fused_loss=True, loss_chunk_rows=64)
    if omission == "entropy_term":
        kw["exit_beta"] = 0.0

    class Omitting(GPT):
        """The pass loop in plain Python, leaving out what it is told."""

        def _run_stacks(self, params, h, dropout_rng=None):
            if omission not in ("pass", "final_norm_between_passes"):
                return super()._run_stacks(params, h, dropout_rng)
            cfg, pos, states = self.cfg, jnp.arange(h.shape[1]), []
            for _ in range(cfg.loop_passes - (omission == "pass")):
                for i in range(cfg.n_layers):
                    h, _, _ = self._block(
                        h, jax.tree.map(lambda a: a[i], params["layers"]),
                        pos, "attn", "dense")
                states.append(self._rms_norm(h, params["ln_f"]))
                if omission != "final_norm_between_passes":
                    h = states[-1]
            states += states[-1:] * (omission == "pass")
            return states[-1], {"loop_hidden": jnp.stack(states)}

    model = Omitting(TransformerConfig(**kw), lr=1e-3)
    model.compute_dtype = jnp.float32
    params = jax.jit(model.init_params)(jax.random.PRNGKey(0))
    params = jax.tree.map(
        lambda a: a + 0.1 * jax.random.normal(
            jax.random.PRNGKey(a.size), a.shape), params)
    params["exit_gate"]["b"] = jnp.full((1,), 0.5)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 128), 0,
                                model_cfg["vocab_size"])
    system_params = params
    if omission == "gate_bias":
        system_params = {**params, "exit_gate": {
            **params["exit_gate"], "b": jnp.zeros((1,))}}
    return model, system_params, params, tokens, model_cfg


@pytest.mark.parametrize("omission", [
    None, "pass", "final_norm_between_passes", "gate_bias", "entropy_term"])
def test_each_omission_fails_the_comparison(omission, monkeypatch):
    """The driver's own check, with the cell's limits: the program in
    float32 passes; a pass left out, the final norm left out between
    passes, a gate without its bias or a loss without its entropy term
    each fails at least one limit."""
    from benchmark.lib import reference_ouro

    model, system_params, params, tokens, model_cfg = _tiny_system(omission)
    # the reference reads the true weights whatever the program was given
    real = reference_ouro.forward, reference_ouro.loss_and_grads
    monkeypatch.setattr(reference_ouro, "forward",
                        lambda p, t, m: real[0](params, t, m))
    monkeypatch.setattr(reference_ouro, "loss_and_grads",
                        lambda p, t, m: real[1](params, t, m))
    check = driver._reference_check(model, system_params, tokens,
                                    reference_ouro, model_cfg)
    assert check["ok"] is (omission is None), check
    failed = [key for key, limit in (
        ("loss_rel_err", driver.TOL_LOSS),
        ("pass_loss_rel_err", driver.TOL_PASS_LOSS),
        ("logit_err_p50", driver.TOL_LOGITS_P50),
        ("logit_err_p99", driver.TOL_LOGITS_P99),
        ("exit_p_err_max", driver.TOL_EXIT_P)) if check[key] > limit]
    if omission is None:
        assert not failed
    elif omission == "entropy_term":
        # everything but the objective and its gradient is untouched
        assert failed == ["loss_rel_err"]
    elif omission == "gate_bias":
        assert "exit_p_err_max" in failed and "logit_err_p50" not in failed
    else:
        assert {"pass_loss_rel_err", "logit_err_p50"} <= set(failed)


def test_cpu_rehearsal_of_the_driver_ends_in_a_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_REHEARSAL="1")
    env.pop("BENCH_RUN", None)
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload",
         "rehearsal-train-ouro", "--seed", "3000000019", "--seconds",
         "1", "--trace", "1"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    records = [json.loads(x) for x in done.stdout.strip().splitlines()]
    line, check = records[-1], next(
        r for r in records if r.get("info") == "reference_check")
    assert line["rehearsal"] is True and line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    # the counter's metric is read on the CPU too; the two scope sums
    # need a device trace
    assert 0 < line["metrics"]["loop_exit_entropy.train"]["value"] <= 100
    assert line["metrics"]["loop_exit_entropy.train"]["unit"] == "%"
    summary = next(r for r in records if r.get("info") == "summary")
    # the toy's bfloat16 noise decides `reference`; everything else holds
    assert set(summary["checks"]) == {
        "reference", "losses_finite", "loss_fell", "no_compile_in_window",
        "every_step_logged"}
    assert all(v for k, v in summary["checks"].items() if k != "reference")
    assert set(check["grad_norm_rel_err"]) == {
        "embedding", "head", "attention", "mlp", "norms", "gate"}
    assert len(check["pass_loss"]) == 3
    assert check["logit_err_p50"] <= check["logit_err_p99"] <= check[
        "logit_err_max"]
    train = next(r for r in records if r.get("info") == "train")
    assert {"loop_loss_pass_1", "loop_loss_pass_2", "loop_loss_pass_3",
            "loop_exit_mean_pass", "loop_exit_entropy"} <= set(train)
    assert 1.0 <= train["loop_exit_mean_pass"] <= 3.0
