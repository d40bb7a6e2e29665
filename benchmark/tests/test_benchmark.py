"""Tests of the benchmark itself (not of the program):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Few and fast, on the CPU.  Tier-1 collects ``tests/`` only.
"""
from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.lib import cells, flops, stats, trace, traffic  # noqa: E402

BENCH = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _names(kind):
    return sorted(f[:-5] for f in os.listdir(os.path.join(BENCH, kind))
                  if f.endswith(".json"))


# --------------------------------------------------------------------- #
# traffic                                                                #
# --------------------------------------------------------------------- #
def test_serve_traffic_is_seeded_clipped_and_the_same_work_for_every_seed():
    mix = cells.load_traffic("chat-closed32")
    a = traffic.serve_requests(mix, 3_000_000_019, 50304, n_blocks=2)
    b = traffic.serve_requests(mix, 3_000_000_019, 50304, n_blocks=2)
    c = traffic.serve_requests(mix, 5, 50304, n_blocks=2)
    assert len(a) == 2 * mix["block_requests"]
    assert all(np.array_equal(x.prompt, y.prompt)
               and x.max_new_tokens == y.max_new_tokens for x, y in zip(a, b))
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, c))
    p, o, s = mix["prompt_tokens"], mix["output_tokens"], mix["shared_prefix"]
    for r in a:
        assert p["min"] <= r.prompt.size <= p["max"]
        assert o["min"] <= r.max_new_tokens <= o["max"]
        assert r.prompt.size + r.max_new_tokens <= 1024
        assert r.prompt.dtype == np.int32 and 0 <= r.prompt.min()
        assert r.prompt.max() < 50304
    # every block of every seed holds the same multiset of shapes
    def shapes(reqs):
        return sorted((r.prompt.size, r.max_new_tokens, r.shared_prefix)
                      for r in reqs)
    n = mix["block_requests"]
    assert shapes(a[:n]) == shapes(a[n:]) == shapes(c[:n])
    with_prefix = [r for r in a[:n] if r.shared_prefix >= 0]
    assert len(with_prefix) == round(n * s["share"])
    firsts = {}
    for r in with_prefix:  # one system prompt per prefix id
        head = r.prompt[:s["tokens"]].tobytes()
        assert firsts.setdefault(r.shared_prefix, head) == head


def test_train_traffic_is_seeded_and_in_range():
    mix = cells.load_traffic("pretrain-1k")
    a = traffic.train_tokens(mix, 2 ** 31 + 11, 6, 1024, 50304)
    b = traffic.train_tokens(mix, 2 ** 31 + 11, 6, 1024, 50304)
    c = traffic.train_tokens(mix, 12, 6, 1024, 50304)
    assert a.shape == (6, 1024) and a.dtype == np.int32
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert 0 <= a.min() and a.max() < 50304
    assert (a < 100).mean() > 0.3      # Zipf: the head of the vocabulary


# --------------------------------------------------------------------- #
# metric arithmetic                                                      #
# --------------------------------------------------------------------- #
def test_percentile_tpot_and_spread_on_hand_made_samples():
    xs = [10, 20, 30, 40, 50]
    assert stats.percentile(xs, 50) == 30
    assert stats.percentile(xs, 95) == pytest.approx(48.0)
    assert stats.percentile(xs, 0) == 10 and stats.percentile(xs, 100) == 50
    assert stats.percentile(list(range(1, 101)), 95) == pytest.approx(95.05)
    with pytest.raises(ValueError):
        stats.percentile([], 50)
    assert stats.time_per_output_token(1.0, 3.0, 5) == pytest.approx(0.5)
    assert stats.time_per_output_token(1.0, 3.0, 1) is None
    # quartiles of statistics.quantiles (exclusive method): 1.75 and 5.25
    assert stats.spread([1, 2, 3, 4, 5, 6]) == pytest.approx(3.5 / 3.5)


def test_operation_counts():
    medium = cells.load_config("gpt2-medium")["model"]
    xl = cells.load_config("gpt2-xl")["model"]
    assert flops.n_params(medium) == 50304 * 1024 + 24 * (
        4 * 1024 ** 2 + 2 * 1024 * 4096 + 2048) + 1024
    assert 353e6 < flops.n_params(medium) < 356e6
    assert 1.55e9 < flops.n_params(xl) < 1.57e9
    assert flops.train_flops_per_token(medium, 1024) == pytest.approx(
        6 * flops.n_params(medium) + 12 * 24 * 1024 * 1024)
    fwd = flops.causal_attention_flops(medium, 1, 1024, backward=False)
    assert fwd == 2 * 1024 * 1024 * 64 * 16 * 24
    assert flops.causal_attention_flops(medium, 1, 1024, True) == 3 * fwd


# --------------------------------------------------------------------- #
# trace reduction                                                        #
# --------------------------------------------------------------------- #
def test_reduction_of_the_recorded_chip_trace():
    """A 65 KB trace recorded on a v5e (three calls of a small jitted
    step with flash attention forward and backward, PR 24)."""
    t = trace.load(os.path.join(BENCH, "tests", "data", "small.xplane.pb"))
    assert list(t.devices) == ["/device:TPU:0"]
    assert [s[0] for s in t.host_spans] == ["bench:step"] * 3
    r = trace.reduce(t)
    ops = t.devices["/device:TPU:0"]
    assert len(ops) == 36
    # no op nests in this trace: busy union == summed durations == exclusive
    total = sum(o.end - o.start for o in ops)
    assert r["busy_s"] == pytest.approx(total, rel=1e-6)
    assert sum(r["exclusive"].values()) == pytest.approx(total, rel=1e-6)
    assert 1.2e-3 < r["busy_s"] < 1.3e-3 and r["busy_s"] < r["window_s"]
    assert len(r["device_ops"]) == 10
    top = [name for name in r["exclusive"] if trace.is_pallas(name)]
    assert len(top) == 4 and all(
        trace.operand_shapes(n)[0] == (16, 1024, 64) for n in top)
    assert r["device_ops"][0][0].endswith(
        "custom-call pallas bf16[16,1024,64]")
    assert r["idle_gaps"][0][0] == "unattributed"


def test_exclusive_time_nesting_idle_gaps_and_exposed_collectives():
    from benchmark.readers import collective_exposed, device_idle

    def op(text, start, end):
        return trace.Op(text, start, end)

    ag = ("%all-gather-start.3 = (bf16[4,8]{1,0}, bf16[16,8]{1,0}) "
          "all-gather-start(bf16[4,8]{1,0} %p), dimensions={0}")
    ops = [
        op("%while.1 = (s32[], f32[8]{0}) while((s32[], f32[8]{0}) %t), "
           "body=%b", 1.0, 5.0),
        op("%fusion.2 = f32[8]{0:T(8)S(1)} fusion(f32[8]{0} %a), "
           "kind=kLoop", 1.0, 2.0),
        op(ag, 2.5, 3.0),
        op("%all-reduce.7 = f32[8]{0} all-reduce(f32[8]{0} %x), "
           "to_apply=%add", 6.0, 6.5),
    ]
    ex = dict((o.name, s) for o, s in trace.exclusive_times(ops))
    assert ex[ops[0].name] == pytest.approx(2.5)   # 4 s less 1 + 0.5 inside
    assert ex[ops[1].name] == pytest.approx(1.0)
    assert [trace.opcode(o.name) for o in ops] == [
        "while", "fusion", "all-gather-start", "all-reduce"]
    assert [trace.is_collective(o.name) for o in ops] == [
        False, False, True, True]
    t = trace.Trace({"/device:TPU:0": ops},
                    [("bench:window", 0.0, 8.0),
                     ("bench:epoch_boundary", 5.2, 5.9)])
    r = trace.reduce(t)
    assert r["window_s"] == 8.0 and r["busy_s"] == pytest.approx(4.5)
    gaps = dict(r["idle_gaps"])
    assert gaps["bench:epoch_boundary"] == pytest.approx(1.0)   # 5.0 - 6.0
    assert gaps["unattributed"] == pytest.approx(1.0 + 1.5)
    ctx = {"trace": r, "counters": {"chips": 4}}
    assert collective_exposed.read(ctx) == pytest.approx(100 * 1.0 / 8.0)
    assert device_idle.read(ctx) == pytest.approx(100 * 3.5 / 8.0)
    assert collective_exposed.read(
        {"trace": r, "counters": {"chips": 1}}) is None
    assert device_idle.read({"trace": None, "counters": {}}) is None


# --------------------------------------------------------------------- #
# the data files                                                         #
# --------------------------------------------------------------------- #
def test_every_data_file_loads_with_allowed_names_and_units():
    for kind in ("configs", "traffic", "workloads", "layer_metrics"):
        for name in _names(kind):
            assert NAME.match(name), (kind, name)
            assert isinstance(cells._load(kind, name), dict)
    for name in _names("workloads"):
        w = cells.load_workload(name)
        cells.load_config(w["config"]), cells.load_traffic(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(
            BENCH, "drivers", w["driver"] + ".py"))
        for metric in w.get("layer_metrics", ()):
            assert metric in _names("layer_metrics")
    for name in _names("layer_metrics"):
        m = cells._load("layer_metrics", name)
        assert m["name"] == name and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m["cells"]) <= set(_names("workloads"))
        assert os.path.exists(os.path.join(
            BENCH, "readers", m["reader"] + ".py"))


def test_benchmark_json_agrees_with_the_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark"]
    configs = {c["name"]: c for c in bench["configs"]}
    for c in bench["configs"]:
        on_disk = cells.load_config(c["name"])
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        assert c["source"] == on_disk["source"]
        assert c["reduced"] == on_disk["reduced"]
    listed = {w["name"]: w for w in bench["workloads"]}
    assert sum(w["chips"] == 4 for w in listed.values()) <= max(
        1, len(listed) // 4)
    for name, w in listed.items():
        on_disk = cells.load_workload(name)
        assert (w["config"], w["traffic"], w["chips"], w["why"]) == (
            on_disk["config"], on_disk["traffic"], on_disk["chips"],
            on_disk["why"])
        assert w["config"] in configs and NAME.match(w["traffic"])
    assert {w["config"] for w in listed.values()} == set(configs)
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert set(m.get("workloads", listed)) <= set(listed)
    assert all(m["bound"] <= 0.1 and m["source"] == "host_clock"
               for m in bench["end_to_end"])
    for m in bench["per_layer"]:
        on_disk = cells._load("layer_metrics", m["name"])
        for key in ("layer", "unit", "better", "source", "moves"):
            assert m[key] == on_disk[key], (m["name"], key)
        assert m["workloads"] == [c for c in on_disk["cells"]
                                  if c in listed]
        # reported only where the metric it moves is reported
        moved = e2e[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", listed))


# --------------------------------------------------------------------- #
# the command                                                            #
# --------------------------------------------------------------------- #
def _run(workload, trace_flag, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    full.pop("BENCH_RUN", None)
    if not env.get("BENCH_REHEARSAL"):
        full.pop("BENCH_REHEARSAL", None)
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", workload,
         "--seed", "3000000019", "--seconds", "1", "--trace",
         str(trace_flag)],
        cwd=ROOT, env=full, capture_output=True, text=True, timeout=600)


def test_no_tpu_no_result():
    done = _run("train-medium-1k", 0)
    assert done.returncode != 0
    assert "metrics" not in done.stdout and "no TPU" in done.stderr


@pytest.mark.parametrize("workload,trace_flag,flags", [
    ("rehearsal-train", 0, ""),
    ("rehearsal-serve", 0, ""),
    ("rehearsal-serve", 1, ""),
    ("rehearsal-train-fsdp4", 1,
     "--xla_force_host_platform_device_count=4"),
])
def test_cpu_rehearsal_ends_in_a_result_line(workload, trace_flag, flags):
    done = _run(workload, trace_flag, BENCH_REHEARSAL="1", XLA_FLAGS=flags)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(line) == RESULT_KEYS | {"rehearsal"}
    assert line["rehearsal"] is True and line["correct"] is True
    assert line["device"]["platform"] == "cpu"      # never a device number
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["metrics"], "no metric reported"
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"} and m["value"] > 0
        if trace_flag:
            assert name in _names("layer_metrics")
    if not trace_flag:
        assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2


def test_reference_agrees_with_the_program_in_float32():
    """The plain reference and the repo's GPT compute the same function:
    float32 on the CPU, where no kernel and no bfloat16 stands between
    them."""
    import jax
    import jax.numpy as jnp

    from benchmark.lib import reference

    model = cells.build_model(cells.load_config("rehearsal-tiny"), {})
    model.compute_dtype = jnp.float32
    params = model.init_params(jax.random.PRNGKey(0))
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 512, (2, 48)),
                         jnp.int32)
    ref = reference.logits(params, tokens)
    np.testing.assert_allclose(np.asarray(model.forward(params, tokens)),
                               np.asarray(ref), atol=2e-4, rtol=2e-4)
    loss, _ = model.training_step(params, tokens, None)
    assert float(loss) == pytest.approx(
        float(reference.lm_loss(ref, tokens)), rel=1e-5)
    assert float(reference.tie_margins(ref, jnp.concatenate(
        [tokens[:, :1], ref.argmax(-1)[:, :-1].astype(jnp.int32)], 1)
    ).max()) == 0.0
