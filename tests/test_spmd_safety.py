"""SPMD safety analyzer: the three static rules (collective axis
consistency, rank divergence, sharding inventory), the cross-rank
collective sanitizer + typed ``CollectiveMismatch`` across both wire
paths, the driver-side sequence checker seams (trainer fan-out /
elastic attempts), the sharding audit, and the graftlint CLI speed/JSON
satellites.

The acceptance loop: a fan-out where one rank traces a DIVERGENT
collective sequence (the silent-deadlock failure mode) surfaces as a
typed ``CollectiveMismatch`` whose diagnosis names the first divergent
call — instead of a generic wedge."""

import json
import os
import subprocess
import sys
import time
import types

import pytest

from ray_lightning_accelerators_tpu.analysis import lint as L
from ray_lightning_accelerators_tpu.testing import spmd_sanitizer as S

pytestmark = pytest.mark.spmd

PKG_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "ray_lightning_accelerators_tpu")
SCRIPTS = os.path.join(os.path.dirname(PKG_DIR), "scripts")

AXES = dict(spmd_axis_names=frozenset({"data", "fsdp", "tensor"}))


def _findings(sources, rule=None, **cfg_kw):
    cfg = L.LintConfig(**cfg_kw) if cfg_kw else L.LintConfig.for_tree(sources)
    out = L.run_lint(sources, cfg)
    if rule is not None:
        out = [f for f in out if f.rule == rule]
    return out


def _active(findings):
    return [f for f in findings if not f.suppressed]


# --------------------------------------------------------------------- #
# rule: spmd-collective                                                 #
# --------------------------------------------------------------------- #
MESH_SRC = '''
DATA_AXIS = "data"
FSDP_AXIS = "fsdp"
BATCH_AXES = (DATA_AXIS, FSDP_AXIS)
'''

SPMD_POSITIVE = '''
import jax

def bad_literal(x):
    return jax.lax.psum(x, "batch")          # undeclared axis name

def bad_tuple(x):
    return jax.lax.all_gather(x, ("data", "model"), axis=0, tiled=True)

def bad_unresolvable(x, cfg):
    axes = cfg.lookup()                      # opaque: not axis-derived
    return jax.lax.pmean(x, axes)

def bad_index():
    return jax.lax.axis_index("replica")     # undeclared, axis arg 0
'''

SPMD_NEGATIVE = '''
import jax
from .meshmod import BATCH_AXES, FSDP_AXIS

def dp_axes(mesh):
    return tuple(BATCH_AXES)                 # an axis function

def fine_literal(x):
    return jax.lax.psum(x, "data")

def fine_constants(x):
    own = jax.lax.axis_index(FSDP_AXIS)
    return jax.lax.all_gather(x, BATCH_AXES, axis=0, tiled=True) + own

def fine_derived(x, mesh):
    axes = dp_axes(mesh)
    data_axes = tuple(a for a in axes if a != FSDP_AXIS)
    part = jax.lax.psum(x, data_axes)
    return jax.lax.pmean(part, axes)

def fine_param(x, axis_name):
    # shard_map-body convention: the axis flows from checked call sites
    return jax.lax.psum(x, axis_name)

def fine_kwarg(x):
    return jax.lax.psum_scatter(x, axis_name="data", tiled=True)
'''


def test_spmd_collective_positives():
    found = _findings({"m.py": SPMD_POSITIVE}, rule="spmd-collective",
                      **AXES)
    active = _active(found)
    msgs = "\n".join(f.message for f in active)
    assert len(active) == 4, found
    assert "['batch']" in msgs
    assert "['model']" in msgs          # only the undeclared half named
    assert "does not resolve" in msgs   # the opaque cfg.lookup() case
    assert "['replica']" in msgs
    assert {f.line for f in active}     # positions populated


def test_spmd_collective_negatives():
    found = _findings({"meshmod.py": MESH_SRC, "m.py": SPMD_NEGATIVE},
                      rule="spmd-collective", **AXES)
    assert _active(found) == [], found


def test_spmd_collective_disabled_without_axis_registry():
    # no declared axes (default config, no axes module in the tree):
    # the rule stays silent instead of flagging everything
    found = _findings({"m.py": SPMD_POSITIVE}, rule="spmd-collective")
    assert found == []


def test_spmd_collective_pragma():
    src = ("import jax\n"
           "def f(x):\n"
           "    # graftlint: ok(spmd-collective) — test fixture axis\n"
           "    return jax.lax.psum(x, 'weird')\n")
    found = _findings({"m.py": src}, rule="spmd-collective", **AXES)
    assert found and all(f.suppressed for f in found)


# --------------------------------------------------------------------- #
# rule: rank-divergence                                                 #
# --------------------------------------------------------------------- #
RANK_POSITIVE = '''
import time
import random
import jax
from jax.experimental import multihost_utils

def gated_collective(x):
    if jax.process_index() == 0:             # rank branch over psum
        x = jax.lax.psum(x, "data")
    return x

def gated_barrier():
    r = jax.process_index()
    if r != 0:                               # via a rank-valued local
        multihost_utils.sync_global_devices("x")

def gated_commit(state, save_sharded):
    if jax.process_index() == 0:
        save_sharded("/ckpt", state, {})     # collective commit, gated

@jax.jit
def nondet_step(x):
    return x * time.time()                   # trace-time host value

def outer():
    def body(x):
        return x + _jitter()
    return jax.jit(body)

def _jitter():
    return random.random()                   # reachable from jitted body
'''

RANK_NEGATIVE = '''
import time
import jax
from jax.experimental import multihost_utils

def count_gated():
    if jax.process_count() > 1:              # uniform across ranks: fine
        multihost_utils.sync_global_devices("ok")

def rank_gated_logging(metrics, log):
    if jax.process_index() == 0:             # host-local work only
        log.info("metrics: %s", metrics)

def host_timing():
    t0 = time.monotonic()                    # not under trace
    return time.monotonic() - t0

@jax.jit
def clean_step(x, rng):
    noise = jax.random.normal(rng, x.shape)  # seeded PRNG: fine
    return x + noise
'''


def test_rank_divergence_positives():
    found = _findings({"m.py": RANK_POSITIVE}, rule="rank-divergence")
    active = _active(found)
    msgs = "\n".join(f.message for f in active)
    assert "collective lax.psum" in msgs
    assert "sync_global_devices" in msgs
    assert "checkpoint commit 'save_sharded'" in msgs
    assert "time.time" in msgs and "TRACE time" in msgs
    assert "random.random" in msgs  # through the within-module closure
    assert len(active) >= 5, found


def test_rank_divergence_flags_elif_arms():
    """Regression (review finding): an elif/else arm of a rank-gated if
    executes only on the COMPLEMENT rank subset — equally divergent."""
    src = ("import jax\n"
           "def f(x, flag):\n"
           "    if jax.process_index() == 0:\n"
           "        pass\n"
           "    elif flag:\n"
           "        x = jax.lax.psum(x, 'data')\n"
           "    return x\n"
           "def g(x):\n"
           "    if jax.process_index() == 0:\n"
           "        pass\n"
           "    else:\n"
           "        x = jax.lax.psum(x, 'data')\n"
           "    return x\n")
    found = _active(_findings({"m.py": src}, rule="rank-divergence"))
    # both the elif and the else spelling are caught
    assert len(found) >= 2, found


def test_rank_divergence_negatives():
    found = _findings({"m.py": RANK_NEGATIVE}, rule="rank-divergence")
    assert _active(found) == [], found


def test_rank_divergence_pragma():
    src = ("import jax\n"
           "def f(state, save_sharded):\n"
           "    # graftlint: ok(rank-divergence) — single-writer meta\n"
           "    if jax.process_index() == 0:\n"
           "        save_sharded('/p', state, {})\n")
    found = _findings({"m.py": src}, rule="rank-divergence")
    assert found and all(f.suppressed for f in found)


# --------------------------------------------------------------------- #
# rule: sharding-inventory                                              #
# --------------------------------------------------------------------- #
SPEC_SRC = '''
import jax
from jax.sharding import PartitionSpec as P

PS = jax.sharding.PartitionSpec

def layouts():
    a = P("data", None)                      # imported-alias spelling
    b = jax.sharding.PartitionSpec(None)     # dotted spelling
    c = PS("fsdp")                           # local-alias spelling
    return a, b, c
'''


def test_sharding_inventory_flags_uninventoried_modules():
    found = _findings({"models/thing.py": SPEC_SRC},
                      rule="sharding-inventory")
    active = _active(found)
    assert len(active) == 3, found  # all three spellings caught
    assert all("uninventoried" in f.message for f in active)


def test_sharding_inventory_allows_inventoried_modules():
    for key in ("parallel/sharding.py", "core/trainer.py",
                "accelerators/base.py"):
        found = _findings({key: SPEC_SRC}, rule="sharding-inventory")
        assert found == [], (key, found)


def test_sharding_inventory_pragma():
    src = ("from jax.sharding import PartitionSpec as P\n"
           "# graftlint: ok(sharding-inventory) — test fixture layout\n"
           "spec = P('data')\n")
    found = _findings({"serve/engine.py": src}, rule="sharding-inventory")
    assert found and all(f.suppressed for f in found)


# --------------------------------------------------------------------- #
# the real tree: new rules enabled, clean, and genuinely firing         #
# --------------------------------------------------------------------- #
def test_tree_is_clean_with_spmd_rules_and_they_fire():
    findings = L.lint_path(PKG_DIR)
    for rule in ("spmd-collective", "rank-divergence",
                 "sharding-inventory"):
        assert [f for f in findings
                if f.rule == rule and not f.suppressed] == [], rule
    # the inventory + divergence rules genuinely fire on this tree
    # (deliberate, pragma'd violations — the paper trail)
    assert any(f.rule == "sharding-inventory" and f.suppressed
               for f in findings)
    assert any(f.rule == "rank-divergence" and f.suppressed
               for f in findings)


# --------------------------------------------------------------------- #
# graftlint CLI satellites: JSON output + parse cache                   #
# --------------------------------------------------------------------- #
def test_cli_format_json_on_tree():
    script = os.path.join(SCRIPTS, "graftlint.py")
    proc = subprocess.run(
        [sys.executable, script, PKG_DIR, "--format", "json"],
        capture_output=True, text=True, timeout=180)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["schema"] == 1 and payload["active"] == 0
    assert payload["exit_code"] == 0 and payload["suppressed"] > 0
    rows = payload["findings"]
    assert rows and all(
        set(r) >= {"rule", "path", "line", "col", "message", "suppressed"}
        for r in rows)


def test_cli_format_json_nonzero_on_violation(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import os\nv = os.environ.get('RLA_TPU_OOPS')\n")
    script = os.path.join(SCRIPTS, "graftlint.py")
    proc = subprocess.run(
        [sys.executable, script, str(bad), "--format", "json"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1
    payload = json.loads(proc.stdout)  # JSON still lands on violation
    assert payload["exit_code"] == 1
    assert any(r["rule"] == "knob-registry" for r in payload["findings"])


def test_parse_cache_is_mtime_keyed(tmp_path):
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "__init__.py").write_text("")
    target = pkg / "mod.py"
    target.write_text("import os\nx = os.environ.get('RLA_TPU_NOPE')\n")
    L.lint_path(str(pkg))
    path = str(target)
    assert path in L._MODULE_CACHE
    first = L._MODULE_CACHE[path][3]
    L.lint_path(str(pkg))
    assert L._MODULE_CACHE[path][3] is first  # cache hit: same object
    # a rewrite (new mtime) reparses — and the findings track the edit
    time.sleep(0.01)
    target.write_text("import os\nx = os.environ.get('XLA_FLAGS')\n")
    found = L.lint_path(str(pkg))
    assert L._MODULE_CACHE[path][3] is not first
    assert not any(f.rule == "knob-registry" for f in found)


# --------------------------------------------------------------------- #
# sanitizer: interception, ring, spill                                  #
# --------------------------------------------------------------------- #
def test_sanitizer_records_traced_collectives(spmd_sanitizer):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P


    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))

    def f(x):
        own = jax.lax.axis_index("data")
        return jax.lax.psum(x, "data") + own

    out = jax.shard_map(f, mesh=mesh, in_specs=P("data"),
                        out_specs=P("data"),
                        check_vma=False)(jnp.arange(4, dtype=jnp.float32))
    assert out.shape == (4,)
    san = spmd_sanitizer.get_sanitizer()
    seq = san.sequence()
    ops = [e["op"] for e in seq]
    assert "axis_index" in ops and "psum" in ops, seq
    psum = seq[ops.index("psum")]
    assert psum["axes"] == ["data"]
    assert psum["dtype"] == "float32"
    assert psum["site"] and "test_spmd_safety.py" in psum["site"]
    # spill landed under the fixture's telemetry dir, driver-labeled
    snaps = spmd_sanitizer.gather_sequences()
    assert "driver" in snaps
    assert [e["op"] for e in snaps["driver"]["events"]] == ops
    # each record also mirrors into the flight recorder's timeline (the
    # sanitizer's own spill stays the authoritative diff channel)
    from ray_lightning_accelerators_tpu.telemetry import recorder as R
    kinds = [e["kind"] for e in R.get_recorder().events()]
    assert "spmd_collective" in kinds


def test_sanitizer_uninstall_restores_jax_lax(spmd_sanitizer):
    import jax
    assert getattr(jax.lax.psum, "_rla_spmd_wrapped", False)
    spmd_sanitizer.uninstall()
    assert not getattr(jax.lax.psum, "_rla_spmd_wrapped", False)
    assert spmd_sanitizer.get_sanitizer() is None
    # double-uninstall is a no-op; fixture teardown tolerates it too
    spmd_sanitizer.uninstall()


def test_sanitizer_reinstall_rebinds_ring_without_double_wrap():
    import jax
    try:
        a = S.install(S.SpmdSanitizer(capacity=8))
        b = S.install(S.SpmdSanitizer(capacity=8))
        jax.lax.axis_index  # patched attr exists
        # one wrapper layer only: recording goes to the NEW ring
        S.get_sanitizer()
        assert S.get_sanitizer() is b
        b.record("psum", "data")
        assert a.sequence() == []
        assert len(b.sequence()) == 1
    finally:
        S.uninstall()
    assert not getattr(jax.lax.psum, "_rla_spmd_wrapped", False)


def test_sanitizer_ring_keeps_absolute_indices():
    san = S.SpmdSanitizer(capacity=4)
    for i in range(10):
        san.record("psum", "data", site=f"m.py:{i}")
    seq = san.sequence()
    assert len(seq) == 4
    assert [e["i"] for e in seq] == [6, 7, 8, 9]
    assert san.snapshot()["n"] == 10


def test_maybe_install_honors_knob(monkeypatch):
    monkeypatch.delenv(S.SANITIZER_ENV, raising=False)
    assert S.maybe_install_from_env() is None
    try:
        san = S.maybe_install_from_env(
            rank=3, env={S.SANITIZER_ENV: "1",
                         "RLA_TPU_SPMD_SEQ_EVENTS": "16"})
        assert san is not None and san.capacity == 16 and san.rank == 3
    finally:
        S.uninstall()


# --------------------------------------------------------------------- #
# checker: diff + typed CollectiveMismatch                              #
# --------------------------------------------------------------------- #
def _seq_snapshot(rank, ops, start=0):
    events = [{"i": start + j, "op": op, "axes": ["data"], "shape": [4],
               "dtype": "float32",
               "site": f"parallel/x.py:{10 + start + j}"}
              for j, op in enumerate(ops)]
    return {"rank": rank, "pid": 1, "n": start + len(ops),
            "capacity": 512, "events": events}


def _write_seq(tdir, rank, ops, start=0):
    os.makedirs(str(tdir), exist_ok=True)
    path = os.path.join(str(tdir), f"rank{rank}.collectives.json")
    with open(path, "w") as f:
        json.dump(_seq_snapshot(rank, ops, start), f)


def test_diff_sequences_agreement_and_divergence():
    same = {"rank0": _seq_snapshot(0, ["psum", "all_gather"]),
            "rank1": _seq_snapshot(1, ["psum", "all_gather"])}
    assert S.diff_sequences(same) is None
    div = {"rank0": _seq_snapshot(0, ["psum", "all_gather"]),
           "rank1": _seq_snapshot(1, ["psum", "pmean"])}
    d = S.diff_sequences(div)
    assert d["first_divergence"] == 1
    assert d["per_rank"]["rank0"]["op"] == "all_gather"
    assert d["per_rank"]["rank1"]["op"] == "pmean"
    # one rank's stream ENDING early is a divergence too
    short = {"rank0": _seq_snapshot(0, ["psum", "pmean"]),
             "rank1": _seq_snapshot(1, ["psum"])}
    d = S.diff_sequences(short)
    assert d["first_divergence"] == 1
    assert d["per_rank"]["rank1"] is None
    # fewer than two rank sequences: nothing to diff (driver excluded)
    assert S.diff_sequences({"rank0": _seq_snapshot(0, ["psum"]),
                             "driver": _seq_snapshot(None, [])}) is None


def test_diff_sequences_aligns_after_ring_drop():
    # rank0's ring dropped entries 0..5; overlap still compares aligned
    full = _seq_snapshot(0, ["pmean"] * 4, start=6)
    other = _seq_snapshot(1, ["psum"] * 6 + ["pmean"] * 4)
    assert S.diff_sequences({"rank0": full, "rank1": other}) is None
    diverged = _seq_snapshot(1, ["psum"] * 6 + ["pmean"] * 3
                             + ["all_gather"])
    d = S.diff_sequences({"rank0": full, "rank1": diverged})
    assert d["first_divergence"] == 9 and d["ring_dropped"]


def test_checker_raises_typed_mismatch(tmp_path):
    _write_seq(tmp_path, 0, ["psum", "all_gather"])
    _write_seq(tmp_path, 1, ["psum", "pmean"])
    with pytest.raises(S.CollectiveMismatch) as ei:
        S.check_collective_sequences(str(tmp_path))
    exc = ei.value
    assert exc.diagnosis["first_divergence"] == 1
    assert "all_gather" in str(exc) and "pmean" in str(exc)
    assert "parallel/x.py:11" in str(exc)  # the divergent call SITE
    # non-raising form for postmortem seams
    back = S.check_collective_sequences(str(tmp_path),
                                        raise_on_mismatch=False)
    assert isinstance(back, S.CollectiveMismatch)


def test_clear_spills_removes_only_sequence_files(tmp_path):
    _write_seq(tmp_path, 0, ["psum"])
    _write_seq(tmp_path, 3, ["pmean"])
    other = os.path.join(str(tmp_path), "rank0.events.json")
    with open(other, "w") as f:
        f.write("{}")
    S.clear_spills(str(tmp_path))
    assert S.gather_sequences(str(tmp_path)) == {}
    assert os.path.exists(other)  # flight-recorder spills untouched


def test_elastic_decodes_hangs_only(tmp_path, monkeypatch):
    """The elastic seam must never read a crash-truncated spill as a
    deterministic divergence — only hang-shaped failures decode."""
    from ray_lightning_accelerators_tpu.runtime.elastic import ElasticRunner
    from ray_lightning_accelerators_tpu.runtime.watchdog import WorkerWedged
    _write_seq(tmp_path, 0, ["psum", "all_gather"])
    _write_seq(tmp_path, 1, ["psum"])           # truncated mid-trace
    monkeypatch.setenv(S.SANITIZER_ENV, "1")
    monkeypatch.setenv("RLA_TPU_TELEMETRY_DIR", str(tmp_path))
    runner = ElasticRunner(types.SimpleNamespace(workers=[]))
    assert runner._collective_mismatch(RuntimeError("worker died")) is None
    wedge = WorkerWedged.for_rank(1, {"detail": "stuck"})
    got = runner._collective_mismatch(wedge)
    assert isinstance(got, S.CollectiveMismatch)


def test_check_world_collectives_is_gated(tmp_path, monkeypatch):
    _write_seq(tmp_path, 0, ["psum"])
    _write_seq(tmp_path, 1, ["pmean"])
    monkeypatch.setenv("RLA_TPU_TELEMETRY_DIR", str(tmp_path))
    monkeypatch.delenv(S.SANITIZER_ENV, raising=False)
    assert S.check_world_collectives() is None   # knob off: no-op
    monkeypatch.setenv(S.SANITIZER_ENV, "1")
    with pytest.raises(S.CollectiveMismatch):
        S.check_world_collectives()


# --------------------------------------------------------------------- #
# wire: CollectiveMismatch crosses the local pipe AND the agent relay   #
# --------------------------------------------------------------------- #
def _raise_mismatch():
    from ray_lightning_accelerators_tpu.testing.spmd_sanitizer import (
        CollectiveMismatch)
    raise CollectiveMismatch.from_divergence({
        "first_divergence": 2,
        "per_rank": {"rank0": {"op": "psum", "axes": ["data"],
                               "shape": [8], "dtype": "float32",
                               "site": "parallel/collectives.py:200"},
                     "rank1": None},
        "lengths": {"rank0": 3, "rank1": 2}})


def test_mismatch_rebuilds_typed_over_local_pipe():
    from ray_lightning_accelerators_tpu.runtime.actors import ActorPool
    with ActorPool(1) as pool:
        fut = pool.execute_all(_raise_mismatch)[0]
        with pytest.raises(S.CollectiveMismatch) as ei:
            fut.result(timeout=120)
    exc = ei.value
    assert exc.remote_typed  # rebuilt from the wire payload
    assert exc.diagnosis["first_divergence"] == 2
    assert exc.diagnosis["per_rank"]["rank1"] is None


def test_mismatch_rebuilds_typed_over_agent_relay():
    from ray_lightning_accelerators_tpu.runtime.agent import (HostAgent,
                                                              RemoteWorker)
    agent = HostAgent(port=0, bind="127.0.0.1")
    agent.serve_in_background()
    w = None
    try:
        w = RemoteWorker(f"127.0.0.1:{agent.port}", rank=0)
        with pytest.raises(S.CollectiveMismatch) as ei:
            w.execute(_raise_mismatch).result(timeout=120)
        exc = ei.value
        assert exc.remote_typed
        assert exc.diagnosis["first_divergence"] == 2
        assert "collectives.py:200" in str(exc)
    finally:
        if w is not None:
            w.kill()
        agent.shutdown()


def test_wire_registry_roundtrips_every_name():
    """Registry<->rebuilder consistency, now including the sanitizer's
    type: every registered name rebuilds to ITS class (the shared
    rebuild_remote both the local collector and the agent relay call)."""
    from ray_lightning_accelerators_tpu.runtime import wire
    assert set(wire.WIRE_EXCEPTION_NAMES) == set(wire._rebuilders())
    assert "CollectiveMismatch" in wire.WIRE_EXCEPTION_NAMES
    for name, build in wire._rebuilders().items():
        sample = (S.CollectiveMismatch.from_divergence(
            {"first_divergence": 0, "per_rank": {}})
            if name == "CollectiveMismatch" else None)
        msg = str(sample) if sample is not None else f"{name}: boom"
        back = wire.rebuild_remote(name, msg, "tb")
        assert type(back).__name__ == name, (name, type(back))
        assert back.remote_typed


# --------------------------------------------------------------------- #
# fan-out acceptance: injected rank-divergent collective               #
# --------------------------------------------------------------------- #
def _trace_rank_collectives(rank, divergent_rank):
    """Worker body: trace a tiny shard_map program whose collective
    sequence DEPENDS ON THE RANK when rank == divergent_rank — the
    injected drift the sanitizer exists to catch.  The sanitizer was
    installed at worker boot from the env overlay."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P


    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))

    def f(x):
        y = jax.lax.psum(x, "data")
        if rank == divergent_rank:   # the rank-divergent collective
            y = jax.lax.pmean(y, "data")
        return y

    out = jax.shard_map(f, mesh=mesh, in_specs=P(None),
                        out_specs=P(None),
                        check_vma=False)(jnp.ones((4,), jnp.float32))
    return float(np.asarray(out)[0])


def _sanitizer_env(tdir):
    return {"RLA_TPU_SPMD_SANITIZER": "1",
            "RLA_TPU_TELEMETRY_DIR": str(tdir)}


def test_fanout_divergence_caught_typed(tmp_path):
    """Two workers trace rank-dependent collective sequences; the
    driver's post-run diff raises the typed CollectiveMismatch naming
    the first divergent call."""
    from ray_lightning_accelerators_tpu.runtime.actors import ActorPool
    env = _sanitizer_env(tmp_path)
    with ActorPool(2, env_per_worker=[dict(env), dict(env)]) as pool:
        futs = pool.execute_per_worker(_trace_rank_collectives,
                                       [(0, 1), (1, 1)])
        assert [f.result(timeout=300) for f in futs] == [1.0, 1.0]
        snaps = S.gather_sequences(str(tmp_path))
        assert set(snaps) == {"rank0", "rank1"}
        with pytest.raises(S.CollectiveMismatch) as ei:
            S.check_collective_sequences(str(tmp_path))
    diag = ei.value.diagnosis
    assert diag["first_divergence"] == 1
    assert diag["per_rank"]["rank0"] is None      # rank0 never made call 1
    assert diag["per_rank"]["rank1"]["op"] == "pmean"
    assert "test_spmd_safety.py" in diag["per_rank"]["rank1"]["site"]


def _warm_jax():
    import jax
    return len(jax.devices())


def _divergent_then_hang(rank):
    _trace_rank_collectives(rank, 1)
    if rank == 1:
        time.sleep(3600)   # the deadlock the divergence would cause
    return rank


@pytest.mark.chaos
def test_elastic_wedge_decodes_to_collective_mismatch(tmp_path,
                                                      monkeypatch):
    """THE acceptance loop: a chaos-style run where the rank-divergent
    rank hangs (as a real mismatched collective would) is reaped as a
    wedge — and the ElasticRunner surfaces the typed CollectiveMismatch
    postmortem TERMINALLY instead of burning retries on a deterministic
    divergence."""
    from ray_lightning_accelerators_tpu.runtime.actors import ActorPool
    from ray_lightning_accelerators_tpu.runtime.elastic import ElasticRunner
    env = _sanitizer_env(tmp_path)
    env["RLA_TPU_WORKER_HEARTBEAT_S"] = "0.05"
    # the driver-side checker reads the same knobs from the process env
    for k, v in _sanitizer_env(tmp_path).items():
        monkeypatch.setenv(k, v)
    pool = ActorPool(2, env_per_worker=[dict(env), dict(env)])
    try:
        for f in pool.execute_all(_warm_jax):   # jax import off the clock
            f.result(timeout=300)
        runner = ElasticRunner(pool, max_failures=2,
                               dispatch_deadline_s=6.0,
                               watchdog_poll_s=0.1)
        with pytest.raises(S.CollectiveMismatch) as ei:
            runner.run(_divergent_then_hang,
                       args_per_worker=lambda a: [(r,) for r in range(2)])
        diag = ei.value.diagnosis
        assert diag["first_divergence"] == 1
        assert diag["per_rank"]["rank1"]["op"] == "pmean"
        # terminal, not retried: the wedge burned ONE attempt
        assert runner.attempts_used == 1
        assert isinstance(ei.value.__cause__, BaseException)
    finally:
        pool.shutdown()


# --------------------------------------------------------------------- #
# trainer seam: fan-out failure decodes to the typed mismatch          #
# --------------------------------------------------------------------- #
class _SeqWorld:
    """Fake world: 'workers' write their (divergent) collective spills
    DURING run() — after the seam's run-entry spill reset, exactly like
    real tracing workers — then wedge or complete."""

    last_stall = ()

    def __init__(self, tdir, wedge=False):
        self.tdir = tdir
        self.wedge = wedge
        self.shut = False

    def run(self, body, queue=None, deadline_s=None):
        _write_seq(self.tdir, 0, ["psum", "all_gather"])
        _write_seq(self.tdir, 1, ["psum", "pmean"])
        if self.wedge:
            from ray_lightning_accelerators_tpu.runtime.watchdog import (
                WorkerWedged)
            raise WorkerWedged.for_rank(1, {"detail": "stopped making "
                                                      "progress"})
        return [{"ok": True}, {"ok": True}]

    def shutdown(self):
        self.shut = True


def _seam_trainer(tmp_path):
    from ray_lightning_accelerators_tpu import Trainer
    return Trainer(max_steps=1, precision="f32", seed=0,
                   enable_checkpointing=False,
                   default_root_dir=str(tmp_path))


def test_trainer_wedge_decodes_to_mismatch(tmp_path, monkeypatch):
    tdir = tmp_path / "telemetry"
    # a STALE spill from a previous run: the run-entry reset must clear
    # it so only what "this run's workers" write below is diffed
    _write_seq(tdir, 7, ["all_to_all"])
    monkeypatch.setenv(S.SANITIZER_ENV, "1")
    monkeypatch.setenv("RLA_TPU_TELEMETRY_DIR", str(tdir))
    trainer = _seam_trainer(tmp_path)
    module = types.SimpleNamespace()
    from ray_lightning_accelerators_tpu.runtime.watchdog import WorkerWedged
    with pytest.raises(S.CollectiveMismatch) as ei:
        trainer._run_in_world(_SeqWorld(tdir, wedge=True), module,
                              None, None)
    # chained off the wedge: both the decoded cause and the raw reap
    # survive in one postmortem
    assert isinstance(ei.value.__cause__, WorkerWedged)
    diag = ei.value.diagnosis
    assert diag["per_rank"]["rank1"]["op"] == "pmean"
    assert "rank7" not in diag["per_rank"]  # stale spill was cleared
    # the failure report carries the DECODED error type
    rep = json.load(open(os.path.join(str(tmp_path), "run_report.json")))
    assert rep["error"]["type"] == "CollectiveMismatch"


def test_trainer_completed_run_still_checked(tmp_path, monkeypatch):
    tdir = tmp_path / "telemetry"
    monkeypatch.setenv(S.SANITIZER_ENV, "1")
    monkeypatch.setenv("RLA_TPU_TELEMETRY_DIR", str(tdir))
    trainer = _seam_trainer(tmp_path)
    world = _SeqWorld(tdir)
    with pytest.raises(S.CollectiveMismatch):
        trainer._run_in_world(world, types.SimpleNamespace(), None, None)
    # unlike the failure path, the world was still ALIVE: the seam must
    # end it, not leak it
    assert world.shut
    # knob off: the same divergent spills are ignored (opt-in contract)
    monkeypatch.delenv(S.SANITIZER_ENV)
    trainer2 = _seam_trainer(tmp_path)
    out = trainer2._run_in_world(_SeqWorld(tdir), types.SimpleNamespace(),
                                 None, None)
    assert out == [{"ok": True}, {"ok": True}]


def test_trainer_crash_failures_are_not_decoded(tmp_path, monkeypatch):
    """A CRASH-shaped failure legitimately truncates a rank's spill
    mid-trace: it must stay the original (retryable) error, never read
    as a deterministic collective divergence."""
    tdir = tmp_path / "telemetry"
    monkeypatch.setenv(S.SANITIZER_ENV, "1")
    monkeypatch.setenv("RLA_TPU_TELEMETRY_DIR", str(tdir))

    class _CrashWorld(_SeqWorld):
        def run(self, body, queue=None, deadline_s=None):
            _write_seq(self.tdir, 0, ["psum", "all_gather"])
            _write_seq(self.tdir, 1, ["psum"])   # truncated mid-trace
            raise RuntimeError("worker 1 died")

    trainer = _seam_trainer(tmp_path)
    with pytest.raises(RuntimeError, match="worker 1 died"):
        trainer._run_in_world(_CrashWorld(tdir), types.SimpleNamespace(),
                              None, None)


# --------------------------------------------------------------------- #
# sharding audit                                                        #
# --------------------------------------------------------------------- #
def test_sharding_audit_inventory_covers_parallel_modules(tmp_path):
    out = tmp_path / "inv.json"
    script = os.path.join(SCRIPTS, "sharding_audit.py")
    proc = subprocess.run(
        [sys.executable, script, "--out", str(out), "--quiet"],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    record = json.loads(proc.stdout.strip().splitlines()[-1])
    assert record["kind"] == "sharding_audit"
    assert "value" not in record  # bench-parser contract: value-less
    assert record["uninventoried"] == 0
    # --skip-drift (the format.sh mode: graftlint already gated) skips
    # the lint pass and says so in the record
    proc = subprocess.run(
        [sys.executable, script, "--no-write", "--quiet", "--skip-drift"],
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0
    rec2 = json.loads(proc.stdout.strip().splitlines()[-1])
    assert rec2["uninventoried"] is None
    inv = json.load(open(str(out)))
    assert inv["schema"] == 1
    for mod in ("parallel/collectives.py", "parallel/sharding.py",
                "parallel/ulysses.py", "parallel/ring_attention.py",
                "parallel/pipeline.py"):
        assert mod in inv["modules"], mod
        assert not inv["modules"][mod].get("missing")
    assert inv["totals"]["partition_spec_literals"] > 10
    assert set(inv["axis_names"]) >= {"data", "fsdp", "pipeline",
                                      "sequence", "tensor", "expert"}
    assert inv["uninventoried"] == []
    # committed artifact stays in sync with the tree (format.sh rewrites
    # it; a stale checkout diff shows up in review)
    committed = os.path.join(os.path.dirname(PKG_DIR),
                             "SHARDING_INVENTORY.json")
    assert os.path.exists(committed)
    assert json.load(open(committed))["totals"] == inv["totals"]


def test_sharding_audit_drift_exits_nonzero(monkeypatch):
    """An uninventoried PartitionSpec literal fails the audit (the
    format.sh gate): exercised through main() with the lint findings
    injected, so no package mutation is needed."""
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "_audit_for_test", os.path.join(SCRIPTS, "sharding_audit.py"))
    audit = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(audit)
    monkeypatch.setattr(audit, "drift_findings", lambda lint: [
        {"rule": "sharding-inventory", "path": "serve/engine.py",
         "line": 10, "col": 0, "suppressed": False,
         "message": "PartitionSpec literal in uninventoried module"}])
    assert audit.main(["--no-write", "--quiet"]) == 1
    monkeypatch.setattr(audit, "drift_findings", lambda lint: [])
    assert audit.main(["--no-write", "--quiet"]) == 0
