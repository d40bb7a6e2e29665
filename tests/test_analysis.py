"""graftlint + compile-guard: the analyzer's rules on fixture snippets
(positive / negative / pragma-suppressed per rule), the knobs/wire
registries, and the runtime compile-count invariants — the serve
engine's 3-program lifecycle and the trainer's zero-retrace-after-
warmup.  All CPU, tier-1 fast."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_lightning_accelerators_tpu.analysis import knobs
from ray_lightning_accelerators_tpu.analysis import lint as L
from ray_lightning_accelerators_tpu.analysis.compile_guard import (
    CompileBudgetExceeded, compile_count, compile_guard)

pytestmark = pytest.mark.analysis

PKG_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "ray_lightning_accelerators_tpu")


def _findings(sources, rule=None, **cfg_kw):
    cfg = L.LintConfig(**cfg_kw) if cfg_kw else L.LintConfig.for_tree(sources)
    out = L.run_lint(sources, cfg)
    if rule is not None:
        out = [f for f in out if f.rule == rule]
    return out


def _active(findings):
    return [f for f in findings if not f.suppressed]


# --------------------------------------------------------------------- #
# host-sync                                                             #
# --------------------------------------------------------------------- #
HOT_CFG = dict(hot_roots={"hot.py": ("Engine.run",)})

HOT_POSITIVE = '''
import jax
import jax.numpy as jnp
import numpy as np

class Engine:
    def run(self, x):
        y = jnp.sum(x)
        a = float(y)                 # float on a device value
        b = y.item()                 # .item()
        c = np.asarray(y)            # host materialization
        d = jax.device_get(y)        # device_get
        jax.block_until_ready(y)     # block
        self.helper(y)
        return a, b, c, d

    def helper(self, y):
        return float(jnp.exp(y))     # reachable via self.run -> helper
'''

HOT_NEGATIVE = '''
import jax.numpy as jnp
import numpy as np

class Engine:
    def run(self, xs):
        n = int(len(xs))             # host int, not a device value
        toks = np.zeros((4,), np.int32)  # host buffer construction
        return jnp.sum(jnp.asarray(toks)) + n

class Cold:
    def elsewhere(self, y):
        return float(jnp.sum(y))     # not reachable from a hot root
'''


def test_host_sync_positives():
    found = _findings({"hot.py": HOT_POSITIVE}, rule="host-sync", **HOT_CFG)
    lines = {f.line for f in _active(found)}
    # float / item / asarray / device_get / block + the helper's float
    assert len(_active(found)) >= 6, found
    assert any(f.message.startswith("'float") for f in found)
    assert any(".item()" in f.message for f in found)
    assert any("np.asarray" in f.message for f in found)
    assert any("device_get" in f.message for f in found)
    assert any("Engine.helper" in f.message for f in found), \
        "reachability must follow self-method calls"
    assert all(f.path == "hot.py" for f in found)
    assert lines  # line numbers populated


def test_host_sync_negatives():
    found = _findings({"hot.py": HOT_NEGATIVE}, rule="host-sync", **HOT_CFG)
    assert _active(found) == [], found


def test_host_sync_pragma_suppression_requires_reason():
    src = (
        "import jax.numpy as jnp\n"
        "class Engine:\n"
        "    def run(self, x):\n"
        "        y = jnp.sum(x)\n"
        "        return float(y)  # graftlint: ok(host-sync) — feed gate\n"
        "    def bad(self, x):\n"
        "        pass  # graftlint: ok(host-sync)\n")
    out = L.run_lint({"hot.py": src}, L.LintConfig(**HOT_CFG))
    hs = [f for f in out if f.rule == "host-sync"]
    assert len(hs) == 1 and hs[0].suppressed
    # a reason-less pragma is itself a finding
    assert any(f.rule == "pragma" and not f.suppressed for f in out)


# --------------------------------------------------------------------- #
# retrace                                                               #
# --------------------------------------------------------------------- #
RETRACE_POSITIVE = '''
import jax
import jax.numpy as jnp
from functools import partial

def per_step(xs):
    outs = []
    for x in xs:
        f = jax.jit(lambda a: a + 1)     # jit constructed per iteration
        outs.append(f(x))
    y = jax.jit(lambda a: a * 2)(xs[0])  # jit used immediately
    return outs, y

@jax.jit
def branchy(x, flag):
    if flag:                              # python branch on traced arg
        return x + 1
    return x - 1

g = jax.jit(lambda a, cfg: a, static_argnums=(1,))
bad = g(jnp.zeros(3), [1, 2])             # unhashable static literal
'''

RETRACE_NEGATIVE = '''
import jax
import jax.numpy as jnp
from functools import partial

_step = jax.jit(lambda a: a + 1)          # constructed once, reused

def drive(xs):
    return [_step(x) for x in xs]

@partial(jax.jit, static_argnames=("mode",))
def ok_static(x, mode):
    if mode == "fast":                    # declared static: fine
        return x + 1
    return x - 1

@jax.jit
def ok_shape(x, y):
    if x.shape[0] > 4:                    # shapes are static under trace
        return x + 1
    if y is None:                         # identity check is static
        return x
    return x - 1
'''


def test_retrace_positives():
    found = _findings({"m.py": RETRACE_POSITIVE}, rule="retrace")
    msgs = "\n".join(f.message for f in _active(found))
    assert "inside a loop body" in msgs
    assert "used immediately" in msgs
    assert "traced value(s) ['flag']" in msgs
    assert "unhashable" in msgs


def test_retrace_negatives():
    found = _findings({"m.py": RETRACE_NEGATIVE}, rule="retrace")
    assert _active(found) == [], found


def test_retrace_pragma():
    src = ("import jax\n"
           "def f(xs):\n"
           "    for x in xs:\n"
           "        # graftlint: ok(retrace) — test fixture, cold path\n"
           "        g = jax.jit(lambda a: a)\n"
           "    return g\n")
    found = _findings({"m.py": src}, rule="retrace")
    assert found and all(f.suppressed for f in found)


# --------------------------------------------------------------------- #
# tracer-leak                                                           #
# --------------------------------------------------------------------- #
LEAK_POSITIVE = '''
import jax

class Model:
    @jax.jit
    def step(self, x):
        self.cache = x * 2       # tracer stored on self
        return x

_stash = None

def outer():
    def body(x):
        global _stash            # smuggling via global
        _stash = x
        return x
    return jax.jit(body)
'''

LEAK_NEGATIVE = '''
import jax

class Model:
    def host_side(self, x):
        self.cache = x           # not jitted: fine

    @jax.jit
    def step(self, x):
        y = x * 2                # local assign inside jit: fine
        return y
'''


def test_tracer_leak():
    pos = _findings({"m.py": LEAK_POSITIVE}, rule="tracer-leak")
    msgs = "\n".join(f.message for f in _active(pos))
    assert "self.cache" in msgs and "global" in msgs
    neg = _findings({"m.py": LEAK_NEGATIVE}, rule="tracer-leak")
    assert _active(neg) == [], neg


# --------------------------------------------------------------------- #
# knob-registry                                                         #
# --------------------------------------------------------------------- #
KNOB_CFG = dict(knob_names=frozenset({"RLA_TPU_REGISTERED"}))

KNOB_POSITIVE = '''
import os
MY_ENV = "RLA_TPU_SECRET_KNOB"
raw = os.environ.get("RLA_TPU_SECRET_KNOB")    # raw read, literal
via_const = os.environ[MY_ENV]                 # raw read via constant
dyn = os.getenv(raw)                           # dynamic key
from ray_lightning_accelerators_tpu.analysis import knobs
bad = knobs.get_int("RLA_TPU_UNREGISTERED", 1)  # getter, unregistered
'''

KNOB_NEGATIVE = '''
import os
from ray_lightning_accelerators_tpu.analysis import knobs
flags = os.environ.get("XLA_FLAGS", "")        # non-RLA name: allowed
os.environ["RLA_TPU_REGISTERED"] = "1"         # write: exempt
ok = knobs.get_int("RLA_TPU_REGISTERED", 1)    # registered getter
'''


def test_knob_registry_rule():
    pos = _findings({"m.py": KNOB_POSITIVE}, rule="knob-registry",
                    **KNOB_CFG)
    msgs = "\n".join(f.message for f in _active(pos))
    assert msgs.count("raw environ read") == 2
    assert "dynamic key" in msgs
    assert "RLA_TPU_UNREGISTERED" in msgs
    neg = _findings({"m.py": KNOB_NEGATIVE}, rule="knob-registry",
                    **KNOB_CFG)
    assert _active(neg) == [], neg


def test_knob_registry_resolves_imported_constants():
    consts = 'GRACE_ENV = "RLA_TPU_PREEMPT_GRACE_S"\n'
    user = ("import os\n"
            "from .consts import GRACE_ENV\n"
            "v = os.environ.get(GRACE_ENV)\n")
    found = _findings({"consts.py": consts, "user.py": user},
                      rule="knob-registry", **KNOB_CFG)
    active = _active(found)
    assert len(active) == 1 and "RLA_TPU_PREEMPT_GRACE_S" in \
        active[0].message


# --------------------------------------------------------------------- #
# wire-exception                                                        #
# --------------------------------------------------------------------- #
WIRE_CFG = dict(wire_names=frozenset({"Registered"}),
                worker_modules=("worker.py",))

WIRE_SRC = '''
class Registered(RuntimeError):
    pass

class Unregistered(RuntimeError):
    pass

def dispatched():
    raise Registered("typed, rebuilds fine")

def also_dispatched(flag):
    if flag:
        raise ValueError("builtins stay generic on purpose")
    raise Unregistered("typed but NOT in the wire registry")
'''


def test_wire_exception_rule():
    pos = _findings({"worker.py": WIRE_SRC}, rule="wire-exception",
                    **WIRE_CFG)
    active = _active(pos)
    assert len(active) == 1 and "Unregistered" in active[0].message
    # same code outside a worker module: out of scope
    neg = _findings({"driver.py": WIRE_SRC}, rule="wire-exception",
                    **WIRE_CFG)
    assert _active(neg) == [], neg


def test_wire_registry_consistent_with_rebuilders():
    from ray_lightning_accelerators_tpu.runtime import wire
    assert set(wire.WIRE_EXCEPTION_NAMES) == set(wire._rebuilders())


def test_rebuild_remote_types():
    from ray_lightning_accelerators_tpu.runtime.actors import RemoteError
    from ray_lightning_accelerators_tpu.runtime.elastic import (
        ElasticResizeError)
    from ray_lightning_accelerators_tpu.runtime.preemption import Preempted
    from ray_lightning_accelerators_tpu.runtime.watchdog import WorkerWedged
    from ray_lightning_accelerators_tpu.runtime.wire import rebuild_remote

    p = Preempted.at_step(7, "/tmp/ck")
    back = rebuild_remote("Preempted", str(p), "tb")
    assert isinstance(back, Preempted) and back.step == 7
    assert back.remote_typed  # came from a worker-raised payload
    w = WorkerWedged.for_rank(3, {"detail": "stuck"})
    back = rebuild_remote("WorkerWedged", str(w), "tb")
    assert isinstance(back, WorkerWedged) and back.rank == 3
    back = rebuild_remote("ElasticResizeError", "bad size", "tb")
    assert isinstance(back, ElasticResizeError)
    from ray_lightning_accelerators_tpu.runtime.guardian import (
        NumericAnomaly)
    a = NumericAnomaly.for_trip(step=9, blame="data", epoch=0, batch_idx=9,
                                flags={"loss_nonfinite": True})
    back = rebuild_remote("NumericAnomaly", str(a), "tb")
    assert isinstance(back, NumericAnomaly)
    assert back.step == 9 and back.blame == "data" and back.batch_idx == 9
    assert back.diagnosis["flags"] == {"loss_nonfinite": True}
    back = rebuild_remote("SomeRandomError", "boom", "tb")
    assert isinstance(back, RemoteError)


def test_replica_failure_triage_with_typed_rebuilds():
    """Regression (review finding): wire-rebuilt worker-raised app
    errors (stale ObjectStoreError) must NOT read as replica death —
    a poisoned request would cascade every replica into the down set."""
    from ray_lightning_accelerators_tpu.runtime.actors import RemoteError
    from ray_lightning_accelerators_tpu.runtime.watchdog import WorkerWedged
    from ray_lightning_accelerators_tpu.runtime.wire import rebuild_remote
    from ray_lightning_accelerators_tpu.serve.replicas import (
        _is_application_failure)

    assert _is_application_failure(RemoteError("ValueError", "x", "tb"))
    stale = rebuild_remote("ObjectStoreError", "stale ref", "tb")
    assert _is_application_failure(stale)  # typed app error: keep replica
    # infra stays infra: driver-side wedge, worker-raised wedge, death
    assert not _is_application_failure(
        WorkerWedged.for_rank(1, {"detail": "stuck"}))
    assert not _is_application_failure(
        rebuild_remote("WorkerWedged", "wedged", "tb"))
    assert not _is_application_failure(RuntimeError("worker 1 died"))


# --------------------------------------------------------------------- #
# the tree itself is clean (THE enforcement test)                       #
# --------------------------------------------------------------------- #
def test_package_tree_has_no_unsuppressed_findings():
    findings = L.lint_path(PKG_DIR)
    active = _active(findings)
    assert active == [], "\n" + "\n".join(f.format() for f in active)
    # the pragmas that do exist all carry reasons (rule 'pragma' active
    # findings would have shown above) and there are some — the rules
    # genuinely fire on this tree
    assert any(f.suppressed for f in findings)


def test_single_file_target_keeps_package_context(tmp_path):
    # a single-file target inside a package must resolve hot-root /
    # worker-module keys and the registries exactly like a package run
    # (a basename key would no-op every path-keyed rule: false clean)
    pkg = tmp_path / "pkg"
    (pkg / "core").mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "core" / "__init__.py").write_text("")
    target = pkg / "core" / "trainer.py"
    target.write_text(
        "class Trainer:\n"
        "    def _fit_step(self, state, batch):\n"
        "        loss = self._step(state, batch)\n"
        "        return float(loss.item())\n")
    active = _active(L.lint_path(str(target)))
    assert any(f.rule == "host-sync" and f.path == "core/trainer.py"
               for f in active), active
    # and on the real tree: the file's pragma'd findings are DETECTED
    # (suppressed), not invisible
    real = L.lint_path(os.path.join(PKG_DIR, "core", "trainer.py"))
    assert real and all(f.path == "core/trainer.py" for f in real)
    assert any(f.suppressed and f.rule == "host-sync" for f in real)
    assert _active(real) == []


def test_cli_exits_zero_on_tree():
    import subprocess
    import sys
    script = os.path.join(os.path.dirname(PKG_DIR), "scripts",
                          "graftlint.py")
    proc = subprocess.run([sys.executable, script, PKG_DIR],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "graftlint:" in proc.stdout


def test_cli_exits_nonzero_on_violation(tmp_path):
    import subprocess
    import sys
    bad = tmp_path / "bad.py"
    bad.write_text("import os\nv = os.environ.get('RLA_TPU_OOPS')\n")
    script = os.path.join(os.path.dirname(PKG_DIR), "scripts",
                          "graftlint.py")
    proc = subprocess.run([sys.executable, script, str(bad)],
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 1, proc.stdout + proc.stderr
    assert "knob-registry" in proc.stdout


# --------------------------------------------------------------------- #
# knobs registry runtime behavior                                       #
# --------------------------------------------------------------------- #
def test_knobs_typed_getters(monkeypatch):
    monkeypatch.setenv("RLA_TPU_FLASH_BLOCK_Q", "256")
    assert knobs.get_int("RLA_TPU_FLASH_BLOCK_Q", 512) == 256
    monkeypatch.setenv("RLA_TPU_FLASH_BLOCK_Q", "banana")
    assert knobs.get_int("RLA_TPU_FLASH_BLOCK_Q", 512) == 512
    monkeypatch.delenv("RLA_TPU_FLASH_BLOCK_Q")
    assert knobs.get_int("RLA_TPU_FLASH_BLOCK_Q", 512) == 512
    # distinct unset vs malformed defaults (the preemption-grace shape)
    monkeypatch.setenv("RLA_TPU_WEDGE_TIMEOUT_S", "nope")
    assert knobs.get_float("RLA_TPU_WEDGE_TIMEOUT_S", None,
                           malformed=30.0) == 30.0
    monkeypatch.delenv("RLA_TPU_WEDGE_TIMEOUT_S")
    assert knobs.get_float("RLA_TPU_WEDGE_TIMEOUT_S", None) is None
    # bool parsing + warn-and-default on junk
    monkeypatch.setenv("RLA_TPU_INSIDE_WORKER", "true")
    assert knobs.get_bool("RLA_TPU_INSIDE_WORKER") is True
    monkeypatch.setenv("RLA_TPU_INSIDE_WORKER", "2")
    assert knobs.get_bool("RLA_TPU_INSIDE_WORKER") is False
    # flag semantics: presence-truthiness (historical gates)
    monkeypatch.setenv("RLA_TPU_DISABLE_PALLAS", "0")
    assert knobs.get_flag("RLA_TPU_DISABLE_PALLAS") is True


def test_knobs_env_overlay(monkeypatch):
    monkeypatch.setenv("RLA_TPU_WORKER_HEARTBEAT_S", "5.0")
    assert knobs.get_float("RLA_TPU_WORKER_HEARTBEAT_S", 1.0) == 5.0
    # per-worker overlay wins when it HAS the key
    assert knobs.get_float("RLA_TPU_WORKER_HEARTBEAT_S", 1.0,
                           env={"RLA_TPU_WORKER_HEARTBEAT_S": "2.5"}) == 2.5
    # overlay with empty value = explicitly unset -> default, no
    # fall-through to the process env
    assert knobs.get_float("RLA_TPU_WORKER_HEARTBEAT_S", 1.0,
                           env={"RLA_TPU_WORKER_HEARTBEAT_S": ""}) == 1.0


def test_knobs_refuse_unregistered():
    with pytest.raises(LookupError, match="not registered"):
        knobs.get_str("RLA_TPU_TOTALLY_NEW_KNOB")


def test_every_package_rla_env_name_is_registered():
    """Belt-and-braces sweep: every RLA_TPU_* string literal in the
    package (reads, writes, docs aside) resolves to a registered knob —
    registry drift can't hide in a write-only site."""
    import re
    unknown = set()
    for dirpath, dirnames, filenames in os.walk(PKG_DIR):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        for fn in filenames:
            if not fn.endswith(".py"):
                continue
            with open(os.path.join(dirpath, fn), encoding="utf-8") as f:
                for name in re.findall(r"RLA_TPU_[A-Z0-9_]+", f.read()):
                    if name not in knobs.KNOBS:
                        unknown.add((fn, name))
    # non-knob wire/protocol constants are the only sanctioned names
    allowed = {"RLA_TPU_AUTH1"}  # agent auth magic, not an env knob
    assert {n for _, n in unknown} <= allowed, unknown


# --------------------------------------------------------------------- #
# compile-guard runtime                                                 #
# --------------------------------------------------------------------- #
def test_compile_guard_counts_and_budgets():
    shape = (13, 29)  # unique: avoid riding another test's cache

    @jax.jit
    def f(x):
        return x * 3 + 1

    with compile_guard() as g:
        f(jnp.ones(shape))
    assert g.new_compiles >= 1
    with compile_guard(max_new_compiles=0, label="cached") as g:
        f(jnp.ones(shape))  # cache hit: no compile
    assert g.new_compiles == 0
    with pytest.raises(CompileBudgetExceeded, match="retracing"):
        with compile_guard(max_new_compiles=0):
            f(jnp.ones((17, 31)))  # new shape: retrace
    # an exception inside the block is never masked by the budget check
    with pytest.raises(RuntimeError, match="inner"):
        with compile_guard(max_new_compiles=0):
            f(jnp.ones((19, 37)))
            raise RuntimeError("inner")


# --------------------------------------------------------------------- #
# the compile ledger: every program by name                             #
# --------------------------------------------------------------------- #
@pytest.fixture
def persistent_cache(tmp_path):
    """jax's persistent compilation cache in ``tmp_path`` for one test
    (the suite runs with it off, tests/conftest.py), the two persistence
    thresholds at zero; ``persistent_cache(default=True)`` puts jax's
    defaults back.  Everything is restored afterwards."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    old = {k: getattr(jax.config, k) for k in keys}

    def thresholds(default=False):
        jax.config.update(keys[2], 1.0 if default else 0.0)
        jax.config.update(keys[3], 0)

    jax.config.update(keys[0], True)
    jax.config.update(keys[1], str(tmp_path))
    thresholds()
    cc.reset_cache()
    try:
        yield thresholds
    finally:
        for k, v in old.items():
            jax.config.update(k, v)
        cc.reset_cache()


def _fresh_program(name, shape):
    """A new jitted function every call (same name, same program): jax's
    in-memory caches do not know it, the persistent cache's key does."""
    def fn(x):
        return jnp.tanh(x) * 3 + 1
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn), jnp.ones(shape)


def test_compile_ledger_tells_miss_hit_and_small_apart(persistent_cache):
    from ray_lightning_accelerators_tpu.analysis import compile_guard as cg
    cg.install()
    x = jnp.ones((3, 41))       # its own small programs, before the clock
    t0, c0 = time.monotonic(), compile_count()
    for _ in range(2):          # compiled and written, then loaded
        f, _ = _fresh_program("ledger_probe", (3, 41))
        f(x)
    persistent_cache(default=True)      # under jax's 1 s: never written
    for _ in range(2):
        g, _ = _fresh_program("ledger_small_probe", (5, 43))
        g(jnp.zeros((5, 43)) + x[0, 0])
    rows = cg.ledger(since=t0)
    probe = [r for r in rows if r["name"] == "jit(ledger_probe)"]
    small = [r for r in rows if r["name"] == "jit(ledger_small_probe)"]
    assert [r["cache"] for r in probe] == ["miss", "hit"], rows
    assert [r["cache"] for r in small] == ["small", "small"], rows
    hit = probe[1]
    assert hit["retrieval_s"] > 0 and 0 < hit["backend_s"] < 1.0
    assert probe[0]["retrieval_s"] == 0.0
    for r in probe + small:     # host floats and strings only
        assert r["trace_s"] > 0 and r["lower_s"] > 0 and r["backend_s"] > 0
        assert r["start"] == pytest.approx(r["end"] - r["backend_s"])
        assert t0 <= r["start"] <= r["end"] <= time.monotonic()
        assert r["thread"] == "MainThread" and r["phase"] is None
        assert all(isinstance(v, (float, str, type(None)))
                   for v in r.values())
    s = cg.summary(probe + small)
    assert (s["programs"], s["built"], s["loaded"], s["missed"]) == (
        4, 3, 1, 1)
    assert s["backend_s"] == pytest.approx(
        sum(r["backend_s"] for r in probe + small))
    assert s["retrieval_s"] == hit["retrieval_s"]
    # the counter keeps its meaning: every backend event, loads included
    assert compile_count() - c0 == len([r for r in rows if r["cache"]])
    assert cg.ledger(since=t0, until=probe[1]["end"]) == [
        r for r in rows if r["end"] < probe[1]["end"]]


def test_compile_ledger_nesting_phase_and_uncached():
    """The suite's own setting: no persistent cache, so every program
    reads ``uncached``.  A jitted function traced inside another is no
    row of its own, and a row carries the innermost open phase."""
    from ray_lightning_accelerators_tpu.analysis import compile_guard as cg
    cg.install()

    @jax.jit
    def ledger_inner(x):
        return x @ x.T

    def ledger_outer(x):
        return ledger_inner(x).sum() + ledger_inner(x * 2).sum()

    x = jnp.ones((7, 47))
    t0 = time.monotonic()
    with cg.phase("outer_phase"):
        with cg.phase("inner_phase"):
            jax.jit(ledger_outer)(x)
        h, _ = _fresh_program("ledger_lowered", (7, 47))
        h.lower(x)                              # traced, lowered, not built
        f, y = _fresh_program("ledger_phase_probe", (7, 47))
        f(y)
    f2, y = _fresh_program("ledger_phase_probe", (7, 47))
    f2(y)
    rows = cg.ledger(since=t0)
    by_name = {}
    for r in rows:
        by_name.setdefault(r["name"], []).append(r)
    assert not any("ledger_inner" in name for name in by_name), by_name
    (built,), (lowered,) = (by_name["jit(ledger_outer)"],
                            by_name["jit(ledger_lowered)"])
    assert built["cache"] == "uncached" and built["phase"] == "inner_phase"
    assert built["trace_s"] > 0 and built["backend_s"] > 0
    assert lowered["cache"] is None and lowered["backend_s"] == 0.0
    assert lowered["lower_s"] > 0 and lowered["phase"] == "outer_phase"
    assert [r["phase"] for r in by_name["jit(ledger_phase_probe)"]] == [
        "outer_phase", None]
    s = cg.summary(rows)
    assert s["built"] == len([r for r in rows if r["cache"] == "uncached"])
    assert s["loaded"] == s["missed"] == 0
    assert s["programs"] == len(rows) > s["built"]


def test_compile_ledger_is_bounded_and_counts_what_it_drops():
    from ray_lightning_accelerators_tpu.analysis import compile_guard as cg
    cg.install()
    cg._reset_ledger_for_tests()
    c0 = compile_count()
    try:
        for i in range(cg.LEDGER_ROWS + 76):    # jax's own three calls
            name = f"jit(fake_{i})"
            cg._on_scalar(cg.TRACE_EVENT, 0.0, fun_name=f"fake_{i}")
            cg._on_event_duration(cg.TRACE_EVENT, 1e-4, fun_name=f"fake_{i}")
            cg._on_scalar(cg.BACKEND_COMPILE_EVENT, 0.0, fun_name=name)
            cg._on_event(cg.CACHE_REQUEST_EVENT)
            cg._on_event_duration(cg.BACKEND_COMPILE_EVENT, 1e-3,
                                  fun_name=name)
        rows = cg.ledger()
        assert len(rows) == cg.LEDGER_ROWS and cg.ledger_dropped() == 76
        assert rows[0]["name"] == "jit(fake_76)"     # the newest are kept
        assert rows[-1]["name"] == f"jit(fake_{cg.LEDGER_ROWS + 75})"
        assert {r["cache"] for r in rows} == {"small"}
        assert [r["end"] for r in rows] == sorted(r["end"] for r in rows)
        # the counter is not the ledger's: it counts on past the bound
        assert compile_count() - c0 == cg.LEDGER_ROWS + 76
    finally:
        cg._reset_ledger_for_tests()
    assert cg.ledger() == [] and cg.ledger_dropped() == 0


def test_one_place_listens_to_jax_monitoring():
    """One listener set a process: a second one (chip_smoke.py had its
    own for cache hits) counts the same events apart from the ledger."""
    repo = os.path.dirname(PKG_DIR)
    files = [os.path.join(repo, f) for f in ("bench.py", "chip_smoke.py")]
    for dirpath, _, names in os.walk(PKG_DIR):
        files += [os.path.join(dirpath, n) for n in names
                  if n.endswith(".py")]
    hits = []
    for path in files:
        with open(path) as f:
            if "monitoring.register_" in f.read():
                hits.append(os.path.relpath(path, repo))
    assert hits == [os.path.join("ray_lightning_accelerators_tpu",
                                 "analysis", "compile_guard.py")]


def test_serve_engine_program_count_invariant():
    """The PR 2 prose, enforced through the paging indirection: a
    staggered join/retire workload over one prompt bucket runs the
    PAGED engine's whole lifecycle in exactly 2 compiled programs
    (bucketed chunk prefill-into-blocks, batched paged step — the slot
    join fused into prefill), the DENSE engine's in exactly 3 (bucket
    prefill, slot join, batched step), and a second wave adds zero to
    either."""
    from ray_lightning_accelerators_tpu.models.transformer import (
        GPT, TransformerConfig)
    from ray_lightning_accelerators_tpu.serve import ServeEngine

    cfg = TransformerConfig(vocab_size=89, d_model=64, n_heads=2,
                            d_ff=128, n_layers=2, max_seq_len=48)
    model = GPT(cfg)
    params = model.init_params(jax.random.PRNGKey(3))
    rng = np.random.default_rng(11)
    # one prompt bucket: lengths 3..8 all pad to 8 (prompt_block and
    # block_len both 8)
    reqs = [(rng.integers(0, 89, size=(int(rng.integers(3, 9)),))
             .astype(np.int32), int(rng.integers(4, 10)))
            for _ in range(6)]
    for paged, expected, what in (
            (True, 2, "chunk prefill/step"),
            (False, 3, "prefill/join/step")):
        eng = ServeEngine(model, params, max_slots=3, queue_depth=32,
                          paged=paged, block_len=8, prefix_cache=False)
        eng.start()  # cache alloc outside the guard: it is not a program
        try:
            with compile_guard(max_new_compiles=expected,
                               label="serve-prog") as g:
                resps = []
                for i, (p, n) in enumerate(reqs):
                    resps.append(eng.submit(p, n))
                    if i % 2 == 1:
                        time.sleep(0.02)  # staggered: join/retire mid-flight
                for r in resps:
                    r.result(timeout=300)
            assert g.new_compiles == expected, (
                f"expected exactly {expected} compiled programs "
                f"({what}, paged={paged}), got {g.new_compiles}")
            # second wave: join + retire + decode reuse every program
            with compile_guard(max_new_compiles=0, label="serve-steady"):
                more = [eng.submit(p, n) for p, n in reqs[:3]]
                for r in more:
                    r.result(timeout=300)
        finally:
            eng.stop()
        snap = eng.stats()
        assert snap["completed"] == 9
        assert snap["steps_batch_gt1"] >= 1  # it genuinely batched


def test_trainer_no_retrace_after_warmup(tmpdir):
    """ROADMAP item 5's precondition, enforced: the train step compiles
    on step 1 and retraces ZERO times over the following >= 10 steps."""
    import sys
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from utils import BoringModel, boring_loaders

    from ray_lightning_accelerators_tpu import (Callback,
                                                RayTPUAccelerator, Trainer)

    counts = []

    class CompileCounter(Callback):
        def on_train_batch_end(self, trainer, module, metrics, batch_idx):
            counts.append(compile_count())

    trainer = Trainer(default_root_dir=str(tmpdir), max_steps=12,
                      max_epochs=3, accelerator=RayTPUAccelerator(2),
                      precision="f32", seed=0, log_every_n_steps=4,
                      callbacks=[CompileCounter()],
                      enable_checkpointing=False)
    train, _ = boring_loaders()
    trainer.fit(BoringModel(), train)
    assert len(counts) == 12
    # step 1 absorbs every compile (placement + train step); steps 2..12
    # must add none — eleven consecutive steps, zero retraces
    assert counts[1:] == [counts[0]] * 11, counts