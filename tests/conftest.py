"""Test env: force an 8-device virtual CPU mesh BEFORE any backend init.

The reference tested multi-worker logic by CPU oversubscription on localhost
with the Gloo backend (reference: ray_lightning/tests/test_ddp.py:17-21 +
ray_ddp.py:227).  The XLA analog: 8 virtual CPU devices, so every
mesh/sharding path runs in CI without TPUs; real-TPU runs are env-gated the
way the reference gated GPU tests (reference: tests/test_ddp_gpu.py:106-109)
via RLA_TPU_TEST_PLATFORM=tpu.

The platform is pinned through ``jax.config`` (not only the env), so the
suite stays on the CPU mesh on a host that has a chip.
"""

import os

os.environ.setdefault("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_force_host_platform_device_count=8"

_platform = os.environ.get("RLA_TPU_TEST_PLATFORM", "cpu")
if _platform == "cpu":
    # Trainer/ServeEngine/worker boot place the persistent compile cache
    # in <checkout>/.jax_cache (utils/compile_cache.py).  The chip tool
    # and the driver copy the checkout as it stands, so the forced-CPU
    # suite must not fill it: off here, and -- through the environment --
    # in every worker and subprocess the tests spawn.
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_platforms", _platform)
if _platform == "cpu":
    jax.config.update("jax_num_cpu_devices", 8)

# RLA_TPU_WORKER_PLATFORM is scoped to the one test that gates on it
# (test_tpu_world.py re-sets it from the stash inside the test): left
# ambient, it would rewrite the platform of EVERY fan-out in the suite
# -- with a real chip, two CPU-gloo tests' workers would contend for the
# single device claim and deadlock.
WORKER_PLATFORM_STASH = os.environ.pop("RLA_TPU_WORKER_PLATFORM", None)

import pytest  # noqa: E402


@pytest.fixture
def compile_guard():
    """The compile-count guard factory (analysis/compile_guard.py):

        with compile_guard(max_new_compiles=3, label="serve"):
            ...  # raises CompileBudgetExceeded past the budget

    Counting is process-global (one jax.monitoring listener installed on
    first use), so guarded blocks must not overlap other tests' compiles
    — fine under the suite's in-process sequential execution."""
    from ray_lightning_accelerators_tpu.analysis.compile_guard import (
        compile_guard as guard)
    return guard


@pytest.fixture
def spmd_sanitizer(tmp_path, monkeypatch):
    """Opt-in SPMD collective sanitizer (testing/spmd_sanitizer.py) for
    THIS process: sets the knob + a telemetry dir, installs the jax.lax
    interception, yields the module (sanitizer at ``get_sanitizer()``),
    and uninstalls afterwards so later tests trace unwrapped
    collectives.  Fan-out tests instead put RLA_TPU_SPMD_SANITIZER in
    env_per_worker — worker boot installs it rank-keyed."""
    from ray_lightning_accelerators_tpu.testing import spmd_sanitizer as S
    tdir = tmp_path / "spmd_telemetry"
    monkeypatch.setenv("RLA_TPU_SPMD_SANITIZER", "1")
    monkeypatch.setenv("RLA_TPU_TELEMETRY_DIR", str(tdir))
    S.install(rank=None)
    try:
        yield S
    finally:
        S.uninstall()


@pytest.fixture
def cpu_mesh_subprocess():
    """Run a python script in a SPAWNED subprocess whose backend comes up
    with an 8-device virtual CPU mesh.

    The in-process suite already forces 8 devices (module top), but some
    tests must prove behavior under a CLEAN backend init — e.g. the
    collectives suite's claim that an exchange compiles on a fresh
    8-device mesh without inheriting this process's jax config: the
    device count is fixed when a backend initializes, which is why this
    is a subprocess, not a fixture-scoped config tweak.

    Returns ``run(script, timeout=120) -> CompletedProcess`` (asserts
    exit 0, stderr in the failure message)."""
    import subprocess
    import sys

    def run(script: str, timeout: float = 120.0, env_extra=None):
        env = dict(os.environ)
        env.update({
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
            "JAX_PLATFORMS": "cpu",
            # the child must not inherit fan-out / chaos state
            "RLA_TPU_INSIDE_WORKER": "",
        })
        env.update(env_extra or {})
        proc = subprocess.run([sys.executable, "-c", script],
                              capture_output=True, text=True,
                              timeout=timeout, env=env)
        assert proc.returncode == 0, (
            f"cpu_mesh_subprocess script failed (rc {proc.returncode}):\n"
            f"--- stdout ---\n{proc.stdout}\n--- stderr ---\n{proc.stderr}")
        return proc

    return run


# long-lived service threads owned by third-party libraries (orbax's
# async-checkpoint machinery keeps these for the process lifetime after
# the first async save; they are joined at interpreter exit by the
# library's own atexit hooks) -- not leaks a test can or should close
_THIRD_PARTY_THREAD_PREFIXES = ("metadata_store", "base_pytree_ch",
                                "ocdbt_", "orbax")


@pytest.fixture(autouse=True)
def _thread_leak_guard(request):
    """Fail any test that leaks a live NON-daemon thread (a leaked
    prefetch producer would hang interpreter shutdown and silently
    serialize every later test).  Prefetch threads are non-daemon BY
    DESIGN so this guard has teeth: every exit path out of an epoch must
    close() its pipeline.  Daemon threads (agent/queue/watchdog service
    loops) and known third-party service threads are exempt."""
    import threading

    before = set(threading.enumerate())
    yield
    leaked = [t for t in threading.enumerate()
              if t not in before and t.is_alive() and not t.daemon
              and not t.name.startswith(_THIRD_PARTY_THREAD_PREFIXES)]
    for t in leaked:  # grace: a joining thread may be mid-exit
        t.join(timeout=2.0)
    leaked = [t for t in leaked if t.is_alive()]
    assert not leaked, (
        f"{request.node.nodeid} leaked non-daemon thread(s) "
        f"{[t.name for t in leaked]}; prefetch pipelines (and anything "
        "else spawning non-daemon threads) must be close()d on every "
        "exit path")


@pytest.fixture(autouse=True)
def _chaos_leak_guard(request):
    """``RLA_TPU_CHAOS`` makes every spawned worker crash/hang/stall on
    purpose (now including ``preempt@...``/``lost@...`` faults and the
    numeric layer — ``nanloss``/``gradspike``/``badbatch``/``bitflip`` —
    which corrupts training numerics in-step): ambient in the driver env
    it would poison EVERY fan-out in the suite.  Only
    ``@pytest.mark.chaos`` (or ``@pytest.mark.preempt``, whose tests
    drive the preemption/lost-host kinds) tests may see it set, and no
    test may leave it behind.  ``RLA_TPU_PREEMPT_GRACE_S`` gets the same
    treatment: left ambient it would install SIGTERM notice handlers in
    every spawned worker of unrelated tests; so does
    ``RLA_TPU_CHAOS_NS`` (the once-only claim namespace) — left behind,
    a later chaos test would silently inherit spent claim tokens and
    never fire its faults."""
    allowed = (request.node.get_closest_marker("chaos") is not None
               or request.node.get_closest_marker("preempt") is not None
               or request.node.get_closest_marker("pipeline_mpmd")
               is not None)
    if not allowed:
        assert "RLA_TPU_CHAOS" not in os.environ, (
            f"RLA_TPU_CHAOS leaked into non-chaos test {request.node.nodeid}"
            " -- chaos specs belong in env_per_worker or a chaos/preempt-"
            "marked test's monkeypatched env")
        assert "RLA_TPU_PREEMPT_GRACE_S" not in os.environ, (
            f"RLA_TPU_PREEMPT_GRACE_S leaked into non-preempt test "
            f"{request.node.nodeid} -- preemption grace belongs in "
            "env_per_worker or a preempt-marked test's monkeypatched env")
    yield
    assert "RLA_TPU_CHAOS" not in os.environ, (
        f"{request.node.nodeid} left RLA_TPU_CHAOS set in the driver env; "
        "later fan-outs would inherit the fault injection")
    assert "RLA_TPU_PREEMPT_GRACE_S" not in os.environ, (
        f"{request.node.nodeid} left RLA_TPU_PREEMPT_GRACE_S set in the "
        "driver env; later fan-outs would install preemption handlers")
    assert "RLA_TPU_CHAOS_NS" not in os.environ, (
        f"{request.node.nodeid} left RLA_TPU_CHAOS_NS set in the driver "
        "env; later chaos tests would inherit its spent claim tokens")
