"""Placement of the persistent compilation cache (utils/compile_cache.py):
``JAX_COMPILATION_CACHE_DIR`` set => the code sets nothing; unset => one
fixed in-checkout directory.  The directory is part of what a later
process must reproduce to hit the cache, so it may never move."""

import os
import subprocess
import sys
import tempfile

import jax
import pytest

from ray_lightning_accelerators_tpu.utils import compile_cache

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield before
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_set_means_code_sets_nothing(monkeypatch, restore_cache_dir):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/the/launcher/chose")
    assert compile_cache.enable() == "/somewhere/the/launcher/chose"
    # jax reads the variable itself at import; enable() must not touch
    # the config on top of it
    assert jax.config.jax_compilation_cache_dir is None


def test_unset_means_the_fixed_in_checkout_path(monkeypatch,
                                                restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    assert compile_cache.enable() == os.path.join(_REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        _REPO, ".jax_cache")
    # every entry point that compiles places it the same way
    from ray_lightning_accelerators_tpu import Trainer
    jax.config.update("jax_compilation_cache_dir", None)
    Trainer(max_epochs=1, enable_checkpointing=False)
    assert jax.config.jax_compilation_cache_dir == compile_cache.DEFAULT_DIR


def test_default_path_holds_no_temp_dir_pid_or_time():
    """Same path from another process, at another time, with another
    TMPDIR: nothing per-run is part of it."""
    env = dict(os.environ, TMPDIR="/some/other/tmp")
    env.pop(compile_cache.ENV_VAR, None)
    out = subprocess.run(
        [sys.executable, "-c",
         "from ray_lightning_accelerators_tpu.utils import compile_cache;"
         "print(compile_cache.enable())"],
        capture_output=True, text=True, env=env, cwd=_REPO, check=True)
    assert out.stdout.strip() == compile_cache.DEFAULT_DIR
    assert compile_cache.DEFAULT_DIR == os.path.join(_REPO, ".jax_cache")
    assert not compile_cache.DEFAULT_DIR.startswith(tempfile.gettempdir())
    with open(os.path.join(_REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_forced_cpu_suite_keeps_the_persistent_cache_off():
    """The chip tool and the driver copy the checkout as it stands, so
    the CPU suite must not fill ``.jax_cache``: conftest switches the
    persistent cache off for this process and, through the environment,
    for every worker and subprocess the tests spawn."""
    assert jax.config.jax_enable_compilation_cache is False
    assert os.environ["JAX_ENABLE_COMPILATION_CACHE"] == "false"


def test_exactly_one_place_sets_the_cache_dir():
    hits = []
    roots = [os.path.join(_REPO, "ray_lightning_accelerators_tpu"),
             os.path.join(_REPO, "scripts"), os.path.join(_REPO, "examples")]
    files = [os.path.join(_REPO, f) for f in ("bench.py", "chip_smoke.py",
                                               "__graft_entry__.py")]
    for root in roots:
        for dirpath, _, names in os.walk(root):
            files += [os.path.join(dirpath, n) for n in names
                      if n.endswith(".py")]
    for path in files:
        with open(path) as f:
            if "compilation_cache_dir" in f.read():
                hits.append(os.path.relpath(path, _REPO))
    assert hits == [os.path.join("ray_lightning_accelerators_tpu", "utils",
                                 "compile_cache.py")]
