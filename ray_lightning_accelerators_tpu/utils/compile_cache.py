"""Where the persistent XLA compilation cache lives.

A cold process on the chip recompiles every program it runs (the 124M
train step alone is tens of seconds), so every entry point that touches
the backend — ``Trainer``, ``ServeEngine``, worker boot, ``bench.py``
children, ``chip_smoke.py`` — calls :func:`enable` first.

The directory is part of what a later process must reproduce to hit the
cache, so it is never derived from a temp dir, a pid or the clock:

- ``JAX_COMPILATION_CACHE_DIR`` set: jax reads the variable itself and
  this module sets nothing in code — whoever launched the process owns
  the placement.
- unset: ``<checkout>/.jax_cache`` (git-ignored), so a second run in the
  same checkout hits what the first one compiled.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def enable() -> str:
    """Place the persistent compilation cache (idempotent; call before
    the first compile).  Returns the directory in use."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
