"""``scoped``: a named scope as a decorator.  Leaf module (imports
nothing of the package): kernels, model and trainer name their parts
with it.  The names are a contract, listed in telemetry/scopes.py."""

from __future__ import annotations

import functools
from typing import Callable


def scoped(name: str) -> Callable[[Callable], Callable]:
    """Run the function under ``jax.named_scope(name)``.  A fresh
    context per call -- ``jax.named_scope(name)`` used as a decorator is
    ONE context manager instance that keeps the outer context on itself,
    which concurrent tracing threads would overwrite for each other."""
    def decorate(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            import jax
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return wrapper
    return decorate
