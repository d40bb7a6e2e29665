"""Scope table: which named scope each compiled instruction belongs to.

A device trace names an op by its HLO instruction (``%fusion.411 = ...``)
and carries nothing of the Python that made it.  The compiled program's
own text does: every instruction's ``metadata={op_name="..."}`` is the
``jax.named_scope`` stack it was traced under, wrapped by JAX's own
transform markers (``jvp(...)`` forward, ``transpose(jvp(...))``
backward, ``rematted_computation`` for a remat's second forward).  So
the program names its parts with scopes (the contract below) and this
module keeps *how to ask for the text* of each jitted program.  Reading
the op names -- which bucket a scope is, how a trace is joined to them --
is the reader's business (``benchmark/lib/scopes.py`` for the per-layer
metrics; ``rla-tpu trace`` prints each op's name beside its time).
Named scopes are metadata only: nothing here runs on the device.

The scope contract (docs/API.md "Named scopes", PERF.md §3):
``gpt/embed``, ``gpt/layers``, ``gpt/loop`` (a looped stack's pass
loop around the layer scan), ``gpt/loop_exit`` (its exit gate, exit
distribution and entropy term, outside ``gpt/loss``; the counters
``loop_loss_pass_<t>``, ``loop_exit_mean_pass`` and ``loop_exit_entropy``
ride the step's logged metrics), ``gpt/attn``, ``gpt/mlp``, ``gpt/norm``,
``gpt/conv``, ``gpt/ssm``, ``gpt/ssm_scan``, ``gpt/moe_route``,
``gpt/moe_dispatch``, ``gpt/moe_experts``, ``gpt/moe_combine``,
``gpt/moe_latent``, ``gpt/moe_shared``, ``gpt/loss``, ``optimizer``,
``guard``, ``exchange``,
``kernel/<name>`` (``flash_fwd``, ``flash_bwd``, ``rms_norm``,
``q8_matmul``, ``moe_gmm``).

A ``Program`` wraps a jitted callable where it is built and, at its
first call, keeps the ABSTRACT arguments (shape, dtype, sharding: no
buffer) and enters itself in the registry under its name.  The registry
holds one program per name, the last to make its first call, and holds
it WEAKLY while its owner does: the callable's closure reaches the owner
(a ``Trainer`` with its device state), and a registry must not keep a
dropped owner's buffers alive.  An owner that lets go of its program in
an orderly way (``Trainer.teardown()``: the device state is gone by
then) calls ``retire()``, and from then on the registry holds the
program strongly, because its text is asked for afterwards (the
benchmark's readers run after teardown): that pins the callable, its
closure and its executable until the next program of that name or
``clear()``.  ``program_text`` lowers and compiles on demand -- the
executable is in jax's in-memory cache, so asking costs a trace of the
function and the text -- and is never called unless somebody asks.
"""

from __future__ import annotations

import re
import threading
import weakref
from typing import Any, Callable, Dict, Optional, Tuple

# name -> a call that gives the program, or None once its owner dropped it
_programs: Dict[str, Callable[[], Optional["Program"]]] = {}
_lock = threading.Lock()

# one instruction of ``compiled.as_text()`` that carries an op name
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?(%[\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"',
    re.M)


def _abstract(x: Any) -> Any:
    import jax
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                    sharding=getattr(x, "sharding", None))
    return x


class Program:
    """A jitted callable under a name.  Calls go straight through; the
    first one registers the program with the abstract arguments it was
    called with (one ``is None`` test a call after that)."""

    def __init__(self, name: str, jitted: Callable):
        self.name, self.jitted = name, jitted
        self.args: Optional[tuple] = None

    def register(self, *args: Any) -> None:
        import jax
        self.args = jax.tree.map(_abstract, args)
        with _lock:
            _programs[self.name] = weakref.ref(self)

    def retire(self) -> None:
        """The owner lets go (its device state released): if the name is
        still this program's, the registry keeps it from now on."""
        with _lock:
            if self.args is not None and _get(self.name) in (self, None):
                _programs[self.name] = lambda: self

    def __call__(self, *args):
        if self.args is None:
            self.register(*args)
        return self.jitted(*args)

    def lower(self, *args, **kwargs):
        return self.jitted.lower(*args, **kwargs)


def _get(name: str) -> Optional[Program]:
    held = _programs.get(name)
    return None if held is None else held()


def registered() -> Tuple[str, ...]:
    with _lock:
        return tuple(n for n in _programs if _get(n) is not None)


def clear() -> None:
    with _lock:
        _programs.clear()


def program_text(name: str) -> str:
    """``compiled.as_text()`` of a registered program (KeyError for a
    name nobody registered, or whose owner dropped it unretired)."""
    with _lock:
        program = _get(name)
    if program is None:
        raise KeyError(name)
    return program.lower(*program.args).compile().as_text()


def scope_table(name: str) -> Dict[str, str]:
    """``{%instruction: op_name}`` of a registered program, as the
    compiler wrote them (an instruction's name is unique within its
    module; one without metadata is left out)."""
    return dict(_INSTRUCTION.findall(program_text(name)))
