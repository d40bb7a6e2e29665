"""Deterministic fault injection for the worker runtime.

Hangs, crashes, and stragglers are the failure modes that cost whole runs
(a silent hang burns its full timeout) -- and the ones hardest to
reproduce on demand.  This harness makes them deterministic:
faults are declared in an env var, honored by every ``Worker`` subprocess
inside its dispatch loop (runtime/actors.py ``_worker_main``), and need no
TPU, no timing races, no monkeypatching of runtime internals.

Syntax (comma-separated faults)::

    RLA_TPU_CHAOS=crash@rank1:step3,hang@rank0,slow@all:2.5

Replica-layer faults (serve tier, honored inside
``serve.replicas._replica_serve`` rather than the worker dispatch
loop)::

    RLA_TPU_CHAOS=crash@replica0:chunk2,hang@replica1:chunk3:once,slow@replica0:1.5

``kind@target[:qualifier...]`` where

- kind: ``crash`` (``os._exit`` with exit code 43), ``hang`` (freeze the
  heartbeat, then sleep forever -- simulates a fully frozen process, so
  the watchdog's stale-beat path fires), ``slow`` (delay the dispatch by
  the given seconds -- a straggler that still completes), ``preempt``
  (deliver SIGTERM to the worker itself -- with a
  ``runtime.preemption`` notice handler installed via
  ``RLA_TPU_PREEMPT_GRACE_S`` this simulates a spot/preemption notice
  the dispatched body drains gracefully; without one it is a plain
  SIGTERM death), ``lost`` (``os._exit`` with exit code 44 AND a
  persistent "host gone" marker under ``RLA_TPU_CHAOS_NS``: every
  respawn of that rank dies at boot, so ``pool.restart_dead()`` can
  never bring it back -- the permanently lost host that forces an
  elastic scale-down), ``rejoin`` (the grow counterpart of ``lost``:
  the host comes back on its Nth respawn AFTER going lost --
  ``rejoin@rank1:step3`` counts boot attempts while rank 1's lost
  marker exists and clears it via :func:`clear_lost` on the 3rd, so
  elastic grow (``ActorPool.revive``) is testable deterministically;
  never fires on a dispatch);
- target: ``rankN`` or ``all`` (worker layer), or ``replicaN`` (replica
  layer: the fault fires inside the replica's SERVE CHUNK path, counted
  per chunk via the ``chunkK`` qualifier -- only ``crash``/``hang``/
  ``slow`` make sense there; ``hang`` freezes the worker's heartbeat so
  the pool watchdog sees a frozen process, exactly like the worker-layer
  kind);
- qualifiers: ``stepN`` -- fire on the Nth dispatch of the worker
  process's lifetime (1-based; crash/hang/preempt/lost default to step
  1, slow defaults to every dispatch); a float -- the delay for
  ``slow``; ``once`` -- fire at most once across process RESTARTS
  (claimed through an atomic token file under the ``RLA_TPU_CHAOS_NS``
  directory), so a wedge->restart->resume loop converges
  deterministically.  ``lost`` markers are keyed by the rank the fault
  fired on: after an elastic scale-down drops that rank, surviving
  ranks (which keep their original rank identity) never inherit the
  marker.

Faults fire BEFORE the dispatched fn runs, counting every dispatch
(including runtime-internal ones such as ``initialize_worker``); tests
pick explicit steps when that matters.  Parse errors raise driver-side
(``parse_chaos``) and ship home as a ``RemoteError`` worker-side rather
than silently dropping the fault.

Numeric-layer faults (the anomaly guardian's test surface, honored at
the train-step BUILD seams in ``core/trainer.py`` rather than any
dispatch loop)::

    RLA_TPU_CHAOS=nanloss@rank0:step3,gradspike@rank1:step5
    RLA_TPU_CHAOS=badbatch@step5,bitflip@rank1:step4

- ``nanloss`` poisons the traced loss metric at global step K;
- ``gradspike`` scales the (per-replica, when a stacked local-gradient
  tree exists and ``rankN`` names a replica) gradients by 1e4 at step K;
- ``badbatch`` NaN-poisons the HOST batch feeding global step K (rank-
  less by nature — the same poisoned batch reaches every replica), so
  the guardian's blame cascade lands on ``data``;
- ``bitflip`` flips one exponent bit of one element in the first
  gradient leaf (replica ``rankN``'s row when stacked) — the silent-
  data-corruption emulation whose per-rank divergence the guardian
  names.

Steps are the 1-based GLOBAL optimizer step.  Numeric faults are
once-by-construction: they are claimed at step-BUILD time through the
``RLA_TPU_CHAOS_NS`` token store, so the recompile after a guardian
rewind replays the window CLEAN (without a namespace dir every build
re-arms them — single-fit unit tests need no namespace).
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass
from typing import Callable, List, Optional

from ..analysis import knobs

CHAOS_ENV = "RLA_TPU_CHAOS"
CHAOS_NS_ENV = "RLA_TPU_CHAOS_NS"
CHAOS_EXIT_CODE = 43
LOST_EXIT_CODE = 44
_KINDS = ("crash", "hang", "slow", "preempt", "lost", "rejoin",
          "nanloss", "gradspike", "badbatch", "bitflip")
# faults that make sense at the replica serve-chunk layer: a replica is
# a full process, so preempt/lost stay worker-layer kinds
_REPLICA_KINDS = ("crash", "hang", "slow")
# numeric faults (anomaly-guardian test surface): honored at the
# train-step build seams in core/trainer.py, never by a dispatch loop
_NUMERIC_KINDS = ("nanloss", "gradspike", "badbatch", "bitflip")

LAYER_WORKER = "worker"
LAYER_REPLICA = "replica"
LAYER_NUMERIC = "numeric"


def _lost_markers(rank: int, ns_dir: Optional[str]) -> List[str]:
    """Persistent 'host gone' marker files for ``rank`` under the chaos
    namespace dir (rank-keyed, so one rank's markers never match
    another's)."""
    if not ns_dir or not os.path.isdir(ns_dir):
        return []
    suffix = f"-r{rank}.lost"
    return [os.path.join(ns_dir, name) for name in sorted(os.listdir(ns_dir))
            if name.endswith(suffix)]


def clear_lost(rank: int, ns_dir: Optional[str] = None) -> List[str]:
    """Remove ``rank``'s persistent 'host gone' markers so the next
    respawn of that rank boots instead of dying -- the test-side grow
    primitive (a host coming back).  ``ns_dir`` defaults to
    ``RLA_TPU_CHAOS_NS``.  Returns the removed marker paths (empty when
    the rank was never lost)."""
    ns_dir = ns_dir or knobs.get_raw(CHAOS_NS_ENV) or None
    removed = []
    for path in _lost_markers(rank, ns_dir):
        try:
            os.remove(path)
            removed.append(path)
        except OSError:
            pass
    return removed


@dataclass(frozen=True)
class ChaosFault:
    kind: str
    rank: Optional[int]  # None = all ranks
    step: Optional[int]  # None = every dispatch (slow) / step 1 (crash|hang)
    delay_s: Optional[float] = None  # slow only
    once: bool = False
    # which injection seam honors this fault: "worker" = the dispatch
    # loop in runtime/actors._worker_main (step = dispatch index),
    # "replica" = serve.replicas._replica_serve (step = chunk index)
    layer: str = LAYER_WORKER
    # pipeline stage-group target ('stageN'): the fault applies to every
    # member of that stage group — injectors constructed in a process
    # whose RLA_TPU_PIPELINE_STAGE differs drop it at filter time
    stage: Optional[int] = None

    def matches(self, rank: int, step: int) -> bool:
        if self.rank is not None and self.rank != rank:
            return False
        if self.step is not None:
            return step == self.step
        # crash/hang without an explicit step fire on the first dispatch;
        # slow without one fires on every dispatch
        return True if self.kind == "slow" else step == 1

    def token(self, rank: int) -> str:
        """Stable per-rank claim key for ``once`` semantics (layer-
        prefixed for replica faults so a replica chunk claim can never
        collide with a worker dispatch claim)."""
        prefix = "replica" if self.layer == LAYER_REPLICA else "rank"
        if self.stage is not None:
            tgt = f"stage{self.stage}"
        elif self.rank is None:
            tgt = "all"
        else:
            tgt = f"{prefix}{self.rank}"
        step = "any" if self.step is None else f"step{self.step}"
        tok = f"{self.kind}-{tgt}-{step}-r{rank}"
        return tok if self.layer == LAYER_WORKER else f"{self.layer}-{tok}"


def parse_chaos(spec: str) -> List[ChaosFault]:
    """Parse an ``RLA_TPU_CHAOS`` spec; raises ``ValueError`` with the
    offending token on any malformed fault."""
    faults: List[ChaosFault] = []
    for part in (p.strip() for p in spec.split(",")):
        if not part:
            continue
        kind, at, target_q = part.partition("@")
        if not at or kind not in _KINDS:
            raise ValueError(
                f"chaos fault {part!r}: expected kind@target with kind in "
                f"{_KINDS}")
        bits = target_q.split(":")
        target = bits[0]
        layer = LAYER_NUMERIC if kind in _NUMERIC_KINDS else LAYER_WORKER
        stage: Optional[int] = None
        if kind == "badbatch" and target.startswith("step") \
                and target[4:].isdigit():
            # badbatch@stepK shorthand: the poisoned batch is global by
            # nature (every replica consumes it), so there is no rank
            if bits[1:]:
                raise ValueError(
                    f"chaos fault {part!r}: badbatch@stepK takes no "
                    "qualifiers")
            if int(target[4:]) < 1:
                raise ValueError(
                    f"chaos fault {part!r}: steps are 1-based")
            faults.append(ChaosFault("badbatch", None, int(target[4:]),
                                     layer=LAYER_NUMERIC))
            continue
        if target == "all":
            rank = None
        elif target.startswith("stage") and target[5:].isdigit():
            # pipeline stage-group fault domain: matches every rank of
            # the stage group (parallel/mpmd sets RLA_TPU_PIPELINE_STAGE
            # in each member's env; the injector filters on it)
            rank = None
            stage = int(target[5:])
        elif target.startswith("rank") and target[4:].isdigit():
            rank = int(target[4:])
        elif target.startswith("replica") and target[7:].isdigit():
            rank = int(target[7:])
            layer = LAYER_REPLICA
            if kind not in _REPLICA_KINDS:
                raise ValueError(
                    f"chaos fault {part!r}: replica-layer faults support "
                    f"{_REPLICA_KINDS} only (preempt/lost are whole-"
                    "process kinds — target the worker with 'rankN')")
        else:
            raise ValueError(
                f"chaos fault {part!r}: target must be 'rankN', "
                f"'replicaN', 'stageN' or 'all', got {target!r}")
        step: Optional[int] = None
        delay: Optional[float] = None
        once = False
        for q in bits[1:]:
            if q == "once":
                once = True
            elif q.startswith("step") and q[4:].isdigit():
                if layer == LAYER_REPLICA:
                    raise ValueError(
                        f"chaos fault {part!r}: replica faults count "
                        "serve CHUNKS — use 'chunkN', not 'stepN'")
                step = int(q[4:])
                if step < 1:
                    raise ValueError(
                        f"chaos fault {part!r}: steps are 1-based")
            elif q.startswith("chunk") and q[5:].isdigit():
                if layer != LAYER_REPLICA:
                    raise ValueError(
                        f"chaos fault {part!r}: 'chunkN' only applies to "
                        "replica-layer targets ('replicaN')")
                step = int(q[5:])
                if step < 1:
                    raise ValueError(
                        f"chaos fault {part!r}: chunks are 1-based")
            else:
                try:
                    delay = float(q)
                except ValueError:
                    raise ValueError(
                        f"chaos fault {part!r}: unknown qualifier {q!r} "
                        "(expected 'stepN'/'chunkN', 'once', or a float "
                        "delay)") from None
        if kind == "slow" and delay is None:
            raise ValueError(
                f"chaos fault {part!r}: 'slow' needs a float delay "
                "qualifier (e.g. slow@all:2.5)")
        if kind != "slow" and delay is not None:
            raise ValueError(
                f"chaos fault {part!r}: only 'slow' takes a delay")
        if kind == "badbatch" and rank is not None:
            raise ValueError(
                f"chaos fault {part!r}: badbatch is rank-less (the "
                "poisoned batch reaches every replica) — use "
                "'badbatch@stepK' or 'badbatch@all:stepK'")
        if kind in _NUMERIC_KINDS and stage is not None:
            raise ValueError(
                f"chaos fault {part!r}: numeric faults target 'rankN' "
                "or 'all' (the SPMD step builders), not a pipeline "
                "stage group")
        faults.append(ChaosFault(kind, rank, step, delay, once,
                                 layer=layer, stage=stage))
    return faults


class ChaosInjector:
    """Worker-process side: one per worker, consulted once per dispatch.

    ``freeze_heartbeat``: callable stopping the worker's beat thread
    (``WorkerBeat.freeze``) so a ``hang`` looks like a frozen process to
    the watchdog, not a long dispatch.

    ``layer`` selects which faults of the spec this injector honors:
    the worker dispatch loop builds a ``"worker"`` injector (steps =
    dispatches), the serve replica layer builds a ``"replica"`` one
    (steps = serve chunks) — one spec can carry both kinds and each
    seam only fires its own.
    """

    def __init__(self, faults: List[ChaosFault], rank: int,
                 freeze_heartbeat: Optional[Callable[[], None]] = None,
                 ns_dir: Optional[str] = None,
                 layer: str = LAYER_WORKER,
                 stage: Optional[int] = None):
        self.layer = layer
        # stage-targeted faults only arm inside their own stage group
        # (``stage`` = this process's RLA_TPU_PIPELINE_STAGE, if any)
        self.faults = [f for f in faults if f.layer == layer
                       and (f.stage is None or f.stage == stage)]
        self.rank = rank
        self.freeze_heartbeat = freeze_heartbeat
        self.ns_dir = ns_dir
        self._step = 0
        if any(f.once or f.kind in ("lost", "rejoin")
               for f in self.faults) and not ns_dir:
            raise ValueError(
                f"chaos 'once', 'lost' and 'rejoin' faults need "
                f"{CHAOS_NS_ENV} set to a directory (the cross-restart "
                "claim store)")
        # rejoin: the lost host comes back on its Kth respawn (K =
        # the fault's stepN, default 1) — count boot attempts while this
        # rank's lost marker(s) exist and clear them at the threshold,
        # BEFORE the death loop below reads them
        for f in self.faults:
            if f.kind != "rejoin" or (f.rank is not None
                                      and f.rank != rank):
                continue
            if not _lost_markers(rank, self.ns_dir):
                continue
            boots_path = os.path.join(self.ns_dir,
                                      f.token(rank) + ".boots")
            with open(boots_path, "ab") as fh:
                fh.write(b".")
            if os.path.getsize(boots_path) >= (f.step or 1):
                clear_lost(rank, self.ns_dir)
        # a rank whose 'lost' fault already fired is a gone host: every
        # respawned generation dies at boot, before serving any dispatch
        for f in self.faults:
            if (f.kind == "lost"
                    and (f.rank is None or f.rank == rank)
                    and os.path.exists(self._lost_marker(f))):
                os._exit(LOST_EXIT_CODE)

    @classmethod
    def from_env(cls, rank: int,
                 freeze_heartbeat: Optional[Callable[[], None]] = None,
                 layer: str = LAYER_WORKER) -> Optional["ChaosInjector"]:
        spec = knobs.get_str(CHAOS_ENV, "")
        if not spec:
            return None
        inj = cls(parse_chaos(spec), rank, freeze_heartbeat,
                  knobs.get_raw(CHAOS_NS_ENV) or None, layer=layer,
                  stage=knobs.get_int("RLA_TPU_PIPELINE_STAGE", None))
        return inj if inj.faults else None

    def _lost_marker(self, fault: ChaosFault) -> str:
        """Persistent 'host gone' marker path for a lost fault on THIS
        rank (rank-keyed: an elastic scale-down that drops the rank never
        leaks the marker onto survivors, which keep their own ranks)."""
        return os.path.join(self.ns_dir, fault.token(self.rank) + ".lost")

    def _claim_once(self, fault: ChaosFault) -> bool:
        """Atomically claim a once-fault across processes AND restarts:
        O_CREAT|O_EXCL on a token file -- first claimant fires, every
        later (re-spawned) process skips."""
        os.makedirs(self.ns_dir, exist_ok=True)
        path = os.path.join(self.ns_dir, fault.token(self.rank))
        try:
            os.close(os.open(path, os.O_CREAT | os.O_EXCL | os.O_WRONLY))
            return True
        except FileExistsError:
            return False

    def on_dispatch(self) -> None:
        """Called by the dispatch loop before executing the shipped fn."""
        self._step += 1
        for fault in self.faults:
            if fault.kind == "rejoin":
                # a boot-time kind (handled in __init__); its stepN
                # counts respawns, not dispatches
                continue
            if not fault.matches(self.rank, self._step):
                continue
            if fault.once and not self._claim_once(fault):
                continue
            if fault.kind == "slow":
                time.sleep(fault.delay_s)
            elif fault.kind == "crash":
                os._exit(CHAOS_EXIT_CODE)
            elif fault.kind == "preempt":
                # a spot notice IS a SIGTERM: the runtime.preemption
                # handler (installed when RLA_TPU_PREEMPT_GRACE_S is in
                # the worker env) flips the notice the dispatched body
                # drains; with no handler the default disposition kills
                # the process -- both are the real contract
                import signal
                os.kill(os.getpid(), signal.SIGTERM)
            elif fault.kind == "lost":
                # host gone: persist the marker FIRST so every respawn
                # dies at boot, then die
                os.makedirs(self.ns_dir, exist_ok=True)
                try:
                    os.close(os.open(self._lost_marker(fault),
                                     os.O_CREAT | os.O_WRONLY))
                except OSError:
                    pass
                os._exit(LOST_EXIT_CODE)
            elif fault.kind == "hang":
                if self.freeze_heartbeat is not None:
                    self.freeze_heartbeat()
                while True:  # wedged until the watchdog reaps us
                    time.sleep(3600)


# --------------------------------------------------------------------- #
# Numeric layer (anomaly-guardian faults, core/trainer.py build seams)   #
# --------------------------------------------------------------------- #
def numeric_faults() -> tuple:
    """Numeric-layer faults of the ambient ``RLA_TPU_CHAOS`` spec (empty
    tuple when unset — the zero-cost common case the trainer checks)."""
    spec = knobs.get_str(CHAOS_ENV, "")
    if not spec:
        return ()
    return tuple(f for f in parse_chaos(spec)
                 if f.layer == LAYER_NUMERIC)


def claim_numeric(fault: ChaosFault, rank: int = 0) -> bool:
    """Claim a numeric fault at step-BUILD time.  With a chaos namespace
    configured the claim is an atomic cross-process/cross-restart token
    (O_CREAT|O_EXCL), so the recompile after a guardian rewind builds a
    CLEAN step; without one every build re-arms the fault (single-fit
    unit tests that never rewind)."""
    ns_dir = knobs.get_raw(CHAOS_NS_ENV) or None
    if not ns_dir:
        return True
    os.makedirs(ns_dir, exist_ok=True)
    path = os.path.join(ns_dir, "numeric-" + fault.token(rank))
    try:
        os.close(os.open(path, os.O_CREAT | os.O_EXCL | os.O_WRONLY))
        return True
    except FileExistsError:
        return False


def poison_batch(batch):
    """``badbatch``'s host-side poison: NaN into the first element of
    every float leaf (copies — the loader's arrays stay clean).  Int-only
    batches pass through untouched (nothing to poison)."""
    import numpy as np

    def rec(x):
        if isinstance(x, dict):
            return {k: rec(v) for k, v in x.items()}
        if isinstance(x, tuple):
            return tuple(rec(v) for v in x)
        if isinstance(x, list):
            return [rec(v) for v in x]
        arr = np.asarray(x)
        if arr.dtype.kind == "f" and arr.size:
            arr = np.array(arr, copy=True)
            arr.reshape(-1)[0] = np.nan
            return arr
        return x

    return rec(batch)


def apply_traced_numeric(fault: ChaosFault, step, metrics, grads=None,
                         stacked=None):
    """Apply one TRACED numeric fault inside a jitted train step.

    ``step`` is the 0-based ``TrainState.step`` scalar (the fault's
    ``stepN`` is the 1-based global step about to complete); ``grads``
    is a global-view gradient tree, ``stacked`` a per-replica
    ``[n_replicas, ...]`` local-gradient tree (compressed paths) —
    whichever the calling builder has.  Everything is ``jnp.where``
    math on the traced values: injecting a fault never changes program
    structure, so the compile-guard retrace pins hold under chaos too.
    Returns ``(metrics, grads, stacked)`` with the transforms applied.
    """
    import jax
    import jax.numpy as jnp

    gate = jnp.asarray(step) == ((fault.step or 1) - 1)
    if fault.kind == "nanloss":
        loss = metrics.get("train_loss")
        if loss is not None:
            metrics = dict(metrics)
            metrics["train_loss"] = jnp.where(
                gate, jnp.asarray(jnp.nan, jnp.asarray(loss).dtype), loss)
        return metrics, grads, stacked

    tgt = stacked if stacked is not None else grads
    if tgt is None:
        return metrics, grads, stacked
    leaves, treedef = jax.tree.flatten(tgt)
    if not leaves:
        return metrics, grads, stacked

    if fault.kind == "gradspike":
        spike = jnp.where(gate, jnp.float32(1e4), jnp.float32(1.0))

        def sc(g):
            s = spike
            if stacked is not None and fault.rank is not None:
                # scale only the targeted replica's row
                row = jnp.arange(g.shape[0]) == fault.rank
                s = jnp.where(row, spike, 1.0).reshape(
                    (-1,) + (1,) * (g.ndim - 1))
            return (g.astype(jnp.float32) * s).astype(g.dtype)

        leaves = [sc(g) for g in leaves]
    elif fault.kind == "bitflip":
        # one exponent bit (1 << 27: +16 on the biased exponent, so the
        # value blows up by 2**16 — survives a bf16 round-trip) of one
        # element of the FIRST leaf; replica `rank`'s row when stacked
        g = leaves[0]
        f32 = g.astype(jnp.float32)
        bits = jax.lax.bitcast_convert_type(f32, jnp.uint32).reshape(-1)
        idx = 0
        if stacked is not None and fault.rank is not None and g.ndim > 0:
            per_row = 1
            for d in g.shape[1:]:
                per_row *= int(d)
            idx = min(fault.rank, g.shape[0] - 1) * per_row
        flipped = bits.at[idx].set(bits[idx] ^ jnp.uint32(1 << 27))
        out = jax.lax.bitcast_convert_type(
            jnp.where(gate, flipped, bits).reshape(f32.shape), jnp.float32)
        leaves = [out.astype(g.dtype)] + leaves[1:]
    else:  # badbatch is a HOST fault; nothing to do in-trace
        return metrics, grads, stacked

    tgt = jax.tree.unflatten(treedef, leaves)
    if stacked is not None:
        return metrics, grads, tgt
    return metrics, tgt, stacked
