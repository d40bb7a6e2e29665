"""Numeric anomaly guardian: in-step detection, blame, rewind-and-skip.

The fault-tolerance stack up to here handles *process-level* failure —
hangs (watchdog), preemptions, wedged replicas, mesh resizes, lost
pipeline stages.  A NaN loss, an exploding grad norm, or a silently
corrupted activation is invisible to all of it: the run keeps training
garbage until a human reads a loss curve.  This module closes that gap
in three layers:

- **Detection (traced, zero extra syncs)**: every train step carries a
  tiny guard vector in ``TrainState.guard_ema`` (f32[``GUARD_WIDTH``])
  updated by ``update()`` INSIDE the jitted step: finiteness of loss and
  global grad norm, grad-norm spike vs. a traced EMA envelope,
  update/param-norm ratio, and — where a per-replica gradient stack is
  available (compressed DP/FSDP) — a per-rank badness vector whose
  divergence names a suspect rank.  The packed flags piggyback on the
  existing metrics readback (``metrics["guard"]``), so guarded steps add
  no device round-trips and no retraces; ``guard=None`` keeps the step
  functions bit-identical to the unguarded build.
- **Blame (host, cold path)**: on trip, ``Guardian.check`` classifies
  before anyone acts.  Per-rank flag divergence → nondeterministic
  hardware fault (suspected SDC) with the rank named; non-finite values
  in the recorded host batch, or a reproducing plain replay (compression
  and int8 disabled) → data-poisoned; a trip that only reproduces with
  the compressed exchange enabled → exchange-induced; a trip that does
  not reproduce at all → suspected SDC.  The verdict ships as a typed
  ``NumericAnomaly`` (wire-registered like ``WorkerWedged``) carrying
  the offending step, the batch index range, and the blame classes.
- **Recovery (ElasticRunner)**: rewind to the newest *verified*
  checkpoint (``latest_checkpoint``'s digest walk — a truncated newest
  file is skipped, never restored), quarantine the blamed data window
  through a skip-list applied to the deterministic loader order (so the
  skip is identical across ranks and across restarts), bounded by a
  ``max_rewinds`` budget separate from the failure budget; the same step
  tripping twice post-quarantine is terminal, and an SDC-suspect verdict
  demotes the named rank via the existing elastic shrink path instead.
"""

from __future__ import annotations

import dataclasses
import json
import os
import tempfile
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Set, Tuple

from ..analysis import knobs
from ..telemetry import recorder as telemetry
from ..utils.logging import log

# --------------------------------------------------------------------- #
# Guard vector layout                                                    #
# --------------------------------------------------------------------- #
# One f32 vector rides in TrainState.guard_ema.  Scalars, not a struct:
# the vector crosses checkpoint serialization, sharding templates, and
# the scan carry unchanged, and a single replicated [GUARD_WIDTH] leaf is
# the cheapest possible addition to the donated state pytree.
I_EMA = 0           # EMA of the global grad norm (healthy steps only)
I_COUNT = 1         # healthy steps folded into the EMA (warmup gate)
I_TRIPPED = 2       # sticky 0/1: any flag fired since the last reset
I_TRIP_STEP = 3     # 0-based TrainState.step of the FIRST trip (-1)
I_FLAG_LOSS = 4     # first-trip flag: loss non-finite
I_FLAG_GRAD = 5     # first-trip flag: global grad norm non-finite
I_FLAG_SPIKE = 6    # first-trip flag: grad norm > spike_factor * EMA
I_FLAG_UPDATE = 7   # first-trip flag: update/param norm ratio too large
I_SUSPECT = 8       # first-trip suspect replica index, -1 = no single rank
I_NBAD = 9          # first-trip count of bad replicas (0 = no rank info)
GUARD_WIDTH = 10

# metrics["guard"] = concat(guard_ema, [grad_norm, update_ratio]) — the
# two live diagnostics ride along for the postmortem without being part
# of the carried state
METRIC_WIDTH = GUARD_WIDTH + 2

BLAME_DATA = "data"          # poisoned batch: quarantine the window
BLAME_EXCHANGE = "exchange"  # compressed-exchange overflow: rewind only
BLAME_SDC = "sdc"            # nondeterministic / rank-divergent: demote
BLAME_UNKNOWN = "unknown"


@dataclasses.dataclass(frozen=True)
class GuardConfig:
    """Guardian tuning; ``Trainer(guard="auto")`` builds it from the
    guard knob family (``from_env`` below names each one) and disables
    the guardian entirely when ``RLA_TPU_GUARD`` is false."""

    spike_factor: float = 10.0     # trip when gnorm > factor * EMA
    spike_floor: float = 1e-3      # gnorm below this never counts as a
    #   spike: a fully converged model's EMA decays toward 0 and the
    #   relative check would otherwise trip on numerically-zero jitter
    ema_decay: float = 0.9         # grad-norm EMA decay (healthy steps)
    warmup_steps: int = 20         # healthy steps before spike/update arm
    update_ratio_max: float = 0.5  # trip when |Δparams|/|params| exceeds
    max_rewinds: int = 2           # rewind budget (ElasticRunner default)

    @classmethod
    def from_env(cls) -> Optional["GuardConfig"]:
        if not knobs.get_bool("RLA_TPU_GUARD", True):
            return None
        return cls(
            spike_factor=knobs.get_float("RLA_TPU_GUARD_SPIKE_FACTOR", 10.0),
            spike_floor=knobs.get_float("RLA_TPU_GUARD_SPIKE_FLOOR", 1e-3),
            ema_decay=knobs.get_float("RLA_TPU_GUARD_EMA_DECAY", 0.9),
            warmup_steps=knobs.get_int("RLA_TPU_GUARD_WARMUP_STEPS", 20),
            update_ratio_max=knobs.get_float(
                "RLA_TPU_GUARD_UPDATE_RATIO_MAX", 0.5),
            max_rewinds=knobs.get_int("RLA_TPU_GUARD_MAX_REWINDS", 2),
        )


# --------------------------------------------------------------------- #
# Traced half: runs INSIDE the jitted train step                         #
# --------------------------------------------------------------------- #
def fresh_state():
    """A new guard vector (host-buildable: used in state templates)."""
    import numpy as np
    g = np.zeros((GUARD_WIDTH,), np.float32)
    g[I_TRIP_STEP] = -1.0
    g[I_SUSPECT] = -1.0
    return g


def per_replica_bad(stacked_local: Any, spike_factor: float):
    """Per-replica badness from a replica-stacked local-gradient tree
    ([n_replicas, ...] leaves): non-finite local grads, or a local norm
    spiking past ``spike_factor`` times the replica median.  Returns
    f32[n_replicas]; divergence (some-but-not-all bad) is the SDC
    signature — a poisoned *global* batch trips every replica at once."""
    import jax
    import jax.numpy as jnp

    sq = None
    finite = None
    for leaf in jax.tree.leaves(stacked_local):
        flat = leaf.reshape((leaf.shape[0], -1)).astype(jnp.float32)
        row_sq = jnp.sum(jnp.where(jnp.isfinite(flat), flat * flat, 0.0),
                         axis=1)
        row_fin = jnp.all(jnp.isfinite(flat), axis=1)
        sq = row_sq if sq is None else sq + row_sq
        finite = row_fin if finite is None else finite & row_fin
    if sq is None:
        return None
    norms = jnp.sqrt(sq)
    med = jnp.median(norms)
    bad = (~finite) | (norms > spike_factor * (med + 1e-12))
    return bad.astype(jnp.float32)


def update(cfg: GuardConfig, guard: Any, step: Any, loss: Any, gnorm: Any,
           ratio: Any, rank_bad: Any = None) -> Tuple[Any, Any]:
    """One traced guard-state transition.  Returns ``(new_guard,
    guard_metric)``: the carried f32[GUARD_WIDTH] vector and the
    f32[METRIC_WIDTH] row packed into ``metrics["guard"]``.  Pure
    element-wise math on scalars — no collectives, no host callbacks —
    so it fuses into the step program and costs nothing observable."""
    import jax.numpy as jnp

    loss = jnp.asarray(loss, jnp.float32)
    gnorm = jnp.asarray(gnorm, jnp.float32)
    ratio = jnp.asarray(ratio, jnp.float32)
    ema = guard[I_EMA]
    count = guard[I_COUNT]
    tripped = guard[I_TRIPPED]

    f_loss = ~jnp.isfinite(loss)
    f_grad = ~jnp.isfinite(gnorm)
    warm = count >= cfg.warmup_steps
    f_spike = warm & jnp.isfinite(gnorm) & (gnorm > cfg.spike_floor) & (
        gnorm > cfg.spike_factor * (ema + 1e-12))
    f_update = warm & ((~jnp.isfinite(ratio)) |
                       (ratio > cfg.update_ratio_max))
    unhealthy = f_loss | f_grad | f_spike | f_update

    if rank_bad is not None:
        n_bad = jnp.sum(rank_bad)
        n = rank_bad.shape[0]
        lone = (n_bad > 0) & (n_bad < n)
        suspect = jnp.where(lone, jnp.argmax(rank_bad).astype(jnp.float32),
                            -1.0)
    else:
        n_bad = jnp.float32(0.0)
        suspect = jnp.float32(-1.0)

    healthy = ~unhealthy
    new_ema = jnp.where(healthy,
                        jnp.where(count > 0,
                                  cfg.ema_decay * ema +
                                  (1.0 - cfg.ema_decay) * gnorm,
                                  gnorm),
                        ema)
    new_count = count + healthy.astype(jnp.float32)
    # the FIRST trip freezes the postmortem fields; later steps keep the
    # sticky bit but never overwrite the evidence
    first = unhealthy & (tripped == 0.0)

    def _pin(new, old):
        return jnp.where(first, new, old)

    new_g = jnp.stack([
        new_ema,
        new_count,
        jnp.maximum(tripped, unhealthy.astype(jnp.float32)),
        _pin(jnp.asarray(step, jnp.float32), guard[I_TRIP_STEP]),
        _pin(f_loss.astype(jnp.float32), guard[I_FLAG_LOSS]),
        _pin(f_grad.astype(jnp.float32), guard[I_FLAG_GRAD]),
        _pin(f_spike.astype(jnp.float32), guard[I_FLAG_SPIKE]),
        _pin(f_update.astype(jnp.float32), guard[I_FLAG_UPDATE]),
        _pin(suspect, guard[I_SUSPECT]),
        _pin(jnp.asarray(n_bad, jnp.float32), guard[I_NBAD]),
    ])
    metric = jnp.concatenate([new_g, jnp.stack([gnorm, ratio])])
    return new_g, metric


# --------------------------------------------------------------------- #
# Typed anomaly (wire-registered)                                        #
# --------------------------------------------------------------------- #
class NumericAnomaly(RuntimeError):
    """A guarded step tripped (or a serve decode produced non-finite
    logits).  Carries the blame verdict so retry layers can branch:
    ``ElasticRunner`` rewinds on data/exchange blame without charging the
    failure budget, and demotes the suspect rank on SDC blame.  Crosses
    the worker pipe via the wire registry (``runtime/wire.py``), with the
    structured postmortem embedded in the message after ``_MARKER``."""

    _MARKER = "| anomaly="

    def __init__(self, message: str, step: Optional[int] = None,
                 blame: str = BLAME_UNKNOWN,
                 suspect_rank: Optional[int] = None,
                 epoch: Optional[int] = None,
                 batch_idx: Optional[int] = None,
                 stage: Optional[int] = None,
                 diagnosis: Optional[Dict[str, Any]] = None):
        super().__init__(message)
        self.step = step
        self.blame = blame
        self.suspect_rank = suspect_rank
        self.epoch = epoch
        self.batch_idx = batch_idx
        self.stage = stage
        self.diagnosis = dict(diagnosis or {})

    @classmethod
    def for_trip(cls, step: int, blame: str,
                 flags: Optional[Dict[str, Any]] = None,
                 suspect_rank: Optional[int] = None,
                 epoch: Optional[int] = None,
                 batch_idx: Optional[int] = None,
                 stage: Optional[int] = None,
                 detail: str = "") -> "NumericAnomaly":
        diagnosis: Dict[str, Any] = {
            "step": step, "blame": blame, "flags": dict(flags or {}),
        }
        if suspect_rank is not None:
            diagnosis["suspect_rank"] = suspect_rank
        if epoch is not None:
            diagnosis["epoch"] = epoch
        if batch_idx is not None:
            diagnosis["batch_idx"] = batch_idx
        if stage is not None:
            diagnosis["stage"] = stage
        where = f"stage {stage} " if stage is not None else ""
        msg = (f"numeric anomaly at {where}step {step} (blame={blame})"
               f"{': ' + detail if detail else ''} "
               f"{cls._MARKER}"
               f"{json.dumps(diagnosis, sort_keys=True, default=str)}")
        return cls(msg, step=step, blame=blame, suspect_rank=suspect_rank,
                   epoch=epoch, batch_idx=batch_idx, stage=stage,
                   diagnosis=diagnosis)

    @classmethod
    def from_message(cls, message: str) -> "NumericAnomaly":
        """Rebuild from a message that crossed a wire as (name, str, tb),
        recovering the embedded postmortem (tolerant of truncation)."""
        diagnosis: Dict[str, Any] = {}
        i = message.find(cls._MARKER)
        if i >= 0:
            try:
                diagnosis = json.loads(message[i + len(cls._MARKER):])
            except ValueError:
                pass
        return cls(message,
                   step=diagnosis.get("step"),
                   blame=diagnosis.get("blame", BLAME_UNKNOWN),
                   suspect_rank=diagnosis.get("suspect_rank"),
                   epoch=diagnosis.get("epoch"),
                   batch_idx=diagnosis.get("batch_idx"),
                   stage=diagnosis.get("stage"),
                   diagnosis=diagnosis)


# --------------------------------------------------------------------- #
# Quarantine ledger (atomic JSON under <root>/guardian/)                 #
# --------------------------------------------------------------------- #
def _quarantine_path(root_dir: str) -> str:
    return os.path.join(root_dir, "guardian", "quarantine.json")


def load_quarantine(root_dir: str) -> Dict[str, Any]:
    path = _quarantine_path(root_dir)
    try:
        with open(path, "r", encoding="utf-8") as fh:
            doc = json.load(fh)
        if isinstance(doc, dict) and isinstance(doc.get("entries"), list):
            return doc
    except (OSError, ValueError):
        pass
    return {"entries": [], "anchor": None}


def _write_quarantine(root_dir: str, doc: Dict[str, Any]) -> None:
    path = _quarantine_path(root_dir)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path),
                               prefix=".quarantine-", suffix=".tmp")
    try:
        with os.fdopen(fd, "w", encoding="utf-8") as fh:
            json.dump(doc, fh, sort_keys=True, indent=1)
        os.replace(tmp, path)  # atomic: a crashed writer never tears it
    except BaseException:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        raise


def add_quarantine(root_dir: str, epoch: int, batch_idx: int, step: int,
                   anchor: Optional[str] = None) -> Dict[str, Any]:
    """Append one blamed (epoch, batch_idx) window and pin the rewind
    anchor (the checkpoint pruning must keep alive while the quarantine
    is active)."""
    doc = load_quarantine(root_dir)
    entry = {"epoch": int(epoch), "batch_idx": int(batch_idx),
             "step": int(step)}
    if entry not in doc["entries"]:
        doc["entries"].append(entry)
    if anchor:
        doc["anchor"] = anchor
    _write_quarantine(root_dir, doc)
    return doc


def release_anchor(root_dir: str) -> None:
    """Drop the prune protection once a fit ran CLEAN past the quarantined
    window — newer verified checkpoints now cover it.  The skip entries
    stay (the data is still bad); only the pin goes."""
    doc = load_quarantine(root_dir)
    if doc.get("anchor"):
        doc["anchor"] = None
        _write_quarantine(root_dir, doc)


def skip_set(root_dir: str, epoch: int) -> Set[int]:
    """Batch indices quarantined for ``epoch`` — consulted by the loader
    wrap; a pure function of the JSON ledger, so every rank and every
    restart computes the identical set."""
    return {int(e["batch_idx"]) for e in load_quarantine(root_dir)["entries"]
            if int(e["epoch"]) == int(epoch)}


def protected_paths(dirpath: str) -> List[str]:
    """Checkpoint paths pruning must keep: the active rewind anchor, if
    a quarantine ledger lives at ``dirpath`` or one directory up (the
    checkpoint dir is usually ``<root>/`` itself or ``<root>/checkpoints``).
    Called by ``ModelCheckpoint._prune``, which has no trainer handle."""
    out: List[str] = []
    for root in (dirpath, os.path.dirname(os.path.abspath(dirpath))):
        anchor = load_quarantine(root).get("anchor")
        if anchor:
            out.append(anchor)
    return out


# --------------------------------------------------------------------- #
# Host half: trip handling, blame, quarantine                            #
# --------------------------------------------------------------------- #
class Guardian:
    """Driver-side companion to the traced guard vector.  Remembers the
    last few dispatched batches (``note_step``), and on a tripped guard
    readback classifies blame, writes the quarantine ledger, emits the
    flight-recorder events, and raises the typed ``NumericAnomaly``."""

    RING = 8  # batches of lookback; trips surface within one readback

    def __init__(self, cfg: GuardConfig, root_dir: str):
        self.cfg = cfg
        self.root_dir = root_dir
        self._ring: deque = deque(maxlen=self.RING)

    # -- bookkeeping ---------------------------------------------------- #
    def note_step(self, step: int, epoch: int, batch_idx: int,
                  kind: str, payload: Any) -> None:
        """Record what the step ABOUT to run at ``step`` consumes.  Host
        refs only — no device work, no copies."""
        self._ring.append((int(step), int(epoch), int(batch_idx), kind,
                           payload))

    def _lookup(self, step: int):
        for rec in reversed(self._ring):
            if rec[0] == step:
                return rec
        return None

    def skip_set(self, epoch: int) -> Set[int]:
        return skip_set(self.root_dir, epoch)

    def has_quarantine(self) -> bool:
        return bool(load_quarantine(self.root_dir)["entries"])

    def release_anchor(self) -> None:
        release_anchor(self.root_dir)

    # -- blame ---------------------------------------------------------- #
    @staticmethod
    def _batch_nonfinite(payload: Any) -> bool:
        import numpy as np
        try:
            for leaf in _tree_leaves(payload):
                arr = np.asarray(leaf)
                if arr.dtype.kind == "f" and not np.all(np.isfinite(arr)):
                    return True
        except Exception:
            return False
        return False

    def classify(self, flags: Dict[str, Any], suspect_rank: int,
                 n_bad: int, entry: Optional[Tuple],
                 replay: Optional[Callable[[Any], Dict[str, bool]]],
                 compression_active: bool) -> Tuple[str, Optional[int]]:
        """The blame cascade.  Cheap evidence first, the replay (a fresh
        compile on the cold path) last:

        1. rank divergence (some-but-not-all replicas bad) → SDC, named;
        2. non-finite floats in the recorded host batch → data;
        3. plain replay (compression/int8 off) reproduces → data;
        4. reproducible only through the compressed exchange → exchange;
        5. nothing reproduces → nondeterministic, suspected SDC.
        """
        if n_bad > 0 and suspect_rank >= 0:
            return BLAME_SDC, suspect_rank
        payload = entry[4] if entry is not None else None
        kind = entry[3] if entry is not None else None
        if kind == "host" and payload is not None and \
                self._batch_nonfinite(payload):
            return BLAME_DATA, None
        if replay is not None and payload is not None and kind == "host":
            try:
                plain = replay(payload)
            except Exception as e:  # replay must never mask the trip
                log(f"guardian: blame replay failed ({e!r})")
                plain = None
            if plain is not None:
                if plain.get("loss_nonfinite") or plain.get(
                        "grad_nonfinite"):
                    return BLAME_DATA, None
                if compression_active and (flags.get("grad_nonfinite") or
                                           flags.get("spike")):
                    return BLAME_EXCHANGE, None
                return BLAME_SDC, None
        return BLAME_UNKNOWN, None

    # -- trip ----------------------------------------------------------- #
    def check(self, guard_host: Any, *,
              replay: Optional[Callable[[Any], Dict[str, bool]]] = None,
              compression_active: bool = False) -> None:
        """Inspect one host guard row (``metrics["guard"]`` after the
        readback that was happening anyway).  No-op while healthy; on a
        sticky trip: blame → quarantine (data blame) → telemetry →
        raise ``NumericAnomaly``."""
        if guard_host is None:
            return
        import numpy as np
        g = np.asarray(guard_host, np.float32).reshape(-1)
        if g.shape[0] < GUARD_WIDTH or g[I_TRIPPED] == 0.0:
            return
        step = int(g[I_TRIP_STEP])
        flags = {
            "loss_nonfinite": bool(g[I_FLAG_LOSS]),
            "grad_nonfinite": bool(g[I_FLAG_GRAD]),
            "spike": bool(g[I_FLAG_SPIKE]),
            "update_ratio": bool(g[I_FLAG_UPDATE]),
        }
        if g.shape[0] >= METRIC_WIDTH:
            flags["grad_norm"] = float(g[GUARD_WIDTH])
            flags["update_ratio_value"] = float(g[GUARD_WIDTH + 1])
        suspect = int(g[I_SUSPECT])
        n_bad = int(g[I_NBAD])
        entry = self._lookup(step)
        epoch = entry[1] if entry is not None else None
        batch_idx = entry[2] if entry is not None else None
        blame, named = self.classify(flags, suspect, n_bad, entry, replay,
                                     compression_active)
        telemetry.emit("anomaly_trip", step=step, blame=blame,
                       suspect_rank=named, epoch=epoch,
                       batch_idx=batch_idx, **{
                           k: v for k, v in flags.items()
                           if isinstance(v, bool)})
        if blame == BLAME_DATA and epoch is not None and \
                batch_idx is not None:
            anchor = self._rewind_anchor()
            add_quarantine(self.root_dir, epoch, batch_idx, step,
                           anchor=anchor)
            telemetry.emit("quarantine", epoch=epoch, batch_idx=batch_idx,
                           step=step, anchor=anchor)
        raise NumericAnomaly.for_trip(
            step, blame, flags=flags, suspect_rank=named, epoch=epoch,
            batch_idx=batch_idx,
            detail=", ".join(k for k, v in flags.items()
                             if isinstance(v, bool) and v) or "tripped")

    def _rewind_anchor(self) -> Optional[str]:
        """Newest VERIFIED checkpoint at trip time — the digest walk in
        ``latest_checkpoint`` skips a truncated newest file, so the
        anchor is always restorable."""
        from ..utils import checkpoint as ckpt_lib
        try:
            return ckpt_lib.latest_checkpoint(self.root_dir)
        except Exception:
            return None


def _tree_leaves(payload: Any):
    """Flatten a host batch without importing jax on the cold path when
    numpy suffices (dicts/tuples/lists of arrays)."""
    if isinstance(payload, dict):
        for v in payload.values():
            yield from _tree_leaves(v)
    elif isinstance(payload, (list, tuple)):
        for v in payload:
            yield from _tree_leaves(v)
    elif payload is not None and not isinstance(payload, (str, bytes)):
        yield payload
