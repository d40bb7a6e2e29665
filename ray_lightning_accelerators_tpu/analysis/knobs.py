"""The ``RLA_TPU_*`` environment-knob registry and its typed getters.

Every env knob the package reads is declared here — name, type, default,
and one-line help — and read through a typed getter.  The contract
(PR 5's warn-and-default behavior, made the checked norm):

- **malformed values never crash**: a bad ``RLA_TPU_FLASH_BLOCK_Q=abc``
  logs one warning and falls back to the default, instead of raising
  deep inside a trace or at import time;
- **unregistered names never parse silently**: a getter called with a
  name missing from ``KNOBS`` raises ``LookupError`` — registering here
  is the one-line cost of adding a knob, and graftlint's
  ``knob-registry`` rule statically rejects raw ``os.environ`` reads of
  ``RLA_TPU_*`` names anywhere else in the package;
- **per-worker overlays**: runtime code that honors a per-worker env
  dict before the process env (watchdog heartbeats, preemption grace)
  passes it as ``env=`` — the overlay wins when it has the key.

This module is a dependency leaf (stdlib only): ``utils.logging`` and
the runtime modules import it, never the reverse.
"""

from __future__ import annotations

import logging
import os
from dataclasses import dataclass
from typing import Dict, Mapping, Optional

# child of the package logger (utils/logging.py configures the parent's
# handler); importing utils.logging here would be circular, since the
# log-level knob itself is read through this registry
log = logging.getLogger("ray_lightning_accelerators_tpu.knobs")

KINDS = ("str", "int", "float", "bool", "flag")

# values get_bool accepts; anything else warns and uses the default
_TRUE = frozenset(("1", "true", "yes", "on"))
_FALSE = frozenset(("0", "false", "no", "off", ""))


@dataclass(frozen=True)
class Knob:
    """One declared environment knob.

    ``kind``: parse discipline — ``flag`` is presence-truthiness (any
    non-empty value enables, matching ``os.environ.get(X)`` gates),
    ``bool`` parses 1/true/yes/on vs 0/false/no/off.  ``default`` is
    documentation of the effective default; call sites may pass their
    own (module constants stay authoritative).  ``scope``: where the
    knob is read — ``package`` knobs are enforced by graftlint; tests/
    scripts knobs are registered for the docs table and tooling."""

    name: str
    kind: str
    default: object
    help: str
    scope: str = "package"


KNOBS: Dict[str, Knob] = {}


def _register(knob: Knob) -> Knob:
    if knob.kind not in KINDS:
        raise ValueError(f"unknown knob kind {knob.kind!r} for {knob.name}")
    if knob.name in KNOBS:
        raise ValueError(f"duplicate knob registration: {knob.name}")
    KNOBS[knob.name] = knob
    return knob


# --------------------------------------------------------------------- #
# Registry (alphabetical).  graftlint extracts these names statically   #
# (Knob("LITERAL", ...)), so names must stay string literals.           #
# --------------------------------------------------------------------- #
_register(Knob("RLA_TPU_AGENTS", "str", "",
               "comma-separated host:port agent list for the multi-host "
               "driver (runtime/agent.py; also set by the CLI)"))
_register(Knob("RLA_TPU_AGENT_CONNECT_TIMEOUT", "float", 30.0,
               "seconds to keep retrying an unreachable agent while it "
               "boots (runtime/agent.py)"))
_register(Knob("RLA_TPU_AGENT_TOKEN", "str", "",
               "shared secret authenticating driver<->agent connections "
               "(runtime/agent.py)"))
_register(Knob("RLA_TPU_ALLOW_TOKENLESS_BIND", "bool", False,
               "allow an agent to bind without RLA_TPU_AGENT_TOKEN "
               "(loopback/dev only; runtime/agent.py)"))
_register(Knob("RLA_TPU_CHAOS", "str", "",
               "deterministic fault-injection spec, e.g. "
               "'hang@rank1:step2' (testing/chaos.py; conftest guards "
               "it outside chaos-marked tests)"))
_register(Knob("RLA_TPU_CHAOS_NS", "str", "",
               "namespace directory keying once-across-restart chaos "
               "token files (testing/chaos.py)"))
_register(Knob("RLA_TPU_DISABLE_PALLAS", "flag", False,
               "disable the pallas flash-attention / fused-norm kernels "
               "(ops/attention.py, ops/norms.py)"))
_register(Knob("RLA_TPU_DISABLE_Q8_KERNEL", "flag", False,
               "disable the int8 matmul decode kernel "
               "(models/transformer.py)"))
_register(Knob("RLA_TPU_ELASTIC_BACKOFF_S", "float", 0.0,
               "base seconds for ElasticRunner's exponential "
               "restart backoff; <=0 disables (runtime/elastic.py)"))
_register(Knob("RLA_TPU_ELASTIC_BACKOFF_CAP_S", "float", 60.0,
               "cap on the exponential restart backoff "
               "(runtime/elastic.py)"))
_register(Knob("RLA_TPU_FLASH_BLOCK_Q", "int", 512,
               "flash-attention q block size, read at trace time "
               "(ops/attention.py)"))
_register(Knob("RLA_TPU_FLASH_BLOCK_K", "int", 512,
               "flash-attention k block size, read at trace time "
               "(ops/attention.py)"))
_register(Knob("RLA_TPU_GLOBAL_SEED", "int", None,
               "global seed honored by seed_everything(); exported to "
               "children (utils/seed.py)"))
_register(Knob("RLA_TPU_GUARD", "bool", True,
               "numeric anomaly guardian: in-step NaN/spike detection "
               "riding the metrics readback, with rewind-and-skip "
               "recovery (runtime/guardian.py)"))
_register(Knob("RLA_TPU_GUARD_EMA_DECAY", "float", 0.9,
               "decay of the traced grad-norm EMA envelope the spike "
               "check compares against (runtime/guardian.py)"))
_register(Knob("RLA_TPU_GUARD_MAX_REWINDS", "int", 2,
               "rewind budget per fit: trips beyond it are terminal "
               "(runtime/guardian.py, runtime/elastic.py)"))
_register(Knob("RLA_TPU_GUARD_SPIKE_FACTOR", "float", 10.0,
               "grad-norm spike threshold as a multiple of the EMA "
               "envelope (runtime/guardian.py)"))
_register(Knob("RLA_TPU_GUARD_SPIKE_FLOOR", "float", 1e-3,
               "absolute grad norm below which the spike check never "
               "fires — keeps a converged model's near-zero EMA from "
               "tripping on jitter (runtime/guardian.py)"))
_register(Knob("RLA_TPU_GUARD_UPDATE_RATIO_MAX", "float", 0.5,
               "max update-norm / param-norm ratio before the guard "
               "flags the step (runtime/guardian.py)"))
_register(Knob("RLA_TPU_GUARD_WARMUP_STEPS", "int", 20,
               "steps before the spike / update-ratio checks arm (the "
               "EMA envelope needs history) (runtime/guardian.py)"))
_register(Knob("RLA_TPU_INSIDE_WORKER", "bool", False,
               "set in spawned workers so nested code never re-launches "
               "a world (core/trainer.py, runtime)"))
_register(Knob("RLA_TPU_LIVE_REFRESH_S", "float", 2.0,
               "driver ClusterView refresh cadence in seconds — how "
               "often every rank's live /snapshot is re-collected "
               "(telemetry/live.py)"))
_register(Knob("RLA_TPU_LOG_JSON", "bool", False,
               "structured-JSON log lines (one object per line with "
               "ts/level/rank/pid/msg) instead of the human formatter "
               "(utils/logging.py)"))
_register(Knob("RLA_TPU_LOG_LEVEL", "str", "WARNING",
               "package logger level; unknown names warn and default "
               "(utils/logging.py)"))
_register(Knob("RLA_TPU_METRICS_PORT", "int", None,
               "enable the live telemetry plane: port for the per-"
               "process /metrics + /statusz + /healthz HTTP server "
               "(loopback-bound; 0 = ephemeral — workers always bind "
               "ephemeral and publish the port via a portfile under "
               "RLA_TPU_TELEMETRY_DIR); unset = no server "
               "(telemetry/live.py)"))
_register(Knob("RLA_TPU_PERF_HBM_SAMPLE_S", "float", 2.0,
               "minimum seconds between HBM-ledger pool samples; the "
               "per-step seam is a no-op inside the window "
               "(telemetry/perf.py)"))
_register(Knob("RLA_TPU_PERF_LEAK_MIN_BYTES", "int", 33554432,
               "total placed-bytes growth a leak streak must reach "
               "before the hbm_leak event fires (telemetry/perf.py)"))
_register(Knob("RLA_TPU_PERF_LEAK_SAMPLES", "int", 8,
               "consecutive growing HBM samples before the leak alarm "
               "arms (telemetry/perf.py)"))
_register(Knob("RLA_TPU_PERF_TIMELINE_RING", "int", 64,
               "per-step phase-timeline ring capacity in recent-step "
               "rows (telemetry/perf.py)"))
_register(Knob("RLA_TPU_PIPELINE_CKPT_EVERY", "int", 1,
               "MPMD pipeline checkpoint cadence in optimizer steps — "
               "the replay floor after a stage-group failure "
               "(parallel/mpmd/driver.py)"))
_register(Knob("RLA_TPU_PIPELINE_HANDOFF_TIMEOUT_S", "float", 60.0,
               "seconds a pipeline stage waits on a neighbor's mailbox "
               "handoff before failing typed PipelineHandoffTimeout "
               "(parallel/mpmd/handoff.py)"))
_register(Knob("RLA_TPU_PIPELINE_MAX_FAILURES", "int", 2,
               "per-stage-group failure budget: charged failures past "
               "this raise terminal PipelineStageFailed "
               "(parallel/mpmd/driver.py)"))
_register(Knob("RLA_TPU_PIPELINE_STAGE", "int", None,
               "this worker's pipeline stage index, set in each stage "
               "group member's env overlay by the PipelineRunner — read "
               "by chaos 'stageN' fault filtering "
               "(parallel/mpmd/driver.py, testing/chaos.py)"))
_register(Knob("RLA_TPU_PIPELINE_STEP_DEADLINE_S", "float", None,
               "driver-side per-step future-gather deadline for MPMD "
               "pipeline steps; unset derives a backstop from the "
               "handoff timeout (parallel/mpmd/driver.py)"))
_register(Knob("RLA_TPU_PREEMPT_CONSENSUS_EVERY", "int", 8,
               "multi-process drain-consensus cadence in steps "
               "(core/trainer.py)"))
_register(Knob("RLA_TPU_PREEMPT_GRACE_S", "float", None,
               "preemption grace budget in seconds; setting it installs "
               "the SIGTERM notice handler (runtime/preemption.py)"))
_register(Knob("RLA_TPU_SEQ_PARALLEL_MODE", "str", "ulysses",
               "default context-parallel attention strategy for "
               "Trainer(seq_parallel>1) when seq_parallel_mode is not "
               "passed: 'ulysses' (all_to_all head-scatter; needs heads "
               "divisible by the axis) or 'ring' (ppermute KV rotation) "
               "(core/trainer.py)"))
_register(Knob("RLA_TPU_SERVE_AFFINITY", "bool", True,
               "prefix-affinity routing: send a request to the replica "
               "whose KV cache holds the longest resident run of its "
               "chain-hashed prefix keys (breaker/drain states always "
               "override; hedges are deliberate misses) "
               "(serve/controller.py)"))
_register(Knob("RLA_TPU_SERVE_AFFINITY_RESIDENCY", "int", 4096,
               "per-replica cap on tracked prefix-key residency (LRU); "
               "bounds router memory, not the replica's real cache "
               "(serve/controller.py)"))
_register(Knob("RLA_TPU_SERVE_AFFINITY_VNODES", "int", 32,
               "virtual nodes per replica on the prefix-affinity "
               "consistent-hash ring; cold keys place on their ring "
               "owner so repeats converge (serve/controller.py)"))
_register(Knob("RLA_TPU_SERVE_BREAKER_FAILURES", "int", 3,
               "serve circuit breaker: failures in the rolling window "
               "before the reopen backoff starts growing exponentially "
               "(below it every open waits the base delay) "
               "(serve/controller.py)"))
_register(Knob("RLA_TPU_SERVE_BREAKER_WINDOW_S", "float", 30.0,
               "serve circuit breaker rolling failure window in seconds "
               "(serve/controller.py)"))
_register(Knob("RLA_TPU_SERVE_BROWNOUT_FRAC", "float", 0.9,
               "queue-depth fraction past which a saturated tier with "
               "no scale-up headroom sheds typed BrownoutShed "
               "(serve/controller.py)"))
_register(Knob("RLA_TPU_SERVE_CHUNK_BLOCKS", "int", 8,
               "big-chunk quantum, in KV blocks, a streaming long-prompt "
               "prefill advances per engine loop while no decode slot is "
               "active (serve/engine.py)"))
_register(Knob("RLA_TPU_SERVE_CHUNK_MIN_BLOCKS", "int", 1,
               "small-chunk quantum, in KV blocks, a streaming long-"
               "prompt prefill advances between live decode waves; keeps "
               "decode cadence bounded while the prefill cursor makes "
               "progress (serve/engine.py)"))
_register(Knob("RLA_TPU_SERVE_HANDOFF_MIN_BLOCKS", "int", 1,
               "minimum full prompt blocks before a request takes the "
               "prefill-lane + KV-handoff path (below it the request "
               "serves end-to-end on a decode-lane replica) "
               "(serve/replicas.py)"))
_register(Knob("RLA_TPU_SERVE_HANDOFF_WAVE_BYTES", "int", 4 << 20,
               "per-wave byte bound on the KV block copy a prefill->"
               "decode handoff ships through the object store "
               "(parallel/redistribute.py wave_schedule; "
               "serve/engine.py)"))
_register(Knob("RLA_TPU_SERVE_HEDGE", "bool", True,
               "hedged re-dispatch of a slow replica's oldest in-flight "
               "chunk onto a healthy replica (serve/controller.py)"))
_register(Knob("RLA_TPU_SERVE_MAX_REPLICAS", "int", None,
               "autoscale ceiling on serve replica count; unset "
               "disables scale-up (serve/controller.py)"))
_register(Knob("RLA_TPU_SERVE_MAX_RETRIES", "int", 2,
               "per-request infra-failure retry budget before a serve "
               "request fails typed (serve/controller.py)"))
_register(Knob("RLA_TPU_SERVE_PREFILL_REPLICAS", "int", 0,
               "replicas dedicated to the prefill lane (lowest ranks); "
               "0 disables disaggregated lanes and every replica serves "
               "end-to-end (serve/controller.py)"))
_register(Knob("RLA_TPU_SERVE_RETRY_BACKOFF_S", "float", 0.02,
               "base seconds of the serve request-retry exponential "
               "backoff (utils/backoff.py schedule; "
               "serve/controller.py)"))
_register(Knob("RLA_TPU_SERVE_RETRY_BACKOFF_CAP_S", "float", 1.0,
               "cap on the serve request-retry backoff "
               "(serve/controller.py)"))
_register(Knob("RLA_TPU_SERVE_REVIVE_BACKOFF_S", "float", 0.5,
               "base seconds of the replica circuit-breaker reopen "
               "backoff (serve/controller.py)"))
_register(Knob("RLA_TPU_SERVE_REVIVE_BACKOFF_CAP_S", "float", 15.0,
               "cap on the replica circuit-breaker reopen backoff "
               "(serve/controller.py)"))
_register(Knob("RLA_TPU_SERVE_SCALE_UP_BURN", "float", 1.0,
               "sustained slo_burn_rate at/above which the serve tier "
               "scales replica count up (serve/controller.py)"))
_register(Knob("RLA_TPU_SERVE_SLOW_P99_S", "float", None,
               "p99 decode-step latency past which a replica is "
               "classified slow (skipped by routing, hedge-eligible); "
               "unset leaves only the watchdog straggler signal "
               "(serve/controller.py)"))
_register(Knob("RLA_TPU_SLO_DEADLINE_S", "float", None,
               "serve SLO: end-to-end deadline stamped on each request "
               "at admission; expired requests are shed typed "
               "(DeadlineExceeded) before prefill (serve/slo.py)"))
_register(Knob("RLA_TPU_SLO_TARGET", "float", 0.99,
               "serve SLO target fraction (e.g. 0.99 = '99% of "
               "requests'); burn rate divides the observed violation "
               "fraction by 1 - target (serve/slo.py)"))
_register(Knob("RLA_TPU_SLO_TOKEN_CADENCE_S", "float", None,
               "serve SLO: per-token inter-arrival target; decode gaps "
               "above it count as violations (serve/slo.py)"))
_register(Knob("RLA_TPU_SLO_TTFT_S", "float", None,
               "serve SLO: time-to-first-token target; prefills landing "
               "above it count as violations (serve/slo.py)"))
_register(Knob("RLA_TPU_SLO_WINDOW_S", "float", 60.0,
               "rolling window for serve SLO burn-rate accounting "
               "(serve/slo.py)"))
_register(Knob("RLA_TPU_SPMD_SANITIZER", "bool", False,
               "opt-in cross-rank collective sanitizer: each process "
               "records its traced collective call sequence and the "
               "driver diffs sequences across ranks after fan-out/chaos "
               "runs (testing/spmd_sanitizer.py)"))
_register(Knob("RLA_TPU_SPMD_SEQ_EVENTS", "int", 512,
               "sanitizer sequence-ring capacity in recorded collective "
               "calls per process (testing/spmd_sanitizer.py)"))
_register(Knob("RLA_TPU_TELEMETRY", "bool", True,
               "enable the flight recorder; 0 makes every emit a no-op "
               "(telemetry/recorder.py)"))
_register(Knob("RLA_TPU_TELEMETRY_DIR", "str", None,
               "directory for per-rank flight-recorder spill files "
               "(rank{N}.events.json) — the crash-observable channel the "
               "watchdog/agent/run-report read (telemetry/recorder.py)"))
_register(Knob("RLA_TPU_TELEMETRY_EVENTS", "int", 256,
               "flight-recorder ring capacity in events "
               "(telemetry/recorder.py)"))
_register(Knob("RLA_TPU_TELEMETRY_SPILL_S", "float", 0.5,
               "minimum seconds between flight-recorder spills; the "
               "first emit always spills (telemetry/recorder.py)"))
_register(Knob("RLA_TPU_TEST_PLATFORM", "str", "cpu",
               "platform the test suite binds (tests/conftest.py); "
               "'tpu' gates real-chip runs", scope="tests"))
_register(Knob("RLA_TPU_TRACE_ID", "str", None,
               "ambient trace id a spawned process stamps on its "
               "flight-recorder events — set in env_per_worker so one "
               "run correlates across driver/agent/workers "
               "(telemetry/recorder.py)"))
_register(Knob("RLA_TPU_WEDGE_TIMEOUT_S", "float", None,
               "stale-heartbeat threshold; setting it arms the watchdog "
               "(runtime/watchdog.py)"))
_register(Knob("RLA_TPU_WORKER_HEARTBEAT_S", "float", 1.0,
               "worker heartbeat interval; <=0 disables the channel "
               "(runtime/watchdog.py)"))
_register(Knob("RLA_TPU_WORKER_PLATFORM", "str", None,
               "jax platform forced onto spawned workers "
               "(core/trainer.py)"))


def registered_names() -> frozenset:
    return frozenset(KNOBS)


# --------------------------------------------------------------------- #
# Typed getters                                                          #
# --------------------------------------------------------------------- #
_MISSING = object()


def _lookup(name: str, env: Optional[Mapping[str, str]]) -> Optional[str]:
    """Raw value: per-worker overlay first (when it HAS the key), then
    the process env; None when unset in both.  Also the registration
    gate: every read funnels through here."""
    if name not in KNOBS:
        raise LookupError(
            f"env knob {name!r} is not registered in analysis/knobs.py; "
            "declare it (name, type, default, help) before reading it")
    if env is not None and name in env:
        return env[name]
    return os.environ.get(name)


def get_raw(name: str, env: Optional[Mapping[str, str]] = None
            ) -> Optional[str]:
    """The unparsed string, or None when unset — for presence gates and
    pass-through values (chaos specs, platform names, tokens)."""
    return _lookup(name, env)


def get_str(name: str, default: Optional[str] = None,
            env: Optional[Mapping[str, str]] = None) -> Optional[str]:
    raw = _lookup(name, env)
    return default if raw in (None, "") else raw


def get_int(name: str, default: Optional[int] = None, *,
            malformed=_MISSING,
            env: Optional[Mapping[str, str]] = None) -> Optional[int]:
    """``default`` when unset/empty; ``malformed`` (defaults to
    ``default``) with one warning when set but unparseable."""
    raw = _lookup(name, env)
    if raw in (None, ""):
        return default
    try:
        return int(raw)
    except ValueError:
        fallback = default if malformed is _MISSING else malformed
        log.warning("bad %s=%r; using %r", name, raw, fallback)
        return fallback


def get_float(name: str, default: Optional[float] = None, *,
              malformed=_MISSING,
              env: Optional[Mapping[str, str]] = None) -> Optional[float]:
    raw = _lookup(name, env)
    if raw in (None, ""):
        return default
    try:
        return float(raw)
    except ValueError:
        fallback = default if malformed is _MISSING else malformed
        log.warning("bad %s=%r; using %r", name, raw, fallback)
        return fallback


def get_bool(name: str, default: bool = False,
             env: Optional[Mapping[str, str]] = None) -> bool:
    raw = _lookup(name, env)
    if raw is None:
        return default
    v = raw.strip().lower()
    if v in _TRUE:
        return True
    if v in _FALSE:
        return False
    log.warning("bad %s=%r (expected 1/0/true/false); using %r",
                name, raw, default)
    return default


def get_flag(name: str, env: Optional[Mapping[str, str]] = None) -> bool:
    """Presence-truthiness: any non-empty value enables.  Matches the
    historical ``if os.environ.get(X):`` gates (so ``X=0`` ENABLES a
    flag knob — use ``bool`` kind for new knobs that want parsing)."""
    return bool(_lookup(name, env))
