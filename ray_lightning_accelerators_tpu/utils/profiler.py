"""Profiling/tracing subsystem.

The reference has none (SURVEY.md §5.1: no profiler, no timing, no spans
anywhere in its tree) and its build note calls for one as a first-class TPU
subsystem: XLA's async dispatch makes naive timing and printf-debugging
useless — a ``time.time()`` around a jitted call measures *dispatch*, not
compute, and device work only surfaces in XLA traces.

Three layers:

- **Span timing** (`Profiler.span`): nested host-side wall-clock spans with
  a thread-local stack.  Each span also opens a
  ``jax.profiler.TraceAnnotation`` named ``rla:<nested path>`` so the
  same names line up inside TensorBoard/XProf device traces and a trace
  reader keeps the program's spans with one prefix test.  ``sync=True``
  spans block on device work (``jax.block_until_ready``) so step spans
  measure real compute.
- **Device traces** (`start_trace`/`stop_trace`): wraps ``jax.profiler`` to
  dump an XPlane/TensorBoard trace directory, with ``scopes.json`` (the
  registered programs' instruction -> named-scope tables,
  telemetry/scopes.py) beside it.
- **Device memory** (`device_memory_stats`): PjRt per-device HBM counters.

The Trainer takes ``profiler=`` and wraps its hot phases
(data fetch / train step / validation) in spans; see core/trainer.py.
"""

from __future__ import annotations

import math
import random
import threading
import time
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

# every host span the program writes into a profiler trace starts with this
TRACE_PREFIX = "rla:"
SCOPES_FILE = "scopes.json"


class HostSpan:
    """A bare ``rla:<name>`` annotation: the program's host span in
    whatever profiler trace is being taken of the process, for callers
    that hold no ``Profiler`` (no statistics, no nesting path).  Enters
    as ``None``, where ``Profiler.span`` gives a handle.  While no trace
    runs it costs what a null context costs."""

    __slots__ = ("_annotation",)

    def __init__(self, name: str):
        import jax

        self._annotation = jax.profiler.TraceAnnotation(TRACE_PREFIX + name)

    def __enter__(self) -> None:
        self._annotation.__enter__()

    def __exit__(self, *exc) -> None:
        self._annotation.__exit__(*exc)


class _SpanHandle:
    """Mutable holder for a span's device outputs (see Profiler.span)."""

    __slots__ = ("value",)

    def __init__(self):
        self.value = None

    def set(self, value: Any) -> None:
        self.value = value


class _SpanStat:
    __slots__ = ("count", "total", "samples", "maxv", "_rng")

    def __init__(self):
        self.count = 0
        self.total = 0.0
        self.samples: List[float] = []  # uniform reservoir for percentiles
        # exact running max: the worst span must survive even after the
        # reservoir evicts it (tail-latency honesty -- serving is judged
        # on its worst request, not its worst sampled request)
        self.maxv = 0.0
        self._rng = random.Random(0x5EED)

    def add(self, dt: float, cap: int = 4096) -> None:
        self.count += 1
        self.total += dt
        if dt > self.maxv:
            self.maxv = dt
        # reservoir sampling: every span has equal probability of being in
        # the percentile sample, so long runs aren't summarized by their
        # first cap spans (compile/warmup) alone
        if len(self.samples) < cap:
            self.samples.append(dt)
        else:
            j = self._rng.randrange(self.count)
            if j < cap:
                self.samples[j] = dt

    def merge(self, count: int, total: float, samples: List[float],
              maxv: float, cap: int = 4096) -> None:
        """Fold another stat's (count, total, reservoir, max) into this
        one.  Count/total/max are exact.  The merged reservoir is a
        near-uniform sample of the UNION of the two underlying
        populations: when the combined sample fits the cap both sets are
        kept whole; otherwise elements are kept by A-Res weighted
        sampling, each sample weighted by how many real observations it
        represents (``count / len(samples)`` on its side) — a reservoir
        summarizing 10k spans must dominate one summarizing 10, or the
        merged percentiles would skew toward the small rank."""
        if count <= 0:
            return
        mine_n, mine = self.count, self.samples
        self.count += int(count)
        self.total += float(total)
        if maxv > self.maxv:
            self.maxv = float(maxv)
        union = list(mine) + list(samples)
        if len(union) <= cap:
            self.samples = union
            return
        weighted = []
        for src_samples, src_count in ((mine, mine_n), (samples, count)):
            if not src_samples:
                continue
            w = max(1.0, src_count / len(src_samples))
            weighted += [(s, w) for s in src_samples]
        # A-Res: key = u^(1/w); the cap largest keys are a weighted
        # sample without replacement.  Seeded rng: merges are
        # deterministic for a given input order.
        rng = random.Random(0xC0FFEE ^ self.count)
        keyed = sorted(((rng.random() ** (1.0 / w), s)
                        for s, w in weighted), reverse=True)
        self.samples = [s for _k, s in keyed[:cap]]


class Profiler:
    """Named nested wall-clock spans + XLA trace annotations."""

    def __init__(self, sync: bool = False):
        """``sync=True``: spans wrapping device work block until it finishes,
        so durations measure compute rather than async dispatch."""
        self.sync = sync
        self._stats: Dict[str, _SpanStat] = {}
        self._tls = threading.local()
        self._lock = threading.Lock()
        self._trace_dir: Optional[str] = None
        self._comms: Optional[Dict[str, Any]] = None
        self._counters: Dict[str, int] = {}
        # gauge -> [count, sum, min, max, last]
        self._gauges: Dict[str, List[float]] = {}

    def __getstate__(self):
        """Ship-able across processes (the Trainer fan-out pickles its
        profiler): locks/thread-locals/stats stay behind -- a worker
        starts its own clean profile."""
        return {"sync": self.sync}

    def __setstate__(self, state):
        self.__init__(sync=state["sync"])

    # ------------------------------------------------------------------ #
    def _stack(self) -> List[str]:
        if not hasattr(self._tls, "stack"):
            self._tls.stack = []
        return self._tls.stack

    @contextmanager
    def span(self, name: str):
        """Time a block under `name`, nested as parent/child in the report.

        Yields a handle; call ``handle.set(outputs)`` with the block's device
        outputs and a sync-mode profiler will block on them before closing,
        so the span measures compute rather than async dispatch."""
        import jax

        handle = _SpanHandle()
        stack = self._stack()
        full = "/".join(stack + [name])
        stack.append(name)
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(TRACE_PREFIX + full):
                yield handle
                if self.sync and handle.value is not None:
                    # graftlint: ok(host-sync) — opt-in sync=True mode:
                    jax.block_until_ready(handle.value)  # measure compute
        finally:
            dt = time.perf_counter() - t0
            stack.pop()
            with self._lock:
                self._stats.setdefault(full, _SpanStat()).add(dt)

    def observe(self, name: str, dt_s: float) -> None:
        """Record an externally timed duration under ``name`` — the same
        statistics as a span without entering one.  Serving metrics time
        request lifecycles (submit -> first token) that are not a single
        with-block on one thread."""
        with self._lock:
            self._stats.setdefault(name, _SpanStat()).add(dt_s)

    # ------------------------------------------------------------------ #
    # Counters & gauges (input-pipeline accounting; data/prefetch.py)     #
    # ------------------------------------------------------------------ #
    def incr(self, name: str, n: int = 1) -> None:
        """Bump a monotonically-increasing counter.  The prefetch
        pipeline counts ``prefetch_starved_steps`` — steps that found no
        batch ready; a nonzero count means the run is input-bound."""
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + n

    def counters(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counters)

    def gauge(self, name: str, value: float) -> None:
        """Sample an instantaneous level (e.g. ``prefetch_depth``, the
        number of batches ready ahead of the consumer).  Tracks
        count/mean/min/max/last."""
        v = float(value)
        with self._lock:
            g = self._gauges.get(name)
            if g is None:
                self._gauges[name] = [1, v, v, v, v]
            else:
                g[0] += 1
                g[1] += v
                g[2] = min(g[2], v)
                g[3] = max(g[3], v)
                g[4] = v

    def gauges(self) -> Dict[str, Dict[str, float]]:
        """name -> {count, mean, min, max, last}."""
        with self._lock:
            items = {k: list(v) for k, v in self._gauges.items()}
        return {k: {"count": int(c), "mean": s / max(c, 1), "min": lo,
                    "max": hi, "last": last}
                for k, (c, s, lo, hi, last) in items.items()}

    # ------------------------------------------------------------------ #
    # Comms accounting (bytes-on-wire; parallel/collectives.py)           #
    # ------------------------------------------------------------------ #
    def record_comms(self, per_step: Dict[str, Any]) -> None:
        """Attach a per-step bytes-on-wire record for the gradient
        exchange (``collectives.wire_bytes_per_step`` shape: baseline
        fp32 bytes, exchange bytes, compression_ratio, ...).  Analytic,
        not sampled — collective payload sizes are static per compiled
        step, so the honest number is computed once at compile time."""
        with self._lock:
            self._comms = dict(per_step)

    def comms(self) -> Optional[Dict[str, Any]]:
        """The last recorded gradient-exchange wire accounting (None when
        no compression-enabled trainer compiled against this profiler)."""
        with self._lock:
            return dict(self._comms) if self._comms is not None else None

    # ------------------------------------------------------------------ #
    # Cross-process merge (telemetry/registry.py)                         #
    # ------------------------------------------------------------------ #
    def export_state(self) -> Dict[str, Any]:
        """A picklable/JSON-able snapshot of everything this profiler
        accumulated — span stats WITH their raw reservoirs (percentile
        merging needs samples, not quantiles), counters, gauges, and the
        comms record.  The cross-rank telemetry gather ships this shape
        home so the driver can ``merge()`` every rank into one report."""
        with self._lock:
            return {
                "stats": {name: {"count": st.count, "total": st.total,
                                 "samples": list(st.samples),
                                 "max": st.maxv}
                          for name, st in self._stats.items()},
                "counters": dict(self._counters),
                "gauges": {k: list(v) for k, v in self._gauges.items()},
                "comms": (dict(self._comms) if self._comms is not None
                          else None),
            }

    def merge(self, other: Any) -> "Profiler":
        """Fold another profiler (or an ``export_state()`` dict from one)
        into this one.  Span counts/totals/maxes are exact; reservoirs
        merge count-weighted (see ``_SpanStat.merge``); counters sum;
        gauges combine count/sum/min/max with the other side's ``last``
        winning (merge order = recency order by convention); the comms
        record is adopted when this profiler has none (it is analytic
        and identical across SPMD ranks).  Returns self for chaining."""
        state = other.export_state() if isinstance(other, Profiler) \
            else other
        if not isinstance(state, dict):
            raise TypeError(
                f"Profiler.merge takes a Profiler or export_state() "
                f"dict, got {type(other).__name__}")
        with self._lock:
            for name, row in (state.get("stats") or {}).items():
                st = self._stats.setdefault(name, _SpanStat())
                st.merge(int(row.get("count", 0)),
                         float(row.get("total", 0.0)),
                         list(row.get("samples") or ()),
                         float(row.get("max", 0.0)))
            for name, n in (state.get("counters") or {}).items():
                self._counters[name] = self._counters.get(name, 0) + int(n)
            for name, g in (state.get("gauges") or {}).items():
                c, s, lo, hi, last = g
                mine = self._gauges.get(name)
                if mine is None:
                    self._gauges[name] = [int(c), float(s), float(lo),
                                          float(hi), float(last)]
                else:
                    mine[0] += int(c)
                    mine[1] += float(s)
                    mine[2] = min(mine[2], float(lo))
                    mine[3] = max(mine[3], float(hi))
                    if c:
                        mine[4] = float(last)
            if self._comms is None and state.get("comms") is not None:
                self._comms = dict(state["comms"])
        return self

    # ------------------------------------------------------------------ #
    def summary(self) -> Dict[str, Dict[str, float]]:
        """name -> {count, total_s, mean_s, p50_s, p95_s, p99_s, max_s}.

        Percentiles come from the uniform reservoir; ``max_s`` is the
        exact running maximum (tail latency is judged on the worst span,
        which the reservoir may have evicted)."""
        out: Dict[str, Dict[str, float]] = {}
        with self._lock:
            items = [(name, st.count, st.total, sorted(st.samples),
                      st.maxv) for name, st in self._stats.items()]
        for name, count, total, xs, maxv in items:
            pick = (lambda q: xs[min(len(xs) - 1,
                                     int(math.ceil(q * len(xs))) - 1)]
                    if xs else 0.0)
            out[name] = {
                "count": count,
                "total_s": total,
                "mean_s": total / max(count, 1),
                "p50_s": pick(0.50),
                "p95_s": pick(0.95),
                "p99_s": pick(0.99),
                "max_s": maxv,
            }
        return out

    def describe(self) -> str:
        """Human-readable table, longest total first."""
        rows = sorted(self.summary().items(),
                      key=lambda kv: -kv[1]["total_s"])
        lines = [f"{'span':<40} {'count':>7} {'total':>9} {'mean':>9} "
                 f"{'p50':>9} {'p95':>9} {'p99':>9} {'max':>9}"]
        for name, s in rows:
            lines.append(
                f"{name:<40} {s['count']:>7d} {s['total_s']:>8.3f}s "
                f"{s['mean_s'] * 1e3:>7.2f}ms {s['p50_s'] * 1e3:>7.2f}ms "
                f"{s['p95_s'] * 1e3:>7.2f}ms {s['p99_s'] * 1e3:>7.2f}ms "
                f"{s['max_s'] * 1e3:>7.2f}ms")
        for name, n in sorted(self.counters().items()):
            lines.append(f"counter {name:<32} {n:>7d}")
        for name, g in sorted(self.gauges().items()):
            lines.append(
                f"gauge   {name:<32} last={g['last']:g} "
                f"mean={g['mean']:.2f} min={g['min']:g} max={g['max']:g}")
        starved = self.counters().get("prefetch_starved_steps", 0)
        if starved:
            steps = self.summary().get("h2d_wait", {}).get("count", 0)
            lines.append(
                f"input pipeline: {starved}/{steps} steps found the "
                "prefetch queue empty — run is input-bound (raise "
                "prefetch_batches or cheapen the host pipeline)")
        c = self.comms()
        if c is not None:
            lines.append(
                f"grad exchange [{c.get('mode')}]: "
                f"{c.get('exchange_bytes_per_step', 0) / 1e6:.2f} MB/step "
                f"on wire vs {c.get('baseline_fp32_bytes_per_step', 0) / 1e6:.2f}"
                f" MB fp32 ({c.get('compression_ratio')}x overall, "
                f"{c.get('compressed_ratio')}x on compressed leaves)")
        return "\n".join(lines)

    def reset(self) -> None:
        with self._lock:
            self._stats.clear()
            self._comms = None
            self._counters.clear()
            self._gauges.clear()

    # ------------------------------------------------------------------ #
    # Device traces (TensorBoard / XProf)                                #
    # ------------------------------------------------------------------ #
    def start_trace(self, log_dir: str) -> None:
        """Begin an XPlane device trace (view in TensorBoard's profiler)."""
        import jax

        if self._trace_dir is not None:
            raise RuntimeError(f"trace already running -> {self._trace_dir}")
        jax.profiler.start_trace(log_dir)
        self._trace_dir = log_dir

    def stop_trace(self) -> Optional[str]:
        """End the trace and write ``scopes.json`` beside it: every
        registered program's instruction -> op-name table
        (telemetry/scopes.py), which is what ``trace_op_summary`` needs
        to tell forward from backward from optimizer."""
        import jax

        if self._trace_dir is None:
            return None
        jax.profiler.stop_trace()
        d, self._trace_dir = self._trace_dir, None
        _write_scope_tables(d)
        return d

    @contextmanager
    def trace(self, log_dir: str):
        self.start_trace(log_dir)
        try:
            yield
        finally:
            self.stop_trace()


def _write_scope_tables(trace_dir: str) -> None:
    """``<trace_dir>/scopes.json``: ``{program: {instruction: op_name}}``.
    Never fails the trace it annotates."""
    import json
    import logging
    import os

    from ..telemetry import scopes
    try:
        tables = {name: scopes.scope_table(name)
                  for name in scopes.registered()}
        with open(os.path.join(trace_dir, SCOPES_FILE), "w") as f:
            json.dump(tables, f)
    except Exception as e:  # a table is an annotation, not the trace
        logging.getLogger(__name__).warning(
            "no %s beside the trace in %s: %s", SCOPES_FILE, trace_dir, e)


def trace_events(trace_dir: str) -> List[Dict[str, Any]]:
    """Device-side op events from the newest ``*.trace.json.gz`` under an
    XPlane trace directory (written by ``Profiler.start_trace``/
    ``jax.profiler.start_trace``).

    Each event: ``{name, ts_us, dur_us, end_us, category, bytes, flops}``
    with durations from the DEVICE clock (``device_duration_ps``): the
    on-chip times, where host wall-clock also counts dispatch.
    Host/python events are excluded."""
    import glob
    import gzip
    import json
    import os

    files = sorted(glob.glob(os.path.join(trace_dir, "**",
                                          "*.trace.json.gz"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no trace.json.gz under {trace_dir}")
    with gzip.open(files[-1]) as f:
        t = json.load(f)
    out: List[Dict[str, Any]] = []
    for e in t.get("traceEvents", []):
        a = e.get("args") or {}
        if e.get("ph") != "X" or "device_duration_ps" not in a:
            continue
        ts = float(a.get("device_offset_ps", 0)) / 1e6
        dur = float(a["device_duration_ps"]) / 1e6
        out.append({
            "name": e["name"], "ts_us": ts, "dur_us": dur,
            "end_us": ts + dur,
            # timeline identity: events nest only WITHIN one device
            # timeline; concurrent chips must not read as parent/child
            "pid": e.get("pid"), "tid": e.get("tid"),
            "category": a.get("hlo_category", "?"),
            "bytes": int(a.get("raw_bytes_accessed",
                               a.get("bytes_accessed", 0) or 0)),
            "flops": int(a.get("model_flops", 0) or 0),
        })
    out.sort(key=lambda ev: (ev["ts_us"], -ev["dur_us"]))
    return out


def trace_op_summary(trace_dir: str, top: int = 0) -> Dict[str, Any]:
    """Roofline-style aggregation of a device trace: EXCLUSIVE (self)
    time per op and per HLO category, with achieved GB/s / TF/s.

    Nested events (``while`` bodies, fusions inside scans) are resolved
    by interval containment, so a scan's children are not double-counted
    against their parent.  Returns ``{"total_ms", "by_category":
    {cat: {self_ms, gbps, tfs, pct}}, "ops": [top-N rows]}``.  Where
    ``Profiler.stop_trace`` left a ``scopes.json`` beside the trace,
    each op row also has ``"scope"``: the op name (named-scope stack,
    under JAX's ``jvp(`` / ``transpose(`` wrappers) of its instruction
    in ``"scope_program"``, the registered program that knows the most
    of the trace's time (``%fusion.3`` exists in more than one)."""
    evs = trace_events(trace_dir)
    # stack-based nesting, one stack PER DEVICE (pid): concurrent chips
    # overlap in time without any parent/child relation, but within one
    # device the module/step wrapper events genuinely contain the op
    # events even when exported on different trace lines (tids)
    stacks: Dict[Any, List[Dict[str, Any]]] = {}
    for e in evs:
        stack = stacks.setdefault(e["pid"], [])
        while stack and stack[-1]["end_us"] <= e["ts_us"] + 1e-6:
            stack.pop()
        e["_child_dur"] = 0.0
        if stack:
            stack[-1]["_child_dur"] += e["dur_us"]
        stack.append(e)
    agg: Dict[Any, List[float]] = {}
    for e in evs:
        key = (e["category"], e["name"])
        row = agg.setdefault(key, [0.0, 0, 0, 0])
        row[0] += max(0.0, e["dur_us"] - e["_child_dur"])
        row[1] += 1
        row[2] += e["bytes"]
        row[3] += e["flops"]
    total_us = sum(v[0] for v in agg.values())

    def rates(dur_us: float, nbytes: int, nflops: int) -> Dict[str, float]:
        secs = dur_us * 1e-6
        return {"gbps": nbytes / secs / 1e9 if secs else 0.0,
                "tfs": nflops / secs / 1e12 if secs else 0.0}

    cats: Dict[str, List[float]] = {}
    for (cat, _name), (dur, _n, b, fl) in agg.items():
        c = cats.setdefault(cat, [0.0, 0, 0])
        c[0] += dur
        c[1] += b
        c[2] += fl
    by_category = {
        cat: {"self_ms": dur / 1e3,
              "pct": 100.0 * dur / total_us if total_us else 0.0,
              **rates(dur, b, fl)}
        for cat, (dur, b, fl) in cats.items()}
    rows = sorted(agg.items(), key=lambda kv: -kv[1][0])
    if top:
        rows = rows[:top]
    ops = [{"category": cat, "name": name, "self_ms": dur / 1e3,
            "count": n,
            "pct": 100.0 * dur / total_us if total_us else 0.0,
            **rates(dur, b, fl)}
           for (cat, name), (dur, n, b, fl) in rows]
    out = {"total_ms": total_us / 1e3, "by_category": by_category,
           "ops": ops}
    program, table = _scope_table(trace_dir, agg)
    if program is not None:
        out["scope_program"] = program
        for op in ops:
            op["scope"] = table.get(_instruction(op["name"]), "")
    return out


def _instruction(event_name: str) -> str:
    """``%fusion.3`` of a trace event named ``%fusion.3 = ...`` (an
    ``.xplane.pb`` names an op by its whole text) or ``fusion.3``."""
    name = event_name.partition(" = ")[0]
    return name if name.startswith("%") else "%" + name


def _scope_table(trace_dir: str, agg) -> tuple:
    """``(program, {instruction: op_name})`` of the table in
    ``scopes.json`` that knows the most of the trace's time;
    ``(None, {})`` without the file."""
    import json
    import os

    path = os.path.join(trace_dir, SCOPES_FILE)
    if not os.path.exists(path):
        return None, {}
    with open(path) as f:
        tables = json.load(f)

    def known_us(table):
        return sum(row[0] for (_cat, name), row in agg.items()
                   if _instruction(name) in table)
    program = max(tables, key=lambda p: known_us(tables[p]), default=None)
    return program, tables.get(program, {})


def device_memory_stats() -> List[Dict[str, Any]]:
    """Per-device PjRt memory counters (bytes_in_use, peak, limit...).

    Empty dicts on backends that don't expose stats (CPU).  The perf
    observatory's HBM ledger (telemetry/perf.py) builds per-pool
    attribution on top: ``device_bytes_in_use()`` below is its ground
    truth where the backend reports real HBM."""
    import jax

    out = []
    for d in jax.local_devices():
        try:
            out.append(dict(d.memory_stats() or {}))
        except Exception:
            out.append({})
    return out


def device_bytes_in_use() -> Optional[int]:
    """Summed PjRt ``bytes_in_use`` across local devices, or None on
    backends that expose no memory stats (CPU) — callers fall back to
    live-array accounting (``telemetry.perf.placed_bytes_total``)."""
    vals = [s.get("bytes_in_use") for s in device_memory_stats()
            if s.get("bytes_in_use")]
    return int(sum(vals)) if vals else None


# --------------------------------------------------------------------- #
# FLOPs / MFU estimation                                                 #
# --------------------------------------------------------------------- #
def flops_estimate(fn, *args, **kwargs) -> Optional[float]:
    """FLOPs for one invocation of (jit-able) ``fn`` on these args, from
    XLA's compiled cost analysis.  None when the backend reports no
    estimate.  Trace-only: nothing executes on device."""
    import jax

    # graftlint: ok(retrace) — trace-only cost estimate, once per bench
    compiled = jax.jit(fn).lower(*args, **kwargs).compile()
    try:
        analyses = compiled.cost_analysis()
    except Exception:
        return None
    if not analyses:
        return None
    a = analyses[0] if isinstance(analyses, (list, tuple)) else analyses
    flops = a.get("flops")
    return float(flops) if flops else None


# Published per-chip dense bf16 peaks (FLOP/s), keyed by a substring of
# jax's ``device_kind``.
PEAK_BF16_FLOPS_SOURCE = ("Google Cloud TPU documentation, system "
                          "architecture pages (TPU v4 / v5e / v5p / v6e)")
PEAK_BF16_FLOPS = {"v5 lite": 197e12, "v5litepod": 197e12, "v5e": 197e12,
                   "v4": 275e12, "v5p": 459e12,
                   "v6 lite": 918e12, "v6e": 918e12}


def peak_bf16_flops(device_kind: str) -> float:
    """The published bf16 peak of one chip of ``device_kind``.  A device
    that is not in the table is an error, not a default: a utilization
    against an unknown peak is not a number."""
    kind = (device_kind or "").lower()
    for key, peak in PEAK_BF16_FLOPS.items():
        if key in kind:
            return peak
    raise ValueError(
        f"no published bf16 peak for device_kind {device_kind!r}; known: "
        f"{sorted(PEAK_BF16_FLOPS)} ({PEAK_BF16_FLOPS_SOURCE}) -- pass "
        f"peak_flops explicitly or add the device to the table")


def mfu(flops_per_step: float, step_time_s: float,
        peak_flops: Optional[float] = None) -> float:
    """Model FLOPs utilization: achieved/peak.  ``peak_flops`` defaults to
    the published per-chip bf16 peak of the current device
    (``peak_bf16_flops``; raises for a device it does not know)."""
    if peak_flops is None:
        import jax

        peak_flops = peak_bf16_flops(jax.devices()[0].device_kind)
    return flops_per_step / (step_time_s * peak_flops)
