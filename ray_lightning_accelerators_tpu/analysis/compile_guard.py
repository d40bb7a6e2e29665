"""compile-guard: count XLA backend compiles, budget them in tests, and
keep the process's start-up ledger of every compiled program by name.

graftlint catches retrace hazards statically; this module catches the
ones only the runtime can see.  It subscribes ONE process-global set of
listeners to ``jax.monitoring`` (installed with the package, so the
caller's first ``jit`` is already seen) and keeps two things.

**A monotonic counter** of ``/jax/core/compile/backend_compile_duration``
events.  jax fires that event once for every program it hands to the
backend: a real compile, AND a load from the persistent compilation
cache (the duration is then the retrieval, milliseconds).  Only a hit
in jax's in-memory executable cache fires nothing.  So with a
persistent cache on, ``compile_count()`` / ``compile_seconds()`` count
compiles and loads together -- either one stalls the step it sits in,
which is what a guard block budgets:

    with compile_guard(max_new_compiles=3) as g:
        ...serve a staggered join/retire workload...
    # raises CompileBudgetExceeded past the budget; g.new_compiles holds
    # the actual count either way

The serve engine's "three compiled programs" lifecycle and the
trainer's "compile once, never retrace after warmup" are pinned this
way in ``tests/test_analysis.py``; the bench probes emit
``compile_count()`` deltas alongside their metric lines so a retrace
regression shows up in the bench trajectory even when nothing asserts.

**The compile ledger**, which tells the two apart by name.  One row a
program jax traces, lowers, compiles or loads (``ledger()``):

    name         jax's ``fun_name`` (``jit(train_step)``)
    trace_s      the OUTERMOST trace's seconds (a ``matmul`` traced
                 inside ``train_step`` is no row and is not added twice)
    lower_s      jaxpr -> MLIR module
    backend_s    the backend event: the compile, or the cache load
    cache        "hit"      asked of the persistent cache and loaded
                 "miss"     asked, compiled, and WRITTEN (jax records
                            ``cache_misses`` where it writes the entry)
                 "small"    asked, compiled, not written: under
                            ``jax_persistent_cache_min_compile_time_secs``
                            or the entry-size threshold, so compiled
                            again in every process by design
                 "uncached" never asked: no cache directory, or a
                            program jax does not cache
                 None       traced (and lowered) but never handed to
                            the backend (``.lower()``, ``eval_shape``)
    retrieval_s  seconds reading the entry (hits)
    saved_s      jax's own "compile time saved" (hits)
    start, end   ``time.monotonic()`` of the backend event, stamped at
                 the listener's call (start = end - backend_s): the
                 flight recorder's clock
    thread       name of the thread that compiled
    phase        the innermost phase open at that moment (``phase()``;
                 the Trainer opens ``setup_*`` and ``epoch_*``), None
                 outside one

Bounded: the newest ``LEDGER_ROWS`` rows a process, older ones counted
(``ledger_dropped()``).  Rows hold host floats and strings only.  Nothing
here runs unless jax is compiling; ``phase()`` is a list append and
remove.  ``summary(rows)`` folds rows into the counts a metric reads.

Counting is process-global (jax's compile cache is too): guards see
compiles from ALL threads, including the serve engine's decode thread —
which is the point.  Guard blocks therefore should not overlap
unrelated concurrent compilation.
"""

from __future__ import annotations

import threading
import time
from typing import List, Optional

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
CACHE_SAVED_EVENT = "/jax/compilation_cache/compile_time_saved_sec"

LEDGER_ROWS = 1024

_lock = threading.Lock()
_installed = False
_count = 0
_seconds = 0.0
_rows: List[dict] = []          # sorted by "end"
_dropped = 0
_phases: List[str] = []         # open phases, innermost last
_tls = threading.local()        # .pipeline: this thread's _Pipeline


class _Pipeline:
    """What one thread's events have said so far about the program it
    is building.  jax runs trace -> lower -> backend on one thread, and
    fires a scalar where each begins and a duration where it ends.
    Traces nest (``matmul`` inside ``train_step``), and a lowering rule
    traces too (``add`` inside the lowering of ``jit(_normal)``): only
    the outermost event of either kind is the program's own."""

    __slots__ = ("depth", "inside_s", "row", "cache")

    def __init__(self):
        self.depth = 0          # open trace and lower events
        self.inside_s = 0.0     # compiles INSIDE the open event
        self.row: Optional[dict] = None     # traced / lowered, not built
        self.cache: Optional[dict] = None   # the open backend event


def _new_row(name: str, now: float) -> dict:
    return {"name": name, "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
            "cache": None, "retrieval_s": 0.0, "saved_s": 0.0,
            "start": now, "end": now,
            "thread": threading.current_thread().name,
            "phase": _phases[-1] if _phases else None}


def _keep(row: dict) -> None:
    """File a finished row (caller holds the lock), in order of ``end``:
    a row that was only traced is filed when its thread starts the next
    program, so it can stand a few places from the back."""
    global _dropped
    if len(_rows) >= LEDGER_ROWS:   # the oldest goes: a retrace late in a
        del _rows[0]                # long process is what a reader asks for
        _dropped += 1
    i = len(_rows)
    while i and _rows[i - 1]["end"] > row["end"]:
        i -= 1
    _rows.insert(i, row)


def _pipeline() -> _Pipeline:
    p = getattr(_tls, "pipeline", None)
    if p is None:
        p = _tls.pipeline = _Pipeline()
    return p


def _row_for(p: _Pipeline, fun_name: str, now: float) -> dict:
    """The thread's open row if it is this program's (the trace calls it
    ``train_step``, lowering and backend ``jit(train_step)``); else the
    open row is filed as it stands and a new one opened."""
    if p.row is None or p.row["name"] not in fun_name:
        if p.row is not None:
            _keep(p.row)
        p.row = _new_row(fun_name, now)
    return p.row


def _on_scalar(event: str, _value=None, fun_name: str = "", **_) -> None:
    """A compile event BEGINS (jax's ``log_elapsed_time.__enter__``)."""
    if event in (TRACE_EVENT, LOWER_EVENT):
        with _lock:
            p = _pipeline()
            p.depth += 1
            if p.depth == 1:
                p.inside_s = 0.0
                if event == TRACE_EVENT and p.row is not None:
                    _keep(p.row)        # traced, never built
                    p.row = None
                _row_for(p, fun_name, time.monotonic())
    elif event == BACKEND_COMPILE_EVENT:
        with _lock:
            _pipeline().cache = {}      # filled by the cache's events


def _on_event(event: str, **_) -> None:
    """The persistent cache's three events, which fire between a backend
    event's begin and its end on the compiling thread."""
    key = {CACHE_REQUEST_EVENT: "asked", CACHE_HIT_EVENT: "hit",
           CACHE_MISS_EVENT: "wrote"}.get(event)
    if key is not None:
        with _lock:
            cache = _pipeline().cache
            if cache is not None:
                cache[key] = True


def _on_event_duration(event: str, *args, fun_name: str = "", **_) -> None:
    global _count, _seconds
    try:
        seconds = float(args[0]) if args else 0.0
    except (TypeError, ValueError):
        seconds = 0.0   # the count stays exact even if a build changes shape
    if event == BACKEND_COMPILE_EVENT:
        now = time.monotonic()
        with _lock:
            _count += 1
            _seconds += seconds
            p = _pipeline()
            cache, p.cache = p.cache or {}, None
            if p.depth:     # an eager op compiled while a trace is open
                row, p.inside_s = _new_row(fun_name, now), p.inside_s + seconds
            else:
                row, p.row = _row_for(p, fun_name, now), None
            row.update(
                name=fun_name or row["name"], backend_s=seconds,
                start=now - seconds, end=now,
                cache=("hit" if cache.get("hit") else
                       "miss" if cache.get("wrote") else
                       "small" if cache.get("asked") else "uncached"),
                retrieval_s=cache.get("retrieval_s", 0.0),
                saved_s=cache.get("saved_s", 0.0),
                phase=_phases[-1] if _phases else None)
            _keep(row)
    elif event in (TRACE_EVENT, LOWER_EVENT):
        with _lock:
            p = _pipeline()
            p.depth = max(0, p.depth - 1)
            if p.depth == 0 and p.row is not None:
                own = max(0.0, seconds - p.inside_s)
                if event == TRACE_EVENT:
                    p.row["trace_s"] = own
                else:
                    p.row.update(name=fun_name, lower_s=own)
                p.row["end"] = time.monotonic()
    elif event in (CACHE_RETRIEVAL_EVENT, CACHE_SAVED_EVENT):
        with _lock:
            cache = _pipeline().cache
            if cache is not None:
                cache["retrieval_s" if event == CACHE_RETRIEVAL_EVENT
                      else "saved_s"] = seconds


def install() -> None:
    """Idempotently register the listeners.  jax.monitoring has no
    per-listener deregistration, so ONE set is installed for the
    process lifetime and guards snapshot the counter around blocks.
    The flag flips only AFTER successful registration: a one-time
    import/registration failure must raise on every call, not silently
    freeze the counter at zero (which would make every guard pass
    vacuously)."""
    global _installed
    with _lock:
        if _installed:
            return
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(
            _on_event_duration)
        jax.monitoring.register_scalar_listener(_on_scalar)
        jax.monitoring.register_event_listener(_on_event)
        _installed = True


def compile_count() -> int:
    """Programs handed to the backend since ``install()`` (monotonic):
    compiles and persistent-cache loads alike.  The first call installs
    the listeners, so deltas are only meaningful between calls AFTER
    the first."""
    install()
    with _lock:
        return _count


def compile_seconds() -> float:
    """Cumulative seconds of those backend events since ``install()``
    (monotonic, same listener as ``compile_count``).  The perf
    observatory's step timeline snapshots this at step boundaries to
    split compile time out of a warmup step's dispatch phase."""
    install()
    with _lock:
        return _seconds


def ledger(since: Optional[float] = None,
           until: Optional[float] = None) -> List[dict]:
    """The ledger's rows (copies, oldest first) whose ``end`` lies in
    ``[since, until)`` on ``time.monotonic()``; either bound may be
    None.  Rows are kept in order of ``end``, so a caller that asks for
    the last epoch's rows pays for those rows only."""
    install()
    out = []
    with _lock:
        for row in reversed(_rows):
            if since is not None and row["end"] < since:
                break
            if until is None or row["end"] < until:
                out.append(dict(row))
    out.reverse()
    return out


def ledger_dropped() -> int:
    """Rows the ledger has let go to stay at ``LEDGER_ROWS`` (the oldest
    first): counted, not kept."""
    with _lock:
        return _dropped


def summary(rows) -> dict:
    """Fold ledger rows into what a metric reads: ``programs`` (rows),
    ``built`` (really compiled in this process: miss + small +
    uncached), ``loaded`` (persistent-cache hits), ``missed`` (the
    ``miss`` rows alone: asked for, not held, compiled and written) and
    the summed seconds of each stage."""
    out = {"programs": 0, "built": 0, "loaded": 0, "missed": 0,
           "trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0,
           "retrieval_s": 0.0}
    for row in rows:
        out["programs"] += 1
        cache = row["cache"]
        out["loaded"] += cache == "hit"
        out["missed"] += cache == "miss"
        out["built"] += cache in ("miss", "small", "uncached")
        for key in ("trace_s", "lower_s", "backend_s", "retrieval_s"):
            out[key] += row[key]
    return out


class phase:
    """Name what the caller is doing while the block runs: rows that
    finish inside carry the innermost open name as ``phase``.  Process-
    global, like the ledger: a program compiled on a helper thread
    belongs to the phase the fit is in.  A list append and a remove."""

    __slots__ = ("name",)

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> None:
        _phases.append(self.name)

    def __exit__(self, *exc) -> None:
        for i in range(len(_phases) - 1, -1, -1):
            if _phases[i] == self.name:
                del _phases[i]
                break


def _reset_ledger_for_tests() -> None:
    global _dropped
    with _lock:
        _rows.clear()
        _tls.pipeline = None
        _dropped = 0


class CompileBudgetExceeded(AssertionError):
    """A guarded block compiled more programs than its budget."""


class compile_guard:
    """Context manager asserting a compile budget over a block.

    ``max_new_compiles=None`` only records (``.new_compiles`` after
    exit).  On budget violation raises ``CompileBudgetExceeded`` —
    unless the block is already unwinding with its own exception, which
    must not be masked."""

    def __init__(self, max_new_compiles: Optional[int] = None,
                 label: str = ""):
        self.max_new_compiles = max_new_compiles
        self.label = label
        self.start_count: Optional[int] = None
        self.new_compiles: Optional[int] = None

    def __enter__(self) -> "compile_guard":
        self.start_count = compile_count()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.new_compiles = compile_count() - self.start_count
        if exc_type is None and self.max_new_compiles is not None \
                and self.new_compiles > self.max_new_compiles:
            what = f" [{self.label}]" if self.label else ""
            raise CompileBudgetExceeded(
                f"compile budget exceeded{what}: {self.new_compiles} new "
                f"XLA backend compiles in a block budgeted for "
                f"{self.max_new_compiles} — something is retracing "
                "(see graftlint's retrace rule for the usual suspects)")
        return False


def assert_no_new_compiles(label: str = "") -> compile_guard:
    """Sugar for the steady-state invariant: zero compiles after
    warmup."""
    return compile_guard(max_new_compiles=0, label=label)


def compile_count_record(probe: str,
                         window_start: Optional[int] = None) -> dict:
    """The bench-honesty tie-in line: probe scripts print this JSON
    record alongside their metric line, so a retrace regression is
    visible in the bench trajectory even when no test asserts on it.
    ``window_start`` (a ``compile_count()`` snapshot taken after warmup)
    adds the measured-window delta — 0 in a healthy run."""
    total = compile_count()
    rec = {"probe": probe, "kind": "compile_count",
           "total_backend_compiles": total}
    if window_start is not None:
        rec["measured_window_compiles"] = total - window_start
    return rec
