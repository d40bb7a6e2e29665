"""Distributed telemetry: flight recorder, trace IDs, unified metrics
export, and crash postmortem reports.

- :mod:`.recorder` — the bounded per-process event ring with trace-ID
  propagation and crash-observable spill files;
- :mod:`.registry` — the driver-side :class:`MetricsRegistry` (merged
  Profiler/ServeMetrics/compile-count export to Prometheus text and
  JSON) and the ``run_report.json`` postmortem writer;
- :mod:`.perf` — the perf observatory: :class:`StepTimeline` (per-step
  phase decomposition), :class:`HbmLedger` (per-pool HBM attribution +
  leak alarm) and :class:`GoodputLedger` (run-level wall-time
  partition), exported through the registry;
- :mod:`.scopes` — the scope table: the named scopes the program's parts
  carry, and how to ask a registered program for its compiled text and
  its instructions' op names.

See docs/API.md "Telemetry & tracing" / "Perf observatory" for event
kinds, phase/pool vocabularies, export formats and the report schema.
"""

from .live import (ClusterView, LiveSources, TelemetryServer,
                   classify_health)
from .perf import (GOODPUT_CATEGORIES, PHASE_KINDS, GoodputLedger,
                   HbmLedger, PerfObservatory, StepTimeline,
                   exposed_comm_crosscheck, placed_bytes_total,
                   tree_nbytes)
from .recorder import (EMBED_TAIL_N, EVENT_KINDS, FlightRecorder,
                       configure, current_rank, current_trace_id, emit,
                       get_recorder, mint_trace_id, read_spill,
                       set_trace_id, spill_path_for, tail_events)
from . import scopes
from .registry import (MetricsRegistry, build_run_report,
                       gather_spill_dir, gather_worker_tails,
                       probe_snapshot_record, write_run_report)

__all__ = [
    "FlightRecorder", "EVENT_KINDS", "EMBED_TAIL_N",
    "get_recorder", "configure", "emit",
    "mint_trace_id", "set_trace_id", "current_trace_id", "current_rank",
    "spill_path_for", "read_spill", "tail_events",
    "MetricsRegistry", "gather_worker_tails", "gather_spill_dir",
    "build_run_report", "write_run_report", "probe_snapshot_record",
    "PerfObservatory", "StepTimeline", "HbmLedger", "GoodputLedger",
    "PHASE_KINDS", "GOODPUT_CATEGORIES", "exposed_comm_crosscheck",
    "tree_nbytes", "placed_bytes_total",
    "TelemetryServer", "LiveSources", "ClusterView", "classify_health",
    "scopes",
]
