#!/usr/bin/env python3
"""The benchmark's command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one cell, one run: set-up (weights from ``--seed`` on the
device, warm-up of the cell's own shapes, the correctness check against
the plain reference), then a measured window of ``--seconds``.  Earlier
lines of stdout are JSON records worth keeping (medians, counts, MFU,
loss, compile-cache hits); the LAST line is the result:

    {"correct", "attempted", "failed", "metrics", "device"[, "breakdown"]}

with the cell's end-to-end metrics (``--trace 0``) or its per-layer
metrics (``--trace 1``).  No TPU, or fewer chips than the cell asks for:
non-zero exit and no result.  ``BENCH_REHEARSAL=1`` lifts that for a CPU
rehearsal of the harness and marks the output ``"rehearsal": true``; a
rehearsal's numbers are never device numbers.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file found by name (see ``README.md``); this file
is edited by no later PR.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up is counted from here

import argparse      # noqa: E402
import importlib     # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def emit(**record) -> None:
    """An earlier line of stdout: a record worth keeping, not the result."""
    print(json.dumps(record), flush=True)


class Compiles:
    """Programs compiled or fetched from the persistent cache by this
    process, from jax's own monitoring events: ``count()`` is what a
    window compares before and after (either kind stalls a step)."""

    def __init__(self):
        import jax.monitoring as monitoring

        self.compiled = self.hits = self.misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event: str, _seconds: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def count(self) -> int:
        return self.compiled + self.hits


def device_record(devices) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": max(peaks)}


def layer_metrics(cell: dict, context: dict) -> dict:
    """Every per-layer metric that lists this cell: its reader's value,
    left out where the reader finds nothing to read."""
    from benchmark.lib import cells

    out = {}
    for metric in cells.load_layer_metrics(cell["name"], cell["workload"]):
        reader = importlib.import_module(
            "benchmark.readers." + metric["reader"])
        value = reader.read(context, **metric.get("args", {}))
        if value is not None:
            out[metric["name"]] = {"value": float(value),
                                   "unit": metric["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    rehearsal = os.environ.get("BENCH_REHEARSAL") == "1"
    # the compile cache sits where the machine says, else at a fixed path
    # inside the checkout; set before jax is imported so that the
    # program's own placement (utils/compile_cache.py) takes it
    os.environ.setdefault(CACHE_ENV, os.path.join(ROOT, ".jax_cache"))

    from benchmark.lib import cells

    workload = cells.load_workload(args.workload)
    cell = {"name": args.workload, "workload": workload,
            "config": cells.load_config(workload["config"]),
            "traffic": cells.load_traffic(workload["traffic"])}

    import jax

    devices = jax.devices()
    chips = int(workload["chips"])
    if not rehearsal and devices[0].platform != "tpu":
        print(f"benchmark: no TPU (jax.devices()[0] is {devices[0]!r}); "
              "nothing is measured on another platform", file=sys.stderr)
        return 3
    if len(devices) < chips:
        print(f"benchmark: {args.workload} needs {chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 3
    devices = devices[:chips]
    compiles = Compiles()
    emit(info="start", workload=args.workload, seed=args.seed,
         seconds=args.seconds, trace=args.trace, rehearsal=rehearsal,
         compile_cache_dir=os.environ[CACHE_ENV], jax=jax.__version__)

    driver = importlib.import_module("benchmark.drivers." + workload["driver"])
    result = driver.run(cell, devices=devices, seed=args.seed,
                        seconds=args.seconds, trace=bool(args.trace),
                        t_process=T_PROCESS, compiles=compiles, emit=emit)

    device = device_record(devices)
    # the allocator's counter leaves a program's temporaries out; a driver
    # that knows the window's true peak (live buffers + the compiler's
    # figure for the program) reports it, and the larger of the two stands
    allocator_peak = device["memory_peak_bytes"]
    device["memory_peak_bytes"] = max(
        allocator_peak, int(result.get("window_peak_bytes", 0)))
    end_to_end = {k: {"value": float(v), "unit": result["units"][k]}
                  for k, v in result["end_to_end"].items()}
    emit(info="summary", end_to_end=end_to_end, correct=result["correct"],
         checks=result["checks"], allocator_peak_bytes=allocator_peak,
         compile_cache_hits=compiles.hits,
         compile_cache_misses=compiles.misses, compiled=compiles.compiled)
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"])}
    if args.trace:
        reduced = result.get("trace")
        if reduced is None and not rehearsal:
            print("benchmark: the traced window holds no device operation",
                  file=sys.stderr)
            return 4
        context = {"cell": cell, "device": device, "trace": reduced,
                   "counters": result["counters"],
                   "end_to_end": result["end_to_end"]}
        line["metrics"] = layer_metrics(cell, context)
        if reduced is not None:
            device.update(busy_s=reduced["busy_s"],
                          window_s=reduced["window_s"])
            line["breakdown"] = {"device_ops": reduced["device_ops"],
                                 "idle_gaps": reduced["idle_gaps"]}
    else:
        line["metrics"] = end_to_end
    line["device"] = device
    if rehearsal:
        line["rehearsal"] = True
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
