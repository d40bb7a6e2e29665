#!/usr/bin/env python3
"""Spread of each metric over saved runs, as the bounds are set from it.

    python3 benchmark/tools/spread.py chiprun_out/sets/<cell>/set1 chiprun_out/sets/<cell>/set2

Each directory holds one file per run whose last line is a result line of
``run.py``.  For every metric: each set's median and spread (distance
between the first and third quartile of ``statistics.quantiles(n=4)`` as
a share of the median), the wider of the spreads, five times it (the
bound it suggests), and how the second set's median sits to the first's.
A set's first run compiled, so its ``setup_s`` is left out.
"""
from __future__ import annotations

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from benchmark.lib import stats  # noqa: E402


def read_set(folder: str) -> dict:
    runs = {}
    for fname in sorted(os.listdir(folder)):
        with open(os.path.join(folder, fname)) as f:
            lines = [ln for ln in f.read().splitlines() if ln.startswith("{")]
        if not lines:
            continue
        result = json.loads(lines[-1])
        if "metrics" not in result:
            continue
        if not result["correct"]:
            print(f"NOT CORRECT: {folder}/{fname}")
        for name, m in result["metrics"].items():
            runs.setdefault(name, []).append(m["value"])
    return runs


def main(argv) -> int:
    sets = [read_set(folder) for folder in argv]
    for name in sorted(set().union(*sets)):
        row, spreads, medians = [], [], []
        for runs in sets:
            values = runs.get(name, [])
            if name == "setup_s":
                values = values[1:]
            if len(values) < 2:
                continue
            medians.append(statistics.median(values))
            spreads.append(stats.spread(values))
            row.append(f"n={len(values)} median={medians[-1]:.6g} "
                       f"spread={100 * spreads[-1]:.3f}%")
        if not spreads:
            continue
        drift = (f" second/first={medians[1] / medians[0]:.4f}"
                 if len(medians) > 1 else "")
        print(f"{name}: " + " | ".join(row)
              + f" | widest={100 * max(spreads):.3f}% "
                f"x5={100 * 5 * max(spreads):.2f}%" + drift)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
