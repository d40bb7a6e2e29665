"""Median host time from one epoch's device-synced end to the next
epoch's start (the Trainer's own bookkeeping between dispatches), ms."""
from benchmark.lib import stats


def read(context):
    gaps = context["counters"].get("epoch_gap_s")
    return stats.percentile(gaps, 50) * 1e3 if gaps else None
