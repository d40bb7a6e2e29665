"""Exclusive device time per step, ms, of ops under any of ``scopes``
(second-level names of ``benchmark/lib/scopes.bucket``: ``conv``,
``moe_experts``, ``kernel/moe_gmm`` ...), forward + recompute + backward.
Left out without a trace, a usable scope table, or any such op (a
program that has no such scope)."""
from benchmark.lib import scopes as scopes_lib


def seconds(joined, scopes):
    return sum(joined["seconds"].get(f"{top}/{scope}", 0.0)
               for top in ("fwd", "recompute", "bwd") for scope in scopes)


def read(context, scopes):
    joined = scopes_lib.of(context)
    steps = context["counters"].get("trace_steps")
    if joined is None or not steps:
        return None
    s = seconds(joined, scopes)
    return s / steps * 1e3 if s > 0 else None
