"""Join the reduced trace to the program's named scopes.

The program names its parts with ``jax.named_scope`` (PERF.md §3 lists
the names); a trace event is named by its HLO instruction and carries no
scope, but the compiled program's text gives every instruction's
``op_name`` -- the scope stack, wrapped by JAX's own ``jvp(`` (forward),
``transpose(jvp(`` (backward) and ``rematted_computation`` (a remat's
second forward).  The yardstick takes from the program only that text
(``telemetry.scopes.registered`` / ``program_text``); the expression,
the bucket rules and the join below are the benchmark's own.
"""
from __future__ import annotations

import json
import re
import sys
import time

from benchmark.lib import trace

_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?((%[\w.\-]+) = (.*?) ([a-z][\w\-]*)\()(.*)$", re.M)
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_CALLS = re.compile(r"calls=(%[\w.\-]+)")
_COMPUTATION = re.compile(r"^(%[\w.\-]+) \([^\n]*\{\n(.*?)^\}", re.M | re.S)
_TUPLE_ROOT = re.compile(r"^\s*ROOT %[\w.\-]+ = .*? tuple\(([^)]*)\)", re.M)
_NAME = re.compile(r"%[\w.\-]+")
_ARRAY = re.compile(r"[a-z]+(\d*)\w*\[([\d,]*)\]")
_HEAD = re.compile(r"^.*? = .*? [a-z][\w\-]*\(")
_SCOPE = re.compile(r"gpt/(\w+)|kernel/(\w+)")
_last = (None, None)     # (the reduction it was made for, the join)


def head(text: str) -> str:
    """``%fusion.3 = f32[8]{0} fusion(``: an instruction's name, result
    type and opcode.  A trace event is named by the instruction's text
    with operand types and without metadata, ``as_text()`` prints it
    without operand types and with metadata: up to the operands the two
    agree."""
    m = _HEAD.match(text)
    return m.group(0) if m else text


def _nbytes(array_type: str) -> int:
    m = _ARRAY.match(array_type)
    if not m:
        return 0
    n = max(int(m.group(1) or 8) // 8, 1)
    for dim in filter(None, m.group(2).split(",")):
        n *= int(dim)
    return n


def table(text: str) -> tuple:
    """``{head: op_name}`` and ``{%instruction: op_name}`` of one
    compiled program's text, and the keys whose op name is not the
    instruction's own.  The compiler names a multi-output fusion after
    one of its outputs, whichever: AdamW's update fused with the
    guardian's norm of it reads ``guard/reduce_sum``.  A multi-output
    fusion with no matmul in it (one with a matmul is the matmul's,
    whatever rides along) therefore takes the op name of the
    instruction that makes its largest output: a reduction that rides
    along costs no traffic of its own."""
    known, size, fusions = {}, {}, []
    for key, name, array, opcode, rest in _INSTRUCTION.findall(text):
        m = _OP_NAME.search(rest)
        if m:
            known[key] = known[name] = m.group(1)
            size[name] = _nbytes(array)
        if opcode == "fusion":
            fusions.append((key, name, _CALLS.search(rest)))
    largest = {}        # computation -> op name of its largest output
    for computation, body in _COMPUTATION.findall(text):
        root = _TUPLE_ROOT.search(body)
        made = [o for o in _NAME.findall(root.group(1))
                if o in size] if root else []
        if made and " convolution(" not in body:
            largest[computation] = known[max(made, key=size.get)]
    renamed = set()
    for key, name, calls in fusions:
        op_name = largest.get(calls.group(1)) if calls else None
        if op_name is not None and op_name != known.get(name):
            known[key] = known[name] = op_name
            renamed.update((key, name))
    return known, renamed


def bucket(op_name: str) -> str:
    """``loss`` | ``optimizer`` | ``guard`` | ``exchange`` | ``unscoped``
    | ``fwd/..`` | ``bwd/..`` | ``recompute/..`` with the innermost
    kernel or model scope as the second level (``fwd/layers`` is the
    layer scan's own carry traffic).  Of op names joined by ``;`` the
    first decides."""
    op = op_name.split(";", 1)[0]
    if "gpt/loss" in op:
        return "loss"
    for scope in ("optimizer", "guard", "exchange"):
        if f"/{scope}/" in f"/{op}/":
            return scope
    found = _SCOPE.findall(op)
    if not found:
        return "unscoped"
    top = ("fwd" if "transpose(" not in op
           else "recompute" if "rematted_computation" in op else "bwd")
    kernels = [k for _, k in found if k]
    return f"{top}/kernel/{kernels[-1]}" if kernels \
        else f"{top}/{found[-1][0]}"


def join(exclusive: dict, texts: dict) -> dict | None:
    """Seconds per bucket (top level, and ``top/second``) of the program
    whose text knows the most of the trace's seconds: ``%fusion.3``
    exists in more than one module.  Joined on the instruction's head
    (name, result type, opcode), else on its name.  Collective ops go
    to ``collective`` (they have a metric of their own) whatever scope
    their op name carries.  Reported, not hidden: ``split_s`` (fusions
    whose op name joins several with ``;``: the first decided),
    ``renamed_s`` (multi-output fusions booked by their largest output,
    see ``table``) and ``unscoped_top`` (the largest ops without a
    scope)."""
    best = None
    for program, text in texts.items():
        known, renamed = table(text)
        seconds, loose, matched, split, moved = {}, {}, 0.0, 0.0, 0.0
        for event, s in exclusive.items():
            key = head(event)
            if key not in known:
                key = trace.instruction(event)
            op_name = known.get(key)
            b = "unscoped" if op_name is None else bucket(op_name)
            if b == "unscoped":
                loose[trace.label(event)] = s
            if op_name is None:
                continue
            matched += s
            split += s if ";" in op_name else 0.0
            moved += s if key in renamed else 0.0
            if trace.is_collective(event):
                b = "collective" if b != "unscoped" else b
            for key in {b, b.split("/", 1)[0]}:
                seconds[key] = seconds.get(key, 0.0) + s
        if best is None or matched > best["matched_s"]:
            best = {"program": program, "seconds": seconds,
                    "matched_s": matched, "split_s": split,
                    "renamed_s": moved, "unscoped_top": sorted(loose.items(),
                                           key=lambda kv: -kv[1])[:8]}
    if best is None:
        return None
    total = sum(exclusive.values())
    seconds = best["seconds"]
    seconds["unscoped"] = seconds.get("unscoped", 0.0) + total - best[
        "matched_s"]
    best["total_s"] = total
    best["scoped_s"] = total - seconds["unscoped"]
    return best


def of(context) -> dict | None:
    """The join for this run's reduced trace, made once; ``None`` -- and
    a line on stderr saying why -- without a trace, on a program that
    registers nothing (the parent of the PR that brought scopes), or
    when fewer than half of the device's seconds find a scope (a stale
    compile-cache entry serves the scope-less text: a wrong split is
    worse than none)."""
    global _last
    reduced = context["trace"]
    if reduced is None:
        return None
    if _last[0] is reduced:
        return _last[1]
    joined, t0 = None, time.perf_counter()
    try:
        from ray_lightning_accelerators_tpu.telemetry import scopes
        texts = {name: scopes.program_text(name)
                 for name in scopes.registered()}
        t1 = time.perf_counter()
        joined = join(reduced["exclusive"], texts)
    except Exception as e:      # never fail a measured run over a reader
        print(f"benchmark: no scope table from the program ({e!r})",
              file=sys.stderr)
    if joined is not None:
        print(json.dumps({
            "info": "scopes", "program": joined["program"],
            "text_bytes": len(texts[joined["program"]]),
            "text_s": t1 - t0, "join_s": time.perf_counter() - t1,
            "total_s": joined["total_s"], "scoped_s": joined["scoped_s"],
            "split_s": joined["split_s"],
            "renamed_s": joined["renamed_s"],
            "seconds": dict(sorted(joined["seconds"].items())),
            "unscoped_top": joined["unscoped_top"]}),
            flush=True)
        if joined["scoped_s"] < 0.5 * joined["total_s"]:
            print("benchmark: under half of the device's seconds carry a "
                  "scope (stale compile cache?); scope metrics left out",
                  file=sys.stderr)
            joined = None
    _last = (reduced, joined)
    return joined
