"""Capture a profiler trace of a steady window and reduce it to numbers.

The reduction reads the ``.xplane.pb`` the JAX profiler writes, through
``jax.profiler.ProfileData`` (nothing but JAX).  What a TPU v5e trace
looks like on this installation (jax 0.9.0, libtpu 0.0.34):

- one plane per chip, ``/device:TPU:<n>``, with the lines ``Steps``,
  ``XLA Modules``, ``XLA Ops`` and ``Async XLA Ops``.  An ``XLA Ops``
  event is one HLO instruction executed by the core: its name is the
  instruction's whole text (``%fusion.3 = bf16[..] fusion(..), ..``),
  with a start and a duration; a ``while`` or ``call`` event contains the
  events of its body, so exclusive time needs the nesting removed.
- a Pallas kernel is a ``custom-call`` with
  ``custom_call_target="tpu_custom_call"``; the kernel's Python name is
  NOT in the trace, so kernels are told apart by their operand shapes.
- host spans (``jax.profiler.TraceAnnotation``) sit on the ``/host:CPU``
  plane, on the same clock as the device lines to within a few
  milliseconds.

All times here are seconds, relative to the start of the trace.
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import os
import re
import shutil
import tempfile

WINDOW = "bench:window"          # the annotation that bounds the window
OPS_LINE = "XLA Ops"
_COLLECTIVE = re.compile(
    r"^(all-gather|reduce-scatter|all-reduce|all-to-all|collective-permute"
    r"|collective-broadcast)(-start|-done)?$")
_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
_SHAPE = re.compile(r"\w+\[([\d,]*)\]")
_ARRAY = re.compile(r"\w+\[[\d,]*\]")


@dataclasses.dataclass(frozen=True)
class Op:
    name: str       # the HLO instruction's text
    start: float
    end: float


@dataclasses.dataclass
class Trace:
    devices: dict        # plane name -> [Op] sorted by start
    host_spans: list     # [(name, start, end)] of "bench:*" annotations


# --------------------------------------------------------------------- #
# capture                                                                #
# --------------------------------------------------------------------- #
@contextlib.contextmanager
def capture():
    """Trace what runs inside the ``with`` into a directory under
    ``$TMPDIR`` (outside the checkout) and yield a one-slot list that
    holds the loaded ``Trace`` afterwards; the files are removed."""
    import jax

    out = [None]
    folder = tempfile.mkdtemp(prefix="bench-trace-")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0   # host spans come from annotations
    try:
        jax.profiler.start_trace(folder, profiler_options=options)
        try:
            yield out
        finally:
            jax.profiler.stop_trace()
        out[0] = load(folder)
    finally:
        shutil.rmtree(folder, ignore_errors=True)


def annotate(name: str):
    """A host span named ``bench:<name>`` in the profiler's own trace."""
    import jax
    return jax.profiler.TraceAnnotation("bench:" + name)


def load(folder_or_file: str) -> Trace:
    from jax.profiler import ProfileData

    path = folder_or_file
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(
            path, "plugins", "profile", "*", "*.xplane.pb")))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = found[-1]
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    names = {}      # one string per distinct op text, not one per event
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [Op(names.setdefault(e.name, e.name),
                              e.start_ns * 1e-9,
                              (e.start_ns + e.duration_ns) * 1e-9)
                           for e in line.events]
            if ops:
                devices[plane.name] = sorted(
                    ops, key=lambda o: (o.start, -o.end))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench:"):
                        spans.append((e.name, e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9))
    return Trace(devices, sorted(spans, key=lambda s: s[1]))


# --------------------------------------------------------------------- #
# reading an op's text                                                   #
# --------------------------------------------------------------------- #
def opcode(name: str) -> str:
    """``fusion``, ``custom-call``, ``all-gather-start`` ... of an HLO
    instruction's text; the text itself where it is not one."""
    head, sep, tail = name.partition(" = ")
    if not sep:
        return name
    m = _OPCODE.search(" " + tail)
    return m.group(1) if m else name


def instruction(name: str) -> str:
    """``%fusion.3`` of ``%fusion.3 = ...``."""
    return name.partition(" = ")[0]


def is_collective(name: str) -> bool:
    return bool(_COLLECTIVE.match(opcode(name)))


def is_pallas(name: str) -> bool:
    return 'custom_call_target="tpu_custom_call"' in name


def operand_shapes(name: str) -> list:
    """Every array shape in the instruction's text, as tuples."""
    return [tuple(int(x) for x in dims.split(",") if x)
            for dims in _SHAPE.findall(name)]


def label(name: str) -> str:
    """A short label for the breakdown: instruction, opcode, the first
    array type in its text (its result, or the first of a tuple)."""
    first = _ARRAY.search(name.partition(" = ")[2])
    text = f"{instruction(name)} {opcode(name)}"
    if is_pallas(name):
        text += " pallas"
    return (text + (" " + first.group(0) if first else ""))[:120]


# --------------------------------------------------------------------- #
# reduction                                                              #
# --------------------------------------------------------------------- #
def window_of(trace: Trace) -> tuple:
    """[start, end] of the ``bench:window`` annotation; without one, the
    span of the device events."""
    for name, start, end in trace.host_spans:
        if name == WINDOW:
            return start, end
    starts = [ops[0].start for ops in trace.devices.values()]
    ends = [max(o.end for o in ops) for ops in trace.devices.values()]
    return min(starts), max(ends)


def _clip(ops, lo, hi):
    return [Op(o.name, max(o.start, lo), min(o.end, hi))
            for o in ops if o.end > lo and o.start < hi]


def busy_intervals(ops) -> list:
    """Union of the ops' intervals, as merged [start, end] pairs."""
    merged = []
    for o in ops:
        if merged and o.start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], o.end)
        else:
            merged.append([o.start, o.end])
    return merged


def exclusive_times(ops) -> list:
    """``[(op, seconds)]``: each op's duration minus the part its nested
    ops cover.  ``ops`` sorted by (start, -end)."""
    out, stack = [], []     # stack of [op, covered-by-children]

    def close():
        op, covered = stack.pop()
        out.append((op, max(op.end - op.start - covered, 0.0)))
        if stack:
            stack[-1][1] += op.end - op.start

    for o in ops:
        while stack and o.start >= stack[-1][0].end:
            close()
        stack.append([o, 0.0])
    while stack:
        close()
    return out


def _span_at(trace: Trace, t: float) -> str:
    best = None
    for name, start, end in trace.host_spans:
        if name != WINDOW and start <= t <= end:
            if best is None or end - start < best[1]:
                best = (name, end - start)
    return best[0] if best else "unattributed"


def reduce(trace: Trace) -> dict | None:
    """The numbers the readers and the last line use:

    ``window_s``, ``busy_s`` (union of device-op intervals inside the
    window, averaged over the chips), ``devices``, and for the first
    chip ``exclusive`` ({op text: seconds}), ``device_ops`` /
    ``idle_gaps`` (top 10 each, for ``breakdown``)."""
    if not trace.devices:
        return None     # no TPU plane (a CPU rehearsal): nothing to read
    lo, hi = window_of(trace)
    busy = []
    for ops in trace.devices.values():
        busy.append(sum(e - s for s, e in busy_intervals(_clip(ops, lo, hi))))
    first = sorted(trace.devices)[0]
    ops = _clip(trace.devices[first], lo, hi)
    exclusive = {}
    for op, seconds in exclusive_times(ops):
        exclusive[op.name] = exclusive.get(op.name, 0.0) + seconds
    by_label = {}
    for name, seconds in exclusive.items():
        by_label[label(name)] = by_label.get(label(name), 0.0) + seconds
    gaps = {}
    edges = [[lo, lo]] + busy_intervals(ops) + [[hi, hi]]
    for (_, end), (start, _) in zip(edges, edges[1:]):
        if start > end:
            key = _span_at(trace, (start + end) / 2)
            gaps[key] = gaps.get(key, 0.0) + (start - end)

    def top(table):
        return [[k, v] for k, v in sorted(table.items(),
                                          key=lambda kv: -kv[1])[:10]]

    return {"window_s": hi - lo, "busy_s": sum(busy) / len(busy),
            "devices": len(busy), "exclusive": exclusive,
            "device_ops": top(by_label), "idle_gaps": top(gaps)}
