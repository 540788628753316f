"""Reduce a profiler trace (``.xplane.pb``) to device metrics.

The harness opens a ``jax.profiler.TraceAnnotation`` span, ``bench.window``,
on its own thread for the window; its bounds on the host clock are the
window here. Device operations are the events of the ``XLA Ops`` line of
each ``/device:TPU:<n>`` plane, on the same clock; on a TPU v5e each
event is named by its HLO instruction, e.g. ``%int4_matmul.1 =
f32[32,6144]{...} custom-call(f32[32,4096]{...} %x.1, ...)``.

* busy: the union of the operations' intervals inside the window,
  averaged over the device planes; idle share is 1 - busy / window;
* per-operation time: device seconds summed by a name that survives
  recompilation, the instruction's name without ``%`` and numeric suffix
  and its output type (``int4_matmul f32[32,6144]``); control flow
  (``while``) is left out, its bodies' operations are counted;
* idle gaps: the spaces between busy intervals, each named by the
  longest host event that overlaps it (other than the window's own
  span), summed by that name;
* kernel calls: every operation whose instruction name is a kernel's,
  with the shapes the instruction states (output first).
"""

from __future__ import annotations

import bisect
import collections
import glob
import os
import re

WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
#: Control-flow operations: their events span the operations of their
#: bodies, which the trace records too, so they count for busy time only.
CONTAINERS = ("while", "conditional", "call")
#: Host events that began up to this long before a gap may name it.
LOOKBACK_NS = 1e9
_INSTR = re.compile(r"^%?([A-Za-z_][\w\-]*?)(?:\.\d+)?\s*=\s*(\w+\[[\d,]*\])?")
_SHAPE = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``trace_dir``."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def _events(plane, line_name=None):
    """``(name, start_ns, end_ns)`` of a plane's events."""
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for line in plane.lines
            if line_name is None or line.name == line_name
            for ev in line.events]


def union(intervals) -> list:
    """Merged, sorted ``(start, end)`` intervals."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [tuple(m) for m in merged]


def clip(intervals, lo, hi) -> list:
    """The parts of ``intervals`` inside ``[lo, hi]``."""
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def instruction(name: str) -> tuple[str, str]:
    """``(instruction name, output type)`` of an op event's name."""
    m = _INSTR.match(name)
    if not m:
        return name, ""
    return m.group(1), m.group(2) or ""


def op_name(name: str) -> str:
    """A name for an operation that survives recompilation."""
    base, out = instruction(name)
    return f"{base} {out}".strip()


def hlo_shapes(text: str) -> list:
    """``[(dtype, dims), ...]`` stated in an instruction, output first."""
    return [(t, tuple(int(d) for d in dims.split(",") if d))
            for t, dims in _SHAPE.findall(text)]


def reduce(planes, span: str = WINDOW_SPAN) -> dict:
    """Device metrics of the traced window (see module docstring)."""
    planes = list(planes)                 # ProfileData yields them once
    host = [p for p in planes if p.name == HOST_PLANE]
    devices = [p for p in planes if p.name.startswith(DEVICE_PREFIX)]
    host_events = _events(host[0]) if host else []
    marks = [(s, e) for n, s, e in host_events if n == span]
    if not marks:
        raise ValueError(f"no {span!r} span in the trace")
    lo, hi = marks[0]
    busy_total, op_time = 0.0, collections.Counter()
    gaps = collections.Counter()
    calls, n_ops = [], 0
    others = sorted((s, e, n) for n, s, e in host_events
                    if n != span and e > s)
    starts = [s for s, _, _ in others]
    for plane in devices:
        inside = [(n, max(s, lo), min(e, hi))
                  for n, s, e in _events(plane, OPS_LINE) if e > lo and s < hi]
        n_ops += len(inside)
        busy = union((s, e) for _, s, e in inside)
        busy_total += sum(e - s for s, e in busy)
        for n, s, e in inside:
            if instruction(n)[0] not in CONTAINERS:
                op_time[op_name(n)] += (e - s) / 1e9
            calls.append((n, (e - s) / 1e9))
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e <= s:
                continue
            first = bisect.bisect_left(starts, s - LOOKBACK_NS)
            last = bisect.bisect_left(starts, e)
            over = [(min(he, e) - max(hs, s), hn)
                    for hs, he, hn in others[first:last] if he > s]
            label = max(over)[1] if over else "no host event"
            gaps[label] += (e - s) / 1e9
    n = max(1, len(devices))
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy_total / n / 1e9,
        "devices": len(devices),
        "ops": n_ops,
        "op_time": dict(op_time),
        "calls": calls,
        "breakdown": {
            "device_ops": [[k, v] for k, v in op_time.most_common(10)],
            "idle_gaps": [[k, v] for k, v in gaps.most_common(10)],
        },
    }


def reduce_dir(trace_dir: str, span: str = WINDOW_SPAN) -> dict:
    """``reduce`` of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(find_xplane(trace_dir)).planes, span)


def idle_share(reduced):
    """Percent of the traced window with no operation on the device, or
    None without a trace or an operation in it."""
    if not reduced or not reduced["ops"] or reduced["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])


def kernel_calls(reduced, kernel: str) -> list:
    """``(seconds, shapes)`` of every traced call of ``kernel``."""
    if not reduced:
        return []
    return [(secs, hlo_shapes(n)) for n, secs in reduced["calls"]
            if instruction(n)[0] == kernel]
