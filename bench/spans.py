"""The program's own spans in a profiler trace, the device's idle time
split by them, and the engine's lifecycle stamps.

The serving path opens ``jax.profiler.TraceAnnotation`` spans with fixed
names at its layer boundaries: ``frontend.*`` on the event loop's thread
(``AsyncServeFrontend``), ``serve.*`` on the engine's thread
(``ServeEngine``: ``step_begin`` holds ``admit``, ``upload`` and
``dispatch``; ``step_commit`` holds ``readback``). They are host events
of the ``/host:CPU`` plane, on the device planes' clock, one line per
thread; ``bench.trace`` reduces the same planes.

* spans: every program span inside the harness's window, clipped to it,
  with its count, total seconds and self seconds (its duration less the
  part its child spans on the same thread cover), by name;
* idle split: the device's idle time in the window, from the busy
  intervals exactly as ``bench.trace.reduce`` computes them, with each
  idle instant given to the innermost ``serve.*`` span open then, else
  the innermost ``frontend.*`` span, else to ``between spans``; summed
  over the device planes and averaged, so that it adds up to
  ``reduce``'s window less its busy time.

The engine stamps each request's ``submit_time``, ``admit_time`` and
``first_token_at`` with ``time.perf_counter``; the client's records are
on the event loop's ``time.monotonic``, which is the same clock on
Linux (``CLOCK_MONOTONIC``).
"""

from __future__ import annotations

import collections

from bench.trace import (DEVICE_PREFIX, HOST_PLANE, OPS_LINE, WINDOW_SPAN,
                         _events, union)

SERVE = "serve."
FRONTEND = "frontend."
BETWEEN = "between spans"
#: Spans that the readers look for by name.
STEP_BEGIN = "serve.step_begin"
STEP_COMMIT = "serve.step_commit"
READBACK = "serve.readback"


def _labelled(spans, lo, hi) -> list:
    """``[(start, end, label)]`` covering ``[lo, hi]``: each piece
    labelled by the innermost ``serve.*`` span open, else the innermost
    ``frontend.*`` one, else ``BETWEEN``. Of the spans open, the
    innermost is the last to start, and of those the first to end."""
    edges = sorted({lo, hi} | {x for _, s, e in spans for x in (s, e)
                               if lo < x < hi})
    starts = sorted(spans, key=lambda sp: sp[1])
    open_, i, out = [], 0, []
    for a, b in zip(edges, edges[1:]):
        open_ = [sp for sp in open_ if sp[2] > a]
        while i < len(starts) and starts[i][1] <= a:
            if starts[i][2] > a:
                open_.append(starts[i])
            i += 1
        label = BETWEEN
        for prefix in (SERVE, FRONTEND):
            mine = [(s, e, n) for n, s, e in open_ if n.startswith(prefix)]
            if mine:
                label = max(mine, key=lambda x: (x[0], -x[1]))[2]
                break
        out.append((a, b, label))
    return out


def _self_seconds(line_spans) -> dict:
    """Self seconds by name of one thread's spans, which nest."""
    own = collections.Counter()
    stack: list = []
    for n, s, e in sorted(line_spans, key=lambda sp: (sp[1], -sp[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        if stack:
            own[stack[-1][0]] -= (e - s) / 1e9
        own[n] += (e - s) / 1e9
        stack.append((n, s, e))
    return own


def reduce_spans(planes, span: str = WINDOW_SPAN) -> dict:
    """Program spans and the device's idle time split by them, in the
    traced window (see module docstring)."""
    planes = list(planes)
    host = [p for p in planes if p.name == HOST_PLANE]
    devices = [p for p in planes if p.name.startswith(DEVICE_PREFIX)]
    marks = [(s, e) for n, s, e in (_events(host[0]) if host else [])
             if n == span]
    if not marks:
        raise ValueError(f"no {span!r} span in the trace")
    lo, hi = marks[0]
    count, total = collections.Counter(), collections.Counter()
    own = collections.Counter()
    spans = []
    for line in host[0].lines:
        mine = [(ev.name, max(ev.start_ns, lo),
                 min(ev.start_ns + ev.duration_ns, hi))
                for ev in line.events
                if ev.name.startswith((SERVE, FRONTEND))
                and ev.start_ns + ev.duration_ns > lo and ev.start_ns < hi]
        for n, s, e in mine:
            count[n] += 1
            total[n] += (e - s) / 1e9
        own.update(_self_seconds(mine))
        spans += mine
    pieces = _labelled(spans, lo, hi)
    idle = collections.Counter()
    for plane in devices:
        busy = union((max(s, lo), min(e, hi))
                     for _, s, e in _events(plane, OPS_LINE)
                     if e > lo and s < hi)
        gaps = [(a, b) for a, b in zip([lo] + [e for _, e in busy],
                                       [s for s, _ in busy] + [hi])
                if b > a]
        j = 0
        for a, b in gaps:
            while pieces[j][1] <= a:
                j += 1
            k = j
            while k < len(pieces) and pieces[k][0] < b:
                pa, pb, label = pieces[k]
                idle[label] += (min(b, pb) - max(a, pa)) / 1e9
                k += 1
    n = max(1, len(devices))
    return {
        "window_s": (hi - lo) / 1e9,
        "devices": len(devices),
        "spans": {name: {"count": count[name], "total_s": total[name],
                         "self_s": own[name]} for name in count},
        "idle_s": {k: v / n for k, v in idle.items()},
    }


def stamp_waits(ctx, begin: str, end: str) -> list:
    """Seconds from stamp ``begin`` to stamp ``end`` (names of the
    engine's stamp dicts held in ``ctx``), for each request due in the
    window that has the first stamp by the end of the follow-on
    (``ctx.t_end``). One without the second by then counts at its wait
    so far, as the client's TTFT counts it. Empty where the program has
    no such stamps."""
    first, second = getattr(ctx, begin, None), getattr(ctx, end, None)
    if first is None or second is None:
        return []
    out = []
    for r in ctx.records:
        if (not ctx.t0 <= r.due < ctx.t1 or r.uid not in first
                or first[r.uid] > ctx.t_end):
            continue
        stop = second.get(r.uid)
        if stop is None or stop > ctx.t_end:
            stop = ctx.t_end
        out.append(stop - first[r.uid])
    return out
