"""Program spans in a traced run: what the host was doing.

The program names every annotation it bridges into the profiler
``duke/<span name>`` (``telemetry/tracing.py``, ``ANNOTATION_PREFIX``)
and gives each the ``time.monotonic_ns()`` reading taken as it was
entered, as the event stat ``monotonic_ns``; the runtime's own host
events (``Transpose``, ``np.asarray(jax.Array)``,
``PythonRefManager::CollectGarbage``) carry neither and are left out.
Over a window [lo, hi) on the trace's clock:

* ``span_stats``: per span name, ``{"count", "seconds"}``: the spans that
  overlap the window, and their time clipped to it;
* ``idle_by_span``: over every interval in which the device ran no op,
  the seconds each innermost open program span covered (across host
  threads, the open span that started last), and under ``NO_SPAN`` the
  seconds with no program span open; averaged over the devices traced,
  like ``tracefile.reduce``'s busy time.

``of_run`` reads a traced run of ``perf/run.py`` from a metric reader's
context: the trace ``run.py`` left under ``TRACE_DIR``, with the run's
measured window (the client's monotonic ``t0`` to its last
acknowledgement) placed on the trace's clock through the annotations'
own readings (``clock_offset_ns``).  Those work on ``tracefile.extract``'s
plain lists, so a hand-made trace beside the tests checks them without a
chip.  A trace with no program span (a program that bridges none, or
none carrying a reading) reads None.
"""

from __future__ import annotations

import glob
import heapq
import os
from collections import defaultdict

import tracefile

PREFIX = "duke/"
ANCHOR = PREFIX + "clock.anchor"
CLOCK_STAT = "monotonic_ns"
NO_SPAN = "no program span"
# where perf/run.py captures a traced run's window (its RUN_DIR/trace)
TRACE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         ".run", "trace")


def _host_events(trace: dict):
    for plane in trace["planes"]:
        if plane["name"].startswith("/host:"):
            for line in plane["lines"]:
                yield from line["events"]


def program_spans(trace: dict):
    """``(name, start_ns, end_ns)`` of every program span, prefix off."""
    return [(name[len(PREFIX):], start, start + dur)
            for name, start, dur in _host_events(trace)
            if name.startswith(PREFIX) and name != ANCHOR]


def clock_offset(events):
    """``trace_ns - monotonic_ns`` of the program's clock, from ``(name,
    start_ns, stats)`` host events: the least over the program's
    annotations of where each starts less the reading it carries (each
    reading is taken just before its event opens), or None when no
    annotation carries one."""
    best = None
    for name, start, stats in events:
        if not name.startswith(PREFIX):
            continue
        for key, value in stats:
            if key == CLOCK_STAT:
                off = int(start) - int(value)
                best = off if best is None else min(best, off)
    return best


def clock_offset_ns(directory: str):
    """``clock_offset`` of the one trace under ``directory``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                        recursive=True)
    data = ProfileData.from_file(path)
    return clock_offset(
        (event.name, event.start_ns, event.stats)
        for plane in data.planes if plane.name.startswith("/host:")
        for line in plane.lines for event in line.events
        if event.name.startswith(PREFIX))


def load(directory: str) -> dict:
    """``tracefile.extract``'s lists, and ``clock_offset_ns``."""
    trace = tracefile.extract(directory)
    trace["clock_offset_ns"] = clock_offset_ns(directory)
    return trace


def span_stats(trace: dict, lo: int, hi: int) -> dict:
    out = defaultdict(lambda: {"count": 0, "seconds": 0.0})
    for name, s, e in program_spans(trace):
        if s < hi and (e > lo or s >= lo):
            row = out[name]
            row["count"] += 1
            row["seconds"] += max(0, min(e, hi) - max(s, lo)) / 1e9
    return dict(out)


def _idle(plane: dict, lo: int, hi: int):
    busy = tracefile._union(
        [(s, s + d) for line in plane["lines"]
         if line["name"] == tracefile.OPS_LINE
         for _, s, d in line["events"]], lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def _attribute(idle, spans, out) -> None:
    """Add to ``out`` the ns of each idle interval under the open span
    that started last (``spans`` sorted by start)."""
    heap = []  # (-start, end, name): the latest start on top
    i = 0
    for a, b in idle:
        t = a
        while t < b:
            while i < len(spans) and spans[i][0] <= t:
                s, e, name = spans[i]
                heapq.heappush(heap, (-s, e, name))
                i += 1
            while heap and heap[0][1] <= t:
                heapq.heappop(heap)  # ended: a span below it shows again
            nxt = b
            if i < len(spans):
                nxt = min(nxt, spans[i][0])
            if heap:
                nxt = min(nxt, heap[0][1])
            out[heap[0][2] if heap else NO_SPAN] += nxt - t
            t = nxt


def idle_by_span(trace: dict, lo: int, hi: int) -> dict:
    devices = tracefile.device_planes(trace)
    if not devices:
        return {}
    spans = sorted((max(s, lo), min(e, hi), name)
                   for name, s, e in program_spans(trace)
                   if min(e, hi) > max(s, lo))
    out = defaultdict(int)
    for plane in devices:
        _attribute(_idle(plane, lo, hi), spans, out)
    n = len(devices)
    return {k: v / n / 1e9 for k, v in out.items() if v > 0}


def summarize(trace: dict, lo: int, hi: int) -> dict:
    return {"span_stats": span_stats(trace, lo, hi),
            "idle_by_span": idle_by_span(trace, lo, hi)}


_RUN = {}  # the last run read: its readers share one pass over the trace


def of_run(ctx, directory: str = TRACE_DIR):
    """``summarize`` of a traced run's measured window, from a metric
    reader's context (``perf/run.py`` ``Context``); None when the run
    was not traced or no program span ties the trace to the program's
    clock."""
    if ctx.trace is None:
        return None
    key = (directory, ctx.t0, ctx.last_ack)
    if key not in _RUN:
        _RUN.clear()
        trace = load(directory)
        off = trace["clock_offset_ns"]
        _RUN[key] = None if off is None else summarize(
            trace, round(ctx.t0 * 1e9) + off,
            round(ctx.last_ack * 1e9) + off)
    summary = _RUN[key]
    return summary if summary and summary["span_stats"] else None


def top(idle: dict, n: int = tracefile.TOP):
    """The ``n`` largest ``[name, seconds]`` of an ``idle_by_span``."""
    return [[k, v] for k, v in
            sorted(idle.items(), key=lambda kv: -kv[1])[:n]]
