"""Profiler traces: capture inside the window, and reduce to numbers.

``capture`` starts JAX's profiler with the Python tracer off (host
spans still arrive through ``jax.profiler.TraceAnnotation``, which the
program's tracing bridge opens when device annotations are on).
``extract`` reads the ``.xplane.pb`` into plain lists, and ``reduce``
turns those into:

* ``busy_s``: the union of the device's op intervals, averaged over the
  devices traced, inside the window;
* ``window_s``: the window's length: the capture, clipped to the run's
  measured window (its start to the last acknowledgement);
* ``modules``: device seconds by XLA module (jitted program) name;
* ``device_ops``: the ten ops that took most device time, each named by
  its HLO name and result shape (``op_name``);
* ``idle_gaps``: the ten longest device idle gaps, each named by the
  host span that was open at its middle (the innermost one).

The reduction works on the plain lists, so a small recorded trace kept
beside the tests checks it without a chip.
"""

from __future__ import annotations

import glob
import json
import os
import time
from collections import defaultdict

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TOP = 10


class Capture:
    def __init__(self, directory: str):
        self.directory = directory
        self.start = self.stop = None

    def __enter__(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        self.start = time.monotonic()
        return self

    def __exit__(self, *exc):
        import jax

        self.stop = time.monotonic()
        jax.profiler.stop_trace()
        return False


def extract(directory: str) -> dict:
    """The trace's device and host planes as plain lists of events:
    {"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
    duration_ns], ...]}]}]}."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {directory}, "
                           f"found {len(paths)}")
    data = ProfileData.from_file(paths[0])
    planes = []
    for plane in data.planes:
        if not (plane.name.startswith("/device:")
                or plane.name.startswith("/host:CPU")):
            continue
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                [e.name, int(e.start_ns), int(e.duration_ns)]
                for e in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _union(intervals, lo: int, hi: int):
    """Merged [start, end) intervals clipped to [lo, hi)."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def device_planes(trace: dict):
    return [p for p in trace["planes"] if p["name"].startswith("/device:")
            and any(line["name"] == OPS_LINE and line["events"]
                    for line in p["lines"])]


def op_name(name: str) -> str:
    """An HLO op's name and result shape, without its operands:
    ``%fusion.62 = f32[2048,64]{...} fusion(...)`` -> ``fusion.62
    f32[2048,64]``; a tuple-typed op (a ``while``) keeps its name alone."""
    if " = " not in name:
        return name
    op, rest = name.split(" = ", 1)
    op = op.lstrip("%")
    if rest.startswith("("):
        return op
    return f"{op} {rest.split(' ', 1)[0].split('{', 1)[0]}"


def reduce(trace: dict, hi_ns: int, lo_ns: int = 0) -> dict:
    """Numbers from an extracted trace, over the window [lo_ns, hi_ns)
    on the trace's clock (0 where the capture started)."""
    window_ns = hi_ns - lo_ns
    devices = device_planes(trace)
    if not devices:
        return {"busy_s": None, "window_s": window_ns / 1e9, "modules": {},
                "device_ops": [], "idle_gaps": []}
    busy_total = 0
    ops = defaultdict(int)
    modules = defaultdict(int)
    gaps = []
    for plane in devices:
        spans = []
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                inside = max(0, min(start + dur, hi_ns) - max(start, lo_ns))
                if line["name"] == OPS_LINE:
                    spans.append((start, start + dur))
                    ops[op_name(name)] += inside
                elif line["name"] == MODULES_LINE:
                    modules[name] += inside
        busy = _union(spans, lo_ns, hi_ns)
        busy_total += sum(e - s for s, e in busy)
        edges = [lo_ns] + [x for iv in busy for x in iv] + [hi_ns]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, s, e))
    host = [ev for p in trace["planes"] if p["name"].startswith("/host:")
            for line in p["lines"] for ev in line["events"]]
    gaps.sort(reverse=True)
    named = []
    for length, s, e in gaps[:TOP]:
        mid = (s + e) // 2
        open_ = [ev for ev in host if ev[1] <= mid < ev[1] + ev[2]]
        label = (max(open_, key=lambda ev: ev[1])[0] if open_
                 else "no host span")
        named.append([label, length / 1e9])
    n = len(devices)
    return {
        "busy_s": busy_total / n / 1e9,
        "window_s": window_ns / 1e9,
        "modules": {k: v / n / 1e9 for k, v in modules.items()},
        "device_ops": [[k, v / n / 1e9] for k, v in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": named,
    }


def load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)
