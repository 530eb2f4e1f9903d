"""The program-span reduction on a hand-made trace kept beside this file:
known span counts and times, device idle time by the span open during
it, the program's clock placed on the trace's, and the four readers
built on them; and the clock readings through a real capture."""

import json
import os
import types

import pytest

import run
import spans

HERE = os.path.dirname(os.path.abspath(__file__))
READERS = ("idle_unattributed_share.resync", "http_parse_ms.resync",
           "sched_idle_ms.resync", "ingest_host_ms.resync")


@pytest.fixture(scope="module")
def fixture():
    with open(os.path.join(HERE, "trace_spans.json")) as f:
        return json.load(f)


def _ctx(fixture, monkeypatch, trace=None):
    """A traced run's reader context whose window is the fixture's
    [lo_ns, hi_ns) once placed on the trace's clock."""
    trace = dict(trace or fixture["trace"],
                 clock_offset_ns=fixture["clock_offset_ns"])
    monkeypatch.setattr(spans, "load", lambda directory: trace)
    spans._RUN.clear()
    off = fixture["clock_offset_ns"]
    return types.SimpleNamespace(
        trace={"busy_s": 0.0}, t0=(fixture["lo_ns"] - off) / 1e9,
        last_ack=(fixture["hi_ns"] - off) / 1e9)


def test_span_stats(fixture):
    out = spans.span_stats(fixture["trace"], fixture["lo_ns"],
                           fixture["hi_ns"])
    want = fixture["expect"]["span_stats"]
    assert set(out) == set(want)
    for name, row in want.items():
        assert out[name]["count"] == row["count"], name
        assert out[name]["seconds"] == pytest.approx(row["seconds"],
                                                     abs=1e-18), name


def test_idle_by_span(fixture):
    out = spans.idle_by_span(fixture["trace"], fixture["lo_ns"],
                             fixture["hi_ns"])
    want = fixture["expect"]["idle_by_span"]
    assert set(out) == set(want)
    for name, seconds in want.items():
        assert out[name] == pytest.approx(seconds, abs=1e-18), name
    # every idle ns lands under exactly one name
    import tracefile

    reduced = tracefile.reduce(fixture["trace"], fixture["hi_ns"],
                               fixture["lo_ns"])
    assert sum(out.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], abs=1e-18)
    top = spans.top(out)
    assert top[0] == ["sched.starved", pytest.approx(2e-07)]
    assert [name for name, _ in top].count(spans.NO_SPAN) == 1


def test_clock_offset():
    """The least start-less-reading over the program's annotations;
    runtime events and annotations without a reading do not count."""
    stat = spans.CLOCK_STAT
    events = [("duke/sched.microbatch", 100, [(stat, 40)]),
              ("duke/encode", 300, [(stat, 230)]),
              ("Transpose", 0, [(stat, 1000)]),
              ("duke/persist", 500, [])]
    assert spans.clock_offset(events) == 60
    assert spans.clock_offset(events[2:]) is None


def test_readers(fixture, monkeypatch):
    ctx = _ctx(fixture, monkeypatch)
    assert spans.of_run(ctx) == spans.summarize(
        fixture["trace"], fixture["lo_ns"], fixture["hi_ns"])
    for name in READERS:
        assert run.reader("layer_metrics", name)(ctx) == pytest.approx(
            fixture["expect"]["readers"][name], rel=1e-12), name


def test_a_trace_without_program_spans_reads_none(fixture, monkeypatch):
    """A program that bridges no span under the mark (the runtime's
    events alone) leaves every span metric out, and so does a run that
    was not traced."""
    trace = json.loads(json.dumps(fixture["trace"]))
    for plane in trace["planes"]:
        for line in plane["lines"]:
            for event in line["events"]:
                event[0] = event[0].replace(spans.PREFIX, "")
    summary = spans.summarize(trace, fixture["lo_ns"], fixture["hi_ns"])
    assert summary["span_stats"] == {}
    assert list(summary["idle_by_span"]) == [spans.NO_SPAN]
    ctx = _ctx(fixture, monkeypatch, trace)
    assert spans.of_run(ctx) is None
    for name in READERS:
        assert run.reader("layer_metrics", name)(ctx) is None, name
    # nor does a program whose annotations carry no clock reading
    ctx = _ctx(fixture, monkeypatch)
    monkeypatch.setattr(spans, "load", lambda directory: dict(
        fixture["trace"], clock_offset_ns=None))
    assert spans.of_run(ctx) is None
    ctx.trace = None
    assert spans.of_run(ctx) is None


def test_a_capture_places_program_times(tmp_path):
    """Through a real profiler capture on the CPU: every program
    annotation reaches the trace with its reading, and a program time
    maps to where its span lies on the trace's clock."""
    import time

    import jax

    from sesam_duke_microservice_tpu.telemetry import tracing

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    tracing.set_device_annotations(True)
    try:
        reading = tracing.clock_anchor()
        t0 = time.monotonic_ns()
        with tracing.span("sched.starved", annotate=True):
            time.sleep(0.01)
        t1 = time.monotonic_ns()
    finally:
        tracing.set_device_annotations(False)
        jax.profiler.stop_trace()
    trace = spans.load(str(tmp_path))
    off = trace["clock_offset_ns"]
    assert off is not None
    (starved,) = spans.program_spans(trace)
    name, start, end = starved
    assert name == "sched.starved"
    # the span opened after t0 and closed before t1, on either clock
    assert t0 + off <= start and end <= t1 + off + 1_000_000
    assert reading + off >= 0
