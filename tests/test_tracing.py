"""Distributed tracing + flight recorder (ISSUE 2).

Covers the tentpole's contract points: traceparent round-trips, span
nesting across the worker-thread pool, tail-latch retention of slow
unsampled requests, dispatch op-tuple propagation (leader + follower
spans sharing one trace id through the digest handshake), Chrome
trace-event export validity, and the ``/debug/*`` HTTP surface.
"""

import json
import socket
import struct
import threading
import time
import urllib.error
import urllib.request

import pytest

from sesam_duke_microservice_tpu.parallel import dispatch
from sesam_duke_microservice_tpu.telemetry import tracing
from sesam_duke_microservice_tpu.utils import profiling

from test_dispatch_auth import _tiny_index

KEY = ("deduplication", "t")


# -- traceparent -------------------------------------------------------------

def test_traceparent_round_trip():
    tid = "0af7651916cd43dd8448eb211c80319c"
    sid = "b7ad6b7169203331"
    for sampled in (True, False):
        ctx = tracing.parse_traceparent(
            tracing.format_traceparent(tid, sid, sampled))
        assert ctx.trace_id == tid
        assert ctx.parent_id == sid
        assert ctx.sampled is sampled


@pytest.mark.parametrize("bad", [
    None,
    "",
    "not-a-traceparent",
    "00-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331",  # missing flags
    "00-" + "0" * 32 + "-b7ad6b7169203331-01",               # zero trace id
    "00-0af7651916cd43dd8448eb211c80319c-" + "0" * 16 + "-01",
    "ff-0af7651916cd43dd8448eb211c80319c-b7ad6b7169203331-01",
    "00-0AF7651916CD43DD8448EB211C80319C-b7ad6b7169203331-01",  # upper hex
])
def test_traceparent_rejects_malformed(bad):
    assert tracing.parse_traceparent(bad) is None


# -- span nesting ------------------------------------------------------------

def test_span_nesting_across_threads():
    recorder = tracing.FlightRecorder(4, 4)
    with tracing.start_trace("root", sampled=True,
                             recorder=recorder) as root:
        with tracing.span("parent") as parent:
            ctx = tracing.current_context()

            def worker():
                with tracing.attach(ctx):
                    with tracing.span("child"):
                        pass

            t = threading.Thread(target=worker)
            t.start()
            t.join()
    record = recorder.get(root.trace_id)
    assert record is not None
    by_name = {s.name: s for s in record.spans}
    assert set(by_name) == {"root", "parent", "child"}
    assert by_name["child"].parent_id == parent.span_id
    assert by_name["parent"].parent_id == root.span_id
    assert by_name["child"].trace_id == root.trace_id


def test_span_is_noop_outside_a_trace():
    assert tracing.current_context() is None
    with tracing.span("orphan") as s:
        assert s is None  # no active trace: nothing recorded, no error


def test_span_cap_bounds_a_pathological_request(monkeypatch):
    monkeypatch.setenv("TRACE_MAX_SPANS", "8")
    recorder = tracing.FlightRecorder(4, 4)
    with tracing.start_trace("root", sampled=True,
                             recorder=recorder) as root:
        for i in range(50):
            with tracing.span(f"s{i}"):
                pass
    record = recorder.get(root.trace_id)
    assert len(record.spans) <= 9  # 8 capped children + the root
    assert record.dropped >= 40
    assert (root.attributes or {}).get("spans_dropped") == record.dropped


# -- tail latch --------------------------------------------------------------

def test_tail_latch_retains_slow_unsampled_trace(monkeypatch):
    monkeypatch.setenv("TRACE_SAMPLE_RATE", "0")
    monkeypatch.setenv("TRACE_SLOW_MS", "1")
    recorder = tracing.FlightRecorder(4, 4)
    with tracing.start_trace("slow", recorder=recorder) as root:
        time.sleep(0.005)
    assert root.trace_id is not None
    record = recorder.get(root.trace_id)
    assert record is not None and record.slow and not record.sampled


def test_fast_unsampled_trace_digested_but_not_retained(monkeypatch):
    monkeypatch.setenv("TRACE_SAMPLE_RATE", "0")
    monkeypatch.setenv("TRACE_SLOW_MS", "60000")
    recorder = tracing.FlightRecorder(4, 4)
    with tracing.start_trace("fast", recorder=recorder) as root:
        pass
    assert recorder.get(root.trace_id) is None
    digests = recorder.digests()
    assert len(digests) == 1
    assert digests[0]["trace_id"] == root.trace_id
    assert digests[0]["retained"] is False


def test_errored_trace_is_retained(monkeypatch):
    monkeypatch.setenv("TRACE_SAMPLE_RATE", "0")
    monkeypatch.setenv("TRACE_SLOW_MS", "60000")
    recorder = tracing.FlightRecorder(4, 4)
    with pytest.raises(RuntimeError):
        with tracing.start_trace("boom", recorder=recorder) as root:
            raise RuntimeError("kaput")
    record = recorder.get(root.trace_id)
    assert record is not None and record.status == "error"


def test_trace_ring_evicts_oldest():
    recorder = tracing.FlightRecorder(2, 16)
    ids = []
    for i in range(4):
        with tracing.start_trace(f"t{i}", sampled=True,
                                 recorder=recorder) as root:
            pass
        ids.append(root.trace_id)
    assert recorder.get(ids[0]) is None and recorder.get(ids[1]) is None
    assert recorder.get(ids[2]) is not None
    assert [s["trace_id"] for s in recorder.summaries()] == [ids[3], ids[2]]


def test_eviction_prefers_unremarkable_over_slow_traces(monkeypatch):
    """A client stamping every request sampled=01 must not flush the
    slow traces the tail latch retained (eviction skips slow/errored
    records while any sampled-only record remains)."""
    monkeypatch.setenv("TRACE_SLOW_MS", "1")
    recorder = tracing.FlightRecorder(2, 16)
    with tracing.start_trace("slow", sampled=True,
                             recorder=recorder) as slow_root:
        time.sleep(0.005)
    monkeypatch.setenv("TRACE_SLOW_MS", "60000")
    fast_ids = []
    for i in range(3):
        with tracing.start_trace(f"fast{i}", sampled=True,
                                 recorder=recorder) as root:
            pass
        fast_ids.append(root.trace_id)
    assert recorder.get(slow_root.trace_id) is not None  # survived
    assert recorder.get(fast_ids[-1]) is not None        # newest kept
    assert len(recorder.summaries()) == 2


def test_repeat_retention_merges_into_one_tree():
    """A follower replaying several ops of one request retains under one
    trace id several times — the trees must merge, not overwrite."""
    recorder = tracing.FlightRecorder(4, 8)
    tc = {"trace_id": "ab" * 16, "parent_id": "cd" * 8, "sampled": True}
    for name in ("follower:commit", "follower:score"):
        with tracing.capture_remote(name, tc, recorder=recorder):
            pass
    record = recorder.get("ab" * 16)
    assert {s.name for s in record.spans} == {
        "follower:commit", "follower:score"}
    assert len(recorder.summaries()) == 1


def test_digest_carries_phase_seconds():
    recorder = tracing.FlightRecorder(4, 4)
    with tracing.start_trace("batch", sampled=True, recorder=recorder):
        base = time.monotonic_ns()
        tracing.add_span("encode", base, base + 2_000_000)
        tracing.add_span("score", base, base + 3_000_000)
    phases = recorder.digests()[0]["phase_seconds"]
    assert phases["encode"] == pytest.approx(0.002)
    assert phases["score"] == pytest.approx(0.003)


# -- dispatch propagation ----------------------------------------------------

def test_with_trace_ctx_appends_only_inside_a_trace():
    op = ("commit", KEY, ["r"])
    assert dispatch.with_trace_ctx(op) == op  # no active trace
    with tracing.start_trace("x", sampled=True,
                             recorder=tracing.FlightRecorder(2, 2)) as root:
        tagged = dispatch.with_trace_ctx(op)
    assert tagged[:3] == op
    assert tagged[3]["trace_id"] == root.trace_id
    assert tagged[3]["sampled"] is True
    assert dispatch._op_trace_ctx(tagged, 3) == tagged[3]
    assert dispatch._op_trace_ctx(op, 3) is None


class _SpanFollower:
    """Loopback follower replaying commits into a real replica index and
    answering the digest handshake with its replay spans (the production
    follower path's frame shape, driven without jax.distributed)."""

    def __init__(self, sock):
        self.sock = sock
        self.index, _, _ = _tiny_index()
        self.thread = threading.Thread(target=self._run, daemon=True)
        self.thread.start()

    def _run(self):
        last_seq = 0
        while True:
            try:
                op, _epoch, seq = dispatch._recv_op(self.sock)
            except (EOFError, OSError):
                return
            if seq <= last_seq:
                continue  # dup frame (CI chaos leg): production fencing drops
            last_seq = seq
            if op[0] != "commit":
                continue
            _, _key, records = op[:3]
            cap = tracing.capture_remote(
                "follower:commit", dispatch._op_trace_ctx(op, 3),
                {"records": len(records), "process": "follower"},
            )
            with cap:
                for r in records:
                    self.index.index(r)
                self.index.commit()
            self.sock.sendall(dispatch._digest_frame(
                True, self.index._mirror_digest, cap.wire()))


def test_leader_and_follower_spans_share_one_trace(monkeypatch):
    """THE acceptance shape: a commit broadcast carries the leader's
    trace context, the follower's replay ships back through the digest
    handshake, and one tree holds both sides under one trace id."""
    a, b = socket.socketpair()
    d = dispatch.Dispatcher(app=None)
    d._conns = [a]
    follower = _SpanFollower(b)
    recorder = tracing.FlightRecorder(4, 4)
    try:
        idx, _, rec = _tiny_index()
        idx._dispatch_key = KEY
        monkeypatch.setattr(dispatch, "_DISPATCHER", d)
        with tracing.start_trace("POST /deduplication/:name/:datasetId",
                                 sampled=True, recorder=recorder) as root:
            idx.index(rec("a", "acme"))
            idx.commit()
        assert d._failed is None
        record = recorder.get(root.trace_id)
        assert record is not None
        remote = [s for s in record.spans if s.name == "follower:commit"]
        assert len(remote) == 1
        assert remote[0].trace_id == root.trace_id
        assert (remote[0].attributes or {}).get("remote") is True
        assert (remote[0].attributes or {}).get("process") == "follower"
        # digests still verified end to end
        assert idx._mirror_digest == follower.index._mirror_digest
    finally:
        a.close()
        b.close()


def test_follower_session_ships_spans_in_digest_frame():
    """Drive the production ``_FollowerSession`` op handler directly and
    decode the frame it answers with."""
    import types

    sent = []
    session = dispatch._FollowerSession(sent.append)

    class _FakeReplica:
        def __init__(self):
            self.index = types.SimpleNamespace(_mirror_digest=b"\x07" * 32)

        def apply_commit(self, records):
            with tracing.span("replica:index"):
                pass

    session.replicas[KEY] = _FakeReplica()
    tc = {"trace_id": "ab" * 16, "parent_id": "cd" * 8, "sampled": True}
    assert session.handle(("commit", KEY, ["r1", "r2"], tc))
    assert len(sent) == 1
    frame = sent[0]
    fixed = dispatch._DIGEST_LEN
    assert frame[:len(dispatch._DIGEST_MAGIC)] == dispatch._DIGEST_MAGIC
    (blob_len,) = struct.unpack(">I", frame[fixed:fixed + 4])
    rows = json.loads(frame[fixed + 4:fixed + 4 + blob_len])
    names = {r["name"] for r in rows}
    assert names == {"follower:commit", "replica:index"}
    assert all(r["trace_id"] == "ab" * 16 for r in rows)


def test_follower_session_without_ctx_sends_empty_blob():
    import types

    sent = []
    session = dispatch._FollowerSession(sent.append)
    replica = types.SimpleNamespace(
        index=types.SimpleNamespace(_mirror_digest=b"\x01" * 32),
        apply_commit=lambda records: None,
    )
    session.replicas[KEY] = replica
    assert session.handle(("commit", KEY, ["r1"]))  # historical op shape
    fixed = dispatch._DIGEST_LEN
    (blob_len,) = struct.unpack(">I", sent[0][fixed:fixed + 4])
    assert blob_len == 0


# -- chrome export -----------------------------------------------------------

def test_chrome_export_schema():
    recorder = tracing.FlightRecorder(4, 4)
    with tracing.start_trace("GET /x", sampled=True,
                             recorder=recorder) as root:
        with tracing.span("encode", {"records": 3}):
            pass
        tracing.graft_remote(json.dumps([{
            "trace_id": root.trace_id, "span_id": "ee" * 8,
            "parent_id": None, "name": "follower:commit",
            "offset_ns": 0, "duration_ns": 1000, "status": "ok",
            "attributes": {},
        }]).encode())
    out = tracing.chrome_trace(recorder.get(root.trace_id))
    json.dumps(out)  # must be valid JSON end to end
    assert out["displayTimeUnit"] == "ms"
    events = out["traceEvents"]
    complete = [e for e in events if e["ph"] == "X"]
    assert {e["name"] for e in complete} == {
        "GET /x", "encode", "follower:commit"}
    for e in complete:
        assert isinstance(e["ts"], float) and isinstance(e["dur"], float)
        assert e["dur"] >= 0 and "pid" in e and "tid" in e
    # remote spans land on the follower tid row
    assert [e["tid"] for e in complete if e["name"] == "follower:commit"] \
        == [1]
    assert any(e["ph"] == "M" for e in events)


# -- HTTP surface ------------------------------------------------------------

@pytest.fixture(scope="module")
def server_url():
    import os

    from sesam_duke_microservice_tpu.core.config import parse_config
    from sesam_duke_microservice_tpu.service.app import DukeApp, serve
    from test_service import CONFIG_XML

    os.environ["MIN_RELEVANCE"] = "0.05"
    app = DukeApp(parse_config(CONFIG_XML), persistent=False)
    server = serve(app, port=0, host="127.0.0.1")
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    yield f"http://127.0.0.1:{server.server_address[1]}"
    server.shutdown()
    del os.environ["MIN_RELEVANCE"]


def _request(url, method="GET", body=None, headers=None):
    req = urllib.request.Request(url, data=body, method=method,
                                 headers=headers or {})
    try:
        with urllib.request.urlopen(req, timeout=30) as resp:
            return resp.status, dict(resp.headers), resp.read()
    except urllib.error.HTTPError as e:
        return e.code, dict(e.headers), e.read()


def test_sampled_batch_lands_in_flight_recorder(server_url):
    tp = tracing.format_traceparent("12" * 16, "34" * 8, True)
    body = json.dumps([
        {"_id": "t1", "name": "ole hansen", "email": "o@x"},
        {"_id": "t2", "name": "ole hanse", "email": "o@x"},
    ]).encode()
    status, headers, _ = _request(
        server_url + "/deduplication/people/crm", "POST", body,
        {"Content-Type": "application/json", "traceparent": tp})
    assert status == 200
    assert headers["X-Trace-Id"] == "12" * 16  # inbound trace honored

    status, _, out = _request(server_url + "/debug/traces")
    assert status == 200
    rows = json.loads(out)["traces"]
    mine = [r for r in rows if r["trace_id"] == "12" * 16]
    assert mine and mine[0]["name"] == "POST /deduplication/:name/:datasetId"

    status, _, out = _request(server_url + "/debug/traces/" + "12" * 16)
    assert status == 200
    tree = json.loads(out)
    names = {s["name"] for s in tree["spans"]}
    # the acceptance tree: root HTTP span + all four engine phase spans
    assert "POST /deduplication/:name/:datasetId" in names
    assert {"encode", "retrieve", "score", "persist"} <= names

    status, _, out = _request(
        server_url + "/debug/traces/" + "12" * 16 + "?format=chrome")
    assert status == 200
    chrome = json.loads(out)
    assert chrome["traceEvents"] and any(
        e.get("ph") == "X" for e in chrome["traceEvents"])


def test_slow_unsampled_request_retained_over_http(server_url, monkeypatch):
    monkeypatch.setenv("TRACE_SAMPLE_RATE", "0")
    monkeypatch.setenv("TRACE_SLOW_MS", "0.0001")
    status, headers, _ = _request(server_url + "/healthz")
    assert status == 200
    tid = headers["X-Trace-Id"]
    status, _, out = _request(server_url + "/debug/traces/" + tid)
    assert status == 200
    assert json.loads(out)["slow"] is True


def test_debug_requests_ring_always_on(server_url, monkeypatch):
    monkeypatch.setenv("TRACE_SAMPLE_RATE", "0")
    monkeypatch.setenv("TRACE_SLOW_MS", "60000")
    status, headers, _ = _request(server_url + "/stats")
    assert status == 200
    tid = headers["X-Trace-Id"]
    # the digest lands at root-span exit, AFTER the response is on the
    # wire — a fresh connection can race the handler thread's last few
    # instructions, so poll briefly
    mine = []
    for _ in range(50):
        status, _, out = _request(server_url + "/debug/requests")
        rows = json.loads(out)["requests"]
        mine = [r for r in rows if r["trace_id"] == tid]
        if mine:
            break
        time.sleep(0.02)
    assert mine and mine[0]["retained"] is False
    assert mine[0]["name"] == "GET /stats"
    # but the unretained request still answered 404 on the tree endpoint
    status, _, _ = _request(server_url + "/debug/traces/" + tid)
    assert status == 404


def test_debug_trace_endpoint_validation(server_url):
    status, _, _ = _request(server_url + "/debug/traces/" + "ab" * 16)
    assert status == 404
    status, _, _ = _request(
        server_url + "/debug/traces/" + "ab" * 16 + "?format=xml")
    assert status == 400


def test_profile_endpoint_capture_cycle(server_url, monkeypatch):
    calls = []
    monkeypatch.setattr(profiling, "profiler_start",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(profiling, "profiler_stop",
                        lambda: calls.append(("stop",)))
    status, _, out = _request(server_url + "/debug/profile")
    assert status == 200 and json.loads(out)["capturing"] is None
    try:
        status, _, out = _request(
            server_url + "/debug/profile?seconds=30", "POST", b"")
        assert status == 200
        assert json.loads(out)["capturing"]["seconds"] == 30.0
        assert calls and calls[0][0] == "start"
        assert tracing.device_annotations_active()
        # one capture at a time
        status, _, _ = _request(
            server_url + "/debug/profile?seconds=1", "POST", b"")
        assert status == 409
        # ...but its status is visible, deadline included
        status, _, out = _request(server_url + "/debug/profile")
        live = json.loads(out)["capturing"]
        assert live is not None and live["remaining_seconds"] > 0
    finally:
        profiling.stop_capture()
    assert ("stop",) in calls
    assert not tracing.device_annotations_active()
    # validation
    status, _, _ = _request(
        server_url + "/debug/profile?seconds=bogus", "POST", b"")
    assert status == 400
    status, _, _ = _request(
        server_url + "/debug/profile?seconds=-1", "POST", b"")
    assert status == 400


def test_profile_reset_rearms_trace_budget(server_url):
    profiling._traced_batches = 5
    status, _, out = _request(
        server_url + "/debug/profile/reset", "POST", b"")
    assert status == 200
    assert json.loads(out)["trace_budget_reset"] is True
    assert profiling._traced_batches == 0


# -- device annotations ------------------------------------------------------

class _AnnotationLog:
    """Recording stand-in for ``tracing._enter_annotation``: one
    ``[name, enter_ns, exit_ns, reading]`` row per annotation entered,
    ``reading`` being the monotonic time the annotation carries."""

    def __init__(self):
        self.rows = []
        self._lock = threading.Lock()

    def __call__(self, name, monotonic_ns):
        row = [name, time.monotonic_ns(), None, monotonic_ns]
        with self._lock:
            self.rows.append(row)
        return _AnnotationExit(row)

    def named(self, name):
        with self._lock:
            return [r for r in self.rows if r[0] == name]


class _AnnotationExit:
    def __init__(self, row):
        self.row = row

    def __exit__(self, *exc):
        self.row[2] = time.monotonic_ns()


@pytest.fixture
def annotations(monkeypatch):
    log = _AnnotationLog()
    monkeypatch.setattr(tracing, "_enter_annotation", log)
    yield log
    tracing.set_device_annotations(False)


def test_annotated_span_outside_a_trace_enters_one_marked_annotation(
        annotations):
    """The dispatcher's waits run outside any request trace: with a
    capture live the span still reaches the profiler, under the
    program's ``duke/`` mark, and records no ``Span``."""
    tracing.set_device_annotations(True)
    assert tracing.current_context() is None
    with tracing.span("sched.starved", annotate=True) as s:
        assert s is None
        assert tracing.current_context() is None
    assert [r[0] for r in annotations.rows] == ["duke/sched.starved"]
    assert annotations.rows[0][2] is not None
    assert all(r[0].startswith(tracing.ANNOTATION_PREFIX)
               for r in annotations.rows)


def test_annotations_off_never_enter_one(annotations):
    recorder = tracing.FlightRecorder(2, 2)
    with tracing.span("sched.starved", annotate=True) as s:
        assert s is None
    with tracing.start_trace("root", sampled=True, recorder=recorder):
        with tracing.span("encode", annotate=True) as s:
            assert s is not None
    assert annotations.rows == []


def test_span_inside_a_trace_records_and_annotates(annotations):
    recorder = tracing.FlightRecorder(2, 2)
    tracing.set_device_annotations(True)
    with tracing.start_trace("root", sampled=True,
                             recorder=recorder) as root:
        with tracing.span("persist", annotate=True):
            pass
        with tracing.span("links:assert_batch"):
            pass
    names = {s.name for s in recorder.get(root.trace_id).spans}
    assert {"persist", "links:assert_batch"} <= names
    assert [r[0] for r in annotations.rows] == ["duke/persist"]


def test_clock_anchor_reads_before_its_annotation(annotations):
    reading = tracing.clock_anchor()
    (row,) = annotations.rows
    name, enter_ns, exit_ns, carried = row
    assert name == "duke/clock.anchor"
    assert carried == reading
    assert reading <= enter_ns <= exit_ns


def test_capture_start_places_the_clock_anchor(annotations, monkeypatch):
    monkeypatch.setattr(profiling, "profiler_start", lambda d: None)
    monkeypatch.setattr(profiling, "profiler_stop", lambda: None)
    try:
        info = profiling.start_capture(30)
    finally:
        profiling.stop_capture()
    (row,) = annotations.named("duke/clock.anchor")
    assert info["anchor_monotonic_ns"] <= row[1]


def _phase_counts(url):
    import re

    _, _, text = _request(url + "/metrics")
    return {m.group(1): int(float(m.group(2))) for m in re.finditer(
        r'duke_engine_phase_seconds_count\{kind="deduplication",'
        r'workload="people",phase="(\w+)"\} (\S+)', text.decode())}


def test_served_microbatch_names_every_host_step(tmp_path, monkeypatch,
                                                 annotations):
    """A real POST through the HTTP handler, the scheduler and the
    device engine, with annotations on: every host step of the
    microbatch enters its named annotation, retrieve ends before score
    starts, both inside ``sched.microbatch``, and the phase histograms
    count exactly what they count with annotations off."""
    from sesam_duke_microservice_tpu.core.config import parse_config
    from sesam_duke_microservice_tpu.service.app import DukeApp, serve
    from test_service import CONFIG_XML

    monkeypatch.setenv("MIN_RELEVANCE", "0.05")
    for knob in ("DUKE_SCHEDULER", "DUKE_WRITE_BEHIND", "DUKE_JOURNAL"):
        monkeypatch.setenv(knob, "1")  # pin against the CI =0 legs
    xml = CONFIG_XML.replace(
        "<DukeMicroService>", f'<DukeMicroService dataFolder="{tmp_path}">'
    ).replace('<Deduplication name="people" link-database-type="in-memory">',
              '<Deduplication name="people">')
    app = DukeApp(parse_config(xml), backend="device", persistent=True)
    server = serve(app, port=0, host="127.0.0.1")
    threading.Thread(target=server.serve_forever, daemon=True).start()
    url = f"http://127.0.0.1:{server.server_address[1]}"
    try:
        def post(prefix):
            body = json.dumps([
                {"_id": f"{prefix}1", "name": f"ole {prefix} hansen",
                 "email": f"{prefix}@x"},
                {"_id": f"{prefix}2", "name": f"ole {prefix} hanse",
                 "email": f"{prefix}@x"},
            ]).encode()
            status, _, _ = _request(
                url + "/deduplication/people/crm", "POST", body,
                {"Content-Type": "application/json"})
            assert status == 200

        post("off")
        counts = [_phase_counts(url)]
        assert annotations.rows == []
        tracing.set_device_annotations(True)
        post("on")
        tracing.set_device_annotations(False)
        counts.append(_phase_counts(url))
    finally:
        server.shutdown()
        app.close()

    steps = ("http.parse", "sched.microbatch", "ingest.convert",
             "ingest.store", "ingest.stamp", "encode", "retrieve", "score",
             "persist", "links.journal_append")
    for step in steps:
        assert annotations.named("duke/" + step), step
    assert all(r[0].startswith("duke/") and r[2] is not None
               for r in annotations.rows)
    # each carries the monotonic time it was entered at, for the clock
    assert all(r[3] <= r[1] for r in annotations.rows)
    (retrieve,) = annotations.named("duke/retrieve")
    (score,) = annotations.named("duke/score")
    assert retrieve[2] <= score[1]
    (batch,) = [r for r in annotations.named("duke/sched.microbatch")
                if r[1] <= retrieve[1]]
    assert batch[1] <= retrieve[1] and score[2] <= batch[2]
    # one more observation per phase, as with annotations off
    phases = ("encode", "retrieve", "score", "persist")
    assert all(counts[0][p] == 1 for p in phases), counts
    assert all(counts[1][p] == 2 for p in phases), counts


def test_error_responses_carry_request_and_trace_ids(server_url):
    status, headers, _ = _request(server_url + "/no/such/path")
    assert status == 404
    assert headers.get("X-Request-Id") not in (None, "-")
    assert headers.get("X-Trace-Id") not in (None, "-")
    # stdlib 501 path (no do_PUT): bypasses _reply, still correlatable —
    # send_error mints an id when dispatch never assigned one
    status, headers, _ = _request(server_url + "/healthz", method="PUT")
    assert status == 501
    assert headers.get("X-Request-Id") not in (None, "-")
