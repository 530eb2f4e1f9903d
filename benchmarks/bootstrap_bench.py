"""Multi-host bootstrap memory benchmark (VERDICT r4 #3 "done" proof).

Measures the frontend's transient memory and the wire-frame sizes while
streaming a large corpus bootstrap to a real follower over a real TCP
socket (two OS processes, the production ``Dispatcher.broadcast`` /
``_FollowerSession`` code), then a hot-reload re-stream.  The r4 protocol
pickled snapshot-bytes + every Record into ONE message — O(corpus) frame
+ O(corpus) transient RAM on both sides; the streamed protocol must hold
the largest frame at ~DUKE_DISPATCH_SNAP_CHUNK and the frontend RSS delta
at O(chunk), independent of --rows.

The frontend topology mirrors the flagship restart: records live in a
SQLite store behind a LazyRecordMap (no eager mirror), features in the
corpus host arrays.  Scoring is deliberately not run — this isolates the
bootstrap path; serving equivalence is tests/test_multihost_serving.py.

Usage::

    python benchmarks/bootstrap_bench.py [--rows 1000000] [--batch 8192]

Prints one JSON line with rss/frames stats.
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import socket
import struct
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("DEVICE_PREWARM", "0")

SCHEMA_XML = """
<DukeMicroService>
  <Deduplication name="people" link-database-type="in-memory">
    <duke>
      <schema>
        <threshold>0.8</threshold>
        <property><name>NAME</name><comparator>levenshtein</comparator><low>0.3</low><high>0.9</high></property>
        <property><name>CITY</name><comparator>exact</comparator><low>0.4</low><high>0.85</high></property>
      </schema>
      <data-source class="io.sesam.dukemicroservice.IncrementalDeduplicationDataSource">
        <param name="dataset-id" value="crm"/>
        <column name="name" property="NAME"/>
        <column name="city" property="CITY"/>
      </data-source>
    </duke>
  </Deduplication>
</DukeMicroService>
"""


def _maxrss_mb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0


def _make_records(start: int, n: int):
    from sesam_duke_microservice_tpu.core.records import (
        ID_PROPERTY_NAME, Record,
    )

    out = []
    for i in range(start, start + n):
        r = Record()
        r.add_value(ID_PROPERTY_NAME, f"crm__crm__r{i}")
        r.add_value("NAME", f"person {i % 97} no {i}")
        r.add_value("CITY", f"city-{i % 1024}")
        out.append(r)
    return out


def follower_child(port: int) -> None:
    """Child: accept the op stream, run _FollowerSession, report rss."""
    from sesam_duke_microservice_tpu.parallel import dispatch

    sock = socket.create_connection(("127.0.0.1", port), timeout=60)
    session = dispatch._FollowerSession(sock.sendall)
    n_ops = 0
    try:
        while True:
            try:
                op = dispatch._recv_msg(sock)
            except EOFError:
                break
            n_ops += 1
            if not session.handle(op):
                break
        key = ("deduplication", "people")
        replica = session.replicas.get(key)
        print(json.dumps({
            "follower_rss_mb": round(_maxrss_mb(), 1),
            "follower_rows": replica.index.corpus.size if replica else 0,
            "follower_ops": n_ops,
        }), flush=True)
    finally:
        session.close()
        sock.close()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--batch", type=int, default=8192)
    ap.add_argument("--_child-port", type=int, default=0)
    args = ap.parse_args()
    if args._child_port:
        follower_child(args._child_port)
        return

    from sesam_duke_microservice_tpu.core.config import parse_config
    from sesam_duke_microservice_tpu.engine.ann_matcher import AnnIndex
    from sesam_duke_microservice_tpu.parallel import dispatch
    from sesam_duke_microservice_tpu.store.records import (
        LazyRecordMap, SqliteRecordStore,
    )

    sc = parse_config(SCHEMA_XML, env={})
    schema = sc.deduplications["people"].duke

    tmp = tempfile.mkdtemp(prefix="bootstrap-bench-")
    store = SqliteRecordStore(os.path.join(tmp, "records.db"))
    index = AnnIndex(schema, tunables=sc.tunables)

    t0 = time.perf_counter()
    for start in range(0, args.rows, args.batch):
        batch = _make_records(start, min(args.batch, args.rows - start))
        store.put_many(batch)
        for r in batch:
            index.index(r)
        index.commit()
    ingest_s = time.perf_counter() - t0
    # flagship restart topology: store-backed lazy mirror, no eager dict
    index.records = LazyRecordMap(store)
    rss_after_build = _maxrss_mb()

    server = socket.create_server(("127.0.0.1", 0))
    port = server.getsockname()[1]
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__),
         "--_child-port", str(port)],
        stdout=subprocess.PIPE, text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    conn, _ = server.accept()

    d = dispatch.Dispatcher(app=None)
    d._conns = [conn]
    frames = {"n": 0, "max": 0, "total": 0}
    orig_broadcast = dispatch.Dispatcher.broadcast

    def counting_broadcast(self, op):
        import pickle

        sz = len(pickle.dumps(op, protocol=pickle.HIGHEST_PROTOCOL))
        frames["n"] += 1
        frames["max"] = max(frames["max"], sz)
        frames["total"] += sz
        orig_broadcast(self, op)

    d.broadcast = counting_broadcast.__get__(d)

    t1 = time.perf_counter()
    d.broadcast((
        "bootstrap_begin", "sharded", SCHEMA_XML, dispatch._env_fingerprint()
    ))
    d._stream_state(("deduplication", "people"), index)
    d.broadcast(("bootstrap_end",))
    stream1_s = time.perf_counter() - t1
    # hot reload path: the same states stream again
    t2 = time.perf_counter()
    d.broadcast(("reload_begin", "sharded", SCHEMA_XML))
    d._stream_state(("deduplication", "people"), index)
    d.broadcast(("bootstrap_end",))
    reload_s = time.perf_counter() - t2
    d.broadcast(("shutdown",))
    conn.close()
    server.close()

    child_out, _ = child.communicate(timeout=600)
    rss_after_stream = _maxrss_mb()
    follower = json.loads(child_out.strip().splitlines()[-1])

    print(json.dumps({
        "rows": args.rows,
        "ingest_s": round(ingest_s, 1),
        "stream_s": round(stream1_s, 1),
        "reload_stream_s": round(reload_s, 1),
        "frontend_rss_after_build_mb": round(rss_after_build, 1),
        "frontend_rss_after_stream_mb": round(rss_after_stream, 1),
        "frontend_stream_rss_delta_mb": round(
            rss_after_stream - rss_after_build, 1
        ),
        "frames": frames["n"],
        "max_frame_mb": round(frames["max"] / 1e6, 2),
        "total_streamed_mb": round(frames["total"] / 1e6, 1),
        **follower,
    }), flush=True)


if __name__ == "__main__":
    main()
