"""Restart-to-serving wall clock at scale (VERDICT r2 #5).

Builds a persistent ANN workload of N seeded records (store puts + feature
extraction into the host corpus mirror — no scoring; the restart path
doesn't need it), saves the corpus snapshot, then measures a cold
"container restart": ``build_workload`` over the same data folder, which
loads the record store and restores the corpus tensors from the snapshot
(O(1) content-hash staleness check against the store's incremental digest
— ``store.records.SqliteRecordStore.content_hash``).

Usage::

    python benchmarks/restart_bench.py [--rows 10000000] [--dim 256]

Prints ONE JSON line with the phase timings.  Scale notes:

  * 10M rows needs DEVICE_INITIAL_CAPACITY pre-sizing (set automatically)
    and ~25 GB free disk (sqlite store + uncompressed snapshot; the bench
    sets SNAPSHOT_COMPRESS=0 — zlib over ~9 GB costs minutes).
  * the restart figure is store-load + snapshot-load + wiring; the first
    scoring batch additionally pays the device upload of the restored
    host mirror and any uncached XLA compiles.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("SNAPSHOT_COMPRESS", "0")


CONFIG_TEMPLATE = """
<DukeMicroService dataFolder="{folder}">
  <Deduplication name="restart" link-database-type="h2">
    <duke>
      <schema>
        <threshold>0.8</threshold>
        <property><name>NAME</name><comparator>levenshtein</comparator><low>0.25</low><high>0.85</high></property>
        <property><name>CITY</name><comparator>exact</comparator><low>0.45</low><high>0.65</high></property>
        <property><name>SSN</name><comparator>qgram</comparator><low>0.2</low><high>0.9</high></property>
      </schema>
      <data-source class="io.sesam.dukemicroservice.IncrementalDeduplicationDataSource">
        <param name="dataset-id" value="src"/>
        <column name="name" property="NAME"/>
        <column name="city" property="CITY"/>
        <column name="ssn" property="SSN"/>
      </data-source>
    </duke>
  </Deduplication>
</DukeMicroService>
"""


def seeded_entities(n, seed=1234):
    import random

    rng = random.Random(seed)
    first = ["ole", "kari", "per", "anne", "nils", "ingrid", "lars", "berit"]
    last = ["hansen", "johansen", "olsen", "larsen", "andersen", "pedersen"]
    cities = ["oslo", "bergen", "trondheim", "stavanger", "tromso"]
    for i in range(n):
        yield {
            "_id": str(i),
            "name": f"{rng.choice(first)} {rng.choice(last)} {i % 977}",
            "city": rng.choice(cities),
            "ssn": f"{rng.randrange(10**10):010d}",
        }


def run(rows: int, folder: str, batch: int = 50_000):
    from sesam_duke_microservice_tpu.core.config import parse_config
    from sesam_duke_microservice_tpu.engine.workload import build_workload

    os.environ.setdefault("MIN_RELEVANCE", "0.05")
    os.environ.setdefault("DEVICE_INITIAL_CAPACITY", str(rows + 4096))
    os.environ.setdefault("DEVICE_PREWARM", "0")
    sc = parse_config(CONFIG_TEMPLATE.format(folder=folder))
    wc = sc.deduplications["restart"]

    out = {"rows": rows}

    # -- build phase: store puts + index/commit (feature extraction) --------
    # (skipped when the folder already holds a built corpus — lets the
    # restart phase re-run without the ~15-minute 10M build)
    prebuilt = os.path.exists(
        os.path.join(wc.data_folder, "corpus_snapshot.npz"))
    if prebuilt:
        out["build_skipped"] = True
        snap = os.path.join(wc.data_folder, "corpus_snapshot.npz")
        out["snapshot_bytes"] = os.path.getsize(snap)
        return _restart_phase(rows, wc, sc, out)
    # a half-built folder (no snapshot) would restore + re-index on top of
    # itself and double the corpus; refuse instead
    if os.path.exists(os.path.join(wc.data_folder, "records.sqlite")):
        raise SystemExit(
            "data folder has a record store but no snapshot; delete it "
            "or point --folder elsewhere"
        )
    wl = build_workload(wc, sc, backend="ann", persistent=True)
    ds = wl.datasources["src"]
    t0 = time.perf_counter()
    t_store = t_index = 0.0
    done = 0
    for start in range(0, rows, batch):
        n = min(batch, rows - start)
        entities = list(seeded_entities(n, seed=start + 1))
        for e in entities:
            e["_id"] = str(start + int(e["_id"]))
        records = ds.records_for_batch(entities)
        t1 = time.perf_counter()
        wl.record_store.put_many(records)
        t2 = time.perf_counter()
        for r in records:
            wl.index.index(r)
        wl.index.commit()
        t3 = time.perf_counter()
        t_store += t2 - t1
        t_index += t3 - t2
        done += n
        if done % 1_000_000 < batch:
            print(f"  built {done}/{rows} rows "
                  f"({done / (time.perf_counter() - t0):.0f} rows/s)",
                  file=sys.stderr)
    out["build_total_s"] = round(time.perf_counter() - t0, 2)
    out["store_put_s"] = round(t_store, 2)
    out["extract_index_s"] = round(t_index, 2)

    t4 = time.perf_counter()
    wl.close()  # snapshot save + store/link close
    out["close_with_snapshot_save_s"] = round(time.perf_counter() - t4, 2)
    snap = os.path.join(wc.data_folder, "corpus_snapshot.npz")
    out["snapshot_bytes"] = os.path.getsize(snap)
    out["store_bytes"] = os.path.getsize(
        os.path.join(wc.data_folder, "records.sqlite")
    )

    return _restart_phase(rows, wc, sc, out)


def _restart_phase(rows, wc, sc, out):
    from sesam_duke_microservice_tpu.engine.workload import build_workload

    # prewarm during the restart leg competes with the snapshot load for
    # the host; default off, opt in with RESTART_PREWARM=1
    os.environ["DEVICE_PREWARM"] = os.environ.get(
        "RESTART_PREWARM", "0")
    t5 = time.perf_counter()
    wl2 = build_workload(wc, sc, backend="ann", persistent=True)
    out["restart_to_serving_s"] = round(time.perf_counter() - t5, 2)
    if out.get("build_skipped"):
        # prebuilt folder: the corpus defines the row count (a --rows
        # mismatch would otherwise size capacity wrong and abort the
        # measurement at the very end)
        out["rows"] = wl2.index.corpus.size
    else:
        assert wl2.index.corpus.size == rows, wl2.index.corpus.size
    out["snapshot_used"] = True

    # serving proof: one tiny transform probe end-to-end (also surfaces
    # the first-batch device upload + compile cost separately)
    t6 = time.perf_counter()
    with wl2.lock:
        wl2.process_batch(
            "src", [next(iter(seeded_entities(1, seed=7)))],
            http_transform=True,
        )
    out["first_probe_s"] = round(time.perf_counter() - t6, 2)
    wl2.close()
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=10_000_000)
    ap.add_argument("--folder", default=None,
                    help="data folder (default: fresh temp dir, deleted)")
    args = ap.parse_args()
    folder = args.folder or tempfile.mkdtemp(prefix="restart_bench_")
    try:
        print(json.dumps(run(args.rows, folder)))
    finally:
        if args.folder is None:
            shutil.rmtree(folder, ignore_errors=True)


if __name__ == "__main__":
    main()
