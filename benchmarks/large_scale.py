"""Large-corpus benchmark: ANN matching throughput at 10^5-10^7 rows.

The workload BASELINE.json configs[4] points at ("10M-record synthetic
dedup, mesh-sharded allgather on v5e-8"): index N synthetic records into
the embedding-ANN backend and measure steady-state incremental matching
throughput — the service's hot loop once a big corpus is resident.

Two modes:

  * single chip (default): the AnnProcessor path on the real device.
  * ``--sharded``: the mesh-sharded ANN program
    (``parallel.ann_sharded.build_sharded_ann_scorer``) over an
    ``--devices``-way mesh.  On a host without that many chips the bench
    re-execs itself on a virtual CPU mesh (the tests/conftest recipe), so
    the full shard_map program — per-shard retrieval + rescoring,
    all_gather merge over the mesh axis — executes for real at 10^5-row
    scale, and the printed HBM budget extrapolates the measured bytes/row
    to the 10M-row v5e-8 target.

Usage::

    python benchmarks/large_scale.py [--rows 1000000] [--batch 1024]
        [--measure-batches 5] [--sharded] [--devices 8]

Prints one JSON line: {"rows", "ingest_rows_per_sec", "query_rows_per_sec",
"effective_pairs_per_sec", "hbm_bytes_per_row"} (+ sharded budget fields).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# v5e HBM per chip (16 GiB)
V5E_HBM_BYTES = 16 * (1 << 30)


def _reexec_on_virtual_mesh(n_devices: int) -> None:
    from sesam_duke_microservice_tpu.utils.virtual_mesh import (
        virtual_mesh_env,
    )

    env = virtual_mesh_env(n_devices, "_LS_SHARDED_INNER")
    proc = subprocess.run([sys.executable] + sys.argv, env=env)
    sys.exit(proc.returncode)


def run_sharded(args) -> None:
    import jax

    if (len(jax.devices()) < args.devices
            and os.environ.get("_LS_SHARDED_INNER") != "1"):
        _reexec_on_virtual_mesh(args.devices)
        return

    import jax.numpy as jnp
    import numpy as np

    from f1_stresstest import generate, stresstest_schema, to_records
    from sesam_duke_microservice_tpu.ops import encoder as E
    from sesam_duke_microservice_tpu.ops import features as F
    from sesam_duke_microservice_tpu.ops import scoring as S
    from sesam_duke_microservice_tpu.parallel import (
        ShardedCorpus,
        build_sharded_ann_scorer,
        corpus_mesh,
    )

    schema = stresstest_schema()
    plan = F.SchemaFeatures.plan(schema)
    dim = int(os.environ.get("DEVICE_ANN_DIM", "256"))
    enc = E.RecordEncoder(schema, dim)

    devices = jax.devices()[: args.devices]
    assert len(devices) == args.devices, (
        f"need {args.devices} devices for the sharded bench, have "
        f"{len(devices)}"
    )
    mesh = corpus_mesh(devices)
    chunk = int(os.environ.get("SHARDED_CHUNK", "1024"))
    top_c = 64

    # slab-extract the corpus feature tensors + embeddings on host
    t0 = time.perf_counter()
    slabs, slab_rows = [], 50_000
    remaining, seed = args.rows, 1000
    while remaining > 0:
        n = min(slab_rows, remaining)
        rows, _ = generate(n, args.dup_rate, seed)
        records = to_records(rows)
        for r in records:
            r.set_values("ID", [f"s{seed}__{r.record_id}"])
        feats = F.extract_batch(plan, records)
        # the production corpus storage dtype (E.STORAGE_DTYPE)
        feats[E.ANN_PROP] = {E.ANN_TENSOR: enc.encode_corpus(records)}
        slabs.append(feats)
        remaining -= n
        seed += 1
    feats = {
        prop: {
            name: np.concatenate([s[prop][name] for s in slabs])
            for name in slabs[0][prop]
        }
        for prop in slabs[0]
    }
    n_rows = args.rows
    ingest_s = time.perf_counter() - t0

    per_row = sum(
        arr.dtype.itemsize * int(arr.size // max(1, arr.shape[0]))
        for tensors in feats.values() for arr in tensors.values()
    ) + 6  # masks: valid (bool) + deleted (bool) + group (int32)

    # place record-axis sharded over the mesh
    placer = ShardedCorpus(mesh, chunk=chunk)
    valid = np.ones((n_rows,), dtype=bool)
    deleted = np.zeros((n_rows,), dtype=bool)
    group = np.full((n_rows,), -1, dtype=np.int32)
    sfeats, svalid, sdeleted, sgroup = placer.place(
        feats, valid, deleted, group
    )
    local_rows = placer.padded_capacity(n_rows) // mesh.size

    scorer = build_sharded_ann_scorer(plan, mesh, chunk=chunk, top_c=top_c)

    def query_batch(seed):
        rows, _ = generate(args.batch, args.dup_rate, seed)
        records = to_records(rows)
        for r in records:
            r.set_values("ID", [f"q{seed}__{r.record_id}"])
        qf = {
            p: {k: jnp.asarray(a) for k, a in t.items()}
            for p, t in F.extract_batch(plan, records).items()
        }
        q_emb = jnp.asarray(enc.encode_batch(records))
        return q_emb, qf

    min_logit = jnp.float32(
        S.probability_to_logit(schema.threshold)
        - S.host_bound_logit(plan.host_props) - 1e-3
    )
    qrow = jnp.full((args.batch,), -1, jnp.int32)
    qgroup = jnp.full((args.batch,), -2, jnp.int32)

    # warm (compile), then steady-state
    q_emb, qf = query_batch(7777)
    scorer(q_emb, qf, sfeats, svalid, sdeleted, sgroup, qgroup, qrow,
           min_logit)[0].block_until_ready()
    times = []
    for i in range(args.measure_batches):
        q_emb, qf = query_batch(8000 + i)
        t0 = time.perf_counter()
        out = scorer(q_emb, qf, sfeats, svalid, sdeleted, sgroup, qgroup,
                     qrow, min_logit)
        out[0].block_until_ready()
        times.append(time.perf_counter() - t0)
    best = min(times)

    # sanity: merged rows are real global rows
    ti = np.asarray(out[1])
    assert ti.max() < placer.padded_capacity(n_rows) and (ti >= -1).all()

    target_rows = 10_000_000
    budget = {
        "hbm_bytes_per_row": per_row,
        "target_rows": target_rows,
        "target_total_gib": round(target_rows * per_row / (1 << 30), 2),
        "target_per_shard_gib": round(
            target_rows * per_row / args.devices / (1 << 30), 3
        ),
        "v5e_hbm_per_chip_gib": 16,
        # the named v5e-8 verdict is always about 8 chips, regardless of
        # the mesh width this validation run used
        "fits_v5e_8": target_rows * per_row / 8 < 0.8 * V5E_HBM_BYTES,
    }
    print(json.dumps({
        "mode": "sharded",
        "devices": mesh.size,
        "backend": jax.default_backend(),
        "rows": n_rows,
        "rows_per_shard": local_rows,
        "ingest_rows_per_sec": round(n_rows / ingest_s, 1),
        "query_rows_per_sec": round(args.batch / best, 1),
        "effective_pairs_per_sec": round(args.batch * n_rows / best, 1),
        "batch_seconds": round(best, 3),
        **budget,
    }))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--batch", type=int, default=1024)
    ap.add_argument("--measure-batches", type=int, default=5)
    ap.add_argument("--dup-rate", type=float, default=0.3)
    ap.add_argument("--sharded", action="store_true",
                    help="run the mesh-sharded ANN program (virtual CPU "
                         "mesh when the host lacks the chips)")
    ap.add_argument("--devices", type=int, default=8)
    args = ap.parse_args()
    if args.measure_batches < 1:
        ap.error("--measure-batches must be >= 1")

    if args.sharded:
        run_sharded(args)
        return

    from f1_stresstest import (
        build_processor,
        generate,
        stresstest_schema,
        to_records,
    )

    schema = stresstest_schema()
    proc = build_processor(schema, "ann")
    index = proc.database

    # ingest in slabs to bound host memory.  The clock covers only the
    # framework's work (index + commit: extraction, embedding, corpus
    # append, digests) — synthetic data generation is harness cost and is
    # reported separately (r4 methodology fix; the r3 number folded
    # generate()+to_records() into the ingest rate).
    ingest_s = 0.0
    gen_s = 0.0
    slab = 100_000
    remaining = args.rows
    seed = 1000
    while remaining > 0:
        n = min(slab, remaining)
        t_gen = time.perf_counter()
        rows, _ = generate(n, args.dup_rate, seed)
        records = to_records(rows)
        # distinct ids per slab
        for r in records:
            r.set_values("ID", [f"s{seed}__{r.record_id}"])
        t0 = time.perf_counter()
        gen_s += t0 - t_gen
        for r in records:
            index.index(r)
        index.commit()
        ingest_s += time.perf_counter() - t0
        remaining -= n
        seed += 1
    ingest_rate = args.rows / ingest_s

    # warm the scorer (compile + K/C settling)
    qrows, _ = generate(args.batch, args.dup_rate, 7777)
    warm = to_records(qrows)
    for r in warm:
        r.set_values("ID", [f"warm__{r.record_id}"])
    proc.deduplicate(warm)

    # steady-state incremental batches; per-phase split from the
    # processor's own stats so regressions name their phase (r5)
    times, splits = [], []
    for i in range(args.measure_batches):
        qrows, _ = generate(args.batch, args.dup_rate, 8000 + i)
        batch = to_records(qrows)
        for r in batch:
            r.set_values("ID", [f"q{i}__{r.record_id}"])
        r0 = proc.stats.retrieval_seconds
        c0 = proc.stats.compare_seconds
        t0 = time.perf_counter()
        proc.deduplicate(batch)
        times.append(time.perf_counter() - t0)
        splits.append((proc.stats.retrieval_seconds - r0,
                       proc.stats.compare_seconds - c0))
    best = min(times)
    score_s, finalize_s = splits[times.index(best)]
    corpus_rows = index.corpus.size

    # device bytes per corpus row (features + embedding + masks)
    per_row = 0
    for tensors in index.corpus.feats.values():
        for arr in tensors.values():
            per_row += arr.dtype.itemsize * int(
                arr.size // max(1, arr.shape[0])
            )

    print(json.dumps({
        "rows": corpus_rows,
        "ingest_rows_per_sec": round(ingest_rate, 1),
        "harness_gen_rows_per_sec": round(args.rows / gen_s, 1),
        "query_rows_per_sec": round(args.batch / best, 1),
        "effective_pairs_per_sec": round(args.batch * corpus_rows / best, 1),
        "hbm_bytes_per_row": per_row,
        "batch_seconds": round(best, 3),
        # device scoring wait (dispatch->resolve) vs host finalization;
        # the remainder of batch_seconds is ingest-side (extract, commit,
        # incremental device update)
        "score_seconds": round(score_s, 3),
        "finalize_seconds": round(finalize_s, 3),
        "ingest_side_seconds": round(best - score_s - finalize_s, 3),
    }))


if __name__ == "__main__":
    main()
