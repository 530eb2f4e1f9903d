"""Ring bulk re-match at scale on the (virtual) mesh — VERDICT r2 #8.

Scores a corpus against itself (the bulk re-match shape, N x N) through
``parallel/ring.py`` — both query and corpus axes sharded, blocks rotating
over ppermute — and, with ``--verify``, re-scores the same queries through
the replicated ``parallel/sharded.py`` layout and asserts the surviving
(pair, logit) sets are identical.

On hosts without enough chips it self-provisions the virtual CPU mesh
(same recipe as the driver's dryrun).  The absolute throughput on the CPU
mesh is an artifact; the result that matters is the layout equality at
>= 100k x 100k and that per-device query memory is N/D.

Usage::

    python benchmarks/ring_rematch_bench.py [--rows 100000] [--devices 8]
        [--verify] [--block 8192]

Prints ONE JSON line.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _reexec(argv, n_devices):
    from sesam_duke_microservice_tpu.utils.virtual_mesh import (
        virtual_mesh_env,
    )

    env = virtual_mesh_env(n_devices, "_RING_BENCH_INNER")
    code = (
        "import runpy, sys; sys.argv = %r; "
        "runpy.run_path(%r, run_name='__main__')"
        % ([sys.argv[0]] + argv, os.path.abspath(__file__))
    )
    proc = subprocess.run([sys.executable, "-c", code], env=env)
    sys.exit(proc.returncode)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=100_000)
    ap.add_argument("--devices", type=int, default=8)
    ap.add_argument("--block", type=int, default=8192,
                    help="query rows per ring call (multiple of devices)")
    ap.add_argument("--chunk", type=int, default=512)
    ap.add_argument("--top-k", type=int, default=64)
    ap.add_argument("--verify", action="store_true",
                    help="also run the replicated layout and compare")
    ap.add_argument("--schema", choices=["lev", "exact"], default="lev",
                    help="'exact' swaps the Levenshtein comparator for a "
                         "hash-equality one: the layout-equality property "
                         "under test is schema-independent, and exact "
                         "pairs run ~100x faster on the 1-core virtual "
                         "CPU mesh, making 100k x 100k tractable there")
    args = ap.parse_args()

    import jax

    if (len(jax.devices()) < args.devices
            and os.environ.get("_RING_BENCH_INNER") != "1"):
        _reexec(sys.argv[1:], args.devices)

    import jax.numpy as jnp
    import numpy as np

    from sesam_duke_microservice_tpu.core import comparators as C
    from sesam_duke_microservice_tpu.core.config import DukeSchema
    from sesam_duke_microservice_tpu.core.records import (
        ID_PROPERTY_NAME,
        Property,
        Record,
    )
    from sesam_duke_microservice_tpu.ops import features as F
    from sesam_duke_microservice_tpu.ops import scoring as S
    from sesam_duke_microservice_tpu.parallel import (
        RingQueryPlacer,
        ShardedCorpus,
        build_ring_scorer,
        build_sharded_scorer,
        corpus_mesh,
    )

    n = args.rows
    mesh = corpus_mesh(jax.devices()[: args.devices])

    comparator = C.Levenshtein() if args.schema == "lev" else C.Exact()
    schema = DukeSchema(
        threshold=0.8, maybe_threshold=None,
        properties=[
            Property(ID_PROPERTY_NAME, id_property=True),
            Property("NAME", comparator, 0.1, 0.95),
        ],
        data_sources=[],
    )
    plan = F.SchemaFeatures.plan(schema)

    rng = np.random.default_rng(1234)
    letters = np.array(list("abcdefghijklmnopqrstuvwxyz"))
    records = []
    prev = None
    for i in range(n):
        r = Record()
        r.add_value(ID_PROPERTY_NAME, f"d__{i}")
        # random 16-char names (distinct rows are far apart in edit
        # distance); every third row duplicates its predecessor -> the
        # survivor set is exactly the seeded duplicate pairs
        if i % 3 == 2 and prev is not None:
            name = prev
        else:
            name = "".join(letters[rng.integers(0, 26, size=16)])
        prev = name
        r.add_value("NAME", name)
        records.append(r)
    feats = F.extract_batch(plan, records)
    valid = np.ones((n,), bool)
    deleted = np.zeros((n,), bool)
    group = np.full((n,), -1, np.int32)

    placer = ShardedCorpus(mesh, chunk=args.chunk)
    sfeats, svalid, sdeleted, sgroup = placer.place(feats, valid, deleted, group)
    qplacer = RingQueryPlacer(mesh)
    ring = build_ring_scorer(plan, mesh, chunk=args.chunk, top_k=args.top_k)
    min_logit = jnp.float32(S.probability_to_logit(0.8) - 1e-3)

    def survivors(tl, ti, rows):
        out = set()
        for qi in range(rows.size):
            keep = tl[qi] > float(min_logit)
            for logit, crow in zip(tl[qi][keep], ti[qi][keep]):
                if int(crow) >= 0:
                    out.add((int(rows[qi]), int(crow), round(float(logit), 4)))
        return out

    ring_pairs = set()
    t0 = time.perf_counter()
    for start in range(0, n, args.block):
        rows = np.arange(start, min(start + args.block, n))
        qf = {p: {k: a[rows] for k, a in t.items()} for p, t in feats.items()}
        rqf, rqg, rqr = qplacer.place(
            qf, group[rows], rows.astype(np.int32)
        )
        tl, ti, cnt = ring(rqf, sfeats, svalid, sdeleted, sgroup, rqg, rqr,
                           min_logit)
        tl = np.asarray(tl)[: rows.size]
        ti = np.asarray(ti)[: rows.size]
        assert int(np.asarray(cnt)[: rows.size].max(initial=0)) <= args.top_k
        ring_pairs |= survivors(tl, ti, rows)
    ring_s = time.perf_counter() - t0

    out = {
        "mode": "ring", "devices": int(mesh.size), "rows": n,
        "pairs_ranked": n * n, "ring_seconds": round(ring_s, 2),
        "pairs_per_sec": round(n * n / ring_s),
        "survivor_pairs": len(ring_pairs),
        "per_device_query_rows": args.block // mesh.size,
    }

    if args.verify:
        sharded = build_sharded_scorer(
            plan, mesh, chunk=args.chunk, top_k=args.top_k
        )
        repl_pairs = set()
        t1 = time.perf_counter()
        for start in range(0, n, args.block):
            rows = np.arange(start, min(start + args.block, n))
            qf = {
                p: {k: jnp.asarray(a[rows]) for k, a in t.items()}
                for p, t in feats.items()
            }
            tl, ti, cnt = sharded(
                qf, sfeats, svalid, sdeleted, sgroup,
                jnp.asarray(group[rows]), jnp.asarray(rows.astype(np.int32)),
                min_logit,
            )
            repl_pairs |= survivors(
                np.asarray(tl)[: rows.size], np.asarray(ti)[: rows.size],
                rows,
            )
        out["replicated_seconds"] = round(time.perf_counter() - t1, 2)
        out["verified_equal"] = ring_pairs == repl_pairs
        assert out["verified_equal"], (
            f"ring != replicated: {len(ring_pairs)} vs {len(repl_pairs)} "
            f"pairs; diff sample: "
            f"{list(ring_pairs ^ repl_pairs)[:5]}"
        )

    print(json.dumps(out))


if __name__ == "__main__":
    main()
