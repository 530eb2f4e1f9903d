"""Sharded scorer vs single-device scorer on the virtual 8-device CPU mesh.

The contract: for any corpus placement, the mesh-sharded scorer returns the
same top-K logits, (global) row indices, and above-bound counts as the
single-device scorer over the concatenated corpus.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sesam_duke_microservice_tpu.ops import features as F
from sesam_duke_microservice_tpu.ops import scoring as S
from sesam_duke_microservice_tpu.parallel import (
    ShardedCorpus,
    build_sharded_scorer,
    corpus_mesh,
)

from test_device_matcher import dedup_schema, random_records

CHUNK = 16
TOP_K = 8


@pytest.fixture(scope="module")
def mesh():
    assert jax.device_count() == 8, "conftest must force 8 virtual CPU devices"
    return corpus_mesh()


def build_inputs(n_corpus, n_queries, seed=17):
    schema = dedup_schema()
    plan = F.SchemaFeatures.plan(schema)
    records = random_records(n_corpus, seed=seed)
    queries = records[:n_queries]
    feats = F.extract_batch(plan, records)
    valid = np.ones((n_corpus,), dtype=bool)
    valid[n_corpus // 3] = False          # one tombstone
    deleted = np.zeros((n_corpus,), dtype=bool)
    deleted[n_corpus // 2] = True         # one dukeDeleted row
    group = np.full((n_corpus,), -1, dtype=np.int32)
    qfeats = F.extract_batch(plan, queries)
    query_row = np.arange(n_queries, dtype=np.int32)
    query_group = np.full((n_queries,), -2, dtype=np.int32)
    return plan, feats, valid, deleted, group, qfeats, query_row, query_group


class TestShardedScorer:
    def test_matches_single_device(self, mesh):
        n = 8 * CHUNK * 2  # 2 chunks per shard
        (plan, feats, valid, deleted, group,
         qfeats, query_row, query_group) = build_inputs(n, 16)

        placer = ShardedCorpus(mesh, chunk=CHUNK)
        sfeats, svalid, sdeleted, sgroup = placer.place(
            feats, valid, deleted, group
        )
        sharded = build_sharded_scorer(plan, mesh, chunk=CHUNK, top_k=TOP_K)
        qf = {p: {k: jnp.asarray(a) for k, a in t.items()}
              for p, t in qfeats.items()}
        min_logit = jnp.float32(-5.0)
        s_logit, s_index, s_count = sharded(
            qf, sfeats, svalid, sdeleted, sgroup,
            jnp.asarray(query_group), jnp.asarray(query_row), min_logit,
        )

        # single-device reference over the same (padded) corpus
        cap = placer.padded_capacity(n)
        def pad(a, fill=0):
            out = np.full((cap,) + a.shape[1:], fill, dtype=a.dtype)
            out[:n] = a
            return out
        single = S.build_corpus_scorer(plan, chunk=CHUNK, top_k=TOP_K)
        d_logit, d_index, d_count = single(
            qf,
            {p: {k: jnp.asarray(pad(a)) for k, a in t.items()}
             for p, t in feats.items()},
            jnp.asarray(pad(valid, False)), jnp.asarray(pad(deleted, False)),
            jnp.asarray(pad(group, -1)),
            jnp.asarray(query_group), jnp.asarray(query_row), min_logit,
        )

        np.testing.assert_allclose(
            np.asarray(s_logit), np.asarray(d_logit), rtol=1e-5, atol=1e-5
        )
        np.testing.assert_array_equal(np.asarray(s_count), np.asarray(d_count))
        # ties may order differently across shards, so raw index equality is
        # too strict; instead every selected global row must score (on the
        # single-device scorer's full logit matrix) exactly what the sharded
        # scorer reported for it — catches any row-offset miscomputation
        s_idx = np.asarray(s_index)
        s_log = np.asarray(s_logit)
        d_idx = np.asarray(d_index)
        d_log = np.asarray(d_logit)
        for qi in range(s_idx.shape[0]):
            # rows scoring strictly above the K-th score are unambiguous
            # (no tie with the cut) and must be selected by both scorers —
            # catches any row-offset miscomputation in the sharded merge
            kth = d_log[qi, -1]
            strict_d = {int(r) for r, v in zip(d_idx[qi], d_log[qi])
                        if v > kth + 1e-4}
            strict_s = {int(r) for r, v in zip(s_idx[qi], s_log[qi])
                        if v > kth + 1e-4}
            assert strict_d == strict_s

    def test_group_filtering_sharded(self, mesh):
        n = 8 * CHUNK
        (plan, feats, valid, deleted, group,
         qfeats, query_row, query_group) = build_inputs(n, 8)
        group = np.asarray([1 + (i % 2) for i in range(n)], dtype=np.int32)
        query_group = np.asarray([1 + (i % 2) for i in range(8)], dtype=np.int32)

        placer = ShardedCorpus(mesh, chunk=CHUNK)
        sfeats, svalid, sdeleted, sgroup = placer.place(
            feats, valid, deleted, group
        )
        sharded = build_sharded_scorer(
            plan, mesh, chunk=CHUNK, top_k=TOP_K, group_filtering=True
        )
        qf = {p: {k: jnp.asarray(a) for k, a in t.items()}
              for p, t in qfeats.items()}
        s_logit, s_index, _ = sharded(
            qf, sfeats, svalid, sdeleted, sgroup,
            jnp.asarray(query_group), jnp.asarray(query_row),
            jnp.float32(-5.0),
        )
        s_index = np.asarray(s_index)
        s_logit = np.asarray(s_logit)
        # every returned candidate must be from the other group
        for qi in range(8):
            for k in range(TOP_K):
                row = s_index[qi, k]
                if row >= 0 and s_logit[qi, k] > S.NEG_INF / 2:
                    assert group[row] != query_group[qi]

    def test_self_exclusion_global_rows(self, mesh):
        # query i IS corpus row i; the sharded scorer must never return the
        # query's own global row even though shards renumber locally
        n = 8 * CHUNK
        (plan, feats, valid, deleted, group,
         qfeats, query_row, query_group) = build_inputs(n, 16)
        placer = ShardedCorpus(mesh, chunk=CHUNK)
        sfeats, svalid, sdeleted, sgroup = placer.place(
            feats, valid, deleted, group
        )
        sharded = build_sharded_scorer(plan, mesh, chunk=CHUNK, top_k=TOP_K)
        qf = {p: {k: jnp.asarray(a) for k, a in t.items()}
              for p, t in qfeats.items()}
        s_logit, s_index, _ = sharded(
            qf, sfeats, svalid, sdeleted, sgroup,
            jnp.asarray(query_group), jnp.asarray(query_row),
            jnp.float32(-5.0),
        )
        s_index = np.asarray(s_index)
        s_logit = np.asarray(s_logit)
        for qi in range(16):
            returned = s_index[qi][s_logit[qi] > S.NEG_INF / 2]
            assert qi not in returned


class TestMultihost:
    def test_initialize_noop_without_coordinator(self, monkeypatch):
        from sesam_duke_microservice_tpu.parallel import multihost

        monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
        assert multihost.initialize() is False

    def test_global_corpus_mesh_spans_all_devices(self):
        import jax

        from sesam_duke_microservice_tpu.parallel import global_corpus_mesh

        mesh = global_corpus_mesh()
        assert mesh.size == jax.device_count()


def test_virtual_mesh_env_is_enough_to_pin_the_cpu():
    """A child started with ``virtual_mesh_env`` sees only the virtual CPU
    devices: JAX_PLATFORMS=cpu set before the interpreter starts needs no
    in-process platform override."""
    import subprocess
    import sys

    from sesam_duke_microservice_tpu.utils.virtual_mesh import (
        virtual_mesh_env,
    )

    env = virtual_mesh_env(4, "_TEST_INNER")
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax; d = jax.devices(); print(d[0].platform, len(d))"],
        env=env, capture_output=True, text=True, timeout=120, check=True,
    ).stdout.split()
    assert out == ["cpu", "4"]
