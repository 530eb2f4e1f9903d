"""The single-device scan stops at the corpus's live high-water mark.

The bounded scorer (``build_corpus_scorer``, ``scan_topk(live_bound=True)``)
must give bit-identical (top_logit, top_index, count) to the full scan over
the capacity in every corpus state, and the host's count of scanned rows
(``DeviceCorpus.valid_hwm`` -> ``duke_device_scan_rows_total``) must equal
the bound the device computes from its own mask."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sesam_duke_microservice_tpu import telemetry
from sesam_duke_microservice_tpu.core import comparators as C
from sesam_duke_microservice_tpu.core.config import DukeSchema
from sesam_duke_microservice_tpu.core.records import ID_PROPERTY_NAME, Property
from sesam_duke_microservice_tpu.engine import device_matcher as DM
from sesam_duke_microservice_tpu.ops import features as F
from sesam_duke_microservice_tpu.ops import scoring as S

from test_device_matcher import make_record, random_records

CHUNK = 8
CAP = 4 * CHUNK
QUERIES = 8


def stresstest_schema():
    """The dedup stresstest's kernel mix: Levenshtein, Exact, QGram."""
    return DukeSchema(
        threshold=0.8,
        maybe_threshold=None,
        properties=[
            Property(ID_PROPERTY_NAME, id_property=True),
            Property("name", C.Levenshtein(), 0.25, 0.85),
            Property("city", C.Exact(), 0.45, 0.65),
            Property("ssn", C.QGram(), 0.2, 0.9),
        ],
        data_sources=[],
    )


def _records(n, seed):
    # 12 distinct records over and over: exact copies tie in the top-K
    base = random_records(12, seed=seed)
    return [make_record(f"s{seed}-{i}", name=base[i % 12].get_value("name"),
                        city=base[i % 12].get_value("city"),
                        ssn=f"{i % 12:05d}")
            for i in range(n)]


def _padded(plan, records, rows):
    feats = F.extract_batch(plan, records)
    return {
        prop: {name: jnp.asarray(DM._pad_rows(arr, rows))
               for name, arr in tensors.items()}
        for prop, tensors in feats.items()
    }


def _valid(state):
    """(valid mask, rows written) of one corpus state over CAP rows."""
    valid = np.zeros(CAP, bool)
    if state == "empty":
        return valid, 0
    if state == "all_tombstoned":
        return valid, CAP
    if state == "tail_tombstoned":
        valid[:20] = True
        valid[14:20] = False
        return valid, 20
    if state == "ends_mid_chunk":
        valid[:20] = True
        valid[3:6] = False
        return valid, 20
    if state == "ends_on_chunk_boundary":
        valid[:2 * CHUNK] = True
        return valid, 2 * CHUNK
    if state == "full":
        valid[:] = True
        return valid, CAP
    if state == "one_row_in_last_chunk":
        valid[CAP - 3] = True
        return valid, CAP - 2
    raise ValueError(state)


# each state's ceil((last valid row + 1) / CHUNK) of CAP // CHUNK = 4
LIVE_CHUNKS = {"empty": 0, "all_tombstoned": 0, "tail_tombstoned": 2,
               "ends_mid_chunk": 3, "ends_on_chunk_boundary": 2, "full": 4,
               "one_row_in_last_chunk": 4}
STATES = tuple(LIVE_CHUNKS)

_PROGRAMS = {}


def _programs(plan, top_k, group_filtering, from_rows):
    """(bounded scorer, full-scan twin): the same jitted program but for
    the trip count.  Every case shares the stresstest plan's shapes."""
    key = (top_k, group_filtering, from_rows)
    if key not in _PROGRAMS:
        bounded = S.build_corpus_scorer(
            plan, chunk=CHUNK, top_k=top_k, group_filtering=group_filtering,
            queries_from_rows=from_rows)
        pair_logits = S.build_pair_logits(plan)

        @jax.jit
        def full(qfeats, cfeats, cvalid, cdeleted, cgroup, qgroup, qrow,
                 min_logit):
            if from_rows:
                qfeats = S.gather_rows(cfeats, jnp.clip(qrow, 0))
            return S.scan_topk(
                pair_logits, qfeats, cfeats, cvalid, cdeleted, cgroup,
                qgroup, qrow, min_logit, chunk=CHUNK, top_k=top_k,
                group_filtering=group_filtering)

        _PROGRAMS[key] = (bounded, full)
    return _PROGRAMS[key]


@pytest.mark.parametrize("state", STATES)
def test_bounded_scan_is_bit_identical_to_full_scan(state):
    plan = F.SchemaFeatures.plan(stresstest_schema())
    corpus_records = _records(CAP, seed=11)
    valid, written = _valid(state)
    cfeats = _padded(plan, corpus_records[:written], CAP)
    deleted = np.zeros(CAP, bool)
    deleted[1::9] = True
    group = np.where(np.arange(CAP) < written, 1 + np.arange(CAP) % 2, -1)
    n_live = int(S.live_chunks(jnp.asarray(valid), CHUNK))
    assert n_live == LIVE_CHUNKS[state]

    probe_feats = _padded(plan, _records(QUERIES - 2, seed=12), QUERIES)
    # indexed queries point at corpus rows (padding -1 last)
    qrow = np.full(QUERIES, -1, np.int32)
    qrow[:QUERIES - 2] = [0, 3, 5, 9, 14, CAP - 3]
    qgroup = np.where(qrow >= 0, 1 + np.arange(QUERIES) % 2, -2)
    args = (cfeats, jnp.asarray(valid), jnp.asarray(deleted),
            jnp.asarray(group.astype(np.int32)),
            jnp.asarray(qgroup.astype(np.int32)), jnp.asarray(qrow),
            jnp.float32(-40.0))
    compared = 0
    for group_filtering in (False, True):
        for from_rows in (False, True):
            qfeats = {} if from_rows else probe_feats
            for top_k in (4, 16):  # the first K and one escalation
                bounded, full = _programs(plan, top_k, group_filtering,
                                          from_rows)
                got = jax.device_get(bounded(qfeats, *args))
                want = jax.device_get(full(qfeats, *args))
                for name, g, w in zip(("top_logit", "top_index", "count"),
                                      got, want):
                    np.testing.assert_array_equal(
                        g, w, err_msg=f"{state} {name} gf={group_filtering} "
                                      f"from_rows={from_rows} k={top_k}")
                compared += int((want[1] >= 0).sum())
    if n_live == 0:
        assert compared == 0
    elif state != "one_row_in_last_chunk":
        assert compared > 0


def _scan_rows():
    return tuple(child.value for child in DM._SCAN_ROWS_CHILDREN)


def _hwm_oracle(corpus):
    rows = np.flatnonzero(corpus.row_valid)
    return int(rows[-1]) + 1 if rows.size else 0


def _check_step(index, probe):
    """The host mark against the mask and the device's own bound, then one
    dispatched block's counter movement: once per scorer call, its
    K-escalation re-runs included."""
    corpus = index.corpus
    assert corpus.valid_hwm == _hwm_oracle(corpus)
    _, cvalid, _, _ = corpus.device_arrays()
    chunk = DM._CHUNK
    assert corpus.live_chunks(chunk) == int(S.live_chunks(cvalid, chunk))
    cache = index.scorer_cache
    want = cache._scanned_rows(corpus)
    escalations = telemetry.SCORER_ESCALATIONS.single().value
    before = _scan_rows()
    cache.score_block([probe], group_filtering=False)
    after = _scan_rows()
    calls = 1 + telemetry.SCORER_ESCALATIONS.single().value - escalations
    assert after[0] - before[0] == calls * want
    assert after[1] - before[1] == calls * corpus.capacity
    return want, calls > 1


def _drive(index):
    """Appends and tombstones, the tail among them, then appends again;
    returns the rows each step's dispatch scanned, and whether it
    escalated K."""
    chunk = DM._CHUNK
    records = _records(2 * chunk + 20, seed=21)
    probe = make_record("probe", name="acme corp", city="oslo", ssn="00042")
    steps = []
    for r in records:
        index.index(r)
    index.commit()
    steps.append(_check_step(index, probe))
    # re-index the tail: tombstones its rows and appends them again
    for r in records[-10:]:
        index.index(r)
    index.commit()
    steps.append(_check_step(index, probe))
    # delete the tail: the mark walks back over it, and over the rows the
    # re-index left dead below it
    for r in records[-25:]:
        index.delete(r)
    steps.append(_check_step(index, probe))
    # re-index a row now under the mark, then the last live one
    for r in (records[0], records[-26]):
        index.index(r)
        index.commit()
    steps.append(_check_step(index, probe))
    for r in _records(5, seed=22):
        index.index(r)
    index.commit()
    steps.append(_check_step(index, probe))
    # and everything deleted
    for r in records[:-25] + _records(5, seed=22):
        index.delete(r)
    steps.append(_check_step(index, probe))
    return [s for s, _ in steps], [e for _, e in steps]


def test_scan_rows_counter_follows_the_device_bound():
    index = DM.DeviceIndex(stresstest_schema())
    chunk = DM._CHUNK
    scanned, escalated = _drive(index)
    assert scanned[0] == 3 * chunk           # 2 chunks + 20 rows
    assert scanned[2] < scanned[1]           # the tail's deletion lowered it
    assert scanned[-1] == 0
    assert index.corpus._dead_runs == []
    # the probe's copies overflow K somewhere: re-runs were counted too
    assert any(escalated)
    index.close()


def test_sharded_scan_rows_counter_counts_the_capacity():
    from sesam_duke_microservice_tpu.engine.sharded_matcher import (
        ShardedDeviceIndex,
    )
    from sesam_duke_microservice_tpu.parallel.sharded import corpus_mesh

    index = ShardedDeviceIndex(stresstest_schema(),
                               mesh=corpus_mesh(jax.devices()[:2]))
    scanned, _ = _drive(index)
    assert set(scanned) == {index.corpus.capacity}
    index.close()


def test_valid_hwm_walk_back_and_recount():
    """The walk-back over dead runs, against the mask oracle, in a
    re-post loop that would walk a growing tail without the runs."""
    corpus = DM.DeviceCorpus(None, 1)
    feats = {"p": {"x": np.zeros((4, 2), np.float32)}}

    def append(n):
        return corpus.append({"p": {"x": np.zeros((n, 2), np.float32)}},
                             np.zeros(n, bool), np.zeros(n, np.int32),
                             [f"r{corpus.size + i}" for i in range(n)])

    corpus.append(feats, np.zeros(4, bool), np.zeros(4, np.int32),
                  ["a", "b", "c", "d"])
    assert corpus.valid_hwm == 4
    corpus.tombstone(1)
    assert corpus.valid_hwm == 4
    corpus.tombstone(3)
    assert corpus.valid_hwm == 3
    corpus.tombstone(2)
    assert corpus.valid_hwm == 1
    rows = append(3)
    for _ in range(20):
        for row in rows[::-1]:
            corpus.tombstone(int(row))
            assert corpus.valid_hwm == _hwm_oracle(corpus)
        rows = append(3)
        assert len(corpus._dead_runs) == 1
    corpus.tombstone(0)
    assert corpus.valid_hwm == corpus.size
    for row in rows:
        corpus.tombstone(int(row))
        assert corpus.valid_hwm == _hwm_oracle(corpus)
    assert corpus.valid_hwm == 0 and corpus._dead_runs == []
    corpus.row_valid[5] = True
    corpus.recount_masks()
    assert corpus.valid_hwm == 6 and corpus._dead_runs == []
    assert corpus.live_rows == 1
