"""Pallas tiled kernels: differential tests vs the XLA path and the scalar
oracle, in interpreter mode on the CPU test backend (tests/conftest.py)."""

import numpy as np
import pytest

from sesam_duke_microservice_tpu.core import comparators as C
from sesam_duke_microservice_tpu.ops import pairwise as pw
from sesam_duke_microservice_tpu.ops import pallas_kernels as pk

import jax.numpy as jnp


def _encode(strings, max_chars=16):
    n = len(strings)
    chars = np.zeros((n, max_chars), np.int32)
    lens = np.zeros((n,), np.int32)
    for i, s in enumerate(strings):
        cps = [ord(ch) for ch in s][:max_chars]
        chars[i, : len(cps)] = cps
        lens[i] = len(cps)
    return jnp.asarray(chars), jnp.asarray(lens)


QUERIES = ["kitten", "saturday", "abc", "", "flaw", "ab", "identical",
           "a" * 16, "xyzzy"]
CORPUS = ["sitting", "sunday", "abc", "lawn", "", "b", "identical",
          "a" * 12 + "bbbb", "plugh", "kitten"]


def test_myers_tiles_vs_flat_myers():
    qc, ql = _encode(QUERIES)
    cc, cl = _encode(CORPUS)
    got = np.asarray(
        pk.myers_distance_tiles(qc, ql, cc, cl, interpret=True)
    )
    # flat reference: expand pairs and run the XLA Myers kernel
    nq, nc = len(QUERIES), len(CORPUS)
    c1 = jnp.repeat(qc, nc, axis=0)
    l1 = jnp.repeat(ql, nc)
    c2 = jnp.tile(cc, (nq, 1))
    l2 = jnp.tile(cl, (nq,))
    want = np.asarray(pw.levenshtein_distance_myers(c1, l1, c2, l2)).reshape(
        nq, nc
    )
    np.testing.assert_array_equal(got, want)


def test_myers_tiles_vs_scalar_oracle():
    qc, ql = _encode(QUERIES)
    cc, cl = _encode(CORPUS)
    got = np.asarray(
        pk.myers_distance_tiles(qc, ql, cc, cl, interpret=True)
    )
    for i, s1 in enumerate(QUERIES):
        for j, s2 in enumerate(CORPUS):
            assert got[i, j] == C.levenshtein_distance(s1, s2), (s1, s2)


def test_myers_tiles_padding_sizes():
    # non-multiple-of-tile shapes round-trip through padding
    rng = np.random.default_rng(7)
    strings = [
        "".join(chr(97 + rng.integers(4)) for _ in range(rng.integers(0, 16)))
        for _ in range(13)
    ]
    qc, ql = _encode(strings[:5])
    cc, cl = _encode(strings)
    got = np.asarray(pk.myers_distance_tiles(qc, ql, cc, cl, interpret=True))
    assert got.shape == (5, 13)
    for i in range(5):
        for j in range(13):
            assert got[i, j] == C.levenshtein_distance(strings[i], strings[j])


def test_myers_two_word_tiles_vs_scalar_oracle():
    """32 < L <= 64 routes to the two-word Hyyro kernel; exact vs the
    scalar DP, including lengths straddling the word boundary."""
    rng = np.random.default_rng(11)
    lens = [0, 1, 31, 32, 33, 40, 47, 63, 64, 20, 50]
    strings = [
        "".join(chr(97 + rng.integers(5)) for _ in range(n)) for n in lens
    ]
    qc, ql = _encode(strings, max_chars=64)
    cc, cl = _encode(strings[::-1], max_chars=64)
    got = np.asarray(pk.myers_distance_tiles(qc, ql, cc, cl, interpret=True))
    rev = strings[::-1]
    for i, s1 in enumerate(strings):
        for j, s2 in enumerate(rev):
            assert got[i, j] == C.levenshtein_distance(s1, s2), (
                len(s1), len(s2), got[i, j]
            )


def test_myers_two_word_matches_one_word_on_short_strings():
    """The two-word kernel degenerates exactly to the one-word result when
    every pattern fits a single word (cross-check of the carry plumbing)."""
    qc, ql = _encode(QUERIES, max_chars=40)   # L=40 -> two-word kernel
    cc, cl = _encode(CORPUS, max_chars=40)
    got = np.asarray(pk.myers_distance_tiles(qc, ql, cc, cl, interpret=True))
    qc1, ql1 = _encode(QUERIES, max_chars=32)
    cc1, cl1 = _encode(CORPUS, max_chars=32)
    want = np.asarray(
        pk.myers_distance_tiles(qc1, ql1, cc1, cl1, interpret=True)
    )
    np.testing.assert_array_equal(got, want)


def test_myers_multiword_tiles_vs_scalar_oracle():
    """64 < L <= 256 routes to the N-word Hyyro kernel (VERDICT r2 #3);
    exact vs the scalar DP, including lengths straddling every word
    boundary in the 4-word (128-char) configuration."""
    rng = np.random.default_rng(13)
    lens = [0, 1, 31, 32, 33, 63, 64, 65, 95, 96, 97, 100, 127, 128]
    strings = [
        "".join(chr(97 + rng.integers(5)) for _ in range(n)) for n in lens
    ]
    qc, ql = _encode(strings, max_chars=128)
    cc, cl = _encode(strings[::-1], max_chars=128)
    got = np.asarray(pk.myers_distance_tiles(qc, ql, cc, cl, interpret=True))
    rev = strings[::-1]
    for i, s1 in enumerate(strings):
        for j, s2 in enumerate(rev):
            assert got[i, j] == C.levenshtein_distance(s1, s2), (
                len(s1), len(s2), got[i, j]
            )


def test_myers_eight_word_tiles_vs_scalar_oracle():
    """The MYERS_MAX_CHARS=256 (8-word) configuration stays exact —
    long-text schemas (addresses, titles) ride the Pallas path."""
    rng = np.random.default_rng(17)
    lens = [0, 1, 64, 128, 129, 191, 192, 193, 255, 256, 200]
    strings = [
        "".join(chr(97 + rng.integers(4)) for _ in range(n)) for n in lens
    ]
    qc, ql = _encode(strings, max_chars=256)
    cc, cl = _encode(strings[::-1], max_chars=256)
    got = np.asarray(pk.myers_distance_tiles(qc, ql, cc, cl, interpret=True))
    rev = strings[::-1]
    for i, s1 in enumerate(strings):
        for j, s2 in enumerate(rev):
            assert got[i, j] == C.levenshtein_distance(s1, s2), (
                len(s1), len(s2), got[i, j]
            )


def test_myers_multiword_matches_two_word_on_short_strings():
    """The 4-word kernel degenerates exactly to the 2-word result when
    every pattern fits 64 chars (cross-check of the carry chain)."""
    qc, ql = _encode(QUERIES, max_chars=100)   # L=100 -> 4-word kernel
    cc, cl = _encode(CORPUS, max_chars=100)
    got = np.asarray(pk.myers_distance_tiles(qc, ql, cc, cl, interpret=True))
    qc1, ql1 = _encode(QUERIES, max_chars=40)  # L=40 -> 2-word kernel
    cc1, cl1 = _encode(CORPUS, max_chars=40)
    want = np.asarray(
        pk.myers_distance_tiles(qc1, ql1, cc1, cl1, interpret=True)
    )
    np.testing.assert_array_equal(got, want)


def test_levenshtein_sim_tiles_matches_comparator():
    qc, ql = _encode(QUERIES)
    cc, cl = _encode(CORPUS)
    equal = np.zeros((len(QUERIES), len(CORPUS)), bool)
    for i, s1 in enumerate(QUERIES):
        for j, s2 in enumerate(CORPUS):
            equal[i, j] = s1 == s2
    sim = np.asarray(
        pk.levenshtein_sim_tiles(
            qc, ql, cc, cl, jnp.asarray(equal), interpret=True
        )
    )
    lev = C.Levenshtein()
    for i, s1 in enumerate(QUERIES):
        for j, s2 in enumerate(CORPUS):
            want = lev.compare(s1, s2)
            assert sim[i, j] == pytest.approx(want, abs=1e-6), (s1, s2)


def test_scoring_program_with_pallas_enabled(monkeypatch):
    """End-to-end: the scoring program routed through the pallas path agrees
    with the XLA path on top-K results."""
    monkeypatch.setenv("DUKE_TPU_PALLAS", "0")
    import jax

    from sesam_duke_microservice_tpu.core.config import DukeSchema
    from sesam_duke_microservice_tpu.core.records import (
        ID_PROPERTY_NAME,
        Property,
        Record,
    )
    from sesam_duke_microservice_tpu.ops import features as F
    from sesam_duke_microservice_tpu.ops import scoring as S

    schema = DukeSchema(
        threshold=0.8,
        maybe_threshold=None,
        properties=[
            Property(ID_PROPERTY_NAME, id_property=True),
            Property("NAME", C.Levenshtein(), 0.3, 0.88),
        ],
        data_sources=[],
    )
    plan = F.SchemaFeatures.plan(schema)
    names = ["oslo", "osло", "bergen", "bergn", "trondheim", "stavanger",
             "stavangr", "tromso"]
    records = []
    for i, nm in enumerate(names):
        r = Record()
        r.add_value(ID_PROPERTY_NAME, f"d__{i}")
        r.add_value("NAME", nm)
        records.append(r)
    feats = F.extract_batch(plan, records)
    def to_dev(t):
        return {p: {k: jnp.asarray(a) for k, a in d.items()}
                for p, d in t.items()}
    dev = to_dev(feats)
    n = len(records)
    valid = jnp.ones((n,), bool)
    deleted = jnp.zeros((n,), bool)
    group = jnp.full((n,), -1, jnp.int32)
    qrow = jnp.arange(n, dtype=jnp.int32)
    qgroup = jnp.full((n,), -2, jnp.int32)

    def run():
        pair_logits = S.build_pair_logits(plan)
        return jax.tree_util.tree_map(
            np.asarray,
            S.scan_topk(
                pair_logits, dev, dev, valid, deleted, group, qgroup, qrow,
                jnp.float32(0.0), chunk=4, top_k=4, group_filtering=False,
            ),
        )

    base = run()
    monkeypatch.setenv("DUKE_TPU_PALLAS", "1")
    pal = run()
    np.testing.assert_allclose(pal[0], base[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(pal[1], base[1])
    np.testing.assert_array_equal(pal[2], base[2])


def test_myers_gathered_vs_scalar_oracle():
    """The gathered-candidate (ANN rescoring layout) kernel: candidate c of
    query q is a specific row, exact vs the scalar DP."""
    rng = np.random.default_rng(5)
    q, c, L = 6, 7, 16
    qs = ["kitten", "saturday", "", "abc", "a" * 16, "flaw"]
    cands = [
        ["".join(chr(97 + rng.integers(5))
                 for _ in range(rng.integers(0, L + 1)))
         for _ in range(c)]
        for _ in range(q)
    ]
    qc, ql = _encode(qs, max_chars=L)
    cc = np.zeros((q, c, L), np.int32)
    cl = np.zeros((q, c), np.int32)
    for i in range(q):
        ch, ln = _encode(cands[i], max_chars=L)
        cc[i] = np.asarray(ch)
        cl[i] = np.asarray(ln)
    got = np.asarray(pk.myers_distance_gathered(
        qc, ql, jnp.asarray(cc), jnp.asarray(cl), interpret=True
    ))
    for i in range(q):
        for j in range(c):
            assert got[i, j] == C.levenshtein_distance(qs[i], cands[i][j]), (
                qs[i], cands[i][j]
            )


def test_gathered_pair_logits_pallas_wiring(monkeypatch):
    """build_gathered_pair_logits routes single-value Levenshtein through
    the gathered kernel and agrees with the flat path."""
    import jax

    from sesam_duke_microservice_tpu.core.config import DukeSchema
    from sesam_duke_microservice_tpu.core.records import (
        ID_PROPERTY_NAME,
        Property,
        Record,
    )
    from sesam_duke_microservice_tpu.ops import features as F
    from sesam_duke_microservice_tpu.ops import scoring as S

    schema = DukeSchema(
        threshold=0.8, maybe_threshold=None,
        properties=[
            Property(ID_PROPERTY_NAME, id_property=True),
            Property("NAME", C.Levenshtein(), 0.3, 0.88),
        ],
        data_sources=[],
    )
    plan = F.SchemaFeatures.plan(schema)
    names = ["oslo", "bergen", "bergn", "trondheim", "stavanger", "tromso"]
    records = []
    for i, nm in enumerate(names):
        r = Record()
        r.add_value(ID_PROPERTY_NAME, f"d__{i}")
        r.add_value("NAME", nm)
        records.append(r)
    feats = F.extract_batch(plan, records)
    n = len(records)
    c = 4
    rng = np.random.default_rng(0)
    rows = rng.integers(0, n, size=(n, c))
    qf = {p: {k: jnp.asarray(a) for k, a in t.items()}
          for p, t in feats.items()}
    cf = {p: {k: jnp.asarray(a[rows.reshape(-1)]).reshape(
              (n, c) + a.shape[1:])
              for k, a in t.items()}
          for p, t in feats.items()}

    monkeypatch.setenv("DUKE_TPU_PALLAS", "0")
    base = np.asarray(S.build_gathered_pair_logits(plan)(qf, cf))
    monkeypatch.setenv("DUKE_TPU_PALLAS", "1")
    pal = np.asarray(S.build_gathered_pair_logits(plan)(qf, cf))
    np.testing.assert_allclose(pal, base, rtol=1e-5, atol=1e-5)


def _encode_sets(value_lists, slots=12):
    from sesam_duke_microservice_tpu.ops.features import SET_PAD

    n = len(value_lists)
    grams = np.full((n, slots), SET_PAD, np.int32)
    counts = np.zeros((n,), np.int32)
    rng = np.random.default_rng(42)
    pool = rng.integers(-2**31, 2**31 - 1, size=1000).astype(np.int32)
    for i, ids in enumerate(value_lists):
        distinct = sorted({int(pool[k % 1000]) for k in ids})[:slots]
        grams[i, : len(distinct)] = distinct
        counts[i] = len(distinct)
    return jnp.asarray(grams), jnp.asarray(counts)


SETS_Q = [[1, 2, 3], [4, 5], [], [1, 2, 3, 4, 5, 6, 7], [9], [1, 9, 17]]
SETS_C = [[1, 2], [5], [3, 4, 5], [], [1, 2, 3, 4, 5, 6, 7], [8, 9, 10]]


def test_set_intersection_tiles_vs_flat():
    qg, qn = _encode_sets(SETS_Q)
    cg, cn = _encode_sets(SETS_C)
    got = np.asarray(
        pk.set_intersection_tiles(qg, qn, cg, cn, interpret=True)
    )
    nq, nc = len(SETS_Q), len(SETS_C)
    g1 = jnp.repeat(qg, nc, axis=0)
    n1 = jnp.repeat(qn, nc)
    g2 = jnp.tile(cg, (nq, 1))
    n2 = jnp.tile(cn, (nq,))
    want = np.asarray(pw.set_intersection_count(g1, n1, g2, n2)).reshape(
        nq, nc
    )
    np.testing.assert_array_equal(got, want)


def test_qgram_sim_tiles_vs_flat():
    qg, qn = _encode_sets(SETS_Q)
    cg, cn = _encode_sets(SETS_C)
    nq, nc = len(SETS_Q), len(SETS_C)
    equal = jnp.zeros((nq, nc), bool)
    for formula in ("overlap", "jaccard", "dice"):
        got = np.asarray(pk.set_sim_tiles(
            qg, qn, cg, cn, equal, formula=formula, interpret=True
        ))
        g1 = jnp.repeat(qg, nc, axis=0)
        n1 = jnp.repeat(qn, nc)
        g2 = jnp.tile(cg, (nq, 1))
        n2 = jnp.tile(cn, (nq,))
        want = np.asarray(pw.qgram_sim(
            g1, n1, g2, n2, equal.reshape(-1), formula=formula
        )).reshape(nq, nc)
        np.testing.assert_allclose(got, want, atol=1e-6)


def test_token_set_sim_tiles_vs_flat():
    qg, qn = _encode_sets(SETS_Q)
    cg, cn = _encode_sets(SETS_C)
    nq, nc = len(SETS_Q), len(SETS_C)
    equal = jnp.zeros((nq, nc), bool)
    for dice in (False, True):
        got = np.asarray(pk.set_sim_tiles(
            qg, qn, cg, cn, equal,
            formula="dice" if dice else "jaccard", interpret=True
        ))
        g1 = jnp.repeat(qg, nc, axis=0)
        n1 = jnp.repeat(qn, nc)
        g2 = jnp.tile(cg, (nq, 1))
        n2 = jnp.tile(cn, (nq,))
        want = np.asarray(pw.token_set_sim(
            g1, n1, g2, n2, equal.reshape(-1), dice=dice
        )).reshape(nq, nc)
        np.testing.assert_allclose(got, want, atol=1e-6)


def _sets_at_width(sizes_q, sizes_c, width, seed):
    """Query and corpus (N, width) SET_PAD-padded sets of the given sizes,
    all drawn from one universe of width + 2 ids, so full sets overlap
    heavily and a 1-element set lies inside most full ones; the first
    full corpus set equals the first full query set."""
    from sesam_duke_microservice_tpu.ops.features import SET_PAD

    rng = np.random.default_rng(seed)
    universe = rng.choice(2**31 - 2, size=width + 2, replace=False) - 2**30

    def draw(sizes):
        sets = np.full((len(sizes), width), SET_PAD, np.int32)
        for i, k in enumerate(sizes):
            sets[i, :k] = np.sort(rng.choice(universe, size=k, replace=False))
        return sets, np.asarray(sizes, np.int32)

    qs, qn = draw(sizes_q)
    cs, cn = draw(sizes_c)
    cs[sizes_c.index(width)] = qs[sizes_q.index(width)]
    return qs, qn, cs, cn


def _flat_pairs(qg, qn, cg, cn):
    nq, nc = qg.shape[0], cg.shape[0]
    return (jnp.repeat(qg, nc, axis=0), jnp.repeat(qn, nc),
            jnp.tile(cg, (nq, 1)), jnp.tile(cn, (nq,)))


@pytest.mark.parametrize("width", [8, 16, 24])
@pytest.mark.parametrize("formula", [None, "overlap", "jaccard", "dice"])
def test_set_tiles_at_sized_widths_vs_flat(width, formula):
    """Sets of 0, 1, width - 1 and width elements, and one tile that mixes
    1-element and full sets, at the widths the device matcher sizes to
    (the cap of the test shapes is 24): intersection counts, and the three
    set formulas, equal the flat XLA path's."""
    edge = [0, 1, width - 1, width]
    qg, qn, cg, cn = map(jnp.asarray, _sets_at_width(
        edge + [1, width] * 4, edge[::-1] + [width, 1] * 5, width,
        seed=width))
    nq, nc = qg.shape[0], cg.shape[0]
    g1, n1, g2, n2 = _flat_pairs(qg, qn, cg, cn)
    if formula is None:
        got = pk.set_intersection_tiles(qg, qn, cg, cn, interpret=True)
        want = pw.set_intersection_count(g1, n1, g2, n2).reshape(nq, nc)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert int(np.asarray(got).max()) == width   # full sets meet
        return
    equal = jnp.zeros((nq, nc), bool)
    got = pk.set_sim_tiles(qg, qn, cg, cn, equal, formula=formula,
                           interpret=True)
    want = pw.qgram_sim(g1, n1, g2, n2, equal.reshape(-1),
                        formula=formula).reshape(nq, nc)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_set_tiles_counts_do_not_depend_on_the_padded_width():
    """The same sets padded to width 8 and to width 24 give identical
    counts, and so do a width-8 side against a width-24 side (an
    http-transform probe wider than the corpus)."""
    from sesam_duke_microservice_tpu.ops.features import SET_PAD

    sizes_q = [0, 1, 7, 8, 1, 8, 3, 8]
    sizes_c = [8, 1, 0, 7, 8, 1, 5, 8, 2]
    qg8, qn, cg8, cn = _sets_at_width(sizes_q, sizes_c, 8, seed=3)

    def widen(sets):
        pad = np.full((sets.shape[0], 16), SET_PAD, np.int32)
        return jnp.asarray(np.concatenate([sets, pad], axis=1))

    qn, cn = jnp.asarray(qn), jnp.asarray(cn)
    at8 = np.asarray(pk.set_intersection_tiles(
        jnp.asarray(qg8), qn, jnp.asarray(cg8), cn, interpret=True))
    at24 = np.asarray(pk.set_intersection_tiles(
        widen(qg8), qn, widen(cg8), cn, interpret=True))
    mixed = np.asarray(pk.set_intersection_tiles(
        widen(qg8), qn, jnp.asarray(cg8), cn, interpret=True))
    np.testing.assert_array_equal(at8, at24)
    np.testing.assert_array_equal(at8, mixed)
    assert at8.max() == 8


def test_scoring_program_set_kernels_pallas_wiring(monkeypatch):
    """The GRAM_SET/TOKEN_SET pallas branch agrees with the XLA path."""
    monkeypatch.setenv("DUKE_TPU_PALLAS", "0")
    import jax

    from sesam_duke_microservice_tpu.core.config import DukeSchema
    from sesam_duke_microservice_tpu.core.records import (
        ID_PROPERTY_NAME,
        Property,
        Record,
    )
    from sesam_duke_microservice_tpu.ops import features as F
    from sesam_duke_microservice_tpu.ops import scoring as S

    schema = DukeSchema(
        threshold=0.8,
        maybe_threshold=None,
        properties=[
            Property(ID_PROPERTY_NAME, id_property=True),
            Property("SSN", C.QGram(), 0.2, 0.9),
            Property("TAGS", C.JaccardIndex(), 0.3, 0.8),
        ],
        data_sources=[],
    )
    plan = F.SchemaFeatures.plan(schema)
    rows = [("12345678", "red green"), ("12345679", "red green"),
            ("87654321", "blue"), ("12340078", "green yellow"),
            ("11112222", "red"), ("12345678", "purple orange")]
    records = []
    for i, (ssn, tags) in enumerate(rows):
        r = Record()
        r.add_value(ID_PROPERTY_NAME, f"d__{i}")
        r.add_value("SSN", ssn)
        r.add_value("TAGS", tags)
        records.append(r)
    feats = F.extract_batch(plan, records)
    def to_dev(t):
        return {p: {k: jnp.asarray(a) for k, a in d.items()}
                for p, d in t.items()}
    dev = to_dev(feats)
    n = len(records)
    valid = jnp.ones((n,), bool)
    deleted = jnp.zeros((n,), bool)
    group = jnp.full((n,), -1, jnp.int32)
    qrow = jnp.arange(n, dtype=jnp.int32)
    qgroup = jnp.full((n,), -2, jnp.int32)

    def run():
        pair_logits = S.build_pair_logits(plan)
        return jax.tree_util.tree_map(
            np.asarray,
            S.scan_topk(
                pair_logits, dev, dev, valid, deleted, group, qgroup, qrow,
                jnp.float32(0.0), chunk=2, top_k=4, group_filtering=False,
            ),
        )

    base = run()
    monkeypatch.setenv("DUKE_TPU_PALLAS", "1")
    pal = run()
    np.testing.assert_allclose(pal[0], base[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(pal[1], base[1])
    np.testing.assert_array_equal(pal[2], base[2])


JW_QUERIES = ["martha", "dixon", "jellyfish", "", "dwayne", "arnab",
              "aabbcc", "identical", "ab"]
JW_CORPUS = ["marhta", "dicksonx", "smellyfish", "word", "duane", "raabn",
             "ccbbaa", "identical", "", "ba"]


def test_jaro_winkler_tiles_vs_scalar_oracle():
    qc, ql = _encode(JW_QUERIES)
    cc, cl = _encode(JW_CORPUS)
    equal = jnp.zeros((len(JW_QUERIES), len(JW_CORPUS)), bool)
    got = np.asarray(pk.jaro_winkler_sim_tiles(
        qc, ql, cc, cl, equal, interpret=True
    ))
    jw = C.JaroWinkler()
    for i, s1 in enumerate(JW_QUERIES):
        for j, s2 in enumerate(JW_CORPUS):
            if not s1 or not s2:
                want = 0.0
            elif s1 == s2:
                want = 1.0  # kernel computes raw jaro = 1 for identical
            else:
                want = jw.compare(s1, s2)
            assert got[i, j] == pytest.approx(want, abs=1e-5), (s1, s2)


def test_jaro_winkler_tiles_vs_flat():
    qc, ql = _encode(JW_QUERIES)
    cc, cl = _encode(JW_CORPUS)
    nq, nc = len(JW_QUERIES), len(JW_CORPUS)
    equal = jnp.zeros((nq, nc), bool)
    got = np.asarray(pk.jaro_winkler_sim_tiles(
        qc, ql, cc, cl, equal, interpret=True
    ))
    c1 = jnp.repeat(qc, nc, axis=0)
    l1 = jnp.repeat(ql, nc)
    c2 = jnp.tile(cc, (nq, 1))
    l2 = jnp.tile(cl, (nq,))
    want = np.asarray(pw.jaro_winkler_sim(
        c1, l1, c2, l2, equal.reshape(-1),
        prefix_scale=0.1, boost_threshold=0.7, max_prefix=4,
    )).reshape(nq, nc)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_scoring_program_jw_pallas_wiring(monkeypatch):
    """The JaroWinkler CHARS pallas branch agrees with the XLA path."""
    monkeypatch.setenv("DUKE_TPU_PALLAS", "0")
    import jax

    from sesam_duke_microservice_tpu.core.config import DukeSchema
    from sesam_duke_microservice_tpu.core.records import (
        ID_PROPERTY_NAME,
        Property,
        Record,
    )
    from sesam_duke_microservice_tpu.ops import features as F
    from sesam_duke_microservice_tpu.ops import scoring as S

    schema = DukeSchema(
        threshold=0.8,
        maybe_threshold=None,
        properties=[
            Property(ID_PROPERTY_NAME, id_property=True),
            Property("CAPITAL", C.JaroWinkler(), 0.3, 0.85),
        ],
        data_sources=[],
    )
    plan = F.SchemaFeatures.plan(schema)
    names = ["oslo", "olso", "stockholm", "stokholm", "helsinki",
             "reykjavik", "copenhagen", "kobenhavn"]
    records = []
    for i, nm in enumerate(names):
        r = Record()
        r.add_value(ID_PROPERTY_NAME, f"d__{i}")
        r.add_value("CAPITAL", nm)
        records.append(r)
    feats = F.extract_batch(plan, records)
    def to_dev(t):
        return {p: {k: jnp.asarray(a) for k, a in d.items()}
                for p, d in t.items()}
    dev = to_dev(feats)
    n = len(records)
    valid = jnp.ones((n,), bool)
    deleted = jnp.zeros((n,), bool)
    group = jnp.full((n,), -1, jnp.int32)
    qrow = jnp.arange(n, dtype=jnp.int32)
    qgroup = jnp.full((n,), -2, jnp.int32)

    def run():
        pair_logits = S.build_pair_logits(plan)
        return jax.tree_util.tree_map(
            np.asarray,
            S.scan_topk(
                pair_logits, dev, dev, valid, deleted, group, qgroup, qrow,
                jnp.float32(0.0), chunk=4, top_k=4, group_filtering=False,
            ),
        )

    base = run()
    monkeypatch.setenv("DUKE_TPU_PALLAS", "1")
    pal = run()
    np.testing.assert_allclose(pal[0], base[0], rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(pal[1], base[1])
    np.testing.assert_array_equal(pal[2], base[2])
