"""Native (C++) comparator parity against the pure-Python oracles.

The pure-Python bodies in core/comparators.py are the semantic reference
(they in turn pin the reference's Duke 1.2 comparator behavior); the ctypes
library must agree on every pair, including empty strings, unicode, and
lengths crossing the Myers 64-codepoint boundary.
"""

import random

import numpy as np
import pytest

from sesam_duke_microservice_tpu import native
from sesam_duke_microservice_tpu.core import comparators as C

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native comparator library unavailable"
)

ALPHABET = "abcdefgh 0123456789åßñ漢字"


def _rand_string(rng, max_len=100):
    n = rng.randint(0, max_len)
    return "".join(rng.choice(ALPHABET) for _ in range(n))


def _pairs(seed=7, n=300, max_len=100):
    rng = random.Random(seed)
    pairs = [("", ""), ("", "abc"), ("abc", ""), ("same", "same"),
             ("a" * 70, "a" * 69 + "b"), ("x" * 65, "y" * 65)]
    for _ in range(n):
        a = _rand_string(rng, max_len)
        # half the pairs are mutations of a (realistic near-duplicates)
        if rng.random() < 0.5:
            b = list(a)
            for _ in range(rng.randint(0, 4)):
                if not b:
                    break
                op = rng.choice("ids")
                pos = rng.randrange(len(b))
                if op == "i":
                    b.insert(pos, rng.choice(ALPHABET))
                elif op == "d":
                    del b[pos]
                else:
                    b[pos] = rng.choice(ALPHABET)
            b = "".join(b)
        else:
            b = _rand_string(rng, max_len)
        pairs.append((a, b))
    return pairs


@pytest.fixture
def pure(monkeypatch):
    """Force the pure-Python comparator path."""
    monkeypatch.setattr(C, "_NATIVE", None)
    yield
    # monkeypatch restores _NATIVE (back to the resolved module)


def test_levenshtein_parity(pure):
    lev = C.Levenshtein()
    pairs = _pairs()
    expected = [lev.compare(a, b) for a, b in pairs]
    got = native.lev_sim_batch([a for a, _ in pairs], [b for _, b in pairs])
    for (a, b), e, g in zip(pairs, expected, got):
        assert abs(e - g) < 1e-12, (a, b, e, g)


def test_jaro_winkler_parity(pure):
    jw = C.JaroWinkler()
    pairs = _pairs(seed=11)
    expected = [jw.compare(a, b) for a, b in pairs]
    got = native.jaro_winkler_batch([a for a, _ in pairs],
                                    [b for _, b in pairs])
    for (a, b), e, g in zip(pairs, expected, got):
        assert abs(e - g) < 1e-12, (a, b, e, g)


def test_jaro_winkler_custom_params_parity(pure):
    jw = C.JaroWinkler()
    jw.prefix_scale = 0.2
    jw.boost_threshold = 0.5
    jw.max_prefix = 2
    pairs = _pairs(seed=13, n=100, max_len=30)
    for a, b in pairs:
        e = jw.compare(a, b)
        g = float(native.jaro_winkler_batch(
            [a], [b], prefix_scale=0.2, boost_threshold=0.5, max_prefix=2)[0])
        assert abs(e - g) < 1e-12, (a, b, e, g)


def test_weighted_levenshtein_parity_ascii(pure):
    wl = C.WeightedLevenshtein()
    rng = random.Random(17)
    ascii_alphabet = "abc XY12345-#"
    for _ in range(200):
        a = "".join(rng.choice(ascii_alphabet) for _ in range(rng.randint(0, 40)))
        b = "".join(rng.choice(ascii_alphabet) for _ in range(rng.randint(0, 40)))
        e = wl.compare(a, b)
        g = float(native.weighted_lev_batch([a], [b])[0])
        assert abs(e - g) < 1e-12, (a, b, e, g)


def test_native_dispatch_used_by_comparators():
    """With the library available the comparator classes route through it
    and still produce oracle-identical values (spot check)."""
    assert C._native_module() is not None
    lev = C.Levenshtein()
    assert lev.compare("jonathan smithe", "jonathan smith") == pytest.approx(
        1.0 - 1.0 / 14.0, abs=1e-12
    )
    jw = C.JaroWinkler()
    assert jw.compare("martha", "marhta") == pytest.approx(0.9611111111, abs=1e-9)


def test_lev_distance_exact():
    assert native.lev_distance("kitten", "sitting") == 3
    assert native.lev_distance("", "abc") == 3
    assert native.lev_distance("a" * 80, "a" * 79 + "b") == 1


def test_native_handles_lone_surrogates():
    """json.loads accepts lone surrogates ('"\\ud800abc"'); the native path
    must score them identically to pure Python instead of raising
    UnicodeEncodeError (utf-32 surrogatepass encoding)."""
    from sesam_duke_microservice_tpu.core import comparators as C

    s1 = "\ud800abc"
    s2 = "xabc"
    lev = C.Levenshtein()
    jw = C.JaroWinkler()
    saved = C._NATIVE
    C._NATIVE = None
    try:
        want_lev = lev.compare(s1, s2)
        want_jw = jw.compare(s1, s2)
    finally:
        C._NATIVE = saved
    assert lev.compare(s1, s2) == pytest.approx(want_lev)
    assert jw.compare(s1, s2) == pytest.approx(want_jw)

    from sesam_duke_microservice_tpu import native

    if native.available():
        assert native.lev_sim(s1, s2) == pytest.approx(want_lev)


def test_embed_batch_matches_numpy_oracle():
    from sesam_duke_microservice_tpu import native
    from sesam_duke_microservice_tpu.ops import encoder as E

    if not native.available():
        pytest.skip("native library unavailable")

    from test_device_matcher import dedup_schema, random_records

    schema = dedup_schema()
    enc = E.RecordEncoder(schema, 128)
    records = random_records(120, seed=9)
    # unicode + empty-field coverage
    records[0].set_values("name", ["åse blåbærsyltetøy 中文"])
    records[1].set_values("name", [""])

    nat = enc.encode_batch(records)
    saved = E._native_embed
    try:
        E._native_embed = lambda: None
        ref = enc.encode_batch(records)
    finally:
        E._native_embed = saved
    np.testing.assert_allclose(nat, ref, atol=1e-6)


def test_library_is_keyed_by_source_hash(tmp_path, monkeypatch):
    """A library built from other source is never loaded: the file name
    moves with the content of duke_native.cpp, not with its mtime."""
    src = tmp_path / "duke_native.cpp"
    src.write_text("int f() { return 1; }")
    monkeypatch.setattr(native, "_SRC", str(src))
    first = native._lib_path()
    assert native._lib_path() == first
    src.write_text("int f() { return 2; }")
    assert native._lib_path() != first
