"""Shared-memory parallel feature extraction (VERDICT r4 #6).

The differential contract: the process-pool + shared-memory path must
produce BIT-IDENTICAL tensors to the serial extractor for every feature
kind, including the ANN embedding, uint16 char units, and auto-width
specs.  Throughput is environment-bound (this CI host exposes ONE core,
where any process pool loses by construction — the r4 finding); the
speedup claim belongs to multi-core deployments and is not asserted
here.
"""

import numpy as np
import pytest

from sesam_duke_microservice_tpu.core import comparators as C
from sesam_duke_microservice_tpu.core.config import DukeSchema
from sesam_duke_microservice_tpu.core.records import (
    ID_PROPERTY_NAME,
    Property,
    Record,
)
from sesam_duke_microservice_tpu.ops import encoder as E
from sesam_duke_microservice_tpu.ops import features as F
from sesam_duke_microservice_tpu.ops import parallel_extract as PX


def _schema():
    return DukeSchema(
        threshold=0.8, maybe_threshold=None,
        properties=[
            Property(ID_PROPERTY_NAME, id_property=True),
            Property("name", C.Levenshtein(), 0.3, 0.9),
            Property("city", C.QGram(), 0.3, 0.85),
            Property("amount", C.Numeric(), 0.4, 0.7),
        ],
        data_sources=[],
    )


def _records(n, with_unicode=True):
    import random

    rng = random.Random(11)
    out = []
    for i in range(n):
        r = Record()
        r.add_value(ID_PROPERTY_NAME, f"r{i}")
        name = f"acme {rng.randint(0, 999)} corp {i % 77}"
        if with_unicode and i % 7 == 0:
            name += " \U0001D4B3å"
        r.add_value("name", name)
        if i % 5:  # some records lack the property entirely
            r.add_value("city", rng.choice(["oslo", "bergen", "tromsø"]))
        r.add_value("amount", str(rng.randint(1, 10 ** 6)))
        if i % 11 == 0:  # multi-valued slot
            r.add_value("name", f"alias {i}")
        out.append(r)
    return out


@pytest.fixture(autouse=True)
def _force_two_workers(monkeypatch):
    monkeypatch.setenv("DEVICE_EXTRACT_WORKERS", "2")
    monkeypatch.setenv("DEVICE_EXTRACT_PARALLEL_MIN", "64")
    yield
    PX._shutdown()


def test_parallel_matches_serial_bit_exact():
    schema = _schema()
    plan = F.SchemaFeatures.plan(schema, values_per_record=2)
    enc = E.RecordEncoder(schema, 64)
    records = _records(700)

    par = PX.extract_batch_parallel(plan, records, encoder=enc)
    assert par is not None
    ser = F._extract_serial(plan, records)
    # storage-mode-aware ({emb} bf16, or {emb, scale} under DUKE_EMB_INT8)
    ser[E.ANN_PROP] = enc.corpus_tensors(records)

    assert set(par) == set(ser)
    for prop in ser:
        assert set(par[prop]) == set(ser[prop])
        for name in ser[prop]:
            a, b = ser[prop][name], par[prop][name]
            assert a.dtype == b.dtype, (prop, name)
            np.testing.assert_array_equal(
                np.asarray(a).view(np.uint16)
                if a.dtype == E.STORAGE_DTYPE else a,
                np.asarray(b).view(np.uint16)
                if b.dtype == E.STORAGE_DTYPE else b,
                err_msg=f"{prop}.{name}",
            )


def test_enabled_gating():
    assert not PX.enabled(10)          # below the slab threshold
    assert PX.enabled(100000)
    # a single-core default disables the pool entirely
    import os

    old = os.environ.pop("DEVICE_EXTRACT_WORKERS")
    try:
        if (os.cpu_count() or 1) < 4:
            assert not PX.enabled(100000)
    finally:
        os.environ["DEVICE_EXTRACT_WORKERS"] = old


def test_extract_batch_routes_through_parallel(monkeypatch):
    """extract_batch uses the pool above the threshold and falls back
    serially when the pool path reports failure."""
    schema = _schema()
    plan = F.SchemaFeatures.plan(schema)
    records = _records(200, with_unicode=False)

    calls = {"n": 0}
    real = PX.extract_batch_parallel

    def spy(plan_, records_, *, encoder=None):
        calls["n"] += 1
        return real(plan_, records_, encoder=encoder)

    monkeypatch.setattr(PX, "extract_batch_parallel", spy)
    out = F.extract_batch(plan, records)
    assert calls["n"] == 1 and "name" in out

    monkeypatch.setattr(
        PX, "extract_batch_parallel",
        lambda plan_, records_, encoder=None: None,
    )
    out2 = F.extract_batch(plan, records)  # serial fallback
    np.testing.assert_array_equal(
        out["name"]["chars"], out2["name"]["chars"]
    )
