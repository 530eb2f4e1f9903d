"""Per-property q-gram set widths sized from the data.

The device matcher gives each QGram property the smallest power-of-two set
width, at least 8, strictly greater than the most q-gram windows of any
value it has held, capped by DEVICE_MAX_GRAMS.  Slots past a set's size
hold SET_PAD and are never counted, so a narrower width changes no link:
the runs here are compared event for event, confidences bit for bit,
against a run pinned at the cap."""

from dataclasses import replace

import pytest

from sesam_duke_microservice_tpu import telemetry
from sesam_duke_microservice_tpu.core import comparators as C
from sesam_duke_microservice_tpu.core.config import DukeSchema, MatchTunables
from sesam_duke_microservice_tpu.core.records import ID_PROPERTY_NAME, Property
from sesam_duke_microservice_tpu.engine import device_matcher as DM
from sesam_duke_microservice_tpu.engine.device_matcher import (
    DeviceIndex,
    DeviceProcessor,
)
from sesam_duke_microservice_tpu.ops import feature_cache as FC
from sesam_duke_microservice_tpu.ops import features as F

from test_device_matcher import make_record

CAP = 64


def ssn_schema():
    return DukeSchema(
        threshold=0.8,
        maybe_threshold=0.6,
        properties=[
            Property(ID_PROPERTY_NAME, id_property=True),
            Property("name", C.Levenshtein(), 0.3, 0.9),
            Property("ssn", C.QGram(), 0.2, 0.9),
        ],
        data_sources=[],
    )


class Events:
    """Every listener event with its exact confidence."""

    def __init__(self):
        self.events = []

    def batch_ready(self, n):
        pass

    def batch_done(self):
        pass

    def matches(self, r1, r2, conf):
        self.events.append(("match", r1.record_id, r2.record_id, conf))

    def matches_perhaps(self, r1, r2, conf):
        self.events.append(("maybe", r1.record_id, r2.record_id, conf))

    def no_match_for(self, r):
        self.events.append(("none", r.record_id))


NAMES = ["kari nordmann", "ola hansen", "per olsen", "anne berg"]


def short_batch():
    """8-digit SSNs, with near duplicates (one digit changed)."""
    out = []
    for i in range(12):
        ssn = f"{10000000 + 7919 * (i // 2):08d}"
        if i % 2:
            ssn = ssn[:5] + str((int(ssn[5]) + 1) % 10) + ssn[6:]
        out.append(make_record(f"s{i}", name=NAMES[i % 4], ssn=ssn))
    return out


LONG = "NO-8841-2207-5519-3306-4418-AB"   # 30 characters, 29 bigrams


def long_batch():
    """Values past width 8 whose differences lie deep in the value."""
    assert len(LONG) == 30
    return [
        make_record("l0", name="kari nordmann", ssn=LONG),
        make_record("l1", name="kari nordmann", ssn=LONG[:-4] + "9-AC"),
        make_record("l2", name="ola hansen", ssn=LONG[:12] + "7777-1111-22"),
        make_record("l3", name="kari nordmann", ssn="10000000"),
    ]


def run(batches, *, pin=None):
    index = DeviceIndex(ssn_schema(), tunables=MatchTunables())
    if pin is not None:
        ssn_spec(index).max_grams = pin
    proc = DeviceProcessor(ssn_schema(), index)
    log = Events()
    proc.add_match_listener(log)
    for batch in batches:
        proc.deduplicate(batch)
    return index, log


def device_scores(index, queries):
    """{(query id, candidate id): f32 device logit} of every survivor the
    scorer keeps for ``queries`` (the pruning scores, before finalize)."""
    result = index.scorer_cache.score_block(queries, group_filtering=False)
    return {(r.record_id, index.corpus.row_ids[row]): logit
            for i, r in enumerate(queries)
            for row, logit in result.survivors(i)}


def ssn_spec(index_or_plan):
    plan = index_or_plan
    if isinstance(plan, DeviceIndex):
        plan = plan.plan
    return next(s for s in plan.device_props if s.name == "ssn")


@pytest.fixture
def cap64(monkeypatch):
    monkeypatch.setattr(F, "MAX_GRAMS", CAP)


def test_eight_digit_values_run_at_width_eight(cap64):
    index, _ = run([short_batch()])
    assert ssn_spec(index).grams == 8
    assert index.corpus.feats["ssn"]["grams"].shape[-1] == 8
    assert int(index.corpus.feats["ssn"]["gram_count"].max()) <= 7
    assert index.set_width_grows == 0


SERVICE_XML = """
<DukeMicroService>
  <Deduplication name="people" link-database-type="in-memory">
    <duke>
      <schema>
        <threshold>0.8</threshold>
        <property><name>NAME</name>
          <comparator>levenshtein</comparator><low>0.3</low><high>0.9</high>
        </property>
        <property><name>SSN</name>
          <comparator>qgram</comparator><low>0.2</low><high>0.9</high>
        </property>
      </schema>
      <data-source class="io.sesam.dukemicroservice.IncrementalDeduplicationDataSource">
        <param name="dataset-id" value="crm"/>
        <column name="name" property="NAME"/>
        <column name="ssn" property="SSN"/>
      </data-source>
    </duke>
  </Deduplication>
</DukeMicroService>
"""


def test_metrics_page_shows_the_width_and_its_growth(cap64):
    from sesam_duke_microservice_tpu.core.config import parse_config
    from sesam_duke_microservice_tpu.service.app import DukeApp

    app = DukeApp(parse_config(SERVICE_XML), backend="device",
                  persistent=False)
    labels = 'kind="deduplication",workload="people"'

    def page():
        return telemetry.render(app.metrics, telemetry.GLOBAL)

    try:
        wl = app.deduplications["people"]
        wl.process_batch("crm", [
            {"_id": f"a{i}", "name": NAMES[i % 4], "ssn": f"{12345670 + i}"}
            for i in range(6)])
        text = page()
        assert f'duke_device_set_width{{{labels},property="SSN"}} 8' in text
        assert f"duke_device_set_width_grows_total{{{labels}}} 0" in text
        wl.process_batch("crm", [
            {"_id": "b0", "name": "ola hansen", "ssn": LONG}])
        text = page()
        assert f'duke_device_set_width{{{labels},property="SSN"}} 32' in text
        assert f"duke_device_set_width_grows_total{{{labels}}} 1" in text
    finally:
        app.close()


@pytest.mark.parametrize("need,width", [
    (0, 8), (1, 8), (7, 8), (8, 16), (15, 16), (16, 32), (29, 32),
    (63, 64), (64, 64), (200, 64),
])
def test_width_is_the_power_of_two_above_the_need(cap64, need, width):
    index = DeviceIndex(ssn_schema(), tunables=MatchTunables())
    assert index._sized_grams(ssn_spec(index), need) == width


def test_windows_needed_counts_code_points():
    spec = ssn_spec(F.SchemaFeatures.plan(ssn_schema()))
    need = DeviceIndex._grams_needed
    assert need(spec, []) == 0
    assert need(spec, [make_record("a", ssn="7")]) == 1
    assert need(spec, [make_record("a", ssn="12345678"),
                       make_record("b", ssn="123")]) == 7
    assert need(spec, [make_record("a", ssn="åøæx")]) == 3


def test_long_value_grows_once_and_links_equal_the_cap_run(cap64):
    index = DeviceIndex(ssn_schema(), tunables=MatchTunables())
    proc = DeviceProcessor(ssn_schema(), index)
    log = Events()
    proc.add_match_listener(log)
    proc.deduplicate(short_batch())
    assert ssn_spec(index).grams == 8 and index.set_width_grows == 0
    corpus = index.corpus
    proc.deduplicate(long_batch())
    # one growth, through a rebuilt corpus at the new width
    assert ssn_spec(index).grams == 32
    assert index.corpus is not corpus
    assert index.corpus.feats["ssn"]["grams"].shape[-1] == 32
    assert index.set_width_grows == 1
    # the same lengths again: no further growth
    proc.deduplicate([make_record("l9", name="per olsen", ssn=LONG[::-1])])
    assert index.set_width_grows == 1

    pinned_index, pinned = run(
        [short_batch(), long_batch(),
         [make_record("l9", name="per olsen", ssn=LONG[::-1])]], pin=CAP)
    assert log.events == pinned.events
    queries = long_batch() + short_batch()
    assert device_scores(index, queries) == device_scores(pinned_index,
                                                          queries)
    assert any(e[0] == "match" and {e[1], e[2]} == {"l0", "l1"}
               for e in log.events)


def test_truncation_only_at_the_cap(monkeypatch):
    # under a cap of 16 the 29-window value clamps: it fills its slots,
    # which flags its pairs into the host-rescore residue as before
    monkeypatch.setattr(F, "MAX_GRAMS", 16)
    index, _ = run([short_batch(), long_batch()])
    assert ssn_spec(index).grams == 16
    counts = index.corpus.feats["ssn"]["gram_count"][: index.corpus.size]
    assert int(counts.max()) == 16
    assert int(counts[:12].max()) <= 7


def test_probe_wider_than_the_corpus_rides_a_wider_query_set(cap64):
    index, _ = run([short_batch()])
    # 15 bigram windows, 10 distinct bigrams: more than 8 slots hold
    probe = make_record("p", name="kari nordmann", ssn="1000000012345678")
    qplan = index._query_plan([probe])
    assert ssn_spec(qplan).grams == 16
    assert ssn_spec(index).grams == 8    # the corpus does not widen
    pinned, _ = run([short_batch()], pin=CAP)
    scores = device_scores(index, [probe])
    assert ("p", "s0") in scores         # ssn 10000000: both its bigrams
    assert scores == device_scores(pinned, [probe])


def test_snapshot_keeps_the_width_and_refuses_another(cap64, tmp_path):
    index, _ = run([short_batch()])
    path = str(tmp_path / "snap.npz")
    index.snapshot_save(path)

    fresh = DeviceIndex(ssn_schema(), tunables=MatchTunables())
    assert fresh.snapshot_load(path, dict(index.records)) is True
    assert ssn_spec(fresh).grams == 8
    assert fresh.corpus.feats["ssn"]["grams"].shape[-1] == 8

    wider = DeviceIndex(ssn_schema(), tunables=MatchTunables())
    ssn_spec(wider).max_grams = 16
    rejected = telemetry.SNAPSHOT_FALLBACKS.labels(reason="schema")
    before = rejected.value
    assert wider.snapshot_load(path, dict(index.records)) is False
    assert rejected.value == before + 1
    assert ssn_spec(wider).grams == 16 and wider.corpus.size == 0


def test_program_and_feature_keys_differ_by_width(cap64):
    index = DeviceIndex(ssn_schema(), tunables=MatchTunables())
    plan8 = index.plan
    plan16 = F.SchemaFeatures(
        device_props=[replace(s, max_grams=16) if s.name == "ssn" else s
                      for s in plan8.device_props],
        host_props=plan8.host_props,
    )
    cache = index.scorer_cache
    keys = [cache._store_key(p, 64, False, True, 1024, 8)
            for p in (plan8, plan16)]
    assert keys[0] != keys[1]
    assert DM._plan_fingerprint(plan8) != DM._plan_fingerprint(plan16)
    assert FC.plan_fingerprint(plan8) != FC.plan_fingerprint(plan16)
    feats = [F.extract_batch(p, short_batch()) for p in (plan8, plan16)]
    assert feats[0]["ssn"]["grams"].shape[-1] == 8
    assert feats[1]["ssn"]["grams"].shape[-1] == 16
