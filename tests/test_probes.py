"""Black-box canary probe plane (ISSUE 20).

Four contracts pinned here:

  * **Invisibility differential** — with the prober ON (cycles actually
    running) vs OFF, the user-visible ``?since=`` feed rows and link
    rows are bit-identical (wall-clock ``_updated`` normalized), and the
    ``__probe__`` namespace is rejected outright at the HTTP surface.
  * **Fault drill** — a seeded ``probe_flip`` fault is caught within ONE
    cycle: latched mismatch ring entry with trace/decision joins,
    ``duke_probe_verdict_mismatches_total`` >= 1, ``/healthz`` flips to
    degraded with the per-workload detail.
  * **Per-range federation probing** — under ``fed_down=<g>`` exactly
    group *g*'s owned ranges fail their reachability probe, surfaced on
    the plane's ``/healthz`` and in the fleet rollup's
    ``duke_probe_range_checks_total``.
  * **Shared-ladder accounting** — the probe shadow resolves to the user
    workload's shared AOT ladder: a device-backend probe cycle adds ZERO
    ``duke_jit_compiles_total``.
"""

import json
import urllib.error
import urllib.request

import pytest

from sesam_duke_microservice_tpu import telemetry
from sesam_duke_microservice_tpu.core.config import parse_config
from sesam_duke_microservice_tpu.service.app import DukeApp, serve
from sesam_duke_microservice_tpu.telemetry import slo, tracing
from sesam_duke_microservice_tpu.telemetry.probes import (
    PROBE_PREFIX,
    _perturb_heavy,
    _perturb_light,
    _token,
    derive_canaries,
    is_probe_name,
    probe_name,
)
from sesam_duke_microservice_tpu.utils import faults

from test_federation import make_fed
from test_observability import parse_exposition

CONFIG_XML = """
<DukeMicroService>
  <Deduplication name="people" link-database-type="in-memory">
    <duke>
      <schema>
        <threshold>0.8</threshold>
        <maybe-threshold>0.7</maybe-threshold>
        <property><name>NAME</name>
          <comparator>levenshtein</comparator><low>0.1</low><high>0.95</high>
        </property>
        <property><name>EMAIL</name>
          <comparator>exact</comparator><low>0.2</low><high>0.95</high>
        </property>
      </schema>
      <data-source class="io.sesam.dukemicroservice.IncrementalDeduplicationDataSource">
        <param name="dataset-id" value="crm"/>
        <column name="name" property="NAME"/>
        <column name="email" property="EMAIL"/>
      </data-source>
    </duke>
  </Deduplication>
  <RecordLinkage name="pairing" link-mode="one-to-one" link-database-type="in-memory">
    <duke>
      <schema>
        <threshold>0.7</threshold>
        <property><name>NAME</name>
          <comparator>levenshtein</comparator><low>0.1</low><high>0.95</high>
        </property>
      </schema>
      <group>
        <data-source class="io.sesam.dukemicroservice.IncrementalRecordLinkageDataSource">
          <param name="dataset-id" value="left"/>
          <column name="name" property="NAME"/>
        </data-source>
      </group>
      <group>
        <data-source class="io.sesam.dukemicroservice.IncrementalRecordLinkageDataSource">
          <param name="dataset-id" value="right"/>
          <column name="name" property="NAME"/>
        </data-source>
      </group>
    </duke>
  </RecordLinkage>
</DukeMicroService>
"""

DEDUP_ONLY_XML = """
<DukeMicroService>
  <Deduplication name="people" link-database-type="in-memory">
    <duke>
      <schema>
        <threshold>0.8</threshold>
        <property><name>NAME</name>
          <comparator>levenshtein</comparator><low>0.1</low><high>0.95</high>
        </property>
        <property><name>EMAIL</name>
          <comparator>exact</comparator><low>0.2</low><high>0.95</high>
        </property>
      </schema>
      <data-source class="io.sesam.dukemicroservice.IncrementalDeduplicationDataSource">
        <param name="dataset-id" value="crm"/>
        <column name="name" property="NAME"/>
        <column name="email" property="EMAIL"/>
      </data-source>
    </duke>
  </Deduplication>
</DukeMicroService>
"""

USER_BATCH = [
    {"_id": "u1", "name": "alice smith", "email": "alice@example.no"},
    {"_id": "u2", "name": "alice smith", "email": "alice@example.no"},
    {"_id": "u3", "name": "bob jones", "email": "bob@example.no"},
]

# the dedup stresstest's shape: 8-digit codes under QGram, whose q-gram
# sets the device matcher sizes to its narrowest width
QGRAM_XML = DEDUP_ONLY_XML.replace(
    "<name>EMAIL</name>\n          <comparator>exact</comparator>",
    "<name>EMAIL</name>\n          <comparator>qgram</comparator>")
assert QGRAM_XML != DEDUP_ONLY_XML
QGRAM_BATCH = [dict(r, email=code) for r, code in
               zip(USER_BATCH, ("12345678", "12345678", "87654321"))]


@pytest.fixture(autouse=True)
def _clean_slate(monkeypatch):
    # force-enable regardless of the CI leg's DUKE_PROBE pin — the OFF
    # arm of the differential overrides per-app below
    monkeypatch.setenv("DUKE_PROBE", "1")
    monkeypatch.setenv("DUKE_PROBE_INTERVAL_S", "3600")
    monkeypatch.setenv("MIN_RELEVANCE", "0.05")
    faults.configure("")
    slo._reset_for_tests()
    yield
    faults.configure(None)
    slo._reset_for_tests()
    tracing.RECORDER.clear()


def make_app(xml=CONFIG_XML, backend="host"):
    return DukeApp(parse_config(xml), backend=backend, persistent=False)


def user_feed(wl):
    """Full user ``?since=`` walk, wall-clock ``_updated`` dropped."""
    rows, since = [], 0
    while True:
        page, nxt = wl.links_page(since, 500)
        if not page:
            break
        rows.extend(page)
        since = nxt
    out = []
    for r in rows:
        r = dict(r)
        r.pop("_updated", None)
        out.append(json.dumps(r, sort_keys=True))
    return sorted(out)


def user_links(wl):
    return sorted(
        (l.id1, l.id2, l.status.value, l.kind.value, round(l.confidence, 12))
        for l in wl.link_database.get_all_links()
    )


def request(url, method="GET", body=None):
    req = urllib.request.Request(url, data=body, method=method)
    try:
        with urllib.request.urlopen(req, timeout=60) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


# -- corpus derivation ---------------------------------------------------------


class TestCorpus:
    def test_perturbations_preserve_the_blocking_token(self):
        """Perturbed values keep word 1 intact — the canary must stay
        retrievable by exact-token blocking so it certifies scoring,
        not candidate-search recall."""
        v = _token("pair", "NAME", "ab")
        head = v.split(" ")[0]
        assert _perturb_light(v).split(" ")[0] == head
        assert _perturb_heavy(v).split(" ")[0] == head
        assert _perturb_light(v) != v
        assert _perturb_heavy(v) != v
        # deterministic: same inputs, same corpus, across processes
        assert v == _token("pair", "NAME", "ab")

    def test_oracle_verdicts_straddle_the_thresholds(self):
        app = make_app()
        try:
            app.prober.run_cycle()
            entry = app.prober._shadows[("deduplication", "people")]
            by_key = {c.key: c for c in entry.corpus}
            assert by_key["identical"].expected_verdict == "match"
            assert by_key["disjoint"].expected_verdict == "reject"
            # per-property near/far pairs exist for every mapped prop
            assert {"near-NAME", "far-NAME", "near-EMAIL",
                    "far-EMAIL"} <= set(by_key)
            # a light perturbation stays above threshold; the oracle
            # probability is recorded for the mismatch forensics
            assert by_key["near-NAME"].expected_prob > 0.8
        finally:
            app.close()


# -- tentpole: invisibility differential ---------------------------------------


class TestInvisibilityDifferential:
    def test_user_feed_and_links_bit_identical_prober_on_off(self, monkeypatch):
        # ON arm: probe cycles interleaved around the user ingest
        app_on = make_app()
        try:
            assert app_on.prober is not None
            app_on.prober.run_cycle()
            app_on.scheduler.submit(
                "deduplication", "people", "crm", list(USER_BATCH))
            app_on.prober.run_cycle()
            wl = app_on.deduplications["people"]
            feed_on, links_on = user_feed(wl), user_links(wl)
        finally:
            app_on.close()

        # OFF arm: DUKE_PROBE=0 restores today's behavior exactly
        monkeypatch.setenv("DUKE_PROBE", "0")
        app_off = make_app()
        try:
            assert app_off.prober is None
            app_off.scheduler.submit(
                "deduplication", "people", "crm", list(USER_BATCH))
            wl = app_off.deduplications["people"]
            feed_off, links_off = user_feed(wl), user_links(wl)
        finally:
            app_off.close()

        assert feed_on == feed_off
        assert links_on == links_off
        assert feed_on  # the differential is about something
        # nothing probe-namespaced leaks into the user surface
        assert not any(PROBE_PREFIX in row for row in feed_on)
        assert not any(is_probe_name(name) for name in app_on.deduplications)

    def test_probe_workloads_never_reach_the_registries(self):
        app = make_app()
        try:
            app.prober.run_cycle()
            assert len(app.prober._shadows) == 2
            assert not any(is_probe_name(n) for n in app.deduplications)
            assert not any(is_probe_name(n) for n in app.record_linkages)
            # the scheduler resolves probe names only through the prober
            assert app._resolve_workload(
                "deduplication", probe_name("people")) is not None
            assert app._resolve_workload(
                "deduplication", probe_name("nope")) is None
        finally:
            app.close()


# -- HTTP surface --------------------------------------------------------------


class TestHttpSurface:
    @pytest.fixture()
    def served(self):
        import threading

        app = make_app()
        server = serve(app, port=0, host="127.0.0.1")
        threading.Thread(target=server.serve_forever, daemon=True).start()
        base = f"http://127.0.0.1:{server.server_address[1]}"
        yield app, base
        server.shutdown()
        app.close()

    def test_probe_namespace_rejected(self, served):
        app, base = served
        app.prober.run_cycle()  # shadows exist — and still unreachable
        status, _ = request(
            f"{base}/deduplication/{PROBE_PREFIX}people/{PROBE_PREFIX}crm",
            "POST", json.dumps(USER_BATCH).encode())
        assert status == 404
        status, _ = request(
            f"{base}/deduplication/people/{PROBE_PREFIX}crm",
            "POST", json.dumps(USER_BATCH).encode())
        assert status == 404
        status, body = request(f"{base}/deduplication/{PROBE_PREFIX}people")
        assert status == 400 and b"reserved" in body

    def test_green_cycle_healthz_metrics_debug(self, served):
        app, base = served
        results = app.prober.run_cycle()
        assert results and all(r["ok"] for r in results.values())

        status, body = request(f"{base}/healthz")
        health = json.loads(body)
        assert status == 200 and health["status"] == "ok"
        assert "probe_verdict_mismatches" not in health

        status, body = request(f"{base}/metrics")
        metrics = parse_exposition(body.decode())
        for kind, name in (("deduplication", "people"),
                           ("recordlinkage", "pairing")):
            lbls = (("kind", kind), ("workload", name))
            assert metrics[("duke_probe_verdict_mismatches_total",
                            lbls)] == 0
            assert metrics[("duke_probe_freshness_seconds", lbls)] >= 0
            for stage in ("ingest", "score", "feed"):
                assert metrics[(
                    "duke_probe_e2e_seconds_count",
                    tuple(sorted(lbls + (("stage", stage),))))] == 1

        status, body = request(f"{base}/debug/probes")
        dbg = json.loads(body)
        assert status == 200 and dbg["enabled"]
        assert {w["workload"] for w in dbg["workloads"]} == {
            "people", "pairing"}
        assert all(w["last"]["ok"] for w in dbg["workloads"])
        assert dbg["mismatches"] == []

    def test_probe_flip_caught_within_one_cycle(self, served):
        """The fault drill the acceptance pins: one seeded verdict
        corruption -> latched ring entry + counter + /healthz flip,
        all observable after a single cycle."""
        app, base = served
        faults.configure("probe_flip=1")
        app.prober.run_cycle()

        status, body = request(f"{base}/healthz")
        health = json.loads(body)
        assert health["status"] == "degraded"
        detail = health["probe_verdict_mismatches"]
        assert detail["verdict_mismatches"] >= 1
        assert any(v >= 1 for v in detail["workloads"].values())

        status, body = request(f"{base}/metrics")
        metrics = parse_exposition(body.decode())
        total = sum(v for (fam, _), v in metrics.items()
                    if fam == "duke_probe_verdict_mismatches_total")
        assert total >= 1

        status, body = request(f"{base}/debug/probes")
        dbg = json.loads(body)
        assert len(dbg["mismatches"]) >= 1
        rec = dbg["mismatches"][0]
        assert rec["expected"] != rec["observed"]
        assert rec["trace"].startswith("/debug/traces/")
        # latched: the first mismatch survives any amount of green churn
        assert app.prober.ring.records()

        # a clean follow-up cycle heals the feed but the latch stays
        faults.configure("")
        app.prober.run_cycle()
        status, body = request(f"{base}/healthz")
        assert json.loads(body)["status"] == "degraded"


# -- federation: per-range probing ---------------------------------------------


class TestRangeProber:
    def test_fed_down_flags_only_that_groups_ranges(self, tmp_path):
        from sesam_duke_microservice_tpu.service.prober import RangeProber

        fed = make_fed(tmp_path, n_groups=2)
        try:
            prober = RangeProber(fed)
            out = prober.run_cycle()
            assert out and all(v == "ok" for v in out.values())
            assert prober.failing_ranges() == []

            faults.configure("fed_down=1")
            out = prober.run_cycle()
            down = sorted(r.range_id for r in fed.map.ranges()
                          if r.group == 1)
            up = sorted(r.range_id for r in fed.map.ranges()
                        if r.group == 0)
            assert sorted(r for r, v in out.items() if v == "fail") == down
            assert all(out[r] == "ok" for r in up)
            assert prober.failing_ranges() == down
            snap = prober.snapshot()
            for rid in down:
                assert snap["ranges"][rid]["last_error"] == "GroupUnavailable"
        finally:
            faults.configure("")
            fed.close()

    def test_plane_healthz_and_rollup_surface_range_failures(self, tmp_path):
        from sesam_duke_microservice_tpu.service.federation_plane import (
            serve_federation,
        )

        fed = make_fed(tmp_path, n_groups=2)
        server = serve_federation(fed)
        base = f"http://127.0.0.1:{server.server_address[1]}"
        try:
            prober = server.RequestHandlerClass.range_prober
            assert prober is not None
            prober.run_cycle()
            faults.configure("fed_down=1")
            prober.run_cycle()
            faults.configure("")

            status, body = request(f"{base}/healthz")
            health = json.loads(body)
            down = sorted(r.range_id for r in fed.map.ranges()
                          if r.group == 1)
            assert health["status"] == "degraded"
            assert health["probe_failing_ranges"] == down

            status, body = request(f"{base}/metrics")
            metrics = parse_exposition(body.decode())
            for rng in fed.map.ranges():
                fails = metrics[("duke_probe_range_checks_total",
                                 (("group", str(rng.group)),
                                  ("outcome", "fail"),
                                  ("range", rng.range_id)))]
                assert fails == (1 if rng.group == 1 else 0)

            status, body = request(f"{base}/debug/probes")
            dbg = json.loads(body)
            assert dbg["enabled"] and dbg["cycles"] == 2
        finally:
            server.shutdown()
            fed.close()


# -- shared AOT ladder: zero probe compiles ------------------------------------


class TestSharedLadder:
    def test_probe_cycle_adds_zero_jit_compiles(self, monkeypatch):
        """The probe shadow shares Property objects with the user
        workload, so its plan fingerprint resolves to the SAME shared
        AOT ladder — a full probe cycle on the device backend must not
        add a single XLA compile."""
        monkeypatch.setenv("DEVICE_PREWARM", "1")
        app = make_app(DEDUP_ONLY_XML, backend="device")
        try:
            wl = app.deduplications["people"]
            t = getattr(wl.index.scorer_cache, "_warm_thread", None)
            if t is not None:
                t.join(timeout=600)
            app.scheduler.submit(
                "deduplication", "people", "crm", list(USER_BATCH))
            before = telemetry.JIT_COMPILES.single().value
            results = app.prober.run_cycle()
            assert results[("deduplication", "people")]["ok"]
            assert telemetry.JIT_COMPILES.single().value == before
            state = app.prober._shadows[("deduplication", "people")].state
            assert state.probe_compiles == 0
        finally:
            app.close()

    def test_qgram_probe_cycle_adds_zero_jit_compiles(self, monkeypatch):
        """The same contract where a property's q-gram set width is sized
        from the data: the canaries' q-gram values stay within the
        narrowest set width, so the shadow's plan is still the user
        workload's."""
        monkeypatch.setenv("DEVICE_PREWARM", "1")
        app = make_app(QGRAM_XML, backend="device")
        try:
            wl = app.deduplications["people"]
            t = getattr(wl.index.scorer_cache, "_warm_thread", None)
            if t is not None:
                t.join(timeout=600)
            app.scheduler.submit(
                "deduplication", "people", "crm", list(QGRAM_BATCH))
            before = telemetry.JIT_COMPILES.single().value
            results = app.prober.run_cycle()
            assert results[("deduplication", "people")]["ok"]
            shadow = app.prober._shadows[("deduplication", "people")]
            widths = [[s.grams for s in w.index.plan.device_props]
                      for w in (wl, shadow.workload)]
            assert widths[0] == widths[1]
            assert telemetry.JIT_COMPILES.single().value == before
            assert shadow.state.probe_compiles == 0
        finally:
            app.close()
