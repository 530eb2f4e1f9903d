"""Bring-up smoke of the served device path on a TPU chip.

    python chip_smoke.py              # one chip: the device backend at 100k
    python chip_smoke.py --chips 4    # four chips: sharded-brute vs device

Everything runs in this one process (a chip belongs to one process): the
service is built in-process, bound to a loopback port and driven over
HTTP the way a Sesam pipe drives it: 1,000-record POST batches, then
update and delete batches, then the ``?since=`` link feed.  Geometry is
the production default: none of the small-shape ``DEVICE_*`` knobs the
CPU tests set, and ``DUKE_TPU_PALLAS`` unset, so the Pallas kernels are
on because the backend is a TPU.

One chip.  The Sesam dedup stresstest schema (name/Levenshtein,
city/Exact, ssn/QGram) over 100,000 records of
``benchmarks/f1_stresstest.generate`` (dup rate 0.3, seed 1234).  Checks:

  * every live device link, re-scored by the host f64 comparators on the
    records as last posted, is above threshold with the confidence equal
    to within ``links.base.CONFIDENCE_EPSILON``; no link touches a
    deleted record;
  * every host-engine link is in the device feed.  The host engine needs
    about an hour at 100k, so this check runs on a 20,000-record workload
    (seed 4321) sent to both backends through the same HTTP path;
  * the registered scorer executables hold Mosaic kernels
    (``tpu_custom_call``) and no ``pallas_call`` was traced with
    ``interpret=True``.

Four chips.  The same workload through ``sharded-brute`` on a 4-device
mesh and through the single-chip ``device`` backend; their live link
sets must be equal.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``;
any failure raises, exits non-zero and prints no such line.  Without a
TPU the script stops before any work.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import random
import shutil
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.abspath(__file__))
BENCHMARKS = os.path.join(REPO, "benchmarks")
DATA = os.path.join(REPO, "chiprun_out", "chip_smoke_data")

N_RECORDS = 100_000
N_CHECK = 20_000
BATCH = 1_000
SEED = 1234
CHECK_SEED = 4321
N_EDITS = 2_000
N_DELETES = 1_000

# knobs the CPU test suite shrinks (tests/conftest.py); the smoke runs the
# production defaults, so any of them set in the environment is an error
SMALL_SHAPE_KNOBS = (
    "DEVICE_CHUNK", "DEVICE_QUERY_BUCKETS", "DEVICE_TOP_K",
    "DEVICE_MAX_CHARS", "DEVICE_MAX_GRAMS", "DEVICE_PREWARM",
    "DUKE_TPU_PALLAS",
)

STRESS_DEDUP = """
  <Deduplication name="{name}" link-database-type="in-memory">
    <duke>
      <schema>
        <threshold>0.8</threshold>
        <property><name>name</name><comparator>levenshtein</comparator><low>0.25</low><high>0.85</high></property>
        <property><name>city</name><comparator>exact</comparator><low>0.45</low><high>0.65</high></property>
        <property><name>ssn</name><comparator>qgram</comparator><low>0.2</low><high>0.9</high></property>
      </schema>
      <data-source class="io.sesam.dukemicroservice.IncrementalDeduplicationDataSource">
        <param name="dataset-id" value="src"/>
        <column name="name" property="name"/>
        <column name="city" property="city"/>
        <column name="ssn" property="ssn"/>
      </data-source>
    </duke>
  </Deduplication>"""


def config_xml(data_folder: str, names) -> str:
    """CONFIG_STRING-style service config: one stresstest dedup per name."""
    body = "".join(STRESS_DEDUP.format(name=n) for n in names)
    return (f'<DukeMicroService dataFolder="{data_folder}">{body}\n'
            "</DukeMicroService>")


def log(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def phase(timings: dict, name: str):
    t0 = time.perf_counter()
    yield
    timings[name] = time.perf_counter() - t0
    log(f"phase {name}: {timings[name]} s")


# -- workload: seeded stresstest records plus edits and deletes --------------


def workload(n: int, seed: int):
    """(batches, truth): the POST batches in order (inserts, then edits,
    then deletes) and the generator's truth."""
    if BENCHMARKS not in sys.path:
        sys.path.insert(0, BENCHMARKS)
    from f1_stresstest import generate

    rows, truth = generate(n, 0.3, seed)
    batches = [rows[s:s + BATCH] for s in range(0, n, BATCH)]
    rng = random.Random(seed + 1)
    picked = rng.sample(range(n), N_EDITS + N_DELETES)
    edits = []
    for i in picked[:N_EDITS]:
        row = dict(rows[i])
        if rng.random() < 0.5:
            row["name"] = row["name"] + rng.choice("aeiou")
        else:
            row["city"] = rng.choice([r["city"] for r in rows[:100]])
        edits.append(row)
    deletes = [{"_id": rows[i]["_id"], "_deleted": True}
               for i in picked[N_EDITS:]]
    batches += [edits[s:s + BATCH] for s in range(0, len(edits), BATCH)]
    batches += [deletes[s:s + BATCH] for s in range(0, len(deletes), BATCH)]
    return batches, truth


class Versions:
    """Each record's posted versions, by the batch that posted them.

    A link keeps the confidence of the batch that last asserted it: an
    edit that lowers a pair's score below threshold does not retract the
    pair's link (reference parity; only deletes retract).  So a link is
    re-scored on the records as they stood in that batch, which its
    ``_updated`` stamp identifies."""

    def __init__(self, batches):
        self.history = {}
        for b, batch in enumerate(batches):
            for row in batch:
                self.history.setdefault(row["_id"], []).append(
                    (b, None if row.get("_deleted") else row))
        self.windows = []  # link-clock stamps around each batch's POST

    def at(self, rid: str, batch: int):
        version = None
        for b, row in self.history[rid]:
            if b > batch:
                break
            version = row
        return version

    def final(self, rid: str):
        return self.history[rid][-1][1]

    def batch_of(self, stamp: int) -> int:
        for b, (lo, hi) in enumerate(self.windows):
            if lo <= stamp <= hi:
                return b
        raise AssertionError(f"link stamp {stamp} outside every POST")


# -- HTTP client: the Sesam pipe's side ---------------------------------------


class Service:
    """A DukeApp bound to a loopback port, served from a daemon thread."""

    def __init__(self, xml: str, backend: str):
        from sesam_duke_microservice_tpu.core.config import parse_config
        from sesam_duke_microservice_tpu.service.app import DukeApp, serve

        self.config = parse_config(xml)
        self.app = DukeApp(self.config, backend=backend, persistent=True)
        self.server = serve(self.app, port=0, host="127.0.0.1")
        self.thread = threading.Thread(target=self.server.serve_forever,
                                       daemon=True)
        self.thread.start()
        self.base = f"http://127.0.0.1:{self.server.server_address[1]}"
        deadline = time.monotonic() + 600
        while self.get_status("/readyz") != 200:
            if time.monotonic() > deadline:
                raise RuntimeError(f"{backend} service never became ready")
            time.sleep(0.2)

    def get_status(self, path: str) -> int:
        try:
            with urllib.request.urlopen(self.base + path, timeout=60) as r:
                return r.status
        except urllib.error.HTTPError as e:
            return e.code

    def get(self, path: str) -> bytes:
        with urllib.request.urlopen(self.base + path, timeout=1800) as r:
            return r.read()

    def post(self, name: str, batch) -> None:
        req = urllib.request.Request(
            f"{self.base}/deduplication/{name}/src",
            data=json.dumps(batch).encode(), method="POST",
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=1800) as r:
            body = r.read()
            if r.status != 200:
                raise RuntimeError(f"POST {name}: {r.status} {body[:200]}")

    def links(self, name: str):
        """Live links from ``?since=0`` paged forward with ``?since=``:
        {(entity1, entity2) sorted: (confidence, _updated)}, and the feed
        rows read."""
        since, live, rows_read = 0, {}, 0
        while True:
            rows = json.loads(self.get(f"/deduplication/{name}?since={since}"))
            if not rows:
                return live, rows_read
            rows_read += len(rows)
            for row in rows:
                key = tuple(sorted((row["entity1"], row["entity2"])))
                if row["_deleted"]:
                    live.pop(key, None)
                else:
                    live[key] = (row["confidence"], row["_updated"])
                since = max(since, row["_updated"])

    def metric(self, family: str) -> float:
        """Sum of a family's samples on ``/metrics``."""
        total = 0.0
        for line in self.get("/metrics").decode().splitlines():
            if line.startswith(family) and line[len(family)] in " {":
                total += float(line.rsplit(" ", 1)[1])
        return total

    def close(self) -> None:
        self.server.shutdown()
        self.server.server_close()
        self.app.close()


def post_all(svc: Service, name: str, batches, versions=None) -> None:
    # the link clock is strictly monotonic per process, so stamps drawn
    # from it around a POST bracket every link the POST wrote
    from sesam_duke_microservice_tpu.links.base import now_millis

    for batch in batches:
        lo = now_millis()
        svc.post(name, batch)
        if versions is not None:
            versions.windows.append((lo, now_millis()))


# -- checks --------------------------------------------------------------------


def f1_score(live, truth, versions: Versions) -> float:
    from f1_stresstest import truth_pairs

    expected = {p for p in truth_pairs(truth)
                if versions.final(p[0]) and versions.final(p[1])}
    tp = len(set(live) & expected)
    precision = tp / len(live) if live else 0.0
    recall = tp / len(expected) if expected else 1.0
    return (2 * precision * recall / (precision + recall)
            if precision + recall else 0.0)


def check_rescored(config, name: str, live, versions: Versions) -> None:
    """Every live link joins two live records, and the host f64 engine
    scores the pair, as it stood when the link was last written, above
    threshold at the link's confidence."""
    from sesam_duke_microservice_tpu.engine.processor import Processor
    from sesam_duke_microservice_tpu.links.base import CONFIDENCE_EPSILON
    from sesam_duke_microservice_tpu.service.datasource import (
        IncrementalDataSource,
    )

    wc = config.deduplications[name]
    source = IncrementalDataSource(wc.duke.data_sources[0])
    proc = Processor(wc.duke, None)
    worst = 0.0
    for (a, b), (conf, stamp) in live.items():
        if versions.final(a) is None or versions.final(b) is None:
            raise AssertionError(f"{name}: live link {a}-{b} touches a "
                                 "deleted record")
        batch = versions.batch_of(stamp)
        prob = proc.compare(
            source.record_for_entity(versions.at(a, batch)),
            source.record_for_entity(versions.at(b, batch)))
        if not prob > wc.duke.threshold:
            raise AssertionError(f"{name}: link {a}-{b} rescored {prob} "
                                 f"<= threshold {wc.duke.threshold}")
        worst = max(worst, abs(prob - conf))
    if worst > CONFIDENCE_EPSILON:
        raise AssertionError(f"{name}: confidence off the host engine by "
                             f"{worst} > {CONFIDENCE_EPSILON}")
    log(f"{name}: {len(live)} live links rescored by the host engine, "
        f"largest confidence difference {worst}")


def check_kernels(traced_interpret) -> None:
    """The registered scorer executables hold Mosaic kernels, and no
    kernel on this path was traced in interpret mode."""
    from sesam_duke_microservice_tpu.utils.jit_cache import SHARED_LADDERS

    exes = SHARED_LADDERS.executables()
    if not exes:
        raise AssertionError("no scorer executable was registered")
    mosaic = [e for e in exes if "tpu_custom_call" in e.as_text()]
    log(f"registered scorer executables: {len(exes)}, with Mosaic "
        f"kernels (tpu_custom_call): {len(mosaic)}")
    if len(mosaic) != len(exes):
        raise AssertionError("a registered scorer has no Mosaic kernel")
    log(f"pallas_call traces: {len(traced_interpret)}, with "
        f"interpret=True: {sum(map(bool, traced_interpret))}")
    if any(traced_interpret):
        raise AssertionError("a pallas_call ran with interpret=True")


def record_pallas_calls():
    """Wrap ``pallas_call`` so every trace's ``interpret`` flag is kept."""
    from jax.experimental import pallas as pl

    seen = []
    real = pl.pallas_call

    def recording(*args, **kwargs):
        seen.append(kwargs.get("interpret", False))
        return real(*args, **kwargs)

    pl.pallas_call = recording
    return seen


def memory_line(devices) -> str:
    parts = []
    for d in devices:
        stats = d.memory_stats() or {}
        parts.append(f"{d.id}: peak {stats.get('peak_bytes_in_use')} "
                     f"in use {stats.get('bytes_in_use')}")
    return "device memory (bytes) " + "; ".join(parts)


# -- the two runs --------------------------------------------------------------


def run_one_chip(timings: dict, n_records: int, n_check: int) -> None:
    import jax

    traced = record_pallas_calls()
    shutil.rmtree(DATA, ignore_errors=True)
    try:
        with phase(timings, "generate"):
            main_batches, main_truth = workload(n_records, SEED)
            check_batches, _ = workload(n_check, CHECK_SEED)
            main_versions = Versions(main_batches)
            check_versions = Versions(check_batches)
        with phase(timings, "start_device"):
            dev = Service(config_xml(os.path.join(DATA, "device"),
                                     ["stress", "check"]), "device")
        try:
            with phase(timings, "ingest_device"):
                post_all(dev, "stress", main_batches, main_versions)
            with phase(timings, "feed_device"):
                live, rows_read = dev.links("stress")
            posted = sum(map(len, main_batches))
            log(f"stress: records posted {posted} in {len(main_batches)} "
                f"batches, feed rows read {rows_read}, live links "
                f"{len(live)}")
            log(f"stress: F1 vs generator truth "
                f"{f1_score(live, main_truth, main_versions)}")
            compiles = dev.metric("duke_jit_compiles_total")
            compile_s = dev.metric("duke_cost_compile_seconds_total")
            loaded = dev.metric('duke_aot_loads_total{outcome="hit"}')
            log(f"compiles (duke_jit_compiles_total): {compiles}, compile "
                f"seconds (duke_cost_compile_seconds_total): {compile_s}, "
                f"executables loaded from the AOT store: {loaded}")
            log(memory_line(jax.devices()[:1]))
            with phase(timings, "rescore_device"):
                check_rescored(dev.config, "stress", live, main_versions)
            with phase(timings, "check_device"):
                post_all(dev, "check", check_batches, check_versions)
                dev_check, _ = dev.links("check")
                check_rescored(dev.config, "check", dev_check,
                               check_versions)
            with phase(timings, "kernels"):
                check_kernels(traced)
        finally:
            dev.close()
        with phase(timings, "check_host"):
            host = Service(config_xml(os.path.join(DATA, "host"),
                                      ["check"]), "host")
            try:
                post_all(host, "check", check_batches)
                host_check, _ = host.links("check")
            finally:
                host.close()
        missing = set(host_check) - set(dev_check)
        log(f"check: host links {len(host_check)}, device links "
            f"{len(dev_check)}, host links missing from the device feed "
            f"{len(missing)}")
        if missing:
            raise AssertionError(f"host links missing from the device "
                                 f"feed, e.g. {sorted(missing)[:5]}")
    finally:
        shutil.rmtree(DATA, ignore_errors=True)


def run_four_chips(timings: dict, n_records: int) -> None:
    import jax

    devices = jax.devices()
    if len(devices) < 4:
        raise RuntimeError(f"--chips 4 needs 4 TPU devices, found "
                           f"{len(devices)}")
    shutil.rmtree(DATA, ignore_errors=True)
    feeds = {}
    try:
        with phase(timings, "generate"):
            batches, _ = workload(n_records, SEED)
        for backend in ("sharded-brute", "device"):
            with phase(timings, backend):
                svc = Service(config_xml(os.path.join(DATA, backend),
                                         ["stress"]), backend)
                try:
                    post_all(svc, "stress", batches)
                    feeds[backend], _ = svc.links("stress")
                    log(f"{backend}: live links {len(feeds[backend])}, "
                        f"compiles {svc.metric('duke_jit_compiles_total')}")
                    log(f"{backend}: " + memory_line(devices))
                finally:
                    svc.close()
        mesh, one = feeds["sharded-brute"], feeds["device"]
        if set(mesh) != set(one):
            raise AssertionError(
                f"link sets differ: {len(set(mesh) - set(one))} only on "
                f"the mesh, {len(set(one) - set(mesh))} only on one chip")
        worst = max((abs(mesh[k][0] - one[k][0]) for k in mesh),
                    default=0.0)
        log(f"sharded-brute and device link sets equal ({len(mesh)} "
            f"links), largest confidence difference {worst}")
    finally:
        shutil.rmtree(DATA, ignore_errors=True)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = parser.parse_args(argv)

    import importlib.metadata

    import jax
    import jaxlib

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found {devices[0].platform} devices")
    log(f"device: {devices[0].device_kind} x {len(devices)}; jax "
        f"{jax.__version__}, jaxlib {jaxlib.__version__}, libtpu "
        f"{importlib.metadata.version('libtpu')}")
    knobs = [k for k in SMALL_SHAPE_KNOBS if k in os.environ]
    if knobs:
        raise SystemExit(f"the smoke runs production geometry; unset {knobs}")
    from sesam_duke_microservice_tpu.utils.jit_cache import (
        enable_persistent_cache,
    )

    log(f"compile cache: {enable_persistent_cache()}")

    timings = {}
    t0 = time.perf_counter()
    if args.chips == 4:
        run_four_chips(timings, N_RECORDS)
    else:
        run_one_chip(timings, N_RECORDS, N_CHECK)
    log(f"total wall: {time.perf_counter() - t0} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
