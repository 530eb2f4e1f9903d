"""One run of one benchmark cell, on the chip.

    python3 perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration
(``perf/configs/<config>.json``: the service XML, backend, data scale and
the limits of the comparison) and a traffic mix
(``perf/traffic/<mix>.json``).  This process holds the chip and runs the
server and nothing else heavy; the pipes run in ``perf/client.py``, a
process that never imports JAX, over loopback HTTP.

A run: refuse without the chip; build the service with a fresh data
folder; preload the deployment's corpus through the workload's store and
index without scoring it; warm up on the cell's own traffic until a
round compiles nothing, and for at least ``WARM_ROUNDS_MIN`` rounds;
then the window.  With ``--trace 1`` the window
is traced and the per-layer metrics are read (``perf/layer_metrics/``);
with ``--trace 0`` the end-to-end ones (``perf/end_to_end/``).  After the
window the service is closed and the links it served are held against
the plain reference (``perf/compare.py``).  The last line of standard
output is the result's one JSON object.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import urllib.error  # noqa: E402
import urllib.request  # noqa: E402

PERF = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PERF)
RUN_DIR = os.path.join(PERF, ".run")
sys.path[:0] = [PERF, ROOT]

import gen  # noqa: E402
import plugins  # noqa: E402
from compare import compare, is_correct  # noqa: E402
from reference import parse_service  # noqa: E402

# the knobs the CPU test suite shrinks (tests/conftest.py): a cell runs
# the production geometry, so any of them set is an error
SMALL_SHAPE_KNOBS = (
    "DEVICE_CHUNK", "DEVICE_QUERY_BUCKETS", "DEVICE_TOP_K",
    "DEVICE_MAX_CHARS", "DEVICE_MAX_GRAMS", "DEVICE_PREWARM",
    "DUKE_TPU_PALLAS",
)
WARM_ROUNDS_MAX = 8
# every run posts at least this many warm-up rounds, a cold checkout's
# first run and a warm one alike: each re-posted record appends a device
# row, and the scan reads up to the last live row, so the window's work
# depends on how many records the warm-up posted (PERF.md)
WARM_ROUNDS_MIN = 3
PREWARM_THREAD = "scorer-prewarm"   # the program's background compiler


def log(msg: str) -> None:
    print(f"[{time.monotonic() - T_START:.1f}s] {msg}", file=sys.stderr,
          flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str):
    bench = load_json(ROOT, "BENCHMARK.json")
    (cell,) = [w for w in bench["workloads"] if w["name"] == name]
    (cfg,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    config = load_json(ROOT, cfg["file"])
    traffic = load_json(PERF, "traffic", f"{cell['traffic']}.json")
    return bench, cell, config, traffic


def reported(bench: dict, cell: dict, section: str):
    """The metrics of ``section`` this cell reports."""
    out = [m for m in bench[section]
           if cell["name"] in m.get("workloads", [cell["name"]])]
    if section == "per_layer":
        e2e = {m["name"] for m in reported(bench, cell, "end_to_end")}
        out = [m for m in out if "workloads" in m or m["moves"] in e2e]
    return out


def reader(kind: str, name: str):
    return plugins.load(kind, name).read


# -- /metrics text -------------------------------------------------------------


def parse_metrics(text: str) -> dict:
    """{(family, frozenset(labels)): value} of a Prometheus text page."""
    out = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        head, value = line.rsplit(" ", 1)
        if "{" in head:
            name, rest = head.split("{", 1)
            labels = frozenset(
                tuple(kv.split("=", 1)) for kv in
                _split_labels(rest.rstrip("}")))
            labels = frozenset((k, v.strip('"')) for k, v in labels)
        else:
            name, labels = head, frozenset()
        out[(name, labels)] = float(value)
    return out


def _split_labels(s: str):
    parts, cur, quoted = [], "", False
    for ch in s:
        if ch == '"':
            quoted = not quoted
        if ch == "," and not quoted:
            parts.append(cur)
            cur = ""
        else:
            cur += ch
    if cur:
        parts.append(cur)
    return parts


def metric_sum(parsed: dict, family: str, **labels) -> float:
    want = set(labels.items())
    return sum(v for (name, ls), v in parsed.items()
               if name == family and want <= ls)


class Context:
    """What a metric reader may read: the window's POSTs and feed rows,
    ``/metrics`` before and after it, and the reduced trace."""

    def __init__(self, cell, config, traffic, service, rows, report,
                 trace=None):
        self.cell = cell
        self.config = config
        self.traffic = traffic
        self.service = service
        self.rows = rows
        self.report = report
        self.trace = trace
        self.t0 = report["t0"]
        self.last_ack = report["last_ack"]
        self.posts = [p for p in report["posts"] if p["phase"] == "window"]
        self.before = parse_metrics(report["metrics_before"])
        self.after = parse_metrics(report["metrics_after"])

    @staticmethod
    def percentile(values, q: float) -> float:
        return percentile(values, q)

    def delta(self, family: str, **labels) -> float:
        return (metric_sum(self.after, family, **labels)
                - metric_sum(self.before, family, **labels))

    def hist_mean(self, family: str, **labels):
        """The mean of a histogram's observations inside the window, over
        this cell's workload; None when it observed nothing."""
        labels["workload"] = self.service["name"]
        n = self.delta(f"{family}_count", **labels)
        if n <= 0:
            return None
        return self.delta(f"{family}_sum", **labels) / n


# -- the service -----------------------------------------------------------------


class Server:
    def __init__(self, config: dict, data_folder: str):
        from sesam_duke_microservice_tpu.core.config import parse_config
        from sesam_duke_microservice_tpu.service.app import DukeApp, serve

        xml = config["service_xml"].replace("{data_folder}", data_folder)
        self.app = DukeApp(parse_config(xml), backend=config["backend"],
                           persistent=True)
        self.http = serve(self.app, port=0, host="127.0.0.1")
        self.thread = threading.Thread(target=self.http.serve_forever,
                                       daemon=True)
        self.thread.start()
        self.port = self.http.server_address[1]
        deadline = time.monotonic() + 600
        while self.status("/readyz") != 200:
            if time.monotonic() > deadline:
                raise RuntimeError("the service never became ready")
            time.sleep(0.1)

    def status(self, path: str) -> int:
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{self.port}{path}", timeout=60) as r:
                return r.status
        except urllib.error.HTTPError as e:
            return e.code

    def metric(self, family: str) -> float:
        url = f"http://127.0.0.1:{self.port}/metrics"
        with urllib.request.urlopen(url, timeout=60) as r:
            return metric_sum(parse_metrics(r.read().decode()), family)

    def workload(self, service: dict):
        registry = (self.app.deduplications
                    if service["kind"] == "deduplication"
                    else self.app.record_linkages)
        return registry[service["name"]]

    def close(self) -> None:
        self.http.shutdown()
        self.http.server_close()
        self.app.close()


def preload(wl, rows: dict, chunk: int = 10_000) -> None:
    """Load the corpus without scoring it: the workload's record store,
    then its index, as a restart replay would (the benchmark's one entry
    below HTTP; see PERF.md)."""
    with wl.lock:
        for ds, ds_rows in rows.items():
            source = wl.datasources[ds]
            for s in range(0, len(ds_rows), chunk):
                records = source.records_for_batch(ds_rows[s:s + chunk])
                wl.record_store.put_many(records)
                for r in records:
                    wl.index.index(r)
                wl.index.commit()


class BackendCompiles:
    """Every XLA backend compile in this process, whoever asked for it
    (a persistent-cache hit or an AOT load is no compile)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self.on_event)

    def on_event(self, event: str, duration: float, **kwargs) -> None:
        if event == self.EVENT:
            self.count += 1


class GcPauses:
    """The interpreter's garbage collections and their pauses, by
    generation (a log line only: a pause stops the server's host steps)."""

    def __init__(self):
        self.count = [0, 0, 0]
        self.seconds = [0.0, 0.0, 0.0]
        self._t = None
        gc.callbacks.append(self.on_gc)

    def on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.count[info["generation"]] += 1
            self.seconds[info["generation"]] += time.perf_counter() - self._t
            self._t = None

    def snapshot(self):
        return list(self.count), list(self.seconds)

    def close(self) -> None:
        gc.callbacks.remove(self.on_gc)


def join_prewarm() -> None:
    for t in threading.enumerate():
        if t.name == PREWARM_THREAD:
            t.join()


class ClientProcess:
    def __init__(self, plan_path: str):
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(PERF, "client.py"), plan_path],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
        self.ask(None)  # {"ready": true}: the traffic is built

    def ask(self, cmd):
        if cmd is not None:
            self.proc.stdin.write(json.dumps(cmd) + "\n")
            self.proc.stdin.flush()
        line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError(f"the client exited ({self.proc.wait()})")
        return json.loads(line)

    def close(self) -> None:
        if self.proc.poll() is None:
            self.proc.stdin.close()
            try:
                self.proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()


# -- one run ---------------------------------------------------------------------


def percentile(values, q: float) -> float:
    """Nearest-rank percentile."""
    s = sorted(values)
    return s[max(0, min(len(s) - 1, -(-len(s) * q // 100) - 1))]


def run_cell(name: str, seed: int, seconds: int, trace: bool, *,
             require_tpu: bool = True, overrides: dict = None,
             control: bool = False) -> dict:
    """One run; returns the result object.  ``require_tpu=False`` and
    ``overrides`` (scale and traffic, merged over the cell's files) are
    for the CPU rehearsal tests only; ``control`` adds the control's
    numbers (``perf/control.py``)."""
    bench, cell, config, traffic = load_cell(name)
    for part, values in (overrides or {}).items():
        {"data": config["data"], "traffic": traffic,
         "env": config.setdefault("env", {})}[part].update(values)
    service = parse_service(config["service_xml"])
    for key, value in config.get("env", {}).items():
        os.environ[key] = value
    # the compile caches stay inside the checkout, at a fixed path, even
    # where the machine sets one of its own: the two sides of a check
    # share nothing (PERF.md: where this leaves ISSUE 22)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.pop("DUKE_AOT_DIR", None)

    import jax

    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell["chips"]):
        raise SystemExit(f"needs {cell['chips']} TPU chip(s); JAX found "
                         f"{len(devices)} {devices[0].platform} device(s)")
    devices = devices[:cell["chips"]]
    log(f"device: {devices[0].device_kind} x {len(devices)}; jax "
        f"{jax.__version__}")

    shutil.rmtree(RUN_DIR, ignore_errors=True)
    os.makedirs(RUN_DIR)
    plan_path = os.path.join(RUN_DIR, "plan.json")
    report_path = os.path.join(RUN_DIR, "report.json")
    with open(plan_path, "w") as f:
        json.dump({"config": config, "traffic": traffic, "seed": seed,
                   "seconds": seconds, "report": report_path}, f)
    backend = BackendCompiles()
    pauses = GcPauses()
    client = ClientProcess(plan_path)
    server = None
    try:
        rows = gen.corpus(config, seed)
        server = Server(config, os.path.join(RUN_DIR, "data"))
        wl = server.workload(service)
        t = time.monotonic()
        preload(wl, rows)
        log(f"preload: {sum(map(len, rows.values()))} records in "
            f"{time.monotonic() - t} s")

        def compiles():
            return (server.metric("duke_jit_compiles_total"), backend.count)

        def escalations():
            return server.metric("duke_scorer_escalations_total")

        before = compiles()
        for n in range(1, WARM_ROUNDS_MAX + 1):
            answer = client.ask({"cmd": "warm", "port": server.port})
            join_prewarm()
            now = compiles()
            log(f"warm-up round {n}: {answer['posts']} POSTs, "
                f"{answer['failed']} failed; program compiles "
                f"(duke_jit_compiles_total) {now[0] - before[0]}, XLA "
                f"backend compiles {now[1] - before[1]}")
            if now == before and n >= WARM_ROUNDS_MIN:
                break
            before = now

        # a full collection now, so that the window's collections fall
        # at the same points of its work in every run
        gc.collect()
        esc_before, gc_before = escalations(), pauses.snapshot()
        if trace:
            from sesam_duke_microservice_tpu.telemetry import tracing

            import tracefile as tr

            tracing.set_device_annotations(True)
            tdir = os.path.join(RUN_DIR, "trace")
            with tr.Capture(tdir) as cap:
                span = client.ask({"cmd": "window"})
            tracing.set_device_annotations(False)
        else:
            client.ask({"cmd": "window"})
        after = compiles()
        esc_after, gc_after = escalations(), pauses.snapshot()
        client.ask({"cmd": "report"})
        memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                          for d in devices)
        server.close()
        server = None
    finally:
        if server is not None:
            server.close()
        client.close()
        pauses.close()

    with open(report_path) as f:
        report = json.load(f)
    log(f"compiles inside the window: duke_jit_compiles_total "
        f"{after[0] - before[0]}, XLA backend compiles {after[1] - before[1]}")
    log(f"scorer escalations inside the window: {esc_after - esc_before}")
    log("garbage collections inside the window, generations 0/1/2: "
        f"{[a - b for a, b in zip(gc_after[0], gc_before[0])]}, pauses s "
        f"{[a - b for a, b in zip(gc_after[1], gc_before[1])]}")
    late = [p["send"] - p["due"] for p in report["posts"]
            if p["phase"] == "window"]
    log(f"generator lateness (send - due), s: p50 {percentile(late, 50)} "
        f"max {max(late)}")

    trace_summary = None
    if trace:
        # the measured window alone, not the scrapes on either side of it
        trace_summary = tr.reduce(
            tr.extract(tdir), int((span["last_ack"] - cap.start) * 1e9),
            int((span["t0"] - cap.start) * 1e9))
    ctx = Context(cell, config, traffic, service, rows, report,
                  trace_summary)
    metrics = {}
    section = "per_layer" if trace else "end_to_end"
    kind = "layer_metrics" if trace else "end_to_end"
    for m in reported(bench, cell, section):
        if m["name"] == "setup_s":
            value = report["t0"] - T_START
        else:
            value = reader(kind, m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    posts = ctx.posts
    result = {
        "correct": None,
        "attempted": len(posts),
        "failed": sum(p["status"] != 200 for p in posts),
        "metrics": metrics,
        "device": {"platform": devices[0].platform,
                   "kind": devices[0].device_kind,
                   "count": len(devices),
                   "memory_peak_bytes": memory_peak},
    }
    if trace:
        result["device"]["busy_s"] = trace_summary["busy_s"]
        result["device"]["window_s"] = trace_summary["window_s"]
        result["breakdown"] = {
            "device_ops": trace_summary["device_ops"],
            "idle_gaps": trace_summary["idle_gaps"],
        }

    t = time.monotonic()
    numbers = compare(config, rows, report["posts"], report["live"], seed,
                      config["check_sample"])
    log(f"reference comparison: {time.monotonic() - t} s")
    result["correct"] = is_correct(numbers)
    if control:
        result["control"] = compare(config, rows, report["posts"],
                                    report["live"], seed,
                                    config["check_sample"], control=True)
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in numbers.items()}
    for k, (v, lim) in numbers.items():
        log(f"compared {k}: {v} limit {lim}")
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=int, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    knobs = [k for k in SMALL_SHAPE_KNOBS if k in os.environ]
    if knobs:
        raise SystemExit(f"a cell runs production geometry; unset {knobs}")
    result = run_cell(args.workload, args.seed, args.seconds,
                      bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
