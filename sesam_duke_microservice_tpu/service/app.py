"""The HTTP frontend — full reference REST surface.

Route-for-route reproduction of App.java:649-887 on the stdlib threading
HTTP server (the reference uses Spark-Java/Jetty on port 4567):

    GET  /                                                homepage
    GET  /config                                          active XML verbatim
    POST /config                                          multipart hot reload
    POST /deduplication/:name/:datasetId                  ingest+match
    POST /deduplication/:name/:datasetId/httptransform    transform
    GET  /deduplication/:name/:datasetId[/httptransform]  405 after validation
    GET  /deduplication/:name?since=N                     incremental feed
    (same six shapes under /recordlinkage)

Semantics preserved: writers take the workload lock unconditionally; feed
readers try for 1 s and answer 503 with the reference's message
(App.java:718-725, 827-834); POST body may be a JSON array or a single
object, and a single-entity transform answers a single object
(App.java:952-965, 1196-1198); unknown names 404 on entity endpoints and 400
on feeds; valid-name GETs on POST-only endpoints answer 405.

Documented divergences: the reference 500s (NPE) on an unknown recordlinkage
feed name — here both feeds answer 400; malformed JSON answers 400 rather
than a Jetty stack-trace 500; hot reload closes the replaced workloads'
resources (fixing quirk Q7's index/connection leak).
"""

from __future__ import annotations

import json
import logging
import re
import threading
import time
from email.parser import BytesParser
from email.policy import default as email_policy
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, Optional, Tuple
from urllib.parse import parse_qs, urlparse

from .. import telemetry
from ..core.config import ConfigError, ServiceConfig, load_default_config, parse_config
from ..engine.scheduler import (
    DatasetGone,
    IngestScheduler,
    SchedulerClosed,
    SchedulerReject,
    WorkloadGone,
    scheduler_enabled,
)
from ..engine.workload import Workload, build_workload
from ..ops.arena import ArenaAdmissionError
from ..telemetry import slo, tracing
from ..telemetry.env import env_flag, env_str
from ..telemetry.logctx import new_request_id, request_id_var
from ..telemetry.probes import is_probe_name, probes_enabled
from . import debug as debug_api
from .homepage import render_homepage
from .metrics import (
    HttpMetrics,
    backend_info,
    make_app_collector,
    make_process_collector,
)

logger = logging.getLogger("duke-tpu-service")

DEFAULT_PORT = 4567  # the reference's Spark default (Dockerfile EXPOSE 4567)

READ_LOCK_TIMEOUT_SECONDS = 1.0
_BUSY_TEMPLATE = (
    "The {kind} is being written to, so reading is not currently possible. "
    "Please wait a bit and try again later."
)

# Request-body ceiling (bytes).  The reference gets effective limits for
# free from its Jetty bootstrap (App.java:649); the stdlib server would
# otherwise read Content-Length bytes unconditionally into memory.  64 MiB
# comfortably fits the stresstest batch shapes (500-row batches are ~100 KB)
# while bounding a hostile/misconfigured POST; override via env.
DEFAULT_MAX_REQUEST_BYTES = 64 * 1024 * 1024


def _max_request_bytes() -> int:
    raw = env_str("MAX_REQUEST_BYTES")
    if not raw:
        return DEFAULT_MAX_REQUEST_BYTES
    try:
        limit = int(raw)
    except ValueError:
        logger.warning(
            "Unparseable MAX_REQUEST_BYTES=%r; using the %d default",
            raw, DEFAULT_MAX_REQUEST_BYTES,
        )
        return DEFAULT_MAX_REQUEST_BYTES
    # <= 0 means unlimited (the common convention; a literal 0 limit would
    # silently write-disable the service)
    return limit if limit > 0 else (1 << 62)


# Links per feed page: one page's fetch + record resolution is the unit of
# workload-lock hold while streaming GET ?since= responses.  5000 links
# resolve in well under 100 ms on every backend.
DEFAULT_FEED_PAGE_SIZE = 5000

# Mid-stream feed lock retries (ISSUE 8 satellite): bounded exponential
# backoff + full jitter under a wall-clock deadline, replacing the 120
# fixed 1 s retries — a wedged writer stops pinning the handler thread at
# a predictable instant, and the retry traffic decays instead of polling
# at 1 Hz for two minutes.
DEFAULT_FEED_RETRY_DEADLINE_S = 120.0
_FEED_BACKOFF_BASE_S = 0.05
_FEED_BACKOFF_CAP_S = 2.0


def _feed_retry_deadline() -> float:
    from ..telemetry.env import env_float

    return max(1.0, env_float("DUKE_FEED_RETRY_DEADLINE",
                              DEFAULT_FEED_RETRY_DEADLINE_S))


def write_chunk(wfile, data: bytes) -> int:
    """One HTTP/1.1 chunk — THE framing primitive, shared by the leader
    feed handler and the replica read plane so the wire format cannot
    drift between the two serving planes.  Zero-length data writes
    nothing (a zero-length chunk would terminate the stream).  Returns
    the payload bytes written."""
    if not data:
        return 0
    wfile.write(b"%x\r\n" % len(data) + data + b"\r\n")
    return len(data)


def _feed_backoff_delay(attempt: int) -> float:
    """Exponential backoff with full jitter for mid-stream lock retries
    (ONE policy copy — utils.backoff — shared with the dispatcher's
    send retries)."""
    from ..utils.backoff import full_jitter_delay

    return full_jitter_delay(attempt, _FEED_BACKOFF_BASE_S,
                             _FEED_BACKOFF_CAP_S)


def _feed_page_size() -> int:
    raw = env_str("FEED_PAGE_SIZE")
    try:
        value = int(raw) if raw else DEFAULT_FEED_PAGE_SIZE
    except ValueError:
        value = DEFAULT_FEED_PAGE_SIZE
    return max(1, value)


class DukeApp:
    """Application state: parsed config + live workloads, hot-swappable."""

    def __init__(self, config: ServiceConfig, *, backend: str = "host",
                 persistent: bool = True,
                 prebuilt: Optional[Tuple[Dict[str, Workload],
                                          Dict[str, Workload]]] = None):
        self.backend = backend
        self.persistent = persistent
        self._swap_lock = threading.Lock()
        self.config: Optional[ServiceConfig] = None
        self.deduplications: Dict[str, Workload] = {}
        self.record_linkages: Dict[str, Workload] = {}
        self.started_monotonic = time.monotonic()
        # per-app metrics registry: HTTP families are children written by
        # the handler threads; engine/corpus/link state is surfaced by a
        # scrape-time collector over the LIVE workload registries (so hot
        # reloads drop replaced workloads' series automatically).
        # /metrics renders this registry plus telemetry.GLOBAL.
        self.metrics = telemetry.MetricRegistry()
        self.http_metrics = HttpMetrics(self.metrics)
        self.metrics.register_collector(make_app_collector(self))
        self.metrics.register_collector(make_process_collector())
        # feed-stream abort visibility (ISSUE 6 satellite): the mid-stream
        # bail-outs (bounded lock-starvation retries exhausted; workload
        # removed by reload) truncate the chunked framing, which a scrape
        # can't see — plain counters surfaced by the app collector and
        # /stats.  Handler threads increment under the lock (rare events).
        self.feed_aborts = {
            "lock_starved": 0, "workload_removed": 0, "deadline": 0,
        }
        self._feed_abort_lock = threading.Lock()
        # promoted-leader marker: adopted workloads hold the ONLY copy of
        # the replicated link state (in-memory replicas; the deposed
        # leader's disk is gone), so apply_config refuses to rebuild them
        self.adopted = prebuilt is not None
        # close() runs from the signal-driven graceful-shutdown thread
        # AND the CLI's serve_forever finally — one caller runs the
        # drain sequence, every other caller BLOCKS until it completes
        # (a no-op second call would let the CLI's main thread exit and
        # take the daemon shutdown thread down mid drain/flush/snapshot)
        self._close_lock = threading.Lock()
        self._closed = False  # guarded by: self._close_lock [writes]
        self._close_done = threading.Event()
        # cold-start observability (ISSUE 15): stamped once by whichever
        # handler thread serves the first successful scoring batch.
        # Plain flag (GIL-atomic; a tied race would double-set a
        # near-identical value, harmless) — service/ is not a metrics
        # hot module, and the gauge is the measured time-to-first-200.
        self._first_batch_served = False
        if prebuilt is not None:
            # leader-failover promotion (parallel.dispatch
            # .promote_follower): the workloads already exist — built
            # around the replica corpus + replicated link DBs — so adopt
            # them instead of rebuilding from durable stores
            self.config = config
            self.deduplications, self.record_linkages = prebuilt
        else:
            self.apply_config(config)
        # continuous cross-request microbatching (ISSUE 6): queues are
        # keyed by (kind, name) and dispatch re-resolves from the live
        # registries, so a hot reload retargets queued requests at the
        # replacement workload.  DUKE_SCHEDULER=0 restores the
        # lock-winner merge inside Workload.submit_batch.
        self.scheduler = (IngestScheduler(self._resolve_workload)
                          if scheduler_enabled() else None)
        # black-box canary prober (ISSUE 20): one shadow workload per
        # user workload under the reserved __probe__ namespace, cycling
        # the derived canary corpus through the REAL path (scheduler,
        # scoring, finalize, link journal, feed materialization) on a
        # background interval.  Shadows live only here — never in the
        # HTTP registries — and DUKE_PROBE=0 restores today's behavior
        # exactly (no prober object, no thread, no collector).
        self.prober = None
        if probes_enabled():
            from .prober import CanaryProber

            self.prober = CanaryProber(self)
            self.prober.start()

    def _resolve_workload(self, kind: str, name: str) -> Optional[Workload]:
        if is_probe_name(name):
            # scheduler dispatch for canary batches: probe names resolve
            # through the prober's shadow registry, invisible to HTTP
            prober = getattr(self, "prober", None)
            return prober.resolve(kind, name) if prober is not None else None
        registry = (self.deduplications if kind == "deduplication"
                    else self.record_linkages)
        return registry.get(name)

    def count_feed_abort(self, reason: str) -> None:
        with self._feed_abort_lock:
            self.feed_aborts[reason] = self.feed_aborts.get(reason, 0) + 1

    def link_flush_errors(self) -> Dict[str, str]:
        """Latched write-behind flush failures by workload (ISSUE 8
        satellite): a dead persistence thread used to be invisible to
        orchestrators until a read drained into the latch — now /readyz
        goes unready and /healthz names the exception.  Lock-free reads
        of the buffers' latched error slots."""
        out: Dict[str, str] = {}
        for kind, registry in (("deduplication", self.deduplications),
                               ("recordlinkage", self.record_linkages)):
            for name, wl in registry.items():
                try:
                    err = wl.link_database.flush_error
                except Exception:
                    continue  # closed/raced workload: not a latch
                if err is not None:
                    out[f"{kind}/{name}"] = repr(err)
        return out

    def recovering(self) -> bool:
        """Whether any of THIS app's workloads is still replaying its
        link journal.  Scoped per workload data folder (ISSUE 14):
        another serving group's replay in the same process does not
        count.  Runs on every HTTP response (the X-Recovering header),
        so the steady-state path is ONE process-wide bool check — the
        per-folder scoping work only runs while some replay, somewhere,
        is actually active."""
        from ..links import journal as link_journal

        if not link_journal.recovery_active(None):
            return False  # nothing recovering anywhere: the common case
        if self.config is None:
            return True
        folders = [
            wc.data_folder
            for wc in (list(self.config.deduplications.values())
                       + list(self.config.record_linkages.values()))
            if wc.data_folder
        ]
        if not folders:
            return link_journal.recovery_active("")
        return any(link_journal.recovery_active(f) for f in folders)

    def note_first_batch(self) -> None:
        """Stamp ``duke_cold_start_seconds`` on the first successfully
        served scoring batch (time-to-first-200, ISSUE 15)."""
        if not self._first_batch_served:
            self._first_batch_served = True
            telemetry.COLD_START_SECONDS.set(
                time.monotonic() - self.started_monotonic)

    def prewarm_errors(self) -> Dict[str, str]:
        """Latched scorer pre-warm failures by workload (ISSUE 15
        satellite): a silently-cold replica — scoring works, but every
        first-contact shape pays a live compile — used to be findable
        only in logs; /healthz now names the last error.  Lock-free
        reads of the caches' error slots."""
        out: Dict[str, str] = {}
        for kind, registry in (("deduplication", self.deduplications),
                               ("recordlinkage", self.record_linkages)):
            for name, wl in registry.items():
                cache = getattr(wl.index, "scorer_cache", None)
                err = getattr(cache, "_warm_error", None)
                if err is not None:
                    out[f"{kind}/{name}"] = err
        return out

    def readiness(self) -> Tuple[bool, Dict[str, bool]]:
        """GET /readyz substance: config parsed, every configured workload
        built and swapped in, (non-host backends) the device backend
        initialized with at least one device, no workload's write-behind
        link persistence latched on a flush failure, and no link-journal
        recovery replay still running (ISSUE 10: /readyz answers
        ``recovering`` until startup replay completes).  With overlapped
        recovery (ISSUE 15, default) a recovering app still serves reads
        — ``write_ready`` is the key that flips only after replay
        completes, and the HTTP layer answers 200 ``recovering`` so
        orchestrators can route read traffic while writes 503."""
        checks = {"config_loaded": self.config is not None}
        # recovery is scoped per workload data folder (ISSUE 14): this
        # app goes "recovering" only for replays of ITS OWN workloads'
        # journals (plus anonymous process-wide entries) — another
        # serving group's replay in the same process no longer flips
        # every group's /readyz
        checks["recovery_complete"] = not self.recovering()
        checks["workloads_built"] = bool(
            self.config is not None
            and set(self.deduplications) == set(self.config.deduplications)
            and set(self.record_linkages) == set(self.config.record_linkages)
        )
        if self.backend == "host":
            checks["device_backend"] = True
        else:
            checks["device_backend"] = backend_info()[1] > 0
        checks["link_persistence"] = not self.link_flush_errors()
        # the read/write readiness split (ISSUE 15): during overlapped
        # recovery reads serve (the whole app is read-ready whenever
        # everything but the replay checks out) while writes stay fenced
        checks["write_ready"] = (checks["recovery_complete"]
                                 and checks["link_persistence"])
        return all(checks.values()), checks

    @property
    def config_string(self) -> str:
        return self.config.config_string if self.config else ""

    def apply_config(self, sc: ServiceConfig) -> None:
        """Quiesce, rebuild, atomically swap (App.java:543-546), close.

        The reference swaps its registries without taking the workload locks
        (quirk Q9), so an in-flight batch can commit records after the new
        workloads snapshot their state.  Here every old workload's lock is
        held while the replacements replay the durable stores, so nothing
        lands between the replay cursor and the swap; the replaced
        workloads' resources are then closed (quirk Q7 fix).

        Reload is stop-the-world for its duration (large corpora replay
        under the locks).  That is the deliberate trade: reload is a rare
        admin operation and the reference's reload pauses service the same
        way while offering weaker consistency.
        """
        if getattr(self, "adopted", False):
            # a promoted leader's workloads wrap replica link DBs that
            # exist nowhere else; rebuilding via build_workload would
            # swap in fresh EMPTY link databases and close the only copy
            # — silent total link loss behind a 200.  Reload again once
            # the group re-forms around durable state.
            raise RuntimeError(
                "config reload is disabled on a promoted leader: its "
                "workloads hold the only copy of the replicated link "
                "state (restart the job to re-form the serving group, "
                "then reload)"
            )
        with self._swap_lock:
            old = list(self.deduplications.values()) + list(self.record_linkages.values())
            for wl in old:
                wl.lock.acquire()
            try:
                # snapshot the quiesced corpora FIRST: the replacements are
                # built before the old workloads close, so without this a
                # device-backend reload would replay the store through full
                # feature re-extraction instead of the snapshot fast path
                for wl in old:
                    wl.save_corpus_snapshot()
                built = []
                try:
                    new_dedups = {}
                    for name, wc in sc.deduplications.items():
                        new_dedups[name] = build_workload(
                            wc, sc, backend=self.backend,
                            persistent=self.persistent)
                        built.append(new_dedups[name])
                    new_linkages = {}
                    for name, wc in sc.record_linkages.items():
                        new_linkages[name] = build_workload(
                            wc, sc, backend=self.backend,
                            persistent=self.persistent)
                        built.append(new_linkages[name])
                except Exception:
                    # failed reload keeps the old config (App.java:543-546);
                    # release whatever the partial build already opened
                    for wl in built:
                        try:
                            wl.close()
                        except Exception:
                            logger.exception("Error closing partially-built workload")
                    raise
                # multi-host serving: ship followers the new config + the
                # just-built corpora so their replicas swap in lockstep
                # (old locks held -> nothing in flight on the op stream)
                from ..parallel import dispatch

                d = dispatch.current()
                if d is not None:
                    with d.op_lock:
                        d.on_reload(sc, new_dedups, new_linkages)
                self.config = sc
                self.deduplications = new_dedups
                self.record_linkages = new_linkages
                for wl in old:
                    try:
                        # snapshot already written above and the corpus is
                        # unchanged (locks held) — skip the duplicate save
                        wl.close(save_snapshot=False)
                    except Exception:
                        logger.exception("Error closing replaced workload")
            finally:
                for wl in old:
                    wl.lock.release()

    def reload_from_string(self, config_string: str) -> None:
        self.apply_config(parse_config(config_string))

    def close(self) -> None:
        """Graceful shutdown: drain the ingest scheduler, then close every
        workload — each close drains its write-behind link flush (leaving
        an EMPTY journal: the watermark catches the head and the file
        compacts to zero bytes) and saves the device-corpus snapshot, so
        an orchestrated restart (docker stop / k8s SIGTERM) starts warm
        with nothing to recover.  Idempotent; called by the signal
        handlers (``install_shutdown_handlers``) and the CLI's
        ``finally`` — the reference has no shutdown hook at all (state
        safety there rests on Lucene/H2 syncing every commit)."""
        with self._close_lock:
            if self._closed:
                already = True
            else:
                self._closed = True
                already = False
        if already:
            # wait for the winning caller's drain sequence: the CLI's
            # finally must not let the process exit while the signal
            # thread is still flushing/snapshotting
            self._close_done.wait()
            return
        try:
            # stop the canary prober before the scheduler drain: its
            # cycles submit through the scheduler this is shutting down
            if getattr(self, "prober", None) is not None:
                self.prober.stop()
            # drain the ingest scheduler FIRST: queued requests complete
            # against still-open workloads (no lost requests), and the
            # dispatcher must be able to take the workload locks this
            # method is about to hold
            if getattr(self, "scheduler", None) is not None:
                self.scheduler.shutdown()
            with self._swap_lock:
                workloads = (list(self.deduplications.values())
                             + list(self.record_linkages.values()))
                self.deduplications = {}
                self.record_linkages = {}
            for wl in workloads:
                with wl.lock:
                    try:
                        wl.close()
                    except Exception:
                        logger.exception(
                            "Error closing workload on shutdown")
        finally:
            self._close_done.set()


class _HttpError(Exception):
    def __init__(self, status: int, message: str, content_type: str = "text/plain",
                 extra_headers: Optional[dict] = None):
        self.status = status
        self.message = message
        self.content_type = content_type
        self.extra_headers = dict(extra_headers or {})


class _BusyError(_HttpError):
    """503 from a workload-lock read timeout (the reference's busy reply,
    App.java:718-725) — its own type so the busy counter counts exactly
    lock-pressure 503s, never e.g. an unready /readyz.

    ``retry_after`` (seconds, from the workload's recent write-hold EWMA)
    rides a ``Retry-After`` header; the reference reply body is
    unchanged."""

    def __init__(self, kind_label: str, retry_after: Optional[int] = None):
        headers = ({"Retry-After": str(retry_after)}
                   if retry_after is not None else None)
        super().__init__(503, _BUSY_TEMPLATE.format(kind=kind_label),
                         extra_headers=headers)


_ENTITY_PATH = re.compile(
    r"^/(deduplication|recordlinkage)/([^/]*)/([^/]*?)(/httptransform)?$"
)
_FEED_PATH = re.compile(r"^/(deduplication|recordlinkage)/([^/]*)$")
_REMATCH_PATH = re.compile(r"^/(deduplication|recordlinkage)/([^/]+)/rematch$")
_DEBUG_TRACE_PATH = re.compile(r"^/debug/traces/([0-9a-f]{32})$")
_DEBUG_DECISION_PATH = re.compile(r"^/debug/decisions/(d\d+)$")

_STATIC_ROUTES = frozenset((
    "/", "/config", "/health", "/healthz", "/readyz", "/metrics", "/stats",
    "/debug/traces", "/debug/requests", "/debug/decisions", "/explain",
    "/debug/profile", "/debug/profile/reset",
    "/debug/costs", "/debug/memory", "/debug/loadmap", "/debug/slo",
    "/debug/probes",
))


def _kind_label(kind: str) -> str:
    """User-facing workload-kind label in error bodies (the reference
    camel-cases recordLinkage — App.java:718)."""
    return "deduplication" if kind == "deduplication" else "recordLinkage"


def _route_template(path: str) -> str:
    """Low-cardinality route label for metrics: path parameters collapse
    to placeholders so a hostile/typo'd URL space cannot mint unbounded
    label values."""
    if path in _STATIC_ROUTES:
        return path
    if _DEBUG_TRACE_PATH.match(path):
        return "/debug/traces/:id"
    if _DEBUG_DECISION_PATH.match(path):
        return "/debug/decisions/:id"
    if m := _REMATCH_PATH.match(path):
        return f"/{m.group(1)}/:name/rematch"
    if m := _ENTITY_PATH.match(path):
        suffix = "/httptransform" if m.group(4) else ""
        return f"/{m.group(1)}/:name/:datasetId{suffix}"
    if m := _FEED_PATH.match(path):
        return f"/{m.group(1)}/:name"
    return "(unmatched)"


class DukeRequestHandler(BaseHTTPRequestHandler):
    app: DukeApp = None  # set by serve()
    protocol_version = "HTTP/1.1"

    # per-request instrumentation state (class-level defaults keep _reply
    # safe for any direct/test caller outside _handle_request)
    _resp_status: Optional[int] = None
    _resp_bytes: int = 0
    request_id: str = "-"
    trace_id: str = "-"

    # -- plumbing -----------------------------------------------------------

    def log_message(self, fmt, *args):
        logger.info("%s %s", self.address_string(), fmt % args)

    def _handle_request(self, method: str, route_fn) -> None:
        """One instrumented request: request-id context, root trace span
        (honoring an inbound W3C ``traceparent``), in-flight gauge,
        route/status counters, latency histogram, byte counters, busy-503
        counter.  The registry children lock for nanoseconds per request
        — HTTP handler threads are never the device scoring path.  The
        root span's exit applies the flight recorder's tail latch, so a
        slow request is retained even when head sampling skipped it."""
        parsed = urlparse(self.path)
        route = _route_template(parsed.path)
        self.request_id = new_request_id()
        request_id_var.set(self.request_id)
        self._resp_status = None
        self._resp_bytes = 0
        busy = False
        hm = self.app.http_metrics
        hm.in_flight.inc()
        t0 = time.monotonic()
        with tracing.start_trace(
            f"{method} {route}",
            traceparent=self.headers.get("traceparent"),
            attributes={
                "http.method": method,
                "http.route": route,
                "http.target": parsed.path,
                "request_id": self.request_id,
            },
        ) as root:
            self.trace_id = root.trace_id
            try:
                try:
                    route_fn(parsed)
                except _HttpError as e:
                    busy = isinstance(e, _BusyError)
                    self._reply(e.status, e.message.encode("utf-8"),
                                e.content_type, e.extra_headers or None)
                except Exception:
                    logger.exception("Error serving %s %s", method, self.path)
                    self._reply_text(500, "Internal server error")
            finally:
                status_code = self._resp_status or 0
                root.set_attribute("http.status", status_code)
                if status_code >= 500:
                    root.status = "error"
                hm.in_flight.dec()
                elapsed = time.monotonic() - t0
                status = str(status_code)
                hm.requests.labels(route=route, method=method,
                                   status=status).inc()
                hm.latency.labels(route=route, method=method).observe(elapsed)
                try:
                    req_bytes = int(self.headers.get("Content-Length") or 0)
                except ValueError:
                    req_bytes = 0
                if req_bytes > 0:
                    hm.request_bytes.labels(route=route).inc(req_bytes)
                if self._resp_bytes:
                    hm.response_bytes.labels(route=route).inc(self._resp_bytes)
                if busy:
                    hm.busy.labels(route=route).inc()
                request_id_var.set("-")

    def _reply(self, status: int, body: bytes, content_type: str = "application/json",
               extra_headers: Optional[dict] = None) -> None:
        self._resp_status = status
        self._resp_bytes += len(body)
        self.send_response(status)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        self.send_header("X-Request-Id", self.request_id)
        self.send_header("X-Trace-Id", self.trace_id)
        # staleness contract during overlapped recovery (ISSUE 15):
        # every response — feeds, /stats, /metrics, errors — carries the
        # header while this app's journal replay runs, so a reader can
        # tell "prefix of the recovered state" from "caught up"
        if self.app is not None and self.app.recovering():
            self.send_header("X-Recovering", "1")
        for k, v in (extra_headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        try:
            self.wfile.write(body)
        except (BrokenPipeError, ConnectionResetError):
            # client went away mid-response; the reference swallows Jetty's
            # EofException the same way (App.java:780-786)
            logger.info("Ignoring client disconnect on %s", self.path)

    def _reply_text(self, status: int, message: str) -> None:
        self._reply(status, message.encode("utf-8"), "text/plain")

    def send_error(self, code, message=None, explain=None):
        """Stdlib error paths (malformed request line, unsupported
        method) bypass ``_reply`` — without this override those are the
        only responses missing the ``X-Request-Id``/``X-Trace-Id``
        correlation headers (ISSUE 2 satellite).

        These calls happen OUTSIDE ``_handle_request`` (the stdlib
        rejects the request before routing), so on a keep-alive
        connection the handler still holds the PREVIOUS request's ids —
        always mint a fresh request id and clear the trace id, or the
        error would correlate to the wrong trace."""
        self.request_id = new_request_id()
        self.trace_id = "-"
        try:
            short = message or BaseHTTPRequestHandler.responses.get(
                code, ("Error",))[0]
        except Exception:
            short = "Error"
        self.close_connection = True
        self._reply(code, short.encode("utf-8", errors="replace"),
                    "text/plain", {"Connection": "close"})

    def _read_body(self) -> bytes:
        try:
            length = int(self.headers.get("Content-Length") or 0)
        except ValueError:
            # unread body bytes would desync the next keep-alive request
            self.close_connection = True
            raise _HttpError(400, "Invalid Content-Length header")
        if length < 0:
            # a negative length would turn rfile.read(length) into
            # read-to-EOF — unbounded buffering, the exact attack the cap
            # exists to stop
            self.close_connection = True
            raise _HttpError(400, "Invalid Content-Length header")
        limit = _max_request_bytes()
        if length > limit:
            # the unread body would be parsed as the next keep-alive
            # request, so the connection closes with the 413
            self.close_connection = True
            raise _HttpError(
                413,
                f"Request body of {length} bytes exceeds the "
                f"{limit}-byte limit (MAX_REQUEST_BYTES)",
            )
        return self.rfile.read(length) if length else b""

    # -- routing ------------------------------------------------------------

    def do_GET(self):
        self._handle_request("GET", self._route_get)

    def do_POST(self):
        self._handle_request("POST", self._route_post)

    def _route_get(self, parsed) -> None:
        self._read_body()  # drain; unread bytes would corrupt keep-alive
        path = parsed.path
        if path == "/":
            self._reply(200, render_homepage(self.app).encode("utf-8"), "text/html")
        elif path == "/config":
            self._reply(200, self.app.config_string.encode("utf-8"), "application/xml")
        elif path in ("/health", "/healthz"):
            # liveness: the process answers — still 200 with a latched
            # flush failure (the process IS alive; /readyz is what goes
            # unready), but the exception is REPORTED here so operators
            # see the dead persistence thread without waiting for a read
            # to drain into it (ISSUE 8 satellite).  /health predates the
            # probe split and stays for compat.
            health = {"status": "ok"}
            flush_errors = self.app.link_flush_errors()
            if flush_errors:
                health["link_flush_errors"] = flush_errors
            # a silently-cold replica is diagnosable (ISSUE 15
            # satellite): the last scorer pre-warm failure per workload
            prewarm_errors = self.app.prewarm_errors()
            if prewarm_errors:
                health["prewarm_errors"] = prewarm_errors
            # canary verdict mismatches are a CORRECTNESS incident: the
            # status flips to degraded (still 200 — the process is
            # alive) and names the offending workloads (ISSUE 20)
            prober = getattr(self.app, "prober", None)
            probe_detail = (prober.health_detail()
                            if prober is not None else None)
            if probe_detail is not None:
                health["status"] = "degraded"
                health["probe_verdict_mismatches"] = probe_detail
            self._reply(200, json.dumps(health).encode("utf-8"),
                        "application/json")
        elif path == "/readyz":
            self._handle_readyz()
        elif path == "/metrics":
            self._handle_metrics()
        elif path == "/stats":
            self._handle_stats()
        elif path == "/debug/traces":
            self._reply(*debug_api.handle_traces())
        elif m := _DEBUG_TRACE_PATH.match(path):
            fmt = (parse_qs(parsed.query).get("format") or ["json"])[0]
            self._reply(*debug_api.handle_trace(m.group(1), fmt))
        elif path == "/debug/requests":
            self._reply(*debug_api.handle_requests())
        elif path == "/debug/decisions":
            self._reply(*debug_api.handle_decisions(self.app))
        elif m := _DEBUG_DECISION_PATH.match(path):
            self._reply(*debug_api.handle_decision(self.app, m.group(1)))
        elif path == "/debug/profile":
            self._reply(*debug_api.handle_profile_status())
        elif path == "/debug/costs":
            self._reply(*debug_api.handle_costs(
                debug_api._app_workloads(self.app)))
        elif path == "/debug/memory":
            self._reply(*debug_api.handle_memory())
        elif path == "/debug/loadmap":
            # the single-process plane routes nothing through a
            # federation router; the payload reports zero ranges (the
            # federation plane serves its router's live heat map)
            self._reply(*debug_api.handle_loadmap(None))
        elif path == "/debug/slo":
            self._reply(*debug_api.handle_slo())
        elif path == "/debug/probes":
            self._reply(*debug_api.handle_probes(
                getattr(self.app, "prober", None)))
        elif m := _ENTITY_PATH.match(path):
            self._validate_entity_path(m)
            raise _HttpError(405, "This endpoint only supports POST requests.")
        elif m := _FEED_PATH.match(path):
            self._handle_feed(m, parse_qs(parsed.query))
        else:
            raise _HttpError(404, "Not found")

    def _route_post(self, parsed) -> None:
        # read the body up front: replying with the body unread would
        # leave its bytes to be parsed as the next keep-alive request
        body = self._read_body()
        path = parsed.path
        if path == "/config":
            self._handle_config_upload(body)
        elif path == "/explain":
            self._reply(*debug_api.handle_explain(self.app, body))
        elif path == "/debug/profile":
            self._reply(*debug_api.handle_profile_start(
                parse_qs(parsed.query)))
        elif path == "/debug/profile/reset":
            self._reply(*debug_api.handle_profile_reset())
        elif m := _REMATCH_PATH.match(path):
            self._handle_rematch(m, body)
        elif m := _ENTITY_PATH.match(path):
            self._handle_post_batch(m, body)
        else:
            raise _HttpError(404, "Not found")

    # -- handlers -----------------------------------------------------------

    def _handle_readyz(self) -> None:
        ready, checks = self.app.readiness()
        http_status = 200 if ready else 503
        if ready:
            status = "ready"
        elif not checks.get("recovery_complete", True):
            # startup journal replay still running: a distinct status so
            # orchestrators (and humans) can tell "redoing the link log"
            # from a genuinely broken dependency
            status = "recovering"
            # overlapped recovery (ISSUE 15, default on): reads already
            # serve the replay's committed prefix, so when the replay is
            # the ONLY thing unready, /readyz answers 200 — the
            # "recovering" 503 window shrinks to the write path (POSTs
            # 503 per-request until write_ready flips).  The legacy
            # serial mode keeps the whole-app 503.
            read_ready = all(v for k, v in checks.items()
                             if k not in ("recovery_complete",
                                          "write_ready"))
            if read_ready and env_flag("DUKE_RECOVERY_OVERLAP", True):
                http_status = 200
        else:
            status = "unready"
        body = json.dumps(
            {"status": status, "checks": checks}
        ).encode("utf-8")
        self._reply(http_status, body, "application/json")

    def _handle_metrics(self) -> None:
        body = telemetry.render(
            self.app.metrics, telemetry.GLOBAL
        ).encode("utf-8")
        self._reply(200, body, telemetry.CONTENT_TYPE)

    def _handle_stats(self):
        """Observability endpoint (new in this build — the reference has no
        metrics/health surface, SURVEY.md section 5.5): per-workload
        ProfileStats counters plus corpus sizes.

        Reads the same lock-free single-writer state the /metrics
        collector scrapes (ProfileStats, live_records, PhaseRecorder,
        LinkDatabase.count) — the JSON shape predates /metrics and stays
        backward-compatible; uptime/platform/device_count/links_rows and
        the per-phase seconds are additive."""
        platform, device_count = backend_info()
        out = {
            "backend": self.app.backend,
            "platform": platform,
            "device_count": device_count,
            "uptime_seconds": round(
                time.monotonic() - self.app.started_monotonic, 3
            ),
            "workloads": [],
        }
        # operator summary of the digest-keyed feature cache (PR 4):
        # until now the hit rate existed only as raw Prometheus series
        from ..ops import feature_cache as FC

        hits, misses, evicted, cache_bytes = FC.stats()
        looked_up = hits + misses
        out["feature_cache"] = {
            "hits": hits,
            "misses": misses,
            "evicted": evicted,
            "bytes": cache_bytes,
            "hit_rate": round(hits / looked_up, 4) if looked_up else None,
        }
        # audit-loss visibility: drop-on-overflow is by design, but an
        # operator treating the JSONL as evidence needs to SEE the loss
        from ..telemetry.decisions import audit_log

        # ingest-scheduler health (ISSUE 6): queue depths, admission
        # split, microbatch fill and the live Retry-After hint per tenant
        if self.app.scheduler is not None:
            out["scheduler"] = self.app.scheduler.stats_snapshot()
        # feed-stream abort visibility (satellite): mid-stream bail-outs
        # truncate chunked framing, invisible to any scrape until now
        with self.app._feed_abort_lock:
            out["feed_aborts"] = dict(self.app.feed_aborts)
        audit = audit_log()
        if audit is not None:
            out["audit_log"] = {
                "path": audit.path,
                "entries": audit.entries,
                "dropped_batches": audit.dropped,
                "disabled": audit.disabled,
            }
        for kind, registry in (
            ("deduplication", self.app.deduplications),
            ("recordlinkage", self.app.record_linkages),
        ):
            for name, wl in registry.items():
                stats = getattr(wl.processor, "stats", None)
                # live (non-dukeDeleted) indexed records, via the O(1)
                # counters the backends maintain (device/ann:
                # live_records; host: len(index)) — lock-free, so a
                # long-running ingest batch never stalls /stats and
                # /stats never stalls ingest
                live = getattr(wl.index, "live_records", None)
                row = {
                    "kind": kind,
                    "name": name,
                    "records_indexed": (
                        live if live is not None else len(wl.index)
                    ),
                }
                try:
                    row["links_rows"] = wl.link_database.count()
                except Exception:
                    pass  # closed/raced link DB: omit rather than 500
                if stats is not None:
                    row.update(
                        batches=stats.batches,
                        records_processed=stats.records_processed,
                        candidates_retrieved=stats.candidates_retrieved,
                        pairs_compared=stats.pairs_compared,
                        retrieval_seconds=round(stats.retrieval_seconds, 3),
                        compare_seconds=round(stats.compare_seconds, 3),
                    )
                    # decisive-band split (PR 3): survivors rescored
                    # host-exact vs certifiably skipped, previously only
                    # visible as duke_finalize_pairs_total series
                    if getattr(wl.processor, "finalizer", None) is not None:
                        finalized = stats.pairs_rescored + stats.pairs_skipped
                        row["finalize"] = {
                            "rescored": stats.pairs_rescored,
                            "skipped": stats.pairs_skipped,
                            "skip_rate": (
                                round(stats.pairs_skipped / finalized, 4)
                                if finalized else None
                            ),
                        }
                recorder = getattr(wl.processor, "decisions", None)
                if recorder is not None and recorder.enabled:
                    row["decisions"] = {
                        "outcomes": dict(recorder.outcomes),
                        "disagreements": recorder.disagreements,
                        "ring": len(recorder.ring),
                        "latched": recorder.latched,
                    }
                phases = getattr(wl.processor, "phases", None)
                if phases is not None:
                    row["phase_seconds"] = {
                        k: round(v, 3)
                        for k, v in phases.phase_seconds().items()
                    }
                out["workloads"].append(row)
        self._reply(200, json.dumps(out).encode("utf-8"), "application/json")

    def _workloads(self, kind: str) -> Dict[str, Workload]:
        return (self.app.deduplications if kind == "deduplication"
                else self.app.record_linkages)

    def _validate_entity_path(self, m) -> Tuple[str, Workload, str, bool]:
        kind, name, dataset_id, transform = m.group(1), m.group(2), m.group(3), bool(m.group(4))
        label = _kind_label(kind)
        if not name:
            raise _HttpError(404, f"The {label}Name cannot be an empty string!")
        if not dataset_id:
            raise _HttpError(404, "The datasetId cannot be an empty string!")
        if is_probe_name(name) or is_probe_name(dataset_id):
            # namespace-exclusion contract (ISSUE 20): probe shadows are
            # never HTTP-addressable, even by their real names
            raise _HttpError(
                404, "The '__probe__' namespace is reserved for the "
                     "synthetic canary prober.")
        workload = self._workloads(kind).get(name)
        if workload is None:
            raise _HttpError(
                404,
                f"Unknown {label} '{name}'! (All {label}s must be specified in "
                f"the configuration)",
            )
        if dataset_id not in workload.datasources:
            raise _HttpError(
                404, f"Unknown dataset-id '{dataset_id}' for the {label} '{name}'!"
            )
        return kind, workload, dataset_id, transform

    @staticmethod
    def _parse_batch(body: bytes):
        """The POST body as ``(entities, single)``: one JSON object or an
        array of them."""
        try:
            payload = json.loads(body.decode("utf-8"))
        except (ValueError, UnicodeDecodeError):
            raise _HttpError(400, "Request body must be a JSON array or object")
        if isinstance(payload, dict):
            batch, single = [payload], True
        elif isinstance(payload, list):
            batch, single = payload, False
        else:
            raise _HttpError(400, "Request body must be a JSON array or object")
        for entity in batch:
            if not isinstance(entity, dict):
                raise _HttpError(400, "Batch elements must be JSON objects")
        return batch, single

    def _handle_post_batch(self, m, body: bytes) -> None:
        with tracing.span("http.parse", annotate=True):
            batch, single = self._parse_batch(body)
            kind, workload, dataset_id, transform = \
                self._validate_entity_path(m)
            if not transform:
                self._check_write_fence(kind, m.group(2), workload)
        sched = self.app.scheduler
        if sched is not None and not transform:
            # continuous microbatching (ISSUE 6): the scheduler coalesces
            # concurrent POSTs into device-shaped microbatches, applies
            # queue-depth admission control, and dispatches fairly across
            # workloads.  Transforms stay on the direct lock path — their
            # response rows are per-request state on the shared listener.
            name, label = m.group(2), _kind_label(kind)
            try:
                sched.submit(kind, name, dataset_id, batch)
            except SchedulerReject as e:
                raise _HttpError(
                    429,
                    f"The {label} '{name}' ingest queue is full "
                    f"({e.depth} requests pending). Please retry after "
                    f"{e.retry_after}s.",
                    extra_headers={"Retry-After": str(e.retry_after)},
                )
            except WorkloadGone:
                raise _HttpError(
                    404,
                    f"Unknown {label} '{name}'! (All {label}s must be "
                    f"specified in the configuration)",
                )
            except DatasetGone as e:
                # a reload replaced the workload with one lacking the
                # dataset after admission validated it — same 404 the
                # up-front validation answers
                raise _HttpError(
                    404,
                    f"Unknown dataset-id '{e.dataset_id}' for the "
                    f"{label} '{name}'!",
                )
            except SchedulerClosed:
                raise _HttpError(503, "The service is shutting down.")
            except ArenaAdmissionError as e:
                # the corpus no longer fits the HBM budget even after
                # spilling every other tenant (ISSUE 19): a loud,
                # actionable 503 — never an allocator OOM
                raise _HttpError(
                    503, f"HBM budget exhausted: {e}",
                    extra_headers={"Retry-After": "30"},
                )
            except _HttpError:
                raise
            except Exception as e:
                logger.exception("Batch processing failed")
                raise _HttpError(500, f"Batch processing failed: {e}")
            rows = []
        else:
            while True:
                # re-resolve until a live workload accepts the batch: a
                # config reload can replace the registry entry between
                # lookup and lock (submit_batch returns None for a replaced
                # workload); ingest requests merge into per-workload device
                # microbatches inside submit_batch
                kind, workload, dataset_id, transform = \
                    self._validate_entity_path(m)
                try:
                    rows = workload.submit_batch(dataset_id, batch,
                                                 http_transform=transform)
                except ArenaAdmissionError as e:
                    raise _HttpError(
                        503, f"HBM budget exhausted: {e}",
                        extra_headers={"Retry-After": "30"},
                    )
                except Exception as e:
                    logger.exception("Batch processing failed")
                    raise _HttpError(500, f"Batch processing failed: {e}")
                if rows is not None:
                    break

        if transform:
            out = rows[0] if single and len(rows) == 1 else rows
            self._reply(200, json.dumps(out).encode("utf-8"))
        else:
            # time-to-first-200 (ISSUE 15): the cold-start gauge stamps
            # on the first successfully served scoring batch
            self.app.note_first_batch()
            self._reply(200, b'{"success": true}')

    def _check_write_fence(self, kind: str, name: str, workload) -> None:
        """503 a scoring POST while this workload's link journal is
        still replaying (overlapped recovery, ISSUE 15): the wrapper
        itself would fence the write anyway — blocking the handler
        thread for the whole replay — so the HTTP layer answers fast
        with Retry-After instead.  Reads are unaffected."""
        db = workload.link_database
        if getattr(db, "recovering", False):
            label = _kind_label(kind)
            # no explicit X-Recovering here: _reply adds it for every
            # response while the app recovers, and this error only fires
            # then — a second copy would duplicate the header
            raise _HttpError(
                503,
                f"The {label} '{name}' is replaying its link journal; "
                "writes resume when recovery completes.",
                extra_headers={"Retry-After": "1"},
            )

    def _handle_feed(self, m, query) -> None:
        """Stream the incremental link feed in bounded pages.

        The reference materializes and writes every row while holding the
        workload lock (App.java:827-874); at millions of links that 503s
        every other reader and blocks writers for the whole response.
        Here each page (FEED_PAGE_SIZE links) takes the lock only for the
        link fetch + record resolution; JSON serialization and the socket
        write happen outside it, and the response is chunked so no full
        materialization ever exists.  The wire format is unchanged
        (same bytes as the reference's single array).
        """
        kind, name = m.group(1), m.group(2)
        label = _kind_label(kind)
        if not name:
            raise _HttpError(400, f"The {label}Name cannot be an empty string!")
        if is_probe_name(name):
            # feed filter half of the namespace-exclusion contract: no
            # probe shadow's links are ever served to a ?since= poller
            raise _HttpError(
                400, "The '__probe__' namespace is reserved for the "
                     "synthetic canary prober.")
        since = 0
        since_params = query.get("since")
        if since_params and since_params[0]:
            try:
                since = int(since_params[0])
            except ValueError:
                raise _HttpError(400, f"Invalid since value '{since_params[0]}'")

        if self.request_version == "HTTP/1.0":
            # HTTP/1.0 clients don't decode chunked framing; serve them the
            # buffered single-array reply (same bytes, Content-Length'd)
            self._handle_feed_buffered(m, kind, name, label, since)
            return

        page_size = _feed_page_size()
        cursor = since
        t0 = time.monotonic()
        started = False   # headers sent (can't switch to an error reply after)
        first_row = True
        lock_attempts = 0
        lock_deadline: Optional[float] = None
        try:
            while True:
                workload = self._workloads(kind).get(name)
                if workload is None:
                    if started:
                        # config reload removed the workload mid-stream: a
                        # clean ']' would make the truncated feed look
                        # complete — kill the chunked framing instead so
                        # the client sees a protocol error
                        logger.warning(
                            "Aborting %s feed stream: workload removed "
                            "by config reload mid-stream", name,
                        )
                        self.app.count_feed_abort("workload_removed")
                        self.close_connection = True
                        return
                    raise _HttpError(
                        400,
                        f"Unknown {label} '{name}'! (All {label}s must be "
                        f"specified in the configuration)",
                    )
                # chaos hook (DUKE_FAULTS slow_lock): deterministic stall
                # before the acquire, driving the deadline path in tests
                from ..utils import faults

                plan = faults.active()
                if plan is not None:
                    stall = plan.lock_delay()
                    if stall:
                        time.sleep(stall)
                if not workload.lock.acquire(timeout=READ_LOCK_TIMEOUT_SECONDS):
                    if not started:
                        # pre-stream: the abort response is the busy 503,
                        # Retry-After derived from the recent write-hold
                        # EWMA (the reference's 1 s try-then-503)
                        raise _BusyError(label, workload.busy_retry_after())
                    # mid-stream contention: no in-band error channel
                    # exists once streaming, so retry — with exponential
                    # backoff + jitter under a wall-clock deadline
                    # (ISSUE 8 satellite; was 120 fixed 1 s retries).  A
                    # wedged writer truncates the chunked framing at the
                    # deadline so the client sees a protocol error, never
                    # silent partial success.
                    now = time.monotonic()
                    if lock_deadline is None:
                        lock_deadline = now + _feed_retry_deadline()
                    lock_attempts += 1
                    if now >= lock_deadline:
                        logger.warning(
                            "Aborting %s feed stream: workload lock "
                            "unavailable past the %.0f s deadline "
                            "(%d attempts)", name, _feed_retry_deadline(),
                            lock_attempts,
                        )
                        self.app.count_feed_abort("deadline")
                        self.close_connection = True
                        return
                    time.sleep(min(_feed_backoff_delay(lock_attempts),
                                   max(0.0, lock_deadline - now)))
                    continue
                lock_attempts = 0
                lock_deadline = None
                try:
                    if workload.closed:
                        continue  # replaced by reload: re-resolve registry
                    rows, cursor = workload.links_page(cursor, page_size)
                finally:
                    workload.lock.release()
                if not started:
                    self._resp_status = 200
                    self.send_response(200)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Transfer-Encoding", "chunked")
                    self.send_header("X-Request-Id", self.request_id)
                    self.send_header("X-Trace-Id", self.trace_id)
                    # staleness signal: this stream is a monotonic
                    # PREFIX of the recovered feed while replay runs
                    if self.app.recovering():
                        self.send_header("X-Recovering", "1")
                    self.end_headers()
                    self._write_chunk(b"[")
                    started = True
                if rows:
                    payload = ",\n".join(json.dumps(r) for r in rows)
                    if not first_row:
                        payload = ",\n" + payload
                    first_row = False
                    self._write_chunk(payload.encode("utf-8"))
                if len(rows) < page_size:
                    break
            # always-on feed SLO signal (ISSUE 16): backlog walk wall
            # time against DUKE_SLO_FEED_MS; reaching the short page
            # means the feed is caught up, so the lag meter stops aging
            slo.tracker("feed", kind, name).record(
                time.monotonic() - t0,
                trace_id=tracing.sampled_trace_id())
            slo.feed_meter(kind, name).note_drain()
            if started:
                self._write_chunk(b"]")
                self.wfile.write(b"0\r\n\r\n")
        except (BrokenPipeError, ConnectionResetError):
            # client went away mid-stream (reference swallows Jetty's
            # EofException the same way, App.java:878-884)
            logger.info("Ignoring client disconnect on %s", self.path)
            self.close_connection = True
        except Exception:
            if not started:
                raise  # pre-headers: the generic 500 path still works
            # mid-stream failure: no in-band error channel; truncate the
            # chunked stream (clients see a protocol error, not silent
            # partial success)
            logger.exception("Error mid-stream on %s", self.path)
            self.close_connection = True

    def _write_chunk(self, data: bytes) -> None:
        self._resp_bytes += write_chunk(self.wfile, data)

    def _handle_feed_buffered(self, m, kind: str, name: str, label: str,
                              since: int) -> None:
        """Pre-streaming feed path for HTTP/1.0 clients: one buffered
        array with Content-Length (holds the lock for the full fetch,
        like the reference)."""
        t0 = time.monotonic()
        while True:
            workload = self._workloads(kind).get(name)
            if workload is None:
                raise _HttpError(
                    400,
                    f"Unknown {label} '{name}'! (All {label}s must be "
                    f"specified in the configuration)",
                )
            if not workload.lock.acquire(timeout=READ_LOCK_TIMEOUT_SECONDS):
                raise _BusyError(label, workload.busy_retry_after())
            try:
                if workload.closed:
                    continue
                rows = workload.links_since(since)
                break
            finally:
                workload.lock.release()
        slo.tracker("feed", kind, name).record(
            time.monotonic() - t0, trace_id=tracing.sampled_trace_id())
        slo.feed_meter(kind, name).note_drain()
        body = "[" + ",\n".join(json.dumps(r) for r in rows) + "]"
        self._reply(200, body.encode("utf-8"))

    def _handle_rematch(self, m, body: bytes) -> None:
        """Admin extension: bulk corpus-vs-corpus re-match through the
        ring layout (engine.rematch) — link-DB backfill / re-population.
        The reference has no bulk operations; a dataset literally named
        'rematch' still wins the route (ingest takes precedence, with the
        posted batch intact)."""
        kind, name = m.group(1), m.group(2)
        workload = self._workloads(kind).get(name)
        if workload is not None and "rematch" in workload.datasources:
            self._handle_post_batch(
                _ENTITY_PATH.match(f"/{kind}/{name}/rematch"), body
            )
            return
        label = _kind_label(kind)
        if workload is None:
            raise _HttpError(
                404,
                f"Unknown {label} '{name}'! (All {label}s must be specified "
                f"in the configuration)",
            )
        from ..engine.rematch import ring_rematch

        # bulk re-match writes the link DB: same recovery fence as ingest
        self._check_write_fence(kind, name, workload)
        with workload.lock:
            if workload.closed:
                raise _BusyError(label)
            try:
                stats = ring_rematch(workload)
            except ValueError as e:
                raise _HttpError(400, str(e))
            except Exception as e:
                logger.exception("ring re-match failed")
                raise _HttpError(500, f"Re-match failed: {e}")
        self._reply(200, json.dumps(stats).encode("utf-8"))

    def _handle_config_upload(self, body: bytes) -> None:
        content_type = self.headers.get("Content-Type", "")
        config_string = None
        if content_type.startswith("multipart/form-data"):
            config_string = _extract_multipart_field(content_type, body, "configfile")
            if config_string is None:
                raise _HttpError(400, "Missing multipart field 'configfile'")
        else:
            # convenience divergence: accept the raw XML as the request body
            config_string = body.decode("utf-8", errors="replace")
        try:
            self.app.reload_from_string(config_string)
        except ConfigError as e:
            raise _HttpError(400, f"Invalid configuration: {e}")
        except Exception as e:
            logger.exception("Config reload failed")
            raise _HttpError(500, f"Config reload failed: {e}")
        # success: redirect to the homepage (App.java:682)
        self._reply(302, b"ok", "text/plain", {"Location": "/"})


def _extract_multipart_field(content_type: str, body: bytes,
                             field: str) -> Optional[str]:
    """Minimal multipart/form-data parsing via the stdlib email parser."""
    message = BytesParser(policy=email_policy).parsebytes(
        b"Content-Type: " + content_type.encode("latin-1") + b"\r\n\r\n" + body
    )
    if not message.is_multipart():
        return None
    for part in message.iter_parts():
        if part.get_param("name", header="content-disposition") == field:
            payload = part.get_payload(decode=True)
            return payload.decode("utf-8", errors="replace")
    return None


def install_shutdown_handlers(app: DukeApp, server) -> None:
    """SIGTERM/SIGINT graceful shutdown (ISSUE 10 satellite): stop
    accepting, drain the ingest scheduler, flush the write-behind link
    batches, save corpus snapshots, close — so an orchestrated restart
    (docker stop, k8s rolling update) finds an empty journal and a warm
    snapshot and never even enters recovery.

    The handler itself only spawns the shutdown thread (signal context
    must not block on workload locks); ``server.shutdown()`` unblocks
    ``serve_forever`` and ``DukeApp.close()`` runs the drain sequence.
    A second signal is a no-op (``close`` is idempotent), NOT an
    escalation — a hard kill is what the crash-recovery journal exists
    for."""
    import signal

    def _shutdown(signum, frame):
        logger.info("signal %d: graceful shutdown (drain -> flush -> "
                    "snapshot -> close)", signum)

        def _run():
            server.shutdown()  # stop accepting; in-flight requests finish
            app.close()

        threading.Thread(target=_run, daemon=True,
                         name="graceful-shutdown").start()

    signal.signal(signal.SIGTERM, _shutdown)
    signal.signal(signal.SIGINT, _shutdown)


def create_app(config: Optional[ServiceConfig] = None, *, backend: str = "host",
               persistent: bool = True) -> DukeApp:
    if config is None:
        config = load_default_config()
    return DukeApp(config, backend=backend, persistent=persistent)


def serve(app: DukeApp, port: int = DEFAULT_PORT,
          host: str = "0.0.0.0") -> ThreadingHTTPServer:
    handler = type("BoundHandler", (DukeRequestHandler,), {"app": app})
    server = ThreadingHTTPServer((host, port), handler)
    return server
