"""Telemetry subsystem: metrics registry, Prometheus exposition, request ids.

Two registries exist at runtime:

  * ``GLOBAL`` (here) — process-wide instruments owned by layers that
    have no DukeApp in scope: the JIT compile/cache counters
    (utils/jit_cache.py), query-padding-bucket and corpus-growth
    counters (engine/device_matcher.py), mesh/dispatch instruments
    (engine/sharded_matcher.py, parallel/dispatch.py).
  * a per-``DukeApp`` registry (service/metrics.py) — HTTP families and
    the workload-walking collector (engine phase histograms, corpus
    gauges, link-store rows).  Per-app so tests and hot reloads never
    leak series across app instances.

``GET /metrics`` renders both (``registry.render(app.metrics, GLOBAL)``).

Naming scheme: every family is ``duke_<subsystem>_<metric>[_total]`` with
base units (seconds, bytes, rows); latency histograms share the fixed
log-scale ladder ``DEFAULT_LATENCY_BUCKETS``.
"""

from .registry import (  # noqa: F401
    CONTENT_TYPE,
    DEFAULT_LATENCY_BUCKETS,
    Counter,
    FamilySnapshot,
    Gauge,
    Histogram,
    MetricRegistry,
    PhaseRecorder,
    histogram_snapshot,
    render,
)

# the tracing layer (ISSUE 2): imported as a submodule attribute so every
# layer can `from ..telemetry import tracing` without a second import line
from . import tracing  # noqa: F401  (imports only stdlib + .logctx)

GLOBAL = MetricRegistry()

# -- JIT layer (written via utils/jit_cache record_* helpers) ----------------
# Label-less: both exist (at 0) from first import, so the acceptance
# contract — /metrics always includes the JIT compile counter — holds on
# every backend including pure-host serving.  Unlocked: the cache-hit
# increment sits on the per-block scoring path, which must stay
# lock-free; concurrent workloads racing an increment may very rarely
# lose a count, accepted for these visibility counters.
JIT_COMPILES = GLOBAL.counter(
    "duke_jit_compiles_total",
    "Scorer/updater program builds (jit cache misses + pre-warm compiles)",
    locked=False,
)
JIT_CACHE_HITS = GLOBAL.counter(
    "duke_jit_cache_hits_total",
    "Scorer lookups served from the in-process jit program cache",
    locked=False,
)
# AOT executable cache (ISSUE 15: utils/jit_cache.AotStore).  Loads run
# at startup / on shape-fingerprint changes, never per block, so the
# labeled family is fine; the per-call fast path only bumps
# JIT_CACHE_HITS above.
AOT_LOADS = GLOBAL.counter(
    "duke_aot_loads_total",
    "Plan-keyed AOT executable-store load attempts by outcome (hit = "
    "deserialized and serving, miss = no entry for the key, reject = "
    "entry present but unusable — recompiled and re-saved by the warm "
    "thread)",
    ("outcome",),
)
PREWARM_FAILURES = GLOBAL.counter(
    "duke_prewarm_failures_total",
    "Scorer pre-warm / AOT warm-thread failures: scoring still works but "
    "the replica is silently cold (first-contact shapes pay live "
    "compiles).  The last error is surfaced in /healthz detail.",
)
COLD_START_SECONDS = GLOBAL.gauge(
    "duke_cold_start_seconds",
    "Seconds from service construction to the first successfully served "
    "scoring batch (time-to-first-200; 0 until a batch lands)",
)

# -- device corpus growth (engine/device_matcher.py) -------------------------
# Process-wide (not per-corpus) so value-slot rebuilds — which replace the
# corpus object — can never reset the series mid-scrape.
CORPUS_GROWTHS = GLOBAL.counter(
    "duke_corpus_capacity_growths_total",
    "Corpus capacity-doubling events (each forces a full device re-upload)",
)
CORPUS_FULL_UPLOADS = GLOBAL.counter(
    "duke_corpus_full_uploads_total",
    "Whole-corpus device uploads (growth, restore, or mask-refresh fallback)",
)

# -- query padding buckets (engine/device_matcher.py) ------------------------
# Unlocked: incremented once per dispatched block by the thread holding
# that workload's lock; concurrent blocks from DIFFERENT workloads can in
# principle race a child, and the rare lost count is accepted — these
# counters exist to make recompile storms and padding waste visible, and
# the scoring path must stay lock-free (acceptance criterion).
QUERY_BLOCKS = GLOBAL.counter(
    "duke_query_blocks_total",
    "Dispatched query blocks by padded bucket size",
    ("bucket",), locked=False,
)
QUERY_PAD_ROWS = GLOBAL.counter(
    "duke_query_padding_rows_total",
    "Padding rows added to reach the block's bucket size",
    ("bucket",), locked=False,
)
# Same unlocked discipline, children pre-resolved in device_matcher: each
# brute-force scorer call (K-escalation re-runs included) adds the rows
# its program scans and the corpus capacity it could have scanned.
DEVICE_SCAN_ROWS = GLOBAL.counter(
    "duke_device_scan_rows_total",
    "Corpus rows the brute-force device scorer scanned per call "
    "(part=scanned: up to the valid high-water mark on one device, the "
    "whole capacity on a mesh) and the capacity it covered "
    "(part=capacity)",
    ("part",), locked=False,
)
SCORER_ESCALATIONS = GLOBAL.counter(
    "duke_scorer_escalations_total",
    "K/C-escalation re-runs of the device scoring program",
)
# stage-attributed escalation series (ISSUE 9): which retrieval stage
# saturated — brute-force top-K, flat-ANN top-C, or the IVF cell probe
# (whose ladder widens nprobe and terminally falls back to the flat
# scan).  The label set is closed (three stages), written only on the
# rare escalation path.
RETRIEVAL_ESCALATIONS = GLOBAL.counter(
    "duke_retrieval_escalations_total",
    "Retrieval-width escalation re-runs by saturated stage "
    "(top_k = brute force, top_c = flat ANN, ivf = cell probe)",
    ("stage",),
)

# -- streaming encode (engine/device_matcher.py) -----------------------------
# Unlocked: incremented by the thread holding the workload lock (same
# discipline as QUERY_BLOCKS).  The encode-cache hit/miss/evicted rows and
# cache-bytes gauge are scrape-time snapshots of ops.feature_cache state
# (service/metrics.make_process_collector) — the encode path never writes
# a registry child for them.
STREAM_APPEND_SLICES = GLOBAL.counter(
    "duke_stream_append_slices_total",
    "Device-corpus append slices flushed under the extract/upload overlap "
    "(DUKE_STREAM_APPEND)",
    locked=False,
)

# -- multi-host dispatch (parallel/dispatch.py) ------------------------------
DISPATCH_OPS = GLOBAL.counter(
    "duke_dispatch_ops_total",
    "Ops broadcast on the multi-host dispatch stream, by op tag",
    ("op",),
)
DISPATCH_BYTES = GLOBAL.counter(
    "duke_dispatch_bytes_total",
    "Serialized bytes broadcast on the multi-host dispatch stream",
)
DISPATCH_FOLLOWERS = GLOBAL.gauge(
    "duke_dispatch_followers",
    "Connected follower processes (frontend only)",
)
DISPATCH_DOWN = GLOBAL.gauge(
    "duke_dispatch_down",
    "1 once the dispatcher latched failed (mesh ops refused until restart)",
)
FOLLOWER_REPLAY_SECONDS = GLOBAL.histogram(
    "duke_follower_replay_seconds",
    "Follower-side replay time per dispatch op",
    ("op",),
)

# -- HA serving group (ISSUE 8: parallel/dispatch.py, links/replica.py) ------
FOLLOWER_EVICTIONS = GLOBAL.counter(
    "duke_follower_evictions_total",
    "Followers evicted from the serving group after exhausted send "
    "retries, a dead digest handshake, or mirror divergence — the slice "
    "degrades to the survivors instead of latching down",
)
DISPATCH_EPOCH = GLOBAL.gauge(
    "duke_epoch",
    "Leadership epoch fencing the dispatch op stream (followers reject "
    "lower-epoch ops from a zombie ex-leader; promotion bumps it)",
)
REPLICA_LAG = GLOBAL.gauge(
    "duke_replica_lag_ops",
    "Link-stream ops this follower has seen but not yet applied to its "
    "replica link DB (head seq - applied watermark), by workload",
    ("kind", "workload"),
)
FAULTS_INJECTED = GLOBAL.counter(
    "duke_faults_injected_total",
    "Faults injected by the deterministic DUKE_FAULTS chaos layer, by "
    "kind",
    ("kind",),
)

# -- crash-consistent ingest (ISSUE 10: links/journal.py, recovery) ----------
# Written on startup/rare paths only; the journal's per-workload gauges
# (duke_journal_batches, duke_journal_bytes) are scrape-time snapshots in
# the app collector, so the append path never writes a registry child.
JOURNAL_TORN_TAILS = GLOBAL.counter(
    "duke_journal_torn_tails_total",
    "Torn or corrupt link-journal tails truncated by the startup scan "
    "(a crash mid-append; bounded to the final partial frame, logged, "
    "never fatal)",
)
RECOVERY_REPLAYED = GLOBAL.counter(
    "duke_recovery_replayed_total",
    "Journaled link batches replayed into the durable link store by "
    "startup recovery (batches a crash stranded between ack and flush)",
)
# recovery progress (ISSUE 16): while /readyz says `recovering`, these
# distinguish "almost done" from "wedged" — remaining counts down chunk
# by chunk as the replay loop applies, applied counts up monotonically.
RECOVERY_REPLAY_REMAINING = GLOBAL.gauge(
    "duke_recovery_replay_remaining_batches",
    "Journaled link batches still awaiting replay by the running "
    "startup recovery (0 when recovery is idle or done)",
)
RECOVERY_REPLAY_APPLIED = GLOBAL.counter(
    "duke_recovery_replay_applied_total",
    "Journaled link batches applied by startup recovery replay loops "
    "since process start (advances chunk by chunk while /readyz still "
    "says recovering)",
)
SNAPSHOT_FALLBACKS = GLOBAL.counter(
    "duke_snapshot_fallbacks_total",
    "Corpus snapshots rejected into a full store replay, by reason "
    "(corrupt = unreadable archive, checksum = stamped content checksum "
    "mismatch, content = store drifted past the snapshot, schema = "
    "plan/tensor-shape mismatch, fingerprint = env/plan fingerprint "
    "mismatch)",
    ("reason",),
)

# -- mesh (engine/sharded_matcher.py) ----------------------------------------
MESH_DEVICES = GLOBAL.gauge(
    "duke_mesh_devices",
    "Devices in the serving mesh (0 until a sharded backend builds one)",
)

# -- runtime SLO signals (ISSUE 16: telemetry/slo.py) ------------------------
# Imported last: slo only needs .env/.registry, and registering its
# scrape-time collector here keeps every process that renders GLOBAL —
# leader app, replica plane, federation plane — serving the burn-rate,
# latency-objective and feed-lag families with no per-surface wiring.
from . import slo  # noqa: E402,F401

GLOBAL.register_collector(slo.collect)

# -- resource attribution (ISSUE 17: telemetry/{costs,memory}.py) ------------
# Same pattern: the device-time cost ledger and the HBM ledger register
# scrape-time collectors on GLOBAL so every plane serves the busy /
# compile / utilization and headroom / overflow families for free.
from . import costs  # noqa: E402,F401
from . import memory  # noqa: E402,F401

GLOBAL.register_collector(costs.collect)
GLOBAL.register_collector(memory.collect)
