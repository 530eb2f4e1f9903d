"""The TPU-native matching backend: device-resident corpus + batched scoring.

Replaces the reference hot path (per-record Lucene candidate query + per-pair
scalar comparator dispatch — SURVEY.md section 3.2, hot loops 1-2) with one
XLA program per query block: the whole corpus lives on device as padded
feature tensors (``ops.features``), a jitted blockwise scorer
(``ops.scoring.build_corpus_scorer``) scores every query against every
corpus row in chunks keeping a running top-K, and the host only finalizes
the surviving K pairs per query.  The scan stops at the corpus's valid
high-water mark (``DeviceCorpus.valid_hwm``), not its capacity: the rows
past it are all masked, so skipping them changes no result.

Semantics contract (held to the host engine by differential tests in
``tests/test_device_matcher.py``):

  * exact brute-force blocking — candidates are a superset of anything
    Lucene retrieves, so recall can only improve (SURVEY.md section 7
    "blocking recall parity");
  * the match/maybe/no-match events equal the host ``engine.processor``'s
    for every pair whose probability clears ``min(threshold,
    maybe_threshold)``: device logits are exact for device-kernel
    properties, and host-only comparators are re-scored exactly for the
    surviving pairs (optimistic-bound pruning, ``ops.scoring``);
  * multi-valued properties score all value pairs on device: the value
    axis auto-sizes to the data (``_maybe_grow_value_slots``, capped by
    ``DEVICE_VALUE_SLOTS_MAX``), so a record whose second value is the
    matching one is pruned identically to the host engine;
  * K-escalation keeps this exact: if any query had more potential
    candidates than K, the scorer re-runs with doubled K until all fit.

Mutation model (vs Lucene's delete-then-readd,
IncrementalLuceneDatabase.java:507-517): the corpus is append-only with
tombstone masks.  Re-indexing an ID tombstones the old row and appends a new
one; ``dukeDeleted`` records stay resolvable by id (the GET feed needs them,
App.java:854-855) but carry a deleted mask bit that excludes them from
candidate scoring (IncrementalLuceneDatabase.java:478).  Capacity grows by
doubling in multiples of the scan chunk, so the jitted scorer recompiles
only O(log N) times over a corpus's lifetime.
"""

from __future__ import annotations

import contextlib
import logging
import os
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import telemetry
from ..core.config import DukeSchema, MatchTunables
from ..core.records import GROUP_NO_PROPERTY_NAME, Record, SchemaError
from ..index.base import CandidateIndex
from ..ops import features as F
from ..ops.features import CHARS as _F_CHARS, CHARS_WEIGHTED as _F_CHARS_W
from ..telemetry import costs, tracing
from ..telemetry.env import env_flag, env_int, env_int_tuple, env_str
from .scheduler import DEFAULT_QUERY_BUCKETS
from ..utils.jit_cache import record_cache_hit, record_compile
from .listeners import MatchListener
from .processor import (
    PHASE_ENCODE,
    PHASE_PERSIST,
    PHASE_RETRIEVE,
    PHASE_SCORE,
    PhaseRecorder,
    ProfileStats,
)

logger = logging.getLogger("device-matcher")

# Query blocks are bucketed to these sizes so batch-size jitter does not
# recompile the scorer (static shapes; SURVEY.md section 7 hard part 2).
# Env-tunable so the CPU test backend can use small shapes; TPU defaults
# are sized for the MXU/VPU (DEVICE_CHUNK rows of corpus per scan step).
# Measured on v5e (20k corpus): chunk 8192 + bucket 1024 runs the scorer at
# ~38M exact pairs/s vs ~16M at chunk 512 + bucket 256 — the scan-step
# fixed costs (top-K merge, kernel dispatch) amortize over 16x more rows
# and 4x more queries per step.  r3: the ladder extends to 4096-query
# blocks — an 8192-query batch runs 86.7M pairs/s end-to-end at bucket
# 4096 vs 67.8M at 1024 (per-block dispatch/fetch overhead halves twice);
# intermediate 2048 keeps mid-size batches from over-padding.
_QUERY_BUCKETS = env_int_tuple(
    "DEVICE_QUERY_BUCKETS", DEFAULT_QUERY_BUCKETS
)
_CHUNK = env_int("DEVICE_CHUNK", 8192)
# Incremental device-update slices bucket independently of the scan chunk:
# a steady-state commit of a few hundred rows must not pay a chunk-sized
# (8192-row) transfer.
_UPDATE_SLICE = env_int("DEVICE_UPDATE_SLICE", 512)
# Pre-sized corpus capacity (rows) for deployments that know their corpus
# scale: capacity-doubling growth transiently needs old + new tensors
# resident, so a corpus near half of HBM cannot double its way up (e.g.
# 10M rows would try to allocate a 16.8M-row copy).  Pre-sizing allocates
# once at the target and never grows through the danger zone.
_INITIAL_CAPACITY = env_int("DEVICE_INITIAL_CAPACITY", 0)
_INITIAL_TOP_K = env_int("DEVICE_TOP_K", 64)
# Value-slot auto-growth cap: pair scoring is O(V^2) combos per property, so
# the per-property value axis stops doubling here; records with more values
# score their first MAX slots on device (host finalization still sees every
# value, so only *pruning* can be affected beyond the cap).
_VALUE_SLOTS_MAX = env_int("DEVICE_VALUE_SLOTS_MAX", 8)
# Per-property char-width auto-growth (CHARS-kind properties): when
# DEVICE_MAX_CHARS is NOT pinned, each property's char tensors start at
# the 32-char Myers width and double to fit the data — so ONE long-text
# field (a description, an abstract) widens only its own tensors while
# the other properties stay on the fast single-word path.  Past
# DEVICE_DEMOTE_CHARS (default = MYERS_MAX_CHARS, the Pallas kernel
# ceiling) the property DEMOTES to the host-scored path instead: the
# device keeps pruning on the remaining short properties with the
# demoted property's maximum contribution folded into the optimistic
# bound (ops.scoring.host_bound_logit), and survivors host-finalize
# exactly — one 1000-char field costs host work per SURVIVOR instead of
# dragging every corpus pair onto the ~86K pairs/s scan-DP kernel.
# DEVICE_DEMOTE_CHARS=0 disables demotion; widths then grow to
# DEVICE_MAX_CHARS_CAP and truncate beyond it.
_CHARS_CAP = env_int("DEVICE_MAX_CHARS_CAP", 1024)
_DEMOTE_CHARS = env_int("DEVICE_DEMOTE_CHARS", 256)
# Per-property q-gram set widths (GRAM_SET): the smallest power of two, at
# least this, strictly greater than the most q-gram windows of any value
# the property has held, capped by DEVICE_MAX_GRAMS.  "Strictly greater"
# keeps a value's set below its slot count unless the cap clamps it, so
# the finalize truncation residue (ops.scoring._dd_unsafe_mask) sees what
# it saw at the fixed cap width.
_GRAMS_MIN = 8
# dead-row runs a corpus remembers below its valid high-water mark
# (DeviceCorpus._dead_runs): shortcuts only, the oldest go first
_DEAD_RUNS_MAX = 64


def query_buckets() -> tuple:
    """The query-padding ladder (public: the ingest scheduler coalesces
    cross-request microbatches toward these boundaries so device launches
    ride already-compiled shapes with minimal padding)."""
    return _QUERY_BUCKETS


def bucket_for(n: int) -> int:
    """Padded query-block size for an ``n``-record batch."""
    for b in _QUERY_BUCKETS:
        if n <= b:
            return b
    return _QUERY_BUCKETS[-1]


# Pre-resolved registry children (dukecheck DK501/DK502): the padding
# ladder is a closed set, so per-bucket children resolve once at import
# and the scoring path writes plain single-writer child counters with no
# family-lock lookup or key-tuple allocation per block.
_BUCKET_CHILDREN = {
    b: (telemetry.QUERY_BLOCKS.labels(bucket=str(b)),  # dukecheck: ignore[DK501] init-time pre-resolution
        telemetry.QUERY_PAD_ROWS.labels(bucket=str(b)))  # dukecheck: ignore[DK501] init-time pre-resolution
    for b in _QUERY_BUCKETS
}
_SCAN_ROWS_CHILDREN = tuple(
    telemetry.DEVICE_SCAN_ROWS.labels(part=part)  # dukecheck: ignore[DK501] init-time pre-resolution
    for part in ("scanned", "capacity")
)
_STREAM_SLICES_CHILD = telemetry.STREAM_APPEND_SLICES.single()


def _stream_append_slice(n: int) -> Optional[int]:
    """Slice size for the streamed extract→upload append, or None for the
    whole-batch path (small batches have nothing to overlap).

    ``DUKE_STREAM_APPEND=0`` pins the legacy whole-batch behavior.  When
    the full batch qualifies for the shared-memory parallel extractor,
    slices grow to its minimum slab so every slice still rides the
    process pool — the overlap must never cost the fan-out.
    """
    if not env_flag("DUKE_STREAM_APPEND", True):
        return None
    slice_n = _UPDATE_SLICE
    from ..ops import parallel_extract as PX

    if PX.enabled(n):
        slice_n = max(slice_n, PX.min_records())
    return slice_n if n > slice_n else None


class DeviceCorpus:
    """Host mirror + device tensors for one workload's indexed records.

    Numpy arrays are the durable host mirror (rebuildable source of truth is
    the record store); device arrays are refreshed lazily per commit.  Rows
    are append-only; ``row_valid`` clears on tombstone.
    """

    def __init__(self, plan, values_per_record: int):
        self.plan = plan
        self.v = values_per_record
        # capacity growth granule: scan-chunk multiples; the sharded
        # corpus raises this to mesh.size * chunk so every shard always
        # holds whole chunks
        self.granule = _CHUNK
        self.capacity = 0
        self.size = 0
        # incremental live-row count (row_valid & ~row_deleted), maintained
        # by append/tombstone: per-batch O(capacity) mask scans to compute
        # it (plus the boolean fancy-index allocation) were measurable at
        # 10M rows.  External mask mutators must recompute it
        # (snapshot_load does), same contract as _dirty_masks.
        self.live_rows = 0
        # valid high-water mark: 1 + the last row with row_valid set, 0
        # when none is — the host's copy of what the scorer bounds its
        # scan by (ops.scoring.live_chunks), kept by append/tombstone
        # under the live_rows contract.  _dead_runs remembers (lo, hi)
        # runs of invalid rows below it, ascending, so a tombstone of the
        # row under the mark walks back in O(1) amortised steps: without
        # them a tail re-indexed again and again would be walked again
        # and again.  They only shorten the walk; dropping one is safe.
        self.valid_hwm = 0
        self._dead_runs: List[Tuple[int, int]] = []
        self.feats: Dict[str, Dict[str, np.ndarray]] = {}
        self.row_valid = np.zeros((0,), dtype=bool)
        self.row_deleted = np.zeros((0,), dtype=bool)
        self.row_group = np.full((0,), -1, dtype=np.int32)
        self.row_ids: List[Optional[str]] = []
        self._device = None           # cached jnp feature mirrors
        self._dirty_full = True       # capacity changed -> full re-upload
        # masks: _dirty_masks forces a FULL (cap,)-sized refresh (growth,
        # snapshot restore, external mutation); steady-state commits ride
        # the incremental trackers instead — at the 10M flagship scale a
        # wholesale mask refresh is ~60 MB over the device link PER
        # COMMIT (r5 measured it dominating the serve batch), while the
        # appended-slice + tombstone-scatter updates are O(batch)
        self._dirty_masks = True
        self._pending_update: Optional[Tuple[int, int]] = None  # appended rows
        self._mask_slice: Optional[Tuple[int, int]] = None  # appended masks
        self._mask_rows: List[int] = []                     # tombstones
        self._mask_device = None
        # serializes device_arrays between the restart warm-upload thread
        # (DeviceIndex.warm_upload_async) and the scoring path; the
        # generation counter detects host-mirror mutations that land
        # while an upload is in flight (writers don't take the lock —
        # they run under the workload lock, which the warm thread is
        # outside of), forcing a re-run so cleared dirty flags can never
        # hide rows from the device copy
        self._upload_lock = threading.Lock()
        self._mutation_gen = 0
        # arena identity (ISSUE 19): the owning workload stamps its
        # kind/name label and a cost-ledger heat callable after build;
        # device_arrays admits through ops.arena under these before
        # every upload (no-ops under DUKE_ARENA=0)
        self.arena_label = ""
        self.arena_heat: Optional[object] = None

    # -- growth --------------------------------------------------------------

    def _target_capacity(self, needed: int) -> int:
        """Doubling growth in ``self.granule`` multiples (one copy of the
        growth policy for the single-device and sharded corpora)."""
        g = self.granule
        cap = max(self.capacity, g)
        if _INITIAL_CAPACITY > 0:
            cap = max(cap, -(-_INITIAL_CAPACITY // g) * g)
        while cap < needed:
            cap *= 2
        return cap

    def _grow(self, needed: int) -> None:
        cap = self._target_capacity(needed)
        if cap == self.capacity:
            return
        if self.capacity > 0:
            # a doubling of an existing corpus: the next device_arrays
            # call re-uploads everything (observability: capacity events
            # explain latency spikes and justify DEVICE_INITIAL_CAPACITY)
            telemetry.CORPUS_GROWTHS.inc()  # dukecheck: ignore[DK502] rare event: capacity doubling, not per-record
        self.row_valid = _grow_1d(self.row_valid, cap, False)
        self.row_deleted = _grow_1d(self.row_deleted, cap, False)
        self.row_group = _grow_1d(self.row_group, cap, -1)
        for prop, tensors in self.feats.items():
            self.feats[prop] = {
                name: _grow_nd(arr, cap) for name, arr in tensors.items()
            }
        self.capacity = cap
        self._dirty_full = True
        self._dirty_masks = True

    def append(self, feats: Dict[str, Dict[str, np.ndarray]],
               deleted: np.ndarray, group: np.ndarray,
               ids: Sequence[str]) -> np.ndarray:
        """Append N rows; returns their row indices."""
        n = len(ids)
        if n == 0:
            return np.zeros((0,), dtype=np.int64)
        if not self.feats:
            # first append defines per-property tensor shapes
            self.feats = {
                prop: {
                    name: np.zeros((0,) + arr.shape[1:], dtype=arr.dtype)
                    for name, arr in tensors.items()
                }
                for prop, tensors in feats.items()
            }
        self._grow(self.size + n)
        rows = np.arange(self.size, self.size + n)
        # appended rows are contiguous: slice assignment is a straight
        # memcpy, where fancy indexing with the arange pays an index path
        lo, hi = self.size, self.size + n
        for prop, tensors in feats.items():
            for name, arr in tensors.items():
                self.feats[prop][name][lo:hi] = arr
        self.row_valid[lo:hi] = True
        self.row_deleted[lo:hi] = deleted
        self.row_group[lo:hi] = group
        self.row_ids.extend(ids)
        self.live_rows += int(n - np.asarray(deleted, dtype=bool).sum())
        if self.valid_hwm < lo:
            self._dead_runs.append((self.valid_hwm, lo))
            del self._dead_runs[:-_DEAD_RUNS_MAX]
        self.valid_hwm = hi
        old_size, self.size = self.size, self.size + n
        self._mutation_gen += 1
        if not self._dirty_full:
            # track the appended range for an incremental device update;
            # merge with a prior un-flushed range (always contiguous)
            if self._pending_update is None:
                self._pending_update = (old_size, n)
            else:
                s, c = self._pending_update
                self._pending_update = (s, old_size + n - s)
            if self._mask_slice is None:
                self._mask_slice = (old_size, n)
            else:
                s, c = self._mask_slice
                self._mask_slice = (s, old_size + n - s)
        return rows

    def tombstone(self, row: int) -> None:
        if self.row_valid[row] and not self.row_deleted[row]:
            self.live_rows -= 1
        self.row_valid[row] = False
        self._mask_rows.append(int(row))
        self._mutation_gen += 1
        if row == self.valid_hwm - 1:
            hwm, runs = self.valid_hwm, self._dead_runs
            while hwm > 0 and not self.row_valid[hwm - 1]:
                hwm = runs.pop()[0] if runs and runs[-1][1] == hwm else hwm - 1
            self.valid_hwm = hwm

    def recount_masks(self) -> None:
        """Recompute ``live_rows`` and ``valid_hwm`` from the host masks:
        for code that writes ``row_valid``/``row_deleted`` outside
        ``append``/``tombstone`` (snapshot_load)."""
        valid = self.row_valid[: self.size]
        self.live_rows = int((valid & ~self.row_deleted[: self.size]).sum())
        rows = np.flatnonzero(valid)
        self.valid_hwm = int(rows[-1]) + 1 if rows.size else 0
        self._dead_runs = []

    def live_chunks(self, chunk: int) -> int:
        """Scan chunks the single-device scorer runs over this corpus:
        the host's twin of ``ops.scoring.live_chunks`` on the device
        mask."""
        return -(-self.valid_hwm // chunk)

    def reserve(self, total_rows: int) -> None:
        """Pre-grow capacity to fit ``total_rows`` ahead of a sliced
        append: a capacity doubling mid-stream would set ``_dirty_full``
        and turn every remaining slice flush into a no-op (the whole
        corpus re-uploads at scoring time instead).  No-op before the
        first append — tensor shapes are defined by the first batch."""
        if self.feats and total_rows > self.capacity:
            self._grow(total_rows)

    def stream_flush(self) -> bool:
        """Streaming-append overlap: enqueue the incremental device-mirror
        update for the rows appended so far.  JAX dispatch is
        asynchronous, so this returns once the jitted tree-update is
        enqueued — the HBM copy of slice N proceeds while the host
        extracts slice N+1 (engine.DeviceIndex._append_rows_only).

        No-op (returns False) while a full upload is pending (cold
        corpus, capacity growth, restored snapshot): re-running the
        whole-corpus upload per slice would multiply the transfer, and
        the scoring-time ``device_arrays()`` pays it exactly once
        instead.  The racy unlocked flag read is writer-side only — the
        appending thread is the one calling this, and a concurrent
        warm-upload thread is serialized by the upload lock inside
        ``device_arrays``.
        """
        if self._device is None or self._dirty_full:
            return False
        self.device_arrays()
        return True

    # -- device mirror -------------------------------------------------------

    def _device_nbytes(self) -> int:
        """Device-mirror footprint: the host mirrors' nbytes (the device
        copies share shapes and dtypes, so the host sum IS the device
        cost).  Lock-free torn reads tolerated — the arena re-admits at
        the settled size on the next call."""
        total = 0
        for tensors in list(self.feats.values()):
            for arr in list(tensors.values()):
                total += int(arr.nbytes)
        for arr in (self.row_valid, self.row_deleted, self.row_group):
            total += int(arr.nbytes)
        return total

    def spill_device(self) -> int:
        """Drop the device mirrors to the host tier (arena eviction).

        Takes the upload lock — the arena's lock is OUTER to it (lock
        order in ops.arena), so a spill waits out any in-flight upload.
        The numpy host mirrors stay authoritative; the owner's next
        query re-admits and faults the corpus back in through the
        normal dirty-full upload.  Returns the freed byte estimate."""
        with self._upload_lock:
            freed = self._device_nbytes() if self._device is not None else 0
            self._device = None
            self._mask_device = None
            self._dirty_full = True
            self._dirty_masks = True
            self._pending_update = None
            self._mask_slice = None
            self._mask_rows = []
            self._mutation_gen += 1
            return freed

    def _place(self, arr: np.ndarray):
        """Host array -> device array; the sharded corpus overrides with
        record-axis-sharded placement over its mesh."""
        import jax.numpy as jnp

        return jnp.asarray(arr)

    def _updater(self):
        """The jitted whole-tree incremental updater to use; the sharded
        corpus overrides with a sharding-constrained variant."""
        return _tree_updater()

    def device_arrays(self):
        """(feats, valid, deleted, group) as device arrays.

        Steady-state incremental batches update the device copy in place
        (one ``dynamic_update_slice`` per feature tensor, O(batch) transfer)
        instead of re-uploading the whole corpus; a full upload happens only
        on capacity growth.  The three mask arrays are ALSO incremental
        (r5): appended ranges ride a slice update and tombstones a
        bucketed scatter — at the 10M flagship scale a wholesale mask
        refresh is ~60 MB over the device link per commit, which
        dominated the serve batch.  External code that mutates
        ``row_valid``/``row_deleted`` outside ``append``/``tombstone``
        MUST set ``_dirty_masks = True`` (snapshot_load does).

        Residency is leased from the shared arena FIRST (ISSUE 19):
        admission may spill colder tenants' mirrors and raises
        ``ops.arena.ArenaAdmissionError`` — surfaced as a 503, never an
        allocator OOM — when the budget cannot fit this corpus.  The
        admit call stays OUTSIDE the upload lock (arena lock is outer).
        """
        from ..ops.arena import ARENA

        ARENA.admit(self, self._device_nbytes(), spill=self.spill_device,
                    label=self.arena_label, heat=self.arena_heat)
        with self._upload_lock:
            while True:
                gen = self._mutation_gen
                out = self._device_arrays_locked()
                if gen == self._mutation_gen:
                    return out
                # a writer mutated the host mirror mid-upload (possible
                # only vs the background warm thread): the flags it set
                # were consumed against possibly-torn reads — redo; the
                # second pass is incremental and cheap

    def _bucketed_slice(self, start: int, count: int) -> Tuple[int, int]:
        """ONE copy of the update-slice bucketing policy (features and
        masks): pow2 lengths from ``_UPDATE_SLICE`` to limit updater
        recompiles, clamped into the capacity."""
        bucket = _UPDATE_SLICE
        while bucket < count:
            bucket *= 2
        bucket = min(bucket, self.capacity)
        return min(start, self.capacity - bucket), bucket

    def _device_arrays_locked(self):
        # DETACH-then-consume everywhere below: trackers are swapped out
        # before any host array is read, so a writer racing the
        # background warm thread lands its entry in a FRESH tracker (and
        # bumps _mutation_gen) — the retry loop in device_arrays then
        # applies it, instead of a post-read clear() silently eating it.
        if self._device is None or self._dirty_full:
            telemetry.CORPUS_FULL_UPLOADS.inc()  # dukecheck: ignore[DK502] rare event: growth/restore re-upload
            self._device = {
                prop: {name: self._place(arr) for name, arr in tensors.items()}
                for prop, tensors in self.feats.items()
            }
            self._pending_update = None
            self._dirty_full = False
        elif self._pending_update is not None:
            (start, count), self._pending_update = self._pending_update, None
            start, bucket = self._bucketed_slice(start, count)
            # ONE jitted call updates the whole tree (donated buffers):
            # per-tensor dispatch would pay the device-link round-trip
            # once per tensor per commit.  (The mask slice below is a
            # second dispatch covering the same range; folding masks into
            # this tree would save it, at the cost of merging the mask
            # and feature mirrors' storage — noted, not yet taken.)
            upd = {
                prop: {
                    name: arr[start:start + bucket]
                    for name, arr in tensors.items()
                }
                for prop, tensors in self.feats.items()
            }
            self._device = self._updater()(
                self._device, upd, np.int32(start)
            )
        # masks: full refresh only when forced (growth/restore/external
        # mutation) or when the scattered-row set got so large the
        # wholesale upload is cheaper; otherwise O(batch) updates
        if (
            self._mask_device is None
            or self._dirty_masks
            or len(self._mask_rows) > max(4096, self.capacity >> 4)
        ):
            self._mask_slice = None
            self._mask_rows = []
            self._dirty_masks = False
            self._mask_device = (
                self._place(self.row_valid),
                self._place(self.row_deleted),
                self._place(self.row_group),
            )
        else:
            if self._mask_slice is not None:
                (start, count), self._mask_slice = self._mask_slice, None
                start, bucket = self._bucketed_slice(start, count)
                self._mask_device = self._mask_updater()(
                    self._mask_device,
                    (self.row_valid[start:start + bucket],
                     self.row_deleted[start:start + bucket],
                     self.row_group[start:start + bucket]),
                    np.int32(start),
                )
            if self._mask_rows:
                rows, self._mask_rows = self._mask_rows, []
                # bucketed scatter: every update SETS the host mirror's
                # current value, so duplicate/padded indices and any
                # ordering vs the slice update are idempotent
                idx = np.asarray(rows, dtype=np.int32)
                bucket = 256
                while bucket < idx.size:
                    bucket *= 2
                pad = np.full(bucket - idx.size, idx[0], dtype=np.int32)
                idx = np.concatenate([idx, pad])
                self._mask_device = self._mask_scatter()(
                    self._mask_device, idx,
                    self.row_valid[idx], self.row_deleted[idx],
                )
        valid, deleted, group = self._mask_device
        return self._device, valid, deleted, group

    def _mask_updater(self):
        """Jitted mask-slice updater (the sharded corpus overrides with a
        sharding-constrained variant)."""
        return _mask_slice_updater()

    def _mask_scatter(self):
        """Jitted tombstone scatter (sharded corpus overrides)."""
        return _mask_scatter_updater()


_MASK_UPDATER = None
_MASK_SCATTER = None


def _mask_slice_updater():
    """One jitted call updating (valid, deleted, group) for a contiguous
    appended range — O(batch) transfer instead of O(capacity)."""
    global _MASK_UPDATER
    if _MASK_UPDATER is None:
        import jax
        from jax import lax

        _MASK_UPDATER = jax.jit(
            lambda masks, upd, start: tuple(
                lax.dynamic_update_slice_in_dim(m, u, start, axis=0)
                for m, u in zip(masks, upd)
            ),
            donate_argnums=(0,),
        )
    return _MASK_UPDATER


def _mask_scatter_updater():
    """One jitted call applying scattered tombstone/liveness updates at
    ``idx`` (group is immutable after append, so only valid/deleted)."""
    global _MASK_SCATTER
    if _MASK_SCATTER is None:
        import jax

        def scatter(masks, idx, vvals, dvals):
            valid, deleted, group = masks
            return (valid.at[idx].set(vvals),
                    deleted.at[idx].set(dvals), group)

        _MASK_SCATTER = jax.jit(scatter, donate_argnums=(0,))
    return _MASK_SCATTER


_TREE_UPDATER = None


def _tree_updater():
    """Jitted whole-tree row updater: one device dispatch per commit.

    ``start`` stays a traced scalar (one compile per tree-structure/shape
    combination, not per update position); donation lets XLA reuse every
    existing device buffer in place.
    """
    global _TREE_UPDATER
    if _TREE_UPDATER is None:
        import jax
        from jax import lax

        _TREE_UPDATER = jax.jit(
            lambda dev, upd, start: jax.tree_util.tree_map(
                lambda d, u: lax.dynamic_update_slice_in_dim(
                    d, u, start, axis=0
                ),
                dev, upd,
            ),
            donate_argnums=(0,),
        )
    return _TREE_UPDATER


def _records_content_hash(records_by_id: Dict[str, Record]) -> str:
    """Order-independent digest of record ids AND values (snapshot guard).

    XOR fold of the canonical per-record digests — the same formula the
    record store (store.records) and the index maintain INCREMENTALLY, so
    this full rehash is only the fallback for callers without a running
    hash (direct snapshot_load calls in tests)."""
    from ..store.records import EMPTY_CONTENT_HASH, record_digest, xor_fold

    acc = EMPTY_CONTENT_HASH
    for record in records_by_id.values():
        acc = xor_fold(acc, record_digest(record))
    return acc.hex()


def _grow_1d(arr: np.ndarray, cap: int, fill) -> np.ndarray:
    out = np.full((cap,), fill, dtype=arr.dtype)
    out[: arr.shape[0]] = arr
    return out


def _grow_nd(arr: np.ndarray, cap: int) -> np.ndarray:
    # grown rows are zero-filled, which is safe ONLY because they stay
    # row_valid=False until append() overwrites them — never read them
    # unmasked (sorted-set tensors would need SET_PAD fill otherwise)
    out = np.zeros((cap,) + arr.shape[1:], dtype=arr.dtype)
    out[: arr.shape[0]] = arr
    return out


class DeviceIndex(CandidateIndex):
    """``CandidateIndex`` backed by the device-resident corpus.

    Candidate retrieval through this interface is exact brute force (every
    live record whose optimistic device score clears ``min_relevance`` is a
    candidate) — but the fast path is ``DeviceProcessor.deduplicate``, which
    never materializes candidate Records and goes straight from the scorer's
    top-K to listener events.
    """

    def __init__(self, schema: DukeSchema, *,
                 tunables: Optional[MatchTunables] = None,
                 values_per_record: Optional[int] = None):
        from ..ops import features as F

        self.schema = schema
        self.tunables = tunables or MatchTunables()
        # Value slots auto-size from the data (Duke records are multi-valued;
        # a record whose *second* value is the matching one must still be
        # visible to device pruning).  An explicit ctor arg or
        # DEVICE_VALUE_SLOTS env pins the width instead.
        env_v = env_str("DEVICE_VALUE_SLOTS")
        self._auto_value_slots = values_per_record is None and env_v is None
        # char widths auto-grow per property unless the operator pinned a
        # global width (tests pin small shapes; long-text deployments let
        # the data size each property's tensors)
        self._auto_chars = env_str("DEVICE_MAX_CHARS") is None
        v = values_per_record or int(env_v or "1")
        self.plan = F.SchemaFeatures.plan(schema, values_per_record=v)
        if not self.plan.device_props:
            raise SchemaError(
                "the device backend needs at least one comparison property "
                "with a device kernel (all configured comparators are "
                "host-only); use the host backend for this schema"
            )
        for spec in self.plan.device_props:
            if spec.kind == F.GRAM_SET:
                spec.max_grams = min(_GRAMS_MIN, F.MAX_GRAMS)
        # batches that widened a q-gram set (single writer: the workload
        # lock; /metrics duke_device_set_width_grows_total reads it)
        self.set_width_grows = 0
        self.corpus = self._make_corpus(self.plan, v)
        self.records: Dict[str, Record] = {}     # id -> live record
        # incremental content digest of ``records`` (same per-record
        # formula as the store's running hash): snapshot_save stamps THIS
        # side and snapshot_load compares the STORE side, so index/store
        # divergence (a store commit whose scoring pass failed) still
        # forces a replay — at O(1) instead of rehashing the corpus.
        # With a LAZY record mirror the incremental fold is impossible
        # (old contents are unobtainable once the store is updated), so
        # the workload instead stamps the store's hash after each fully
        # successful batch (mark_store_synced); a batch that failed
        # between the store write and the index commit leaves the stamp at
        # the pre-batch value, which no longer matches the store — replay.
        from ..store.records import EMPTY_CONTENT_HASH

        self._content_hash = EMPTY_CONTENT_HASH
        self._store_synced_hash: Optional[str] = None
        # multi-host mirror-consistency digest: a sha256 CHAIN over every
        # committed batch (record content + assigned row), maintained by
        # the shared commit() path so frontend and follower replicas fold
        # identically when — and only when — they applied the same
        # mutations in the same order with the same row layout.  Chained
        # (not XOR-folded) on purpose: a missed or doubled batch must
        # change the digest, not cancel out.  Compared frontend-vs-
        # follower after every multi-host commit (parallel.dispatch
        # digest handshake); orthogonal to _content_hash, which guards
        # snapshot/store staleness across restarts.
        self._mirror_digest = EMPTY_CONTENT_HASH
        # O(1) live count (non-dukeDeleted records) for /stats — counting
        # by iterating ``records`` would need the workload lock for the
        # whole scan (seconds at 10M rows)
        self.live_records = 0
        self.id_to_row: Dict[str, int] = {}
        self.indexing_disabled = False
        self._pending: List[Record] = []
        self._lock = threading.Lock()
        self._scorer_cache: Optional["_ScorerCache"] = None
        self._cap_warned: set = set()

    def _make_corpus(self, plan, values_per_record: int) -> DeviceCorpus:
        """Corpus factory (used at construction AND value-slot rebuild);
        the sharded index overrides with its mesh-placed corpus."""
        return DeviceCorpus(plan, values_per_record)

    @property
    def scorer_cache(self) -> "_ScorerCache":
        if self._scorer_cache is None:
            self._scorer_cache = _ScorerCache(self)
        return self._scorer_cache

    # -- CandidateIndex ------------------------------------------------------

    def index(self, record: Record) -> None:
        if self.indexing_disabled:
            return
        with self._lock:
            self._pending.append(record)

    def _extract(self, records: Sequence[Record], plan=None):
        """Feature extraction for a record batch; subclasses may add pseudo-
        properties (the ANN backend rides its embedding matrix in here).
        ``plan`` overrides the corpus plan for query-side extraction."""
        from ..ops import features as F

        return F.extract_batch(plan or self.plan, records)

    def _sized_slots(self, spec, records: Sequence[Record]) -> int:
        """Power-of-two value width fitting ``records`` for one property,
        clamped to DEVICE_VALUE_SLOTS_MAX (warns once per property when the
        clamp makes 9th+ values invisible to device pruning)."""
        need = max(
            (sum(1 for val in r.get_values(spec.name) if val)
             for r in records),
            default=0,
        )
        if need > _VALUE_SLOTS_MAX and spec.name not in self._cap_warned:
            self._cap_warned.add(spec.name)
            logger.warning(
                "property %r has records with %d values; device pruning "
                "sees the first %d (DEVICE_VALUE_SLOTS_MAX)",
                spec.name, need, _VALUE_SLOTS_MAX,
            )
        v = 1
        while v < need:
            v *= 2
        return max(1, min(v, _VALUE_SLOTS_MAX))

    def _query_plan(self, records: Sequence[Record]):
        """Plan for non-indexed query records (http-transform): the value
        axis is sized to the probe batch (power of two, capped) so a query's
        2nd+ values stay visible to pruning WITHOUT widening the corpus —
        scoring handles asymmetric Vq x Vc value combos."""
        from dataclasses import replace

        from ..ops import features as F

        specs = []
        for spec in self.plan.device_props:
            sized = {}
            v = self._sized_slots(spec, records)
            if v != spec.v:
                sized["values_per_record"] = v
            if spec.kind == F.GRAM_SET:
                # a probe's longer value rides a wider query set instead
                # of truncating (the tile kernel pads the corpus side)
                g = self._sized_grams(spec, self._grams_needed(spec, records))
                if g != spec.grams:
                    sized["max_grams"] = g
            specs.append(replace(spec, **sized) if sized else spec)
        return F.SchemaFeatures(
            device_props=specs, host_props=self.plan.host_props
        )

    def commit(self) -> None:
        with self._lock:
            pending, self._pending = self._pending, []
        if not pending:
            return
        # multi-host serving: the drained batch is exactly the corpus
        # mutation about to apply — broadcast it so follower replicas make
        # the identical mutation (parallel.dispatch invariant 1).  The key
        # is tagged by the dispatcher on the frontend only; followers and
        # single-process runs skip.
        from ..parallel import dispatch

        key = getattr(self, "_dispatch_key", None)
        d = dispatch.current() if key is not None else None
        if d is not None:
            # the trailing trace context makes the follower replay a
            # remote child span of this request's trace (ISSUE 2)
            d.broadcast(dispatch.with_trace_ctx(("commit", key, pending)))
        # once broadcast, a local failure leaves followers one commit
        # AHEAD (permanent mirror divergence) — latch before propagating
        with dispatch.latch_on_failure(
            d, "frontend commit failed after broadcast"
        ):
            # last write per ID wins within a batch (Duke re-index semantics)
            by_id: Dict[str, Record] = {}
            for r in pending:
                by_id[r.record_id] = r
            records = list(by_id.values())
            # capture pre-batch liveness BEFORE any value-slot rebuild: a
            # lazy rebuild streams record state from the STORE, which the
            # workload already updated with this batch — rows rebuilt from
            # it reflect the new state, so liveness read after the rebuild
            # would be wrong
            old_live = self._old_liveness(records)
            self._maybe_grow_value_slots(records)
            for r in records:
                old = self.id_to_row.get(r.record_id)
                if old is not None:
                    self.corpus.tombstone(old)
            self._append_records(records, old_live=old_live)
            self._fold_mirror_digest(records)
        # loud mirror verification (multi-host only): every follower just
        # replayed this exact batch through this exact code — compare the
        # resulting chained digests so an asymmetric failure (a swallowed
        # replay exception, OOM, nondeterminism) halts the job here
        # instead of hanging a later collective or finalizing wrong links
        if d is not None:
            d.verify_mirror_digest(key, self._mirror_digest)

    def _fold_mirror_digest(self, records: Sequence[Record]) -> None:
        """Chain one committed batch into the mirror-consistency digest:
        per record, its canonical content digest plus the corpus row it
        landed on (row layout is what the collective programs actually
        consume, so layout divergence must change the digest too)."""
        import hashlib
        import struct as _struct

        from ..store.records import record_digest

        h = hashlib.sha256(self._mirror_digest)
        for r in records:
            h.update(record_digest(r))
            h.update(_struct.pack(
                "<q", self.id_to_row.get(r.record_id, -1)
            ))
        self._mirror_digest = h.digest()

    def _append_chunk(self, records: Sequence[Record]) -> np.ndarray:
        """Extract + corpus append + row mapping for one contiguous chunk."""
        feats = self._extract(records)
        deleted = np.array([r.is_deleted() for r in records], dtype=bool)
        group = np.array(
            [int(r.get_value(GROUP_NO_PROPERTY_NAME) or -1) for r in records],
            dtype=np.int32,
        )
        ids = [r.record_id for r in records]
        rows = self.corpus.append(feats, deleted, group, ids)
        for r, row in zip(records, rows):
            self.id_to_row[r.record_id] = int(row)
        return rows

    def _append_rows_only(self, records: Sequence[Record]) -> np.ndarray:
        """Extract + corpus append + row mapping — no record-mirror, hash,
        or live-count updates (also the streaming rebuild path, where the
        record SET is unchanged).

        Batches past one update slice stream: the batch is appended in
        ``_UPDATE_SLICE``-bucketed slices (grown to the parallel-extract
        minimum when the slab qualifies for the process-pool fan-out, so
        slicing never forfeits it) and each slice's jitted device update
        is enqueued asynchronously while the NEXT slice extracts on host
        — the HBM copy hides under Python extraction instead of
        serializing after it at scoring time.  Host mirrors, dirty-range
        accounting, and row mapping advance per slice, so crash/snapshot
        consistency and the resulting host state are identical to the
        whole-batch path (held by tests/test_feature_cache.py).
        """
        n = len(records)
        slice_n = _stream_append_slice(n)
        if slice_n is None:
            return self._append_chunk(records)
        corpus = self.corpus
        # pre-grow once so no slice crosses a capacity doubling (growth
        # forces a full re-upload, which must not run per slice)
        corpus.reserve(corpus.size + n)
        if corpus._device is None or corpus._dirty_full:
            # nothing to overlap: a full upload is pending (cold corpus,
            # rebuild, growth, restored snapshot), so every slice flush
            # would no-op — keep the whole-batch slab (and its full-size
            # parallel-extract fan-out); scoring pays the one full upload
            # exactly as before this subsystem
            return self._append_chunk(records)
        out = np.empty((n,), dtype=np.int64)
        done = 0
        with tracing.span(
            "encode.stream_append",
            {"records": n, "slice": slice_n},
            annotate=True,
        ):
            while done < n:
                chunk = records[done:done + slice_n]
                rows = self._append_chunk(chunk)
                out[done:done + len(chunk)] = rows
                done += len(chunk)
                if corpus.stream_flush():
                    _STREAM_SLICES_CHILD.inc()
        return out

    def _old_liveness(self, records: Sequence[Record]) -> List[bool]:
        """Pre-batch liveness per record, from INDEX state (id_to_row +
        the old row's deleted mask) — never from a mirror read: a lazy
        mirror reads through to the store, which the workload already
        updated with the NEW values, and counting (or hash-folding) those
        as "old" silently corrupts the live count and the content digest."""
        corpus = self.corpus
        out = []
        for r in records:
            old_row = self.id_to_row.get(r.record_id)
            out.append(
                old_row is not None and not corpus.row_deleted[old_row]
            )
        return out

    def _append_records(self, records: Sequence[Record],
                        old_live: Optional[List[bool]] = None) -> None:
        from ..store.records import LazyRecordMap, record_digest, xor_fold

        if old_live is None:
            old_live = self._old_liveness(records)
        self._append_rows_only(records)
        lazy = isinstance(self.records, LazyRecordMap)
        delta = 0
        acc = self._content_hash
        for r, was_live in zip(records, old_live):
            delta += (0 if r.is_deleted() else 1) - (1 if was_live else 0)
            if not lazy:
                old = self.records.get(r.record_id)
                if old is not None:
                    acc = xor_fold(acc, record_digest(old))
                acc = xor_fold(acc, record_digest(r))
            self.records[r.record_id] = r
        # in lazy mode the incremental fold is impossible (the true old
        # content is gone — the store was updated first); snapshot
        # integrity rides the store-synced stamp instead (mark_store_synced)
        if not lazy:
            self._content_hash = acc
        # one publication per batch: lock-free /stats readers must never
        # observe a mid-append partial count
        self.live_records += delta

    # -- value-slot auto-sizing ----------------------------------------------

    def _chars_needed(self, spec, records: Sequence[Record]) -> int:
        from ..ops.features import char_units

        need = 0
        for r in records:
            for val in r.get_values(spec.name):
                # width in UTF-16 code units — the char-axis unit
                # (ops.features.CHAR_DTYPE); len() undercounts non-BMP
                if len(val) * 2 < need:
                    continue  # cannot beat the running max even if all
                              # chars were surrogate pairs
                n = char_units(val)
                if n > need:
                    need = n
        return need

    def _sized_chars(self, spec, need: int) -> int:
        """Power-of-two char width fitting ``need`` codepoints, at least
        the current width, clamped to DEVICE_MAX_CHARS_CAP (warns once
        per property on clamp)."""
        if need > _CHARS_CAP:
            key = f"chars:{spec.name}"
            if key not in self._cap_warned:
                self._cap_warned.add(key)
                logger.warning(
                    "property %r has a %d-char value; device pruning sees "
                    "the first %d chars (DEVICE_MAX_CHARS_CAP; host "
                    "finalization stays exact)", spec.name, need, _CHARS_CAP,
                )
        width = spec.chars
        while width < need and width < _CHARS_CAP:
            width *= 2
        return min(width, _CHARS_CAP)

    @staticmethod
    def _grams_needed(spec, records: Sequence[Record]) -> int:
        """The most q-gram windows of any of the property's values in
        ``records`` (``len - q + 1``, 1 for a non-empty value shorter
        than q): an upper bound on its distinct q-grams."""
        q = int(getattr(spec.comparator, "q", 2))
        longest = max(
            (len(val) for r in records for val in r.get_values(spec.name)),
            default=0,
        )
        return max(longest - q + 1, 1) if longest else 0

    def _sized_grams(self, spec, need: int) -> int:
        """Power-of-two set width strictly greater than ``need`` q-gram
        windows, at least the current width, clamped to DEVICE_MAX_GRAMS
        (warns once per property on clamp)."""
        cap = F.MAX_GRAMS
        if need >= cap:
            key = f"grams:{spec.name}"
            if key not in self._cap_warned:
                self._cap_warned.add(key)
                logger.warning(
                    "property %r has a value of %d q-gram windows; device "
                    "pruning sees its first %d distinct q-grams "
                    "(DEVICE_MAX_GRAMS; host finalization stays exact)",
                    spec.name, need, cap,
                )
        width = spec.grams
        while width <= need and width < cap:
            width *= 2
        return min(width, cap)

    def _maybe_grow_value_slots(self, records: Sequence[Record]) -> None:
        """Grow per-property value slots, char widths AND q-gram set
        widths to fit the batch.

        Duke scores the max over *all* value pairs per property
        (IncrementalDataSource.java:69-73 feeds multi-values), and its
        comparators accept arbitrary-length strings
        (testdukeconfig.xml:25-42 puts no bound on property values); the
        device tensors bound both axes for static shapes, so when a batch
        arrives with more values — or longer values — than the current
        widths, the plan widens (power-of-two, capped) and the corpus
        tensors rebuild from the host-resident records.  Growth happens
        at most O(log max) times per axis per property, and widths are
        PER PROPERTY: one long-text field rides the wide (or scan-DP)
        kernels alone while short fields keep the one-word Myers path.
        """
        grew = grew_sets = False
        demote = []
        for spec in self.plan.device_props:
            if self._auto_value_slots:
                v = self._sized_slots(spec, records)
                if v > spec.values_per_record:
                    spec.values_per_record = v
                    grew = True
            if self._auto_chars and spec.kind in (_F_CHARS, _F_CHARS_W):
                need = self._chars_needed(spec, records)
                if _DEMOTE_CHARS and need > _DEMOTE_CHARS:
                    demote.append(spec)
                    continue
                width = self._sized_chars(spec, need)
                if width > spec.chars:
                    spec.max_chars = width
                    grew = True
            if spec.kind == F.GRAM_SET:
                width = self._sized_grams(
                    spec, self._grams_needed(spec, records))
                if width > spec.grams:
                    spec.max_grams = width
                    grew_sets = True
        if demote and self._demote_to_host(demote):
            grew = True
        if grew_sets:
            self.set_width_grows += 1
        if grew or grew_sets:
            self._rebuild_corpus()

    def _demote_to_host(self, specs) -> bool:
        """Move long-text CHARS properties to the host-scored side (see
        the _DEMOTE_CHARS comment).  Never demotes the LAST device
        property — the scorer needs at least one (that one stays at the
        cap width, truncating).  Returns True when the plan changed."""
        changed = False
        keep_one = len(self.plan.device_props) - len(specs) < 1
        if keep_one:
            kept, specs = specs[0], specs[1:]  # first candidate stays
            width = self._sized_chars(kept, _CHARS_CAP)
            key = f"keep:{kept.name}"
            if key not in self._cap_warned:
                self._cap_warned.add(key)
                logger.warning(
                    "property %r is the only device-kernel property, so it "
                    "stays on device at width %d; longer values truncate "
                    "for pruning (host finalization stays exact)",
                    kept.name, width,
                )
            if width > kept.chars:
                kept.max_chars = width
                changed = True  # caller must rebuild the corpus tensors
        if not specs:
            return changed
        names = {s.name for s in specs}
        self.plan.device_props[:] = [
            s for s in self.plan.device_props if s.name not in names
        ]
        for prop in self.schema.comparison_properties():
            if prop.name in names:
                self.plan.host_props.append(prop)
        logger.warning(
            "long-text properties %s demoted to host scoring (values past "
            "DEVICE_DEMOTE_CHARS=%d; device pruning keeps the remaining "
            "properties with the demoted ones' max contribution in the "
            "optimistic bound)", sorted(names), _DEMOTE_CHARS,
        )
        # cached scorer builders snapshotted the old device_props list;
        # drop them (and the warm fingerprint) so the next dispatch
        # rebuilds from the updated plan
        cache = self._scorer_cache
        if cache is not None:
            cache._scorers.clear()
            cache._warmed = None
        return True

    def _rebuild_corpus(self) -> None:
        """Re-extract every stored record under the current feature plan.

        Holds the index lock for the whole swap so a concurrent ``delete``
        cannot land between the old-state capture and the replacement (its
        tombstone would otherwise be resurrected by the re-append).
        """
        from ..store.records import LazyRecordMap

        with self._lock:
            old_records = self.records
            lazy = isinstance(old_records, LazyRecordMap)
            self.corpus = self._make_corpus(
                self.plan, max((s.v for s in self.plan.device_props), default=1)
            )
            self.id_to_row = {}
            if old_records:
                logger.info(
                    "value-slot growth: rebuilding corpus tensors for %d "
                    "records (slots now %s)%s", len(old_records),
                    {s.name: s.v for s in self.plan.device_props},
                    " — streaming from the store" if lazy else "",
                )
            if lazy:
                # stream the store in bounded batches (values() decodes
                # through the capped LRU): a 10M-row lazy corpus must not
                # materialize ~60 GB of Records for a rebuild.  The record
                # set, live count, and content stamp are all unchanged —
                # only the feature tensors re-extract.
                batch: List[Record] = []
                for record in old_records.values():
                    batch.append(record)
                    if len(batch) >= 50_000:
                        self._append_rows_only(batch)
                        batch = []
                if batch:
                    self._append_rows_only(batch)
            else:
                self.records = {}
                # live_records is deliberately NOT zeroed before the
                # re-append: lock-free /stats readers must never observe a
                # transient near-zero count for a populated corpus.  The
                # re-append of the same record set double-counts (every
                # record looks new against the cleared map), so the
                # pre-rebuild count is subtracted once at the end — readers
                # transiently see between 1x and 2x, never a collapse.
                prev_live = self.live_records
                # the record SET is unchanged by a rebuild; re-appending
                # would fold every digest a second time (XOR: fold twice =
                # remove), so the running hash is preserved
                prev_hash = self._content_hash
                if old_records:
                    self._append_records(list(old_records.values()))
                self.live_records -= prev_live
                self._content_hash = prev_hash

    def find_record_by_id(self, record_id: str) -> Optional[Record]:
        return self.records.get(record_id)

    def find_candidate_matches(self, record: Record,
                               group_filtering: bool = False) -> List[Record]:
        """Interface-parity path: scores one record against the corpus and
        returns every live record whose *device* probability clears
        ``min_relevance``-equivalent pruning.  The DeviceProcessor fast path
        bypasses this."""
        result = self.scorer_cache.score_block(
            [record], group_filtering=group_filtering
        )
        out: List[Record] = []
        for row, _logit in result.survivors(0):
            rid = self.corpus.row_ids[row]
            rec = self.records.get(rid)
            if rec is not None:
                out.append(rec)
        return out

    def explain_retrieval(self, record: Record, candidate: Record,
                          group_filtering: bool = False) -> Dict:
        """Retrieval provenance (ISSUE 5): brute force scores every live
        corpus row, so the only ways a pair can fail to meet are corpus
        state (not indexed / tombstoned) and the candidate-mask policy
        (self-pair, same group) — the pair's actual f32 verdict and
        bounds ride the ``device`` section of the explanation
        (engine.explain.device_breakdown)."""
        out: Dict = {
            "mode": "device-brute",
            "exhaustive": True,
            "survivor_bound": self.scorer_cache._min_logit(),
        }
        row = self.id_to_row.get(candidate.record_id)
        out["candidate_indexed"] = row is not None
        if row is not None:
            corpus = self.corpus
            out["candidate_live"] = bool(
                corpus.row_valid[row] and not corpus.row_deleted[row]
            )
        if group_filtering:
            g1 = record.get_value(GROUP_NO_PROPERTY_NAME)
            g2 = candidate.get_value(GROUP_NO_PROPERTY_NAME)
            out["group_excluded"] = bool(g1 and g1 == g2)
        out["self_pair"] = record.record_id == candidate.record_id
        return out

    def delete(self, record: Record) -> None:
        from ..store.records import LazyRecordMap, record_digest, xor_fold

        with self._lock:
            lazy = isinstance(self.records, LazyRecordMap)
            row = self.id_to_row.pop(record.record_id, None)
            if row is not None:
                # liveness from index state (see _old_liveness)
                if not self.corpus.row_deleted[row]:
                    self.live_records -= 1
                self.corpus.tombstone(row)
            if lazy:
                # no decode: the removed value is unused in lazy mode
                # (the content fold rides the store-synced stamp)
                self.records.discard(record.record_id)
            else:
                old = self.records.pop(record.record_id, None)
                if old is not None:
                    self._content_hash = xor_fold(
                        self._content_hash, record_digest(old)
                    )

    def set_indexing_disabled(self, disabled: bool) -> None:
        self.indexing_disabled = disabled

    # -- extraction snapshot (restart acceleration) --------------------------
    #
    # The durable record store is the source of truth (SURVEY.md section 7
    # "State"); the corpus tensors are a rebuildable cache.  Rebuilding
    # means re-running per-record feature extraction — the dominant restart
    # cost at 10^5+ rows — so the host mirror can be snapshotted to one
    # .npz and reloaded in one mmap'd read, the orbax-style device-state
    # snapshot SURVEY.md section 5.4 calls an optimization, never truth:
    # any mismatch (schema change, env-sized tensor shapes, store drift)
    # silently falls back to full replay.

    @staticmethod
    def _snapshot_checksum(entries: Dict[str, np.ndarray]) -> str:
        """Content checksum over the snapshot's payload arrays (ISSUE 10):
        CRC32 chained over (key, dtype, shape, bytes) in sorted key
        order.  Stamped as ``__checksum`` at save and re-derived from
        the as-stored arrays at load, so a flipped byte, a swapped
        member, or a partially-written archive is rejected into a store
        replay instead of silently scoring corrupt features (the zip
        layer's per-member CRC catches most of this; the stamp also
        catches member-level substitution and pre-decompression
        truncation modes it cannot)."""
        import zlib as _zlib

        crc = 0
        for key in sorted(entries):
            arr = np.ascontiguousarray(entries[key])
            meta = f"{key}\x1f{arr.dtype.str}\x1f{arr.shape}".encode()
            crc = _zlib.crc32(arr.tobytes(), _zlib.crc32(meta, crc))
        return format(crc & 0xFFFFFFFF, "08x")

    def _snapshot_reject(self, reason: str, detail: str) -> bool:
        """A snapshot check failed: warn + count, fall back to replay.
        Never raises — the store remains the source of truth and a bad
        snapshot must cost a rebuild, not availability."""
        telemetry.SNAPSHOT_FALLBACKS.labels(reason=reason).inc()  # dukecheck: ignore[DK501] startup/reload-only rejection path, never per-batch
        logger.warning(
            "corpus snapshot rejected (%s: %s); replaying from the "
            "record store", reason, detail,
        )
        return False

    def _snapshot_fingerprint(self) -> str:
        import hashlib

        # plan semantics + every env knob that sizes the feature tensors
        # (must be computable before any data is loaded; value-slot widths
        # are data-derived, so they ride in the snapshot payload instead —
        # __value_slots — and are applied at load)
        spec = repr((
            [(s.name, s.kind, s.low, s.high)
             for s in self.plan.device_props],
            env_str("DEVICE_MAX_CHARS", ""),
            env_str("DEVICE_MAX_CHARS_CAP", ""),
            env_str("DEVICE_DEMOTE_CHARS", ""),
            env_str("DEVICE_MAX_GRAMS", ""),
            env_str("DEVICE_MAX_TOKENS", ""),
            getattr(self, "dim", None),          # ANN embedding width
            getattr(self, "emb_storage", None),  # ANN embedding dtype
            # char-tensor storage dtype (r5: uint16 UTF-16 code units) —
            # a pre-r5 int32-codepoint snapshot must be rejected into a
            # replay, not silently adopted with the wrong text model
            str(np.dtype(F.CHAR_DTYPE)),
        ))
        return hashlib.sha256(spec.encode()).hexdigest()

    def snapshot_save(self, path: str) -> None:
        import ml_dtypes

        corpus = self.corpus
        if corpus.size == 0:
            return
        # stamp the last store-synced digest when the workload maintains
        # one (the lazy-mirror mode), else the index's own running fold —
        # either way a store commit whose scoring/index pass failed leaves
        # the stamp different from the store's current hash, and the
        # restart's compare must then reject the snapshot (stale features
        # must never score)
        content_hash = (self._store_synced_hash
                        if self._store_synced_hash is not None
                        else self._content_hash.hex())
        # np.savez cannot round-trip ml_dtypes (bf16 loads back as raw
        # void); such tensors are saved as uint16 bit views and listed in
        # __bf16_keys so load can view them back
        flat = {}
        bf16_keys = []
        for prop, tensors in corpus.feats.items():
            for name, arr in tensors.items():
                key = f"feat\x1f{prop}\x1f{name}"
                a = arr[: corpus.size]
                if a.dtype == ml_dtypes.bfloat16:
                    bf16_keys.append(key)
                    a = a.view(np.uint16)
                flat[key] = a
        # payload arrays also feed the stamped content checksum (same
        # set the load-side verification re-derives)
        payload = dict(flat)
        payload["__row_valid"] = corpus.row_valid[: corpus.size]
        payload["__row_deleted"] = corpus.row_deleted[: corpus.size]
        payload["__row_group"] = corpus.row_group[: corpus.size]
        # fixed-width unicode, NOT object dtype: object arrays
        # pickle, and a pickle-bearing snapshot would force
        # allow_pickle=True at load — an arbitrary-code-execution
        # vector for anyone who can write the data volume
        payload["__row_ids"] = np.array(
            [rid or "" for rid in corpus.row_ids], dtype=str
        )
        # write-then-rename: a SIGKILL mid-save must never leave a truncated
        # snapshot (np.load would fail and silently force a full replay)
        tmp = f"{path}.tmp.{os.getpid()}"
        # compression trades restart time for disk: zlib over a multi-GB
        # corpus (10M rows ≈ 9 GB with embeddings) takes minutes, so large
        # deployments set SNAPSHOT_COMPRESS=0 and pay disk instead
        savez = (np.savez_compressed
                 if env_flag("SNAPSHOT_COMPRESS", True)
                 else np.savez)
        try:
            savez(
                tmp,
                __fingerprint=np.array(self._snapshot_fingerprint()),
                __content=np.array(content_hash),
                __checksum=np.array(self._snapshot_checksum(payload)),
                __bf16_keys=np.array(bf16_keys, dtype=str),
                __value_slots=np.array(
                    [s.v for s in self.plan.device_props], dtype=np.int64
                ),
                __char_widths=np.array(
                    [s.chars for s in self.plan.device_props],
                    dtype=np.int64,
                ),
                __set_widths=np.array(
                    [s.grams for s in self.plan.device_props],
                    dtype=np.int64,
                ),
                # surviving device properties (r4): a plan that demoted a
                # long-text property to host scoring persists that choice,
                # so a restart re-demotes instead of rejecting the
                # snapshot for a prop-count mismatch and replaying
                __device_props=np.array(
                    [s.name for s in self.plan.device_props], dtype=str
                ),
                **payload,
            )
            # kill-differential site (ISSUE 10): die in the tmp-written/
            # not-yet-renamed window — the restart must find the PREVIOUS
            # snapshot (or none) intact and never the torn tmp
            from ..utils import faults as _faults

            _faults.check_crash("mid_snapshot_save")
            # np.savez appends .npz to names without it
            os.replace(tmp if tmp.endswith(".npz") else f"{tmp}.npz", path)
        except BaseException:
            for cand in (tmp, f"{tmp}.npz"):
                try:
                    os.unlink(cand)
                except OSError:
                    pass
            raise

    def snapshot_load(self, path: str,
                      records_by_id: Dict[str, Record],
                      content_hash: Optional[str] = None) -> bool:
        """Restore the corpus tensors from a snapshot; False -> replay.

        ``records_by_id`` is the durable store's live view; the snapshot is
        rejected unless its live rows are exactly the store's record set.
        ``content_hash`` is the store's incremental content digest
        (store.records.RecordStore.content_hash) — when provided the
        staleness check is an O(1) compare instead of rehashing every
        record's every value.
        """
        import ml_dtypes

        if self.corpus.size != 0 or not os.path.exists(path):
            return False
        try:
            with np.load(path) as data:  # no pickle: plain arrays only
                if str(data["__fingerprint"]) != self._snapshot_fingerprint():
                    return self._snapshot_reject(
                        "fingerprint", "plan/env fingerprint changed")
                if "__value_slots" not in data.files:
                    return self._snapshot_reject(
                        "schema", "missing __value_slots")
                # re-apply persisted long-text demotions BEFORE the
                # per-prop list compares (see snapshot_save __device_props)
                if "__device_props" in data.files and self._auto_chars:
                    saved = [str(x) for x in data["__device_props"]]
                    current = [s.name for s in self.plan.device_props]
                    missing = [
                        s for s in self.plan.device_props
                        if s.name not in saved
                    ]
                    if missing and set(saved) < set(current):
                        # applied even if a later check rejects the
                        # snapshot: the demotion was data-driven, so the
                        # replay that follows a rejection re-ingests the
                        # same long values and would re-demote anyway —
                        # starting demoted is conservative and exact
                        self._demote_to_host(missing)
                    if [s.name for s in self.plan.device_props] != saved:
                        return self._snapshot_reject(
                            "schema", "device property set changed")
                slots = [int(x) for x in data["__value_slots"]]
                if len(slots) != len(self.plan.device_props):
                    return self._snapshot_reject(
                        "schema", "value-slot count mismatch")
                if self._auto_value_slots:
                    # snapshot written under a larger cap: replaying re-grows
                    # under the current one instead of adopting oversize axes
                    if any(v > _VALUE_SLOTS_MAX for v in slots):
                        return self._snapshot_reject(
                            "schema", "value slots exceed the current cap")
                elif slots != [s.v for s in self.plan.device_props]:
                    return self._snapshot_reject(
                        "schema", "value-slot widths changed")
                # per-property char widths (r4): absent key = pre-r4
                # snapshot, valid only at the plan's default widths
                if "__char_widths" in data.files:
                    widths = [int(x) for x in data["__char_widths"]]
                    if len(widths) != len(self.plan.device_props):
                        return self._snapshot_reject(
                            "schema", "char-width count mismatch")
                    if self._auto_chars:
                        if any(w > _CHARS_CAP for w in widths):
                            return self._snapshot_reject(
                                "schema",
                                "char widths exceed the current cap")
                    elif widths != [s.chars for s in self.plan.device_props]:
                        return self._snapshot_reject(
                            "schema", "char widths changed")
                else:
                    widths = [s.chars for s in self.plan.device_props]
                # per-property q-gram set widths: the saved tensors hold
                # their width; a plan already wider than the snapshot
                # (or a cap below it) must not adopt them
                if "__set_widths" not in data.files:
                    return self._snapshot_reject(
                        "schema", "missing __set_widths")
                set_widths = [int(x) for x in data["__set_widths"]]
                if len(set_widths) != len(self.plan.device_props):
                    return self._snapshot_reject(
                        "schema", "set-width count mismatch")
                if any(w < s.grams or w > F.MAX_GRAMS
                       for s, w in zip(self.plan.device_props, set_widths)
                       if s.kind == F.GRAM_SET):
                    return self._snapshot_reject(
                        "schema", "q-gram set widths differ from the plan's")
                # record CONTENT hash, not just the id set: an id-set check
                # would accept a snapshot predating an in-place record
                # update that only the store persisted (crash before the
                # next snapshot save) and score stale features
                expected = (content_hash if content_hash is not None
                            else _records_content_hash(records_by_id))
                if str(data["__content"]) != expected:
                    return self._snapshot_reject(
                        "content", "record store drifted past the snapshot")
                accepted_hash = bytes.fromhex(expected)
                row_ids = list(data["__row_ids"])
                row_valid = data["__row_valid"]
                row_deleted = data["__row_deleted"]
                row_group = data["__row_group"]
                live = {
                    rid for rid, ok in zip(row_ids, row_valid) if ok
                }
                if live != set(records_by_id):
                    return self._snapshot_reject(
                        "content", "live row set differs from the store")
                bf16_keys = (
                    {str(k) for k in data["__bf16_keys"]}
                    if "__bf16_keys" in data.files else set()
                )
                feats: Dict[str, Dict[str, np.ndarray]] = {}
                # as-stored arrays, pre-bf16-view: the checksum stamp was
                # computed over exactly these at save time
                raw_payload: Dict[str, np.ndarray] = {
                    "__row_ids": data["__row_ids"],
                    "__row_valid": row_valid,
                    "__row_deleted": row_deleted,
                    "__row_group": row_group,
                }
                for key in data.files:
                    if not key.startswith("feat\x1f"):
                        continue
                    _, prop, name = key.split("\x1f", 2)
                    arr = data[key]
                    raw_payload[key] = arr
                    if key in bf16_keys:
                        arr = arr.view(ml_dtypes.bfloat16)
                    feats.setdefault(prop, {})[name] = arr
                # stamped content checksum (ISSUE 10); absent = pre-stamp
                # snapshot, accepted for upgrade compatibility (the zip
                # member CRCs still guard it)
                if "__checksum" in data.files and (
                        str(data["__checksum"])
                        != self._snapshot_checksum(raw_payload)):
                    return self._snapshot_reject(
                        "checksum", "stamped content checksum mismatch")
        except Exception as e:
            logger.exception("snapshot load failed; replaying from store")
            return self._snapshot_reject("corrupt", repr(e))

        # every check passed — only now adopt the snapshot's value-slot,
        # char and set widths (a rejected snapshot must leave the plan
        # untouched)
        if self._auto_value_slots:
            for spec, v in zip(self.plan.device_props, slots):
                spec.values_per_record = v
        if self._auto_chars:
            for spec, w in zip(self.plan.device_props, widths):
                spec.max_chars = w
        for spec, w in zip(self.plan.device_props, set_widths):
            if spec.kind == F.GRAM_SET:
                spec.max_grams = w
        corpus = self.corpus
        n = len(row_ids)
        rows = corpus.append(
            feats, np.asarray(row_deleted), np.asarray(row_group),
            [str(r) for r in row_ids],
        )
        corpus.row_valid[: n] = row_valid
        corpus._dirty_masks = True
        # the direct mask overwrite above bypassed append/tombstone — the
        # incremental live counter and high-water mark follow it
        corpus.recount_masks()
        live_count = corpus.live_rows
        # corpus tensors are assembled: stream them to HBM while the rest
        # of the restore (row-map wiring below, store/link bring-up in
        # build_workload, service startup) runs on the host
        self.warm_upload_async()
        from ..store.records import LazyRecordMap

        lazy = isinstance(records_by_id, LazyRecordMap)
        for rid, row, ok in zip(row_ids, rows, row_valid):
            if ok:
                self.id_to_row[str(rid)] = int(row)
                if not lazy:
                    self.records[str(rid)] = records_by_id[str(rid)]
        if lazy:
            # store-backed on-demand mirror: restart skips materializing
            # every record (the 10M-row eager decode took ~24 min / 60 GB)
            self.records = records_by_id
        # live = valid rows that are not dukeDeleted (identical to counting
        # non-deleted records, without touching the record payloads)
        self.live_records = live_count
        self._prewarm_feature_cache(feats, records_by_id)
        # adopt the verified digest as the index's running hash AND the
        # store-synced stamp (the restore bypassed the incremental fold)
        self._content_hash = accepted_hash
        self._store_synced_hash = accepted_hash.hex()
        logger.info("corpus snapshot restored: %d rows from %s%s", n, path,
                    " (lazy record mirror)" if lazy else "")
        return True

    def _prewarm_feature_cache(self, feats, records_by_id) -> None:
        """Seed the digest-keyed feature cache from restored snapshot
        tensors so the FIRST resync after a restart already hits.

        Digests come from the durable store's raw rows (no record decode
        — ``RecordStore.row_digests`` folds the stored serialization,
        byte-identical to ``record_digest`` of the live record); plain
        dict mirrors (tests) fall back to hashing the records.  Budget-
        bounded by the cache itself; best-effort — a failure leaves the
        cache cold, never the restore broken.
        """
        from ..ops import feature_cache as FC

        cache = FC.active()
        if cache is None:
            return
        try:
            store = getattr(records_by_id, "_store", None)
            if store is not None and hasattr(store, "row_digests"):
                digest_iter = store.row_digests()
            elif hasattr(records_by_id, "items"):
                from ..store.records import record_digest

                digest_iter = (
                    (rid, record_digest(r))
                    for rid, r in records_by_id.items()
                )
            else:
                return
            warmed = FC.prewarm(
                self.plan, getattr(self, "encoder", None), feats,
                self.id_to_row, digest_iter, cache,
            )
            if warmed:
                logger.info(
                    "feature cache pre-warmed with %d rows from the "
                    "snapshot", warmed,
                )
        except Exception:  # pragma: no cover - degraded, not broken
            logger.exception(
                "feature-cache pre-warm failed (cache stays cold)"
            )

    def warm_upload_async(self) -> None:
        """Dispatch the host-mirror -> HBM corpus upload in the background.

        A restored 10M-row corpus is ~9 GB of device transfer; paying it
        on the first query made restart-to-first-answer ~10 minutes
        (VERDICT r3 #6).  Kicked from snapshot_load as soon as the corpus
        tensors are assembled, so the transfers stream while the rest of
        startup (row-map wiring, link DB, HTTP bring-up) runs; the first
        query's device_arrays() then finds the mirrors already resident
        (or waits on the upload lock for the in-flight remainder).
        """
        # Small corpora upload in milliseconds on first query — not worth
        # a background thread (and its writer-race surface) at all
        if self.corpus.size < 65536:
            return
        # Default ON: the transfer streams during the load's host work.
        # Not measured on today's code on a directly attached chip.
        if not env_flag("DEVICE_WARM_UPLOAD", True):
            return

        def _upload():
            try:
                # MUST go through the retrying entry point: writers run
                # under the workload lock, which this thread is outside of,
                # so the generation check in device_arrays() is the only
                # guard against a commit/tombstone landing mid-upload and
                # having its dirty flags consumed against torn reads (a
                # direct _device_arrays_locked() call here could clear
                # _pending_update/_dirty_* for rows it never uploaded,
                # silently hiding committed rows from scoring)
                feats, valid, deleted, group = self.corpus.device_arrays()
                # block on completion INSIDE the thread so the upload is
                # actually done (not merely enqueued) before we log
                import jax

                jax.block_until_ready((valid, deleted, group))
                jax.block_until_ready(feats)
                logger.info("warm corpus upload complete (%d rows)",
                            self.corpus.size)
            except Exception:  # pragma: no cover - degraded, not broken
                logger.exception(
                    "warm corpus upload failed (first query will retry)"
                )

        t = threading.Thread(target=_upload, daemon=True,
                             name="corpus-upload")
        t.start()

    def mark_store_synced(self, store_hash: Optional[str]) -> None:
        """Record that the index has fully applied every store write up to
        ``store_hash`` (the workload calls this after each successful
        batch).  snapshot_save stamps this value; a store write without a
        subsequent successful index commit leaves it stale and the next
        restart replays."""
        if store_hash is not None:
            self._store_synced_hash = store_hash

    def close(self) -> None:
        # drop the arena lease and the shared-ladder ref NOW instead of
        # waiting for GC: a hot reload's replacement workload must see
        # this tenant's HBM residency and AOT refcount released
        from ..ops.arena import ARENA

        ARENA.forget(self.corpus)
        cache = self._scorer_cache
        if cache is not None:
            cache.release_shared()


class _BlockResult:
    """Scored query block: per-query candidate rows above the pruning bound."""

    def __init__(self, top_logit: np.ndarray, top_index: np.ndarray,
                 min_logit: float):
        self.top_logit = top_logit
        self.top_index = top_index
        self.min_logit = min_logit
        # device-finalize attachments (ISSUE 12): the query-side device
        # context the dd rescore re-uses (set by dispatch_block via
        # resolve_block) and the resolved dd rescore output
        # (hi, lo, unsafe numpy arrays aligned with top_index) consumed
        # by engine.finalize
        self.dd_ctx = None
        self.dd = None

    def survivor_triples(self, q: int) -> List[Tuple[int, int, float]]:
        """(k_position, corpus_row, device_logit) survivors of query q —
        the position indexes the dd rescore arrays (engine.finalize)."""
        logits = self.top_logit[q]
        rows = self.top_index[q]
        keep = np.nonzero(logits > self.min_logit)[0]
        return [(int(k), int(rows[k]), float(logits[k])) for k in keep]

    def survivors(self, q: int) -> List[Tuple[int, float]]:
        """(corpus_row, device_logit) pairs that may clear the threshold."""
        return [(row, logit) for _, row, logit in self.survivor_triples(q)]


def _fp_value(v, depth: int = 0):
    """JSON-able fingerprint image of a comparator/spec attribute: the
    HLO bakes these values in, so the AOT store key must cover them.
    Objects recurse one level through ``vars()`` (a nested comparator's
    parameters matter); anything deeper or unrecognized reduces to its
    type name — a lossy reduction can only cause a spurious key match
    between configs that differ solely inside such a value, and the
    scoring-source hash in the store key bounds that exposure."""
    if isinstance(v, (bool, int, float, str)) or v is None:
        return v
    if isinstance(v, (list, tuple)):
        return [_fp_value(x, depth) for x in v]
    if isinstance(v, dict):
        return sorted((str(k), _fp_value(x, depth)) for k, x in v.items())
    if depth < 2 and hasattr(v, "__dict__"):
        return [type(v).__name__,
                sorted((k, _fp_value(x, depth + 1))
                       for k, x in vars(v).items())]
    return type(v).__name__


def _plan_fingerprint(plan) -> list:
    """Deterministic image of everything in a feature plan the scorer
    HLO depends on: per-property widths, bounds, and comparator
    parameters (the probability map constants are baked into the
    program; thresholds ride as the runtime ``min_logit`` argument and
    deliberately do NOT key the cache)."""
    return [
        [s.name, s.kind, s.v, s.chars, s.grams, s.low, s.high,
         _fp_value(s.comparator)]
        for s in plan.device_props
    ]


# Daemon threads killed mid-XLA-compile abort the process at interpreter
# teardown; atexit instead signals the warm loop to stop at the next ladder
# step and waits briefly for the in-flight compile to finish.
_WARM_SHUTDOWN = threading.Event()
_WARM_THREADS: List[threading.Thread] = []
_WARM_ATEXIT = False


def _register_warm_thread(t: threading.Thread) -> None:
    global _WARM_ATEXIT
    _WARM_THREADS.append(t)
    if not _WARM_ATEXIT:
        import atexit

        def _drain():
            _WARM_SHUTDOWN.set()
            for th in _WARM_THREADS:
                th.join(timeout=60.0)

        atexit.register(_drain)
        _WARM_ATEXIT = True


class _ScorerCache:
    """Builds/caches jitted scorers per (top_k, group_filtering) and runs the
    exact K-escalation loop."""

    # Indexed-query batches normally gather their features on device from
    # the corpus rows (only the row-index array crosses the host->device
    # link).  The sharded caches disable this: queries ride replicated over
    # the mesh, so they upload bucket-shaped feature tensors instead.
    queries_from_rows = True

    # AOT executable-store participation (ISSUE 15/18): on by default;
    # the sharded caches keep it on with mesh-annotated lowering shapes
    # and mesh facets in the store key (engine.sharded_matcher).
    supports_aot = True
    # store-key namespace: the ANN cache's programs share the ladder
    # geometry but different HLO, so the builders must never collide
    aot_builder = "corpus"

    def __init__(self, index: DeviceIndex):
        self.index = index
        self._scorers: Dict[Tuple[int, bool], object] = {}
        self._warmed = None
        self._warm_thread: Optional[threading.Thread] = None
        self._warm_compiled = 0  # successful AOT compiles (observability)
        self._aot_loaded = 0     # executables deserialized from the store
        self._warm_seconds = 0.0  # last AOT-load/ladder pass duration
        # last warm-thread failure (repr), surfaced in /healthz detail so
        # a silently-cold replica is diagnosable (ISSUE 15 satellite)
        self._warm_error: Optional[str] = None
        # shape-registered executables: (k, group_filtering, from_rows,
        # capacity, bucket) -> compiled/deserialized executable.
        # Lock-free by design: values are immutable once stored, writes
        # (the synchronous load pass, the warm thread) and reads (the
        # dispatch fast path) are GIL-atomic dict ops, and a stale read
        # only costs one jit-path fallback.
        # With DUKE_SHARED_AOT (default), this dict IS a shared ladder's
        # map (utils.jit_cache.SHARED_LADDERS): every cache with the
        # same (plan fingerprint, geometry) key points at ONE dict, so
        # N same-schema tenants share one warm pass and one set of
        # executables.  The holder indirection lets weakref.finalize
        # release the ref when this cache dies without resurrecting it.
        self._aot: Dict[tuple, object] = {}
        self._shared_holder: List[Optional[object]] = [None]
        self._shared_finalizer = None
        # serializes lease churn (rebind/release): two concurrent plan
        # moves on one cache must not double-release a lease or strand
        # an acquired one in an overwritten holder slot
        self._shared_rebind_lock = threading.Lock()

    # -- compile-ladder pre-warm / AOT load ---------------------------------

    def _ladder(self, cap: int) -> List[tuple]:
        """The (capacity, bucket, from_rows) executable ladder for the
        current shape fingerprint — the current capacity plus
        (speculatively) the next doubling step, every padding bucket,
        and both query variants (indexed gather / http-transform
        upload).  ONE enumeration shared by the AOT loader and the warm
        compiler, so a loaded ladder and a compiled ladder can never
        cover different shapes."""
        out = []
        for cap_i in (cap, cap * 2):
            for bucket in _QUERY_BUCKETS:
                for from_rows in (True, False):
                    out.append((cap_i, bucket, from_rows))
        return out

    def _ladder_k(self, cap: int) -> int:
        """Initial candidate width for a ``cap``-row corpus (the ANN
        cache overrides with its top-C)."""
        return min(_INITIAL_TOP_K, cap)

    def _min_warm_cap(self) -> int:
        """Smallest capacity the ladder lowers at — one scan chunk for
        the single-device programs; the sharded caches override with the
        mesh granule (every shard needs whole chunks)."""
        return _CHUNK

    def _store_key(self, plan, k: int, group_filtering: bool,
                   from_rows: bool, cap: int, bucket: int) -> dict:
        """The AOT store key for one ladder entry: everything the
        compiled HLO depends on that the store does not already cover
        (utils.jit_cache adds backend, device kind, jax/jaxlib versions,
        XLA flags, and the scoring-source hash)."""
        return {
            "builder": self.aot_builder,
            "plan": _plan_fingerprint(plan),
            "chunk": _CHUNK,
            "value_slots_max": _VALUE_SLOTS_MAX,
            "k": k,
            "group_filtering": bool(group_filtering),
            "from_rows": bool(from_rows),
            "cap": cap,
            "bucket": bucket,
        }

    def _shared_ladder_key(self, group_filtering: bool) -> tuple:
        """The cross-workload ladder identity: the AOT store key minus
        the per-entry facets (k, variant, capacity, bucket all live
        inside the map's akeys).  Derived through ``_store_key`` so the
        sharded caches' mesh facets ride along automatically — two
        tenants share a ladder iff their entries would share store
        files."""
        import json

        doc = self._store_key(self.index.plan, 0, group_filtering,
                              True, 0, 0)
        for facet in ("k", "from_rows", "cap", "bucket"):
            doc.pop(facet, None)
        return (json.dumps(doc, sort_keys=True, separators=(",", ":"),
                           default=str),)

    def _rebind_shared_ladder(self, group_filtering: bool) -> None:
        """Point ``self._aot`` at the shared ladder for the current
        (fingerprint, geometry) key, releasing any previous lease — the
        refcounted form of the plan-mutation eviction seam: THIS
        tenant's plan moved, so it steps off the old ladder (which
        other tenants may still be on) and onto the new key's; the old
        ladder's executables die with its last leaseholder."""
        import weakref

        from ..utils.jit_cache import (
            SHARED_LADDERS,
            release_shared_lease,
        )

        key = self._shared_ladder_key(group_filtering)
        with self._shared_rebind_lock:
            lease = self._shared_holder[0]
            if lease is not None and lease.key == key:
                return
            SHARED_LADDERS.release(lease)
            lease = SHARED_LADDERS.acquire(key)
            self._shared_holder[0] = lease
            self._aot = lease.map
            if self._shared_finalizer is None:
                self._shared_finalizer = weakref.finalize(
                    self, release_shared_lease, self._shared_holder)

    def release_shared(self) -> None:
        """Drop this cache's shared-ladder ref eagerly (index close)."""
        from ..utils.jit_cache import release_shared_lease

        with self._shared_rebind_lock:
            release_shared_lease(self._shared_holder)
            if self._aot:
                self._aot = {}

    def _warm_serial(self):
        """Context serializing warm compiles over the shared ladder so
        N same-schema tenants pay ONE compile per entry (the losers
        find it registered and skip); per-workload ladders need no
        serialization (one warm thread per cache)."""
        lease = self._shared_holder[0]
        return (lease.warm_lock if lease is not None
                else contextlib.nullcontext())

    def prewarm_async(self, group_filtering: bool) -> None:
        """Make the (query-bucket x capacity x K x variant) scorer ladder
        hot for the current corpus shapes — and speculatively the next
        capacity-doubling step — so a cold run's early batches don't
        stall on sequential jit compiles.  Safe to call often: no-ops
        while the shape fingerprint is unchanged.

        With the AOT store on (``DUKE_AOT``, default), the ladder is
        first *deserialized* synchronously — the whole point is that the
        FIRST batch after a restart scores through a stored executable,
        so the load must not race it — and the background warm thread
        becomes the miss-filler: it compiles only the entries the store
        lacked and serializes each one back (plus seeding the persistent
        XLA compile cache as before)."""
        from ..utils.jit_cache import aot_enabled, enable_persistent_cache

        aot = aot_enabled() and self.supports_aot
        prewarm = env_flag("DEVICE_PREWARM", True)
        if not aot and not prewarm:
            return
        # the warm compiles land in the persistent XLA cache (private jit
        # instances; the live scorer reads the cache on first contact) —
        # make sure it is actually on, whatever the embedding context.
        # With the AOT store on, warming helps even without it (fresh
        # executables register for the dispatch fast path directly).
        if enable_persistent_cache() is None and not aot:
            return  # no cache -> warming could never help the live scorer
        cap = max(self.index.corpus.capacity, self._min_warm_cap())
        key = (
            cap,
            tuple((s.v, s.chars, s.grams)
                  for s in self.index.plan.device_props),
            bool(group_filtering),
        )
        prev = self._warmed
        if prev == key:
            return
        self._warmed = key
        from ..utils.jit_cache import shared_aot_enabled

        if shared_aot_enabled() and self.supports_aot:
            # shared-ladder form of the eviction seam (ISSUE 19): the
            # ladder key embeds the full plan fingerprint, so a plan
            # move rebinds this cache to a DIFFERENT shared map — other
            # tenants still on the old plan keep theirs, and the old
            # ladder's executables die with its last leaseholder
            # (refcounted evict).  Capacity-only changes keep the lease
            # (the key has no capacity facet).
            self._rebind_shared_ladder(group_filtering)
        elif prev is not None and prev[1:] != key[1:]:
            # the PLAN moved (value-slot/char/set growth, demotion, filtering
            # flip): every registered executable was built for the old
            # tensor shapes, and its (k, gf, from_rows, cap, bucket) akey
            # would otherwise BLOCK the load pass from refilling that
            # slot — the stale entry would only die at dispatch as a
            # call-time reject with no refill path.  Rebind (not mutate):
            # an in-flight reader of the old dict at worst takes one
            # rejected call.  Capacity-only changes keep the map — old-cap
            # entries are unreachable but the current-cap ones stay hot.
            self._aot = {}
        missing = None
        if aot:
            missing = self._aot_load_ladder(group_filtering, key)
            if not missing:
                return  # full ladder deserialized: nothing to compile
        if not prewarm:
            return  # background compiles off: misses stay on the jit path
        t = threading.Thread(
            target=self._prewarm, args=(group_filtering, key, missing),
            daemon=True, name="scorer-prewarm",
        )
        self._warm_thread = t
        _register_warm_thread(t)
        t.start()

    def _aot_load_ladder(self, group_filtering: bool, key):
        """Deserialize every ladder entry the AOT store holds for the
        current shape fingerprint, registering each for the dispatch
        fast path; returns the (cap, bucket, from_rows) entries still
        missing (the warm thread's compile list), or the full ladder
        when the load pass itself failed."""
        from ..utils.jit_cache import AotStore

        t0 = time.monotonic()
        loaded = 0
        missing: Optional[List[tuple]] = []
        try:
            store = AotStore()
            plan = self._frozen_plan()
            for cap_i, bucket, from_rows in self._ladder(key[0]):
                k = self._ladder_k(cap_i)
                akey = (k, bool(group_filtering), bool(from_rows),
                        cap_i, bucket)
                if akey in self._aot:
                    continue
                exe = store.load(self._store_key(
                    plan, k, group_filtering, from_rows, cap_i, bucket))
                if exe is None:
                    missing.append((cap_i, bucket, from_rows))
                else:
                    self._aot[akey] = exe
                    loaded += 1
        except Exception:  # pragma: no cover - store/backend specific
            logger.exception(
                "AOT ladder load failed (falling back to compiles)")
            missing = None
        self._aot_loaded += loaded
        self._warm_seconds = time.monotonic() - t0
        if loaded:
            logger.info(
                "AOT executable cache: %d scorer(s) deserialized in "
                "%.3f s (%d missing)", loaded, self._warm_seconds,
                len(missing) if missing is not None else -1,
            )
        return missing if missing is not None else self._ladder(key[0])

    def aot_call(self, k: int, group_filtering: bool, from_rows: bool,
                 bucket: int, args: tuple):
        """Run the scoring program through a registered AOT/pre-built
        executable when one matches this exact (K, filtering, variant,
        capacity, bucket) shape; None = caller takes the jit path.  A
        shape drift (the plan mutated after the executable was built)
        raises inside the call — the entry is dropped (counted as a
        reject) and the jit path serves."""
        if not self._aot:
            return None
        akey = (k, bool(group_filtering), bool(from_rows),
                self.index.corpus.capacity, bucket)
        fn = self._aot.get(akey)
        if fn is None:
            return None
        try:
            out = fn(*args)
        except Exception:
            from ..utils.jit_cache import record_aot_reject

            record_aot_reject()
            self._aot.pop(akey, None)
            logger.warning(
                "registered AOT executable rejected at call time (plan "
                "drift since it was built?); jit path serves this shape",
                exc_info=True,
            )
            return None
        record_cache_hit()
        return out

    def _row_shapes(self):
        """Per-row feature tensor shapes under the current plan, derived by
        extracting one empty record (no corpus data needed)."""
        from ..core.records import ID_PROPERTY_NAME

        dummy = Record()
        dummy.add_value(ID_PROPERTY_NAME, "__prewarm__")
        return self.index._extract([dummy])

    def _sds(self, shape, dtype, family: str = "corpus"):
        """Lowering-shape factory: the abstract aval one ladder entry
        lowers against.  ``family`` names the partition-rule family the
        tensor belongs to ("corpus" record-axis state vs "queries"
        replicated query-side inputs) — meaningless on one device, but
        the sharded caches override this to annotate each aval with its
        mesh sharding so AOT executables compile against the real
        layouts (parallel.sharded.PARTITION_RULES)."""
        import jax

        return jax.ShapeDtypeStruct(shape, dtype)

    def _lower_args(self, row_feats, cap: int, bucket: int):
        def sds(a):
            return self._sds((cap,) + a.shape[1:], a.dtype)

        cfeats = {
            prop: {name: sds(arr) for name, arr in tensors.items()}
            for prop, tensors in row_feats.items()
        }
        mb = self._sds((cap,), np.bool_)
        mi = self._sds((cap,), np.int32)
        qr = self._sds((bucket,), np.int32, "queries")
        qg = self._sds((bucket,), np.int32, "queries")
        ml = self._sds((), np.float32, "queries")
        return cfeats, (mb, mb, mi, qg, qr, ml)

    def _probe_shapes(self):
        """Per-row feature shapes of a typical http-transform probe (not in
        the corpus, so extracted under the query plan — value width sized to
        the probe, which for the common single-valued case is 1)."""
        from ..core.records import ID_PROPERTY_NAME

        dummy = Record()
        dummy.add_value(ID_PROPERTY_NAME, "__prewarm__")
        return self.index._extract(
            [dummy], plan=self.index._query_plan([dummy])
        )

    def _lower_one(self, row_feats, cap: int, bucket: int,
                   group_filtering: bool, *, from_rows: bool = True,
                   probe_feats=None, plan=None):
        cfeats, (mb, mb2, mi, qg, qr, ml) = self._lower_args(
            row_feats, cap, bucket
        )
        k = self._ladder_k(cap)
        # a PRIVATE jit instance: tracing the live scorer object from this
        # thread while the main thread traces it too corrupts shared pjit
        # state; _build is the single builder both paths share, so the HLO
        # is identical and the XLA compile lands in the persistent cache
        # the live scorer reads
        scorer = self._build(k, group_filtering, from_rows, plan=plan)
        if from_rows:
            qfeats = {}
        else:
            qfeats = {
                prop: {
                    name: self._sds(
                        (bucket,) + arr.shape[1:], arr.dtype, "queries"
                    )
                    for name, arr in tensors.items()
                }
                for prop, tensors in probe_feats.items()
            }
        return scorer.lower(qfeats, cfeats, mb, mb2, mi, qg, qr, ml).compile()

    def _frozen_plan(self):
        """Immutable copy of the index plan for the warm thread.

        The live plan's specs mutate in place (value-slot / char-width
        growth, long-text demotion) while the main thread ingests; a
        trace in this thread reading a spec mid-mutation produced
        intermittent tracing corruption (KeyError on a jaxpr Var).  The
        copy freezes the state the warm started from; if the live plan
        moves on, these compiles are stale-but-harmless and the shape
        guard kicks a fresh warm."""
        from dataclasses import replace

        from ..ops import features as F

        return F.SchemaFeatures(
            device_props=[replace(s) for s in self.index.plan.device_props],
            host_props=list(self.index.plan.host_props),
        )

    def _prewarm(self, group_filtering: bool, key, missing=None) -> None:
        """Compile the ladder entries ``missing`` (None = the full
        ladder — the AOT store was off or its load pass failed), and
        with the store on serialize each fresh executable back so the
        NEXT process deserializes instead of compiling.  Both query
        variants ride the ladder: http-transform probes score through
        from_rows=False (bucket-shaped qfeats) and would otherwise
        stall on first-contact compiles despite the warm having run."""
        from ..utils.jit_cache import AotStore, aot_enabled

        try:
            store = (AotStore()
                     if aot_enabled() and self.supports_aot else None)
            plan = self._frozen_plan()
            row_feats = self._row_shapes()
            probe_feats = self._probe_shapes()
            entries = self._ladder(key[0]) if missing is None else missing
            self._prewarm_entries(entries, key, group_filtering, store,
                                  plan, row_feats, probe_feats)
        except Exception as e:  # pragma: no cover - warm failures are rare
            # counted + latched (ISSUE 15 satellite): a silently-cold
            # replica — scoring works but every first-contact shape pays
            # a live compile — must be diagnosable from /healthz
            telemetry.PREWARM_FAILURES.inc()  # dukecheck: ignore[DK502] rare event: warm-thread failure, never per-block
            self._warm_error = repr(e)
            logger.exception(
                "scorer pre-warm failed (scoring unaffected, but this "
                "replica stays cold)")

    @staticmethod
    def _cache_bypass():
        """Thread-local context disabling jax's persistent compilation
        cache for one warm compile.  Compiles destined for the AOT store
        must be FRESH: an XLA compile served from that cache yields an
        executable that serializes thin (missing jit symbols — see
        AotStore.save).  The live path keeps its cache (thread-local
        config); direct registration supersedes the old cache-seeding
        role."""
        try:
            from jax._src.config import enable_compilation_cache

            return enable_compilation_cache(False)
        except Exception:  # pragma: no cover - private jax API drift
            return contextlib.nullcontext()  # save()'s validation guards

    def _prewarm_entries(self, entries, key, group_filtering, store,
                         plan, row_feats, probe_feats) -> None:
        for cap_i, bucket, from_rows in entries:
            if self._warmed != key or _WARM_SHUTDOWN.is_set():
                return  # superseded / interpreter exiting
            k = self._ladder_k(cap_i)
            akey = (k, bool(group_filtering), bool(from_rows),
                    cap_i, bucket)
            if akey in self._aot:
                # already registered — on a shared ladder this is the
                # fingerprint-batched prewarm: another tenant's warm (or
                # load pass) filled the slot, so this tenant pays zero
                continue
            with self._warm_serial():
                if akey in self._aot:
                    continue  # lost the race: the winner compiled it
                record_compile()
                ctx = (self._cache_bypass() if store is not None
                       else contextlib.nullcontext())
                t_compile = time.monotonic()
                with ctx, tracing.span("scorer.compile", annotate=True):
                    compiled = self._lower_one(
                        row_feats, cap_i, bucket, group_filtering,
                        from_rows=from_rows,
                        probe_feats=None if from_rows else probe_feats,
                        plan=plan,
                    )
                costs.note_compile(time.monotonic() - t_compile)
                self._warm_compiled += 1
                # serve the fresh executable directly — first contact in
                # THIS process skips the live jit trace too; setdefault
                # so a deserialized entry (or a newer warm) is never
                # replaced mid-use
                self._aot.setdefault(akey, compiled)
            if store is not None and not store.save(
                    self._store_key(plan, k, group_filtering,
                                    from_rows, cap_i, bucket),
                    compiled):
                # this backend cannot serialize executables (or the
                # store is unwritable): stop bypassing the persistent
                # XLA compile cache — without the fallback, NOTHING
                # would seed it (the live path serves from the _aot
                # registrations) and every restart would re-pay the
                # full ladder compile, a regression vs the pre-AOT
                # behavior.  Remaining entries compile cache-enabled,
                # converging on the legacy restart story.
                store = None
                logger.warning(
                    "AOT executable save unsupported here; remaining "
                    "warm compiles seed the persistent XLA cache "
                    "instead")

    def _build(self, top_k: int, group_filtering: bool, from_rows: bool,
               plan=None):
        """The ONE scorer builder — both the live cached path (_scorer) and
        the prewarm's private instances (_lower_one) go through it, so the
        two can never drift onto different HLO (which would silently turn
        pre-warming into cache-missing busywork).  ``plan`` overrides for
        the warm thread's frozen copy (_frozen_plan)."""
        from ..ops import scoring as S

        return S.build_corpus_scorer(
            plan or self.index.plan, chunk=_CHUNK, top_k=top_k,
            group_filtering=group_filtering, queries_from_rows=from_rows,
        )

    def _scorer(self, top_k: int, group_filtering: bool,
                from_rows: bool = False):
        key = (top_k, group_filtering, from_rows)
        if key not in self._scorers:
            # a build here is a first-contact shape: XLA compiles at the
            # first call (or reads the persistent cache).  The counter
            # pair makes recompile storms visible on /metrics.
            record_compile()
            t_compile = time.monotonic()
            with tracing.span("scorer.compile", annotate=True):
                self._scorers[key] = self._build(top_k, group_filtering,
                                                 from_rows)
            costs.note_compile(time.monotonic() - t_compile)
        else:
            record_cache_hit()
        return self._scorers[key]

    def _scanned_rows(self, corpus: DeviceCorpus) -> int:
        """Corpus rows one call of this cache's scorer scans: whole chunks
        up to the valid high-water mark (``scan_topk``'s ``live_bound``;
        never past the capacity, a whole number of chunks).  The mesh
        cache overrides it with the capacity its full scan covers."""
        return corpus.live_chunks(_CHUNK) * _CHUNK

    def _min_logit(self) -> float:
        from ..ops import scoring as S

        index = self.index
        # the long-validated 1e-3 insurance margin covering float32
        # kernel error at the bound (differential-tested; surviving pairs
        # are re-scored host-exact, so it only costs extra
        # finalizations).  Deliberately NOT widened to the certified
        # per-plan margin: for degenerate configs (low=0.0 / high=1.0)
        # the certified bound explodes and would disable the filter
        # entirely; such schemas instead get an empty decisive band
        # (prune bound below this filter bound -> nothing skipped), which
        # degrades to rescore-everything, never to unsoundness.  Both
        # bounds derive from the ONE emit_bound_logit formula so the
        # threshold/host-bound handling can never drift apart.
        return S.emit_bound_logit(index.schema, index.plan, 1e-3)

    def _prepare_queries(self, records: Sequence[Record],
                         group_filtering: bool):
        """Query-side arrays for a block: (qfeats device tree or {} when the
        scorer gathers on device, from_rows flag, query_row, query_group)."""
        import jax.numpy as jnp

        index = self.index
        bucket = bucket_for(len(records))
        # padding-bucket visibility: which static shapes blocks land on
        # and how many padded rows they carry (unlocked counters — this
        # is the scoring path; see telemetry.QUERY_BLOCKS)
        blocks_child, pad_child = _BUCKET_CHILDREN[bucket]
        blocks_child.inc()
        if bucket > len(records):
            pad_child.inc(bucket - len(records))
        # (a block larger than the biggest bucket is split by the caller)
        rows = [index.id_to_row.get(r.record_id, -1) for r in records]
        from_rows = self.queries_from_rows and all(row >= 0 for row in rows)
        if from_rows:
            # normal dedup/linkage path: the batch was just indexed, so its
            # features already sit on device in the corpus tensors — the
            # scorer gathers them there from query_row, and the only
            # query-side upload is the row-index array (host->device
            # traffic is the dominant steady-state cost over a
            # high-latency device link)
            qfeats = {}
        else:
            # http-transform: queries are not in the corpus; extract under a
            # query-sized value axis (a probe may carry more values than any
            # indexed record — the corpus plan must not widen for it)
            qfeats_np = index._extract(
                records, plan=index._query_plan(records)
            )
            qfeats = {
                prop: {
                    name: jnp.asarray(_pad_rows(arr, bucket))
                    for name, arr in tensors.items()
                }
                for prop, tensors in qfeats_np.items()
            }
        query_row = np.full((bucket,), -1, dtype=np.int32)
        query_group = np.full((bucket,), -2, dtype=np.int32)
        for i, r in enumerate(records):
            query_row[i] = rows[i]
            group_no = r.get_value(GROUP_NO_PROPERTY_NAME)
            if group_filtering and not group_no:
                # host-engine parity (index.inverted.find_candidate_matches)
                raise ValueError(
                    f"The '{GROUP_NO_PROPERTY_NAME}' property was missing "
                    "or empty!"
                )
            query_group[i] = int(group_no) if group_no else -2
        return (qfeats, from_rows, jnp.asarray(query_row),
                jnp.asarray(query_group))

    def dispatch_block(self, records: Sequence[Record], *,
                       group_filtering: bool):
        """Enqueue the device scoring program for a query block and return
        a pending handle — JAX dispatch is asynchronous, so the host can
        finalize the *previous* block (or extract the next) while the
        device crunches this one.  ``resolve`` blocks on the result and
        runs the (rare) K-escalation loop synchronously.
        """
        from ..ops import scoring as S
        import jax.numpy as jnp

        index = self.index
        corpus = index.corpus
        n = len(records)
        min_logit = self._min_logit()

        if corpus.size == 0:
            return _BlockResult(
                np.full((n, 1), S.NEG_INF, np.float32),
                np.full((n, 1), -1, np.int32), min_logit,
            )

        qfeats, from_rows, query_row_j, query_group_j = self._prepare_queries(
            records, group_filtering
        )
        bucket = int(query_row_j.shape[0])
        cfeats, cvalid, cdeleted, cgroup = corpus.device_arrays()
        args = (cfeats, cvalid, cdeleted, cgroup, query_group_j,
                query_row_j, jnp.float32(min_logit))
        scanned_child, capacity_child = _SCAN_ROWS_CHILDREN
        scanned, capacity = self._scanned_rows(corpus), corpus.capacity

        def call(k):
            scanned_child.inc(scanned)
            capacity_child.inc(capacity)
            # AOT fast path (ISSUE 15): a deserialized/pre-built
            # executable registered for this exact shape skips the jit
            # trace entirely — a restarted process's first batch scores
            # with ZERO compiles (tests/test_aot_cache.py)
            out = self.aot_call(k, group_filtering, from_rows, bucket,
                                (qfeats,) + args)
            if out is not None:
                return out
            return self._scorer(k, group_filtering, from_rows)(qfeats, *args)

        k = min(_INITIAL_TOP_K, corpus.capacity)
        # brute force is exact for any K that fits every candidate above
        # the bound: escalate while some query overflowed K
        pending = _PendingBlock(
            corpus.capacity, n, min_logit, k, call,
            lambda cmax, kk: cmax > kk, *call(k)
        )
        # query-side context for the post-resolve dd rescore (ISSUE 12):
        # the same uploaded/gathered query features the scorer used
        pending.dd_ctx = (qfeats, from_rows, query_row_j)
        return pending

    def score_block(self, records: Sequence[Record], *,
                    group_filtering: bool) -> _BlockResult:
        pending = self.dispatch_block(records, group_filtering=group_filtering)
        return resolve_block(pending)

    # device-resident certified finalization (ISSUE 12/18): on for every
    # single-process backend — the sharded caches route the survivor
    # gather through a replicated-layout mesh program first (_dd_call
    # override in engine.sharded_matcher, gated off multi-host meshes)
    # and then run the same dd rescorer
    supports_dd = True

    def dd_rescore(self, result: _BlockResult):
        """Run the dd survivor rescore for a resolved block.

        Returns (hi, lo, unsafe) numpy arrays aligned with
        ``result.top_index`` — the two-float emulated-f64 logit over the
        dd-certifiable device properties plus the truncation-safety mask
        (ops.scoring.build_dd_rescorer) — or None when the block cannot
        ride the device (no certifiable property, no survivors at all,
        multi-host mesh).  Collective-free on multi-host: under a
        dispatcher this extra device program runs on the frontend only,
        so the sharded caches expose ``supports_dd`` only when the whole
        mesh is addressable from this process (their ``_dd_call`` gather
        IS a collective — safe single-process, a deadlock cross-host).
        """
        if not self.supports_dd:
            return None
        ctx = result.dd_ctx
        if ctx is None:
            return None
        from ..ops import scoring as S
        import jax.numpy as jnp

        plan = self.index.plan
        # block-level dispatch gate: only survivors whose f32 logit sits
        # low enough to possibly be a certified reject justify the
        # program (dd_gate_bound — certified events and residue take the
        # host compare either way).  Also skips empty blocks, and small
        # tests never pay the first-contact compile.
        gate = S.dd_gate_bound(self.index.schema, plan)
        candidates = ((result.top_logit > result.min_logit)
                      & (result.top_logit <= gate))
        if not bool(candidates.any()):
            return None
        qfeats, from_rows, query_row_j = ctx
        fn = S.dd_rescorer(
            plan, queries_from_rows=from_rows,
            value_slots_cap=_VALUE_SLOTS_MAX,
        )
        if fn is None:
            return None
        cfeats_all = self.index.corpus.device_arrays()[0]
        cfeats = {s.name: cfeats_all[s.name] for s in S.dd_plan_specs(plan)}
        hi, lo, unsafe = self._dd_call(fn, qfeats, cfeats, query_row_j,
                                       jnp.asarray(result.top_index))
        return (np.asarray(hi), np.asarray(lo), np.asarray(unsafe))

    def _dd_call(self, fn, qfeats, cfeats, query_row_j, top_index):
        """Run the dd program against the corpus tensors.  One device:
        the gather happens inside ``fn``.  The sharded caches override
        this to pre-gather the survivors to replicated layout and feed
        ``fn`` an identity index — same program, same arithmetic, so the
        verdicts stay bit-identical across backends."""
        return fn(qfeats, cfeats, query_row_j, top_index)


class _PendingBlock:
    """In-flight device scoring call (see ``_ScorerCache.dispatch_block``).

    ``call(k)`` re-invokes the jitted scorer at width ``k``;
    ``needs_escalation(count_max, k)`` is the backend's saturation
    predicate (brute force: some query overflowed K; ANN: retrieval
    saturated at C).
    """

    def __init__(self, capacity, n, min_logit, k, call, needs_escalation,
                 top_logit, top_index, count, stage: str = "top_k"):
        self.capacity = capacity
        self.n = n
        self.min_logit = min_logit
        self.k = k
        self.call = call
        self.needs_escalation = needs_escalation
        self.top_logit = top_logit
        self.top_index = top_index
        self.count = count
        # retrieval stage the escalation metric attributes re-runs to:
        # "top_k" (brute force), "top_c" (flat ANN), "ivf" (cell probe,
        # incl. its terminal flat-scan fallback)
        self.stage = stage


# process-wide escalation count (observability: the F1-at-scale harness
# reports how often K/C-escalation actually fired at a given corpus size).
# Guarded: resolve_block runs on multiple workload threads in service mode.
ESCALATIONS = 0
_ESCALATIONS_LOCK = threading.Lock()


def _count_escalation(stage: str = "top_k") -> None:
    global ESCALATIONS
    with _ESCALATIONS_LOCK:
        ESCALATIONS += 1
    # mirrored on /metrics; escalations are rare by construction (each
    # doubles K), so the registry update is off the steady-state path
    telemetry.SCORER_ESCALATIONS.inc()  # dukecheck: ignore[DK502] rare by construction (each escalation doubles K)
    # stage-attributed series (ISSUE 9): brute-force K, flat-ANN C, or
    # IVF probe escalations tell different capacity stories
    telemetry.RETRIEVAL_ESCALATIONS.labels(stage=stage).inc()  # dukecheck: ignore[DK501,DK502] rare by construction (each escalation doubles the width)


def resolve_block(pending) -> _BlockResult:
    """Wait for a dispatched block; re-run with doubled width if the
    backend's saturation predicate fires (exactness / recall contract)."""
    if isinstance(pending, _BlockResult):  # empty-corpus short-circuit
        return pending
    import jax

    k = pending.k
    top_logit, top_index, count = (
        pending.top_logit, pending.top_index, pending.count
    )
    while True:
        # ONE device fetch for all three outputs: fetching the count
        # first and the logits after costs a second device round trip
        # per block in the common no-escalation case; the logits are
        # ~256 KB, so fetching them with the count costs little
        count_np, logit_np, index_np = jax.device_get(
            (count, top_logit, top_index)
        )
        cmax = int(count_np[: pending.n].max(initial=0))
        if k >= pending.capacity or not pending.needs_escalation(cmax, k):
            res = _BlockResult(logit_np, index_np, pending.min_logit)
            res.dd_ctx = getattr(pending, "dd_ctx", None)
            return res
        k = min(k * 2, pending.capacity)
        _count_escalation(getattr(pending, "stage", "top_k"))
        logger.info(
            "escalation: %d candidates at the bound, retrying with "
            "width=%d", cmax, k,
        )
        top_logit, top_index, count = pending.call(k)


def _pad_rows(arr: np.ndarray, bucket: int) -> np.ndarray:
    n = arr.shape[0]
    if n == bucket:
        return arr
    out = np.zeros((bucket,) + arr.shape[1:], dtype=arr.dtype)
    out[:n] = arr
    return out


class DeviceProcessor:
    """Drop-in for ``engine.processor.Processor`` running the TPU path.

    Same listener event protocol (SURVEY.md section 1 L1); the per-record
    candidate loop becomes: block queries -> one device scoring program ->
    host finalization of the surviving top-K pairs.
    """

    # brute force scores every live corpus row with the exact comparator
    # kernels; the ANN subclass retrieves then rescores only top-C, so its
    # pairs_compared stat must count the rescored candidates instead
    exhaustive = True
    # multi-host follower replicas replay only the device-program side of
    # a batch (parallel.dispatch): host finalization of survivors — and
    # everything downstream of it (listeners, link DBs) — runs on the
    # frontend alone.  The device-program ORDER must stay identical either
    # way, so the flag guards only the per-query host loop.
    finalize_survivors = True

    def __init__(self, schema: DukeSchema, database: DeviceIndex, *,
                 group_filtering: bool = False, profile: bool = False,
                 threads: int = 1):
        from ..telemetry.decisions import DecisionRecorder
        from .explain import host_breakdown
        from .finalize import FinalizeExecutor

        self.schema = schema
        self.database = database
        self.group_filtering = group_filtering
        self.profile = profile
        self.listeners: List[MatchListener] = []
        self.stats = ProfileStats()
        # single-writer per-batch phase durations (workload lock holds
        # the writer exclusivity; readers are lock-free scrapes)
        self.phases = PhaseRecorder()
        # decision flight recorder + quality-drift monitors (ISSUE 5):
        # written ONLY by the coordinating thread that emits listener
        # events (single-writer), scraped lock-free by /metrics and
        # served by /debug/decisions
        self.decisions = DecisionRecorder(
            schema.threshold, schema.maybe_threshold,
            breakdown=lambda q, c: host_breakdown(schema, q, c),
            resolver=database.find_record_by_id,
        )
        self._scorers = database.scorer_cache
        # host finalization of the surviving top-K pairs fans out over
        # this executor (DUKE_FINALIZE_THREADS overrides ``threads``);
        # events still emit in strict query order (engine.finalize)
        self.finalizer = FinalizeExecutor(threads)
        # compile the scorer shape ladder in the background while the
        # service finishes startup / the first batches are parsed
        self._scorers.prewarm_async(group_filtering)

    def add_match_listener(self, listener: MatchListener) -> None:
        self.listeners.append(listener)

    # host-exact pair probability: surviving pairs are finalized with the
    # same double-precision math as the host engine, so threshold decisions
    # and reported confidences are bit-identical to ``engine.processor``
    # (SURVEY.md section 7 hard part 4) — the device program is a pruning
    # filter, never the source of emitted probabilities.
    def compare(self, r1: Record, r2: Record) -> float:
        from .processor import Processor

        return Processor.compare(self, r1, r2)

    def deduplicate(self, records: Sequence[Record]) -> None:
        t0 = time.monotonic()
        for listener in self.listeners:
            listener.batch_ready(len(records))

        # annotate=True bridges the span into jax.profiler.TraceAnnotation
        # while an on-demand capture is live (utils/profiling), so the
        # device timeline carries the same phase names as the trace tree
        with tracing.span(PHASE_ENCODE, {"records": len(records)},
                          annotate=True):
            for record in records:
                self.database.index(record)
            self.database.commit()
        encode_dt = time.monotonic() - t0
        self.phases.observe(PHASE_ENCODE, encode_dt)
        retrieval0 = self.stats.retrieval_seconds
        compare0 = self.stats.compare_seconds
        # corpus growth / value-slot widening changes the scorer shapes;
        # kick the (no-op-when-unchanged) background warm for the new
        # fingerprint plus the next doubling step
        self._scorers.prewarm_async(self.group_filtering)

        # multi-host serving: followers replay the scoring pass with the
        # same query records (the corpus mutation already broadcast from
        # commit()); must precede _score_blocks so every process enqueues
        # the block programs in the same global order
        from ..parallel import dispatch

        key = getattr(self.database, "_dispatch_key", None)
        d = dispatch.current() if key is not None else None
        if d is not None:
            d.broadcast(dispatch.with_trace_ctx(("score", key, list(records))))
        # a frontend that aborts mid-pass (listener exception, OOM) has
        # entered fewer collective programs than the followers it just
        # instructed — latch before propagating (advisor r4 medium)
        with dispatch.latch_on_failure(
            d, "frontend scoring pass aborted after broadcast"
        ):
            self._score_blocks(records)

        self.stats.batches += 1
        retrieve_dt = self.stats.retrieval_seconds - retrieval0
        score_dt = self.stats.compare_seconds - compare0
        self.phases.observe(PHASE_RETRIEVE, retrieve_dt)
        self.phases.observe(PHASE_SCORE, score_dt)
        t_persist = time.monotonic()
        with tracing.span(PHASE_PERSIST, annotate=True):
            for listener in self.listeners:
                listener.batch_done()
        persist_dt = time.monotonic() - t_persist
        self.phases.observe(PHASE_PERSIST, persist_dt)
        # the same four durations feed the process-wide busy ledger, so
        # per-workload phase counters reconcile against it by definition
        costs.note_busy(encode_dt + retrieve_dt + score_dt + persist_dt)
        if self.profile:
            logger.info(
                "batch=%d records, corpus=%d, %.3fs",
                len(records), self.database.corpus.size,
                time.monotonic() - t0,
            )

    def _score_blocks(self, records: Sequence[Record]) -> None:
        """The device-program side of a batch: double-buffered block
        dispatch + escalation, then (frontend only) host finalization.

        Multi-host follower replicas call this directly with
        ``finalize_survivors=False``: the dispatch structure — block
        order, pre-dispatch of block N+1 before block N resolves,
        escalation re-runs — must match the frontend program-for-program
        or the cross-host collectives deadlock, so the loop is shared
        rather than reimplemented (parallel.dispatch invariant 2).
        """
        corpus = self.database.corpus
        # incremental counter (append/tombstone-maintained): the per-batch
        # O(capacity) mask scans + boolean fancy-index allocation this
        # replaces were real work at 10M rows
        live_rows = corpus.live_rows

        from ..utils.profiling import trace_batch

        # double-buffered dispatch: block N+1's device program is enqueued
        # before block N's results are fetched, so host finalization of N
        # overlaps device scoring of N+1 (SURVEY.md section 7 hard part 6)
        blocks = [
            records[start:start + _QUERY_BUCKETS[-1]]
            for start in range(0, len(records), _QUERY_BUCKETS[-1])
        ]
        pending = None
        if blocks:
            pending = self._scorers.dispatch_block(
                blocks[0], group_filtering=self.group_filtering
            )
        for bi, block in enumerate(blocks):
            t1 = time.monotonic()
            nxt = None
            # the retrieve/score spans cover exactly what the phase
            # timers below measure: the host's wait for block bi (with
            # block bi+1's dispatch), then its finalization
            with tracing.span(PHASE_RETRIEVE, annotate=True):
                if bi + 1 < len(blocks):
                    nxt = self._scorers.dispatch_block(
                        blocks[bi + 1],
                        group_filtering=self.group_filtering,
                    )
                with trace_batch(f"score_block[{len(block)}]"):
                    result = resolve_block(pending)
            pending = nxt
            t2 = time.monotonic()
            self.stats.retrieval_seconds += t2 - t1

            if not self.finalize_survivors:
                continue
            with tracing.span(PHASE_SCORE, annotate=True):
                if self.finalizer.device:
                    # dd survivor rescore: one more collective-
                    # free device program over the resolved (Q, K) pair
                    # list; engine.finalize certifies verdicts against it
                    # and skips the host compare for certified rejects
                    result.dd = self._scorers.dd_rescore(result)
                # parallel host finalization: workers compute the exact
                # f64 rescores (and the decisive-band skips) per query;
                # events then emit HERE, serially and in query order, so
                # listener streams and link rows are identical to the
                # serial path at any DUKE_FINALIZE_THREADS
                # (engine.finalize)
                outcomes = self.finalizer.finalize_block(self, block, result)
                stats = self.stats
                for qi, (record, out) in enumerate(zip(block, outcomes)):
                    for event, candidate, prob in out.events:
                        self._emit(event, record, candidate, prob)
                    if not out.events:
                        for listener in self.listeners:
                            listener.no_match_for(record)
                    if out.decisions:
                        # drift monitors + sampled/latched ring records,
                        # on the serial event-coordinator thread
                        # (single-writer)
                        self.decisions.observe(
                            record, out.decisions, prune=out.prune,
                            margin=out.margin, host_bound=out.host_bound,
                        )
                    stats.records_processed += 1
                    stats.candidates_retrieved += out.survivors
                    stats.pairs_rescored += out.rescored
                    stats.pairs_skipped += out.skipped
                    stats.pairs_device_certified += out.device_certified
                    stats.dd_residue_margin += out.residue_margin
                    stats.dd_residue_kind += out.residue_kind
                    stats.dd_residue_truncation += out.residue_truncation
                    if self.exhaustive:
                        # the device ran the exact comparator kernels
                        # against every live corpus row for this query
                        stats.pairs_compared += live_rows
                    else:
                        # ANN: exact kernels ran only on the retrieved
                        # top-C (the retrieval matmul touches every row,
                        # but that is blocking work, not pair comparison)
                        stats.pairs_compared += int(
                            (result.top_index[qi] >= 0).sum()
                        )
            self.stats.compare_seconds += time.monotonic() - t2

    def _emit(self, event: str, r1: Record, r2: Record, prob: float) -> None:
        for listener in self.listeners:
            getattr(listener, event)(r1, r2, prob)
