"""Digest-keyed feature-row cache: make re-encode of unchanged records free.

A Sesam full resync — the reference's normal sync mode — re-POSTs entire
datasets of mostly-unchanged entities.  The corpus is append-only with
digest-tracked re-upserts (engine.device_matcher), so the *index* side of a
re-upsert is cheap (tombstone + append), but every appended row re-runs
per-record host feature extraction (ops.features.extract_batch) even when
the record's bytes did not change.  After PR 3 removed the post-device
finalization bottleneck, that re-extraction is the serial segment bounding
steady-state ingest.

This module caches extracted feature ROWS keyed by

    (record content digest, feature-plan fingerprint)

where the digest is the store's canonical per-record digest
(``store.records.record_digest`` — the exact bytes the durable store folds,
so a cache hit is guaranteed to describe the same record content) and the
fingerprint covers everything that shapes or parameterizes extraction:
per-property kind, value-slot width, char width, comparator class (and its
``q``), the global gram/token paddings, the char-tensor dtype, and the ANN
encoder (dim, props, storage dtype) when one rides along.  Value-slot
widening, char-width growth, long-text demotion, and schema changes all
change the fingerprint, so stale rows can never be scattered into a
corpus built under a different plan — the cache is self-invalidating, no
explicit flush hooks anywhere.

Budget: ``DUKE_FEATURE_CACHE_MB`` (default 256; ``0`` disables) bounds the
cached tensor bytes with LRU eviction.  One row is ~1 KB for a typical
schema, so the default holds a few hundred thousand hot rows.

Consumers: ``ops.features.extract_batch`` consults the cache for every
batch — corpus appends, config-reload / plan-change rebuilds, and
query-side extraction (http-transform probes, follower score replay) all
share that one entry point, so they all hit when their plan matches the
plan the rows were cached under.  ``engine.device_matcher.snapshot_load``
pre-warms the cache from the restored corpus tensors so the FIRST resync
after a restart is already warm.

Thread safety: one lock around the LRU map.  The workload lock serializes
the ingest path, but the scorer pre-warm thread extracts dummy records and
the restart warm path runs outside it, so the cache must not rely on it.
"""

from __future__ import annotations

import collections
import threading
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from ..telemetry.env import env_int

DEFAULT_MB = 256

# per-entry bookkeeping overhead (key bytes, dict-of-dict structure) added
# to the tensor bytes so the budget tracks real memory, not just payload
_ENTRY_OVERHEAD = 256

RowDict = Dict[str, Dict[str, np.ndarray]]


class FeatureCache:
    """Byte-budgeted LRU of extracted feature rows."""

    def __init__(self, budget_bytes: int):
        self.budget_bytes = int(budget_bytes)
        self._lock = threading.Lock()
        self._rows: "collections.OrderedDict[tuple, Tuple[RowDict, int]]" = (
            collections.OrderedDict()  # guarded by: self._lock
        )
        self.bytes = 0  # guarded by: self._lock [writes]
        # monotonic, single-writer-per-increment under self._lock; scraped
        # lock-free by the /metrics process collector (torn reads of a
        # plain int are fine for visibility counters)
        self.hits = 0  # guarded by: self._lock [writes]
        self.misses = 0  # guarded by: self._lock [writes]
        self.evicted = 0  # guarded by: self._lock [writes]

    def __len__(self) -> int:
        with self._lock:
            return len(self._rows)

    def get_many(self, fp, digests: Sequence[Optional[bytes]]
                 ) -> Dict[int, RowDict]:
        """Look up a batch; returns ``{batch_index: row}`` for the hits.

        ``None`` digests (records without an ID, foreign record-likes)
        always miss and are counted as misses — they are rows the cache
        cannot help with, which is exactly what the hit ratio should say.
        """
        out: Dict[int, RowDict] = {}
        with self._lock:
            for i, digest in enumerate(digests):
                if digest is None:
                    continue
                entry = self._rows.get((fp, digest))
                if entry is not None:
                    self._rows.move_to_end((fp, digest))
                    out[i] = entry[0]
            self.hits += len(out)
            self.misses += len(digests) - len(out)
        return out

    def put_many(self, fp, items: Iterable[Tuple[bytes, RowDict]]) -> None:
        """Insert freshly extracted rows; evicts LRU past the byte budget."""
        with self._lock:
            for digest, row in items:
                nbytes = _ENTRY_OVERHEAD + sum(
                    arr.nbytes for tensors in row.values()
                    for arr in tensors.values()
                )
                if nbytes > self.budget_bytes:
                    continue  # a single over-budget row would only thrash
                key = (fp, digest)
                old = self._rows.pop(key, None)
                if old is not None:
                    self.bytes -= old[1]
                self._rows[key] = (row, nbytes)
                self.bytes += nbytes
            while self.bytes > self.budget_bytes and self._rows:
                _, (_, nbytes) = self._rows.popitem(last=False)
                self.bytes -= nbytes
                self.evicted += 1

    def clear(self) -> None:
        with self._lock:
            self._rows.clear()
            self.bytes = 0


_CACHE: Optional[FeatureCache] = None
_CACHE_MB: Optional[int] = None
_CACHE_LOCK = threading.Lock()


def budget_mb() -> int:
    return env_int("DUKE_FEATURE_CACHE_MB", DEFAULT_MB)


def active() -> Optional[FeatureCache]:
    """The process-wide cache, or None when disabled.  Re-reads the env
    budget on every call (cheap) so tests can flip it live; a budget
    change replaces the cache (operators never change env mid-process)."""
    global _CACHE, _CACHE_MB
    mb = budget_mb()
    if mb <= 0:
        return None
    with _CACHE_LOCK:
        if _CACHE is None or _CACHE_MB != mb:
            _CACHE = FeatureCache(mb << 20)
            _CACHE_MB = mb
        return _CACHE


def reset() -> None:
    """Drop the process-wide cache (tests)."""
    global _CACHE, _CACHE_MB
    with _CACHE_LOCK:
        _CACHE = None
        _CACHE_MB = None


def stats() -> Tuple[int, int, int, int]:
    """(hits, misses, evicted_rows, bytes) of the active cache — zeros when
    disabled.  Lock-free snapshot reads (scrape path must never block)."""
    cache = _CACHE if budget_mb() > 0 else None
    if cache is None:
        return (0, 0, 0, 0)
    return (cache.hits, cache.misses, cache.evicted, cache.bytes)


# -- keys ---------------------------------------------------------------------


def plan_fingerprint(plan, encoder=None) -> tuple:
    """Everything that parameterizes extraction for ``plan``.

    Deliberately EXCLUDES low/high probability bounds: they shape scoring,
    not the extracted tensors, so a config reload that only retunes
    thresholds re-uses every cached row.  Includes the comparator class
    name (PHONETIC covers Soundex/Metaphone/Norphone, which extract
    different codes) and QGram's ``q``.

    The encoder leg carries the resolved embedding storage mode (bf16 vs
    the DUKE_EMB_INT8 per-row symmetric int8 + scale layout) and the
    DUKE_IVF retrieval mode — mirroring the ``emb_storage`` snapshot
    guard (engine.ann_matcher) in the cache key, so a dtype or IVF flip
    between restarts self-invalidates cached rows instead of scattering
    one storage layout into a corpus built under the other.
    """
    from . import features as F

    specs = tuple(
        (s.name, s.kind, int(s.values_per_record), int(s.chars),
         int(s.grams), type(s.comparator).__name__,
         getattr(s.comparator, "q", None))
        for s in plan.device_props
    )
    enc = None
    if encoder is not None:
        from . import encoder as E
        from . import ivf

        enc = (int(encoder.dim), tuple(encoder.props),
               getattr(encoder, "storage", None) or E.storage_name(),
               bool(ivf.enabled()))
    return (specs, F.MAX_GRAMS, F.MAX_TOKENS,
            str(np.dtype(F.CHAR_DTYPE)), enc)


def record_key(record) -> Optional[bytes]:
    """Canonical content digest for ``record``, or None when the record
    cannot be keyed (no ID / foreign record-like) — such rows extract
    directly and are never cached."""
    from ..store.records import record_digest

    try:
        if record.record_id is None:
            return None
        return record_digest(record)
    except (AttributeError, ValueError, TypeError):
        return None


# -- batch assembly -----------------------------------------------------------


def _row_slice(feats: RowDict, j: int) -> RowDict:
    """Copy row ``j`` out of batch tensors (a view would pin the whole
    batch's memory and break the byte accounting).

    The trailing ``reshape`` pins the cached row to exactly the batch
    tensor's per-row shape: ``np.ascontiguousarray`` promotes 0-d slices
    to ``(1,)``, which would make rows of 1-D per-row tensors (the int8
    embedding scale) scatter back with a phantom axis on the all-hit
    path and silently produce ``(n, 1)`` where misses produce ``(n,)``.
    """
    return {
        prop: {name: np.ascontiguousarray(arr[j]).reshape(arr.shape[1:])
               for name, arr in tensors.items()}
        for prop, tensors in feats.items()
    }


def cached_extract(cache: FeatureCache, plan, records, *,
                   encoder=None) -> RowDict:
    """``features.extract_batch`` semantics through the cache: hits scatter
    from cached rows, misses extract through the normal path (including
    the shared-memory parallel fan-out when the miss slab qualifies) and
    are inserted for the next sync."""
    from . import features as F

    if not records:
        return F._extract_direct(plan, records, encoder=encoder)
    n = len(records)
    fp = plan_fingerprint(plan, encoder)
    keys = [record_key(r) for r in records]
    hits = cache.get_many(fp, keys)
    miss_idx = [i for i in range(n) if i not in hits]

    miss_out = None
    if miss_idx:
        miss_out = F._extract_direct(
            plan, [records[i] for i in miss_idx], encoder=encoder
        )

    if not hits:
        out = miss_out  # no hits and records non-empty => all missed
    else:
        # output shapes/dtypes from the miss extraction when there is one
        # (authoritative for this plan), else from any cached row (same
        # fingerprint => same layout by construction)
        if miss_out is not None:
            shapes = {
                (prop, name): (arr.shape[1:], arr.dtype)
                for prop, tensors in miss_out.items()
                for name, arr in tensors.items()
            }
        else:
            proto = hits[next(iter(hits))]
            shapes = {
                (prop, name): (arr.shape, arr.dtype)
                for prop, tensors in proto.items()
                for name, arr in tensors.items()
            }
        out = {}
        for (prop, name), (shape, dtype) in shapes.items():
            out.setdefault(prop, {})[name] = np.zeros(
                (n,) + shape, dtype=dtype
            )
        hit_idx = np.fromiter(sorted(hits), dtype=np.int64, count=len(hits))
        miss_arr = np.asarray(miss_idx, dtype=np.int64)
        for (prop, name) in shapes:
            dst = out[prop][name]
            if miss_out is not None and miss_arr.size:
                dst[miss_arr] = miss_out[prop][name]
            if hit_idx.size:
                dst[hit_idx] = np.stack(
                    [hits[int(i)][prop][name] for i in hit_idx]
                )

    if miss_out is not None:
        cache.put_many(fp, (
            (keys[i], _row_slice(miss_out, j))
            for j, i in enumerate(miss_idx)
            if keys[i] is not None
        ))
    return out


# -- restart pre-warm ---------------------------------------------------------


def prewarm(plan, encoder, feats: RowDict, id_to_row: Dict[str, int],
            digest_iter: Iterable[Tuple[str, bytes]],
            cache: FeatureCache) -> int:
    """Seed the cache from restored corpus tensors (snapshot load).

    ``digest_iter`` yields (record_id, canonical digest) — from the
    durable store's raw rows (``RecordStore.row_digests``), so no record
    decode happens here.  Stops at the byte budget: a 10M-row corpus
    warms only as many rows as the cache could ever hold anyway.
    Returns the number of rows warmed.
    """
    fp = plan_fingerprint(plan, encoder)
    warmed = 0
    batch: List[Tuple[bytes, RowDict]] = []
    for rid, digest in digest_iter:
        if cache.bytes >= cache.budget_bytes:
            break
        row = id_to_row.get(rid)
        if row is None:
            continue
        batch.append((digest, _row_slice(feats, row)))
        warmed += 1
        if len(batch) >= 1024:
            cache.put_many(fp, batch)
            batch = []
    if batch:
        cache.put_many(fp, batch)
    return warmed
