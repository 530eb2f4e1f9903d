"""Per-workload runtime bundle and the ingest/feed flows.

The equivalent of the reference's ``App.Deduplication`` / ``App.RecordLinkage``
inner classes (App.java:87-189): each workload owns its datasources, blocking
index, processor, listener, link database, and a lock serializing access
(writers block; readers time out after 1 s and surface 503 — App.java:718-725,
827-834, enforced by the HTTP layer).

Flow parity notes:
  * POST batch (App.java:924-1028 / 1065-1179): parse -> records -> partition
    deleted/live -> tombstone + retract links for deleted -> deduplicate live.
  * Deleted-record detection uses the hidden ``dukeDeleted`` property for
    BOTH workloads.  The reference's dedup path checks a nonexistent
    ``_deleted`` property (App.java:974) so its dedup deletes never retract
    links (SURVEY.md quirk Q2) — deliberately fixed here.
  * http-transform disables indexing AND link-db updates for BOTH workloads.
    The reference only does so for record linkage (quirk Q6: a dedup
    "transform" has full side effects) — deliberately fixed here.
  * GET feed rows (App.java:744-770): `_id` = id1+"_"+id2 with ':'->'_',
    `_updated` = link timestamp, `_deleted` = retracted, entity/dataset
    fields resolved by index point-lookups.
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
import weakref
from typing import Dict, List, Optional, Sequence

from ..core.config import ServiceConfig, WorkloadConfig
from ..core.records import Record
from ..index.base import CandidateIndex
from ..index.inverted import InvertedIndex
from ..links import create_link_database
from ..links.base import LinkDatabase
from ..service.datasource import IncrementalDataSource
from ..store.records import RecordStore
from ..telemetry import memory, tracing
from ..utils import faults
from .listeners import ServiceMatchListener
from .processor import Processor


def _snapshot_path(data_folder: str) -> str:
    import os

    return os.path.join(data_folder, "corpus_snapshot.npz")


def _hbm_components(wl_ref) -> Dict[str, int]:
    """Device-buffer bytes for one workload, keyed by component — the
    HBM ledger's registered callable.  Reads single-writer numpy mirrors
    lock-free (torn reads tolerated, the /stats stance); host backends
    (no device corpus) report nothing."""
    wl = wl_ref()
    if wl is None:
        return {}
    corpus = getattr(wl.index, "corpus", None)
    if corpus is None:
        return {}
    from ..ops.encoder import ANN_PROP, ANN_SCALE

    out = {"corpus_tensors": 0, "corpus_embeddings": 0, "int8_scales": 0}
    for prop, arrays in list(corpus.feats.items()):
        for name, arr in list(arrays.items()):
            nbytes = int(getattr(arr, "nbytes", 0) or 0)
            if prop == ANN_PROP:
                if name == ANN_SCALE:
                    out["int8_scales"] += nbytes
                else:
                    out["corpus_embeddings"] += nbytes
            else:
                out["corpus_tensors"] += nbytes
    for mask in ("row_valid", "row_deleted", "row_group"):
        arr = getattr(corpus, mask, None)
        out["corpus_tensors"] += int(getattr(arr, "nbytes", 0) or 0)
    ivf = getattr(wl.index, "ivf", None)
    if ivf is not None:
        out["ivf_membership"] = sum(
            int(getattr(getattr(ivf, field, None), "nbytes", 0) or 0)
            for field in ("centroids", "cell_of", "cell_rows", "counts"))
    return {k: v for k, v in out.items() if v}


def _arena_heat(wl_ref) -> float:
    """Accumulated per-workload device-seconds from the cost ledger's
    phase recorder — the arena's eviction heat (ISSUE 19): among cold
    candidates, the tenant that has burned the least device time spills
    first.  Lock-free torn reads tolerated (ordering hint only)."""
    wl = wl_ref()
    if wl is None:
        return 0.0
    phases = getattr(wl.processor, "phases", None)
    if phases is None:
        return 0.0
    try:
        return float(sum(phases.phase_seconds().values()))
    except Exception:
        return 0.0


class _BatchRequest:
    """One queued ingest request awaiting the merged device batch."""

    __slots__ = ("dataset_id", "entities", "event", "error")

    def __init__(self, dataset_id: str, entities: Sequence[dict]):
        self.dataset_id = dataset_id
        self.entities = entities
        self.event = threading.Event()
        self.error: Optional[Exception] = None


class Workload:
    def __init__(self, config: WorkloadConfig, index: CandidateIndex,
                 processor: Processor, listener: ServiceMatchListener,
                 link_database: LinkDatabase,
                 record_store: Optional[RecordStore] = None):
        self.config = config
        self.name = config.name
        self.kind = config.kind
        self.index = index
        self.processor = processor
        self.listener = listener
        self.link_database = link_database
        self.record_store = record_store
        self.lock = threading.Lock()
        # set under self.lock when a config reload replaces this workload;
        # handlers that resolved a stale reference re-check after locking
        self.closed = False
        # ingest microbatching: concurrent POSTs queue here and whichever
        # thread wins the workload lock processes the whole queue as ONE
        # device batch (self._mb_mutex orders queue access; it is never
        # held while acquiring self.lock)
        self._mb_mutex = threading.Lock()
        self._mb_queue: List[_BatchRequest] = []
        # recent write-side lock-hold EWMA (seconds): busy-503s derive
        # their Retry-After from it, so a reader told to come back gets a
        # hint shaped by how long writers actually hold this workload.
        # Written under self.lock (every observed hold IS a lock hold),
        # read lock-free by the HTTP layer.
        self._hold_ewma: Optional[float] = None
        # Sticky store/index divergence latch: set when a record_store
        # write committed but its index application (tombstone indexing /
        # link retraction / scoring pass) then failed.  While set, the
        # store holds rows the index never applied, so _mark_synced must
        # never stamp again in this process — ANY later stamp would cover
        # the orphaned rows (the store hash includes them) and the restart
        # staleness guard would skip the replay that re-applies them.
        # Cleared only by a restart replay (a fresh Workload).
        self._store_dirty = False
        self.datasources: Dict[str, IncrementalDataSource] = {
            ds.dataset_id: IncrementalDataSource(ds)
            for ds in config.duke.data_sources
        }
        # HBM ledger enrollment (telemetry/memory.py): the components
        # callable holds this workload weakly, so a reload-replaced
        # workload drops out of the books with its last reference and
        # the closed flag hides it meanwhile.  Arena-enabled device
        # corpora register as LOGICAL views (ISSUE 19): the arena owns
        # the physical slab bytes and attributes them once; this
        # registration keeps per-tenant attribution without double
        # counting the budget.
        from ..ops.arena import arena_enabled

        wl_ref = weakref.ref(self)
        corpus = getattr(index, "corpus", None)
        memory.register(self, self.kind, self.name,
                        lambda: _hbm_components(wl_ref),
                        logical=corpus is not None and arena_enabled())
        if corpus is not None:
            # arena identity + eviction heat: device_arrays admits
            # under these (engine.device_matcher.DeviceCorpus)
            corpus.arena_label = f"{self.kind}/{self.name}"
            corpus.arena_heat = lambda: _arena_heat(wl_ref)

    def replace_link_database(self, link_database: LinkDatabase) -> None:
        """Swap the link database wrapper in place — the dispatcher
        installs the HA link-stream publisher this way (ISSUE 8).  Call
        before serving starts or with ``self.lock`` held: the write path
        (listener chain), the read path (feeds), and the delete path all
        resolve through the new wrapper from then on."""
        self.link_database = link_database
        self.listener._wrapped.linkdb = link_database

    # -- lock-hold observations ---------------------------------------------

    def note_lock_hold(self, seconds: float) -> None:
        """Fold one write-side lock-hold duration into the EWMA (call with
        ``self.lock`` held — batch paths and the scheduler dispatcher)."""
        from .scheduler import fold_ewma

        self._hold_ewma = fold_ewma(self._hold_ewma, seconds)

    def busy_retry_after(self) -> int:
        """Whole-second Retry-After hint for lock-timeout busy replies:
        the recent write hold, ceil'd and clamped (ONE policy copy —
        engine.scheduler.retry_after_seconds — for every Retry-After
        source)."""
        from .scheduler import retry_after_seconds

        ewma = self._hold_ewma
        if ewma is None:
            return 1
        return retry_after_seconds(ewma)

    # -- ingest + match -----------------------------------------------------

    def submit_batch(self, dataset_id: str, entities: Sequence[dict],
                     http_transform: bool = False) -> Optional[List[dict]]:
        """Handler entry: lock discipline + ingest microbatching.

        Non-transform POSTs that arrive while another request holds the
        workload lock are queued; whichever thread next wins the lock runs
        the whole queue as ONE merged device batch (per-request conversion
        errors stay per-request), so many small concurrent POSTs cost one
        scoring program instead of N — the request-aggregation half of
        SURVEY.md section 7 hard part 6.  The reference serializes every
        POST on the workload lock (App.java:947) with no aggregation.

        Transforms keep their own lock-held call: their response rows are
        per-request state on the shared listener.  Returns None when the
        workload was replaced by a config reload mid-flight (caller
        re-resolves the registry and resubmits); raises this request's
        error otherwise.
        """
        if http_transform:
            with self.lock:
                if self.closed:
                    return None
                return self.process_batch(dataset_id, entities,
                                          http_transform=True)

        req = _BatchRequest(dataset_id, entities)
        with self._mb_mutex:
            self._mb_queue.append(req)
        with self.lock:
            if not req.event.is_set():
                with self._mb_mutex:
                    if self.closed:
                        # a reload replaced this workload while we waited;
                        # withdraw (if a pre-close leader already took the
                        # request its event is set and we fall through)
                        if req in self._mb_queue:
                            self._mb_queue.remove(req)
                            return None
                    work, self._mb_queue = self._mb_queue, []
                if work:
                    t0 = time.monotonic()
                    try:
                        self._run_merged(work)
                    finally:
                        self.note_lock_hold(time.monotonic() - t0)
        if not req.event.is_set():  # withdrawn post-close without a leader
            return None
        if req.error is not None:
            raise req.error
        return []

    def _retract_links_for(self, deleted: Sequence[Record]) -> None:
        """Retract every link touching the deleted records.

        ONE batched prefetch for the whole set: per-record
        ``get_all_links_for`` calls would pay a write-behind drain
        round-trip per record (each record's buffered retracts sealed and
        flushed by the next record's read).  A link touching two deleted
        records is retracted once; re-asserting it identically is
        idempotent either way.
        """
        if not deleted:
            return
        ids = [r.record_id for r in deleted]
        for link in self.link_database.get_links_for_ids(ids):
            link.retract()
            self.link_database.assert_link(link)

    def _mesh_op_lock(self):
        """Multi-host serving: the dispatcher's global op lock, held across
        every device-program-producing section so processes enqueue mesh
        programs in ONE global order (parallel.dispatch invariant 2).
        Single-process serving gets a no-op context."""
        from ..parallel import dispatch

        d = dispatch.current()
        return d.op_lock if d is not None else contextlib.nullcontext()

    def _mark_synced(self) -> None:
        """Stamp the index as fully caught up with the store (consumed by
        the snapshot staleness guard — engine.device_matcher
        .mark_store_synced).  Called only after a batch applied end to
        end; a failure between the store write and the index commit
        leaves the stamp stale, forcing a replay on the next restart.
        Once any batch left the store ahead of the index
        (``_store_dirty``), no later batch may stamp either — the store
        hash would cover the orphaned rows."""
        if self.record_store is None or self._store_dirty:
            return
        mark = getattr(self.index, "mark_store_synced", None)
        if mark is not None:
            with tracing.span("ingest.stamp", annotate=True):
                mark(self.record_store.content_hash())

    def _run_merged(self, work: List[_BatchRequest]) -> None:
        """Process queued requests as one batch (call with self.lock held).

        Serializability: merging applies every request's deletes before one
        shared scoring pass, so a merged group whose requests delete and
        upsert the SAME record id with opposite polarity (req A deletes X /
        adds Y merged with req B deletes Y / adds X) would end in a state
        matching no serial order.  Such conflicts split the queue: the
        merged group flushes (deletes + one scoring pass) before the
        conflicting request starts a new group, making the outcome equal to
        executing the groups — and therefore the requests — in queue order.
        Same-polarity overlap needs no split: repeated deletes retract
        idempotently and repeated upserts index in queue order inside one
        scoring pass (later content wins), exactly as serial execution.
        """
        group: List[_BatchRequest] = []
        group_records: List[List[Record]] = []
        deleted_ids: set = set()
        live_ids: set = set()

        def flush():
            nonlocal group, group_records, deleted_ids, live_ids
            all_live: List[Record] = []
            any_deleted = False
            ok: List[_BatchRequest] = []
            with tracing.span("ingest.store", annotate=True):
                for req, records in zip(group, group_records):
                    put_done = False
                    try:
                        if self.record_store is not None:
                            self.record_store.put_many(records)
                            # kill-differential site: store rows
                            # durable, index/scoring/links not yet
                            # applied
                            faults.check_crash("post_store_put")
                            put_done = True
                        deleted = [r for r in records if r.is_deleted()]
                        for record in deleted:
                            self.index.index(record)
                        self._retract_links_for(deleted)
                    except Exception as e:  # store errors stay per-request
                        if put_done:
                            # the store committed rows the index will
                            # never apply: latch the divergence so no
                            # later stamp (this flush or any future batch)
                            # can mask it (_mark_synced honors the latch)
                            self._store_dirty = True
                        req.error = e
                        req.event.set()
                        continue
                    any_deleted = any_deleted or bool(deleted)
                    all_live.extend(r for r in records if not r.is_deleted())
                    ok.append(req)
            try:
                with self._mesh_op_lock():
                    if any_deleted:
                        self.index.commit()
                        # seal the retraction writes even when no scoring
                        # pass (and thus no listener batch_done/commit)
                        # follows — a delete-only group must not leave
                        # them unsealed in the write-behind buffer
                        self.link_database.commit()
                    if all_live:
                        self.processor.deduplicate(all_live)
                if ok:
                    self._mark_synced()
            except Exception as e:
                if self.record_store is not None and ok:
                    # the group's store writes committed but the shared
                    # scoring/commit pass did not complete
                    self._store_dirty = True
                for req in ok:
                    req.error = e
            finally:
                for req in ok:
                    req.event.set()
            group, group_records = [], []
            deleted_ids, live_ids = set(), set()

        for req in work:
            try:  # conversion errors stay per-request
                datasource = self.datasources[req.dataset_id]
                with tracing.span("ingest.convert", annotate=True):
                    records = datasource.records_for_batch(req.entities)
            except Exception as e:
                req.error = e
                req.event.set()
                continue
            req_deleted = {r.record_id for r in records if r.is_deleted()}
            req_live = {r.record_id for r in records if not r.is_deleted()}
            if (req_deleted & live_ids) or (req_live & deleted_ids):
                flush()
            group.append(req)
            group_records.append(records)
            deleted_ids |= req_deleted
            live_ids |= req_live
        if group:
            flush()

    def process_batch(self, dataset_id: str, entities: Sequence[dict],
                      http_transform: bool = False) -> List[dict]:
        """Ingest a batch and run matching; returns the transform response
        rows (input entities + duke_links) when ``http_transform``."""
        t_hold = time.monotonic()
        datasource = self.datasources[dataset_id]
        records = datasource.records_for_batch(entities)
        live = [r for r in records if not r.is_deleted()]
        deleted = [r for r in records if r.is_deleted()]

        put_done = False
        try:
            if http_transform:
                self.index.set_indexing_disabled(True)
                self.listener.set_link_database_updates_disabled(True)
            else:
                if self.record_store is not None:
                    # durable source of truth first; the blocking index is a
                    # replayable cache of this store (SURVEY.md section 7)
                    self.record_store.put_many(records)
                    faults.check_crash("post_store_put")
                    put_done = True
                for record in deleted:
                    # tombstone in the index (still resolvable by the GET
                    # feed's point lookups); links retract batched below
                    self.index.index(record)
                self._retract_links_for(deleted)

            with self._mesh_op_lock():
                if deleted and not http_transform:
                    self.index.commit()
                    # seal retraction writes for delete-only batches (see
                    # _run_merged; no-op when a scoring pass follows)
                    self.link_database.commit()
                if live or http_transform:
                    self.processor.deduplicate(live)

            if http_transform:
                return self._transform_response(entities)
            self._mark_synced()
            return []
        except BaseException:
            if put_done:
                # store committed, index application failed: latch so no
                # later batch can stamp over the divergence (_mark_synced
                # honors the latch; a restart replay re-applies the rows)
                self._store_dirty = True
            raise
        finally:
            self.note_lock_hold(time.monotonic() - t_hold)
            self.index.set_indexing_disabled(False)
            self.listener.set_link_database_updates_disabled(False)

    def _transform_response(self, entities: Sequence[dict]) -> List[dict]:
        rows = []
        for entity in entities:
            row = dict(entity)
            entity_id = entity.get("_id")
            entity_id = str(entity_id) if entity_id is not None else None
            row["duke_links"] = self.listener.get_links_for_entity(entity_id)
            rows.append(row)
        return rows

    # -- incremental feed (call with self.lock held) ------------------------

    def _link_row(self, link) -> dict:
        """One feed row (wire format per App.java:744-770) — THE shared
        materialization (``links.replica.feed_row``): the follower read
        plane resolves through the same function, so leader and replica
        feeds cannot drift by construction (ISSUE 8)."""
        from ..links.replica import feed_row

        return feed_row(link, self.index.find_record_by_id)

    def links_since(self, since: int = 0) -> List[dict]:
        """Full materialized feed (the HTTP layer streams via links_page;
        this serves the HTTP/1.0 fallback and tests).  Internally paged so
        lazy record mirrors resolve endpoints through bounded batched
        prefetches instead of one point SELECT per link."""
        rows: List[dict] = []
        cursor = since
        while True:
            page, cursor = self.links_page(cursor, 5000)
            if not page:
                return rows
            rows.extend(page)

    def links_page(self, since: int, limit: int):
        """One bounded feed page: (rows, next_cursor).

        The HTTP layer streams a large ``?since=`` poll as a sequence of
        these pages, re-taking the workload lock per page so a
        multi-million-link backlog never holds the lock for the whole
        response (the reference holds its lock across the entire row loop,
        App.java:827-874).  ``next_cursor`` is the last row's timestamp
        (strictly-greater-than feed semantics); an empty ``rows`` means the
        feed is drained."""
        from ..links.replica import links_feed_page

        return links_feed_page(self.link_database, self.index, since, limit)

    def save_corpus_snapshot(self) -> None:
        """Persist the device-corpus snapshot (no-op for host backends).

        Best-effort: a failed save only logs; the record store remains the
        source of truth and the next start falls back to full replay."""
        if (self.record_store is None
                or not hasattr(self.index, "snapshot_save")):
            return
        try:
            # drain any write-behind link flush first: a snapshot must
            # never be newer than the link rows its batches produced
            self.link_database.drain()
            self.index.snapshot_save(_snapshot_path(self.config.data_folder))
        except Exception:
            logging.getLogger("workload").exception(
                "corpus snapshot save failed (replay will rebuild)"
            )

    def close(self, save_snapshot: bool = True) -> None:
        """Release index/link-db resources (the reference leaks these on hot
        reload — SURVEY.md quirk Q7; fixed by calling this on config swap).

        Device backends additionally persist a corpus snapshot so the next
        start can skip feature re-extraction; hot reload passes
        ``save_snapshot=False`` because it already saved under the quiesce
        locks (the corpus cannot have changed since)."""
        self.closed = True
        if save_snapshot:
            self.save_corpus_snapshot()
        finalizer = getattr(self.processor, "finalizer", None)
        if finalizer is not None:
            finalizer.shutdown()
        self.index.close()
        self.link_database.close()
        if self.record_store is not None:
            self.record_store.close()


def build_workload(wc: WorkloadConfig, sc: ServiceConfig, *,
                   backend: str = "host",
                   persistent: bool = True) -> Workload:
    """Assemble a workload: blocking index + processor + listener + link DB.

    ``backend``: 'host' (inverted index + scalar scoring — the conformance/
    baseline path), 'device' (TPU-resident corpus + batched kernels, exact
    brute-force blocking, see engine.device_matcher), 'ann' (embedding
    cosine blocking + exact rescoring, see engine.ann_matcher — for corpora
    where brute force stops being free), 'sharded' (the ANN backend over a
    jax.sharding.Mesh — record-axis-sharded corpus, all_gather top-K merge;
    the v5e-8 / multi-host serving configuration, engine.sharded_matcher),
    or 'sharded-brute' (exact brute force over the same mesh).
    """
    group_filtering = wc.is_record_linkage
    if backend != "host":
        # device-family backends compile multi-second XLA programs per
        # (capacity, bucket, K) shape; the persistent cache turns every
        # restart's first-contact compiles into disk reads.  Enabled here
        # so EVERY embedder gets it (the service CLI, benches, tests and
        # direct build_workload callers used to enable it individually —
        # the restart bench didn't, and its first probe silently paid
        # ~10-20 s of re-compiles per process)
        from ..utils.jit_cache import enable_persistent_cache

        enable_persistent_cache()
    if backend == "device":
        from .device_matcher import DeviceIndex, DeviceProcessor

        index = DeviceIndex(wc.duke, tunables=sc.tunables)
        processor = DeviceProcessor(
            wc.duke, index, group_filtering=group_filtering,
            profile=sc.profile, threads=sc.threads,
        )
    elif backend == "ann":
        from .ann_matcher import AnnIndex, AnnProcessor

        index = AnnIndex(wc.duke, tunables=sc.tunables)
        processor = AnnProcessor(
            wc.duke, index, group_filtering=group_filtering,
            profile=sc.profile, threads=sc.threads,
        )
    elif backend == "sharded":
        from .sharded_matcher import ShardedAnnIndex, ShardedAnnProcessor

        index = ShardedAnnIndex(wc.duke, tunables=sc.tunables)
        processor = ShardedAnnProcessor(
            wc.duke, index, group_filtering=group_filtering,
            profile=sc.profile, threads=sc.threads,
        )
    elif backend == "sharded-brute":
        from .sharded_matcher import (
            ShardedDeviceIndex,
            ShardedDeviceProcessor,
        )

        index = ShardedDeviceIndex(wc.duke, tunables=sc.tunables)
        processor = ShardedDeviceProcessor(
            wc.duke, index, group_filtering=group_filtering,
            profile=sc.profile, threads=sc.threads,
        )
    else:
        index = InvertedIndex(wc.duke, tunables=sc.tunables)
        processor = Processor(
            wc.duke,
            index,
            group_filtering=group_filtering,
            threads=sc.threads,
            profile=sc.profile,
        )

    link_database = None
    record_store: Optional[RecordStore] = None
    try:
        link_database = create_link_database(
            wc.link_database_type,
            wc.data_folder if persistent else None,
            is_record_linkage=wc.is_record_linkage,
        )
        # per-workload link-mode from the XML; ONE_TO_ONE env overrides
        # globally (None = defer to each workload's attribute)
        one_to_one = (wc.enforce_one_to_one if sc.one_to_one is None
                      else sc.one_to_one and wc.is_record_linkage)
        listener = ServiceMatchListener(
            wc.name, link_database, kind=wc.kind,
            one_to_one=one_to_one,
            record_resolver=index.find_record_by_id,
        )
        processor.add_match_listener(listener)

        if persistent and wc.data_folder:
            import os

            from ..store.records import SqliteRecordStore

            record_store = SqliteRecordStore(
                os.path.join(wc.data_folder, "records.sqlite")
            )
            # resume: rebuild the blocking index from the durable store (the
            # reference resumes by reopening its Lucene dir in APPEND mode —
            # IncrementalLuceneDatabase.java:233-244).  Device backends may
            # shortcut the per-record feature re-extraction through a
            # corpus snapshot — attempted FIRST with a lazy store-backed
            # record mirror, so a successful snapshot restart never decodes
            # the whole store (the 10M-row eager decode took ~24 minutes);
            # the store stays the source of truth and any snapshot mismatch
            # falls back to full replay.
            loaded = False
            snap = _snapshot_path(wc.data_folder)
            if hasattr(index, "snapshot_load") and os.path.exists(snap):
                from ..store.records import LazyRecordMap

                loaded = index.snapshot_load(
                    snap,
                    LazyRecordMap(record_store),
                    content_hash=record_store.content_hash(),
                )
            restored = loaded
            if not loaded:
                records_by_id = {
                    r.record_id: r for r in record_store.all_records()
                }
                if records_by_id:
                    restored = True
                    for record in records_by_id.values():
                        index.index(record)
                    index.commit()
                mark = getattr(index, "mark_store_synced", None)
                if mark is not None:
                    mark(record_store.content_hash())
            # the restored corpus' capacity/value-slot fingerprint differs
            # from the empty-corpus warm the processor ctor kicked; re-warm
            # so the first real batch doesn't stall on scorer compiles
            cache = getattr(index, "scorer_cache", None)
            if restored and cache is not None:
                cache.prewarm_async(group_filtering)
            if restored and not loaded:
                # replay path: stream the rebuilt corpus to HBM now (the
                # snapshot path kicks this inside snapshot_load) so the
                # first query doesn't pay the full upload
                warm = getattr(index, "warm_upload_async", None)
                if warm is not None:
                    warm()
    except BaseException:
        # a half-built workload never reaches the caller; release whatever
        # opened so a failing hot reload cannot leak handles (quirk Q7)
        for resource in (index, link_database, record_store):
            if resource is not None:
                try:
                    resource.close()
                except Exception:
                    pass
        raise
    return Workload(wc, index, processor, listener, link_database, record_store)


def adopt_workload(wc: WorkloadConfig, sc: ServiceConfig, *, backend: str,
                   index: CandidateIndex, link_database: LinkDatabase,
                   record_store: Optional[RecordStore] = None) -> Workload:
    """Build a SERVING workload around an already-populated index + link
    database — the leader-failover promotion path (ISSUE 8).

    A promoted follower's replica corpus (bootstrap snapshot + replayed
    commits) and replica link DB (published op stream at the applied
    watermark) are already bit-identical to the deposed leader's, so only
    the write-plane objects are new: a full processor (host finalization
    ON — the follower replica ran with it off) and a match listener whose
    events land in the replica link DB from now on.
    """
    group_filtering = wc.is_record_linkage
    if backend == "device":
        from .device_matcher import DeviceProcessor as _P
    elif backend == "ann":
        from .ann_matcher import AnnProcessor as _P
    elif backend == "sharded":
        from .sharded_matcher import ShardedAnnProcessor as _P
    elif backend == "sharded-brute":
        from .sharded_matcher import ShardedDeviceProcessor as _P
    else:
        raise RuntimeError(
            f"promotion needs a device-family backend (got {backend!r})"
        )
    processor = _P(wc.duke, index, group_filtering=group_filtering,
                   profile=sc.profile, threads=sc.threads)
    one_to_one = (wc.enforce_one_to_one if sc.one_to_one is None
                  else sc.one_to_one and wc.is_record_linkage)
    listener = ServiceMatchListener(
        wc.name, link_database, kind=wc.kind, one_to_one=one_to_one,
        record_resolver=index.find_record_by_id,
    )
    processor.add_match_listener(listener)
    return Workload(wc, index, processor, listener, link_database,
                    record_store)
