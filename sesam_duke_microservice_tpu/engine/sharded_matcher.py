"""Mesh-sharded serving backends: the REST service over a device mesh.

Round 2 left the mesh machinery (``parallel/sharded.py``,
``parallel/ann_sharded.py``) as a library with no consumer in the service:
``build_workload`` could only construct single-device backends, so a v5e-8
deployment could not serve HTTP from a sharded corpus.  This module closes
that gap — the reference wires its matcher straight into the request
handlers (App.java:343-345,1005); here the same wiring scales to a
``jax.sharding.Mesh``:

  * ``ShardedDeviceCorpus`` keeps the exact append/tombstone/incremental-
    update model of ``DeviceCorpus`` (host numpy mirror as rebuildable
    truth) but places every device tensor record-axis sharded over the
    mesh, with capacity aligned to ``mesh.size * chunk`` granules so each
    shard holds whole scan chunks;
  * ``ShardedAnnIndex`` / ``ShardedDeviceIndex`` are the ANN and exact
    brute-force blocking backends over that corpus — snapshots, value-slot
    growth, delete/tombstone and the ``CandidateIndex`` interface are all
    inherited unchanged;
  * the scorer caches swap the single-device programs for the
    constraint-driven mesh ones (``parallel.sharded.PARTITION_RULES``):
    per-shard retrieval/scan with global row offsets, local exact
    rescoring, and a replicated-layout top-K merge the partitioner lowers
    to one all-gather over ICI — communication is O(Q * K * D) while
    compute scales 1/D (SURVEY.md section 5.7);
  * both mesh caches are first-class engine citizens (ISSUE 18): they
    ride the AOT executable store (mesh facets join the store key, the
    prewarm ladder lowers against mesh-annotated avals) and the certified
    dd finalize (survivors gather to replicated layout, then the same
    ``ops.scoring.build_dd_rescorer`` program runs bit-identical to the
    single-device path).

Queries are replicated (uploaded per block, never gathered cross-shard),
escalation loops (K for brute force, C for ANN recall) run unchanged
through ``_PendingBlock``/``resolve_block``, and host finalization is the
same double-precision path — so emitted probabilities are bit-identical to
the single-chip backends (differential-tested in
``tests/test_sharded_service.py`` on the virtual 8-device mesh).

Deployment: single-host this shards over every local device — the
flagship v5e-8 configuration (BASELINE configs[4]) runs one process
driving all 8 chips, full REST surface included.  Multi-host meshes
(``parallel.multihost.initialize()``) work end to end: the HTTP frontend
is a single-controller and follower processes replay every corpus
mutation and scoring pass in lockstep through ``parallel/dispatch.py``
(token-authenticated op broadcast over DCN; see that module for the
ordering/failure invariants).  Exercised by
``tests/test_multihost_serving.py`` — two OS processes, real HTTP, the
same link set as a single-process run — and by the driver dryrun's
two-process smoke.
"""

from __future__ import annotations

import logging
import threading
from typing import Optional

from ..core.config import DukeSchema, MatchTunables
from ..ops import encoder as E
from .ann_matcher import AnnIndex, AnnProcessor, _AnnScorerCache
from .device_matcher import (
    DeviceCorpus,
    DeviceIndex,
    DeviceProcessor,
    _CHUNK,
    _ScorerCache,
)

logger = logging.getLogger("sharded-matcher")

_MESH_LOCK = threading.Lock()
_MESH = None


def serving_mesh():
    """The process-wide 1-D corpus mesh the sharded backends serve from.

    Joins the multi-host job first when one is configured (no-op
    otherwise), then builds the mesh over every global device — one mesh
    for all workloads, so hot config reloads don't re-initialize
    distributed state.
    """
    global _MESH
    with _MESH_LOCK:
        if _MESH is None:
            from ..parallel import multihost

            multihost.initialize()
            _MESH = multihost.global_corpus_mesh()
            from .. import telemetry

            telemetry.MESH_DEVICES.set(_MESH.size)  # dukecheck: ignore[DK502] once per process: mesh construction
            logger.info(
                "serving mesh: %d device(s), axis %r",
                _MESH.size, _MESH.axis_names,
            )
        return _MESH


class ShardedDeviceCorpus(DeviceCorpus):
    """``DeviceCorpus`` whose device mirror is record-axis sharded.

    Capacity grows in ``mesh.size * chunk`` granules (each shard always
    holds whole scan chunks — required by the mesh scorers' per-shard
    ``row_offset`` arithmetic); placement and the incremental tree updater
    carry explicit shardings so the arrays never silently collapse to a
    single device.
    """

    def __init__(self, plan, values_per_record: int, mesh):
        from ..parallel.sharded import LeadingAxisPlacer

        super().__init__(plan, values_per_record)
        self.mesh = mesh
        # ONE copy of the sharding/granule conventions: the same placer
        # machinery parallel/sharded.py and parallel/ring.py use
        self._placer = LeadingAxisPlacer(mesh, mesh.size * _CHUNK)
        self.granule = self._placer.granule
        self._updater_fn = None
        self._mask_updater_fn = None
        self._mask_scatter_fn = None

    def _sharding(self, ndim: int):
        return self._placer._sharding(ndim)

    def _place(self, arr):
        import jax

        return jax.device_put(arr, self._sharding(arr.ndim))

    def _updater(self):
        """Sharding-constrained incremental updater: the global-row update
        slice lands on whichever shard owns those rows, and the outputs are
        pinned back to the record sharding so a commit can never migrate
        the corpus off the mesh."""
        if self._updater_fn is None:
            import jax
            from jax import lax

            def update_tree(dev, upd, start):
                out = jax.tree_util.tree_map(
                    lambda d, u: lax.dynamic_update_slice_in_dim(
                        d, u, start, axis=0
                    ),
                    dev, upd,
                )
                return jax.tree_util.tree_map(
                    lambda a: lax.with_sharding_constraint(
                        a, self._sharding(a.ndim)
                    ),
                    out,
                )

            self._updater_fn = jax.jit(update_tree, donate_argnums=(0,))
        return self._updater_fn

    def _mask_updater(self):
        """Sharding-constrained mask-slice updater (see _updater)."""
        if self._mask_updater_fn is None:
            import jax
            from jax import lax

            def update_masks(masks, upd, start):
                out = tuple(
                    lax.dynamic_update_slice_in_dim(m, u, start, axis=0)
                    for m, u in zip(masks, upd)
                )
                return tuple(
                    lax.with_sharding_constraint(m, self._sharding(1))
                    for m in out
                )

            self._mask_updater_fn = jax.jit(
                update_masks, donate_argnums=(0,)
            )
        return self._mask_updater_fn

    def _mask_scatter(self):
        """Sharding-constrained tombstone scatter (see _updater)."""
        if self._mask_scatter_fn is None:
            import jax
            from jax import lax

            def scatter(masks, idx, vvals, dvals):
                valid, deleted, group = masks
                out = (valid.at[idx].set(vvals),
                       deleted.at[idx].set(dvals))
                out = tuple(
                    lax.with_sharding_constraint(m, self._sharding(1))
                    for m in out
                )
                return out + (group,)

            self._mask_scatter_fn = jax.jit(scatter, donate_argnums=(0,))
        return self._mask_scatter_fn


class _MeshProgramLift:
    """dd + AOT lifts shared by the mesh scorer caches (ISSUE 18).

    Mixed in ahead of the base caches, this makes the sharded backends
    first-class: queries upload replicated (never gathered cross-shard),
    the dd survivor rescore runs on device through a replicated-layout
    gather, and the AOT executable store serves mesh executables whose
    store keys carry the mesh facets and whose lowering avals carry the
    real shardings (``parallel.sharded.PARTITION_RULES``).
    """

    queries_from_rows = False
    supports_aot = True

    # single-writer mesh observability (plain ints — scrape-time
    # snapshots in service/metrics.py, never a registry write here)
    _dd_gathers = 0
    _dd_gather_rows = 0

    @property
    def supports_dd(self) -> bool:
        """dd finalize runs on the FRONTEND only (the follower replay of
        parallel/dispatch.py never enqueues it), so the survivor-gather
        collective in ``_dd_call`` is only safe when every mesh device is
        addressable from this process.  A multi-host mesh keeps the host
        dd path (README: dd/AOT parity matrix)."""
        return self._mesh_fully_addressable()

    def _mesh_fully_addressable(self) -> bool:
        cached = getattr(self, "_mesh_local", None)
        if cached is None:
            import jax

            pid = jax.process_index()
            cached = all(d.process_index == pid
                         for d in self.index.mesh.devices.flat)
            self._mesh_local = cached
        return cached

    def _dd_call(self, fn, qfeats, cfeats, query_row_j, top_index):
        """Certified dd finalize over the mesh: gather the resolved
        block's (Q, K) survivors from the record-axis-sharded corpus
        tensors into a compact replicated block, then run the SAME
        memoized single-device dd program against it with an identity
        index.  Clipping ``top_index`` before the gather reproduces the
        single-device "-1 padding gathers row 0" semantics exactly, so
        the verdicts are bit-identical (tests/test_mesh_parity.py)."""
        import jax.numpy as jnp

        from ..parallel.sharded import (
            build_replicated_gather,
            replicated_program,
        )

        gather = getattr(self, "_dd_gather_fn", None)
        if gather is None:
            gather = build_replicated_gather(self.index.mesh)
            self._dd_gather_fn = gather
            self._dd_programs = {}
        program = self._dd_programs.get(fn)
        if program is None:
            program = self._dd_programs[fn] = replicated_program(
                self.index.mesh, fn)
        q, k = top_index.shape
        rows = jnp.clip(top_index, 0).reshape(-1)
        gathered = gather(cfeats, rows)
        self._dd_gathers += 1
        self._dd_gather_rows += int(q * k)
        ident = jnp.arange(q * k, dtype=jnp.int32).reshape(q, k)
        return program(qfeats, gathered, query_row_j, ident)

    def _sds(self, shape, dtype, family: str = "corpus"):
        """Mesh-annotated lowering avals: corpus-family tensors carry the
        record-axis sharding, query-family tensors the replicated spec —
        so an AOT executable compiles against (and at load time only
        accepts) the layouts dispatch actually passes."""
        import jax

        from ..parallel.sharded import rule_sharding

        fam = "corpus" if family == "corpus" else "queries"
        return jax.ShapeDtypeStruct(
            shape, dtype,
            sharding=rule_sharding(self.index.mesh, fam, len(shape)),
        )

    def _ladder(self, cap: int):
        # mesh queries never gather from corpus rows (queries_from_rows
        # is False), so only the replicated-upload variant is ever
        # dispatched — half the single-device ladder
        return [e for e in super()._ladder(cap) if not e[2]]

    def _min_warm_cap(self) -> int:
        # the smallest real corpus capacity is one mesh granule (every
        # shard holds whole scan chunks); lowering below it would bake
        # shapes dispatch can never present
        return self.index.corpus.granule

    def _store_key(self, plan, k: int, group_filtering: bool,
                   from_rows: bool, cap: int, bucket: int) -> dict:
        from ..utils.jit_cache import mesh_fingerprint

        key = super()._store_key(plan, k, group_filtering, from_rows,
                                 cap, bucket)
        # a mesh executable is only valid on the topology it was
        # partitioned for: a 4-way entry must be unreachable from an
        # 8-way mesh even on the same host (tests/test_mesh_aot.py)
        key["mesh"] = mesh_fingerprint(self.index.mesh)
        return key


class _ShardedScorerCache(_MeshProgramLift, _ScorerCache):
    """Brute-force scorer cache over the mesh (parallel.sharded program)."""

    def _build(self, top_k: int, group_filtering: bool, from_rows: bool,
               plan=None):
        from ..parallel.sharded import build_sharded_scorer

        # signature matches the single-device from_rows=False scorer:
        # fn(qfeats, cfeats, valid, deleted, group, qgroup, qrow, min_logit)
        return build_sharded_scorer(
            plan or self.index.plan, self.index.mesh, chunk=_CHUNK, top_k=top_k,
            group_filtering=group_filtering,
        )


class _ShardedAnnScorerCache(_MeshProgramLift, _AnnScorerCache):
    """ANN scorer cache over the mesh (parallel.ann_sharded program)."""

    def _build(self, top_c: int, group_filtering: bool, from_rows: bool,
               plan=None):
        import jax

        from ..parallel.ann_sharded import build_sharded_ann_scorer

        base = build_sharded_ann_scorer(
            plan or self.index.plan, self.index.mesh, chunk=_CHUNK, top_c=top_c,
            group_filtering=group_filtering,
        )

        # a JITTED adapter to the single-device ANN call convention (the
        # embedding tree — and the int8 scale when present — rides
        # separately and is reassembled as the ANN_PROP pseudo-property
        # inside the trace): AOT lowering needs a traceable callable
        # with the engine's flat signature, not a host-side wrapper
        @jax.jit
        def call(q_emb, qfeats, corpus_emb, corpus_feats, cvalid, cdeleted,
                 cgroup, query_group, query_row, min_logit):
            cfeats = dict(corpus_feats)
            cfeats[E.ANN_PROP] = E.as_emb_tree(corpus_emb)
            return base(q_emb, qfeats, cfeats, cvalid, cdeleted, cgroup,
                        query_group, query_row, min_logit)

        return call

    def _build_ivf(self, top_c: int, nprobe: int, group_filtering: bool,
                   from_rows: bool):
        from ..parallel.ann_sharded import build_sharded_ivf_scorer

        base = build_sharded_ivf_scorer(
            self.index.plan, self.index.mesh, top_c=top_c, nprobe=nprobe,
            group_filtering=group_filtering,
        )

        def call(q_emb, qfeats, emb_tree, centroids, cell_rows,
                 corpus_feats, cvalid, cdeleted, cgroup, query_group,
                 query_row, min_logit):
            cfeats = dict(corpus_feats)
            cfeats[E.ANN_PROP] = E.as_emb_tree(emb_tree)
            return base(q_emb, qfeats, cfeats, centroids, cell_rows,
                        cvalid, cdeleted, cgroup, query_group, query_row,
                        min_logit)

        return call

    def _ivf_placers(self):
        """SNIPPETS.md pjit partition-rule pattern, through the shared
        rule table: replicate the small lookup table (centroids), shard
        the big per-row state (the stacked local-row membership matrix)
        on the record axis."""
        import jax

        from ..parallel.sharded import rule_sharding

        mesh = self.index.mesh
        repl = rule_sharding(mesh, "centroids", 2)
        sharded = rule_sharding(mesh, "ivf_membership", 2)
        return (
            lambda arr: jax.device_put(arr, repl),
            lambda arr: jax.device_put(arr, sharded),
        )


class ShardedDeviceIndex(DeviceIndex):
    """Exact brute-force blocking over a record-axis-sharded corpus."""

    def __init__(self, schema: DukeSchema, *,
                 tunables: Optional[MatchTunables] = None,
                 values_per_record: Optional[int] = None,
                 mesh=None):
        # the corpus factory runs inside super().__init__
        self.mesh = mesh if mesh is not None else serving_mesh()
        super().__init__(
            schema, tunables=tunables, values_per_record=values_per_record
        )

    def _make_corpus(self, plan, values_per_record: int):
        return ShardedDeviceCorpus(plan, values_per_record, self.mesh)

    @property
    def scorer_cache(self) -> _ShardedScorerCache:
        if self._scorer_cache is None:
            self._scorer_cache = _ShardedScorerCache(self)
        return self._scorer_cache


class ShardedAnnIndex(AnnIndex):
    """Embedding-ANN blocking over a record-axis-sharded corpus.

    The flagship scale configuration (BASELINE.json configs[4]): corpus
    embeddings and feature tensors shard over the mesh, per-shard cosine
    top-C + local exact rescoring, all_gather merge.  Everything else —
    encoder, snapshots, recall escalation semantics — is ``AnnIndex``.
    """

    def __init__(self, schema: DukeSchema, *,
                 tunables: Optional[MatchTunables] = None,
                 values_per_record: Optional[int] = None,
                 mesh=None, **kwargs):
        self.mesh = mesh if mesh is not None else serving_mesh()
        super().__init__(
            schema, tunables=tunables, values_per_record=values_per_record,
            **kwargs,
        )

    def _make_corpus(self, plan, values_per_record: int):
        return ShardedDeviceCorpus(plan, values_per_record, self.mesh)

    def _ivf_shards(self) -> int:
        # the IVF membership matrix stacks per-shard (K, B) blocks of
        # LOCAL row ids so P(SHARD_AXIS) placement hands each mesh
        # program lane exactly its own block (parallel.ann_sharded)
        return self.mesh.size

    @property
    def scorer_cache(self) -> _ShardedAnnScorerCache:
        if self._scorer_cache is None:
            self._scorer_cache = _ShardedAnnScorerCache(self)
        return self._scorer_cache


class ShardedDeviceProcessor(DeviceProcessor):
    """DeviceProcessor over a ShardedDeviceIndex (exhaustive stats)."""

    exhaustive = True


class ShardedAnnProcessor(AnnProcessor):
    """AnnProcessor over a ShardedAnnIndex (rescored-candidate stats)."""

    exhaustive = False
