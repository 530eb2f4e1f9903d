"""Sharded corpus scoring over a device mesh.

Data layout (the scaling-book recipe — pick a mesh, annotate shardings, let
XLA insert collectives):

  * corpus feature tensors: sharded along the record axis over mesh axis
    ``"shard"`` — each device holds ``capacity / n_devices`` rows in HBM;
  * query block: replicated — every device scores the same queries against
    its local rows (no query-side communication at all);
  * merge: each device's local top-K is constrained back to replicated
    layout ((D, Q, K) — K is tiny, so the all-gather XLA inserts moves
    Q*K*D*8 bytes over ICI, not the candidate matrix) and reduced to the
    global top-K on every device.

The program is a ``jit`` over ``NamedSharding``-placed inputs.  The
brute-force per-shard scan is a ``shard_map`` (it holds Pallas kernels,
and GSPMD cannot partition a Mosaic call); the merge is a constraint to
replicated layout, so the partitioner chooses that collective.  The
partition rules per tensor family live in ``PARTITION_RULES`` and are
shared by the placement helpers here, the in-program constraints, and
the IVF placers in ``engine/sharded_matcher.py``.

This scales the O(Q x N) pair-scoring work linearly in device count while
the communication stays O(Q x K x D): the framework's counterpart of
ring-attention-style sequence parallelism for the corpus axis (SURVEY.md
section 5.7 — "sharded candidate retrieval").

The reference's single-JVM design has no equivalent (SURVEY.md section 2
rows 16-17); parity obligations stop at "same results as one device", which
``tests/test_parallel.py`` checks on a virtual 8-device CPU mesh.
"""

from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import scoring as S

SHARD_AXIS = "shard"

# Partition-rule table: tensor family -> leading-axis PartitionSpec maker.
# Record-carrying families shard their leading axis over the mesh; query-side
# and centroid tensors replicate.  Everything that places or constrains a
# tensor (LeadingAxisPlacer, the in-program constraints below, the IVF
# placers in engine/sharded_matcher.py) goes through this table so the
# layout contract lives in exactly one place.
PARTITION_RULES: Dict[str, Callable[[int], P]] = {
    # corpus feature tensors / embedding codes / int8 scales: record axis
    "corpus": lambda ndim: P(SHARD_AXIS, *([None] * (ndim - 1))),
    # IVF cell membership (stacked shard-local row-id matrix): record axis
    "ivf_membership": lambda ndim: P(SHARD_AXIS, *([None] * (ndim - 1))),
    # query block, thresholds, masks-of-queries: replicated
    "queries": lambda ndim: P(),
    # IVF centroids (and any other model-side small tensors): replicated
    "centroids": lambda ndim: P(),
}


def rule_sharding(mesh: Mesh, family: str, ndim: int) -> NamedSharding:
    """NamedSharding for ``family`` (a PARTITION_RULES key) at ``ndim``."""
    return NamedSharding(mesh, PARTITION_RULES[family](ndim))


def corpus_mesh(devices: Optional[Sequence] = None) -> Mesh:
    """A 1-D mesh over all (or the given) devices; the single sharding axis
    carries the corpus record dimension."""
    devices = list(devices) if devices is not None else jax.devices()
    return Mesh(np.array(devices), (SHARD_AXIS,))


def shardwise(mesh: Mesh):
    """(cap, ...) -> (ndev, cap/ndev, ...) with the leading axis pinned to
    the mesh.  The flat array is already record-axis sharded with a
    shard-granule-aligned capacity, so the reshape moves no data — it just
    exposes the shard axis for ``vmap``."""
    ndev = mesh.size

    def split(a):
        local = a.shape[0] // ndev
        r = jnp.reshape(a, (ndev, local) + a.shape[1:])
        return lax.with_sharding_constraint(
            r, NamedSharding(mesh, PARTITION_RULES["corpus"](r.ndim)))

    return split


def replicated(mesh: Mesh):
    """Constrain to replicated layout; XLA inserts the all-gather."""
    def repl(a):
        return lax.with_sharding_constraint(a, NamedSharding(mesh, P()))

    return repl


def shard_offsets(mesh: Mesh, local_cap) -> jnp.ndarray:
    """Per-shard global row offset, one element resident per device."""
    offs = jnp.arange(mesh.size, dtype=jnp.int32) * jnp.int32(local_cap)
    return lax.with_sharding_constraint(
        offs, NamedSharding(mesh, P(SHARD_AXIS)))


def merge_topk(mesh: Mesh, top_logit, top_index, top_k: int):
    """Reduce per-shard (D, Q, K) candidates to the global top-K.

    The transpose/reshape ordering (shard 0's K entries first, then shard
    1's, ...) matches the historical all_gather merge, so ``lax.top_k``'s
    stable tie-breaking-by-position yields the same winners.
    """
    repl = replicated(mesh)
    ndev, q = top_logit.shape[0], top_logit.shape[1]
    merged_logit = repl(jnp.transpose(top_logit, (1, 0, 2)).reshape(q, ndev * top_k))
    merged_index = repl(jnp.transpose(top_index, (1, 0, 2)).reshape(q, ndev * top_k))
    out_logit, sel = lax.top_k(merged_logit, top_k)
    out_index = jnp.take_along_axis(merged_index, sel, axis=1)
    return out_logit, out_index, merged_logit


def build_sharded_scorer(
    plan,
    mesh: Mesh,
    *,
    chunk: int = 512,
    top_k: int = 64,
    group_filtering: bool = False,
) -> Callable:
    """Like ``ops.scoring.build_corpus_scorer`` but over a sharded corpus.

    Input contract matches the single-device scorer, except the ``corpus_*``
    arrays must have their leading (record) axis divisible by
    ``mesh.size * chunk`` and be placed with ``ShardedCorpus`` (record-axis
    sharded).  Row indices in ``top_index`` and ``query_row`` are global.
    """
    pair_logits = S.build_pair_logits(plan)
    corpus = P(SHARD_AXIS)

    # the per-shard scan is a shard_map, not a GSPMD-partitioned vmap:
    # it holds Mosaic kernels, which the partitioner cannot split
    def local_scan(qfeats, cf, cv, cd, cg, query_group, query_row,
                   min_logit):
        row_offset = lax.axis_index(SHARD_AXIS) * cv.shape[0]
        top_logit, top_index, count = S.scan_topk(
            pair_logits, qfeats, cf, cv, cd, cg,
            query_group, query_row, min_logit,
            chunk=chunk, top_k=top_k, group_filtering=group_filtering,
            row_offset=row_offset,
        )
        return top_logit[None], top_index[None], count[None]

    scan = jax.shard_map(
        local_scan, mesh=mesh,
        in_specs=(P(), corpus, corpus, corpus, corpus, P(), P(), P()),
        out_specs=(corpus, corpus, corpus), check_vma=False,
    )

    def score_shard(qfeats, corpus_feats, corpus_valid, corpus_deleted,
                    corpus_group, query_group, query_row, min_logit):
        top_logit, top_index, count = scan(
            qfeats, corpus_feats, corpus_valid, corpus_deleted,
            corpus_group, query_group, query_row, min_logit)
        out_logit, out_index, _ = merge_topk(mesh, top_logit, top_index, top_k)
        total_count = replicated(mesh)(count.sum(axis=0))
        return out_logit, out_index, total_count

    return jax.jit(score_shard)


def replicated_program(mesh: Mesh, fn: Callable) -> Callable:
    """``fn``, a single-device program, run on every device of the mesh
    over replicated operands.  A program holding Mosaic kernels cannot be
    partitioned by GSPMD, not even to replicate it; inside a shard_map
    each device runs it whole."""
    return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                                 check_vma=False))


def build_replicated_gather(mesh: Mesh) -> Callable:
    """Gather corpus rows from record-axis-sharded tensors into a compact
    replicated layout.

    ``rows`` is a flat vector of global (non-negative) row ids; the result
    tree holds ``(len(rows), ...)`` arrays constrained to replicated layout,
    so XLA inserts the cross-shard gather and every device ends up with the
    full survivor block.  This is the bridge that lets the sharded backends
    reuse the single-device ``build_dd_rescorer`` program bit-identically:
    gather the resolved block's (Q, K) survivors here, then rescore with an
    identity ``top_index``.
    """
    repl = replicated(mesh)

    @jax.jit
    def gather(cfeats, rows):
        return jax.tree_util.tree_map(
            lambda a: repl(jnp.take(a, rows, axis=0)), cfeats)

    return gather


class LeadingAxisPlacer:
    """Shared placement machinery: pad the leading axis to ``granule``
    multiples and device_put with leading-axis sharding over the mesh.

    Base for ``ShardedCorpus`` (record axis, granule = mesh.size * chunk)
    and ``parallel.ring.RingQueryPlacer`` (query axis, granule =
    mesh.size) — one copy of the padding/sharding conventions.  The
    shardings come from ``PARTITION_RULES["corpus"]``.
    """

    def __init__(self, mesh: Mesh, granule: int):
        self.mesh = mesh
        self.granule = granule
        self._sharding_cache: Dict[int, NamedSharding] = {}

    def padded_capacity(self, size: int) -> int:
        g = self.granule
        return max(g, ((size + g - 1) // g) * g)

    def _sharding(self, ndim: int) -> NamedSharding:
        if ndim not in self._sharding_cache:
            self._sharding_cache[ndim] = rule_sharding(self.mesh, "corpus", ndim)
        return self._sharding_cache[ndim]

    def _put(self, arr: np.ndarray, size: int, cap: int, fill=0):
        if arr.shape[0] != cap:
            out = np.full((cap,) + arr.shape[1:], fill, dtype=arr.dtype)
            out[:size] = arr[:size]
            arr = out
        return jax.device_put(arr, self._sharding(arr.ndim))

    def _put_tree(self, feats: Dict[str, Dict[str, np.ndarray]],
                  size: int, cap: int):
        return {
            prop: {
                name: self._put(arr, size, cap)
                for name, arr in tensors.items()
            }
            for prop, tensors in feats.items()
        }


class ShardedCorpus(LeadingAxisPlacer):
    """Places host corpus arrays onto the mesh, record-axis sharded.

    The capacity is padded up to a multiple of ``mesh.size * chunk`` so
    every shard gets the same number of whole scan chunks (padding rows are
    ``valid=False`` and masked out by the scorer).
    """

    def __init__(self, mesh: Mesh, *, chunk: int = 512):
        super().__init__(mesh, mesh.size * chunk)
        self.chunk = chunk

    def place(self, feats: Dict[str, Dict[str, np.ndarray]],
              row_valid: np.ndarray, row_deleted: np.ndarray,
              row_group: np.ndarray):
        """Pad to the shard granule and device_put with record-axis sharding.

        Returns (feats, valid, deleted, group) as sharded device arrays.
        """
        size = row_valid.shape[0]
        cap = self.padded_capacity(size)
        dev_feats = self._put_tree(feats, size, cap)
        valid = self._put(row_valid, size, cap, False)
        deleted = self._put(row_deleted, size, cap, False)
        group = self._put(row_group, size, cap, -1)
        return dev_feats, valid, deleted, group
