"""Hashed character-n-gram record embeddings for ANN candidate blocking.

The embedding-ANN backend (``engine.ann_matcher``) replaces exhaustive
brute-force blocking with a two-stage program: a cosine top-C retrieval over
a dense embedding matrix (one bf16 matmul per corpus chunk — pure MXU work),
followed by exact rescoring of only the retrieved candidates.  This is the
TPU-native counterpart of the reference's Lucene token blocking
(IncrementalLuceneDatabase.java:459-492): where Lucene ORs analyzed tokens
into a BooleanQuery and scores tf-idf overlap, we hash character n-grams of
every comparison property into a signed D-dimensional feature vector
(Weinberger et al.'s hashing trick) and let cosine similarity rank the
corpus.  Character n-grams — not word tokens — so the blocking stage is
robust to exactly the typo classes the comparators (Levenshtein,
Jaro-Winkler, q-gram) are configured to tolerate.

Encoding runs on host (numpy scatter-add; O(len) per record, once per
ingest) because it is tiny next to retrieval; retrieval runs on device where
the corpus-sized work is.  No learned weights, no external model downloads —
the encoder is deterministic from the schema alone.
"""

from __future__ import annotations

import math
from typing import Dict, List, Sequence

import ml_dtypes
import numpy as np

from ..core.records import Record
from ..telemetry.env import env_flag, env_float, env_int
from . import features as F

# Pseudo-property under which the corpus embedding matrix rides inside the
# DeviceCorpus feature tree (so growth/upload/incremental-update machinery in
# engine.device_matcher applies to it unchanged).
ANN_PROP = "__ann__"
ANN_TENSOR = "emb"
# int8 storage mode only (DUKE_EMB_INT8): the per-row symmetric
# quantization scale rides the corpus tree as a second ANN_PROP tensor,
# so append/growth/tombstone/snapshot machinery covers it for free.
ANN_SCALE = "scale"

# Storage dtype for the corpus embedding matrix — THE single decision
# point (ann_matcher, the sharded bench, the driver dryrun, and the
# sharded tests all take it from here).  bf16: retrieval casts both
# matmul operands to bf16 for the MXU anyway, so denser storage halves
# the dominant HBM/row term and the scan's memory traffic at identical
# blocking quality (candidates are rescored exactly either way).
#
# DUKE_EMB_INT8=1 halves it AGAIN: rows are stored as symmetric per-row
# int8 (q = round(v * 127 / max|v|), scale = max|v| / 127 riding as the
# ANN_SCALE tensor) and retrieval runs an int8 x int8 -> int32 MXU
# matmul rescaled by the two row scales.  The int32 accumulation is
# EXACT (D * 127^2 << 2^31 for any dim up to ~130k), so the only
# retrieval error is the vector quantization itself, bounded by
# ``int8_cosine_eps`` and credited to the recall-escalation trigger
# (ops.scoring.build_ann_scorer) instead of silently eating recall.
STORAGE_DTYPE = ml_dtypes.bfloat16


def int8_enabled() -> bool:
    """int8 embedding storage toggle (read at encoder construction so one
    index can never mix dtypes mid-life; the snapshot fingerprint and the
    feature-cache plan fingerprint both carry the resolved mode)."""
    return env_flag("DUKE_EMB_INT8", False)


def storage_name(storage: str = None) -> str:
    """Canonical storage-mode string (snapshot + cache fingerprints)."""
    if storage is not None:
        return storage
    return "int8" if int8_enabled() else str(np.dtype(STORAGE_DTYPE))


def int8_cosine_eps(dim: int) -> float:
    """Certified worst-case |exact cosine - int8-reconstructed cosine|.

    Rows are L2-normalized before quantization, so per component the
    reconstruction error is at most scale/2 with scale = max|v|/127 <= 1/127,
    giving a per-vector L2 error of at most sqrt(D)/254.  With
    q = v + dq, c = v' + dc (||v|| = ||v'|| = 1):

        |q.c - v.v'| <= ||dq|| + ||dc|| + ||dq||*||dc||
                     <= 2*sqrt(D)/254 + D/254^2

    The int32 dot of the stored int8 codes is exact (D * 127^2 < 2^31),
    so this bound covers the WHOLE retrieval-score error.  Used to widen
    the recall-escalation trigger: retrieved candidates within 2*eps of
    the top-C cutoff could be displaced by quantization, so they are
    counted as saturation evidence (ops.scoring.build_ann_scorer).
    """
    per_side = math.sqrt(float(dim)) / 254.0
    return 2.0 * per_side + per_side * per_side


def int8_cosine_eps_dynamic(q_tree: Dict, c_tree: Dict):
    """Traced per-block certified cosine-error bound from the ACTUAL
    row scales: ``sqrt(D)/2 * (sq + sc) + D/4 * sq * sc`` with sq/sc the
    max query/corpus scale in the block.

    Same derivation as ``int8_cosine_eps`` (which substitutes the
    worst-possible scale 1/127) — hashed-n-gram rows have max components
    well below 1, so the actual scales are typically ~4x smaller and the
    bound ~4x tighter while staying a deterministic worst case.  The
    static bound made the escalation credit fire routinely on flat
    cosine tails (a ~0.26 band at dim 256); this one keeps the credit a
    rare-saturation signal.  Returns a jnp scalar (trace-safe).
    """
    import jax.numpy as jnp

    d = float(q_tree[ANN_TENSOR].shape[-1])
    root = math.sqrt(d) / 2.0
    sq = jnp.max(q_tree[ANN_SCALE])
    sc = jnp.max(c_tree[ANN_SCALE])
    return root * (sq + sc) + (root * sq) * (root * sc)


def quantize_rows(rows: np.ndarray):
    """Symmetric per-row int8 quantization of f32 embedding rows.

    Returns ``(codes int8 (N, D), scale f32 (N,))`` with
    ``codes * scale[:, None]`` the reconstruction.  All-zero rows (empty
    records) keep scale 0 — they reconstruct to zero and cosine 0, the
    same behavior the f32 path has for them.
    """
    rows = np.asarray(rows, dtype=np.float32)
    peak = np.abs(rows).max(axis=1)
    scale = (peak / 127.0).astype(np.float32)
    inv = np.where(scale > 0.0, 1.0 / np.where(scale > 0.0, scale, 1.0), 0.0)
    codes = np.rint(rows * inv[:, None]).astype(np.int8)
    return codes, scale


def corpus_tensors_from_f32(rows: np.ndarray, storage: str):
    """f32 embedding rows -> the ANN_PROP tensor dict for ``storage``
    ("int8" or a float dtype name).  ONE conversion point shared by the
    serial extractor, the shared-memory parallel extractor's parent-side
    assembly, and the encoder itself."""
    if storage == "int8":
        codes, scale = quantize_rows(rows)
        return {ANN_TENSOR: codes, ANN_SCALE: scale}
    return {ANN_TENSOR: rows.astype(STORAGE_DTYPE)}


def dequantize_rows(tree) -> np.ndarray:
    """ANN_PROP tensor dict -> f32 rows (host side: k-means training,
    explain provenance).  Accepts both storage layouts."""
    emb = tree[ANN_TENSOR]
    if emb.dtype == np.int8:
        return emb.astype(np.float32) * np.asarray(
            tree[ANN_SCALE], dtype=np.float32
        )[:, None]
    return np.asarray(emb, dtype=np.float32)


_NGRAM = 3

# Vectorized n-gram hashing: one odd multiplier per codepoint position in
# the window plus a murmur3-style finalizer, all in wrapping uint64 numpy
# arithmetic — the whole record hashes in a handful of array ops instead of
# a per-byte Python loop (ingest-side hot path for large corpora).
_H_MULT = (
    np.uint64(0x9E3779B97F4A7C15),
    np.uint64(0xC2B2AE3D27D4EB4F),
    np.uint64(0x165667B19E3779F9),
    np.uint64(0x27D4EB2F165667C5),
    np.uint64(0x85EBCA77C2B2AE63),
)
assert _NGRAM <= len(_H_MULT), "add a multiplier per n-gram position"
_FM1 = np.uint64(0xFF51AFD7ED558CCD)
_FM2 = np.uint64(0xC4CEB9FE1A85EC53)

_SALTS: Dict[str, np.uint64] = {}


def _native_embed():
    """The C++ bulk encoder, or None (pure numpy fallback/oracle)."""
    if _NGRAM != 3:  # the native kernel hardcodes the trigram window
        return None
    try:
        from .. import native
    except Exception:  # pragma: no cover - import is cheap and total
        return None
    return native if native.available() else None


def _salt(prop: str) -> np.uint64:
    # separate salt per property so "oslo" in NAME and "oslo" in CAPITAL
    # hash to different buckets — field-tagged n-grams, like Lucene's
    # per-field terms
    s = _SALTS.get(prop)
    if s is None:
        s = _SALTS[prop] = np.uint64(F.fnv1a64(prop))
    return s


def _hash_ngrams(value: str, salt: np.uint64) -> np.ndarray:
    """uint64 hashes of all character n-grams of `` value `` (padded)."""
    padded = f" {value.lower()} "
    cp = np.frombuffer(
        padded.encode("utf-32-le", "surrogatepass"), dtype=np.uint32
    ).astype(np.uint64)
    if cp.size < _NGRAM:
        cp = np.pad(cp, (0, _NGRAM - cp.size))
    with np.errstate(over="ignore"):
        nwin = cp.size - _NGRAM + 1
        h = salt
        for j in range(_NGRAM):
            h = h ^ (cp[j:j + nwin] * _H_MULT[j])
        h ^= h >> np.uint64(33)
        h *= _FM1
        h ^= h >> np.uint64(29)
        h *= _FM2
        h ^= h >> np.uint64(32)
    return h


def embed_values(prop_values: Sequence[tuple], dim: int) -> np.ndarray:
    """One L2-normalized signed-hash embedding from (property, value) pairs."""
    vec = np.zeros((dim,), dtype=np.float32)
    hashes = [
        _hash_ngrams(value, _salt(prop)) for prop, value in prop_values
    ]
    if not hashes:
        return vec
    uniq, counts = np.unique(np.concatenate(hashes), return_counts=True)
    buckets = (uniq % np.uint64(dim)).astype(np.int64)
    signs = np.where(
        (uniq >> np.uint64(32)) & np.uint64(1), 1.0, -1.0
    ).astype(np.float32)
    # sublinear tf weighting
    np.add.at(vec, buckets, signs * np.sqrt(counts).astype(np.float32))
    norm = float(np.linalg.norm(vec))
    if norm > 0.0:
        vec /= norm
    return vec


class RecordEncoder:
    """Schema-bound encoder: Record -> (dim,) normalized f32 embedding."""

    def __init__(self, schema, dim: int):
        self.dim = dim
        # every comparison property contributes to blocking; recall against
        # brute force is measured, not assumed (SURVEY.md section 7 hard
        # part 5), and more fields can only add evidence
        self.props: List[str] = [p.name for p in schema.comparison_properties()]
        # corpus storage mode, resolved ONCE at construction: an index
        # whose env flips mid-life must never mix dtypes in one corpus
        # (the snapshot fingerprint and feature-cache key both carry this)
        self.storage = storage_name()

    def encode(self, record: Record) -> np.ndarray:
        pairs = []
        for name in self.props:
            for value in record.get_values(name):
                if value:
                    pairs.append((name, value))
        return embed_values(pairs, self.dim)

    def encode_corpus(self, records: Sequence[Record]) -> np.ndarray:
        """Corpus-resident embeddings: ``encode_batch`` in STORAGE_DTYPE.

        bf16-mode helper kept for the benches/dryrun that assemble the
        corpus tree by hand; storage-mode-aware callers (ops.features)
        use ``corpus_tensors`` instead."""
        return self.encode_batch(records).astype(STORAGE_DTYPE)

    def corpus_tensors(self, records: Sequence[Record]) -> Dict[str, np.ndarray]:
        """The ANN_PROP tensor dict for a record batch under this
        encoder's storage mode ({emb} in bf16, {emb, scale} in int8)."""
        return corpus_tensors_from_f32(self.encode_batch(records),
                                       self.storage)

    def encode_batch(self, records: Sequence[Record]) -> np.ndarray:
        if not records:
            return np.zeros((0, self.dim), dtype=np.float32)
        native = _native_embed()
        if native is not None:
            return self._encode_batch_native(records, native)
        return np.stack([self.encode(r) for r in records])

    def _encode_batch_native(self, records: Sequence[Record],
                             native) -> np.ndarray:
        # bulk path through the C++ library: one FFI call for the whole
        # chunk (tests pin it to the numpy path's exact output)
        strings: List[str] = []
        salts: List[np.uint64] = []
        rec_off = np.zeros(len(records) + 1, dtype=np.int64)
        empty: List[str] = []
        prop_salts = [(name, _salt(name)) for name in self.props]
        for i, record in enumerate(records):
            values_map = record._values  # read-only peek (no copies)
            for name, salt in prop_salts:
                for value in values_map.get(name, empty):
                    if value:  # defensive: keep parity with encode()'s guard
                        strings.append(f" {value.lower()} ")
                        salts.append(salt)
            rec_off[i + 1] = len(strings)
        return native.embed_batch(
            strings, np.asarray(salts, dtype=np.uint64), rec_off,
            self.dim,
        )


def _fused_retrieval(q_emb, corpus_emb, corpus_valid, corpus_deleted,
                     corpus_group, query_group, query_row, *,
                     top_c: int, group_filtering: bool, row_offset,
                     recall_target: float):
    """The Pallas fast path of ``retrieval_scan``: fused matmul + mask +
    segment-max in VMEM (ops.pallas_kernels.retrieval_segmax), then an
    approximate top-C over the SEG-x-smaller segment winners.  Returns
    (top_sim, top_idx) or None when the shapes don't fit the kernel
    (caller falls back to the XLA scan)."""
    import jax.numpy as jnp
    from jax import lax

    from . import pallas_kernels as pk

    n, d = corpus_emb.shape
    q = q_emb.shape[0]
    seg = env_int("DEVICE_ANN_SEG", 64)
    if d % 128 != 0 or seg <= 0 or seg & (seg - 1) or n < 2 * seg:
        return None
    # corpus tile: sized so the (TC, QP) f32 score tile stays ~<=8 MB of
    # VMEM; a power of two >= 1024 (the mask operand's (TC, 128) int8
    # block needs TC/128 >= 8 sublanes) that divides the capacity
    qp = -(-q // 128) * 128
    tc = n & -n  # largest power-of-2 divisor of the capacity
    tc = min(tc, 2048, (1 << 21) // qp)  # tc*qp*4B <= 8 MB of VMEM
    nbins = n // seg
    # Bin-count floor: expected segment-phase recall of the true top-C is
    # ~1 - C/(2*nbins) (birthday collisions into nbins strided bins), so
    # honoring recall_target needs nbins >= C / (1 - target) — with slack
    # for the approx-over-bins second stage, which carries its own
    # recall_target reduction.  Below the floor (small corpora, or an
    # escalated C approaching the bin count = a saturated candidate
    # budget) drop to the per-chunk approx scan, whose reduction adapts
    # to its input size.  Empirically this floor is what separates the
    # 10M run's 0.975 measured recall from the 10k-corpus case that
    # silently lost 0.989-confidence pairs at 256 bins (r5 bringup).
    min_bins = int(top_c / max(1e-3, 1.0 - recall_target))
    if tc < max(1024, seg * 8) or n % tc or nbins < min_bins:
        return None

    if qp != q:
        pad = qp - q
        q_emb = jnp.pad(q_emb, ((0, pad), (0, 0)))
        # padded queries: no self-row; their outputs are sliced away
        # below, so their group value only needs to be well-formed (it
        # clips to -1, the dedup no-group encoding)
        query_row = jnp.pad(query_row, (0, pad), constant_values=-1)
        query_group = jnp.pad(query_group, (0, pad),
                              constant_values=-1)

    qT = q_emb.astype(jnp.bfloat16).T
    # Encoded int8 mask broadcast across a 128-lane axis — tile-native,
    # where an (N, 1) int32 column operand would T(8,128)-pad 128x into
    # a multi-GB temp at the flagship scale (see pk.GROUP_OFFSET note).
    # POLICY: this encodes exactly scoring.candidate_mask (the one-place
    # eligibility policy — keep the two in sync): live & not tombstoned,
    # group exclusion, self-row exclusion.  int8 range is safe because
    # group ids are the <group> element ordinals 1..2 (core/config.py
    # enforces exactly two groups) or -1; both sides clip identically so
    # the compare could only coarsen together, never diverge.
    live = corpus_valid & ~corpus_deleted
    enc_col = jnp.where(
        live,
        (jnp.clip(corpus_group, -1, 100)
         + jnp.int32(pk.GROUP_OFFSET)).astype(jnp.int8),
        jnp.int8(0),
    )
    enc = jnp.broadcast_to(enc_col[:, None], (n, 128))
    # the kernel masks in LOCAL row coordinates; shift the query's own
    # global row down (negative stays impossible-to-match)
    qrow_local = (query_row - row_offset)[None, :].astype(jnp.int32)
    qgroup_enc = (jnp.clip(query_group, -1, 100)
                  + pk.GROUP_OFFSET)[None, :].astype(jnp.int32)

    seg_max, seg_arg = pk.retrieval_segmax(
        qT, corpus_emb.astype(jnp.bfloat16), enc, qrow_local,
        qgroup_enc, tc=tc, seg=seg, group_filtering=group_filtering,
    )
    smax = seg_max.T[:q]                                  # (Q, nbins)
    sarg = seg_arg.T[:q]
    top_sim, bin_sel = lax.approx_max_k(
        smax, top_c, recall_target=recall_target
    )
    local = jnp.take_along_axis(sarg, bin_sel, axis=1)
    top_idx = jnp.where(
        top_sim < jnp.float32(-1e30), jnp.int32(-1), local + row_offset
    )
    return top_sim, top_idx


def as_emb_tree(x) -> Dict:
    """Normalize an embedding operand to the ANN_PROP tensor-dict layout.

    Bare arrays (the legacy bf16 call convention used by the benches and
    the fused-retrieval tests) wrap as ``{ANN_TENSOR: x}``; dicts — the
    corpus tree's ANN_PROP entry, carrying the int8 scale when
    DUKE_EMB_INT8 storage is active — pass through."""
    return x if isinstance(x, dict) else {ANN_TENSOR: x}


def is_int8_tree(tree: Dict) -> bool:
    return ANN_SCALE in tree


def chunk_sims(q_tree: Dict, c_emb, c_scale=None):
    """(Q, chunk) cosine-score tile for one corpus chunk.

    bf16 storage: both operands cast to bf16, f32 MXU accumulation — the
    pre-existing path, bit-for-bit.  int8 storage: int8 x int8 -> int32
    MXU matmul (exact: D * 127^2 << 2^31) rescaled by the per-row
    query/corpus scales; roughly double the matmul throughput of bf16 at
    half the HBM traffic."""
    import jax
    import jax.numpy as jnp

    q_emb = q_tree[ANN_TENSOR]
    if c_scale is not None:
        raw = jax.lax.dot_general(
            q_emb, c_emb,
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32,
        ).astype(jnp.float32)
        return (raw * q_tree[ANN_SCALE][:, None]) * c_scale[None, :]
    return jax.lax.dot_general(
        q_emb.astype(jnp.bfloat16), c_emb.astype(jnp.bfloat16),
        (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


def retrieval_scan(q_emb, corpus_emb, corpus_valid, corpus_deleted,
                   corpus_group, query_group, query_row, *,
                   chunk: int, top_c: int, group_filtering: bool,
                   row_offset=0):
    """Blockwise cosine top-C over the corpus embedding matrix.

    ``q_emb`` / ``corpus_emb`` accept either a bare matrix (bf16 legacy
    convention) or the ANN_PROP tensor dict — ``{emb}`` for float
    storage, ``{emb, scale}`` for DUKE_EMB_INT8 (see ``chunk_sims``).

    Same scan/mask/merge skeleton as ``ops.scoring.scan_topk`` but the chunk
    score is a single (Q, D) x (D, chunk) matmul in bf16 with f32
    accumulation — the MXU path.  Returns (top_sim, top_index) with global
    row indices (``row_offset`` as in scan_topk for sharded use).

    The scan chunk is widened to ``DEVICE_ANN_RETRIEVAL_CHUNK`` (default
    65536) when the corpus allows:
    the matmul is so cheap per row that per-step overhead (top-C merge,
    scan bookkeeping) dominates with small chunks.  Capacities are
    power-of-2 multiples of the base chunk, so any power-of-2 widening
    divides evenly.

    Per-chunk top-C uses ``lax.approx_max_k`` — the TPU-native
    PartialReduce op (Chern et al. 2022): instead of fully sorting the
    (Q, chunk) similarity tile each step (a vector-unit sort that left
    the r4 scan ~0.4% MFU, two orders of magnitude off the matmul+HBM
    roofline), the chunk is reduced bin-wise to ~C survivors at a
    configurable expected recall, and only the (Q, 2C) merge with the
    running carry is sorted exactly.  Recall loss only ever shrinks the
    candidate *set* (never corrupts a score — candidates are rescored
    exactly either way), the escalation loop still widens C on
    saturation, and ``DEVICE_ANN_RECALL_TARGET`` / ``DEVICE_ANN_EXACT_TOPK=1``
    restore tighter or exact semantics.  This is the TPU answer to the
    reference's "single biggest influence on search performance" knob —
    its Lucene candidate-search limits (IncrementalLuceneDatabase.java:
    349-358 ``max_search_hits``): both trade bounded blocking recall for
    retrieval speed, and both rescore survivors exactly.
    """
    wide = env_int("DEVICE_ANN_RETRIEVAL_CHUNK", 65536)
    cap_total = corpus_valid.shape[0]
    while chunk < wide and chunk * 2 <= cap_total and cap_total % (chunk * 2) == 0:
        chunk *= 2
    import jax.numpy as jnp
    from jax import lax

    from . import scoring

    q_tree = as_emb_tree(q_emb)
    c_tree = as_emb_tree(corpus_emb)
    int8 = is_int8_tree(c_tree)
    q_emb = q_tree[ANN_TENSOR]
    corpus_emb = c_tree[ANN_TENSOR]
    q = q_emb.shape[0]
    cap = corpus_valid.shape[0]
    nchunks = cap // chunk

    neg = jnp.float32(scoring.NEG_INF)
    init_sim = jnp.full((q, top_c), neg, jnp.float32)
    init_idx = jnp.full((q, top_c), -1, jnp.int32)

    # exact full-sort merge when forced, or when the chunk is so narrow
    # (escalated C approaching chunk width) that the bin reduction cannot
    # shrink anything worth the second merge step
    exact = env_flag("DEVICE_ANN_EXACT_TOPK", False) or top_c * 4 >= chunk
    recall_target = env_float("DEVICE_ANN_RECALL_TARGET", 0.99)

    from . import pallas_kernels as pk

    if (
        not exact
        and not int8  # the fused segmax kernel stages bf16 operands only
        and env_flag("DEVICE_ANN_FUSED", True)
        and pk.pallas_enabled()
    ):
        fused = _fused_retrieval(
            q_emb, corpus_emb, corpus_valid, corpus_deleted, corpus_group,
            query_group, query_row, top_c=top_c,
            group_filtering=group_filtering, row_offset=row_offset,
            recall_target=recall_target,
        )
        if fused is not None:
            return fused

    def body(carry, ci):
        top_sim, top_idx = carry
        start = ci * chunk
        emb_c = lax.dynamic_slice_in_dim(corpus_emb, start, chunk, axis=0)
        scale_c = (
            lax.dynamic_slice_in_dim(c_tree[ANN_SCALE], start, chunk)
            if int8 else None
        )
        sims = chunk_sims(q_tree, emb_c, scale_c)  # (Q, chunk)

        cvalid = lax.dynamic_slice_in_dim(corpus_valid, start, chunk)
        cdel = lax.dynamic_slice_in_dim(corpus_deleted, start, chunk)
        cgroup = lax.dynamic_slice_in_dim(corpus_group, start, chunk)
        cidx = row_offset + start + jnp.arange(chunk, dtype=jnp.int32)

        mask = scoring.candidate_mask(
            cvalid, cdel, cgroup, cidx, query_group, query_row,
            group_filtering,
        )
        sims = jnp.where(mask, sims, neg)

        if exact:
            merged_sim = jnp.concatenate([top_sim, sims], axis=1)
            merged_idx = jnp.concatenate(
                [top_idx, jnp.broadcast_to(cidx[None, :], (q, chunk))],
                axis=1,
            )
        else:
            chunk_sim, chunk_arg = lax.approx_max_k(
                sims, top_c, recall_target=recall_target
            )
            merged_sim = jnp.concatenate([top_sim, chunk_sim], axis=1)
            # carry entries come FIRST in the concat: lax.top_k breaks
            # ties by position, so all-masked (NEG_INF) chunk survivors
            # can never displace the carry's -1 "empty slot" sentinels —
            # the invariant build_ann_scorer's `retrieved` mask rests on
            merged_idx = jnp.concatenate(
                [top_idx,
                 row_offset + start + chunk_arg.astype(jnp.int32)],
                axis=1,
            )
        top_sim, sel = lax.top_k(merged_sim, top_c)
        top_idx = jnp.take_along_axis(merged_idx, sel, axis=1)
        return (top_sim, top_idx), None

    (top_sim, top_idx), _ = lax.scan(
        body, (init_sim, init_idx), jnp.arange(nchunks, dtype=jnp.int32)
    )
    return top_sim, top_idx
