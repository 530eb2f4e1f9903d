"""Pallas TPU kernels for the pairwise hot ops.

The XLA path (ops.pairwise + ops.scoring._pair_expand) materializes expanded
``(Q*C, L)`` codepoint operands in HBM for every corpus chunk — O(Q*C*L)
memory traffic for O(Q*C*L) compute.  The kernels here tile the pair matrix
flash-attention style instead: a grid over (query-tile x corpus-tile) loads
``O(T*L)`` characters into VMEM once and computes the full ``(TQ, TC)``
distance tile on-chip, so HBM traffic drops from O(Q*C*L) to
O((Q/TQ + C/TC) * T * L) while all O(Q*C*L) bit-parallel work stays in
VMEM/registers.  This is the "comparators become batched Pallas kernels"
component of the north-star plan (BASELINE.json) — the reference's scalar
per-pair ``Comparator.compare`` hot loop (reference App.java:1005 ->
Duke Processor.compare) becomes one device program.

Kernel inventory:

  * ``myers_distance_tiles`` — batched Levenshtein distance over all
    query x corpus pairs via Myers/Hyyro bit-parallel DP: one uint32 word
    per pair for patterns <= 32 codepoints, and an N-word
    carry-propagated variant (Hyyro's block formulation) up to
    ``MYERS_MAX_CHARS`` = 256, so default 64-char configs AND long-text
    schemas (128/256 chars) stay on the Pallas path.  Differentially
    tested against ``ops.pairwise`` and the scalar oracle.
  * ``myers_distance_gathered`` — the same DP in the ANN-rescoring layout:
    candidate c of query q is a specific gathered row, so the candidate
    axis rides the lanes and text chars differ per pair.
  * ``set_intersection_tiles`` — |A ∩ B| for all query x corpus pairs of
    hashed id sets (q-grams / tokens): dense equality compare in VMEM,
    O(T*G) HBM traffic per tile instead of the XLA path's expanded
    (Q*C, G) pair operands.  Backs ``set_sim_tiles`` (QGram / Jaccard /
    Dice).
  * ``jaro_winkler_sim_tiles`` — Jaro-Winkler over all pairs via matched-
    position uint32 bitmasks (greedy window matching + lowest-bit
    transposition walk).  Differentially tested against the scalar
    comparator oracle.

Enabling: ``pallas_enabled()`` — env ``DUKE_TPU_PALLAS`` ("1" force on,
"0" force off); default on only when the active JAX backend is TPU.  On
non-TPU backends kernels run in interpreter mode (slow, test-only).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..telemetry.env import env_flag
from .features import SET_PAD

_VMEM = pltpu.VMEM


def _backend() -> str:
    return jax.default_backend()


def pallas_enabled() -> bool:
    """Should the scoring program route char kernels through Pallas?"""
    return env_flag("DUKE_TPU_PALLAS", _backend() == "tpu")


def _interpret() -> bool:
    return _backend() != "tpu"


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


# Shared operand staging for every pair-matrix tile kernel (Myers, JW, set
# intersection): queries row-major (Q, W) + lengths, corpus transposed
# (W, C) + lengths, padded to tile multiples.  One place for the padding
# and BlockSpec conventions so a layout fix cannot miss a kernel family.


def _pair_tile_specs(w_q: int, w_c: int, tile_q: int, tile_c: int):
    return [
        pl.BlockSpec((tile_q, w_q), lambda i, j: (i, 0), memory_space=_VMEM),
        pl.BlockSpec((tile_q, 1), lambda i, j: (i, 0), memory_space=_VMEM),
        pl.BlockSpec((w_c, tile_c), lambda i, j: (0, j), memory_space=_VMEM),
        pl.BlockSpec((1, tile_c), lambda i, j: (0, j), memory_space=_VMEM),
    ]


def _stage_pair_operands(qx, qn, cx, cn, *, tile_q_cap: int,
                         tile_c_cap: int):
    """Pad to tile multiples; returns (qp_arr, qn2, cxt, cn2, tile_q,
    tile_c).  Padded rows compute garbage the caller masks out."""
    q, w = qx.shape
    c = cx.shape[0]
    tile_q = min(tile_q_cap, _round_up(max(q, 1), 8))
    tile_c = min(tile_c_cap, _round_up(max(c, 1), 128))
    qp = _round_up(max(q, 1), tile_q)
    cp = _round_up(max(c, 1), tile_c)
    qa = jnp.zeros((qp, w), jnp.int32).at[:q].set(qx)
    qn2 = jnp.zeros((qp, 1), jnp.int32).at[:q, 0].set(qn)
    cxt = jnp.zeros((w, cp), jnp.int32).at[:, :c].set(cx.T)
    cn2 = jnp.zeros((1, cp), jnp.int32).at[0, :c].set(cn)
    return qa, qn2, cxt, cn2, tile_q, tile_c


# -- Myers bit-parallel Levenshtein, tiled over the pair matrix --------------


def _myers_word_init(ql):
    """One-word DP init: (pv0, hibit) for pattern lengths <= 32.

    min/max on int32 (Mosaic lacks unsigned vector min), then cast to
    uint32 for the shifts.  bit j of pv0 set iff j < ql (guard the
    undefined <<32).
    """
    one = jnp.uint32(1)
    full = jnp.uint32(0xFFFFFFFF)
    pv0 = jnp.where(
        ql >= 32, full, (one << jnp.minimum(ql, 31).astype(jnp.uint32)) - one
    )
    hibit = one << (jnp.maximum(ql, 1) - 1).astype(jnp.uint32)
    return pv0, hibit


def _myers_word_step(eq, pv, mv, score, active, hibit):
    """One text step of the one-word Myers recurrence (shared by the
    cross-product and gathered kernels — one copy of the math)."""
    one = jnp.uint32(1)
    xv = eq | mv
    xh = (((eq & pv) + pv) ^ pv) | eq
    ph = mv | ~(xh | pv)
    mh = pv & xh
    score = score + jnp.where(active & ((ph & hibit) != 0), 1, 0)
    score = score - jnp.where(active & ((mh & hibit) != 0), 1, 0)
    ph = (ph << one) | one
    mh = mh << one
    pv = jnp.where(active, mh | ~(xv | ph), pv)
    mv = jnp.where(active, ph & xv, mv)
    return pv, mv, score


def _myers_tile_kernel(qc_ref, ql_ref, cct_ref, cl_ref, out_ref, *, L: int):
    """One (TQ, TC) distance tile.

    qc_ref:  (TQ, L)  query codepoints (pattern), 0-padded
    ql_ref:  (TQ, 1)  query lengths
    cct_ref: (L, TC)  corpus codepoints, transposed (text)
    cl_ref:  (1, TC)  corpus lengths
    out_ref: (TQ, TC) int32 distances
    """
    tq = qc_ref.shape[0]
    tc = cct_ref.shape[1]
    qc = qc_ref[...]                      # (TQ, L)
    ql = ql_ref[...][:, :1]               # (TQ, 1)
    cl = cl_ref[...][:1, :]               # (1, TC)

    pv0, hibit = _myers_word_init(ql)     # (TQ, 1)

    pv = jnp.broadcast_to(pv0, (tq, tc))
    mv = jnp.zeros((tq, tc), jnp.uint32)
    score = jnp.broadcast_to(ql.astype(jnp.int32), (tq, tc))

    def step(i, carry):
        pv, mv, score = carry
        t = cct_ref[pl.ds(i, 1), :]                        # (1, TC)
        eq = jnp.zeros((tq, tc), jnp.uint32)
        for j in range(L):  # static unroll: disjoint bits, pure VPU work
            eq = eq | jnp.where(qc[:, j : j + 1] == t, jnp.uint32(1 << j), 0)
        return _myers_word_step(eq, pv, mv, score, i < cl, hibit)

    pv, mv, score = lax.fori_loop(0, L, step, (pv, mv, score))
    # empty pattern: distance is the text length
    out_ref[...] = jnp.where(
        ql == 0, jnp.broadcast_to(cl.astype(jnp.int32), (tq, tc)), score
    )


@functools.partial(
    jax.jit, static_argnames=("tile_q", "tile_c", "interpret")
)
def _myers_tiles_padded(qc, ql2, cct, cl2, *, tile_q, tile_c, interpret):
    qp, l = qc.shape
    cp = cct.shape[1]
    grid = (qp // tile_q, cp // tile_c)
    kernel = functools.partial(_myers_tile_kernel, L=l)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((qp, cp), jnp.int32),
        grid=grid,
        in_specs=_pair_tile_specs(l, l, tile_q, tile_c),
        out_specs=pl.BlockSpec(
            (tile_q, tile_c), lambda i, j: (i, j), memory_space=_VMEM
        ),
        interpret=interpret,
    )(qc, ql2, cct, cl2)


# Longest pattern the tiled Myers kernels cover (uint32 words unroll
# statically; beyond this the scan-DP fallback takes over).  8 words =
# 256 chars comfortably covers long text properties (addresses, titles).
MYERS_MAX_CHARS = 256


def myers_distance_tiles(qchars, qlen, cchars, clen, *, interpret=None):
    """All-pairs Levenshtein distance d(query_i, corpus_j) -> (Q, C) int32.

    qchars: (Q, L) int32 codepoints (0-padded), L <= MYERS_MAX_CHARS;
    qlen: (Q,) int32; cchars: (C, L) int32; clen: (C,) int32

    L <= 32 runs the one-word kernel; longer patterns the N-word Hyyro
    variant (explicit carry propagation, N = ceil(L/32) <= 8) — so 64-char
    default configs AND long-text schemas (128/256 chars) stay on the
    Pallas path instead of the ~600x slower scan-DP fallback.  Pads Q up
    to a sublane multiple and C up to a lane multiple; padded rows compute
    garbage distances that callers mask via their validity bits.
    """
    q = qchars.shape[0]
    c = cchars.shape[0]
    l = qchars.shape[1]
    if l > MYERS_MAX_CHARS:
        raise ValueError(
            f"Myers pallas kernels need L <= {MYERS_MAX_CHARS}, got {l}"
        )
    if interpret is None:
        interpret = _interpret()
    words = -(-l // 32)
    # lane tiles shrink as the per-pair DP state (O(W) uint32 words) grows,
    # keeping the live VMEM footprint roughly constant
    tile_c_cap = 512 if words == 1 else (256 if words <= 4 else 128)
    qc, ql2, cct, cl2, tile_q, tile_c = _stage_pair_operands(
        qchars, qlen, cchars, clen,
        tile_q_cap=128, tile_c_cap=tile_c_cap,
    )
    if words == 1:
        out = _myers_tiles_padded(
            qc, ql2, cct, cl2, tile_q=tile_q, tile_c=tile_c,
            interpret=interpret,
        )
    else:
        out = _myersN_tiles_padded(
            qc, ql2, cct, cl2, tile_q=tile_q, tile_c=tile_c,
            interpret=interpret, words=words,
        )
    return out[:q, :c]


def _carry_out(a: jnp.ndarray, b: jnp.ndarray, s: jnp.ndarray) -> jnp.ndarray:
    """Carry out of the uint32 addition s = a + b, as 0/1 uint32.

    Bitwise majority of the sign bits — Mosaic has no unsigned vector
    compare, so overflow is detected without one.
    """
    return ((a & b) | ((a ^ b) & ~s)) >> jnp.uint32(31)


def _myersN_tile_kernel(qc_ref, ql_ref, cct_ref, cl_ref, out_ref, *,
                        L: int, W: int):
    """N-word Myers/Hyyro tile: pattern lengths up to ``32 * W`` chars.

    Same layout contract as ``_myers_tile_kernel``; the bit-parallel DP
    state (Pv/Mv) spans ``W`` 32-bit words with explicit carry propagation
    through the add chain and the horizontal shifts (Hyyro's block
    formulation generalized from the round-2 two-word kernel).  The word
    lists unroll statically, so the Mosaic program grows O(W) per text
    step while all O(TQ * TC * W) bit-parallel work stays on the VPU.
    """
    tq = qc_ref.shape[0]
    tc = cct_ref.shape[1]
    qc = qc_ref[...]                      # (TQ, L)
    ql = ql_ref[...][:, :1]               # (TQ, 1)
    cl = cl_ref[...][:1, :]               # (1, TC)

    one = jnp.uint32(1)
    full = jnp.uint32(0xFFFFFFFF)

    def bits_below(n):  # (1 << n) - 1 for n in [0, 32]
        nn = jnp.clip(n, 0, 32)
        return jnp.where(nn >= 32, full,
                         (one << nn.astype(jnp.uint32)) - one)

    pv = [
        jnp.broadcast_to(bits_below(ql - 32 * w), (tq, tc)) for w in range(W)
    ]
    mv = [jnp.zeros((tq, tc), jnp.uint32) for _ in range(W)]
    # the score bit rides in the pattern's last word/bit, per query
    hi_word = (jnp.maximum(ql, 1) - 1) // 32        # (TQ, 1)
    hibit = one << ((jnp.maximum(ql, 1) - 1) % 32).astype(jnp.uint32)
    score = jnp.broadcast_to(ql.astype(jnp.int32), (tq, tc))

    def step(i, carry):
        pv = list(carry[0:W])
        mv = list(carry[W:2 * W])
        score = carry[2 * W]
        t = cct_ref[pl.ds(i, 1), :]                       # (1, TC)
        eq = []
        for w in range(W):
            e = jnp.zeros((tq, tc), jnp.uint32)
            for j in range(32 * w, min(32 * (w + 1), L)):
                e = e | jnp.where(
                    qc[:, j : j + 1] == t, jnp.uint32(1 << (j - 32 * w)), 0
                )
            eq.append(e)
        xv = [eq[w] | mv[w] for w in range(W)]
        # xh = (((eq & pv) + pv) ^ pv) | eq with a carry chain across the
        # words (the carry out of the last word falls off the pattern
        # window)
        xh = []
        c = None
        for w in range(W):
            a = eq[w] & pv[w]
            s = a + pv[w]
            cout = _carry_out(a, pv[w], s)
            if c is not None:
                s2 = s + c
                cout = cout | _carry_out(s, c, s2)
                s = s2
            xh.append((s ^ pv[w]) | eq[w])
            c = cout
        ph = [mv[w] | ~(xh[w] | pv[w]) for w in range(W)]
        mh = [pv[w] & xh[w] for w in range(W)]

        active = i < cl                                   # (1, TC)
        ph_hi, mh_hi = ph[0], mh[0]
        for w in range(1, W):
            sel = hi_word == w
            ph_hi = jnp.where(sel, ph[w], ph_hi)
            mh_hi = jnp.where(sel, mh[w], mh_hi)
        score = score + jnp.where(active & ((ph_hi & hibit) != 0), 1, 0)
        score = score - jnp.where(active & ((mh_hi & hibit) != 0), 1, 0)

        # horizontal shifts with cross-word carries
        ph_c = [p >> jnp.uint32(31) for p in ph]
        mh_c = [m >> jnp.uint32(31) for m in mh]
        nph = [(ph[0] << one) | one] + [
            (ph[w] << one) | ph_c[w - 1] for w in range(1, W)
        ]
        nmh = [mh[0] << one] + [
            (mh[w] << one) | mh_c[w - 1] for w in range(1, W)
        ]
        pv = [
            jnp.where(active, nmh[w] | ~(xv[w] | nph[w]), pv[w])
            for w in range(W)
        ]
        mv = [jnp.where(active, nph[w] & xv[w], mv[w]) for w in range(W)]
        return (*pv, *mv, score)

    out = lax.fori_loop(0, L, step, (*pv, *mv, score))
    score = out[2 * W]
    out_ref[...] = jnp.where(
        ql == 0, jnp.broadcast_to(cl.astype(jnp.int32), (tq, tc)), score
    )


@functools.partial(
    jax.jit, static_argnames=("tile_q", "tile_c", "interpret", "words")
)
def _myersN_tiles_padded(qc, ql2, cct, cl2, *, tile_q, tile_c, interpret,
                         words):
    qp, l = qc.shape
    cp = cct.shape[1]
    grid = (qp // tile_q, cp // tile_c)
    kernel = functools.partial(_myersN_tile_kernel, L=l, W=words)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((qp, cp), jnp.int32),
        grid=grid,
        in_specs=_pair_tile_specs(l, l, tile_q, tile_c),
        out_specs=pl.BlockSpec(
            (tile_q, tile_c), lambda i, j: (i, j), memory_space=_VMEM
        ),
        interpret=interpret,
    )(qc, ql2, cct, cl2)


# -- Jaro-Winkler, tiled over the pair matrix --------------------------------


def _jw_tile_kernel(qc_ref, ql_ref, cct_ref, cl_ref, out_ref, *,
                    L: int, prefix_scale: float, boost_threshold: float,
                    max_prefix: int):
    """One (TQ, TC) Jaro similarity tile (Winkler boost applied here too).

    Matched positions live in uint32 bitmasks (L <= 32): the greedy
    matching pass sets, for each query char, the lowest available bit of
    the candidate window; the transposition pass walks both masks in
    lowest-bit order extracting chars through one-hot dot products.
    Parity oracle: core.comparators._jaro / JaroWinkler (tests).
    """
    tq = qc_ref.shape[0]
    tc = cct_ref.shape[1]
    qc = qc_ref[...]                                  # (TQ, L)
    ql = ql_ref[...][:, :1].astype(jnp.int32)         # (TQ, 1)
    cl = cl_ref[...][:1, :].astype(jnp.int32)         # (1, TC)

    one = jnp.uint32(1)
    full = jnp.uint32(0xFFFFFFFF)
    l1 = jnp.broadcast_to(ql, (tq, tc))
    l2 = jnp.broadcast_to(cl, (tq, tc))
    window = jnp.maximum(jnp.maximum(l1, l2) // 2 - 1, 0)

    def bits_below(n):
        # (1 << n) - 1 with n in [0, 32]
        nn = jnp.clip(n, 0, 32)
        return jnp.where(
            nn >= 32, full, (one << nn.astype(jnp.uint32)) - one
        )

    m1 = jnp.zeros((tq, tc), jnp.uint32)
    m2 = jnp.zeros((tq, tc), jnp.uint32)
    matches = jnp.zeros((tq, tc), jnp.int32)

    for i in range(L):  # static: greedy matching, all pairs in lockstep
        ci = qc[:, i : i + 1]                         # (TQ, 1)
        eq = jnp.zeros((tq, tc), jnp.uint32)
        for j in range(L):
            eq = eq | jnp.where(
                cct_ref[j : j + 1, :] == ci, jnp.uint32(1 << j), 0
            )
        lo = jnp.maximum(i - window, 0)
        hi = jnp.minimum(l2, i + window + 1)
        wmask = bits_below(hi) & ~bits_below(lo)
        active = i < l1
        avail = eq & wmask & ~m2
        avail = jnp.where(active, avail, jnp.uint32(0))
        j_star = avail & (jnp.uint32(0) - avail)      # lowest set bit
        found = j_star != 0
        m2 = m2 | j_star
        m1 = m1 | jnp.where(found, jnp.uint32(1 << i), 0)
        matches = matches + found.astype(jnp.int32)

    # transposition pass: walk both masks lowest-bit-first, compare the
    # k-th matched chars; char extraction via one-hot dot over positions
    m1r, m2r = m1, m2
    trans = jnp.zeros((tq, tc), jnp.int32)
    for _ in range(L):
        a = m1r & (jnp.uint32(0) - m1r)
        b = m2r & (jnp.uint32(0) - m2r)
        m1r = m1r ^ a
        m2r = m2r ^ b
        ca = jnp.zeros((tq, tc), jnp.int32)
        cb = jnp.zeros((tq, tc), jnp.int32)
        for i in range(L):
            bit = jnp.uint32(1 << i)
            ca = ca + jnp.where((a & bit) != 0, qc[:, i : i + 1], 0)
            cb = cb + jnp.where((b & bit) != 0, cct_ref[i : i + 1, :], 0)
        trans = trans + ((a != 0) & (ca != cb)).astype(jnp.int32)

    m = matches.astype(jnp.float32)
    l1f = l1.astype(jnp.float32)
    l2f = l2.astype(jnp.float32)
    half_trans = (trans // 2).astype(jnp.float32)
    jaro = (m / jnp.maximum(l1f, 1.0) + m / jnp.maximum(l2f, 1.0)
            + (m - half_trans) / jnp.maximum(m, 1.0)) / 3.0
    jaro = jnp.where((matches == 0) | (l1 == 0) | (l2 == 0), 0.0, jaro)

    # Winkler common-prefix boost (max_prefix static, typically 4)
    prefix = jnp.zeros((tq, tc), jnp.int32)
    still = jnp.ones((tq, tc), jnp.bool_)
    for i in range(min(L, max_prefix)):
        ok = ((qc[:, i : i + 1] == cct_ref[i : i + 1, :])
              & (i < jnp.minimum(l1, l2)))
        still = still & ok
        prefix = prefix + still.astype(jnp.int32)
    boosted = jaro + prefix.astype(jnp.float32) * jnp.float32(
        prefix_scale
    ) * (1.0 - jaro)
    out_ref[...] = jnp.where(
        jaro < jnp.float32(boost_threshold), jaro, boosted
    )


@functools.partial(
    jax.jit,
    static_argnames=("tile_q", "tile_c", "interpret", "prefix_scale",
                     "boost_threshold", "max_prefix"),
)
def _jw_tiles_padded(qc, ql2, cct, cl2, *, tile_q, tile_c, interpret,
                     prefix_scale, boost_threshold, max_prefix):
    qp, l = qc.shape
    cp = cct.shape[1]
    grid = (qp // tile_q, cp // tile_c)
    kernel = functools.partial(
        _jw_tile_kernel, L=l, prefix_scale=prefix_scale,
        boost_threshold=boost_threshold, max_prefix=max_prefix,
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((qp, cp), jnp.float32),
        grid=grid,
        in_specs=_pair_tile_specs(l, l, tile_q, tile_c),
        out_specs=pl.BlockSpec(
            (tile_q, tile_c), lambda i, j: (i, j), memory_space=_VMEM
        ),
        interpret=interpret,
    )(qc, ql2, cct, cl2)


def jaro_winkler_sim_tiles(qchars, qlen, cchars, clen, equal, *,
                           prefix_scale=0.1, boost_threshold=0.7,
                           max_prefix=4, interpret=None):
    """All-pairs Jaro-Winkler similarity -> (Q, C) f32.

    Same layout contract as ``myers_distance_tiles``; ``equal`` is the
    (Q, C) exact-equality mask (comparator's v1==v2 early exit -> 1.0).
    """
    q = qchars.shape[0]
    c = cchars.shape[0]
    if qchars.shape[1] > 32:
        raise ValueError(
            f"JW pallas kernel needs L <= 32, got {qchars.shape[1]}"
        )
    if interpret is None:
        interpret = _interpret()
    # smaller tiles than Myers: the static unrolls are O(L^2), so keep the
    # program size and VMEM live range in check
    qc, ql2, cct, cl2, tile_q, tile_c = _stage_pair_operands(
        qchars, qlen, cchars, clen, tile_q_cap=64, tile_c_cap=256
    )
    out = _jw_tiles_padded(
        qc, ql2, cct, cl2, tile_q=tile_q, tile_c=tile_c,
        interpret=interpret, prefix_scale=float(prefix_scale),
        boost_threshold=float(boost_threshold), max_prefix=int(max_prefix),
    )[:q, :c]
    return jnp.where(equal, 1.0, out)


# -- gathered-candidate Myers (ANN rescoring layout) -------------------------


def _myers_gathered_kernel(qc_ref, ql_ref, cclt_ref, cl_ref, out_ref, *,
                           L: int):
    """Per-query gathered-candidate Levenshtein tile.

    Unlike the cross-product tiles, candidate c of query q here is a
    SPECIFIC gathered corpus row (the ANN rescoring layout): text chars
    differ per (q, c) pair, so the candidate axis rides the lanes and the
    bit-parallel DP state is (TQ, TC) with per-pair text.

    qc_ref:   (TQ, L)      query codepoints (pattern)
    ql_ref:   (TQ, 1)      query lengths
    cclt_ref: (TQ, L, TC)  candidate codepoints, char axis in sublanes
    cl_ref:   (TQ, TC)     candidate lengths
    out_ref:  (TQ, TC)     int32 distances
    """
    tq = qc_ref.shape[0]
    tc = cl_ref.shape[1]
    qc = qc_ref[...]                      # (TQ, L)
    ql = ql_ref[...][:, :1]               # (TQ, 1)
    cl = cl_ref[...]                      # (TQ, TC)

    pv0, hibit = _myers_word_init(ql)     # (TQ, 1)

    pv = jnp.broadcast_to(pv0, (tq, tc))
    mv = jnp.zeros((tq, tc), jnp.uint32)
    score = jnp.broadcast_to(ql.astype(jnp.int32), (tq, tc))

    def step(i, carry):
        pv, mv, score = carry
        t = cclt_ref[:, pl.ds(i, 1), :].reshape(tq, tc)   # (TQ, TC)
        eq = jnp.zeros((tq, tc), jnp.uint32)
        for j in range(L):
            eq = eq | jnp.where(qc[:, j : j + 1] == t, jnp.uint32(1 << j), 0)
        return _myers_word_step(eq, pv, mv, score, i < cl, hibit)

    pv, mv, score = lax.fori_loop(0, L, step, (pv, mv, score))
    out_ref[...] = jnp.where(
        ql == 0, cl.astype(jnp.int32), score
    )


@functools.partial(
    jax.jit, static_argnames=("tile_q", "tile_c", "interpret")
)
def _myers_gathered_padded(qc, ql2, cclt, cl2, *, tile_q, tile_c, interpret):
    qp, l = qc.shape
    cp = cclt.shape[2]
    grid = (qp // tile_q, cp // tile_c)
    kernel = functools.partial(_myers_gathered_kernel, L=l)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((qp, cp), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_q, l), lambda i, j: (i, 0),
                         memory_space=_VMEM),
            pl.BlockSpec((tile_q, 1), lambda i, j: (i, 0),
                         memory_space=_VMEM),
            pl.BlockSpec((tile_q, l, tile_c), lambda i, j: (i, 0, j),
                         memory_space=_VMEM),
            pl.BlockSpec((tile_q, tile_c), lambda i, j: (i, j),
                         memory_space=_VMEM),
        ],
        out_specs=pl.BlockSpec(
            (tile_q, tile_c), lambda i, j: (i, j), memory_space=_VMEM
        ),
        interpret=interpret,
    )(qc, ql2, cclt, cl2)


def myers_distance_gathered(qchars, qlen, cchars, clen, *, interpret=None):
    """Levenshtein distance for gathered candidates -> (Q, C) int32.

    qchars: (Q, L) int32, L <= 32; qlen: (Q,)
    cchars: (Q, C, L) int32 — candidate c of query q; clen: (Q, C)
    """
    q, l = qchars.shape
    c = cchars.shape[1]
    if l > 32:
        raise ValueError(f"gathered Myers kernel needs L <= 32, got {l}")
    if interpret is None:
        interpret = _interpret()
    tile_q = min(64, _round_up(max(q, 1), 8))
    tile_c = 128  # candidate axis always pads to (at least) one full lane
    qp = _round_up(max(q, 1), tile_q)
    cp = _round_up(max(c, 1), tile_c)
    qc = jnp.zeros((qp, l), jnp.int32).at[:q].set(qchars)
    ql2 = jnp.zeros((qp, 1), jnp.int32).at[:q, 0].set(qlen)
    cclt = jnp.zeros((qp, l, cp), jnp.int32).at[:q, :, :c].set(
        jnp.transpose(cchars, (0, 2, 1))
    )
    cl2 = jnp.zeros((qp, cp), jnp.int32).at[:q, :c].set(clen)
    out = _myers_gathered_padded(
        qc, ql2, cclt, cl2, tile_q=tile_q, tile_c=tile_c, interpret=interpret
    )
    return out[:q, :c]


def levenshtein_sim_gathered(qchars, qlen, cchars, clen, equal, *,
                             interpret=None):
    """Duke Levenshtein similarity for gathered candidates: (Q, C) f32."""
    from .pairwise import levenshtein_sim_from_distance

    dist = myers_distance_gathered(
        qchars, qlen, cchars, clen, interpret=interpret
    )
    return levenshtein_sim_from_distance(
        dist, qlen[:, None], clen, equal
    )


# -- set intersection (q-grams / token sets), tiled --------------------------


def _intersect_tile_kernel(qg_ref, qn_ref, cgt_ref, cn_ref, out_ref, *, G: int):
    """One (TQ, TC) intersection-count tile.

    qg_ref:  (TQ, G)  query gram/token hashes (SET_PAD-padded)
    qn_ref:  (TQ, 1)  query set sizes
    cgt_ref: (G, TC)  corpus hashes, transposed
    cn_ref:  (1, TC)  corpus set sizes
    out_ref: (TQ, TC) int32 |A ∩ B|
    """
    tq = qg_ref.shape[0]
    tc = cgt_ref.shape[1]
    qn = qn_ref[...][:, :1]                          # (TQ, 1)
    cn = cn_ref[...][:1, :]                          # (1, TC)
    qg = qg_ref[...]                                 # (TQ, G)
    lane = lax.broadcasted_iota(jnp.int32, (tq, G), 1)

    # outer loop over query grams is a fori_loop so the program stays O(G)
    # (a static G x G unroll produced 4096-step Mosaic programs at the
    # default DEVICE_MAX_GRAMS=64); Mosaic cannot dynamic-slice the lane
    # axis, so the query column is extracted by a masked lane reduction.
    # The inner corpus loop unrolls statically: sublane slices are static
    # and every step is one (TQ, TC) vector compare on the VPU.
    def step(i, count):
        qv = jnp.sum(
            jnp.where(lane == i, qg, 0), axis=1, keepdims=True
        )                                            # (TQ, 1)
        hit = jnp.zeros((tq, tc), jnp.bool_)
        for j in range(G):
            jvalid = j < cn                          # (1, TC)
            hit = hit | ((qv == cgt_ref[j : j + 1, :]) & jvalid)
        # sets are distinct: each query element matches at most one corpus
        # element, so OR-then-add counts the intersection exactly
        return count + jnp.where(hit & (i < qn), 1, 0)

    out_ref[...] = lax.fori_loop(
        0, G, step, jnp.zeros((tq, tc), jnp.int32)
    )


@functools.partial(
    jax.jit, static_argnames=("tile_q", "tile_c", "interpret")
)
def _intersect_tiles_padded(qg, qn2, cgt, cn2, *, tile_q, tile_c, interpret):
    qp, g = qg.shape
    cp = cgt.shape[1]
    grid = (qp // tile_q, cp // tile_c)
    kernel = functools.partial(_intersect_tile_kernel, G=g)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((qp, cp), jnp.int32),
        grid=grid,
        in_specs=_pair_tile_specs(g, g, tile_q, tile_c),
        out_specs=pl.BlockSpec(
            (tile_q, tile_c), lambda i, j: (i, j), memory_space=_VMEM
        ),
        interpret=interpret,
    )(qg, qn2, cgt, cn2)


def _pad_set_width(sets, width: int):
    """(N, G) sets padded with SET_PAD to (N, width) slots."""
    if sets.shape[1] == width:
        return sets
    return jnp.pad(sets, ((0, 0), (0, width - sets.shape[1])),
                   constant_values=SET_PAD)


def set_intersection_tiles(qgrams, qn, cgrams, cn, *, interpret=None):
    """All-pairs |set_i ∩ set_j| -> (Q, C) int32.

    qgrams: (Q, G) int32 hashed ids (SET_PAD-padded); qn: (Q,) set sizes
    cgrams: (C, G') int32; cn: (C,) — same layout as ops.features GRAM_SET /
    TOKEN_SET tensors.  Padded rows compute garbage counts that callers
    mask via validity bits.  The two widths may differ (an http-transform
    probe's set can be wider than the corpus's): the narrower side is
    padded, and its pad slots lie past its set size, which the kernel
    never counts.
    """
    q = qgrams.shape[0]
    c = cgrams.shape[0]
    g = max(qgrams.shape[1], cgrams.shape[1])
    qgrams = _pad_set_width(qgrams, g)
    cgrams = _pad_set_width(cgrams, g)
    if interpret is None:
        interpret = _interpret()
    qg, qn2, cgt, cn2, tile_q, tile_c = _stage_pair_operands(
        qgrams, qn, cgrams, cn, tile_q_cap=128, tile_c_cap=512
    )
    out = _intersect_tiles_padded(
        qg, qn2, cgt, cn2, tile_q=tile_q, tile_c=tile_c, interpret=interpret
    )
    return out[:q, :c]


def set_sim_tiles(qids, qn, cids, cn, equal, *, formula,
                  interpret=None):
    """Set-comparator similarity over all query x corpus pairs: (Q, C) f32.

    One tile entry point for QGram (``formula`` = its configured formula),
    JaccardIndex ('jaccard'), and DiceCoefficient ('dice'); the
    intersection -> similarity math is the shared
    ``ops.pairwise.sim_from_set_intersection``, so the tile and flat paths
    cannot drift.
    """
    from .pairwise import sim_from_set_intersection

    common = set_intersection_tiles(qids, qn, cids, cn, interpret=interpret)
    return sim_from_set_intersection(
        common, qn[:, None], cn[None, :], equal, formula=formula
    )


def levenshtein_sim_tiles(qchars, qlen, cchars, clen, equal, *, interpret=None):
    """Duke Levenshtein similarity over all query x corpus pairs: (Q, C) f32.

    Mirrors ops.pairwise.levenshtein_sim (core.comparators.Levenshtein
    semantics) on tiled pair distances; ``equal`` is the (Q, C) exact
    string-equality mask (from value hashes).
    """
    from .pairwise import levenshtein_sim_from_distance

    dist = myers_distance_tiles(qchars, qlen, cchars, clen, interpret=interpret)
    return levenshtein_sim_from_distance(dist, qlen[:, None], clen[None, :], equal)


# -- fused ANN retrieval: matmul + mask + segment-max in VMEM ----------------
#
# The XLA retrieval scan materializes a (Q, chunk) f32 similarity tile in
# HBM every step just so a top-C merge can read it back — at 10M rows and
# Q=1024 that is ~40 GB of traffic for a 5 GB corpus, which is why the r4
# scan measured ~0.4% MFU (VERDICT r4).  This kernel fuses the cosine
# matmul, the candidate mask, and a segment-max reduction into one VMEM
# pass: per (TC x Q) tile the scores live only on-chip, and what reaches
# HBM is the (TC/SEG, Q) per-segment running maxima + argmaxima — a SEG-x
# reduction of the write traffic.  The final top-C then runs over the
# (Q, rows/SEG) segment winners (ops.encoder.retrieval_scan), which is
# SEG-x cheaper than sorting raw similarities.  Semantically this is the
# first phase of lax.approx_max_k's PartialReduce (Chern et al. 2022) with
# the bin layout chosen to match the corpus tiling — recall loss is the
# same birthday-collision bound, configured via DEVICE_ANN_SEG.
#
# Layout: scores are computed TRANSPOSED — (TC corpus rows, Q queries) —
# so the segment reduction runs over sublanes (corpus axis) while queries
# ride the lanes; outputs are (rows/SEG, Q) and the caller transposes
# once (O(rows/SEG * Q) traffic, amortized SEG-x).


# Encoded candidate mask: one int8 per corpus row, broadcast across a
# 128-lane axis so the operand is tile-native — (N, 1) int32 columns get
# T(8,128)-padded 128x by XLA's custom-call layout (a 4.8 GB temp copy at
# 10M rows, measured OOM), and Mosaic cannot shape-cast a lane-major
# block back to a column, so the kernel recovers the column with a lane
# max-reduction instead.  enc = 0 dead/tombstoned, group + GROUP_OFFSET
# live; group ids in this engine are tiny (-1 for dedup, the dataset
# group numbers 1/2 for linkage — service/datasource.py), so int8 holds
# them with room to spare.
GROUP_OFFSET = 2


def _retrieval_segmax_kernel(qT_ref, c_ref, enc_ref, qrow_ref,
                             qgroupe_ref, max_ref, arg_ref, *,
                             tc: int, seg: int, group_filtering: bool,
                             neg: float):
    scores = jnp.dot(
        c_ref[...], qT_ref[...], preferred_element_type=jnp.float32
    )  # (TC, Q) on the MXU
    cidx = (pl.program_id(0) * tc
            + lax.broadcasted_iota(jnp.int32, (tc, 1), 0))
    enc = jnp.max(enc_ref[...].astype(jnp.int32), axis=1, keepdims=True)
    mask = enc > 0                                        # (TC, 1)
    if group_filtering:
        mask = mask & (enc != qgroupe_ref[...])           # (TC, Q)
    mask = mask & (cidx != qrow_ref[...])                 # self-exclusion
    scores = jnp.where(mask, scores, jnp.float32(neg))
    q = scores.shape[1]
    # STRIDED binning: row r of the tile lands in bin r mod (TC/SEG), so
    # ADJACENT corpus rows go to DIFFERENT bins.  Duplicates are adjacent
    # by construction in this workload (a batch commits into contiguous
    # rows), so contiguous binning would collapse a duplicate cluster
    # into one survivor — silently dropping matches AND starving the
    # count-saturation signal the C-escalation loop needs.  Strided bins
    # tolerate clusters up to TC/SEG rows per tile (lax.approx_max_k's
    # TPU PartialReduce is adjacency-safe the same way, verified in
    # tests/test_fused_retrieval.py); wider clusters degrade to TC/SEG
    # retrieved members, which still saturates the count signal whenever
    # C <= TC/SEG.
    s3 = scores.reshape(seg, tc // seg, q)
    seg_max = jnp.max(s3, axis=0)                         # (TC/SEG, Q)
    rid3 = cidx.reshape(seg, tc // seg, 1)
    big = jnp.int32(2**31 - 1)
    seg_arg = jnp.min(
        jnp.where(s3 == seg_max[None, :, :], rid3, big), axis=0
    )
    max_ref[...] = seg_max
    arg_ref[...] = seg_arg


@functools.partial(
    jax.jit,
    static_argnames=("tc", "seg", "group_filtering", "interpret"),
)
def retrieval_segmax(qT, corpus_emb, enc, qrow_local, qgroup_enc, *,
                     tc: int, seg: int, group_filtering: bool,
                     interpret=None):
    """Fused retrieval phase 1: per-segment (max, argmax) of masked cosine
    scores over the whole corpus.

    Operands (pre-staged by ops.encoder.retrieval_scan):
      qT          (D, Q)   bf16 — queries transposed, Q a lane multiple
      corpus_emb  (N, D)   bf16 — N a multiple of ``tc``
      enc         (N, 128) int8 — encoded mask, identical across lanes:
                  0 = dead/tombstoned, group + GROUP_OFFSET = live
      qrow_local  (1, Q)   int32 — query's own LOCAL corpus row (-1 none)
      qgroup_enc  (1, Q)   int32 — query group + GROUP_OFFSET

    Returns (seg_max (N/seg, Q) f32, seg_arg (N/seg, Q) int32) with LOCAL
    row ids; all-masked segments carry ``neg`` and an arbitrary masked
    row — the caller turns those into -1 via the value sentinel.
    """
    if interpret is None:
        interpret = _interpret()
    n, d = corpus_emb.shape
    q = qT.shape[1]
    neg = -3.0e38
    grid = (n // tc,)
    kernel = functools.partial(
        _retrieval_segmax_kernel, tc=tc, seg=seg,
        group_filtering=group_filtering, neg=neg,
    )
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((d, q), lambda i: (0, 0), memory_space=_VMEM),
            pl.BlockSpec((tc, d), lambda i: (i, 0), memory_space=_VMEM),
            pl.BlockSpec((tc, 128), lambda i: (i, 0), memory_space=_VMEM),
            pl.BlockSpec((1, q), lambda i: (0, 0), memory_space=_VMEM),
            pl.BlockSpec((1, q), lambda i: (0, 0), memory_space=_VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n // seg, q), jnp.float32),
            jax.ShapeDtypeStruct((n // seg, q), jnp.int32),
        ],
        out_specs=[
            pl.BlockSpec((tc // seg, q), lambda i: (i, 0),
                         memory_space=_VMEM),
            pl.BlockSpec((tc // seg, q), lambda i: (i, 0),
                         memory_space=_VMEM),
        ],
        interpret=interpret,
    )(qT, corpus_emb, enc, qrow_local, qgroup_enc)
