"""The device scoring program: per-property kernels + naive-Bayes combine.

Assembles, for a given schema feature plan (ops.features.SchemaFeatures), a
jitted function that scores a block of Q query records against the
device-resident corpus in chunks, up to its last valid row, maintaining a
running top-K per query.
This replaces the reference hot loop (candidate fetch + per-pair comparator
dispatch + Bayes fold, SURVEY.md section 3.2) with one XLA program:

    for each corpus chunk up to the live high-water mark (a while loop
    whose trip count is computed on device, ``live_chunks``):
        sims  = per-property pairwise kernels        (ops.pairwise)
        probs = Duke's [low, high] similarity map    (per property)
        logit = sum of clamped log-odds              (naive Bayes, 0.5 prior)
        merge chunk scores into running top-K        (lax.top_k)

Hybrid host properties: comparators without a device kernel contribute an
*optimistic* constant logit bound on device (max(0, logit(high)) per
property); ranking is by the device partial logit (the constant does not
reorder), and the host adds the exact contributions for the surviving top-K
pairs only — exact semantics at O(K) host work per query instead of O(N).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Callable, Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core import comparators as C
from . import features as F
from . import pairwise as pw
from . import pallas_kernels as pk

# Sentinel for empty top-K slots (logit scale).
NEG_INF = -3.0e38

# Matches core.bayes._EPS: probabilities clamped away from {0, 1}.
_EPS = 1e-10
_MAX_LOGIT = math.log((1.0 - _EPS) / _EPS)


def probability_to_logit(p: float) -> float:
    p = min(max(p, _EPS), 1.0 - _EPS)
    return math.log(p / (1.0 - p))


def host_bound_logit(host_props) -> float:
    """Optimistic total logit the host-scored properties could contribute."""
    return sum(max(0.0, probability_to_logit(p.high)) for p in host_props)


_F32_EPS = float(np.finfo(np.float32).eps)


def certified_f32_margin(plan: "F.SchemaFeatures") -> float:
    """Certified upper bound on |device f32 logit - exact f64 logit|.

    The device program computes, per property, a similarity, Duke's
    quadratic probability map, and a clamped log-odds, then sums the
    per-property logits — all in float32.  Per property the error budget
    has two parts:

      * **similarity error through the map**: a per-kernel-kind
        similarity budget (``_SIM_ERROR_BOUND``: 64 ulps for the
        integer-count-ratio kernels, wider for weighted-Levenshtein and
        numeric, uncertifiable for THAT PROPERTY under geoposition —
        an ``inf`` entry collapses this whole-schema bound, so decisive
        pruning degrades to rescore-everything, but the device-finalize
        split in ``engine.finalize`` falls back to the host PER
        PROPERTY: the remaining certifiable properties keep their
        device verdicts), amplified by the
        worst-case slope of the probability→log-odds composition.
        ``|dlogit/dp| = 1/(p(1-p))`` and ``|dp/dsim| <= 1``, so the
        amplification is bounded by ``1/min(high(1-high), low(1-low))``
        — a property with an extreme ``high`` (sharp log-odds) correctly
        demands a wider margin;
      * **direct rounding of the log-odds**: 32 ulps of the clamp
        ``_MAX_LOGIT``.

    The final sum of ``n`` clamped terms adds ``n * ulp(n * _MAX_LOGIT)``
    of accumulation error.  Branch discontinuities (the ``sim >= 0.5``
    split in the probability map) are outside any rounding bound — they
    are the same measure-zero exposure the device-side survivor filter
    has always had and are covered by the differential tests, not by
    this margin.

    When a schema's sharp properties push this margin past the device
    filter's fixed 1e-3 insurance margin the decisive band is empty
    (the prune bound falls below the filter bound, so no survivor ever
    sits in it) — pruning degrades to "rescore everything", never to
    unsoundness.  The filter itself deliberately stays at 1e-3: a
    degenerate config (low=0.0 / high=1.0) makes this margin huge, and
    widening the filter with it would stop filtering at all.

    Used by decisive-band pruning (engine.finalize): a survivor whose
    device logit plus this margin plus the optimistic host-property bound
    still cannot reach ``logit(min_threshold)`` certifiably cannot emit an
    event, so its exact host rescore is skipped.
    """
    n = max(1, len(plan.device_props))
    total = n * _F32_EPS * (n * _MAX_LOGIT)  # accumulation of the sum
    for spec in plan.device_props:
        high = min(max(float(spec.high), _EPS), 1.0 - _EPS)
        low = min(max(float(spec.low), _EPS), 1.0 - _EPS)
        amplification = 1.0 / min(high * (1.0 - high), low * (1.0 - low))
        sim_err = _SIM_ERROR_BOUND.get(spec.kind, float("inf"))
        # a property's logit is clamped to [-_MAX_LOGIT, _MAX_LOGIT], so
        # however steep the map, its error cannot exceed the clamp range
        total += min(sim_err * amplification, 2.0 * _MAX_LOGIT)
        total += 32.0 * _F32_EPS * _MAX_LOGIT      # log-odds rounding
    return total


# Per-kind absolute similarity-error bounds for the certified margin.
# Edit-distance / set / hash / phonetic sims are ratios of exact integer
# counts with one final f32 division — 64 ulps is generous.  Weighted
# Levenshtein accumulates up to ~256 f32 weight additions; numeric is a
# ratio of f32-quantized doubles; both get wider budgets.  Geoposition is
# NOT certifiable — but only PER PROPERTY: f32 lat/lon quantization alone
# is meters of position error, arbitrarily large in similarity units for
# small max-distance.  Because this whole-schema margin takes a sum over
# properties, one inf entry still collapses the decisive band (rescore
# everything) for any schema carrying a geo property — the sound default
# for unknown future kinds too — while the per-property device-finalize
# split (``engine.finalize``, ISSUE 12) routes ONLY the geo property to
# the host and keeps certified device verdicts for the rest.
# Ledger derivations (scripts/dukecheck/budgets, docs/ERROR_BUDGETS.md):
# the ratio kinds pay one f32 division plus the quadratic map (~8 ulps
# total before amplification), weighted Levenshtein pays ~256 weight
# accumulations, numeric a ratio of f32-quantized doubles.  GEO is
# uncertifiable BY DESIGN (inf — no annotation; see the block comment).
_SIM_ERROR_BOUND = {
    F.CHARS: 64.0 * _F32_EPS,          # dd-budget: _SIM_ERROR_BOUND[CHARS] covers 8 * eps32 headroom 4
    F.GRAM_SET: 64.0 * _F32_EPS,       # dd-budget: _SIM_ERROR_BOUND[GRAM_SET] covers 8 * eps32 headroom 4
    F.TOKEN_SET: 64.0 * _F32_EPS,      # dd-budget: _SIM_ERROR_BOUND[TOKEN_SET] covers 8 * eps32 headroom 4
    F.HASH: 64.0 * _F32_EPS,           # dd-budget: _SIM_ERROR_BOUND[HASH] covers 2 * eps32 headroom 16
    F.PHONETIC: 64.0 * _F32_EPS,       # dd-budget: _SIM_ERROR_BOUND[PHONETIC] covers 2 * eps32 headroom 16
    F.CHARS_WEIGHTED: 2048.0 * _F32_EPS,  # dd-budget: _SIM_ERROR_BOUND[CHARS_WEIGHTED] covers 2 * 256 * eps32 headroom 2
    F.NUMERIC: 256.0 * _F32_EPS,       # dd-budget: _SIM_ERROR_BOUND[NUMERIC] covers 64 * eps32 headroom 2
    F.GEO: float("inf"),
}


def emit_bound_logit(schema, plan: "F.SchemaFeatures",
                     margin: float) -> float:
    """ONE copy of the survivor-bound formula: the device logit below
    which a pair cannot emit an event at the given error ``margin`` —
    ``logit(min(threshold, maybe_threshold))`` minus the optimistic
    host-property contribution minus ``margin``.  The device-side
    survivor filter and decisive-band pruning both derive from this, so
    they can never drift onto different threshold/host-bound handling
    (pruning soundness requires the prune bound to sit inside the
    filter's retained band)."""
    thresholds = [schema.threshold]
    if schema.maybe_threshold:
        thresholds.append(schema.maybe_threshold)
    return (
        probability_to_logit(min(thresholds))
        - host_bound_logit(plan.host_props)
        - margin
    )


def decisive_prune_logit(schema, plan: "F.SchemaFeatures") -> float:
    """Device-logit bound below which a survivor is *decisively* a
    non-event: ``device_logit <= decisive_prune_logit`` implies the exact
    f64 pair probability cannot exceed ``min(threshold, maybe_threshold)``
    even with every host-scored property at its optimistic maximum and the
    certified float32 error credited in the survivor's favor.  Survivors
    at or below this bound skip the host ``compare`` call entirely;
    everything above it is rescored host-exact, so emitted probabilities
    stay bit-identical to the host engine."""
    return emit_bound_logit(schema, plan, certified_f32_margin(plan))


# -- certified double-double (emulated-f64) finalization ---------------------
#
# ISSUE 12 tentpole.  The f32 margin above is a PRUNING bound: sharp
# schemas amplify 64 float32 ulps into a band wide enough that most
# survivors still need the host's exact f64 ``compare``.  The dd rescore
# re-runs the comparator->probability->log-odds pipeline for the
# surviving top-K pairs in two-float (~49-bit) arithmetic (ops.dd): the
# integer counts the comparators reduce to (edit distances, set
# intersection sizes, match/transposition counts, lengths) are already
# exact on device, so only the final ratio, Duke's quadratic probability
# map, and the clamped Bayes logit sum need the extended precision.  The
# resulting per-pair dd logit is within ``certified_dd_margin`` —
# typically ~1e-10 logit units — of the host's f64 value, so a verdict
# whose logit sits farther than the margin from every decision boundary
# is *bit-certified*: the host compare provably classifies it the same
# way, and a certified reject can skip the host entirely.
#
# Branch-discontinuity soundness: every branch predicate in the
# certified family compares a rational of BOUNDED INTEGERS against a
# constant.  For the single-division kinds (Levenshtein, sets) the
# argument is spacing: a rational a/b differs from a non-equal constant
# p/q by at least 1/(qb) — >= ~1e-7 at the width caps, five orders above
# the dd evaluation error — and when the exact ratio EQUALS the constant
# the division is exact in both f64 and dd (dyadic results round clean),
# so both sides take the same branch.  Jaro-Winkler is different: its
# ``j`` is a SUM of three ratios, so an exactly-attainable boundary value
# (j == 1/2 or 7/10 — e.g. (1/3 + 1/2 + 2/3)/3 == 0.5 exactly) is
# computed INEXACTLY by both the host f64 chain and the dd chain, and
# the two roundings can land on opposite sides of the comparison
# (observed in the randomized differential: host j == 0.5 took the map's
# high branch, dd j == 0.5 - 2^-45 took ``low`` — a 1.17-logit verdict
# flip).  JW pairs whose dd ``j`` sits within ``_DD_JW_BRANCH_GUARD`` of
# a branch constant are therefore flagged into the host residue; off the
# guard band, |host j - dd j| <= ~1e-12 << guard keeps the branches
# aligned.  Hash-collision exposure (``equal`` and gram/token ids ride
# 64/32-bit FNV hashes) is exactly the f32 certified path's existing
# featurization assumption — and a false-positive ``equal`` only RAISES
# the dd logit, pushing the pair toward host rescore, never toward a
# wrong certified reject.

def _dd():
    from . import dd as D

    return D


# Feature kinds whose device counts are exact integers — the certified
# dd family.  CHARS_WEIGHTED (f32 weight accumulation), NUMERIC (inputs
# f32-quantized at extraction) and GEO (uncertifiable per the f32 table)
# fall back to the host per property.
DD_KINDS = (F.CHARS, F.GRAM_SET, F.TOKEN_SET, F.HASH, F.PHONETIC)

# Kinds that deliberately take the per-property host fallback instead of
# a certified dd kernel.  DECLARATIVE, and machine-checked: dukecheck's
# numerics gate (DK604) asserts DD_KINDS and DD_FALLBACK_KINDS partition
# ``ops.features.ALL_KINDS`` exactly, and that every dd kind carries a
# ``_DD_SIM_OPS`` budget and every kind a ``_SIM_ERROR_BOUND`` entry —
# a future comparator kind cannot silently ship without a reviewed
# margin entry or an explicit fallback decision.
DD_FALLBACK_KINDS = (F.CHARS_WEIGHTED, F.NUMERIC, F.GEO)

# Jaro-Winkler's branch constants (boost 0.7, the 0.5 map split) are
# compared against rationals with denominator 3*n1*n2*m; past this char
# width the rational spacing argument above thins below 1e-7, so wider
# JW properties fall back to the host instead of eroding the proof.
_DD_JW_MAX_CHARS = 64

# dd similarity-error budgets, in units absorbed by certified_dd_margin:
# ratio kinds pay one dd division + the map's ~6 dd ops; JW pays three
# divisions, the 3-term average and the boost; hash/phonetic are
# constants reproduced from the oracle's own f64 values.  All generous
# multiples of the per-op DD_EPS.
# (ledger: ratio kinds pay one dd division + ~2 fold ops + the ~6-op
# map; JW pays three divisions, the 3-term average, the prefix boost and
# the map, with every term of magnitude <= 2; hash/phonetic reproduce
# oracle constants through the map alone.)
_DD_SIM_OPS = {
    F.CHARS: 64.0,      # dd-budget: _DD_SIM_OPS[CHARS] covers 12 headroom 4
    F.GRAM_SET: 64.0,   # dd-budget: _DD_SIM_OPS[GRAM_SET] covers 14 headroom 4
    F.TOKEN_SET: 64.0,  # dd-budget: _DD_SIM_OPS[TOKEN_SET] covers 14 headroom 4
    F.HASH: 16.0,       # dd-budget: _DD_SIM_OPS[HASH] covers 4 headroom 2
    F.PHONETIC: 16.0,   # dd-budget: _DD_SIM_OPS[PHONETIC] covers 4 headroom 2
}
# dd-budget: _DD_JW_SIM_OPS covers 2 * 22 headroom 4
_DD_JW_SIM_OPS = 256.0


def dd_certifiable_spec(spec: "F.PropertyFeatureSpec") -> bool:
    """Can this device property's verdict ride the certified dd rescore?

    Kind must be in the integer-count-ratio family; Jaro-Winkler
    additionally caps the char width (see ``_DD_JW_MAX_CHARS``).
    """
    if spec.kind not in DD_KINDS:
        return False
    if spec.kind == F.CHARS and isinstance(spec.comparator, C.JaroWinkler):
        return spec.chars <= _DD_JW_MAX_CHARS
    return True


def dd_plan_specs(plan: "F.SchemaFeatures"):
    """The dd-certifiable subset of the plan's device properties."""
    return [s for s in plan.device_props if dd_certifiable_spec(s)]


def dd_fallback_props(schema, plan: "F.SchemaFeatures"):
    """Properties the device-finalize path evaluates on host PER PAIR:
    the plan's host-only properties plus device properties whose kind is
    not dd-certifiable (weighted-lev / numeric / geo — the per-property
    fallback, not a per-schema collapse).  Returns core Property objects
    in schema order so the host-side fold matches the oracle's."""
    dd_names = {s.name for s in dd_plan_specs(plan)}
    return [p for p in schema.comparison_properties()
            if p.name not in dd_names]


def certified_dd_margin(plan: "F.SchemaFeatures") -> float:
    """Certified bound on |dd device logit - host f64 logit| for the
    dd-certifiable properties of ``plan``.

    Sibling of ``certified_f32_margin`` with the same structure — a
    per-property similarity budget amplified by the worst-case
    probability->log-odds slope, a per-property log-evaluation budget,
    and a sum-accumulation term — but charged at the dd per-op epsilon
    (``ops.dd.DD_EPS`` = 2^-44, itself generous against the ~2^-47 true
    double-float bounds) instead of float32 ulps.  The slack also
    absorbs the HOST side's own f64 rounding (u64 = 2^-53 per op,
    hundreds of times below DD_EPS), so the bound is against the host's
    computed value, not the exact real — which is what verdict
    certification needs.  Typical schemas land near 1e-10 logit units,
    ~7 orders of magnitude inside the f32 margin; even a degenerate
    high=1-1e-8 property (amplification 1e8) keeps the dd band at
    ~1e-3, where the f32 band has long since collapsed.

    Only dd-certifiable properties contribute: the uncertifiable kinds
    are evaluated on host per property (``dd_fallback_props``), exactly,
    so they add f64 noise covered by the accumulation term, never an
    amplified similarity error.
    """
    D = _dd()
    specs = dd_plan_specs(plan)
    n_all = max(1, len(plan.device_props) + len(plan.host_props))
    # f64 accumulation-order slack: the oracle interleaves dd and host
    # properties in schema order, the split path sums them in two runs
    total = n_all * D.DD_EPS * (n_all * _MAX_LOGIT)
    for spec in specs:
        high = min(max(float(spec.high), _EPS), 1.0 - _EPS)
        low = min(max(float(spec.low), _EPS), 1.0 - _EPS)
        amplification = 1.0 / min(high * (1.0 - high), low * (1.0 - low))
        if spec.kind == F.CHARS and isinstance(spec.comparator,
                                               C.JaroWinkler):
            sim_err = _DD_JW_SIM_OPS * D.DD_EPS
        else:
            sim_err = _DD_SIM_OPS[spec.kind] * D.DD_EPS
        total += min(sim_err * amplification, 2.0 * _MAX_LOGIT)
        # dd log evaluation: absolute + relative parts (ops.dd bounds)
        total += 2.0 * (D.LOG_ERR_ABS + D.DD_EPS * _MAX_LOGIT)
    return total


def _dd_threshold_slack(threshold: float) -> float:
    """Logit-space slack covering the host's PROBABILITY-space compare.

    The oracle classifies ``sigmoid(logit) > t`` with both sides in f64;
    certification compares logits against ``probability_to_logit(t)``.
    The translation costs a few f64 ulps of the sigmoid evaluation
    amplified by the logit slope at ``t`` plus the rounding of
    ``logit(t)`` itself — generous at 64 u64 per part.
    """
    t = min(max(float(threshold), _EPS), 1.0 - _EPS)
    u64 = 2.0 ** -53
    return 64.0 * u64 * (1.0 / (t * (1.0 - t))) + 64.0 * u64 * _MAX_LOGIT


def dd_reject_bound(schema, plan: "F.SchemaFeatures") -> float:
    """Total-logit bound below which a survivor is a *certified reject*:
    ``dd_logit + exact host-property logits <= this`` implies the host
    f64 probability cannot exceed ``min(threshold, maybe_threshold)``,
    so no event is possible and the host ``compare`` is skipped.

    Unlike ``decisive_prune_logit`` there is no optimistic host-property
    bound to subtract — the fallback properties are evaluated EXACTLY on
    host per pair — so the band around the boundary is just the dd
    margin plus the probability-space comparison slack."""
    thresholds = [schema.threshold]
    if schema.maybe_threshold:
        thresholds.append(schema.maybe_threshold)
    t = min(thresholds)
    return (probability_to_logit(t) - certified_dd_margin(plan)
            - _dd_threshold_slack(t))


def dd_gate_bound(schema, plan: "F.SchemaFeatures") -> float:
    """f32-device-logit bound above which a survivor certifiably CANNOT
    be a dd certified reject — the block-level dispatch gate.

    A pair's certification total is the f64 logit over every property:
    the f32 device logit approximates the device-property part within
    ``certified_f32_margin`` (infinite for geo/degenerate schemas —
    then the gate is +inf and the dd program always dispatches, which
    is sound), and the host-only properties contribute at least
    ``sum(min(0, logit(min(low, 0.5))))`` (each is missing-neutral 0 or
    at worst its clamped ``low``).  A survivor whose f32 logit already
    exceeds ``dd_reject_bound`` plus those two allowances can only be a
    certified EVENT or residue — both take the host compare regardless
    — so a block with no survivor under this bound skips the dd rescore
    program entirely (the common shape for duplicate-heavy ingest,
    where every survivor is an emitter)."""
    lmin = 0.0
    for p in plan.host_props:
        lmin += min(0.0, probability_to_logit(min(float(p.low), 0.5)))
    return (dd_reject_bound(schema, plan) + certified_f32_margin(plan)
            - lmin)


def dd_event_bound(schema, plan: "F.SchemaFeatures") -> float:
    """Total-logit bound above which a survivor *certifiably emits* some
    event (match or maybe).  Such pairs still take one host ``compare``
    — the emitted confidence must be the bit-exact f64 value — but they
    are a certified verdict, not ambiguous residue: the host work is
    O(emitted events), not O(survivors)."""
    thresholds = [schema.threshold]
    if schema.maybe_threshold:
        thresholds.append(schema.maybe_threshold)
    t = min(thresholds)
    return (probability_to_logit(t) + certified_dd_margin(plan)
            + _dd_threshold_slack(t))


# -- the dd rescore program ---------------------------------------------------


def _dd_map_probability(spec, sim, one):
    """Duke's probability map in dd, returning (p, one_minus_p).

    ``p`` mirrors the oracle's f64 expression ``(high-0.5)*sim^2 + 0.5``
    term for term (the dd constants are splits of the very f64
    intermediates the host computes), while ``one_minus_p`` uses the
    cancellation-free rearrangement ``0.5*(1-sim^2) + (1-high)*sim^2``
    so its RELATIVE accuracy survives ``high`` near 1 — the log of the
    complement is where a naive ``1 - p`` would burn the whole margin.
    """
    D = _dd()
    like = sim[0]
    half = D.const(0.5, like=like)
    ge05 = D.ge(sim, half)
    s2 = D.mul(sim, sim)
    hc = D.const(float(spec.high) - 0.5, like=like)
    p_hi = D.add(D.mul(hc, s2), half)
    omp_hi = D.add(
        D.mul(half, D.sub(one, s2)),
        D.mul(D.const(1.0 - float(spec.high), like=like), s2),
    )
    p_lo = D.const(float(spec.low), like=like)
    omp_lo = D.const(1.0 - float(spec.low), like=like)
    return D.where(ge05, p_hi, p_lo), D.where(ge05, omp_hi, omp_lo)


def _dd_levenshtein_sim(c1, l1, c2, l2, equal, *, dist=None):
    """Levenshtein similarity in dd from the exact integer distance."""
    D = _dd()
    if dist is None:
        if c1.shape[1] <= 32:
            dist = pw.levenshtein_distance_myers(c1, l1, c2, l2)
        else:
            dist = pw.levenshtein_distance(c1, l1, c2, l2)
    shorter = jnp.minimum(l1, l2)
    longer = jnp.maximum(l1, l2)
    dist = jnp.minimum(dist, shorter)
    one = D.from_f32(jnp.ones(dist.shape, jnp.float32))
    sim = D.sub(one, D.div(D.from_int(dist),
                           D.from_int(jnp.maximum(shorter, 1))))
    zero = ((longer - shorter) * 2 > shorter) | (shorter == 0)
    sim = D.where(zero, D.const(0.0, like=sim[0]), sim)
    return D.where(equal, one, sim)


# JW branch-guard half-width (see the soundness block above): far above
# the ~1e-12 dd + f64 evaluation noise of ``j``, far below the ~1e-7
# rational spacing of non-boundary j values — pairs inside it go to the
# host residue instead of trusting a branch both sides computed
# inexactly.  Two-sided ledger check: the guard must cover the ~20-op
# dd evaluation noise of ``j`` with two orders of slack (covers), AND
# stay an order under the worst rational spacing 1/(q_max * 3 * n^3) at
# the 64-char JW width cap with boundary-constant denominator q_max=10
# (0.5 = 1/2, 0.7 = 7/10) — widening it past that would flag pairs the
# spacing proof already certifies (below).
# dd-budget: _DD_JW_BRANCH_GUARD covers 100 * 20 * DD_EPS headroom 4 below 1 / (10 * 3 * 64**3) / 8
_DD_JW_BRANCH_GUARD = 1e-9


def _dd_jaro_winkler_sim(c1, l1, c2, l2, equal, cmp):
    """Jaro-Winkler in dd from the exact match/transposition counts.

    Returns (sim, branch_unsafe): pairs whose ``j`` sits inside the
    guard band of the 0.5 map split or the boost threshold cannot be
    certified (host f64 and dd may round an exactly-boundary ``j`` to
    opposite sides) and must take the host path.
    """
    D = _dd()
    m, t = pw.jaro_counts(c1, l1, c2, l2)
    prefix = pw.common_prefix_count(c1, c2, l1, l2,
                                    max_prefix=int(cmp.max_prefix))
    md = D.from_int(m)
    a = D.div(md, D.from_int(jnp.maximum(l1, 1)))
    b = D.div(md, D.from_int(jnp.maximum(l2, 1)))
    cpart = D.div(D.from_int(m - t), D.from_int(jnp.maximum(m, 1)))
    like = a[0]
    j = D.div(D.add(D.add(a, b), cpart), D.const(3.0, like=like))
    zero = (m == 0) | (l1 == 0) | (l2 == 0)
    j = D.where(zero, D.const(0.0, like=like), j)
    one = D.from_f32(jnp.ones_like(like))
    # oracle: j + prefix * prefix_scale * (1.0 - j), left-associated
    boosted = D.add(j, D.mul(
        D.mul(D.from_int(prefix), D.const(float(cmp.prefix_scale),
                                          like=like)),
        D.sub(one, j),
    ))
    boost_c = D.const(float(cmp.boost_threshold), like=like)
    sim = D.where(D.lt(j, boost_c), j, boosted)
    # the dd sub's hi word carries the (cancellation-exact) distance to
    # the branch constants at full small-magnitude f32 resolution
    guard = jnp.float32(_DD_JW_BRANCH_GUARD)
    near_map = jnp.abs(D.sub(j, D.const(0.5, like=like))[0]) < guard
    near_boost = jnp.abs(D.sub(j, boost_c)[0]) < guard
    unsafe = (near_map | near_boost) & ~equal & ~zero
    return D.where(equal, one, sim), unsafe


def _dd_set_sim(common, f1, f2, equal, *, formula):
    """Set-overlap similarity in dd from exact intersection counts."""
    D = _dd()
    c = D.from_int(common)
    if formula == "jaccard":
        sim = D.div(c, D.from_int(jnp.maximum(f1 + f2 - common, 1)))
    elif formula == "dice":
        sim = D.div(D.from_int(2 * common),
                    D.from_int(jnp.maximum(f1 + f2, 1)))
    else:
        sim = D.div(c, D.from_int(jnp.maximum(jnp.minimum(f1, f2), 1)))
    one = D.from_f32(jnp.ones(common.shape, jnp.float32))
    sim = D.where((f1 == 0) | (f2 == 0), D.const(0.0, like=sim[0]), sim)
    return D.where(equal, one, sim)


def _dd_property_sim(spec: "F.PropertyFeatureSpec", qf, cf,
                     pallas_ok: bool):
    """(dd sim, combo_valid, branch_unsafe | None) for one certified
    property, gathered layout ((Q, Vq, ...) queries x (Q, C, Vc, ...)
    candidates), flat combos.  ``branch_unsafe`` is non-None only for
    kinds with a multi-op similarity (Jaro-Winkler) whose boundary
    values need the runtime guard band."""
    D = _dd()
    expand = _pair_expand_gathered
    hh1, hh2 = expand(qf["hash_hi"], cf["hash_hi"])
    hl1, hl2 = expand(qf["hash_lo"], cf["hash_lo"])
    v1, v2 = expand(qf["valid"], cf["valid"])
    combo_valid = v1 & v2
    equal = (hh1 == hh2) & (hl1 == hl2) & combo_valid

    kind = spec.kind
    cmp = spec.comparator
    if kind == F.CHARS and isinstance(cmp, C.JaroWinkler):
        c1, c2 = expand(qf["chars"], cf["chars"])
        l1, l2 = expand(qf["length"], cf["length"])
        sim, branch_unsafe = _dd_jaro_winkler_sim(c1, l1, c2, l2, equal,
                                                  cmp)
        return sim, combo_valid, branch_unsafe
    if kind == F.CHARS:
        if (
            pallas_ok
            and qf["chars"].shape[1] == 1      # single value slot per side
            and cf["chars"].shape[2] == 1
            and qf["chars"].shape[2] <= 32
            and pk.pallas_enabled()
        ):
            # ride the existing gathered Myers Pallas tile kernel — the
            # dd path only needs its exact integer DISTANCE, the ratio
            # and map run in dd outside the kernel
            q = qf["valid"].shape[0]
            c = cf["valid"].shape[1]
            dist = pk.myers_distance_gathered(
                qf["chars"][:, 0], qf["length"][:, 0],
                cf["chars"][:, :, 0], cf["length"][:, :, 0],
            ).reshape(-1)
            l1 = jnp.broadcast_to(
                qf["length"][:, None, 0], (q, c)).reshape(-1)
            l2 = cf["length"][:, :, 0].reshape(-1)
            return (_dd_levenshtein_sim(None, l1, None, l2, equal,
                                        dist=dist), combo_valid,
                    None)
        c1, c2 = expand(qf["chars"], cf["chars"])
        l1, l2 = expand(qf["length"], cf["length"])
        return (_dd_levenshtein_sim(c1, l1, c2, l2, equal), combo_valid,
                None)
    if kind == F.GRAM_SET:
        g1, g2 = expand(qf["grams"], cf["grams"])
        n1, n2 = expand(qf["gram_count"], cf["gram_count"])
        common = pw.set_intersection_count(g1, n1, g2, n2)
        return _dd_set_sim(common, n1, n2, equal,
                           formula=cmp.formula), combo_valid, None
    if kind == F.TOKEN_SET:
        t1, t2 = expand(qf["tokens"], cf["tokens"])
        n1, n2 = expand(qf["token_count"], cf["token_count"])
        formula = "dice" if isinstance(cmp, C.DiceCoefficient) else "jaccard"
        common = pw.set_intersection_count(t1, n1, t2, n2)
        return _dd_set_sim(common, n1, n2, equal,
                           formula=formula), combo_valid, None
    if kind == F.HASH:
        one = D.from_f32(jnp.ones(equal.shape, jnp.float32))
        zero = D.const(0.0, like=one[0])
        if isinstance(cmp, C.Different):
            return D.where(equal, zero, one), combo_valid, None
        return D.where(equal, one, zero), combo_valid, None
    if kind == F.PHONETIC:
        ch1, ch2 = expand(qf["code_hi"], cf["code_hi"])
        cl1, cl2 = expand(qf["code_lo"], cf["code_lo"])
        cv1, cv2 = expand(qf["code_valid"], cf["code_valid"])
        one = D.from_f32(jnp.ones(equal.shape, jnp.float32))
        code_eq = (ch1 == ch2) & (cl1 == cl2) & cv1 & cv2
        sim = D.where(code_eq, D.const(0.9, like=one[0]),
                      D.const(0.0, like=one[0]))
        return D.where(equal, one, sim), combo_valid, None
    raise ValueError(  # pragma: no cover - dd_certifiable_spec gates kinds
        f"no dd kernel for feature kind {kind!r}")


# The oracle's clamp rails (core.bayes.probability_logit): pairs whose
# best probability clamps reproduce the host's exact f64 logit constant.
_DD_EPS_P = 1e-10


def _dd_property_logit(spec, qf, cf, q: int, c: int, pallas_ok: bool):
    """One certified property's clamped log-odds in dd plus its
    branch-unsafety: (((Q, C) hi, lo), (Q, C) bool).

    Mirrors ``_property_logit`` — max over value-pair combos in
    probability space, then the clamped logit — with every float step in
    dd and the clamp rails emitting the oracle's own f64 constants.  A
    pair is branch-unsafe when ANY of its valid combos carries a
    branch-guard flag (conservative: a flagged non-best combo still
    flags the pair — the best-combo fold itself is only dd-accurate).
    """
    D = _dd()
    sim, combo_valid, branch_unsafe = _dd_property_sim(spec, qf, cf,
                                                       pallas_ok)
    one = D.from_f32(jnp.ones_like(sim[0]))
    p, omp = _dd_map_probability(spec, sim, one)
    # fold the combo axis: max in probability space, carrying the
    # matching complement (combo count is small and static — unrolled)
    ncombo = sim[0].shape[0] // (q * c)
    p3 = (p[0].reshape(q, c, ncombo), p[1].reshape(q, c, ncombo))
    omp3 = (omp[0].reshape(q, c, ncombo), omp[1].reshape(q, c, ncombo))
    valid3 = combo_valid.reshape(q, c, ncombo)
    neg = D.const(-1.0, like=p3[0][:, :, 0])
    best_p = neg
    best_omp = D.const(1.0, like=neg[0])
    for i in range(ncombo):
        pi = (p3[0][:, :, i], p3[1][:, :, i])
        oi = (omp3[0][:, :, i], omp3[1][:, :, i])
        take = valid3[:, :, i] & D.lt(best_p, pi)
        best_p = D.where(take, pi, best_p)
        best_omp = D.where(take, oi, best_omp)
    any_valid = valid3.any(axis=2)

    like = best_p[0]
    eps = D.const(_DD_EPS_P, like=like)
    ome = D.const(1.0 - _DD_EPS_P, like=like)
    below = D.le(best_p, eps)
    above = D.ge(best_p, ome)
    pc = D.clamp(best_p, eps, ome)
    # complement floor far below the real rail: rail lanes are overridden
    # with the oracle's exact constants right after, this only keeps the
    # division finite
    ompc = D.clamp(best_omp, D.const(1e-12, like=like),
                   D.const(1.0, like=like))
    logit = D.log(D.div(pc, ompc))
    logit = D.where(above, D.const(probability_to_logit(1.0), like=like),
                    logit)
    logit = D.where(below, D.const(probability_to_logit(0.0), like=like),
                    logit)
    zero = D.const(0.0, like=like)
    if branch_unsafe is None:
        unsafe_qc = jnp.zeros((q, c), bool)
    else:
        unsafe_qc = (branch_unsafe.reshape(q, c, ncombo)
                     & valid3).any(axis=2)
    return D.where(any_valid, logit, zero), unsafe_qc


def _dd_unsafe_mask(spec, qf, cf, *, value_slots_cap: int) -> jnp.ndarray:
    """(Q, C) bool: pairs whose tensors MAY have truncated the records.

    Certification needs the device counts to be the counts of the FULL
    record values; the padded layout truncates in three places — value
    slots past the auto-growth cap, char widths at the per-property
    width, set sizes at the gram/token tensor width.  The tensors carry
    the evidence conservatively: a saturated slot (length == width,
    count == capacity, all value slots valid at the cap) may or may not
    have truncated, so it flags the pair into the host-rescore residue
    (reason="truncation").  False positives (a value exactly at the
    width) cost one host compare; false negatives cannot happen.
    """
    def side(f):
        valid = f["valid"]
        u = jnp.zeros(valid.shape[:-1], bool)
        if value_slots_cap and valid.shape[-1] >= value_slots_cap:
            u = u | valid.all(axis=-1)
        if spec.kind == F.CHARS:
            width = f["chars"].shape[-1]
            u = u | ((f["length"] >= width) & valid).any(axis=-1)
        elif spec.kind == F.GRAM_SET:
            cap = f["grams"].shape[-1]
            u = u | ((f["gram_count"] >= cap) & valid).any(axis=-1)
        elif spec.kind == F.TOKEN_SET:
            cap = f["tokens"].shape[-1]
            u = u | ((f["token_count"] >= cap) & valid).any(axis=-1)
        return u

    uq = side(qf)                # (Q,)
    uc = side(cf)                # (Q, C)
    return uq[:, None] | uc


def build_dd_rescorer(plan: "F.SchemaFeatures", *,
                      queries_from_rows: bool = True,
                      value_slots_cap: int = 0,
                      pallas_ok: bool = True):
    """The jitted survivor dd-rescore program, or None when no property
    is dd-certifiable.

    Signature::

        fn(qfeats, corpus_feats, query_row, top_index)
          -> (logit_hi (Q, K) f32, logit_lo (Q, K) f32, unsafe (Q, K) bool)

    ``top_index`` is the resolved block's (Q, K) global candidate rows
    (-1 padding gathers row 0, results ignored by the caller);
    ``qfeats`` is ``{}`` under ``queries_from_rows`` (query features
    gather on device from the corpus at ``query_row``, the same
    convention as ``build_corpus_scorer``).  ``logit_hi + logit_lo``
    (summed in f64 on host — exact for a float32 pair) is the dd logit
    over the dd-certifiable device properties; ``unsafe`` marks pairs
    whose tensors may have truncated the records (``_dd_unsafe_mask``).

    Rides ``rescore_retrieved``'s gathered layout: candidate k of query
    q is a specific corpus row, and the dominant single-value CHARS
    shape rides the existing gathered Myers Pallas kernel for its
    integer distance.
    """
    specs = dd_plan_specs(plan)
    if not specs:
        return None
    D = _dd()

    @jax.jit
    def rescore(qfeats, corpus_feats, query_row, top_index):
        q, k = top_index.shape
        rows = jnp.clip(top_index, 0).reshape(-1)
        if queries_from_rows:
            qrows = jnp.clip(query_row, 0)
            qfeats_l = {
                spec.name: {
                    name: jnp.take(arr, qrows, axis=0)
                    for name, arr in corpus_feats[spec.name].items()
                }
                for spec in specs
            }
        else:
            qfeats_l = qfeats
        total = (jnp.zeros((q, k), jnp.float32),
                 jnp.zeros((q, k), jnp.float32))
        unsafe = jnp.zeros((q, k), bool)
        for spec in specs:
            cf = {
                name: jnp.take(arr, rows, axis=0).reshape(
                    (q, k) + arr.shape[1:]
                )
                for name, arr in corpus_feats[spec.name].items()
            }
            qf = qfeats_l[spec.name]
            prop_logit, branch_unsafe = _dd_property_logit(
                spec, qf, cf, q, k, pallas_ok
            )
            total = D.add(total, prop_logit)
            unsafe = unsafe | branch_unsafe | _dd_unsafe_mask(
                spec, qf, cf, value_slots_cap=value_slots_cap
            )
        return total[0], total[1], unsafe

    return rescore


# Process-wide memo of built dd rescorers by plan VALUE fingerprint: many
# workloads (and, in the test suite, many short-lived indexes) share one
# schema shape, and each jitted instance pays its own XLA compiles —
# sharing one instance turns that into per-unique-(plan, shape) compiles
# for the whole process.  Deliberately LOCK-FREE (ISSUE 12: the dd
# rescore introduces no new lock): a concurrent miss builds twice and
# one instance wins the dict slot — benign, the loser is just an extra
# tracing.  Bounded FIFO like engine.explain's per-plan cache.
_DD_CACHE: Dict[tuple, object] = {}
_DD_CACHE_CAP = 64


def _dd_plan_key(plan: "F.SchemaFeatures", extra: tuple) -> tuple:
    key = [extra]
    for s in dd_plan_specs(plan):
        cmp = s.comparator
        key.append((
            s.name, s.kind, float(s.low), float(s.high), s.v, s.chars,
            s.grams, type(cmp).__name__,
            getattr(cmp, "formula", None),
            float(getattr(cmp, "prefix_scale", 0.0)),
            float(getattr(cmp, "boost_threshold", 0.0)),
            int(getattr(cmp, "max_prefix", 0)),
        ))
    return tuple(key)


def dd_rescorer(plan: "F.SchemaFeatures", *, queries_from_rows: bool = True,
                value_slots_cap: int = 0, pallas_ok: bool = True):
    """Memoized ``build_dd_rescorer`` (None when nothing is certifiable)."""
    specs = dd_plan_specs(plan)
    if not specs:
        return None
    key = _dd_plan_key(plan, (queries_from_rows, value_slots_cap, pallas_ok))
    fn = _DD_CACHE.get(key)
    if fn is None:
        fn = build_dd_rescorer(
            plan, queries_from_rows=queries_from_rows,
            value_slots_cap=value_slots_cap, pallas_ok=pallas_ok,
        )
        if len(_DD_CACHE) >= _DD_CACHE_CAP:
            _DD_CACHE.pop(next(iter(_DD_CACHE)))
        _DD_CACHE[key] = fn
    return fn


# -- per-property pair similarity -------------------------------------------


def _pair_expand(qa: jnp.ndarray, ca: jnp.ndarray) -> tuple:
    """(Q, Vq, ...) x (C, Vc, ...) -> flat (Q*C*Vq*Vc, ...) pair operands.

    The value axes may differ: an http-transform query can carry more
    values than any indexed record, and its extra slots ride a wider query
    tensor instead of forcing a corpus rebuild (engine.device_matcher).
    """
    q, vq = qa.shape[0], qa.shape[1]
    c, vc = ca.shape[0], ca.shape[1]
    rq = qa.shape[2:]
    rc = ca.shape[2:]
    a = jnp.broadcast_to(qa[:, None, :, None], (q, c, vq, vc) + rq)
    b = jnp.broadcast_to(ca[None, :, None, :], (q, c, vq, vc) + rc)
    return (a.reshape((q * c * vq * vc,) + rq),
            b.reshape((q * c * vq * vc,) + rc))


def _pair_expand_gathered(qa: jnp.ndarray, ca: jnp.ndarray) -> tuple:
    """(Q, Vq, ...) x gathered (Q, C, Vc, ...) -> flat (Q*C*Vq*Vc, ...).

    The per-query candidate axis is already aligned (candidate row c of
    query q, not a corpus cross product) — used by the ANN rescoring stage.
    """
    q, vq = qa.shape[0], qa.shape[1]
    c, vc = ca.shape[1], ca.shape[2]
    rq = qa.shape[2:]
    rc = ca.shape[3:]
    a = jnp.broadcast_to(qa[:, None, :, None], (q, c, vq, vc) + rq)
    b = jnp.broadcast_to(ca[:, :, None, :], (q, c, vq, vc) + rc)
    return (a.reshape((q * c * vq * vc,) + rq),
            b.reshape((q * c * vq * vc,) + rc))


def _tiled_combo_sim(tile_fn, q: int, c: int, vq: int, vc: int,
                     equal) -> jnp.ndarray:
    """Shared value-combo scaffold for the Pallas tile branches: run a
    (Q, C) tile kernel per (query-value, corpus-value) slot pair and stack
    into the flat (Q*C*Vq*Vc,) layout ``_pair_expand`` produces."""
    eq4 = equal.reshape(q, c, vq, vc)
    rows = []
    for a in range(vq):
        cols = [tile_fn(a, b, eq4[:, :, a, b]) for b in range(vc)]
        rows.append(jnp.stack(cols, axis=-1))         # (Q, C, Vc)
    return jnp.stack(rows, axis=-2).reshape(-1)       # (Q, C, Vq, Vc)


def _property_sim(spec: F.PropertyFeatureSpec, qf: Dict, cf: Dict,
                  expand=_pair_expand, pallas_ok: bool = True,
                  gathered: bool = False) -> tuple:
    """Pair similarity for one property.

    Returns (sim, combo_valid), both flat (Q*C*V*V,).  ``gathered`` marks
    the aligned-candidate layout (cf tensors are (Q, C, V, ...) gathered
    rows, not a corpus cross product) — it selects the gathered Pallas
    branch and disables the cross-product tile branches.
    """
    hh1, hh2 = expand(qf["hash_hi"], cf["hash_hi"])
    hl1, hl2 = expand(qf["hash_lo"], cf["hash_lo"])
    v1, v2 = expand(qf["valid"], cf["valid"])
    combo_valid = v1 & v2
    equal = (hh1 == hh2) & (hl1 == hl2) & combo_valid

    kind = spec.kind
    cmp = spec.comparator
    if (
        gathered
        and pallas_ok
        and kind == F.CHARS
        and not isinstance(cmp, C.JaroWinkler)
        and qf["chars"].shape[1] == 1      # single value slot per side —
        and cf["chars"].shape[2] == 1      # the dominant rescoring shape
        and qf["chars"].shape[2] <= 32
        and pk.pallas_enabled()
    ):
        # ANN rescoring path: candidate chars ride VMEM tiles with the
        # candidate axis on lanes (per-pair text), instead of the flat
        # XLA kernels over expanded (Q*C, L) HBM operands
        q = qf["valid"].shape[0]
        c = cf["valid"].shape[1]
        sim = pk.levenshtein_sim_gathered(
            qf["chars"][:, 0], qf["length"][:, 0],
            cf["chars"][:, :, 0], cf["length"][:, :, 0],
            equal.reshape(q, c),
        ).reshape(-1)
        return sim, combo_valid
    if (
        not gathered
        and
        pallas_ok
        and kind == F.CHARS
        # Levenshtein rides the N-word Myers kernels up to MYERS_MAX_CHARS
        # (256); the Jaro-Winkler tile kernel is single-word bitmask only
        and qf["chars"].shape[2]
        <= (32 if isinstance(cmp, C.JaroWinkler) else pk.MYERS_MAX_CHARS)
        and pk.pallas_enabled()
    ):
        # Pallas tiled path: (TQ, TC) similarity tiles computed in VMEM
        # from O(T*L) operands — no expanded (Q*C, L) pair arrays in HBM.
        if isinstance(cmp, C.JaroWinkler):
            def tile(a, b, eq):
                return pk.jaro_winkler_sim_tiles(
                    qf["chars"][:, a], qf["length"][:, a],
                    cf["chars"][:, b], cf["length"][:, b], eq,
                    prefix_scale=cmp.prefix_scale,
                    boost_threshold=cmp.boost_threshold,
                    max_prefix=int(cmp.max_prefix),
                )
        else:
            def tile(a, b, eq):
                return pk.levenshtein_sim_tiles(
                    qf["chars"][:, a], qf["length"][:, a],
                    cf["chars"][:, b], cf["length"][:, b], eq,
                )
        sim = _tiled_combo_sim(
            tile,
            qf["valid"].shape[0], cf["valid"].shape[0],
            qf["chars"].shape[1], cf["chars"].shape[1], equal,
        )
        return sim, combo_valid
    if (
        not gathered
        and pallas_ok
        and kind in (F.GRAM_SET, F.TOKEN_SET)
        # width guard (mirrors the chars branch's L <= 32): the tile
        # kernel's inner loop unrolls O(G), so a huge DEVICE_MAX_GRAMS /
        # DEVICE_MAX_TOKENS falls back to the flat XLA kernels instead of
        # silently emitting an enormous Mosaic program
        and qf["grams" if kind == F.GRAM_SET else "tokens"].shape[2] <= 256
        and pk.pallas_enabled()
    ):
        # Pallas tiled path: (TQ, TC) intersection tiles in VMEM from
        # O(T*G) operands — no expanded (Q*C, G) pair arrays in HBM.
        if kind == F.GRAM_SET:
            gk, nk, formula = "grams", "gram_count", cmp.formula
        else:
            gk, nk = "tokens", "token_count"
            formula = "dice" if isinstance(cmp, C.DiceCoefficient) else "jaccard"
        sim = _tiled_combo_sim(
            lambda a, b, eq: pk.set_sim_tiles(
                qf[gk][:, a], qf[nk][:, a],
                cf[gk][:, b], cf[nk][:, b], eq, formula=formula,
            ),
            qf["valid"].shape[0], cf["valid"].shape[0],
            qf["valid"].shape[1], cf["valid"].shape[1], equal,
        )
        return sim, combo_valid
    if kind == F.CHARS:
        c1, c2 = expand(qf["chars"], cf["chars"])
        l1, l2 = expand(qf["length"], cf["length"])
        if isinstance(cmp, C.JaroWinkler):
            sim = pw.jaro_winkler_sim(
                c1, l1, c2, l2, equal,
                prefix_scale=cmp.prefix_scale,
                boost_threshold=cmp.boost_threshold,
                max_prefix=int(cmp.max_prefix),
            )
        else:
            sim = pw.levenshtein_sim(c1, l1, c2, l2, equal)
    elif kind == F.CHARS_WEIGHTED:
        c1, c2 = expand(qf["chars"], cf["chars"])
        k1, k2 = expand(qf["classes"], cf["classes"])
        l1, l2 = expand(qf["length"], cf["length"])
        sim = pw.weighted_levenshtein_sim(
            c1, k1, l1, c2, k2, l2, equal,
            digit_weight=cmp.digit_weight,
            letter_weight=cmp.letter_weight,
            other_weight=cmp.other_weight,
        )
    elif kind == F.GRAM_SET:
        g1, g2 = expand(qf["grams"], cf["grams"])
        n1, n2 = expand(qf["gram_count"], cf["gram_count"])
        sim = pw.qgram_sim(g1, n1, g2, n2, equal, formula=cmp.formula)
    elif kind == F.TOKEN_SET:
        t1, t2 = expand(qf["tokens"], cf["tokens"])
        n1, n2 = expand(qf["token_count"], cf["token_count"])
        sim = pw.token_set_sim(
            t1, n1, t2, n2, equal, dice=isinstance(cmp, C.DiceCoefficient)
        )
    elif kind == F.HASH:
        sim = (
            pw.different_sim(equal)
            if isinstance(cmp, C.Different)
            else pw.exact_sim(equal)
        )
    elif kind == F.PHONETIC:
        ch1, ch2 = expand(qf["code_hi"], cf["code_hi"])
        cl1, cl2 = expand(qf["code_lo"], cf["code_lo"])
        cv1, cv2 = expand(qf["code_valid"], cf["code_valid"])
        sim = pw.phonetic_sim(equal, (ch1 == ch2) & (cl1 == cl2), cv1 & cv2)
    elif kind == F.NUMERIC:
        d1, d2 = expand(qf["number"], cf["number"])
        nv1, nv2 = expand(qf["number_valid"], cf["number_valid"])
        sim = pw.numeric_sim(d1, nv1, d2, nv2, min_ratio=cmp.min_ratio)
    elif kind == F.GEO:
        la1, la2 = expand(qf["lat"], cf["lat"])
        lo1, lo2 = expand(qf["lon"], cf["lon"])
        gv1, gv2 = expand(qf["geo_valid"], cf["geo_valid"])
        sim = pw.geoposition_sim(
            la1, lo1, gv1, la2, lo2, gv2, max_distance=cmp.max_distance
        )
    else:  # pragma: no cover - plan() never emits unknown kinds
        raise ValueError(f"no device kernel for feature kind {kind!r}")
    return sim, combo_valid


def _property_logit(spec: F.PropertyFeatureSpec, qf: Dict, cf: Dict,
                    q: int, c: int, expand=_pair_expand,
                    pallas_ok: bool = True,
                    gathered: bool = False) -> jnp.ndarray:
    """Per-pair clamped log-odds contribution of one property: (Q, C) f32.

    Duke's PropertyImpl.compare map (core.records.Property.compare_probability):
    sim >= 0.5 -> (high-0.5)*sim^2 + 0.5, else -> low; properties missing on
    either side are neutral (prob 0.5 -> logit 0).  Max over value-pair
    combos is taken in probability space — the map is applied per combo, so
    semantics match the host engine even for low > 0.5 configs.
    """
    sim, combo_valid = _property_sim(spec, qf, cf, expand, pallas_ok,
                                     gathered)
    prob = jnp.where(
        sim >= 0.5, (spec.high - 0.5) * sim * sim + 0.5, jnp.float32(spec.low)
    )
    prob = jnp.where(combo_valid, prob, -1.0)
    # the trailing (Vq*Vc) combo axis folds away; Vq may differ from Vc
    prob4 = prob.reshape(q, c, -1)
    valid4 = combo_valid.reshape(q, c, -1)
    best = prob4.max(axis=2)
    any_valid = valid4.any(axis=2)
    best = jnp.where(any_valid, best, 0.5)
    best = jnp.clip(best, _EPS, 1.0 - _EPS)
    return jnp.log(best) - jnp.log1p(-best)


def build_pair_logits(plan: F.SchemaFeatures) -> Callable:
    """Returns fn(qfeats, cfeats) -> (Q, C) partial logit over device props."""

    specs = list(plan.device_props)

    def pair_logits(qfeats: Dict[str, Dict], cfeats: Dict[str, Dict]) -> jnp.ndarray:
        first = next(iter(qfeats.values()))
        q = first["valid"].shape[0]
        firstc = next(iter(cfeats.values()))
        c = firstc["valid"].shape[0]
        total = jnp.zeros((q, c), jnp.float32)
        for spec in specs:
            total = total + _property_logit(
                spec, qfeats[spec.name], cfeats[spec.name], q, c
            )
        return total

    return pair_logits


def build_property_logits(plan: F.SchemaFeatures) -> Callable:
    """The ``explain=True`` variant of ``build_pair_logits``: returns
    fn(qfeats, cfeats) -> (Q, C, P) with the PER-PROPERTY clamped
    log-odds vector kept un-reduced (axis P follows
    ``plan.device_props`` order).  Sums over P to the same pair logit
    the fast path computes — same kernels, same probability map, same
    clamps — but lives as a SEPARATE builder so the jitted fast path
    (``build_pair_logits``/``scan_topk``) is never perturbed by explain
    traffic.  Pallas tile branches are disabled (``pallas_ok=False``):
    explain calls score a handful of pairs, where the flat XLA kernels
    avoid compiling Mosaic programs for one-off shapes.

    Used by the decision-explainability layer (engine.explain) to
    reproduce a pair's device f32 verdict with per-property provenance.
    """

    specs = list(plan.device_props)

    def property_logits(qfeats: Dict[str, Dict],
                        cfeats: Dict[str, Dict]) -> jnp.ndarray:
        first = next(iter(qfeats.values()))
        q = first["valid"].shape[0]
        firstc = next(iter(cfeats.values()))
        c = firstc["valid"].shape[0]
        per_prop = [
            _property_logit(spec, qfeats[spec.name], cfeats[spec.name],
                            q, c, pallas_ok=False)
            for spec in specs
        ]
        return jnp.stack(per_prop, axis=-1)  # (Q, C, P)

    return property_logits


def candidate_mask(cvalid, cdeleted, cgroup, cidx, query_group, query_row,
                   group_filtering: bool):
    """(Q, chunk) candidate-eligibility mask shared by every retrieval path.

    Policy (one place, so brute-force and ANN retrieval can never diverge):
    live non-tombstoned rows only; linkage excludes same-group rows
    (IncrementalLuceneDatabase.java:467-475); a query never matches its own
    corpus row.

    One other site encodes this same policy and must stay in sync: the
    fused Pallas retrieval mask (ops.encoder._fused_retrieval /
    ops.pallas_kernels._retrieval_segmax_kernel), which packs it into an
    int8 per-row encoding because a Mosaic kernel cannot consume the
    boolean columns directly.
    """
    mask = cvalid & ~cdeleted
    if group_filtering:
        mask = mask & (cgroup[None, :] != query_group[:, None])
    return mask & (cidx[None, :] != query_row[:, None])


def candidate_mask_gathered(gvalid, gdeleted, ggroup, grows, query_group,
                            query_row, group_filtering: bool):
    """``candidate_mask`` for ALIGNED gathered candidates: all operands
    are (Q, S) per-query gathers (IVF probe scan, ops.ivf) plus the
    global row ids ``grows`` (-1 for padding slots).  Same policy, same
    one place: live non-tombstoned, group exclusion, self-row exclusion
    — plus the padding-slot exclusion the gathered layout introduces."""
    mask = (grows >= 0) & gvalid & ~gdeleted
    if group_filtering:
        mask = mask & (ggroup != query_group[:, None])
    return mask & (grows != query_row[:, None])


def retrieval_amb_eps(q_tree, emb_tree):
    """Quantization ambiguity credit for the recall-escalation trigger:
    the certified per-block cosine error bound under int8 storage
    (``ops.encoder.int8_cosine_eps_dynamic`` — derived from the block's
    ACTUAL row scales), or None for float storage (where the trigger
    stays exactly the pre-int8 predicate)."""
    from . import encoder as E

    if E.is_int8_tree(emb_tree):
        return E.int8_cosine_eps_dynamic(q_tree, emb_tree)
    return None


def saturation_count(logits, top_sim, retrieved, min_logit, amb_eps):
    """ONE copy of the escalation-count predicate shared by every
    retrieval tail (single-device flat/IVF and the per-shard sharded
    tails): above-``min_logit`` candidates, plus — under int8 storage —
    the quantization-ambiguity credit.

    ``amb_eps`` (None for float storage) widens the saturation trigger:
    when the retrieved set is FULL, a candidate whose retrieval cosine
    sits within ``2 * amb_eps`` of the top-C cutoff AND whose exact
    rescore clears the pruning bound counts as saturation evidence a
    second time — a true candidate displaced by quantization error (the
    dropped one's exact cosine can exceed the cutoff by at most 2*eps)
    is cosine-adjacent to exactly these band members, and if they matter
    after rescoring, the dropped neighbor could too, so the search
    escalates instead of silently eating recall.  The above-bound
    conjunct is what keeps the credit a *saturation* signal and not a
    tail-density detector: it reasons from rescored evidence, the same
    way the original "every retrieved candidate cleared the bound"
    predicate does — a dense cosine tail of non-matches at the cutoff
    (the common no-match query) takes no credit and cannot ladder
    (measured: the unconditioned band escalated routinely on the
    stresstest corpus; this form matches the bf16 path's zero).  With
    the credit absent (or eps 0) this is bit-identical to the pre-int8
    predicate (no retrieved cosine is strictly below the cutoff).  A
    non-full retrieved set means retrieval never truncated, so no
    ambiguity credit applies (and tiny corpora cannot trigger pointless
    escalation ladders)."""
    import jax.numpy as jnp

    above = logits > min_logit
    count = above.sum(axis=1).astype(jnp.int32)
    if amb_eps is not None:
        full = retrieved.all(axis=1)
        cutoff = top_sim[:, -1:]  # sorted desc: the smallest retrieved
        amb = ((top_sim < cutoff + 2.0 * amb_eps)
               & retrieved & above).sum(axis=1).astype(jnp.int32)
        count = count + jnp.where(full, amb, 0)
    return count


def rescore_retrieved(pair_logits, qfeats, corpus_feats, top_sim, top_index,
                      min_logit, *, amb_eps=None):
    """The shared tail of every two-stage retrieval program (flat ANN and
    IVF): gather the retrieved rows' feature tensors, score them with the
    exact per-property kernels, and derive the escalation count
    (``saturation_count`` — ``amb_eps`` documented there)."""
    import jax.numpy as jnp

    retrieved = top_index >= 0
    top_c = top_index.shape[1]
    rows = jnp.clip(top_index, 0).reshape(-1)
    q = top_index.shape[0]
    cfeats = {
        prop: {
            name: jnp.take(arr, rows, axis=0).reshape(
                (q, top_c) + arr.shape[1:]
            )
            for name, arr in tensors.items()
        }
        for prop, tensors in corpus_feats.items()
    }
    logits = pair_logits(qfeats, cfeats)
    logits = jnp.where(retrieved, logits, NEG_INF)
    count = saturation_count(logits, top_sim, retrieved, min_logit, amb_eps)
    return logits, top_index, count


def build_gathered_pair_logits(plan: F.SchemaFeatures) -> Callable:
    """Returns fn(qfeats (Q,...), cfeats gathered (Q, C, ...)) -> (Q, C).

    The aligned-candidate variant of ``build_pair_logits`` used by the ANN
    rescoring stage: candidate c of query q is a specific gathered corpus
    row, not a cross product.  Levenshtein single-value properties ride
    the gathered Pallas Myers kernel (candidate axis on lanes); other
    kinds use the flat XLA kernels — the pair count here is Q*C, already
    pruned by retrieval.
    """
    specs = list(plan.device_props)

    def pair_logits(qfeats: Dict[str, Dict], cfeats: Dict[str, Dict]) -> jnp.ndarray:
        first = next(iter(cfeats.values()))
        q, c = first["valid"].shape[0], first["valid"].shape[1]
        total = jnp.zeros((q, c), jnp.float32)
        for spec in specs:
            total = total + _property_logit(
                spec, qfeats[spec.name], cfeats[spec.name], q, c,
                expand=_pair_expand_gathered, gathered=True,
            )
        return total

    return pair_logits


def build_ann_scorer(
    plan: F.SchemaFeatures,
    *,
    chunk: int = 512,
    top_c: int = 64,
    group_filtering: bool = False,
    queries_from_rows: bool = False,
) -> Callable:
    """Two-stage ANN scoring program: cosine retrieval + exact rescoring.

    Stage 1 ranks the whole corpus by embedding cosine (ops.encoder — one
    bf16 matmul per chunk, MXU) keeping the top ``top_c`` rows per query;
    stage 2 gathers those rows' feature tensors and scores them with the
    exact per-property kernels.  Returned logits are therefore on the same
    scale (and with the same host-property bound semantics) as
    ``build_corpus_scorer`` — only the candidate *set* is approximate.

    Signature::

        fn(q_emb, qfeats, corpus_emb, corpus_feats, corpus_valid,
           corpus_deleted, corpus_group, query_group, query_row, min_logit)
        -> (top_logit (Q, C), top_index (Q, C), count_above (Q,))

    ``count_above`` saturating at ``top_c`` signals the caller to escalate C
    (recall escalation — the ANN analogue of the brute-force K-escalation).
    Under int8 embedding storage (DUKE_EMB_INT8) the count additionally
    credits quantization-ambiguous candidates at the retrieval cutoff —
    see ``rescore_retrieved``.

    ``corpus_emb`` (and ``q_emb`` when not from rows) accept the
    ANN_PROP tensor dict — ``{emb}`` for bf16 storage, ``{emb, scale}``
    for int8 — or a bare bf16 matrix (legacy convention).

    ``queries_from_rows``: as in ``build_corpus_scorer`` — ``q_emb`` and
    ``qfeats`` are ignored (pass empty placeholders) and both are gathered
    on device from the corpus at ``query_row``.
    """
    from . import encoder as E

    pair_logits = build_gathered_pair_logits(plan)

    @jax.jit
    def score(q_emb, qfeats, corpus_emb, corpus_feats, corpus_valid,
              corpus_deleted, corpus_group, query_group, query_row,
              min_logit):
        emb_tree = E.as_emb_tree(corpus_emb)
        if queries_from_rows:
            qrows = jnp.clip(query_row, 0)
            q_tree = {
                name: jnp.take(arr, qrows, axis=0)
                for name, arr in emb_tree.items()
            }
            qfeats = gather_rows(corpus_feats, qrows)
        else:
            q_tree = E.as_emb_tree(q_emb)
        top_sim, top_index = E.retrieval_scan(
            q_tree, emb_tree, corpus_valid, corpus_deleted, corpus_group,
            query_group, query_row,
            chunk=chunk, top_c=top_c, group_filtering=group_filtering,
        )
        return rescore_retrieved(
            pair_logits, qfeats, corpus_feats, top_sim, top_index,
            min_logit, amb_eps=retrieval_amb_eps(q_tree, emb_tree),
        )

    return score


# -- the blockwise corpus scorer --------------------------------------------


@dataclass
class ScoreResult:
    """Top-K device scores for a query block (numpy, already fetched)."""

    top_logit: np.ndarray   # (Q, K) partial device logit, NEG_INF when empty
    top_index: np.ndarray   # (Q, K) corpus row index
    count_above: np.ndarray  # (Q,) candidates whose optimistic prob clears min threshold


def live_chunks(corpus_valid, chunk: int):
    """Scan chunks up to the corpus's live high-water mark: ceil((last
    index where ``corpus_valid`` is True, + 1) / chunk), 0 when no row is
    valid.  A traced int32 scalar, so the trip count it bounds never
    changes a compiled shape.  The host keeps the same number per corpus
    (``engine.device_matcher.DeviceCorpus.live_chunks``)."""
    cap = corpus_valid.shape[0]
    rows = jnp.max(jnp.where(corpus_valid,
                             jnp.arange(1, cap + 1, dtype=jnp.int32), 0))
    return (rows + (chunk - 1)) // chunk


def scan_topk(
    pair_logits: Callable,
    qfeats,
    corpus_feats,
    corpus_valid,
    corpus_deleted,
    corpus_group,
    query_group,
    query_row,
    min_logit,
    *,
    chunk: int,
    top_k: int,
    group_filtering: bool,
    row_offset=0,
    init=None,
    live_bound: bool = False,
):
    """The blockwise scan core: scores Q queries against a (local) corpus.

    ``row_offset`` maps local corpus rows to global row ids — 0 on a single
    device; ``shard_index * shard_capacity`` inside ``shard_map`` (see
    parallel.sharded), so self-exclusion via ``query_row`` and the returned
    ``top_index`` stay global.  Traced (non-static) offsets are fine.

    ``init`` seeds the running (top_logit, top_index, count) carry — the
    ring scorer (parallel.ring) threads a query block's accumulated top-K
    through successive corpus shards with it.

    ``live_bound`` stops the scan after the last chunk holding a valid row
    (``live_chunks``) instead of covering the whole capacity.  The rows it
    skips are ones ``candidate_mask`` drops, which add nothing to the count
    and, since ``lax.top_k`` keeps the lower position on ties and every
    empty carry slot already holds (NEG_INF, -1), nothing to the top-K: the
    outputs are bit-identical to the full scan.  The single-device scorer
    sets it; the mesh scorers keep the full scan, since rows fill shard 0
    first and the last shard's full scan would set the pace anyway.
    """
    first = next(iter(qfeats.values()))
    q = first["valid"].shape[0]
    cap = corpus_valid.shape[0]
    nchunks = live_chunks(corpus_valid, chunk) if live_bound else cap // chunk

    if init is not None:
        init_logit, init_index, init_count = init
    else:
        init_logit = jnp.full((q, top_k), NEG_INF, jnp.float32)
        init_index = jnp.full((q, top_k), -1, jnp.int32)
        init_count = jnp.zeros((q,), jnp.int32)

    def body(ci, carry):
        top_logit, top_index, count = carry
        start = ci * chunk
        cf = jax.tree_util.tree_map(
            lambda a: lax.dynamic_slice_in_dim(a, start, chunk, axis=0),
            corpus_feats,
        )
        logits = pair_logits(qfeats, cf)  # (Q, chunk)

        cvalid = lax.dynamic_slice_in_dim(corpus_valid, start, chunk)
        cdel = lax.dynamic_slice_in_dim(corpus_deleted, start, chunk)
        cgroup = lax.dynamic_slice_in_dim(corpus_group, start, chunk)
        cidx = row_offset + start + jnp.arange(chunk, dtype=jnp.int32)

        mask = candidate_mask(
            cvalid, cdel, cgroup, cidx, query_group, query_row,
            group_filtering,
        )
        logits = jnp.where(mask, logits, NEG_INF)

        count = count + (logits > min_logit).sum(axis=1).astype(jnp.int32)

        merged_logit = jnp.concatenate([top_logit, logits], axis=1)
        merged_index = jnp.concatenate(
            [top_index, jnp.broadcast_to(cidx[None, :], (q, chunk))], axis=1
        )
        top_logit, sel = lax.top_k(merged_logit, top_k)
        top_index = jnp.take_along_axis(merged_index, sel, axis=1)
        return top_logit, top_index, count

    if live_bound:
        return lax.fori_loop(0, nchunks, body,
                             (init_logit, init_index, init_count))

    def scan_body(carry, ci):
        return body(ci, carry), None

    out, _ = lax.scan(scan_body, (init_logit, init_index, init_count),
                      jnp.arange(nchunks, dtype=jnp.int32))
    return out


def gather_rows(tree, rows: jnp.ndarray):
    """Gather record rows out of a corpus feature tree (on device)."""
    return jax.tree_util.tree_map(
        lambda arr: jnp.take(arr, rows, axis=0), tree
    )


def build_corpus_scorer(
    plan: F.SchemaFeatures,
    *,
    chunk: int = 512,
    top_k: int = 64,
    group_filtering: bool = False,
    queries_from_rows: bool = False,
) -> Callable:
    """Build the jitted query-block x corpus scorer.

    Returned callable signature::

        fn(qfeats, corpus_feats, corpus_valid, corpus_deleted, corpus_group,
           query_group, query_row, min_logit) -> (top_logit, top_index, count_above)

    ``corpus_*`` arrays are padded to a capacity that is a multiple of
    ``chunk``; recompiles only when the capacity changes (doubling growth).
    The scan stops at the corpus's live high-water mark (``scan_topk``
    ``live_bound``): chunks past the last valid row are never scored, and
    the bound is a traced scalar, so a growing corpus compiles nothing.
    ``query_row`` is each query's own corpus row (-1 when not indexed, e.g.
    http-transform) for self-pair exclusion; ``min_logit`` is
    logit(min(threshold, maybe_threshold)) minus the host-property bound.

    With ``queries_from_rows`` the ``qfeats`` argument is ignored (pass an
    empty dict) and query features are gathered **on device** from the
    corpus at ``query_row`` — the common dedup/linkage case where the query
    batch was just indexed.  This keeps the per-batch host->device traffic
    to one small int32 array instead of re-uploading every query feature
    tensor (the dominant steady-state cost over a high-latency device
    link).  Padding rows (-1) gather row 0; their results are discarded by
    the caller.
    """

    pair_logits = build_pair_logits(plan)

    @partial(jax.jit, static_argnames=())
    def score(qfeats, corpus_feats, corpus_valid, corpus_deleted, corpus_group,
              query_group, query_row, min_logit):
        if queries_from_rows:
            qfeats = gather_rows(corpus_feats, jnp.clip(query_row, 0))
        return scan_topk(
            pair_logits, qfeats, corpus_feats, corpus_valid, corpus_deleted,
            corpus_group, query_group, query_row, min_logit,
            chunk=chunk, top_k=top_k, group_filtering=group_filtering,
            live_bound=True,
        )

    return score


def logit_to_probability(logit: np.ndarray) -> np.ndarray:
    return 1.0 / (1.0 + np.exp(-np.asarray(logit, dtype=np.float64)))
