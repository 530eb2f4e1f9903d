"""Matching-quality (F1) stresstest harness: seeded corpus, known truth.

The reference validates matching quality only through the external Sesam
stresstest pipes (sesam_node_deduplication_stresstest_config.conf.json and
its recordlinkage twin — 10,000 fake entities per source, seed 1234, value
pools sized so duplicates occur at a known rate, SURVEY.md section 4).
This harness is the in-process equivalent with a *measurable* ground
truth: every record derives from a true underlying identity, field values
are perturbed with seeded noise (typos, digit swaps), and two records are
true duplicates/links iff they share the identity.  That turns the
BASELINE.json metric ("dedup F1 @ fixed wall-clock") into a number.

Workloads: ``--workload dedup`` (one group, duplicates within) or
``--workload linkage`` (two groups over a shared identity pool, group
filtering on; ``--one-to-one`` additionally attaches the real ONE_TO_ONE
service listener and scores its surviving links).

Usage::

    python benchmarks/f1_stresstest.py
        [--backend host|device|ann|sharded|sharded-brute]
        [--workload dedup|linkage] [--one-to-one]
        [--entities 2000] [--dup-rate 0.3] [--batch 500] [--seed 1234]

Prints one JSON line: {"backend", "workload", "f1", "precision",
"recall", "wall_s", "records_per_sec", "true_pairs", "emitted_pairs",
(+ ProfileStats fields when the backend exposes them)}.
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import random
import sys
import time
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

FIRST = ["ole", "kari", "per", "anne", "nils", "ingrid", "lars", "berit",
         "jan", "liv", "arne", "astrid", "knut", "solveig", "odd", "randi",
         "gunnar", "turid", "leif", "marit"]
LAST = ["hansen", "johansen", "olsen", "larsen", "andersen", "pedersen",
        "nilsen", "kristiansen", "jensen", "karlsen", "johnsen", "pettersen",
        "eriksen", "berg", "haugen", "hagen"]
CITIES = ["oslo", "bergen", "trondheim", "stavanger", "tromso", "drammen",
          "fredrikstad", "kristiansand", "sandnes", "sarpsborg"]


def _typo(rng: random.Random, s: str) -> str:
    if len(s) < 2:
        return s
    op = rng.randrange(3)
    pos = rng.randrange(len(s))
    if op == 0:    # substitute
        return s[:pos] + rng.choice("abcdefghijklmnop") + s[pos + 1:]
    if op == 1:    # delete
        return s[:pos] + s[pos + 1:]
    return s[:pos] + rng.choice("abcdefghijklmnop") + s[pos:]  # insert


_SYL = ["ba", "be", "bo", "da", "de", "di", "ga", "go", "ha", "he", "jo",
        "ka", "ke", "ko", "la", "le", "li", "ma", "me", "mo", "na", "ne",
        "no", "ra", "re", "ro", "sa", "se", "so", "ta", "te", "to", "va",
        "ve", "vi"]


def _surname(rng: random.Random, min_syllables: int = 2,
             max_syllables: int = 4) -> str:
    # syllable-generated surnames: enough entropy that coincidental
    # full-name collisions between DIFFERENT identities stay rare at 10k+
    # scale (the fixed LAST pool saturates and poisons precision with
    # generator artifacts rather than matcher errors).  At 10^6 scale the
    # default 2-4 syllable space ITSELF saturates — ~1/3 of surnames draw
    # from only ~7k forms, so hundreds of thousands of distinct identities
    # genuinely collide within 0-2 edits and every engine (reference
    # included) scores them above threshold; pass min_syllables=3,
    # max_syllables=5 (--name-syllables 3-5) so precision at 1M measures
    # the matcher, not the name pool.
    n = rng.randint(min_syllables, max_syllables)
    return "".join(rng.choice(_SYL) for _ in range(n)) + \
        rng.choice(["sen", "berg", "vik", "dal", "nes", "stad"])


def generate(n_entities: int, dup_rate: float, seed: int = 1234,
             name_syllables=(2, 4)):
    """Seeded corpus: ``n_entities`` records over ~n*(1-dup_rate) identities.

    Returns (records_as_dicts, truth) where truth maps record _id -> true
    identity.  Mirrors the reference stresstest's seeded-pool construction
    but with derived (not independent) fields so duplicate pairs are
    *near*-duplicates the comparators must actually work for.
    """
    rng = random.Random(seed)
    n_identities = max(1, int(n_entities * (1.0 - dup_rate)))
    lo, hi = name_syllables
    identities = {}
    for ident in range(n_identities):
        identities[ident] = {
            "name": f"{rng.choice(FIRST)} {_surname(rng, lo, hi)}",
            "city": rng.choice(CITIES),
            "ssn": str(rng.randint(10_000_000, 99_999_999)),
        }
    rows, truth = [], {}
    for i in range(n_entities):
        # first n_identities records cover every identity once; the rest
        # are duplicates of a random identity with perturbed fields
        ident = i if i < n_identities else rng.randrange(n_identities)
        base = identities[ident]
        name, city, ssn = base["name"], base["city"], base["ssn"]
        if i >= n_identities:
            if rng.random() < 0.5:
                name = _typo(rng, name)
            if rng.random() < 0.2:
                name = _typo(rng, name)
            if rng.random() < 0.15:   # one digit wrong
                pos = rng.randrange(len(ssn))
                ssn = ssn[:pos] + str(rng.randrange(10)) + ssn[pos + 1:]
        rid = f"e{i}"
        rows.append({"_id": rid, "name": name, "city": city, "ssn": ssn})
        truth[rid] = ident
    return rows, truth


def truth_pairs(truth):
    by_ident = defaultdict(list)
    for rid, ident in truth.items():
        by_ident[ident].append(rid)
    pairs = set()
    for members in by_ident.values():
        for a, b in itertools.combinations(sorted(members), 2):
            pairs.add((a, b))
    return pairs


def stresstest_schema(ssn_exact: bool = False):
    """The measured matching schema.

    ``ssn_exact`` swaps the ssn comparator from QGram(high=0.9) to Exact:
    q-grams over 8-digit strings draw from only 100 possible bigrams, so
    at 10^6-entity (~10^12 candidate-pair) density two UNRELATED ssns
    routinely share enough grams to score 0.7+, and (with a city match)
    the Bayes product crosses the threshold — FPs every engine emits identically (host-exact verified),
    i.e. a schema artifact, not a matcher one.  Large-corpus quality runs
    use --ssn-exact so precision measures the matcher.  The default stays
    QGram, the reference stresstest's comparator.
    """
    from sesam_duke_microservice_tpu.core import comparators as C
    from sesam_duke_microservice_tpu.core.config import DukeSchema
    from sesam_duke_microservice_tpu.core.records import (
        ID_PROPERTY_NAME,
        Property,
    )

    return DukeSchema(
        threshold=0.8,
        maybe_threshold=None,
        properties=[
            Property(ID_PROPERTY_NAME, id_property=True),
            Property("name", C.Levenshtein(), 0.25, 0.85),
            Property("city", C.Exact(), 0.45, 0.65),
            Property("ssn", C.Exact() if ssn_exact else C.QGram(),
                     0.2, 0.9),
        ],
        data_sources=[],
    )


class PairCollector:
    def __init__(self):
        self.pairs = {}

    def batch_ready(self, n):
        pass

    def batch_done(self):
        pass

    def matches(self, r1, r2, confidence):
        a, b = sorted((r1.record_id, r2.record_id))
        self.pairs[(a, b)] = confidence

    def matches_perhaps(self, r1, r2, confidence):
        pass

    def no_match_for(self, record):
        pass


def build_processor(schema, backend: str, group_filtering: bool = False):
    from sesam_duke_microservice_tpu.core.config import MatchTunables

    if backend != "host":
        from sesam_duke_microservice_tpu.utils.jit_cache import (
            enable_persistent_cache,
        )

        enable_persistent_cache()
    if backend == "sharded":
        from sesam_duke_microservice_tpu.engine.sharded_matcher import (
            ShardedAnnIndex,
            ShardedAnnProcessor,
        )

        index = ShardedAnnIndex(schema, tunables=MatchTunables())
        return ShardedAnnProcessor(schema, index,
                                   group_filtering=group_filtering)
    if backend == "sharded-brute":
        from sesam_duke_microservice_tpu.engine.sharded_matcher import (
            ShardedDeviceIndex,
            ShardedDeviceProcessor,
        )

        index = ShardedDeviceIndex(schema, tunables=MatchTunables())
        return ShardedDeviceProcessor(schema, index,
                                      group_filtering=group_filtering)
    if backend == "device":
        from sesam_duke_microservice_tpu.engine.device_matcher import (
            DeviceIndex,
            DeviceProcessor,
        )

        index = DeviceIndex(schema, tunables=MatchTunables())
        return DeviceProcessor(schema, index,
                               group_filtering=group_filtering)
    if backend == "ann":
        from sesam_duke_microservice_tpu.engine.ann_matcher import (
            AnnIndex,
            AnnProcessor,
        )

        index = AnnIndex(schema, tunables=MatchTunables())
        return AnnProcessor(schema, index, group_filtering=group_filtering)
    from sesam_duke_microservice_tpu.engine.processor import Processor
    from sesam_duke_microservice_tpu.index.inverted import InvertedIndex

    index = InvertedIndex(schema, MatchTunables())
    return Processor(schema, index, group_filtering=group_filtering)


def to_records(rows):
    from sesam_duke_microservice_tpu.core.records import (
        DATASET_ID_PROPERTY_NAME,
        ID_PROPERTY_NAME,
        ORIGINAL_ENTITY_ID_PROPERTY_NAME,
        Record,
    )

    records = []
    for row in rows:
        r = Record()
        r.add_value(ID_PROPERTY_NAME, f"ds__{row['_id']}")
        r.add_value(ORIGINAL_ENTITY_ID_PROPERTY_NAME, row["_id"])
        r.add_value(DATASET_ID_PROPERTY_NAME, "ds")
        for k in ("name", "city", "ssn"):
            r.add_value(k, row[k])
        records.append(r)
    return records


def generate_linkage(n_per_group: int, overlap: float, seed: int = 1234):
    """Two-group corpus (reference recordlinkage stresstest shape): both
    groups drawn from a shared identity pool; a cross-group pair is a true
    link iff the identities match.

    Exactly 2*n_per_group rows are generated and round-robin split — no
    over-generation/truncation, so the duplicate rate stays ``overlap``
    (truncating would keep only overlap^2 of the duplicate rows, since
    generate() emits all duplicates after the canonical block)."""
    rows, truth = generate(n_per_group * 2, overlap, seed)
    g1, g2 = [], []
    for i, row in enumerate(rows):
        (g1 if i % 2 == 0 else g2).append(row)
    t1 = {row["_id"]: truth[row["_id"]] for row in g1}
    t2 = {row["_id"]: truth[row["_id"]] for row in g2}
    return g1, g2, t1, t2


def truth_links(t1, t2):
    by_ident = defaultdict(list)
    for rid, ident in t2.items():
        by_ident[ident].append(rid)
    links = set()
    for rid, ident in t1.items():
        for other in by_ident.get(ident, ()):
            links.add(tuple(sorted((rid, other))))
    return links


def run(backend: str, n_entities: int, dup_rate: float, batch: int,
        seed: int = 1234, workload: str = "dedup",
        one_to_one: bool = False, name_syllables=(2, 4),
        ssn_exact: bool = False, dump_pairs: str = None):
    from sesam_duke_microservice_tpu.core.records import (
        GROUP_NO_PROPERTY_NAME,
    )

    if workload == "linkage":
        g1, g2, t1, t2 = generate_linkage(n_entities // 2, dup_rate, seed)
        del name_syllables  # linkage harness keeps the default pool
        r1, r2 = to_records(g1), to_records(g2)
        for r in r1:
            r.add_value(GROUP_NO_PROPERTY_NAME, "1")
        for r in r2:
            r.add_value(GROUP_NO_PROPERTY_NAME, "2")
        records = r1 + r2
        expected_links = truth_links(t1, t2)
    else:
        rows, truth = generate(n_entities, dup_rate, seed,
                               name_syllables=name_syllables)
        records = to_records(rows)
        expected_links = None

    schema = stresstest_schema(ssn_exact=ssn_exact)
    proc = build_processor(schema, backend,
                           group_filtering=(workload == "linkage"))
    if one_to_one:
        # the REAL service policy (per-batch greedy resolution with
        # cross-batch retraction), not a post-hoc approximation: attach the
        # actual listener over an in-memory link DB and read its live links
        from sesam_duke_microservice_tpu.engine.listeners import (
            ServiceMatchListener,
        )
        from sesam_duke_microservice_tpu.links.base import LinkStatus
        from sesam_duke_microservice_tpu.links.memory import (
            InMemoryLinkDatabase,
        )

        linkdb = InMemoryLinkDatabase()
        listener = ServiceMatchListener(
            "bench", linkdb,
            kind="recordlinkage" if workload == "linkage" else "deduplication",
            one_to_one=True,
            # displacement replay fails closed without a resolver; wire the
            # index lookup exactly as build_workload does
            record_resolver=proc.database.find_record_by_id,
        )
        proc.add_match_listener(listener)
    else:
        collector = PairCollector()
        proc.add_match_listener(collector)

    escalations_start = 0
    if backend != "host":
        from sesam_duke_microservice_tpu.engine import device_matcher as DM

        escalations_start = DM.ESCALATIONS
    t0 = time.perf_counter()
    for start in range(0, len(records), batch):
        proc.deduplicate(records[start:start + batch])
    wall = time.perf_counter() - t0

    if one_to_one:
        pair_items = {
            (link.id1, link.id2): link.confidence
            for link in linkdb.get_changes_since(0)
            if link.status != LinkStatus.RETRACTED
        }
    else:
        pair_items = collector.pairs

    def one_to_one_ceiling():
        """Structural F1 bound of 1:1 mode against all-truth-pairs ground
        truth: an identity with a copies in group 1 and b in group 2
        contributes a*b truth pairs but at most min(a, b) one-to-one links
        (dedup: k copies -> C(k,2) pairs, floor(k/2) links), so recall —
        hence F1 — is capped below 1.0 by the corpus itself, not by the
        matcher.  Returned so the 1:1 score can be read against the number
        it can actually reach."""
        if workload == "linkage":
            c1, c2 = defaultdict(int), defaultdict(int)
            for ident in t1.values():
                c1[ident] += 1
            for ident in t2.values():
                c2[ident] += 1
            max_links = sum(
                min(c1[i], c2[i]) for i in set(c1) & set(c2)
            )
            total = len(expected_links)
        else:
            counts = defaultdict(int)
            for ident in truth.values():
                counts[ident] += 1
            max_links = sum(k // 2 for k in counts.values())
            total = sum(k * (k - 1) // 2 for k in counts.values())
        r = max_links / total if total else 1.0
        return max_links, (2 * r / (1 + r) if r else 0.0)

    stats = getattr(proc, "stats", None)

    emitted = {
        tuple(sorted((a.split("__", 1)[1], b.split("__", 1)[1])))
        for a, b in pair_items
    }
    expected = (expected_links if expected_links is not None
                else truth_pairs(truth))
    tp = len(emitted & expected)
    precision = tp / len(emitted) if emitted else 0.0
    recall = tp / len(expected) if expected else 1.0
    f1 = (2 * precision * recall / (precision + recall)
          if precision + recall else 0.0)
    if dump_pairs:
        # emitted pair set + host-exact confidences, for cross-backend
        # link-set agreement diffs (VERDICT r3 #4)
        with open(dump_pairs, "w") as f:
            for (a, b), conf in sorted(pair_items.items()):
                f.write(f"{a}\t{b}\t{conf:.12f}\n")
    out = {
        "backend": backend,
        "workload": workload,
        "f1": round(f1, 4),
        "precision": round(precision, 4),
        "recall": round(recall, 4),
        "wall_s": round(wall, 2),
        "records_per_sec": round(len(records) / wall, 1),
        "true_pairs": len(expected),
        "emitted_pairs": len(emitted),
    }
    if one_to_one:
        max_links, f1_ceiling = one_to_one_ceiling()
        out["one_to_one_max_links"] = max_links
        out["f1_ceiling"] = round(f1_ceiling, 4)
        out["f1_vs_ceiling"] = round(f1 / f1_ceiling, 4) if f1_ceiling else 0.0
    if stats is not None:
        out["retrieval_s"] = round(stats.retrieval_seconds, 2)
        out["compare_s"] = round(stats.compare_seconds, 2)
        out["pairs_compared"] = stats.pairs_compared
    if backend != "host":
        from sesam_duke_microservice_tpu.engine import device_matcher as DM

        # delta vs run start so repeated in-process runs don't attribute
        # earlier configurations' escalations to this one
        out["escalations"] = DM.ESCALATIONS - escalations_start
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--backend", default="host",
                    choices=["host", "device", "ann", "sharded",
                             "sharded-brute"])
    ap.add_argument("--entities", type=int, default=2000)
    ap.add_argument("--dup-rate", type=float, default=0.3)
    ap.add_argument("--batch", type=int, default=500)
    ap.add_argument("--seed", type=int, default=1234)
    ap.add_argument("--workload", default="dedup",
                    choices=["dedup", "linkage"])
    ap.add_argument("--one-to-one", action="store_true",
                    help="greedy best-match assignment (ONE_TO_ONE policy)")
    ap.add_argument("--ssn-exact", action="store_true",
                    help="scale-appropriate schema: Exact ssn comparator "
                         "(see stresstest_schema)")
    ap.add_argument("--dump-pairs", default=None,
                    help="write the emitted pair set (id1\\tid2\\tconf) "
                         "to this path for cross-backend agreement diffs")
    ap.add_argument("--name-syllables", default="2-4",
                    help="surname syllable range lo-hi (use 3-5 at 10^6 "
                         "scale so the name pool doesn't saturate)")
    args = ap.parse_args()
    lo, hi = (int(x) for x in args.name_syllables.split("-"))
    print(json.dumps(
        run(args.backend, args.entities, args.dup_rate, args.batch,
            args.seed, workload=args.workload, one_to_one=args.one_to_one,
            name_syllables=(lo, hi), ssn_exact=args.ssn_exact,
            dump_pairs=args.dump_pairs)
    ))


if __name__ == "__main__":
    main()
