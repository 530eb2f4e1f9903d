"""The comparison that decides ``correct``.

What the timed path produces is the live link feed after the window:
every link the window's POSTs (and the warm-up's) wrote, through the
scheduler, extraction, the device scorer and its kernels, certified
finalize, the listener and the link database with its journal.  It is
held against the plain reference (``reference.py``) on the records as
each was posted.

A record's *final* content is certain when its last applied POST began
after every earlier POST of it was acknowledged (else two POSTs were in
flight together and either may have been applied last).  The last
scoring pass that touched a pair compared both records' final contents,
so:

* ``missing_links``: for a sample of posted records drawn from the seed,
  every record whose final content scores above threshold + MARGIN with
  it (both final and live) must be linked to it in the live feed.  Under
  one-to-one (``link_mode``) a record links to one counterpart only, so
  there such a pair is missing when neither record has any live link.
* ``conf_gap``: every live link's confidence against the reference score
  of the contents the two records had (any posted version of each), the
  nearest one above threshold - MARGIN; the largest such gap.
* ``unsound_links``: live links that no version of the two records
  scores above threshold - MARGIN, that touch a record whose final
  state is deleted, that join two records of one group (linkage), or
  that give a record a second link under one-to-one.
* ``unanswered_posts``: POSTs answered neither 200 nor 429 (a refusal is
  an answer, and counts as failed).

The control puts the reference computed in float32 in the program's
place: its confidences, and its own decision whether a sampled pair
links.
"""

from __future__ import annotations

import random
from collections import defaultdict

import numpy as np

from reference import Corpus, Schema, parse_service

# pairs this close to the threshold are neither required nor refused:
# two correct float64 folds of the same probabilities differ by far
# less, and Duke's own comparisons are strict
MARGIN = 1e-9


def _content(entity: dict, columns) -> tuple:
    return tuple(entity.get(c) for c in columns)


class History:
    """Every version of every record: the preload, then each POST."""

    def __init__(self, service: dict, rows: dict, posts):
        self.columns = [p["column"] for p in service["properties"]]
        self.group_of = {ds: i for i, ds in enumerate(service["datasets"])}
        self.versions = defaultdict(set)    # key -> {content or None}
        self.applied = defaultdict(list)    # key -> [(send, ack, content)]
        self.uncertain = set()
        self.posted = set()
        for ds, ds_rows in rows.items():
            for r in ds_rows:
                key = (ds, r["_id"])
                c = _content(r, self.columns)
                self.versions[key].add(c)
                self.applied[key].append((float("-inf"), float("-inf"), c))
        for p in posts:
            for e in p["entities"]:
                key = (p["dataset"], str(e["_id"]))
                c = None if e.get("_deleted") else _content(e, self.columns)
                self.versions[key].add(c)
                if p["status"] == 200:
                    self.applied[key].append((p["send"], p["ack"], c))
                    self.posted.add(key)
                elif p["status"] != 429:
                    self.uncertain.add(key)

    def final(self, key):
        """(certain, content): content None for a deleted record."""
        hist = self.applied.get(key)
        if not hist or key in self.uncertain:
            return False, None
        last = max(hist, key=lambda h: h[0])
        certain = all(h is last or h[1] < last[0] for h in hist)
        return certain, last[2]

    def record(self, content) -> dict:
        return dict(zip(self.columns, content))


def compare(config: dict, rows: dict, posts, live, seed: int,
            sample: int, control: bool = False) -> dict:
    """The numbers compared, each with its limit: {name: [value, limit]}."""
    service = parse_service(config["service_xml"])
    schema = Schema(service)
    limits = config["limits"]
    one_to_one = service["link_mode"] == "one-to-one"
    linkage = service["kind"] == "recordlinkage"
    hist = History(service, rows, posts)
    thr = schema.threshold
    dtype = np.float32 if control else np.float64

    # -- the live feed ---------------------------------------------------------
    links = {}
    for row in live:
        a = (row["dataset1"], row["entity1"])
        b = (row["dataset2"], row["entity2"])
        links[frozenset((a, b))] = row["confidence"]
    degree = defaultdict(int)
    for pair in links:
        for key in pair:
            degree[key] += 1

    gap, unsound = 0.0, 0
    for pair, conf in links.items():
        if len(pair) != 2:
            unsound += 1
            continue
        a, b = sorted(pair)
        if linkage and hist.group_of[a[0]] == hist.group_of[b[0]]:
            unsound += 1
            continue
        if one_to_one and (degree[a] > 1 or degree[b] > 1):
            unsound += 1
        if any(hist.final(k) == (True, None) for k in (a, b)):
            unsound += 1
            continue
        best = None
        for ca in hist.versions[a] - {None}:
            for cb in hist.versions[b] - {None}:
                ref = schema.score(hist.record(ca), hist.record(cb))
                if ref <= thr - MARGIN:
                    continue
                if control:  # the control's confidence for this link
                    conf = schema.score(hist.record(ca), hist.record(cb),
                                        dtype)
                d = abs(conf - ref)
                if best is None or d < best:
                    best = d
        if best is None:
            unsound += 1
        else:
            gap = max(gap, best)

    # -- completeness on a sample ------------------------------------------------
    finals = {}
    for key in hist.applied:
        certain, content = hist.final(key)
        if certain and content is not None:
            finals[key] = content
    queries = sorted(k for k in hist.posted if k in finals)
    random.Random(f"{seed}:check").shuffle(queries)
    queries = queries[:sample]
    by_group = defaultdict(list)
    for key in finals:
        by_group[hist.group_of[key[0]] if linkage else 0].append(key)
    corpora = {g: Corpus(schema, keys, [hist.record(finals[k]) for k in keys])
               for g, keys in by_group.items()}
    missing = 0
    for a in queries:
        g = (1 - hist.group_of[a[0]]) if linkage else 0
        want = corpora[g].matches(hist.record(finals[a]), MARGIN)
        want.pop(a, None)
        if control:
            got = corpora[g].matches(hist.record(finals[a]), 0.0, dtype)
        for b, ref in want.items():
            pair = frozenset((a, b))
            if control:
                present = b in got
                if present:
                    gap = max(gap, abs(got[b] - ref))
            elif one_to_one:
                present = degree[a] > 0 or degree[b] > 0
            else:
                present = pair in links
            if not present:
                missing += 1

    unanswered = sum(p["status"] not in (200, 429) for p in posts)
    return {
        "conf_gap": [gap, limits["conf_gap"]],
        "missing_links": [missing, limits["missing_links"]],
        "unsound_links": [unsound, limits["unsound_links"]],
        "unanswered_posts": [unanswered, limits["unanswered_posts"]],
        "checked": [len(links), None],
        "sampled": [len(queries), None],
    }


def is_correct(numbers: dict) -> bool:
    return all(v <= lim for v, lim in numbers.values() if lim is not None)
