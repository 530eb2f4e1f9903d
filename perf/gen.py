"""Traffic generator: corpora and POST sequences from a seed.

One general generator for every cell.  A configuration file says what
the deployment holds (``data``); a traffic file says how pipes post to it
(``loop``, pipes, batch size, edit share and kinds).  Nothing here
imports JAX or the program: the client process and the harness both
build the same inputs from ``--seed``.

The record generator is a copy of ``benchmarks/f1_stresstest.py``'s
``generate``/``generate_linkage`` (PR 22), kept here so that a later edit
of ``benchmarks/`` cannot move the yardstick.

Seeds.  The data (which records, which ids, which edits) comes from the
run's seed.  The amount of work does not: batch sizes and edit counts
are the traffic file's, and each dataset's source order is a seeded
shuffle, so that a window meets the generator's duplicates (its last
ids) at their share of the corpus whatever the offset it starts from.
"""

from __future__ import annotations

import random

from reference import parse_service

FIRST = ["ole", "kari", "per", "anne", "nils", "ingrid", "lars", "berit",
         "jan", "liv", "arne", "astrid", "knut", "solveig", "odd", "randi",
         "gunnar", "turid", "leif", "marit"]
CITIES = ["oslo", "bergen", "trondheim", "stavanger", "tromso", "drammen",
          "fredrikstad", "kristiansand", "sandnes", "sarpsborg"]
_SYL = ["ba", "be", "bo", "da", "de", "di", "ga", "go", "ha", "he", "jo",
        "ka", "ke", "ko", "la", "le", "li", "ma", "me", "mo", "na", "ne",
        "no", "ra", "re", "ro", "sa", "se", "so", "ta", "te", "to", "va",
        "ve", "vi"]

# the stream that fixes the amount of work, the same for every seed
BASE_SEED = 0


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


def _surname(rng: random.Random, lo: int = 2, hi: int = 4) -> str:
    n = rng.randint(lo, hi)
    return "".join(rng.choice(_SYL) for _ in range(n)) + \
        rng.choice(["sen", "berg", "vik", "dal", "nes", "stad"])


def _ssn_digit(rng: random.Random, ssn: str) -> str:
    pos = rng.randrange(len(ssn))
    return ssn[:pos] + str(rng.randrange(10)) + ssn[pos + 1:]


def generate(n_entities: int, dup_rate: float, seed: int):
    """``n_entities`` records over about n*(1-dup_rate) identities; the
    records after the first n_identities are perturbed duplicates.
    Returns (rows, truth) with truth mapping _id to identity."""
    rng = random.Random(seed)
    n_identities = max(1, int(n_entities * (1.0 - dup_rate)))
    identities = {}
    for ident in range(n_identities):
        identities[ident] = {
            "name": f"{rng.choice(FIRST)} {_surname(rng)}",
            "city": rng.choice(CITIES),
            "ssn": str(rng.randint(10_000_000, 99_999_999)),
        }
    rows, truth = [], {}
    for i in range(n_entities):
        ident = i if i < n_identities else rng.randrange(n_identities)
        base = identities[ident]
        name, city, ssn = base["name"], base["city"], base["ssn"]
        if i >= n_identities:
            if rng.random() < 0.5:
                name = _typo(rng, name)
            if rng.random() < 0.2:
                name = _typo(rng, name)
            if rng.random() < 0.15:
                ssn = _ssn_digit(rng, ssn)
        rid = f"e{i}"
        rows.append({"_id": rid, "name": name, "city": city, "ssn": ssn})
        truth[rid] = ident
    return rows, truth


def generate_linkage(n_per_group: int, overlap: float, seed: int):
    """Two groups drawn round-robin from one ``generate`` corpus."""
    rows, truth = generate(n_per_group * 2, overlap, seed)
    g1, g2 = rows[0::2], rows[1::2]
    t1 = {row["_id"]: truth[row["_id"]] for row in g1}
    t2 = {row["_id"]: truth[row["_id"]] for row in g2}
    return g1, g2, t1, t2


def corpus(config: dict, seed: int) -> dict:
    """{dataset_id: rows} preloaded into the deployment, each dataset in
    its source's order: a seeded shuffle.  (In id order the duplicates
    sit at the end, and a re-sync window that starts among them finds
    several times the links of one that does not: PERF.md.)"""
    data = config["data"]
    datasets = parse_service(config["service_xml"])["datasets"]
    if data["generator"] == "linkage":
        g1, g2, _, _ = generate_linkage(data["records"] // 2,
                                        data["dup_rate"], seed)
        out = dict(zip(datasets, (g1, g2)))
    else:
        rows, _ = generate(data["records"], data["dup_rate"], seed)
        n = len(datasets)
        out = {ds: rows[i::n] for i, ds in enumerate(datasets)}
    for ds, ds_rows in out.items():
        random.Random(f"{seed}:order:{ds}").shuffle(ds_rows)
    return out


def perturb(rng: random.Random, row: dict, kinds) -> dict:
    """One edit of the generator's kinds: a name typo or one ssn digit."""
    out = dict(row)
    kind = rng.choice(kinds)
    if kind == "name_typo":
        out["name"] = _typo(rng, out["name"])
    elif kind == "ssn_digit":
        out["ssn"] = _ssn_digit(rng, out["ssn"])
    else:
        raise ValueError(f"unknown edit kind {kind!r}")
    return out


class Post:
    """One POST: the dataset it goes to and its entities."""

    __slots__ = ("dataset", "entities")

    def __init__(self, dataset: str, entities: list):
        self.dataset = dataset
        self.entities = entities


# -- closed loop: re-sync ------------------------------------------------------


def resync_pipes(config: dict, traffic: dict, seed: int, rows: dict):
    """One POST list per pipe: the pipe's dataset re-posted in source
    order from a seed-chosen offset, ``batch`` records at a time, with an exact
    ``edit_share`` of the records carrying one edit.  Warm-up takes the
    first POSTs of each list and the window goes on from there."""
    rng = random.Random(f"{seed}:resync")
    batch = traffic["batch"]
    pipes = []
    for ds in parse_service(config["service_xml"])["datasets"]:
        ds_rows = rows[ds]
        n = len(ds_rows)
        start = rng.randrange(n)
        order = ds_rows[start:] + ds_rows[:start]
        edited = set(rng.sample(range(n), round(n * traffic["edit_share"])))
        posted = [perturb(rng, r, traffic["edit_kinds"]) if i in edited
                  else r for i, r in enumerate(order)]
        for _ in range(traffic["pipes_per_dataset"]):
            pipes.append([Post(ds, posted[s:s + batch])
                          for s in range(0, n, batch)])
    return pipes
