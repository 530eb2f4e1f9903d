"""The reference's dedup stresstest records: ``name``/``city``/``ssn``
rows over a set of identities, the duplicates perturbed copies.

A copy of ``benchmarks/f1_stresstest.py``'s ``generate``, kept here so
that a later edit of ``benchmarks/`` cannot move the yardstick.  The
duplicates are the last ids: ``e<i>`` for i at or past the number of
identities."""

from __future__ import annotations

import random

FIRST = ["ole", "kari", "per", "anne", "nils", "ingrid", "lars", "berit",
         "jan", "liv", "arne", "astrid", "knut", "solveig", "odd", "randi",
         "gunnar", "turid", "leif", "marit"]
CITIES = ["oslo", "bergen", "trondheim", "stavanger", "tromso", "drammen",
          "fredrikstad", "kristiansand", "sandnes", "sarpsborg"]
_SYL = ["ba", "be", "bo", "da", "de", "di", "ga", "go", "ha", "he", "jo",
        "ka", "ke", "ko", "la", "le", "li", "ma", "me", "mo", "na", "ne",
        "no", "ra", "re", "ro", "sa", "se", "so", "ta", "te", "to", "va",
        "ve", "vi"]

# the edits a re-sync applies to a posted record (``edit``)
EDIT_KINDS = ("name_typo", "ssn_digit")


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


def corpus(data: dict, datasets, seed: int) -> dict:
    """``data["records"]`` records split round-robin over the datasets."""
    rows, _ = generate(data["records"], data["dup_rate"], seed)
    n = len(datasets)
    return {ds: rows[i::n] for i, ds in enumerate(datasets)}


def edit(rng: random.Random, row: dict, kind: str) -> dict:
    """A copy of ``row`` with one edit: a name typo or one ssn digit."""
    out = dict(row)
    if kind == "name_typo":
        out["name"] = _typo(rng, out["name"])
    elif kind == "ssn_digit":
        out["ssn"] = _ssn_digit(rng, out["ssn"])
    else:
        raise ValueError(f"unknown edit kind {kind!r}")
    return out


def is_duplicate(row_id: str, data: dict) -> bool:
    """Whether ``corpus(data, ...)`` made the row as a duplicate."""
    n_identities = max(1, int(data["records"] * (1.0 - data["dup_rate"])))
    return int(row_id[1:]) >= n_identities
