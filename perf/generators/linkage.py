"""The reference's record-linkage stresstest records: two groups drawn
round-robin from one ``dedup`` corpus, with its edits.

A copy of ``benchmarks/f1_stresstest.py``'s ``generate_linkage``."""

from __future__ import annotations

import plugins

dedup = plugins.load("generators", "dedup")

EDIT_KINDS = dedup.EDIT_KINDS
edit = dedup.edit


def generate_linkage(n_per_group: int, overlap: float, seed: int):
    """Two groups drawn round-robin from one ``generate`` corpus."""
    rows, truth = dedup.generate(n_per_group * 2, overlap, seed)
    g1, g2 = rows[0::2], rows[1::2]
    t1 = {row["_id"]: truth[row["_id"]] for row in g1}
    t2 = {row["_id"]: truth[row["_id"]] for row in g2}
    return g1, g2, t1, t2


def corpus(data: dict, datasets, seed: int) -> dict:
    """``data["records"] // 2`` records in each of the two groups."""
    g1, g2, _, _ = generate_linkage(data["records"] // 2, data["dup_rate"],
                                    seed)
    return dict(zip(datasets, (g1, g2)))


def is_duplicate(row_id: str, data: dict) -> bool:
    """Whether ``corpus(data, ...)`` made the row as a duplicate."""
    return dedup.is_duplicate(row_id, dict(data,
                                           records=data["records"] // 2 * 2))
