"""Duke's QGramComparator with its defaults: q = 2, the overlap formula
``common / min(|g1|, |g2|)`` over the two sets of q-grams."""

import numpy as np

Q = 2


def grams(value: str) -> set:
    if len(value) < Q:
        return {value} if value else set()
    return {value[i:i + Q] for i in range(len(value) - Q + 1)}


def compare(v1: str, v2: str) -> float:
    if v1 == v2:
        return 1.0
    g1, g2 = grams(v1), grams(v2)
    if not g1 or not g2:
        return 0.0
    return len(g1 & g2) / min(len(g1), len(g2))


class Column:
    """All corpus values of one property as bit sets over their q-gram
    vocabulary, so one query scans every row with AND and a popcount."""

    def __init__(self, values):
        sets = [grams(v) for v in values]
        vocab = sorted(set().union(*sets))
        self.index = {g: i for i, g in enumerate(vocab)}
        self.words = max(1, -(-len(vocab) // 64))
        self.bits = np.zeros((len(values), self.words), dtype=np.uint64)
        for row, s in enumerate(sets):
            for g in s:
                i = self.index[g]
                self.bits[row, i // 64] |= np.uint64(1 << (i % 64))
        self.sizes = np.array([len(s) for s in sets], dtype=np.float64)
        self.empty = np.array([not v for v in values])

    def similarity(self, value: str) -> np.ndarray:
        g = grams(value)
        q = np.zeros(self.words, dtype=np.uint64)
        for gram in g:
            i = self.index.get(gram)
            if i is not None:
                q[i // 64] |= np.uint64(1 << (i % 64))
        common = np.bitwise_count(self.bits & q).sum(axis=1).astype(np.float64)
        smaller = np.minimum(self.sizes, float(len(g)))
        # equal strings have equal gram sets, so they score 1 here too
        with np.errstate(divide="ignore", invalid="ignore"):
            return np.where(smaller > 0, common / smaller, 0.0)
