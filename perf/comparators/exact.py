"""Duke's ExactComparator: 1 when the strings are equal, else 0."""

import numpy as np


def compare(v1: str, v2: str) -> float:
    return 1.0 if v1 == v2 else 0.0


class Column:
    """All corpus values of one property as integer codes, for a
    vectorized scan."""

    def __init__(self, values):
        self.codes = {}
        self.rows = np.array([self.codes.setdefault(v, len(self.codes))
                              for v in values], dtype=np.int64)
        self.empty = self.rows == self.codes.get("", -1)

    def similarity(self, value: str) -> np.ndarray:
        return (self.rows == self.codes.get(value, -1)).astype(np.float64)
