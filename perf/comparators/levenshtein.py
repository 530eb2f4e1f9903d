"""Duke's Levenshtein comparator: ``1 - d / shorter`` over Java char units.

Strings whose lengths alone force a similarity under 0.5 score 0 (the
property maps any similarity under 0.5 to its ``low`` anyway), and the
distance (rapidfuzz's unit-cost edit distance; nothing of the program)
is capped at the shorter length.
"""

from rapidfuzz.distance import Levenshtein


def _units(s: str) -> str:
    # Java measures strings in UTF-16 units: a character outside the BMP
    # counts twice
    if s.isascii():
        return s
    return "".join(chr(u) for u in memoryview(
        s.encode("utf-16-le", "surrogatepass")).cast("H"))


def compare(v1: str, v2: str) -> float:
    if v1 == v2:
        return 1.0
    v1, v2 = _units(v1), _units(v2)
    shorter, longer = min(len(v1), len(v2)), max(len(v1), len(v2))
    if shorter == 0 or (longer - shorter) * 2 > shorter:
        return 0.0
    return 1.0 - min(Levenshtein.distance(v1, v2), shorter) / shorter

