"""Duke's LowerCaseNormalizeCleaner (the program's ``lowercase``): lower
case, accents stripped (canonical decomposition, then the combining
marks dropped), every run of whitespace one space, trimmed."""

import re
import unicodedata

_WS = re.compile(r"\s+")


def clean(value: str) -> str:
    value = "".join(ch for ch in unicodedata.normalize("NFD", value.lower())
                    if unicodedata.category(ch) != "Mn")
    return _WS.sub(" ", value).strip()
