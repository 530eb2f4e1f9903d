"""The code that a configuration or a metric brings as files of its own,
found by name: ``perf/<kind>/<name>.py``.

Kinds: ``comparators`` and ``cleaners`` (named in a configuration's
service XML), ``generators`` (a configuration's ``data.generator``),
``end_to_end`` and ``layer_metrics`` (a metric's name).  Each file is
loaded once per process."""

from __future__ import annotations

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))

_LOADED = {}


def load(kind: str, name: str):
    """The module ``perf/<kind>/<name>.py``."""
    mod = _LOADED.get((kind, name))
    if mod is None:
        path = os.path.join(HERE, kind, f"{name}.py")
        module_name = f"perf_{kind}_{name}".replace(".", "_").replace("-", "_")
        spec = importlib.util.spec_from_file_location(module_name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _LOADED[(kind, name)] = mod
    return mod
