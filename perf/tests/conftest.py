"""CPU rehearsal of the benchmark (run by the builder, not by tier-1):
``JAX_PLATFORMS=cpu python -m pytest perf/tests -q``.

The device shapes are the CPU test suite's small ones (tests/conftest.py),
set before JAX or the program is imported."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
for key, value in {
    "DEVICE_CHUNK": "64", "DEVICE_QUERY_BUCKETS": "8,32",
    "DEVICE_TOP_K": "16", "DEVICE_MAX_CHARS": "24",
    "DEVICE_MAX_GRAMS": "24", "DEVICE_PREWARM": "0",
    "DUKE_PROBE_INTERVAL_S": "3600",
}.items():
    os.environ.setdefault(key, value)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

# a cell cut to what the CPU runs in seconds: the corpus and the batches,
# with the device corpus left to grow
TINY = {
    "data": {"records": 1200},
    "traffic": {"batch": 100},
    "env": {"DEVICE_INITIAL_CAPACITY": "0"},
}
