"""Test configuration.

Device-touching tests run on a virtual 8-device CPU mesh so the multi-chip
sharding paths execute in CI without TPU hardware (``chip_smoke.py --chips
4`` runs the mesh path on real chips).  Setting the XLA flags must happen
before jax initializes, hence the env mutation at import time.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"

# Small device-matcher shapes: the CPU backend executes the brute-force
# scorer orders of magnitude slower than a TPU; production defaults
# (chunk=512, buckets up to 256) are sized for the MXU/VPU.
os.environ.setdefault("DEVICE_CHUNK", "64")
os.environ.setdefault("DEVICE_QUERY_BUCKETS", "8,32")
os.environ.setdefault("DEVICE_TOP_K", "16")
os.environ.setdefault("DEVICE_MAX_CHARS", "24")
os.environ.setdefault("DEVICE_MAX_GRAMS", "24")
# background compile pre-warm off by default in tests (it competes with the
# slow CPU-interpret compiles); test_device_matcher re-enables it explicitly
os.environ.setdefault("DEVICE_PREWARM", "0")
# canary prober (ISSUE 20): keep the background probe cycle from firing
# mid-test — probe suites drive run_cycle() synchronously, and every
# other suite should see an idle prober (no shadow builds, no probe
# traces in the flight recorder)
os.environ.setdefault("DUKE_PROBE_INTERVAL_S", "3600")
# AOT executable store (ISSUE 15): point at a session-scoped temp dir so
# test runs never write the operator's ~/.cache (subprocess-differential
# tests pin their own DUKE_AOT_DIR); removed at interpreter exit so dev
# boxes don't accumulate serialized-executable dirs across runs
import atexit  # noqa: E402
import shutil  # noqa: E402
import tempfile  # noqa: E402

if "DUKE_AOT_DIR" not in os.environ:
    _aot_tmp = tempfile.mkdtemp(prefix="duke-aot-tests-")
    os.environ["DUKE_AOT_DIR"] = _aot_tmp
    atexit.register(shutil.rmtree, _aot_tmp, True)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_sessionfinish(session, exitstatus):
    """Sanitizer legs: a recorded lock-order inversion (DUKE_LOCKCHECK=1)
    or certified-numerics violation (DUKE_NUMCHECK=1) fails the whole
    session even if every individual test passed — the sanitizers
    validate committed invariants (the static lock hierarchy, the
    certified margin bounds), not any one test."""
    from sesam_duke_microservice_tpu.utils import lockcheck, numcheck

    # DUKE_NUMCHECK leg: any certified-vs-oracle disagreement or
    # margin-bound violation recorded during the run fails it (checked
    # unconditionally — injection tests reset() their deliberate
    # violations, so anything left here is real)
    numfound = numcheck.violations()
    if numfound:
        print("\nnumcheck: certified-numerics violations recorded:")
        for line in numfound:
            print("  " + line)
        session.exitstatus = 1

    if not lockcheck.enabled():
        return
    found = lockcheck.inversions()
    if found:
        print("\nlockcheck: lock-order inversions recorded:")
        for line in found:
            print("  " + line)
        session.exitstatus = 1
    rep = lockcheck.report()
    if rep["unknown_edges"]:
        # analyzer drift: the runtime saw a nesting the static graph
        # doesn't model — fail the leg so it gets triaged into
        # MANUAL_EDGES (or the analysis fixed), keeping the committed
        # hierarchy the single source of truth
        print("\nlockcheck: %d observed edge(s) missing from the static "
              "graph (triage scripts/dukecheck/config.py):"
              % len(rep["unknown_edges"]))
        for line in rep["unknown_edges"]:
            print("  " + line)
        session.exitstatus = 1
    if rep["unmapped_lock_edges"]:
        # a lock the hierarchy doc could not even name — naming drift in
        # the static analyzer; advisory until someone extends lockorder's
        # definition extraction for that creation pattern
        print("\nlockcheck: %d observed edge(s) involve a lock with no "
              "static identity (analyzer naming drift):"
              % len(rep["unmapped_lock_edges"]))
        for line in rep["unmapped_lock_edges"]:
            print("  " + line)
