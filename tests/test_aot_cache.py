"""Plan-keyed AOT executable cache (ISSUE 15).

The acceptance contract: a process restart against a populated AOT store
performs ZERO scorer compiles before serving its first scoring batch —
pinned via the ``JIT_COMPILES`` counter in a real two-process
differential — with the event stream bit-identical to the cold run.
Plus the store's key-derivation/invalidation semantics, the call-time
reject fallback, the ``DUKE_JIT_CACHE_MIN_SECS`` knob, and the pre-warm
failure latch.
"""

import json
import os
import pickle
import subprocess
import sys

import numpy as np
import pytest

from sesam_duke_microservice_tpu import telemetry
from sesam_duke_microservice_tpu.utils.jit_cache import (
    AotStore,
    aot_enabled,
    enable_persistent_cache,
    environment_fingerprint,
)

CHILD = os.path.join(os.path.dirname(__file__), "aot_restart_child.py")


def _run_child(aot_dir, xla_dir, *, prewarm="1", aot="1"):
    env = dict(os.environ)
    env.update({
        "DEVICE_CHUNK": "64",
        # one bucket keeps the ladder at 4 entries (2 caps x 2 variants)
        # so the cold arm stays fast on the CPU backend
        "DEVICE_QUERY_BUCKETS": "8",
        "DEVICE_TOP_K": "16",
        "DEVICE_MAX_CHARS": "24",
        "DEVICE_MAX_GRAMS": "24",
        "DEVICE_PREWARM": prewarm,
        "DUKE_AOT": aot,
        "DUKE_AOT_DIR": str(aot_dir),
        "JAX_COMPILATION_CACHE_DIR": str(xla_dir),
        "DUKE_JIT_CACHE_MIN_SECS": "0",
    })
    proc = subprocess.run(
        [sys.executable, CHILD], capture_output=True, text=True,
        timeout=600, env=env,
    )
    assert proc.returncode == 0, proc.stderr
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


def test_restart_compiles_zero_scorers(tmp_path):
    """THE acceptance differential: process 1 compiles + serializes the
    ladder; process 2 deserializes everything — zero compiles through
    its first scoring batch (and after the warm-thread join too), same
    events."""
    aot_dir, xla_dir = tmp_path / "aot", tmp_path / "xla"
    cold = _run_child(aot_dir, xla_dir)
    assert cold["warm_compiled"] == 4, cold  # 2 caps x 1 bucket x 2 variants
    assert cold["jit_compiles"] >= 4
    saved = list(aot_dir.glob("*.aotx"))
    assert len(saved) == 4, saved

    warm = _run_child(aot_dir, xla_dir)
    assert warm["jit_compiles_at_first_batch"] == 0, warm
    assert warm["jit_compiles"] == 0, warm  # no miss-fill ran either
    assert warm["aot_loaded"] == 4
    assert warm["warm_compiled"] == 0
    # the scoring outcome is the same program: bit-identical events
    assert warm["events"] == cold["events"]
    # the dispatched blocks were served as program-cache hits
    assert warm["jit_cache_hits"] >= 1


def test_aot_off_leg_still_serves(tmp_path):
    """DUKE_AOT=0 pins the legacy jit path: nothing saved, restart
    compiles again, events unchanged."""
    aot_dir, xla_dir = tmp_path / "aot", tmp_path / "xla"
    cold = _run_child(aot_dir, xla_dir)
    off = _run_child(aot_dir, xla_dir, aot="0")
    assert off["aot_loaded"] == 0
    assert off["jit_compiles"] > 0
    assert off["events"] == cold["events"]


def test_store_roundtrip_and_key_isolation(tmp_path, monkeypatch):
    """Save/load round-trip of a real executable; a different key
    misses; a corrupt entry rejects (counted) and is deleted."""
    import jax
    import jax.numpy as jnp

    monkeypatch.setenv("DUKE_AOT_DIR", str(tmp_path / "store"))
    store = AotStore()
    fn = jax.jit(lambda x: (x * 2.0).sum())
    compiled = fn.lower(
        jax.ShapeDtypeStruct((8,), jnp.float32)).compile()
    key = {"builder": "test", "cap": 8}
    hit0 = telemetry.AOT_LOADS.labels(outcome="hit").value
    miss0 = telemetry.AOT_LOADS.labels(outcome="miss").value
    rej0 = telemetry.AOT_LOADS.labels(outcome="reject").value

    assert store.save(key, compiled) is True
    loaded = store.load(key)
    assert loaded is not None
    out = loaded(np.arange(8, dtype=np.float32))
    assert float(out) == float(compiled(np.arange(8, dtype=np.float32)))
    assert telemetry.AOT_LOADS.labels(outcome="hit").value == hit0 + 1

    # a different key is a different entry: miss
    assert store.load({"builder": "test", "cap": 16}) is None
    assert telemetry.AOT_LOADS.labels(outcome="miss").value == miss0 + 1

    # corrupt the entry: reject, counted, file deleted so a re-save can
    # land
    path = store._path(key)
    with open(path, "wb") as f:
        f.write(b"garbage")
    assert store.load(key) is None
    assert telemetry.AOT_LOADS.labels(outcome="reject").value == rej0 + 1
    assert not os.path.exists(path)

    # a stored-key mismatch under the same filename also rejects
    store.save(key, compiled)
    blob = pickle.loads(open(path, "rb").read())
    doctored = ({"not": "the-key"},) + blob[1:]
    with open(path, "wb") as f:
        f.write(pickle.dumps(doctored))
    assert store.load(key) is None


def test_env_fingerprint_keys_the_path(tmp_path, monkeypatch):
    """Same logical key, different environment fingerprint -> different
    file: a cross-version/cross-backend entry is unreachable, never
    wrong."""
    monkeypatch.setenv("DUKE_AOT_DIR", str(tmp_path))
    a = AotStore()
    b = AotStore()
    b._env = dict(environment_fingerprint())
    b._env["jax"] = "some-other-version"
    key = {"builder": "test", "cap": 8}
    assert a._path(key) != b._path(key)


def test_call_time_reject_falls_back_to_jit(monkeypatch):
    """A registered executable that raises (plan drift after it was
    built) is dropped — counted as a reject — and the jit path serves
    the block; scoring output is unaffected."""
    from test_device_matcher import EventLog, dedup_schema, random_records

    from sesam_duke_microservice_tpu.engine.device_matcher import (
        DeviceIndex,
        DeviceProcessor,
    )

    schema = dedup_schema()
    index = DeviceIndex(schema)
    processor = DeviceProcessor(schema, index, group_filtering=False)
    log = EventLog()
    processor.add_match_listener(log)
    records = random_records(24, seed=7)
    processor.deduplicate(records)
    baseline = list(log.events)

    cache = index.scorer_cache

    def broken(*args):
        raise TypeError("shape drift")

    rej0 = telemetry.AOT_LOADS.labels(outcome="reject").value
    # poison EVERY shape the next batch could dispatch on
    from sesam_duke_microservice_tpu.engine import device_matcher as DM

    cap = index.corpus.capacity
    poisoned = []
    for bucket in DM._QUERY_BUCKETS:
        for from_rows in (True, False):
            akey = (cache._ladder_k(cap), False, from_rows, cap, bucket)
            cache._aot[akey] = broken
            poisoned.append(akey)

    log.events.clear()
    processor.deduplicate(records)  # identical re-ingest: same events
    assert log.events == baseline
    assert telemetry.AOT_LOADS.labels(outcome="reject").value > rej0
    # the dispatched shape's poisoned entry was dropped
    assert any(k not in cache._aot for k in poisoned)


def test_plan_mutation_evicts_registered_executables(monkeypatch):
    """A live plan mutation (value-slot/char growth) re-keys the warm
    fingerprint; registered executables built for the OLD shapes must be
    evicted — a stale entry would otherwise occupy its akey slot, block
    the load pass from refilling it, and die at dispatch as a reject
    with no refill path.  A capacity-only change keeps the map."""
    from test_device_matcher import dedup_schema

    from sesam_duke_microservice_tpu.engine.device_matcher import (
        DeviceIndex,
    )

    monkeypatch.setenv("DEVICE_PREWARM", "0")  # no background compiles
    schema = dedup_schema()
    index = DeviceIndex(schema)
    cache = index.scorer_cache
    cache.prewarm_async(False)
    key0 = cache._warmed
    assert key0 is not None
    sentinel = object()
    cache._aot[(16, False, True, 64, 8)] = sentinel

    # capacity-only change: entries survive (old-cap keys are merely
    # unreachable)
    cache._warmed = (key0[0] * 2,) + key0[1:]
    cache._warmed, moved = key0, cache._warmed
    cache._warmed = moved
    cache.prewarm_async(False)  # back to key0's cap via live corpus
    assert cache._aot.get((16, False, True, 64, 8)) is sentinel

    # plan-shape change: widen one spec's char tensors -> evicted
    index.plan.device_props[0].max_chars = (
        index.plan.device_props[0].chars * 2)
    cache.prewarm_async(False)
    assert cache._warmed != key0
    assert (16, False, True, 64, 8) not in cache._aot


def test_jit_cache_min_secs_knob(tmp_path, monkeypatch):
    """DUKE_JIT_CACHE_MIN_SECS feeds jax's persistence floor (the
    hard-coded 1.0 s meant CPU programs never persisted — untestable in
    CI)."""
    import jax

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("DUKE_JIT_CACHE_MIN_SECS", "0.25")
    assert enable_persistent_cache() == str(tmp_path)
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.25
    monkeypatch.setenv("DUKE_JIT_CACHE_MIN_SECS", "not-a-number")
    enable_persistent_cache()  # malformed -> fail-to-default, no raise
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 1.0


def test_caches_follow_jax_compilation_cache_dir(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR places both caches; without it they sit
    at one fixed, git-ignored directory inside the checkout."""
    from sesam_duke_microservice_tpu.utils.jit_cache import (
        aot_dir,
        cache_dir,
    )

    monkeypatch.delenv("DUKE_AOT_DIR", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert cache_dir() == str(tmp_path)
    assert aot_dir() == os.path.join(str(tmp_path), "aot")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert cache_dir() == os.path.join(checkout, ".jax_cache")
    assert aot_dir() == os.path.join(checkout, ".jax_cache", "aot")
    with open(os.path.join(checkout, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_aot_enabled_knob(monkeypatch):
    monkeypatch.delenv("DUKE_AOT", raising=False)
    assert aot_enabled() is True
    monkeypatch.setenv("DUKE_AOT", "0")
    assert aot_enabled() is False


def test_prewarm_failure_counted_and_surfaced(monkeypatch):
    """A warm-thread failure increments duke_prewarm_failures_total and
    latches the error for /healthz detail (a silently-cold replica must
    be diagnosable)."""
    from test_device_matcher import dedup_schema

    from sesam_duke_microservice_tpu.engine.device_matcher import (
        DeviceIndex,
    )

    schema = dedup_schema()
    index = DeviceIndex(schema)
    cache = index.scorer_cache
    fail0 = telemetry.PREWARM_FAILURES.single().value

    monkeypatch.setattr(
        type(cache), "_lower_one",
        lambda self, *a, **k: (_ for _ in ()).throw(
            RuntimeError("boom: no HBM left")),
    )
    # drive the warm body synchronously (thread scheduling out of the
    # assertion path)
    key = (64, tuple(), False)
    cache._warmed = key
    cache._prewarm(False, key, missing=[(64, 8, True)])
    assert telemetry.PREWARM_FAILURES.single().value == fail0 + 1
    assert cache._warm_error is not None
    assert "boom" in cache._warm_error


def test_prewarm_error_in_healthz(tmp_path, monkeypatch):
    """app.prewarm_errors() names the workload and the latched error —
    the /healthz detail surface."""
    from test_crash_recovery import _durable_app

    app = _durable_app(tmp_path, backend="ann")
    try:
        wl = app.deduplications["people"]
        cache = getattr(wl.index, "scorer_cache", None)
        assert cache is not None
        assert app.prewarm_errors() == {}
        cache._warm_error = "RuntimeError('boom')"
        errs = app.prewarm_errors()
        assert errs == {"deduplication/people": "RuntimeError('boom')"}
    finally:
        app.close()


@pytest.mark.skipif(
    os.environ.get("DEVICE_QUERY_BUCKETS") is None,
    reason="needs the conftest small-shape env")
def test_in_process_warm_registers_executables():
    """Within ONE process, warm-thread compiles register for the
    dispatch fast path too (first contact skips the live jit trace)."""
    from test_device_matcher import dedup_schema

    from sesam_duke_microservice_tpu.engine.device_matcher import (
        DeviceIndex,
    )

    schema = dedup_schema()
    index = DeviceIndex(schema)
    cache = index.scorer_cache
    assert cache.supports_aot is True
    # the ladder enumeration covers the speculative next doubling and
    # both variants
    ladder = cache._ladder(64)
    caps = {c for c, _, _ in ladder}
    assert caps == {64, 128}
    assert {fr for _, _, fr in ladder} == {True, False}
