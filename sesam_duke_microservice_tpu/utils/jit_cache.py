"""Compiled-program caching: persistent XLA cache + plan-keyed AOT store.

The matcher's jitted programs recompile on corpus-capacity growth and
candidate-K escalation (O(log N) distinct shapes over a corpus's lifetime,
engine.device_matcher).  On TPU each compile costs tens of seconds, which
dominates cold-start and first-contact-with-new-shape latency.  Two
layers remove that cost (the service counterpart of the reference
reopening its Lucene index in APPEND mode instead of rebuilding —
IncrementalLuceneDatabase.java:233-244 — applied to compiled programs
instead of data):

  * ``enable_persistent_cache`` points jax's persistent compilation
    cache at disk, so an XLA *compile* of an already-seen program is a
    cache read.  The first contact with a shape still pays trace +
    lower + cache lookup.
  * ``AotStore`` (ISSUE 15) goes further: whole compiled executables —
    serialized via ``jax.experimental.serialize_executable`` — persist
    on disk keyed by (plan fingerprint, shape tuple, backend,
    jax/jaxlib version, scoring-code hash).  A restart *deserializes*
    the scorer ladder instead of compiling it: zero traces, zero XLA
    invocations before the first scoring batch
    (``tests/test_aot_cache.py`` pins restart-compiles-zero via the
    ``JIT_COMPILES`` counter).

Invalidation is entirely key-derivation: any change to the feature plan
(widths, comparators, probabilities), the ladder geometry (chunk, K,
buckets), the backend/device kind, the jax/jaxlib version, or the
scoring source itself produces a different key — a stale entry is never
*wrong*, only unreachable (and the warm thread re-fills the new key).
Entries that exist but fail to deserialize (foreign runtime, torn file
predating atomic writes, PJRT drift) count as ``reject`` and fall back
to the compile path.
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import threading
from typing import Dict, List, Optional

from ..telemetry import AOT_LOADS, GLOBAL, JIT_CACHE_HITS, JIT_COMPILES
from ..telemetry.env import env_flag, env_float, env_str
from ..telemetry.registry import FamilySnapshot

logger = logging.getLogger("jit-cache")

# Without JAX_COMPILATION_CACHE_DIR the caches live at one fixed,
# git-ignored path inside the checkout: the path is part of the cache key,
# so it must not move between runs, and the program writes nothing outside
# its checkout.
_DEFAULT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__)))),
    ".jax_cache",
)

# AOT-load outcome children pre-resolved at import (closed label set,
# same DK501 discipline as the device matcher's bucket children)
_AOT_HIT = AOT_LOADS.labels(outcome="hit")  # dukecheck: ignore[DK501] init-time pre-resolution
_AOT_MISS = AOT_LOADS.labels(outcome="miss")  # dukecheck: ignore[DK501] init-time pre-resolution
_AOT_REJECT = AOT_LOADS.labels(outcome="reject")  # dukecheck: ignore[DK501] init-time pre-resolution


def record_compile(n: int = 1) -> None:
    """Count a program build (in-process jit-cache miss or pre-warm AOT
    compile).  A recompile storm — capacity doublings or K-escalation
    racing through new shapes — shows as this counter climbing while
    ``duke_jit_cache_hits_total`` stalls."""
    JIT_COMPILES.inc(n)


def record_cache_hit(n: int = 1) -> None:
    """Count a scorer lookup served from the in-process program cache
    (jitted-function reuse or a registered AOT executable)."""
    JIT_CACHE_HITS.inc(n)


def record_aot_reject(n: int = 1) -> None:
    """Count a registered AOT executable rejected at call time (shape
    drift after it was built) — the caller falls back to the jit path."""
    _AOT_REJECT.inc(n)


def cache_dir() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` when set, else the checkout's
    ``.jax_cache``."""
    return env_str("JAX_COMPILATION_CACHE_DIR") or _DEFAULT


def enable_persistent_cache() -> Optional[str]:
    """Point jax at the on-disk compilation cache (``cache_dir()``);
    returns the path used.

    Safe to call multiple times; a failure (read-only fs) only logs —
    the cache is an optimization, never a requirement.

    ``DUKE_JIT_CACHE_MIN_SECS`` sets the persistence floor (jax's
    ``jax_persistent_cache_min_compile_time_secs``).  The historical
    hard-coded 1.0 s meant CPU-lowered programs — which compile in
    milliseconds — never persisted, so the cache path was untestable in
    CI; tests and CPU deployments set it to 0.
    """
    import jax

    path = cache_dir()
    try:
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs",
            env_float("DUKE_JIT_CACHE_MIN_SECS", 1.0),
        )
        return path
    except OSError as exc:  # pragma: no cover - depends on the fs
        logger.warning("persistent compilation cache disabled: %s", exc)
        return None


# -- plan-keyed AOT executable store (ISSUE 15) -------------------------------


def aot_enabled() -> bool:
    """``DUKE_AOT`` gates the executable store (default on); =0 pins the
    legacy jit-only path exactly (the CI opt-out leg)."""
    return env_flag("DUKE_AOT", True)


def aot_dir() -> str:
    return env_str("DUKE_AOT_DIR") or os.path.join(cache_dir(), "aot")


_CODE_FP: Optional[str] = None


def code_fingerprint() -> str:
    """Hash of the scoring-relevant sources (the ``ops`` package plus
    the matcher modules).  Any edit to kernel/scoring/feature code
    yields new store keys, so an AOT entry can never serve HLO compiled
    from different source — the invalidation rule README documents.
    Computed once per process."""
    global _CODE_FP
    if _CODE_FP is None:
        from .. import core, engine, ops

        h = hashlib.sha256()
        roots = [os.path.dirname(ops.__file__),
                 os.path.dirname(engine.__file__),
                 os.path.dirname(core.__file__)]
        for root in roots:
            for name in sorted(os.listdir(root)):
                if not name.endswith(".py"):
                    continue
                with open(os.path.join(root, name), "rb") as f:
                    h.update(name.encode("utf-8"))
                    h.update(f.read())
        _CODE_FP = h.hexdigest()[:16]
    return _CODE_FP


def environment_fingerprint() -> dict:
    """The runtime facets a serialized executable is only valid under:
    backend platform + device kind (a CPU executable must never load
    into a TPU process and vice versa), jax/jaxlib versions (PJRT
    serialization formats drift), and the XLA flags (they change
    codegen, e.g. the forced host device count)."""
    import jax
    import jaxlib

    devices = jax.devices()
    return {
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "platform": jax.default_backend(),
        "device_kind": devices[0].device_kind if devices else "none",
        "device_count": len(devices),
        "xla_flags": env_str("XLA_FLAGS", "") or "",
        "code": code_fingerprint(),
    }


def mesh_fingerprint(mesh) -> dict:
    """The mesh facets a sharded executable is only valid under: device
    grid shape and axis names.  These join the store KEY (not the env
    fingerprint — single-device and mesh workloads share a process, and
    ``device_count`` alone cannot distinguish a 4-way from an 8-way mesh
    on the same 8-device host), so an executable partitioned for one
    topology is unreachable from any other.

    In a multi-process job the facets also carry this controller's
    process coordinates: every process of the job shares the store
    directory and derives otherwise-identical keys, but a serialized
    executable embeds the saving process's device assignment — process 0
    must never deserialize process 1's artifact."""
    import jax

    doc = {
        "shape": [int(s) for s in mesh.devices.shape],
        "axes": [str(a) for a in mesh.axis_names],
    }
    if jax.process_count() > 1:
        doc["proc"] = [jax.process_index(), jax.process_count()]
    return doc


def _devices(ids: List[int]) -> list:
    """The visible devices with these ids, in this order (KeyError when
    one is missing — the caller's reject path)."""
    import jax

    by_id = {d.id: d for d in jax.devices()}
    return [by_id[i] for i in ids]


class AotStore:
    """On-disk store of serialized compiled executables.

    One file per (plan, shape, backend, version) key: the key dict is
    canonical-JSON-hashed into the filename, and the file holds a pickle
    of ``(key, payload, in_tree, out_tree, device_ids)`` where
    payload/trees come from ``jax.experimental.serialize_executable
    .serialize`` and ``device_ids`` are the devices the executable was
    compiled for: it is loaded onto exactly those, not onto every
    visible device.  Writes
    are crash-atomic (``utils.atomicio``); concurrent savers of the same
    key race benignly (identical content, last replace wins).  No lock:
    load/save are pure file ops keyed by immutable content.
    """

    def __init__(self, root: Optional[str] = None):
        self.root = root or aot_dir()
        self._env = None  # environment fingerprint, resolved lazily

    def _key_doc(self, key: dict) -> dict:
        if self._env is None:
            self._env = environment_fingerprint()
        doc = dict(key)
        doc["__env__"] = self._env
        return doc

    def _path(self, key: dict) -> str:
        blob = json.dumps(self._key_doc(key), sort_keys=True,
                          separators=(",", ":"), default=str)
        digest = hashlib.sha256(blob.encode("utf-8")).hexdigest()
        return os.path.join(self.root, digest + ".aotx")

    def save(self, key: dict, compiled) -> bool:
        """Serialize ``compiled`` under ``key``; False (logged once per
        cause) when the backend/executable does not support
        serialization — saving is an optimization, never a requirement."""
        from jax.experimental import serialize_executable as se

        from .atomicio import atomic_write_bytes

        try:
            payload, in_tree, out_tree = se.serialize(compiled)
            device_ids = [d.id for d in
                          compiled._executable._unloaded_executable
                          .device_list]
            # round-trip validation BEFORE the write: an executable whose
            # XLA compile was served from jax's persistent compilation
            # cache serializes THIN (the payload references jit symbols
            # it does not carry — observed as "Symbols not found" at
            # deserialize).  Persisting one would reject on every future
            # restart; refusing the save leaves the entry to a fresh
            # compile instead (the warm thread compiles cache-bypassed
            # for exactly this reason).
            se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=_devices(device_ids))
            blob = pickle.dumps(
                (self._key_doc(key), payload, in_tree, out_tree,
                 device_ids),
                protocol=pickle.HIGHEST_PROTOCOL,
            )
            os.makedirs(self.root, exist_ok=True)
            atomic_write_bytes(self._path(key), blob)
            return True
        except Exception as exc:
            logger.warning("AOT executable save failed for %s: %s",
                           key, exc)
            return False

    def load(self, key: dict):
        """Deserialize the executable stored under ``key``, or None.

        Outcomes land in ``duke_aot_loads_total``: hit (loaded), miss
        (no file), reject (file present but key-mismatched or
        undeserializable — deleted so the warm thread's re-save isn't
        rejected forever)."""
        from jax.experimental import serialize_executable as se

        path = self._path(key)
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError:
            _AOT_MISS.inc()
            return None
        try:
            (stored_key, payload, in_tree, out_tree,
             device_ids) = pickle.loads(blob)
            if stored_key != self._key_doc(key):
                raise ValueError("stored key mismatch (hash collision?)")
            loaded = se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=_devices(device_ids))
        except Exception as exc:
            _AOT_REJECT.inc()
            logger.warning(
                "rejecting AOT executable %s (%s); it will be recompiled "
                "and re-saved", path, exc)
            try:
                os.unlink(path)
            except OSError:
                pass
            return None
        _AOT_HIT.inc()
        return loaded


# -- shared in-process AOT ladders (ISSUE 19 tentpole b) ----------------------


def shared_aot_enabled() -> bool:
    """``DUKE_SHARED_AOT`` gates the cross-workload ladder sharing
    (default on); =0 pins the per-workload registration maps exactly."""
    return env_flag("DUKE_SHARED_AOT", True)


class SharedLadder:
    """One refcounted (plan fingerprint, geometry) executable ladder.

    ``map`` is the scorer caches' ``_aot`` registration dict — the same
    lock-free akey->executable contract as before, now pointed at by
    every tenant on the schema.  ``warm_lock`` serializes the tenants'
    warm threads over the ladder so N same-schema tenants pay ONE warm
    compile per entry (the losers find the entry present and skip).
    ``refs`` is guarded by the registry lock."""

    __slots__ = ("key", "map", "refs", "warm_lock")

    def __init__(self, key: tuple):
        self.key = key
        self.map: Dict[tuple, object] = {}
        self.refs = 0  # guarded by: self._lock (the registry's — SharedLadder has no lock of its own)
        self.warm_lock = threading.Lock()


class SharedLadderRegistry:
    """Process-wide (fingerprint, geometry) -> :class:`SharedLadder` map.

    The on-disk :class:`AotStore` already dedupes by plan fingerprint;
    this is the in-process counterpart: N tenants with identical keys
    share one registration map (and so one warm pass and one set of
    live executables) instead of compiling N ladders.  Release is
    refcounted — the PR 14 plan-mutation eviction seam releases the
    tenant's lease, and the LAST tenant off a plan drops the ladder and
    its executables (the refcounted evict)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: Dict[tuple, SharedLadder] = {}  # guarded by: self._lock

    def acquire(self, key: tuple) -> SharedLadder:
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                entry = self._entries[key] = SharedLadder(key)
            entry.refs += 1
            return entry

    def release(self, entry: Optional[SharedLadder]) -> None:
        if entry is None:
            return
        with self._lock:
            entry.refs -= 1
            if entry.refs <= 0:
                self._entries.pop(entry.key, None)

    def executables(self) -> List[object]:
        """Every registered executable across the live ladders."""
        with self._lock:
            return [exe for e in self._entries.values()
                    for exe in list(e.map.values())]

    def stats(self) -> Dict[str, int]:
        """{ladders, refs, executables} — bench/debug surface."""
        with self._lock:
            entries = list(self._entries.values())
            return {
                "ladders": len(entries),
                "refs": sum(e.refs for e in entries),
                "executables": sum(len(e.map) for e in entries),
            }

    def _reset_for_tests(self) -> None:
        with self._lock:
            self._entries.clear()


SHARED_LADDERS = SharedLadderRegistry()


def release_shared_lease(holder: List[Optional[SharedLadder]]) -> None:
    """weakref.finalize target for a scorer cache's lease holder: the
    cache dying (workload reload/close) must release its ref so the
    last tenant off a schema actually evicts the shared ladder."""
    lease, holder[0] = holder[0], None
    SHARED_LADDERS.release(lease)


def _collect_shared() -> List[FamilySnapshot]:
    """Scrape-time collector (registered on ``telemetry.GLOBAL``)."""
    stats = SHARED_LADDERS.stats()
    return [
        FamilySnapshot(
            "duke_aot_shared_refs", "gauge",
            "Scorer caches currently leasing a shared AOT ladder "
            "(tenants sharing compiled executables by plan fingerprint "
            "+ geometry)",
            [("", (), float(stats["refs"]))]),
    ]


GLOBAL.register_collector(_collect_shared)
