"""Shared-memory parallel feature extraction (VERDICT r4 #6).

Bulk ingest is host-bound Python glue: per-value string encoding, flat
list construction, gram/phonetic/class loops (ops.features).  The r4
parallel attempts documented WHY the obvious fan-outs lose: threads are
GIL-bound (the numpy/C bulk passes already release the GIL but no longer
dominate), and a process pool that returns tensors pays pickling + IPC
for ~1 KB/row both ways — 3-5x slower than serial.

This module keeps the process pool but deletes the expensive half of the
round trip: workers write their slice's feature tensors DIRECTLY into
``multiprocessing.shared_memory`` segments at their row offsets and
return nothing.  The input half (pickling the record slice in) is cheap
— records are a few hundred bytes of strings, ~5x smaller than their
extracted tensors.  Output shapes/dtypes are derived by running the
extractor on an EMPTY batch (no parallel re-implementation of the layout
to drift out of sync).

Workers are spawned (never forked: the parent holds live JAX/TPU runtime
threads) and import only numpy + the jax-free ops.features/ops.encoder
modules.  Env knobs (DEVICE_MAX_*) reach workers through inherited
environ, and the specs themselves ship per call, so auto-sized widths
are always current.

Enable: on by default for batches >= DEVICE_EXTRACT_PARALLEL_MIN (8192);
DEVICE_EXTRACT_WORKERS=0 disables.  Reference analog: the ingest fan-out
the reference gets from its servlet worker pool (App.java:231-236,344).
"""

from __future__ import annotations

import atexit
import os
import threading
from concurrent.futures import ProcessPoolExecutor
from multiprocessing import get_context, shared_memory
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..telemetry.env import env_int

_POOL: Optional[ProcessPoolExecutor] = None
_POOL_WORKERS = 0
# guards pool creation/replacement AND serializes map calls: two
# workloads extracting concurrently must not race the lazy init (leaked
# pool) or have a worker-count change terminate a pool mid-map
_POOL_LOCK = threading.Lock()


def workers() -> int:
    """Read at call time (not import) so tests/ops can retune live.  The
    default derives from the visible cores: on a single-core host (the
    bench environment here — nproc=1) every process pool loses by
    construction, exactly what the r4 measurements observed, so the
    pipeline self-disables; multi-core deployments get cores/2."""
    return env_int("DEVICE_EXTRACT_WORKERS", min(8, (os.cpu_count() or 1) // 2))


def min_records() -> int:
    """Smallest slab worth the process-pool fan-out.  Exported because the
    streaming-append slicer (engine.device_matcher) sizes its extract
    slices to at least this when the whole batch qualifies — slicing a
    bulk slab below it would silently forfeit the parallel path."""
    return env_int("DEVICE_EXTRACT_PARALLEL_MIN", 8192)


def enabled(n_records: int) -> bool:
    return workers() >= 2 and n_records >= min_records()


def _pool() -> ProcessPoolExecutor:
    """Call with _POOL_LOCK held.  ProcessPoolExecutor, not mp.Pool: a
    worker dying mid-task (OOM kill at slab scale) raises
    BrokenProcessPool from map() — which the caller's except clause
    turns into a serial fallback — where mp.Pool.map would block
    forever holding the workload lock."""
    global _POOL, _POOL_WORKERS
    w = workers()
    if _POOL is not None and _POOL_WORKERS != w:
        _shutdown_locked()
    if _POOL is None:
        _POOL = ProcessPoolExecutor(
            max_workers=w, mp_context=get_context("spawn"),
            initializer=_worker_init,
        )
        _POOL_WORKERS = w
        atexit.register(_shutdown)
    return _POOL


def _shutdown_locked() -> None:
    global _POOL
    if _POOL is not None:
        _POOL.shutdown(wait=False, cancel_futures=True)
        _POOL = None


def _shutdown() -> None:
    with _POOL_LOCK:
        _shutdown_locked()


def _worker_init() -> None:
    # workers never touch an accelerator: the parent holds the chip, and
    # a worker that reached for it would fail or hang.  Forced, not
    # defaulted — a parent started with JAX_PLATFORMS=tpu passes that on
    os.environ["JAX_PLATFORMS"] = "cpu"  # dukecheck: ignore[DK301] spawned-worker env WRITE, not a knob read


def _worker_extract(task) -> None:
    """Extract one record slice into the shared segments.  Runs in a
    spawned worker; returns None — the tensors travel via shm."""
    specs, encoder, values_by_prop, lo, layout = task
    from . import features as F

    handles = []
    try:
        for spec in specs:
            out = F.extract_property(spec, values_by_prop[spec.name])
            for tname, arr in out.items():
                _write(layout[(spec.name, tname)], lo, arr, handles)
        if encoder is not None:
            records = _records_from_values(values_by_prop, encoder.props)
            emb = encoder.encode_batch(records).astype(np.float32)
            _write(layout[("__ann__", "emb_f32")], lo, emb, handles)
    finally:
        for shm in handles:
            shm.close()


def _write(entry, lo: int, arr: np.ndarray, handles: list) -> None:
    shm_name, shape, dtype = entry
    shm = shared_memory.SharedMemory(name=shm_name)
    handles.append(shm)
    view = np.ndarray(shape, dtype=np.dtype(dtype), buffer=shm.buf)
    view[lo:lo + arr.shape[0]] = arr


def _records_from_values(values_by_prop: Dict[str, List[List[str]]],
                         props: Sequence[str]):
    """Rebuild minimal Record stand-ins for the encoder (it only reads
    ``_values``), so records themselves never ride the task pickle twice."""
    from ..core.records import Record

    n = len(next(iter(values_by_prop.values())))
    out = []
    for i in range(n):
        r = Record.__new__(Record)
        r._values = {
            prop: values_by_prop[prop][i]
            for prop in props
            if prop in values_by_prop and values_by_prop[prop][i]
        }
        out.append(r)
    return out


def extract_batch_parallel(plan, records, *, encoder=None
                           ) -> Optional[Dict[str, Dict[str, np.ndarray]]]:
    """Shared-memory fan-out of ``features.extract_batch``; returns None
    when the pool is unavailable (caller falls back to serial)."""
    from . import encoder as E
    from . import features as F

    n = len(records)
    nw = max(1, workers())
    per = -(-n // nw)

    # the task payload: per-property value lists (strings), not Record
    # objects — smaller pickles and no Record internals in the wire format
    empty: List[str] = []
    prop_names = [s.name for s in plan.device_props]
    if encoder is not None:
        prop_names = sorted(set(prop_names) | set(encoder.props))
    values_by_prop = {
        name: [r._values.get(name, empty) for r in records]
        for name in prop_names
    }

    # output layout from the extractor itself on an empty batch
    layout: Dict[tuple, tuple] = {}
    segments: List[shared_memory.SharedMemory] = []

    def alloc(key, shape, dtype) -> None:
        nbytes = max(1, int(np.prod(shape)) * np.dtype(dtype).itemsize)
        shm = shared_memory.SharedMemory(create=True, size=nbytes)
        segments.append(shm)
        layout[key] = (shm.name, shape, str(np.dtype(dtype)))

    try:
        try:
            for spec in plan.device_props:
                proto = F.extract_property(spec, [])
                for tname, arr in proto.items():
                    alloc((spec.name, tname), (n,) + arr.shape[1:],
                          arr.dtype)
            if encoder is not None:
                alloc(("__ann__", "emb_f32"), (n, encoder.dim), np.float32)
        except OSError:
            # /dev/shm too small for the slab (Docker defaults to 64 MB)
            # — the contract is a transparent serial fallback, never a
            # failed ingest request
            import logging

            logging.getLogger("parallel-extract").exception(
                "shared-memory allocation failed; falling back to serial"
            )
            return None

        tasks = []
        for w in range(nw):
            lo, hi = w * per, min(n, (w + 1) * per)
            if lo >= hi:
                break
            slice_values = {
                name: vals[lo:hi] for name, vals in values_by_prop.items()
            }
            tasks.append((plan.device_props, encoder, slice_values, lo,
                          layout))
        try:
            with _POOL_LOCK:
                # list() drains the generator so worker exceptions
                # (including BrokenProcessPool from a dead worker)
                # surface HERE, inside the fallback guard
                list(_pool().map(_worker_extract, tasks))
        except Exception:
            import logging

            logging.getLogger("parallel-extract").exception(
                "shared-memory extraction failed; falling back to serial"
            )
            _shutdown()
            return None

        out: Dict[str, Dict[str, np.ndarray]] = {}
        for spec in plan.device_props:
            out[spec.name] = {}
        for (prop, tname), (shm_name, shape, dtype) in layout.items():
            shm = next(s for s in segments if s.name == shm_name)
            view = np.ndarray(shape, dtype=np.dtype(dtype), buffer=shm.buf)
            if (prop, tname) == ("__ann__", "emb_f32"):
                # parent-side storage conversion (workers always emit
                # f32): bf16 cast, or int8 quantization + scale vector
                # under DUKE_EMB_INT8 — the ONE conversion point shared
                # with the serial path (ops.encoder.corpus_tensors_from_f32)
                out[E.ANN_PROP] = E.corpus_tensors_from_f32(
                    view, encoder.storage
                )
            else:
                out[prop][tname] = view.copy()
        return out
    finally:
        for shm in segments:
            shm.close()
            try:
                shm.unlink()
            except FileNotFoundError:
                pass
