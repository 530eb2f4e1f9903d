"""Multi-host scale-out: jax.distributed over DCN + mesh construction.

The reference's only "distribution" is the Sesam node HTTP-polling one
microservice container (SURVEY.md section 2 component #16).  The TPU-native
replacement for real scale is the standard JAX multi-controller model: one
Python process per host, ``jax.distributed.initialize`` over the
coordinator (DCN), and a global mesh whose record-sharding axis spans every
chip in the job.

Layout policy for this workload (corpus-sharded matching,
parallel/sharded.py + parallel/ann_sharded.py):

  * the corpus axis shards over ALL devices, hosts included — each chip
    holds ``N / total_chips`` rows and scores the replicated query block
    against them locally;
  * the only cross-device traffic is the per-shard top-K ``all_gather``
    ((D, Q, K), K tiny).  Within a slice it rides ICI; across slices the
    same collective rides DCN.  Because the merge payload is O(Q x K) per
    device — not O(corpus) — DCN bandwidth is not a bottleneck, so a flat
    1-D mesh is the right default (no need for the hierarchical
    ICI-inner/DCN-outer factorization a bandwidth-bound workload needs);
  * ingest is single-writer per workload (the service's lock discipline,
    SURVEY.md section 1 L5): the frontend host extracts features and
    ``device_put``s each shard slice; queries replicate.

``initialize()`` wraps ``jax.distributed.initialize`` with env-var
defaults (JAX_COORDINATOR_ADDRESS, JAX_NUM_PROCESSES, JAX_PROCESS_ID) and
is a no-op for single-process runs, so the same entrypoint works on a
laptop, one TPU VM, or a multi-host slice job.
"""

from __future__ import annotations

import logging
from typing import Optional

from ..telemetry.env import env_str

logger = logging.getLogger("multihost")


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> bool:
    """Join the multi-controller job when one is configured; returns True
    if distributed.

    A job is configured by the arguments, by the standard env vars
    (JAX_COORDINATOR_ADDRESS, JAX_NUM_PROCESSES, JAX_PROCESS_ID), or by a
    Cloud TPU multi-host slice, whose metadata jax reads itself: one that
    names more than one host in TPU_WORKER_HOSTNAMES, or a multislice job
    (MEGASCALE_COORDINATOR_ADDRESS).  A single-host TPU VM sets
    TPU_WORKER_HOSTNAMES=localhost and TPU_WORKER_ID=0; it, a laptop and
    CI are single-process runs and join nothing.  A configured join that
    fails raises: a silent local-only mesh would serve partial-corpus
    results.
    """
    import jax

    # idempotent: the service entrypoint initializes once, then every
    # sharded index construction calls through serving_mesh() again — a
    # second jax.distributed.initialize would raise ("must be called
    # before any JAX calls") because the first one already brought the
    # backend up
    from jax._src import distributed as _dist

    if _dist.global_state.client is not None:
        return jax.process_count() > 1

    coordinator_address = coordinator_address or env_str(
        "JAX_COORDINATOR_ADDRESS"
    )
    if coordinator_address is None:
        hosts = [h for h in (env_str("TPU_WORKER_HOSTNAMES") or "").split(",")
                 if h.strip()]
        if len(hosts) <= 1 and not env_str("MEGASCALE_COORDINATOR_ADDRESS"):
            return False
        _enable_cpu_collectives()
        jax.distributed.initialize()
        logger.info(
            "joined auto-detected distributed job: process %d/%d",
            jax.process_index(), jax.process_count(),
        )
        return True
    kwargs = {"coordinator_address": coordinator_address}
    num_processes = num_processes or _int_env("JAX_NUM_PROCESSES")
    process_id = process_id if process_id is not None else _int_env(
        "JAX_PROCESS_ID"
    )
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    logger.info("jax.distributed.initialize(%s)", kwargs)
    _enable_cpu_collectives()
    jax.distributed.initialize(**kwargs)
    logger.info(
        "joined distributed job: process %d/%d, %d local / %d global devices",
        jax.process_index(), jax.process_count(),
        jax.local_device_count(), jax.device_count(),
    )
    return True


def _int_env(name: str) -> Optional[int]:
    raw = env_str(name)
    return int(raw) if raw and raw.isdigit() else None


def _enable_cpu_collectives() -> None:
    """XLA:CPU only runs cross-process programs through an explicit
    collectives layer; without one every multi-process computation —
    including ``device_put`` onto a global-mesh sharding — fails with
    "Multiprocess computations aren't implemented on the CPU backend".
    Select gloo before the distributed client comes up (the backend
    captures the option at client init).  TPU/GPU backends ignore it, so
    this is safe to set unconditionally for any distributed job."""
    import jax

    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except Exception as exc:  # pragma: no cover - jaxlib built without gloo
        logger.warning(
            "CPU collectives backend unavailable (%s); multi-process CPU "
            "meshes will not run", exc)


def global_corpus_mesh():
    """1-D corpus mesh over every device in the job (all hosts).

    Single-host this equals ``corpus_mesh()``; multi-host it spans the
    global device list, so the record axis shards across hosts and the
    top-K merge collective crosses DCN transparently.
    """
    import jax

    from .sharded import corpus_mesh

    return corpus_mesh(jax.devices())
