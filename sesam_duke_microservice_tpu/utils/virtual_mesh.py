"""Virtual CPU mesh provisioning for hosts without enough chips.

THE one copy of the re-exec recipe (`JAX_PLATFORMS=cpu` +
``--xla_force_host_platform_device_count=N`` + an inner-guard env var)
that `tests/conftest.py` pioneered: the sharded benchmarks
(`benchmarks/large_scale.py`, `benchmarks/ring_rematch_bench.py`,
`bench.py`'s mesh section) validate N-way programs on single-chip hosts
by re-exec'ing themselves in a child with these settings.  The env must
be set before the child's interpreter starts (jax reads it at init).
"""

from __future__ import annotations

import os
from typing import Dict


def virtual_mesh_env(n_devices: int, inner_flag: str) -> Dict[str, str]:
    """Child-process env for an ``n_devices`` virtual CPU mesh.

    ``inner_flag`` is the guard the child checks to know it has been
    re-exec'd (so it provisions instead of re-exec'ing again).
    """
    env = dict(os.environ)  # dukecheck: ignore[DK301] child-process env composition, not a knob read
    env[inner_flag] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    flags = " ".join(
        f for f in env.get("XLA_FLAGS", "").split()
        if "xla_force_host_platform_device_count" not in f
    )
    env["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n_devices}".strip()
    )
    return env
