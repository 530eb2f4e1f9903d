"""Two-process DCN smoke test: jax.distributed over localhost.

``parallel/multihost.py`` is the multi-host entrypoint (one controller
process per host, coordinator over DCN).  This test actually exercises it:
two OS processes join a distributed job through
``multihost.initialize(coordinator_address="localhost:<port>")``, build
the global corpus mesh spanning both processes' devices (2 virtual CPU
devices each -> 4 global), run a psum/all_gather across the process
boundary, and execute the real sharded corpus scorer with the record axis
sharded across processes (see ``dcn_smoke_child.py``).  This is the
closest a single machine gets to the v5e multi-host deployment — same
code path, coordinator handshake, and collectives, with gRPC-over-
localhost standing in for DCN.
"""

import os
import socket
import subprocess
import sys

CHILD = os.path.join(os.path.dirname(__file__), "dcn_smoke_child.py")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_two_process_distributed_init_and_sharded_scoring():
    env = dict(os.environ)
    # children force their own platform/device-count; scrub the suite's
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    # _free_port closes its probe socket before the coordinator binds it —
    # a TOCTOU window another process can win on a busy host; retry once
    # with a fresh port so such a loss doesn't fail the test spuriously
    last = None
    for _ in range(2):
        coordinator = f"localhost:{_free_port()}"
        procs = [
            subprocess.Popen(
                [sys.executable, CHILD, str(pid), coordinator],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
                env=env, cwd=REPO,
            )
            for pid in (0, 1)
        ]
        outs = []
        try:
            for p in procs:
                out, err = p.communicate(timeout=240)
                outs.append((p.returncode, out, err))
        except subprocess.TimeoutExpired:
            for p in procs:
                p.kill()
            raise
        if all(rc == 0 and "DCN_OK" in out for rc, out, _ in outs):
            return
        last = outs
    for rc, out, err in last:
        assert rc == 0, f"child failed (rc={rc}):\n{err[-4000:]}"
        assert "DCN_OK" in out, (out, err[-2000:])


def _stub_join(monkeypatch, outcome):
    import jax

    from sesam_duke_microservice_tpu.parallel import multihost

    calls = []

    def join(*args, **kwargs):
        calls.append(kwargs)
        if isinstance(outcome, Exception):
            raise outcome

    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    monkeypatch.delenv("MEGASCALE_COORDINATOR_ADDRESS", raising=False)
    monkeypatch.setattr(jax.distributed, "initialize", join)
    monkeypatch.setattr(multihost, "_enable_cpu_collectives", lambda: None)
    return multihost, calls


def test_single_host_tpu_vm_joins_nothing(monkeypatch):
    """The v5e machines set TPU_WORKER_HOSTNAMES=localhost and
    TPU_WORKER_ID=0: a single-process run, not a job to join."""
    multihost, calls = _stub_join(monkeypatch, None)
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
    monkeypatch.setenv("TPU_WORKER_ID", "0")
    assert multihost.initialize() is False
    assert calls == []


def test_multi_host_slice_join_failure_raises(monkeypatch):
    """A slice that names several hosts joins; a failed join is not
    swallowed into a single-process run."""
    import pytest

    multihost, calls = _stub_join(monkeypatch, RuntimeError("unreachable"))
    monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "host0,host1")
    with pytest.raises(RuntimeError, match="unreachable"):
        multihost.initialize()
    assert calls == [{}]
