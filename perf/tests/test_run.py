"""The harness end to end on the CPU: it refuses without a TPU, a tiny
run through the test-only path reaches its result line, the control
reads not correct, and so does each fault planted under the timed path."""

import json
import os
import subprocess
import sys

import pytest

from conftest import TINY

import run

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_refuses_without_tpu():
    env = {k: v for k, v in os.environ.items()
           if k not in run.SMALL_SHAPE_KNOBS}
    proc = subprocess.run(
        [sys.executable, os.path.join(PERF, "run.py"), "--workload",
         "dedup-resync", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "needs 1 TPU" in proc.stderr
    assert proc.stdout.strip() == ""


def _cells():
    with open(os.path.join(os.path.dirname(PERF), "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


@pytest.fixture(params=_cells())
def cell(request):
    return request.param


def test_tiny_run_reaches_the_result_line(cell):
    result = run.run_cell(cell, 2**31 + 7, 4, False, require_tpu=False,
                          overrides=TINY, control=True)
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "setup_s" in result["metrics"]
    assert list(result)[-1] == "compared"
    json.dumps(result)
    # the control: the reference in float32 in the program's place
    control = result["control"]
    from compare import is_correct
    assert not is_correct(control), control


def test_every_run_posts_the_same_warm_up():
    """A warm checkout's run posts as many warm-up rounds as a cold one's:
    the window's scan starts at the same device row either way."""
    run.run_cell("dedup-resync", 2**31 + 8, 2, False, require_tpu=False,
                 overrides=TINY)
    with open(os.path.join(run.RUN_DIR, "report.json")) as f:
        phases = {p["phase"] for p in json.load(f)["posts"]}
    rounds = {p for p in phases if p.startswith("warm-")}
    assert len(rounds) >= run.WARM_ROUNDS_MIN, sorted(phases)


def _run(monkeypatch, cell, target, replacement):
    monkeypatch.setattr(target[0], target[1], replacement)
    return run.run_cell(cell, 99, 4, False, require_tpu=False,
                        overrides=TINY)


def test_fault_state_unchanged(monkeypatch, cell):
    """The POST is acknowledged and nothing is applied."""
    from sesam_duke_microservice_tpu.engine.workload import Workload

    def ack_only(self, work):
        for req in work:
            req.event.set()

    result = _run(monkeypatch, cell, (Workload, "_run_merged"), ack_only)
    assert result["correct"] is False
    assert result["compared"]["missing_links"]["value"] > 0


def test_fault_half_batch(monkeypatch, cell):
    """Half of each scoring batch is left out."""
    from sesam_duke_microservice_tpu.engine.device_matcher import (
        DeviceProcessor,
    )

    real = DeviceProcessor.deduplicate

    def half(self, records):
        return real(self, list(records)[: len(records) // 2])

    result = _run(monkeypatch, cell, (DeviceProcessor, "deduplicate"), half)
    assert result["correct"] is False
    assert result["compared"]["missing_links"]["value"] > 0


def test_fault_answer_altered(monkeypatch, cell):
    """Each link's confidence is altered where it is produced."""
    from sesam_duke_microservice_tpu.engine.listeners import (
        LinkMatchListener,
    )

    real = LinkMatchListener.matches

    def altered(self, r1, r2, confidence):
        return real(self, r1, r2, confidence * (1 + 1e-7))

    result = _run(monkeypatch, cell, (LinkMatchListener, "matches"), altered)
    assert result["correct"] is False
    assert result["compared"]["conf_gap"]["value"] > 1e-9
