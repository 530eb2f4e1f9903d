"""The trace reduction on a small recorded trace kept beside this file:
known busy time, idle gaps and scorer time."""

import json
import os

import tracefile

HERE = os.path.dirname(os.path.abspath(__file__))


def test_reduce_small_trace():
    with open(os.path.join(HERE, "trace_small.json")) as f:
        fixture = json.load(f)
    out = tracefile.reduce(fixture["trace"], fixture["window_ns"])
    want = fixture["expect"]
    assert abs(out["busy_s"] - want["busy_s"]) < 1e-12
    assert out["window_s"] == fixture["window_ns"] / 1e9
    scorer = sum(v for k, v in out["modules"].items()
                 if want["scorer_pattern"] in k)
    assert abs(scorer - want["scorer_s"]) < 1e-12
    assert out["idle_gaps"] == want["idle_gaps"]
    assert [name for name, _ in out["device_ops"]] == want["top_ops"]


def test_reduce_recorded_trace():
    with open(os.path.join(HERE, "trace_recorded.json")) as f:
        fixture = json.load(f)
    out = tracefile.reduce(fixture["trace"], fixture["window_ns"])
    want = fixture["expect"]
    assert abs(out["busy_s"] - want["busy_s"]) <= want["busy_tolerance_s"]
    scorer = sum(v for k, v in out["modules"].items()
                 if want["scorer_pattern"] in k)
    assert abs(scorer - want["scorer_s"]) < 1e-9
    idle = sum(s for _, s in out["idle_gaps"])
    assert idle <= out["window_s"] - out["busy_s"] + 1e-9


def test_op_name_drops_operands():
    assert tracefile.op_name(
        "%_intersect_tiles_padded.8 = s32[2048,8192]{1,0:T(8,128)} "
        "custom-call(s32[2048,64]{1,0} %bitcast.89)") == \
        "_intersect_tiles_padded.8 s32[2048,8192]"
    assert tracefile.op_name(
        "%while.22 = (s32[]{:T(128)}, f32[16,64]) while(%tuple.105)") == \
        "while.22"
    assert tracefile.op_name("fusion.1") == "fusion.1"


def test_reduce_clips_to_the_measured_window():
    """Only [lo, hi) counts: what the device did, or did not do, in the
    scrapes before and after the window is left out."""
    with open(os.path.join(HERE, "trace_small.json")) as f:
        fixture = json.load(f)
    out = tracefile.reduce(fixture["trace"], 550, 200)
    assert out["window_s"] == 350e-9
    assert abs(out["busy_s"] - 200e-9) < 1e-18  # [200,300) [350,400) [500,550)
    assert abs(out["modules"]["jit_corpus_scorer(123)"] - 200e-9) < 1e-18
    assert abs(out["modules"]["jit_tombstone(7)"] - 50e-9) < 1e-18
    assert sorted(s for _, s in out["idle_gaps"]) == [50e-9, 100e-9]
