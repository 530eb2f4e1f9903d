"""``scan_rows_share.resync`` from a hand-made pair of ``/metrics`` pages:
the window's scanned rows over its capacity rows, and nothing from a
program that keeps no such counter or scanned nothing."""

import pytest

import run

FAMILY = "duke_device_scan_rows_total"


def _page(scanned, capacity):
    if scanned is None:
        return "# a program without the counter\nduke_jit_compiles_total 3\n"
    return (f"# TYPE {FAMILY} counter\n"
            f'{FAMILY}{{part="scanned"}} {scanned}\n'
            f'{FAMILY}{{part="capacity"}} {capacity}\n')


def _read(before, after):
    report = {"t0": 0.0, "last_ack": 1.0, "posts": [],
              "metrics_before": _page(*before),
              "metrics_after": _page(*after)}
    ctx = run.Context({}, {}, {}, {"name": "w"}, {}, report)
    return run.reader("layer_metrics", "scan_rows_share.resync")(ctx)


def test_reads_the_window_share():
    # 3 calls over a 262,144-row capacity, 17 chunks of 8,192 rows each
    before = (5 * 8192.0, 262144.0)
    after = (before[0] + 3 * 17 * 8192, before[1] + 3 * 262144)
    assert _read(before, after) == pytest.approx(100.0 * 17 / 32, rel=1e-12)
    assert _read((0, 0), (262144, 262144)) == pytest.approx(100.0)


def test_reads_none_when_nothing_was_scanned():
    assert _read((4096, 8192), (4096, 8192)) is None
    assert _read((None, None), (None, None)) is None
