"""Trace reduction: device busy and idle share, kernel time, and idle
gaps attributed to the host span that covered them."""

import gzip
import json

import chipbench_support as sup
import pytest

from benchmarks.chip import devtrace

DEV, HOST = "/device:TPU:0", "/host:CPU"


def _ev(plane, line, name, a, b):
    return (plane, line, name, float(a), float(b - a))


HAND = [
    _ev(HOST, "python", "window", 0, 100),
    _ev(DEV, "XLA Ops", "circle_score_argmin_x", 10, 20),
    _ev(DEV, "XLA Ops", "fusion", 15, 30),
    _ev(DEV, "XLA Ops", "circle_score_argmin_x", 50, 60),
    _ev(DEV, "XLA Ops", "late", 100, 120),             # outside the window
    _ev(DEV, "XLA Modules", "jit_module", 10, 30),     # modules line: not ops
]
WORKER = [("score", 5.0, 35.0), ("score.solve", 12.0, 23.0),
          ("fluid.advance", 40.0, 50.0)]


def test_hand_trace():
    r = devtrace.reduce(HAND, {"argmin": ("XLA Ops", ("circle_score_argmin",)),
                               "module": ("XLA Modules", ("jit_",))}, WORKER)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(30e-9)          # [10, 30] and [50, 60]
    assert r["argmin_s"] == pytest.approx(20e-9)
    assert r["argmin_events"] == 2
    assert r["module_s"] == pytest.approx(20e-9) and r["module_events"] == 1
    # gaps [0, 10], [30, 50], [60, 100]; midpoints 5, 40, 80
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"score": 10e-9, "fluid.advance": 20e-9 + 40e-9})
    assert r["device_ops"][0][0] == "circle_score_argmin_x"
    assert dict(devtrace.reduce(HAND)["idle_gaps"]) == pytest.approx({"outside spans": 70e-9})


def test_recorded_trace():
    """A slice of a real one-chip trace of dense64-fine.churn (TPU v5 lite),
    kept with the expected reduction computed when it was recorded."""
    data = json.loads(gzip.decompress(
        (sup.ROOT / "benchmarks/chip/tests/data/trace_dense.json.gz").read_bytes()))
    events = [tuple(e) for e in data["events"]]
    r = devtrace.reduce(events, {k: tuple(v) for k, v in data["groups"].items()},
                        [tuple(s) for s in data["host_spans"]])
    for k, v in data["expected"].items():
        assert r[k] == pytest.approx(v, rel=1e-9), k
    assert dict(r["idle_gaps"]) == pytest.approx(dict(data["expected_idle_gaps"]))
    # busy time by a plain sweep over the ops' end points
    ops = [e for e in events if e[1] == "XLA Ops"]
    marks = sorted([(e[3], 1) for e in ops] + [(e[3] + e[4], -1) for e in ops])
    busy, depth, start = 0.0, 0, None
    for t, d in marks:
        if depth == 0 and d == 1:
            start = t
        depth += d
        if depth == 0:
            busy += t - start
    assert r["busy_s"] == pytest.approx(busy * 1e-9, rel=1e-12)
    assert 0.0 < r["busy_s"] < r["window_s"]
    assert r["kernel_s"] >= r["argmin_s"] > 0.0
