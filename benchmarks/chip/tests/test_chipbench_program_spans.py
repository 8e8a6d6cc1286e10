"""The readers of the program's own spans: their arithmetic on made-up
records, the window they read, what they give against a program that
records no spans, and a whole small run with and without tracing."""

import sys

import chipbench_support as sup
import pytest

from benchmarks.chip import bench, program_spans
from benchmarks.chip.probes import Span
from repro import spans
from repro.spans import Record

NEW = ("launch_host_ms_per_decision", "device_wait_ms_per_decision",
       "h2d_bytes_per_decision", "fluid_events_per_decision",
       "fluid_solve_ms_per_decision", "fluid_offcpu_ms_per_decision",
       "prefetch_cpu_ms_per_decision")
MS = 1_000_000


def _rec(name, t0_ms, t1_ms, thread="serve-worker", cpu_ms=0.0, **attrs):
    return Record(0, name, thread, int(t0_ms * MS), int(t1_ms * MS),
                  int(cpu_ms * MS), None, None, attrs)


def _run(records, monkeypatch, decisions=2):
    """A run whose window is [1000, 1100] ms on the benchmark's worker
    spans, with ``records`` in the program's buffer."""
    monkeypatch.setattr(spans, "records", lambda: list(records))
    return {"decisions": decisions,
            "spans": [Span("decision", "serve-worker", 1.000, 1.050),
                      Span("fluid.advance", "serve-worker", 1.050, 1.100),
                      Span("score", "serve-prefetch", 0.5, 2.0)]}


def _read(name, run):
    return bench.metric_reader(sup.ROOT, name)(run)


SYNTHETIC = [
    # one argmin launch whose results the kernel probe read back in the
    # 2 ms after its dispatch, and one segmin launch with its accept scan
    _rec("launch/prep", 1001, 1002),
    _rec("launch/put", 1002, 1003, bytes=1000, arrays=5),
    _rec("launch/dispatch", 1003, 1004),
    _rec("launch/fetch", 1006, 1006.5),
    _rec("launch/prep", 1010, 1011),
    _rec("launch/put", 1011, 1012, bytes=500, arrays=5),
    _rec("launch/dispatch", 1012, 1013),
    _rec("accept/dispatch", 1013.5, 1014, bytes=24),
    _rec("accept/fetch", 1014, 1017),
    # the prefetch thread's launch: bytes count, host time does not
    _rec("launch/put", 1020, 1021, thread="serve-prefetch_0", bytes=4000),
    _rec("prefetch/warm", 1019, 1040, thread="serve-prefetch_0", cpu_ms=12.0),
    _rec("fluid/advance", 1050, 1090, cpu_ms=30.0, events=70, solves=3,
         solve_ns=5 * MS),
    _rec("fluid/advance", 1090, 1100, cpu_ms=10.0, events=10, solves=1,
         solve_ns=1 * MS),
    # outside the window: before it, and straddling its end
    _rec("fluid/advance", 900, 950, events=1000, solve_ns=100 * MS),
    _rec("fluid/advance", 1095, 1105, events=1000),
]


@pytest.mark.parametrize("name,want", [
    ("launch_host_ms_per_decision", (3 + 3 + 0.5) / 2),
    ("device_wait_ms_per_decision", (2 + 0.5 + 0.5 + 3) / 2),
    ("h2d_bytes_per_decision", (1000 + 500 + 24 + 4000) / 2),
    ("fluid_events_per_decision", 80 / 2),
    ("fluid_solve_ms_per_decision", 6 / 2),
    ("fluid_offcpu_ms_per_decision", (50 - 40) / 2),
    ("prefetch_cpu_ms_per_decision", 12 / 2),
])
def test_readers_on_made_up_records(monkeypatch, name, want):
    got = _read(name, _run(SYNTHETIC, monkeypatch))
    assert got == pytest.approx(want, rel=1e-9)


def test_window_keeps_only_records_inside_the_worker_spans(monkeypatch):
    run = _run(SYNTHETIC, monkeypatch)
    got = program_spans.window(run)
    assert len(got) == len(SYNTHETIC) - 2
    assert all(1000 * MS <= r.t0_ns and r.t1_ns <= 1100 * MS for r in got)
    # nothing inside the window reads as nothing to read
    run = _run(SYNTHETIC[-2:], monkeypatch)
    assert program_spans.window(run) is None
    assert all(_read(name, run) is None for name in NEW)


def test_launch_readers_need_a_launch(monkeypatch):
    run = _run([r for r in SYNTHETIC if r.name.startswith(("fluid", "prefetch"))],
               monkeypatch)
    assert _read("launch_host_ms_per_decision", run) is None
    assert _read("device_wait_ms_per_decision", run) is None
    assert _read("h2d_bytes_per_decision", run) is None
    assert _read("fluid_events_per_decision", run) == 40


def test_readers_return_none_without_repro_spans(monkeypatch):
    run = _run(SYNTHETIC, monkeypatch)
    monkeypatch.setitem(sys.modules, "repro.spans", None)
    for name in NEW:
        assert _read(name, run) is None, name


def test_traced_small_run_reports_the_program_span_metrics():
    res = sup.run_small("dense64-fine.churn", trace=True)
    assert res["correct"] is True, res["checks"]
    m = res["metrics"]
    for name in ("fluid_events_per_decision", "fluid_solve_ms_per_decision",
                 "fluid_offcpu_ms_per_decision", "prefetch_cpu_ms_per_decision"):
        assert m[name]["value"] is not None and m[name]["value"] >= 0, name
    assert m["fluid_events_per_decision"]["value"] > 0
    assert m["prefetch_cpu_ms_per_decision"]["value"] > 0
    # the CPU run scores on the host's numpy path: no launch to read
    assert not set(m) & {"launch_host_ms_per_decision",
                         "device_wait_ms_per_decision", "h2d_bytes_per_decision"}


def test_untraced_small_run_records_no_span():
    spans.clear()
    res = sup.run_small("dense64-fine.churn")
    assert res["correct"] is True, res["checks"]
    assert spans.records() == [] and spans.dropped() == 0
