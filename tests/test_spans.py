"""Spans inside the program (``repro.spans``): off unless a JAX profiler
session runs, the records they keep while one does, and the spans of the
kernel launches and the fluid engine."""

from __future__ import annotations

import threading
import types

import jax
import numpy as np
import pytest

import repro.cluster.network as network
from repro import spans
from repro.cluster import FluidNetworkSim, Topology, snapshot_trace
from repro.cluster.job import JobState
from repro.kernels.circle_score import ops


@pytest.fixture
def traced(tmp_path):
    """A profiler session around the test, with an empty span buffer."""
    spans.clear()
    with jax.profiler.trace(str(tmp_path)):
        yield
    assert not spans.enabled()


def _names(recs):
    return [r.name for r in recs]


def _small_fluid():
    t = Topology.paper_testbed()
    jobs = snapshot_trace([("vgg19", 2, 1400), ("vgg19", 2, 1400)], iters=200)
    jobs[0].placement = (0, 6)
    jobs[1].placement = (1, 7)   # same rack pair: contended uplink
    for j in jobs:
        j.state = JobState.RUNNING
    sim = FluidNetworkSim(t)
    sim.configure(jobs)
    return sim


def _ragged_inputs():
    rng = np.random.default_rng(3)
    base = rng.random((2, 128)).astype(np.float32)
    cand = rng.random((2, 128)).astype(np.float32)
    return base, cand, np.float32(1.5), np.array([60, 100]), np.array([120, 100])


def test_off_records_nothing_and_reads_no_clock(monkeypatch):
    def no_clock():
        raise AssertionError("a span read the clock with spans off")

    stub = types.SimpleNamespace(perf_counter_ns=no_clock, thread_time_ns=no_clock)
    monkeypatch.setattr(spans, "time", stub)
    monkeypatch.setattr(network, "time", stub)
    spans.clear()
    assert not spans.enabled()
    with spans.span("serve/reschedule", decision=0) as sp:
        sp.set(trigger="test")
        assert spans.current() is None
    assert spans.span("a/b") is spans.span("c/d", x=1)
    sim = _small_fluid()
    sim.advance(20_000.0)
    ops.circle_score_ragged_argmin(*_ragged_inputs())
    assert spans.records() == [] and spans.dropped() == 0


def test_nesting_parent_thread_decision_and_attrs(traced):
    with spans.span("serve/reschedule", decision=7, trigger="arrival") as root:
        launcher = spans.current()
        assert launcher is root
        with spans.span("a/inner") as inner:
            inner.set(count=3)
            sum(range(20_000))

        def side():
            with spans.span("prefetch/warm", parent=launcher):
                with spans.span("a/child"):
                    pass

        t = threading.Thread(target=side, name="side-thread")
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
    with spans.span("a/alone"):
        pass
    recs = {r.name: r for r in spans.records()}
    assert set(recs) == {"serve/reschedule", "a/inner", "prefetch/warm",
                         "a/child", "a/alone"}
    top = recs["serve/reschedule"]
    assert top.parent is None and top.decision == 7
    assert top.attrs == {"trigger": "arrival"}
    assert top.thread == threading.current_thread().name
    assert recs["a/inner"].parent == top.id and recs["a/inner"].decision == 7
    assert recs["a/inner"].attrs == {"count": 3}
    warm = recs["prefetch/warm"]
    assert warm.parent == top.id and warm.decision == 7
    assert warm.thread == "side-thread"
    assert recs["a/child"].parent == warm.id and recs["a/child"].decision == 7
    assert recs["a/alone"].parent is None and recs["a/alone"].decision is None
    for r in recs.values():
        assert 0 <= r.cpu_ns <= r.t1_ns - r.t0_ns
    assert top.t0_ns <= recs["a/inner"].t0_ns <= recs["a/inner"].t1_ns <= top.t1_ns
    assert spans.dropped() == 0


def test_buffer_bound_drops_the_oldest(traced, monkeypatch):
    monkeypatch.setattr(spans, "_buffer", spans._Buffer(3))
    for i in range(5):
        with spans.span(f"a/s{i}"):
            pass
    assert _names(spans.records()) == ["a/s2", "a/s3", "a/s4"]
    assert spans.dropped() == 2
    spans.clear()
    assert spans.records() == [] and spans.dropped() == 0


def test_ragged_argmin_launch_spans(traced):
    base, cand, cap, valid, na = _ragged_inputs()
    want = ops.circle_score_ragged_argmin(base, cand, cap, valid, na)
    recs = spans.records()
    assert _names(recs) == ["launch/prep", "launch/put", "launch/dispatch",
                            "launch/fetch"]
    assert all(a.t1_ns <= b.t0_ns for a, b in zip(recs, recs[1:]))
    sched = ops._schedule("circle_score_argmin", 128, True)
    lb = ops.row_bucket(2, sched["block_l"])
    # two (lb, 128) float32 rows, three (lb,) 4-byte vectors
    assert recs[1].attrs == {"arrays": 5, "bytes": lb * 128 * 4 * 2 + lb * 4 * 3}
    # with spans on, the answer is the one the scalar reference gives
    ref_idx, ref_val = ops.circle_score_argmin_ref(base, cand, cap, valid, na)
    np.testing.assert_array_equal(want[0], ref_idx)
    np.testing.assert_array_equal(want[1], ref_val)


def test_ragged_segmin_launch_and_accept_spans(traced):
    base, cand, cap, valid, na = _ragged_inputs()
    seg = np.array([0, 1])
    init = np.array([np.inf, 5.0])
    got = ops.circle_score_ragged_segmin(base, cand, cap, valid, na, seg, init)
    recs = spans.records()
    assert _names(recs) == ["launch/prep", "launch/put", "launch/dispatch",
                            "accept/dispatch", "accept/fetch"]
    assert all(a.t1_ns <= b.t0_ns for a, b in zip(recs, recs[1:]))
    sched = ops._schedule("circle_score_segmin", 128, True)
    lb = ops.row_bucket(2, sched["block_l"])
    assert recs[1].attrs == {"arrays": 5, "bytes": lb * 128 * 4 * 2 + lb * 4 * 3}
    # lb int32 segment ids; incumbents padded to a power of two > 2, f64
    assert recs[3].attrs == {"bytes": lb * 4 + 4 * 8}
    spans.clear()
    assert len(got) == 4


def test_fluid_advance_counts_its_event_steps(traced):
    sim = _small_fluid()
    steps = 0
    solve = sim._cached_solve

    def counted(mask):
        nonlocal steps
        steps += 1
        return solve(mask)

    sim._cached_solve = counted   # called once per event step
    solves0 = sim.alloc_solves
    sim.advance(30_000.0)
    (rec,) = spans.records()
    assert rec.name == "fluid/advance"
    assert rec.attrs["events"] == steps > 100
    assert rec.attrs["solves"] == sim.alloc_solves - solves0 > 0
    assert 0 < rec.attrs["solve_ns"] <= rec.t1_ns - rec.t0_ns
    assert sim._solve_ns is None
