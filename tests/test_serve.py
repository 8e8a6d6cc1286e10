"""Serve mode: golden equivalence vs the batch simulator, queue bounds,
prefetch parity, stream semantics (ISSUE 6 tentpole)."""

from __future__ import annotations

import math
import threading
import time
from itertools import islice

import pytest

from repro.cluster import Topology, iter_poisson_trace, poisson_trace
from repro.engine import get_scenario
from repro.serve import (
    JobArrival,
    JobDeparture,
    LatencyRecorder,
    QueryPlacement,
    QueueFullError,
    SchedulerService,
)


def _decision_tuples(decisions):
    return [
        (t, d.placements, d.time_shifts_ms)
        for t, d in decisions
    ]


def _run_batch(spec, scheduler_name):
    built = spec.build(scheduler_name)
    metrics = built.simulator.run(built.jobs, horizon_ms=spec.horizon_ms)
    return metrics, built.simulator.decisions


def _run_served(spec, scheduler_name, *, prefetch=True):
    topo = spec.topology()
    svc = SchedulerService(
        topo,
        spec.make_scheduler(scheduler_name),
        epoch_ms=spec.epoch_ms,
        compute_jitter=spec.compute_jitter,
        vectorized=spec.vectorized,
        seed=spec.sim_seed,
        prefetch=prefetch,
    )
    with svc:
        for job in spec.arrival_stream(topo):
            svc.submit(JobArrival(job))
        metrics = svc.drain(spec.horizon_ms)
        telemetry = svc.telemetry()
    return metrics, svc.decisions, telemetry


# --------------------------------------------------------------------- #
# golden equivalence (acceptance criterion)
# --------------------------------------------------------------------- #
class TestGoldenEquivalence:
    def test_multitenant8_replay_matches_batch(self):
        """The served multitenant-8 arrival replay produces every placement,
        time-shift and metric identically to the batch pipeline."""
        spec = get_scenario("multitenant-8")
        m_batch, d_batch = _run_batch(spec, "cassini")
        m_serve, d_serve, telemetry = _run_served(spec, "cassini")
        assert m_batch.summary() == m_serve.summary()
        assert _decision_tuples(d_batch) == _decision_tuples(d_serve)
        # every epoch reconfiguration took the delta path (the replay only
        # appends arrivals / drops departures — no survivor reordering)
        assert telemetry["configure_delta"] == len(d_serve)
        assert telemetry.get("configure_rebuild", 0.0) == 0.0

    def test_dynamic_arrivals_match_batch_themis_cassini(self):
        """Arrival/departure churn with a real host scheduler (Themis):
        decisions may reorder survivors — the service must fall back to
        rebuilds where needed and still match the batch run exactly."""
        spec = get_scenario("dynamic-burst")
        m_batch, d_batch = _run_batch(spec, "th+cassini")
        m_serve, d_serve, _ = _run_served(spec, "th+cassini")
        assert m_batch.summary() == m_serve.summary()
        assert _decision_tuples(d_batch) == _decision_tuples(d_serve)

    def test_prefetch_off_parity(self):
        """Speculative cache warming must not change any decision."""
        spec = get_scenario("multitenant-4")
        m_on, d_on, tel_on = _run_served(spec, "cassini", prefetch=True)
        m_off, d_off, tel_off = _run_served(spec, "cassini", prefetch=False)
        assert m_on.summary() == m_off.summary()
        assert _decision_tuples(d_on) == _decision_tuples(d_off)
        assert tel_on["prefetch_launched"] > 0
        assert "prefetch_launched" not in tel_off

    def test_batch_totals_sum_every_solve(self, monkeypatch):
        """``batch_*`` telemetry sums ``BatchStats`` over every batched
        solve, the prefetch thread's included; ``last_batch_stats`` stays
        the last call's."""
        import dataclasses

        import repro.core.plugin as plugin
        from repro.core import compat

        # the kernel path even at the scenario's coarse circles
        monkeypatch.setattr(compat, "_kernel_eligible", lambda backend, a: True)
        calls, lock = [], threading.Lock()
        solve = plugin.find_rotations_batched

        def recorded(batch, **kw):
            out = solve(batch, **kw)
            with lock:
                calls.append(kw["stats"])
            return out

        monkeypatch.setattr(plugin, "find_rotations_batched", recorded)
        spec = get_scenario("dynamic-burst")
        svc = SchedulerService(
            spec.topology(), spec.make_scheduler("th+cassini"),
            epoch_ms=spec.epoch_ms, seed=spec.sim_seed, prefetch=True,
        )
        with svc:
            for job in spec.arrival_stream(svc.topo):
                svc.submit(JobArrival(job))
            svc.drain(spec.horizon_ms)
            tel = svc.telemetry()
        assert len(calls) > 1
        module = svc.scheduler.module
        assert module.last_batch_stats in calls or module.last_batch_stats is None
        for f in dataclasses.fields(compat.BatchStats):
            assert tel[f"batch_{f.name}"] == sum(getattr(c, f.name) for c in calls)
        assert tel["batch_problems"] > 0 and tel["batch_launches"] > 0


# --------------------------------------------------------------------- #
# service semantics
# --------------------------------------------------------------------- #
class TestServiceSemantics:
    def _spec(self):
        return get_scenario("multitenant-4")

    def test_query_placement(self):
        spec = self._spec()
        topo = spec.topology()
        with SchedulerService(
            topo, spec.make_scheduler("cassini"), epoch_ms=spec.epoch_ms,
            compute_jitter=0.0, seed=spec.sim_seed,
        ) as svc:
            jobs = list(spec.arrival_stream(topo))
            for job in jobs:
                svc.submit(JobArrival(job))
            # watermark past the t=0 batch forces its admission + decision
            view = svc.query(at_ms=1.0)
            assert set(view.placements) == {j.job_id for j in jobs}
            _, latest = svc.decisions[-1]
            assert view.placements == {
                jid: tuple(srv) for jid, srv in latest.placements.items()
            }
            one = svc.query(job_id=jobs[0].job_id)
            assert one.placements == {
                jobs[0].job_id: view.placements[jobs[0].job_id]
            }
            with pytest.raises(KeyError):
                svc.query(job_id="no-such-job")

    def test_same_timestamp_arrivals_admitted_as_one_batch(self):
        """All t=0 tenants must enter with ONE scheduling decision, exactly
        like the batch simulator — not one decision per submit."""
        spec = self._spec()
        topo = spec.topology()
        with SchedulerService(
            topo, spec.make_scheduler("cassini"), epoch_ms=spec.epoch_ms,
            compute_jitter=0.0, seed=spec.sim_seed,
        ) as svc:
            for job in spec.arrival_stream(topo):
                svc.submit(JobArrival(job))
            assert svc.query().placements == {}  # watermark still at t=0
            svc.query(at_ms=1.0)
            tel = svc.telemetry()
            assert tel["reschedule_arrival"] == 1.0

    def test_departure_cancels_job(self):
        spec = self._spec()
        topo = spec.topology()
        with SchedulerService(
            topo, spec.make_scheduler("cassini"), epoch_ms=spec.epoch_ms,
            compute_jitter=0.0, seed=spec.sim_seed,
        ) as svc:
            jobs = list(spec.arrival_stream(topo))
            for job in jobs:
                svc.submit(JobArrival(job))
            victim = jobs[0].job_id
            svc.submit(JobDeparture(job_id=victim, at_ms=5_000.0)).result()
            view = svc.query()
            assert victim not in view.placements
            metrics = svc.drain(spec.horizon_ms)
            by_id = {j.job_id: j for j in metrics.jobs}
            assert by_id[victim].finish_ms is None  # cancelled, not finished

    def test_out_of_order_events_rejected(self):
        spec = self._spec()
        topo = spec.topology()
        with SchedulerService(
            topo, spec.make_scheduler("cassini"), epoch_ms=spec.epoch_ms,
            compute_jitter=0.0, seed=spec.sim_seed,
        ) as svc:
            svc.query(at_ms=10_000.0)
            with pytest.raises(ValueError, match="watermark"):
                svc.query(at_ms=5_000.0)

    def test_bounded_queue_backpressure(self):
        spec = self._spec()
        topo = spec.topology()
        svc = SchedulerService(
            topo, spec.make_scheduler("cassini"), epoch_ms=spec.epoch_ms,
            queue_size=2, start=False,  # no worker: the queue can only fill
        )
        jobs = poisson_trace(topo, num_jobs=3, seed=1)
        svc.submit(JobArrival(jobs[0]))
        svc.submit(JobArrival(jobs[1]))
        with pytest.raises(QueueFullError):
            svc.submit(JobArrival(jobs[2]))
        assert svc.metrics.counter("queue_rejected") == 1
        assert svc.metrics.snapshot()["queue_depth_peak"] == 2.0

    def test_closed_service_rejects_submissions(self):
        spec = self._spec()
        topo = spec.topology()
        svc = SchedulerService(
            topo, spec.make_scheduler("cassini"), epoch_ms=spec.epoch_ms,
        )
        svc.close()
        with pytest.raises(RuntimeError, match="closed"):
            svc.submit(QueryPlacement())


# --------------------------------------------------------------------- #
# worker thread lifecycle (crash propagation + bounded shutdown)
# --------------------------------------------------------------------- #
class TestWorkerLifecycle:
    def _spec(self):
        return get_scenario("multitenant-2")

    def _service(self, **kw):
        spec = self._spec()
        return SchedulerService(
            spec.topology(), spec.make_scheduler("cassini"),
            epoch_ms=spec.epoch_ms, seed=spec.sim_seed, **kw,
        )

    @staticmethod
    def _crash(svc):
        """Kill the worker loop *outside* the per-request handler: result
        delivery succeeds, then latency recording blows up the loop."""
        def boom(*a, **kw):
            raise ZeroDivisionError("telemetry exploded")

        svc.metrics.observe = boom
        fut = svc.submit(QueryPlacement())
        fut.result(timeout=10)  # the request itself completed fine
        for _ in range(500):    # …then the loop died recording it
            if svc._worker_exc is not None:
                return
            time.sleep(0.01)
        raise AssertionError("worker did not record its crash")

    def test_worker_crash_reraises_on_submit(self):
        svc = self._service()
        self._crash(svc)
        with pytest.raises(RuntimeError, match="worker crashed") as ei:
            svc.submit(QueryPlacement())
        assert isinstance(ei.value.__cause__, ZeroDivisionError)
        assert svc.metrics.counter("worker_crashed") == 1
        svc.close()

    def test_worker_crash_reraises_on_drain(self):
        svc = self._service()
        self._crash(svc)
        with pytest.raises(RuntimeError, match="worker crashed"):
            svc.drain(1_000.0)
        svc.close()

    def test_worker_crash_fails_queued_futures(self):
        """Requests already queued behind the crash must error out, not
        leave their callers blocked on a Future nobody will resolve."""
        svc = self._service(start=False)
        svc.metrics.observe = lambda *a, **kw: (_ for _ in ()).throw(
            ZeroDivisionError("telemetry exploded")
        )
        first = svc.submit(QueryPlacement())
        stuck = [svc.submit(QueryPlacement()) for _ in range(3)]
        svc.start()
        first.result(timeout=10)
        for fut in stuck:
            with pytest.raises(RuntimeError, match="worker crashed"):
                fut.result(timeout=10)
        svc.close()

    def test_close_idempotent_after_crash(self):
        svc = self._service()
        self._crash(svc)
        svc.close()  # dead worker: join returns immediately, no hang
        svc.close()  # and again — idempotent
        assert svc._worker is None

    def test_close_timeout_on_wedged_worker(self):
        svc = self._service()
        gate = threading.Event()
        orig = svc._handle
        svc._handle = lambda ev: (gate.wait(), orig(ev))[1]
        svc.submit(QueryPlacement())
        try:
            with pytest.raises(RuntimeError, match="did not stop"):
                svc.close(timeout_s=0.2)
        finally:
            gate.set()  # release the worker so the daemon thread exits


# --------------------------------------------------------------------- #
# streaming traces (satellite: O(1)-memory arrival streams)
# --------------------------------------------------------------------- #
class TestArrivalStreams:
    def test_iter_poisson_prefix_matches_list(self):
        topo = Topology.paper_testbed()
        lst = poisson_trace(topo, num_jobs=10, seed=5)
        stream = list(islice(iter_poisson_trace(topo, num_jobs=None, seed=5), 10))
        assert [
            (j.job_id, j.model, j.num_workers, j.duration_iters, j.arrival_ms)
            for j in lst
        ] == [
            (j.job_id, j.model, j.num_workers, j.duration_iters, j.arrival_ms)
            for j in stream
        ]

    def test_scenario_arrival_stream_matches_trace(self):
        for name in ("poisson-paper", "arrival-burst", "multitenant-8"):
            spec = get_scenario(name)
            topo = spec.topology()
            lst = spec.trace(topo)
            stream = list(spec.arrival_stream(topo))
            assert [(j.job_id, j.arrival_ms) for j in lst] == [
                (j.job_id, j.arrival_ms) for j in stream
            ]

    def test_unbounded_stream_is_lazy(self):
        topo = Topology.paper_testbed()
        it = iter_poisson_trace(topo, num_jobs=None, seed=0)
        head = [next(it) for _ in range(100)]
        assert len({j.job_id for j in head}) == 100
        assert all(
            a.arrival_ms <= b.arrival_ms for a, b in zip(head, head[1:])
        )


# --------------------------------------------------------------------- #
# latency recorder
# --------------------------------------------------------------------- #
class TestLatencyRecorder:
    def test_percentiles_nearest_rank(self):
        rec = LatencyRecorder()
        for v in range(1, 101):  # 1..100 ms
            rec.observe("query", float(v))
        pct = rec.percentiles("query")
        assert pct == {"p50": 50.0, "p95": 95.0, "p99": 99.0}

    def test_empty_kind_is_nan(self):
        rec = LatencyRecorder()
        assert all(math.isnan(v) for v in rec.percentiles("nope").values())

    def test_snapshot_counters_and_gauges(self):
        rec = LatencyRecorder()
        rec.count("hits", 3)
        rec.gauge("depth", 5.0)
        rec.gauge("depth", 2.0)
        snap = rec.snapshot()
        assert snap["hits"] == 3.0
        assert snap["depth"] == 2.0
        assert snap["depth_peak"] == 5.0

    def test_window_bounds_memory(self):
        rec = LatencyRecorder(window=16)
        for v in range(1000):
            rec.observe("q", float(v))
        snap = rec.snapshot()
        assert snap["q_count"] == 1000.0
        assert rec.percentiles("q")["p50"] >= 984.0  # only the tail kept

    def test_single_sample_is_every_percentile(self):
        # nearest-rank over n=1: ceil(q/100)-1 == 0 for every q — the one
        # sample answers p50, p95 and p99 alike (no interpolation to NaN)
        rec = LatencyRecorder()
        rec.observe("q", 7.5)
        assert rec.percentiles("q") == {"p50": 7.5, "p95": 7.5, "p99": 7.5}

    def test_two_samples_split_by_rank(self):
        # n=2: p50 → ceil(1.0)-1 = index 0 (the smaller sample), p95/p99
        # → ceil(1.9)/ceil(1.98)-1 = index 1 (the larger) — well-defined,
        # order-independent
        rec = LatencyRecorder()
        rec.observe("q", 9.0)
        rec.observe("q", 3.0)
        assert rec.percentiles("q") == {"p50": 3.0, "p95": 9.0, "p99": 9.0}

    def test_snapshot_never_raises_on_sparse_kinds(self):
        # telemetry() calls snapshot() mid-incident: 0/1/2-sample kinds
        # must export cleanly alongside warm ones
        rec = LatencyRecorder()
        rec.observe("one", 1.0)
        rec.observe("two", 2.0)
        rec.observe("two", 4.0)
        snap = rec.snapshot()
        assert snap["one_p99_ms"] == 1.0
        assert snap["two_p50_ms"] == 2.0 and snap["two_p99_ms"] == 4.0
        assert snap["one_count"] == 1.0

    def test_invalid_window_rejected_at_construction(self):
        # fail fast (not mid-incident on the first observe())
        with pytest.raises(ValueError, match="window must be >= 1"):
            LatencyRecorder(window=0)
        with pytest.raises(ValueError, match="-3"):
            LatencyRecorder(window=-3)
