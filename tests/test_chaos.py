"""repro.chaos: deterministic fault injection, bit-identical replays and
graceful degradation (ISSUE 8 tentpole).

Covers the fault taxonomy + schedule determinism, injector semantics
against a live fluid sim, the typed mutation errors, batch-vs-serve
replay parity on every churn-* scenario, engine symmetry (scalar /
vectorized / incremental) under faults, and the serve fallback path —
the worker must answer every query while the pipeline is on fire.
"""

from __future__ import annotations

import math

import pytest

from repro.chaos import FaultInjector, FaultSchedule
from repro.chaos.events import (
    JobResize,
    LinkDegrade,
    LinkDown,
    LinkRecover,
    NicFlap,
    PhaseJitter,
)
from repro.chaos.inject import DOWN_GBPS
from repro.cluster import (
    FluidNetworkSim,
    Topology,
    poisson_trace,
    snapshot_trace,
)
from repro.cluster.errors import UnknownJobError, UnknownLinkError
from repro.engine import get_scenario
from repro.sched.base import ClusterState, Decision, Scheduler
from repro.serve import JobArrival, SchedulerService

CHURN = ("churn-linkfail", "churn-elastic", "churn-jitter")


def _decision_tuples(decisions):
    return [(t, d.placements, d.time_shifts_ms) for t, d in decisions]


def _run_batch(spec, scheduler_name):
    built = spec.build(scheduler_name)
    metrics = built.simulator.run(built.jobs, horizon_ms=spec.horizon_ms)
    return metrics, built.simulator.decisions, built.simulator.chaos


def _run_served(spec, scheduler_name, **kw):
    topo = spec.topology()
    jobs = list(spec.arrival_stream(topo))
    svc = SchedulerService(
        topo, spec.make_scheduler(scheduler_name), epoch_ms=spec.epoch_ms,
        compute_jitter=spec.compute_jitter, vectorized=spec.vectorized,
        seed=spec.sim_seed,
        fault_schedule=spec.make_fault_schedule(topo, jobs), **kw,
    )
    with svc:
        for job in jobs:
            svc.submit(JobArrival(job))
        metrics = svc.drain(spec.horizon_ms)
        telemetry = svc.telemetry()
    return metrics, svc.decisions, telemetry


# --------------------------------------------------------------------- #
# schedules: validation, determinism, resolution
# --------------------------------------------------------------------- #
class TestFaultSchedule:
    def test_of_sorts_by_time(self):
        s = FaultSchedule.of(
            LinkRecover(500.0, "up:r0-sp0"),
            LinkDown(100.0, "up:r0-sp0"),
            PhaseJitter(300.0, "j0", 2.0),
        )
        assert [ev.at_ms for ev in s] == [100.0, 300.0, 500.0]
        assert len(s) == 3 and not s.empty

    def test_validation(self):
        with pytest.raises(ValueError, match="before t=0"):
            FaultSchedule.of(LinkDown(-1.0, "x"))
        with pytest.raises(ValueError, match="factor"):
            FaultSchedule.of(LinkDegrade(0.0, "x", 1.0))
        with pytest.raises(ValueError, match="factor"):
            FaultSchedule.of(LinkDegrade(0.0, "x", 0.0))
        with pytest.raises(ValueError, match="down_ms"):
            FaultSchedule.of(NicFlap(0.0, 0, 0.0))

    def test_generators_are_deterministic(self):
        topo = Topology.paper_testbed()
        jobs = poisson_trace(topo, num_jobs=6, seed=3)
        for mk in (
            lambda s: FaultSchedule.linkfail(topo, seed=s, horizon_ms=1e5),
            lambda s: FaultSchedule.elastic(jobs, seed=s, horizon_ms=1e5),
            lambda s: FaultSchedule.jitter(
                jobs, seed=s, horizon_ms=1e5, magnitude_ms=5.0
            ),
        ):
            assert mk(7).events == mk(7).events
            assert mk(7).events != mk(8).events

    def test_zero_magnitude_jitter_is_empty(self):
        topo = Topology.paper_testbed()
        jobs = poisson_trace(topo, num_jobs=3, seed=0)
        assert FaultSchedule.jitter(
            jobs, seed=1, horizon_ms=1e5, magnitude_ms=0.0
        ).empty

    def test_resolve_expands_nicflap(self):
        topo = Topology.paper_testbed()
        link = topo.host_link(3).name
        s = FaultSchedule.of(
            NicFlap(1_000.0, 3, 500.0), PhaseJitter(1_200.0, "j", 1.0)
        )
        resolved = s.resolve(topo)
        kinds = [(type(ev).__name__, ev.at_ms) for ev in resolved]
        assert kinds == [
            ("LinkDown", 1_000.0),
            ("PhaseJitter", 1_200.0),
            ("LinkRecover", 1_500.0),
        ]
        assert resolved[0].link == resolved[2].link == link


# --------------------------------------------------------------------- #
# typed mutation errors (satellite 1)
# --------------------------------------------------------------------- #
class TestTypedErrors:
    def test_unknown_link_names_id_and_live_set(self):
        topo = Topology.paper_testbed()
        with pytest.raises(UnknownLinkError) as ei:
            topo.set_link_capacity("up:nope", 10.0)
        assert ei.value.link == "up:nope"
        assert "unknown link 'up:nope'" in str(ei.value)
        assert "live:" in str(ei.value)
        assert isinstance(ei.value, KeyError)  # historical contract

    def test_unknown_job_on_remove_and_update(self):
        topo = Topology.paper_testbed()
        sim = FluidNetworkSim(topo)
        jobs = poisson_trace(topo, num_jobs=2, seed=1)
        for i, j in enumerate(jobs):
            j.placement = (2 * i, 2 * i + 1)
        sim.configure(jobs)
        with pytest.raises(UnknownJobError) as ei:
            sim.remove_job("ghost")
        assert ei.value.job_id == "ghost"
        assert jobs[0].job_id in str(ei.value)  # live set summarized
        with pytest.raises(KeyError):  # historical contract
            sim.remove_job("ghost")
        with pytest.raises(UnknownJobError):
            sim.perturb_job("ghost", 1.0)

    def test_incidence_row_errors_are_index_errors_too(self):
        topo = Topology.paper_testbed()
        inc = topo.incidence([(0, 6), (1, 7)])
        with pytest.raises(UnknownJobError) as ei:
            inc.without_row(5)
        assert isinstance(ei.value, IndexError)
        assert isinstance(ei.value, KeyError)
        assert ei.value.job_id == 5
        with pytest.raises(IndexError):
            inc.replace_row(9, topo.job_link_ids((0, 1)))

    def test_negative_capacity_rejected(self):
        topo = Topology.paper_testbed()
        name = next(iter(topo.links))
        with pytest.raises(ValueError, match="negative"):
            topo.set_link_capacity(name, -1.0)


# --------------------------------------------------------------------- #
# injector semantics on a live sim
# --------------------------------------------------------------------- #
def _two_job_sim(iters=50):
    topo = Topology.paper_testbed()
    jobs = snapshot_trace(
        [("vgg19", 2, 1400), ("vgg19", 2, 1400)], iters=iters
    )
    jobs[0].placement = (0, 6)
    jobs[1].placement = (1, 7)
    sim = FluidNetworkSim(topo)
    sim.configure(jobs)
    return topo, jobs, sim


class TestFaultInjector:
    def test_down_degrade_recover_against_pristine(self):
        topo, jobs, sim = _two_job_sim()
        name = topo.host_link(0).name
        pristine = topo.links[name].capacity_gbps
        inj = FaultInjector(sim, FaultSchedule.of(
            LinkDown(0.0, name),
            LinkDegrade(10.0, name, 0.5),
            LinkRecover(20.0, name),
        ))
        assert inj.next_ms == 0.0
        inj.apply_due(0.0, jobs)
        assert topo.links[name].capacity_gbps == DOWN_GBPS
        inj.apply_due(10.0, jobs)
        # degrade is relative to the PRISTINE capacity, not the downed one
        assert topo.links[name].capacity_gbps == pytest.approx(
            pristine * 0.5
        )
        inj.apply_due(20.0, jobs)
        assert topo.links[name].capacity_gbps == pristine
        assert inj.applied_count == 3 and inj.skipped == 0
        assert inj.next_ms == math.inf

    def test_capacity_mutation_reaches_allocation(self):
        topo, jobs, sim = _two_job_sim()
        sim.advance(50.0)
        name = topo.host_link(0).name
        inj = FaultInjector(sim, FaultSchedule.of(LinkDown(50.0, name)))
        before = dict(sim._allocate())
        inj.apply_due(sim.now_ms, jobs)
        after = dict(sim._allocate())
        # job 0 crosses the downed host link: its rate collapses to the
        # trickle while job 1 keeps a real allocation
        j0, j1 = jobs[0].job_id, jobs[1].job_id
        if j0 in before and before[j0] > 1e-6:
            assert after.get(j0, 0.0) <= DOWN_GBPS + 1e-12
        if j1 in after and j1 in before:
            assert after[j1] > DOWN_GBPS

    def test_resize_routes_through_remesh_planner(self):
        topo = Topology.paper_testbed()
        jobs = poisson_trace(topo, num_jobs=2, seed=5)
        jobs[0].num_workers = 4
        jobs[0].placement = (0, 1, 2, 3)
        jobs[1].placement = (6, 7)
        sim = FluidNetworkSim(topo)
        sim.configure(jobs)
        inj = FaultInjector(sim, FaultSchedule.of(
            JobResize(0.0, jobs[0].job_id, -2)
        ))
        realign = inj.apply_due(0.0, jobs)
        assert realign  # shape changes request a re-alignment pass
        assert jobs[0].num_workers == 2
        (plan,) = inj.remesh_plans
        assert plan.old_shape == (4,) and plan.new_shape == (2,)

    def test_resize_never_kills_last_worker(self):
        topo = Topology.paper_testbed()
        jobs = poisson_trace(topo, num_jobs=1, seed=5)
        jobs[0].num_workers = 3
        jobs[0].placement = (0, 1, 2)
        sim = FluidNetworkSim(topo)
        sim.configure(jobs)
        inj = FaultInjector(sim, FaultSchedule.of(
            JobResize(0.0, jobs[0].job_id, -99)
        ))
        inj.apply_due(0.0, jobs)
        assert jobs[0].num_workers == 1  # clamped, not zero

    def test_stale_targets_are_skipped_not_raised(self):
        topo, jobs, sim = _two_job_sim()
        inj = FaultInjector(sim, FaultSchedule.of(
            JobResize(0.0, "finished-long-ago", +2),
            PhaseJitter(0.0, "never-placed", 3.0),
        ))
        realign = inj.apply_due(0.0, jobs)
        assert not realign
        assert inj.applied_count == 0 and inj.skipped == 2

    def test_jitter_perturbs_delay(self):
        topo, jobs, sim = _two_job_sim()
        jid = jobs[0].job_id
        d0 = sim._execs[jid].delay_ms
        inj = FaultInjector(sim, FaultSchedule.of(
            PhaseJitter(0.0, jid, 7.5),
            PhaseJitter(1.0, jid, -1e9),  # floor at zero, never negative
        ))
        realign = inj.apply_due(0.0, jobs)
        assert not realign  # jitter is absorbed by the drift agent
        assert sim._execs[jid].delay_ms == pytest.approx(d0 + 7.5)
        inj.apply_due(1.0, jobs)
        assert sim._execs[jid].delay_ms == 0.0

    def test_pristine_snapshot_defeats_stacked_faults(self):
        topo, jobs, sim = _two_job_sim()
        name = topo.host_link(1).name
        pristine = topo.links[name].capacity_gbps
        inj = FaultInjector(sim, FaultSchedule.of(
            LinkDegrade(0.0, name, 0.5),
            LinkDegrade(1.0, name, 0.5),  # does NOT compound to 0.25
            LinkRecover(2.0, name),
        ))
        inj.apply_due(1.0, jobs)
        assert topo.links[name].capacity_gbps == pytest.approx(
            pristine * 0.5
        )
        inj.apply_due(2.0, jobs)
        assert topo.links[name].capacity_gbps == pristine


# --------------------------------------------------------------------- #
# capacity deltas × the incremental water-filling machinery
# --------------------------------------------------------------------- #
class TestIncrementalCapacityDeltas:
    def test_incremental_matches_rebuild_after_capacity_change(self):
        """A set_link_capacity between advances must flow into the delta
        re-solve: rates after the mutation match a from-scratch sim that
        saw the same capacities."""
        topo_a = Topology.paper_testbed()
        topo_b = Topology.paper_testbed()
        sims = []
        for topo, incremental in ((topo_a, True), (topo_b, False)):
            jobs = poisson_trace(topo, num_jobs=6, seed=13)
            g = 0
            for j in jobs:
                take = min(j.num_workers, 3)
                j.placement = tuple(range(g, g + take))
                g += take
            sim = FluidNetworkSim(topo, incremental=incremental)
            sim.configure(jobs)
            sim.advance(300.0)
            name = topo.host_link(0).name
            sim.set_link_capacity(name, 12.5)
            sim.advance(600.0)
            sims.append(sim)
        inc, full = sims
        ra, rb = inc._allocate(), full._allocate()
        assert set(ra) == set(rb)
        for jid in ra:
            assert ra[jid] == pytest.approx(rb[jid], rel=1e-9)

    def test_set_link_capacity_clears_alloc_cache(self):
        topo, jobs, sim = _two_job_sim()
        sim.advance(100.0)
        assert sim._alloc_cache
        old = sim.set_link_capacity(topo.host_link(0).name, 1.0)
        assert old > 1.0
        assert not sim._alloc_cache  # stale rates can't be served


# --------------------------------------------------------------------- #
# replay determinism: batch vs serve, scalar vs vectorized
# --------------------------------------------------------------------- #
class TestReplayParity:
    @pytest.mark.parametrize("name", CHURN)
    def test_batch_run_is_reproducible(self, name):
        spec = get_scenario(name)
        m1, d1, c1 = _run_batch(spec, "themis")
        m2, d2, c2 = _run_batch(spec, "themis")
        assert m1.summary() == m2.summary()
        assert _decision_tuples(d1) == _decision_tuples(d2)
        assert c1.applied_count == c2.applied_count > 0

    def test_serve_replay_matches_batch_linkfail(self):
        spec = get_scenario("churn-linkfail")
        m_batch, d_batch, chaos = _run_batch(spec, "th+cassini")
        m_serve, d_serve, tel = _run_served(spec, "th+cassini")
        assert m_batch.summary() == m_serve.summary()
        assert _decision_tuples(d_batch) == _decision_tuples(d_serve)
        assert tel["faults_applied"] == chaos.applied_count > 0
        assert tel["degraded_decisions"] == 0.0

    def test_serve_replay_matches_batch_elastic(self):
        spec = get_scenario("churn-elastic")
        m_batch, d_batch, chaos = _run_batch(spec, "th+cassini")
        m_serve, d_serve, tel = _run_served(spec, "th+cassini")
        assert m_batch.summary() == m_serve.summary()
        assert _decision_tuples(d_batch) == _decision_tuples(d_serve)
        assert tel["faults_applied"] == chaos.applied_count > 0

    def test_serve_replay_matches_batch_jitter(self):
        spec = get_scenario("churn-jitter")
        m_batch, d_batch, chaos = _run_batch(spec, "th+cassini")
        m_serve, d_serve, tel = _run_served(spec, "th+cassini")
        assert m_batch.summary() == m_serve.summary()
        assert _decision_tuples(d_batch) == _decision_tuples(d_serve)
        assert tel["faults_applied"] == chaos.applied_count > 0

    @pytest.mark.slow
    @pytest.mark.parametrize("name", CHURN)
    def test_scalar_vectorized_parity_under_faults(self, name):
        """Fault application is engine-symmetric: the scalar oracle and
        the vectorized engine replay a schedule bit-identically.  (The
        all-registered equivalence sweep covers this too — this row keeps
        a named, per-scenario failure when it breaks.)"""
        spec = get_scenario(name)
        rv = spec.run("themis", vectorized=True)
        rs = spec.run("themis", vectorized=False)
        assert rv.metrics.summary() == rs.metrics.summary()

    def test_empty_cluster_gap_does_not_stall_clock(self):
        """A fault window where every job is queued (e.g. a grow past the
        fabric) leaves the cluster empty mid-run; advance must jump the
        clock instead of spinning the event loop."""
        topo = Topology.paper_testbed()
        sim = FluidNetworkSim(topo)
        sim.advance(5_000.0)
        assert sim.now_ms == 5_000.0


# --------------------------------------------------------------------- #
# graceful degradation: the serve worker never dies
# --------------------------------------------------------------------- #
class _FlakyScheduler(Scheduler):
    """Raises on every Nth schedule() call; trivial placements otherwise."""

    name = "flaky"

    def __init__(self, every: int = 2) -> None:
        self.calls = 0
        self.every = every

    def allocate_workers(self, state: ClusterState) -> dict[str, int]:
        return {j.job_id: min(j.num_workers, 2) for j in state.running}

    def propose(self, state, workers, k):
        placements = {}
        g = 0
        for job in state.running:
            take = workers.get(job.job_id, 0)
            placements[job.job_id] = tuple(range(g, g + take))
            g += take
        return [placements]

    def schedule(self, state: ClusterState) -> Decision:
        self.calls += 1
        if self.calls % self.every == 0:
            raise RuntimeError("pipeline exploded")
        return super().schedule(state)


class TestGracefulDegradation:
    def _arrivals(self, topo, n=4):
        jobs = poisson_trace(topo, num_jobs=n, seed=21)
        for i, j in enumerate(jobs):
            j.num_workers = min(j.num_workers, 2)
            j.arrival_ms = i * 1_000.0  # keep the stream ahead of queries
        return jobs

    def test_pipeline_exception_falls_back_and_recovers(self):
        """Every other decision raises: the worker survives, counts the
        fallbacks, answers every query, and the healthy epochs go back to
        the real pipeline."""
        topo = Topology.paper_testbed()
        sched = _FlakyScheduler(every=2)
        svc = SchedulerService(
            topo, sched, epoch_ms=10_000.0, compute_jitter=0.0,
        )
        with svc:
            for job in self._arrivals(topo):
                svc.submit(JobArrival(job))
            for k in range(1, 9):
                view = svc.query(at_ms=k * 12_000.0)  # never raises
                assert view.placements is not None
            tel = svc.telemetry()
        assert tel["pipeline_errors"] > 0
        assert tel["degraded_decisions"] == tel["pipeline_errors"]
        # healthy epochs outnumber the failures: the service recovered
        assert tel["decisions"] > tel["degraded_decisions"]
        assert svc._worker_exc is None  # the worker never died

    def test_fallback_uses_host_scheduler(self):
        """CassiniAugmented pipeline that raises → the host (Themis)
        placement is used, not the frozen last decision."""
        from repro.sched import CassiniAugmented, ThemisScheduler

        topo = Topology.paper_testbed()
        sched = CassiniAugmented(ThemisScheduler())
        calls = {"n": 0}

        def boom(state):
            calls["n"] += 1
            raise ValueError("scoring blew up")

        sched.pipeline.schedule = boom  # break the CASSINI stages only
        svc = SchedulerService(topo, sched, epoch_ms=30_000.0)
        with svc:
            for job in self._arrivals(topo, n=3):
                svc.submit(JobArrival(job))
            view = svc.query(at_ms=3_000.0)
            tel = svc.telemetry()
        # the fallback produced a real placement via the Themis host
        assert any(view.placements.values())
        assert tel["degraded_decisions"] > 0

    def test_realign_timeout_counts_as_degraded(self):
        topo = Topology.paper_testbed()

        class Slow(_FlakyScheduler):
            name = "slow"

            def schedule(self, state):
                import time as _t

                _t.sleep(0.02)
                return super(_FlakyScheduler, self).schedule(state)

        svc = SchedulerService(
            topo, Slow(), epoch_ms=30_000.0, realign_timeout_ms=1.0,
        )
        with svc:
            for job in self._arrivals(topo, n=2):
                svc.submit(JobArrival(job))
            svc.query(at_ms=1_000.0)
            tel = svc.telemetry()
        assert tel["realign_timeouts"] > 0
        assert tel["degraded_decisions"] >= tel["realign_timeouts"]

    def test_fallback_off_propagates(self):
        """fallback=False restores the old contract: the pipeline error
        kills the worker (and surfaces on the next submit)."""
        topo = Topology.paper_testbed()
        svc = SchedulerService(
            topo, _FlakyScheduler(every=1), epoch_ms=10_000.0,
            fallback=False,
        )
        with svc:
            for job in self._arrivals(topo, n=2):
                svc.submit(JobArrival(job))
            with pytest.raises(Exception):
                svc.query(at_ms=1_000.0)

    def test_faults_plus_flaky_pipeline_answers_everything(self):
        """Faults and pipeline failures together: every QueryPlacement is
        answered and the books balance in telemetry()."""
        topo = Topology.paper_testbed()
        jobs = self._arrivals(topo, n=4)
        schedule = FaultSchedule.linkfail(
            topo, seed=3, horizon_ms=80_000.0, events=4
        )
        svc = SchedulerService(
            topo, _FlakyScheduler(every=3), epoch_ms=10_000.0,
            fault_schedule=schedule,
        )
        with svc:
            for job in jobs:
                svc.submit(JobArrival(job))
            for k in range(1, 11):
                svc.query(at_ms=k * 10_000.0)
            metrics = svc.drain(200_000.0)
            tel = svc.telemetry()
        assert tel["faults_applied"] > 0
        assert tel["degraded_decisions"] > 0
        assert svc._worker_exc is None
        assert metrics.jobs  # drained to a real Metrics


# --------------------------------------------------------------------- #
# telemetry hardening (satellite 2 rides here: see also test_serve.py)
# --------------------------------------------------------------------- #
class TestTelemetryUnderFire:
    def test_telemetry_never_raises_mid_incident(self):
        """telemetry() with a half-broken service (net counters gone,
        scheduler module missing) still returns the core counters."""
        topo = Topology.paper_testbed()
        svc = SchedulerService(
            topo, _FlakyScheduler(), epoch_ms=10_000.0, start=False,
        )
        svc.net.alloc_solves = None  # poison the net-counter section
        tel = svc.telemetry()
        assert tel["degraded_decisions"] == 0.0
        assert tel["decisions"] == 0.0
        assert "alloc_cache_solves" not in tel  # degraded to fewer keys
        assert "alloc_cache_comp_solves" not in tel
        assert "alloc_cache_comp_hits" not in tel
