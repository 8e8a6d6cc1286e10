"""Incremental cache parity: delta add/remove/update sequences must
reproduce the full-rebuild state bit for bit — incidence tables, exec
state, water-filling allocations, advance traces, plugin link cache
(ISSUE 6 tentpole, property-tested; the hypothesis harness deepens the
seeded sweeps when hypothesis is installed)."""

from __future__ import annotations

import copy
import random

import numpy as np
import pytest

from repro.cluster import FluidNetworkSim, Topology, poisson_trace
from repro.cluster.job import JobState
from repro.core.plugin import CassiniModule


def _placed_jobs(topo, n, seed, workers_cap=3):
    jobs = poisson_trace(topo, num_jobs=n, seed=seed)
    g = 0
    for j in jobs:
        take = min(j.num_workers, workers_cap)
        j.placement = tuple(range(g, g + take))
        g += take
    return jobs


def _state_sig(sim):
    if sim.vectorized and sim._inc is not None:
        sim._sync_execs()
    return {
        jid: (
            ex.seg_idx, ex.remaining, ex.delay_ms, ex.marks,
            ex.iter_start_ms, ex.applied_shift_ms, ex.ideal_next_ms,
            ex.consec_adjust, ex.skip_record,
        )
        for jid, ex in sim._execs.items()
    }


def _incidence_sig(sim):
    """Live rows of the delta engine's incidence, in exec order."""
    if not sim.vectorized:
        return None
    return [
        sim._inc.rows[sim._slot_of[jid]].tolist() for jid in sim._execs
    ]


def _assert_equal(rebuild, delta):
    assert _state_sig(rebuild) == _state_sig(delta)
    assert rebuild._allocate() == delta._allocate()
    assert rebuild._mark_rates() == delta._mark_rates()
    if rebuild.vectorized:
        # a rebuilt incidence row set over the same running order (an
        # engine rebuilt over no running job holds no incidence at all)
        inc = rebuild._inc
        rows = [] if inc is None else [r.tolist() for r in inc.rows]
        assert rows == _incidence_sig(delta)


def _apply_script(topo, script, *, advance_ms=400.0):
    """Run one op script through rebuild-only and delta engines in
    lockstep, checking bit-exact parity after every step.

    ``script`` is a list of ("add", job) / ("remove", job_id) /
    ("migrate", job_id, new_placement) /
    ("resize", job_id, new_num_workers, new_placement) /
    ("cutoff", job_id) / ("advance",) ops over deep-copied job
    populations.
    """
    A = FluidNetworkSim(topo, seed=0)           # rebuild reference
    B = FluidNetworkSim(topo, seed=0)           # delta engine
    jobs_a: list = []
    jobs_b: list = []

    def by_id(jobs, jid):
        return next(j for j in jobs if j.job_id == jid)

    for op in script:
        if op[0] == "add":
            ja, jb = copy.deepcopy(op[1]), copy.deepcopy(op[1])
            jobs_a.append(ja)
            jobs_b.append(jb)
            A.configure(list(jobs_a))
            assert B.configure_incremental(list(jobs_b)) == "delta"
        elif op[0] == "remove":
            jobs_a = [j for j in jobs_a if j.job_id != op[1]]
            jobs_b = [j for j in jobs_b if j.job_id != op[1]]
            A.configure(list(jobs_a))
            assert B.configure_incremental(list(jobs_b)) == "delta"
        elif op[0] == "migrate":
            by_id(jobs_a, op[1]).placement = tuple(op[2])
            by_id(jobs_b, op[1]).placement = tuple(op[2])
            A.configure(list(jobs_a))
            assert B.configure_incremental(list(jobs_b)) == "delta"
        elif op[0] == "resize":
            # elastic resize (chaos JobResize follow-through): the worker
            # count changes the comm pattern/segments, the placement the
            # link columns — update_job must drop the alloc cache for both
            for jobs in (jobs_a, jobs_b):
                j = by_id(jobs, op[1])
                j.num_workers = op[2]
                j.placement = tuple(op[3])
            A.configure(list(jobs_a))
            assert B.configure_incremental(list(jobs_b)) == "delta"
        elif op[0] == "cutoff":
            by_id(jobs_a, op[1]).state = JobState.CUTOFF
            by_id(jobs_b, op[1]).state = JobState.CUTOFF
        elif op[0] == "advance":
            fa = A.advance(A.now_ms + advance_ms)
            fb = B.advance(B.now_ms + advance_ms)
            assert [j.job_id for j in fa] == [j.job_id for j in fb]
            assert A.now_ms == B.now_ms
        else:  # pragma: no cover
            raise AssertionError(op)
        _assert_equal(A, B)
    return A, B


# --------------------------------------------------------------------- #
# seeded sweeps (always run)
# --------------------------------------------------------------------- #
class TestDeltaParitySeeded:
    def test_arrival_departure_churn(self):
        topo = Topology.paper_testbed()
        jobs = _placed_jobs(topo, 8, seed=3)
        script = []
        for j in jobs[:5]:
            script += [("add", j), ("advance",)]
        script += [
            ("remove", jobs[1].job_id), ("advance",),
            ("add", jobs[5]), ("advance",),
            ("remove", jobs[3].job_id),
            ("remove", jobs[0].job_id), ("advance",),
            ("add", jobs[6]), ("add", jobs[7]), ("advance",),
        ]
        _apply_script(topo, script)

    def test_cutoff_jobs_stay_frozen(self):
        """CUTOFF jobs hold no link share in either engine — the delta
        path must agree through cutoff churn too."""
        topo = Topology.paper_testbed()
        jobs = _placed_jobs(topo, 4, seed=9)
        script = [("add", j) for j in jobs]
        script += [
            ("advance",),
            ("cutoff", jobs[0].job_id), ("advance",),
            ("cutoff", jobs[2].job_id), ("advance",),
            ("remove", jobs[0].job_id), ("advance",),
        ]
        _apply_script(topo, script)

    def test_inplace_migration_clears_cache(self):
        topo = Topology.paper_testbed()
        jobs = _placed_jobs(topo, 4, seed=5)
        script = [("add", j) for j in jobs] + [("advance",)]
        # move job 1 to a different rack: link columns change in place
        script += [
            ("migrate", jobs[1].job_id, tuple(range(18, 18 + len(jobs[1].placement)))),
            ("advance",),
        ]
        A, B = _apply_script(topo, script)
        assert B._execs  # sanity: still running

    def test_departure_keeps_alloc_cache(self):
        """remove_job only clears the alive bit — the water-filling cache
        survives, and post-departure solves reuse it where sound."""
        topo = Topology.paper_testbed()
        jobs = _placed_jobs(topo, 5, seed=7)
        B = FluidNetworkSim(topo, seed=0)
        for i, j in enumerate(jobs):
            assert B.configure_incremental(jobs[: i + 1]) == "delta"
        B.advance(B.now_ms + 1000.0)
        cache_before = len(B._alloc_cache)
        B.configure_incremental([j for j in jobs if j is not jobs[2]])
        assert len(B._alloc_cache) == cache_before  # retained, not cleared

    def test_compaction_after_heavy_departures(self):
        """Dead slots outnumbering live ones trigger a compacting rebuild;
        parity must hold across the compaction boundary."""
        topo = Topology(num_racks=8, servers_per_rack=6)
        jobs = _placed_jobs(topo, 14, seed=11, workers_cap=2)
        script = [("add", j) for j in jobs] + [("advance",)]
        for j in jobs[:11]:  # 11 dead vs 3 live → compaction fires
            script.append(("remove", j.job_id))
        script += [("advance",), ("add", _placed_jobs(topo, 15, seed=12)[-1])]
        A, B = _apply_script(topo, script)
        assert len(B._slots) == int(np.count_nonzero(B._alive))  # compacted

    def test_resize_churn_matches_rebuild(self):
        """Mid-epoch elastic resizes (grow and shrink) mixed with
        arrivals/departures: the update_job resize path must stay
        bit-exact against the full rebuild (ISSUE 8 satellite)."""
        topo = Topology.paper_testbed()
        jobs = _placed_jobs(topo, 6, seed=13)
        script = [("add", j) for j in jobs[:4]] + [("advance",)]
        script += [
            # shrink job 0 (device loss), same base slot
            ("resize", jobs[0].job_id, 2, (0, 1)), ("advance",),
            # grow job 2 onto a wider span (crosses a rack boundary)
            ("resize", jobs[2].job_id, 4, (10, 11, 12, 13)), ("advance",),
            ("add", jobs[4]), ("remove", jobs[1].job_id), ("advance",),
            # resize straight after membership churn
            ("resize", jobs[3].job_id, 3, (18, 19, 20)),
            ("add", jobs[5]), ("advance",),
            # resize back to the original width: no stale cache reuse
            ("resize", jobs[0].job_id, 3, (0, 1, 2)), ("advance",),
        ]
        _apply_script(topo, script)

    def test_resize_same_placement_drops_cache(self):
        """A resize that keeps the placement (pattern change only) must
        still invalidate the allocation cache — the (mask, seg) keys
        would otherwise serve rates for the old segment list."""
        topo = Topology.paper_testbed()
        jobs = _placed_jobs(topo, 3, seed=21)
        B = FluidNetworkSim(topo, seed=0)
        assert B.configure_incremental(copy.deepcopy(jobs)) == "delta"
        B.advance(B.now_ms + 500.0)
        assert B._alloc_cache  # warmed
        resized = copy.deepcopy(jobs)
        resized[1].num_workers = max(1, resized[1].num_workers - 1)
        assert B.configure_incremental(resized) == "delta"
        assert not B._alloc_cache  # dropped, not reused
        B.advance(B.now_ms + 500.0)  # re-solves cleanly on the new pattern
        assert B._alloc_cache

    def test_reorder_falls_back_to_rebuild(self):
        topo = Topology.paper_testbed()
        jobs = _placed_jobs(topo, 4, seed=2)
        B = FluidNetworkSim(topo, seed=0)
        assert B.configure_incremental(list(jobs)) == "delta"
        assert B.configure_incremental(list(reversed(jobs))) == "rebuild"
        A = FluidNetworkSim(topo, seed=0)
        A.configure(list(reversed(copy.deepcopy(jobs))))
        assert _state_sig(A) == _state_sig(B)

    def test_add_existing_job_rejected(self):
        topo = Topology.paper_testbed()
        jobs = _placed_jobs(topo, 2, seed=0)
        B = FluidNetworkSim(topo, seed=0)
        B.configure_incremental(jobs)
        with pytest.raises(ValueError, match="already configured"):
            B.add_job(jobs[0])
        with pytest.raises(KeyError):
            B.remove_job("nope")

    def test_scalar_engine_delta_parity(self):
        """The delta path is engine-agnostic: the scalar oracle under
        configure_incremental matches its own rebuild too."""
        topo = Topology.paper_testbed()
        jobs = _placed_jobs(topo, 5, seed=4)
        A = FluidNetworkSim(topo, seed=0, vectorized=False)
        B = FluidNetworkSim(topo, seed=0, vectorized=False)
        ja, jb = copy.deepcopy(jobs), copy.deepcopy(jobs)
        for i in range(len(jobs)):
            A.configure(ja[: i + 1])
            assert B.configure_incremental(jb[: i + 1]) == "delta"
            A.advance(A.now_ms + 300.0)
            B.advance(B.now_ms + 300.0)
            assert _state_sig(A) == _state_sig(B)


# --------------------------------------------------------------------- #
# topology incidence deltas
# --------------------------------------------------------------------- #
class TestIncidenceDeltas:
    def test_with_row_matches_rebuild(self):
        topo = Topology.paper_testbed()
        placements = [(0, 6), (1, 7), (2, 13)]
        inc = topo.incidence(placements[:2])
        grown = inc.with_row(topo.job_link_ids(placements[2]))
        full = topo.incidence(placements)
        assert (grown.matrix == full.matrix).all()
        assert grown.num_links == full.num_links

    def test_without_row_matches_rebuild(self):
        topo = Topology.paper_testbed()
        placements = [(0, 6), (1, 7), (2, 13)]
        inc = topo.incidence(placements)
        shrunk = inc.without_row(1)
        full = topo.incidence([placements[0], placements[2]])
        assert (shrunk.matrix == full.matrix).all()
        with pytest.raises(IndexError):
            inc.without_row(3)


# --------------------------------------------------------------------- #
# plugin link-cache deltas
# --------------------------------------------------------------------- #
class TestPluginCacheDeltas:
    def _score_pair(self, module, topo, placements, jobs):
        from repro.core.plugin import PlacementCandidate

        patterns = {j.job_id: j.pattern(num_workers=len(j.placement)) for j in jobs}
        caps = {}
        job_links = {}
        for j in jobs:
            links = topo.job_links(j.placement)
            job_links[j.job_id] = [l.name for l in links]
            caps.update({l.name: l.capacity_gbps for l in links})
        cand = PlacementCandidate(job_links=job_links, meta={})
        return module.score_candidates([cand], patterns, caps)

    def test_remove_job_evicts_and_resolves_identically(self):
        topo = Topology.paper_testbed()
        jobs = _placed_jobs(topo, 3, seed=1)
        # force link sharing: put everyone on the same uplink-heavy span
        for i, j in enumerate(jobs):
            j.placement = (i, 6 + i, 12 + i)
        module = CassiniModule(seed=0)
        first = self._score_pair(module, topo, None, jobs)
        hits0, misses0 = module.cache_hits, module.cache_misses
        assert misses0 > 0
        again = self._score_pair(module, topo, None, jobs)
        assert module.cache_hits > hits0          # warm second pass
        assert module.cache_misses == misses0
        evicted = module.remove_job(jobs[0].pattern(num_workers=3))
        assert evicted > 0
        cold = self._score_pair(module, topo, None, jobs)
        # re-solving after eviction reproduces the same frozen results
        assert [cand.link_scores for cand, _, _ in cold] == [
            cand.link_scores for cand, _, _ in again
        ]

    def test_add_job_is_documented_noop(self):
        module = CassiniModule(seed=0)
        topo = Topology.paper_testbed()
        jobs = _placed_jobs(topo, 1, seed=1)
        module.add_job(jobs[0].pattern(num_workers=2))
        assert module.remove_job("not-cached-model") == 0


# --------------------------------------------------------------------- #
# hypothesis harness (property-based churn; the seeded sweeps above run
# regardless, so the module keeps coverage where hypothesis is absent)
# --------------------------------------------------------------------- #
def _random_script(topo, seed: int, length: int):
    """Random churn script: arrivals, departures, migrations, elastic
    resizes, cutoffs and advances over a 10-job population (shared by
    hypothesis and the seeded fuzz fallback)."""
    rng = random.Random(seed)
    jobs = _placed_jobs(topo, 10, seed=seed % 50)
    alive: list = []
    pool = list(jobs)
    script = []
    widths: dict[str, int] = {}
    for _ in range(length):
        choices = ["advance"]
        if pool:
            choices += ["add", "add"]
        if alive:
            choices += ["remove", "migrate", "resize", "cutoff"]
        op = rng.choice(choices)
        if op == "add":
            j = pool.pop(0)
            alive.append(j)
            widths[j.job_id] = len(j.placement)
            script.append(("add", j))
        elif op == "remove":
            j = alive.pop(rng.randrange(len(alive)))
            script.append(("remove", j.job_id))
        elif op == "migrate":
            j = rng.choice(alive)
            w = widths[j.job_id]
            base = rng.randrange(0, topo.num_gpus - w)
            script.append(
                ("migrate", j.job_id, tuple(range(base, base + w)))
            )
        elif op == "resize":
            # elastic grow/shrink to a fresh width, chaos-JobResize style
            j = rng.choice(alive)
            w = rng.randint(1, 4)
            widths[j.job_id] = w
            base = rng.randrange(0, topo.num_gpus - w)
            script.append(
                ("resize", j.job_id, w, tuple(range(base, base + w)))
            )
        elif op == "cutoff":
            script.append(("cutoff", rng.choice(alive).job_id))
        else:
            script.append(("advance",))
    return script


@pytest.mark.parametrize("seed", [0, 17, 4242])
def test_random_churn_scripts_match_rebuild(seed):
    topo = Topology(num_racks=6, servers_per_rack=6)
    script = _random_script(topo, seed, length=14)
    _apply_script(topo, script, advance_ms=250.0)


try:
    from hypothesis import HealthCheck, given, settings
    from hypothesis import strategies as st
except ImportError:  # pragma: no cover - optional dev dependency
    pass
else:

    @given(seed=st.integers(0, 10_000), length=st.integers(4, 18))
    @settings(
        max_examples=25,
        deadline=None,
        suppress_health_check=[HealthCheck.too_slow],
    )
    def test_delta_sequences_match_rebuild(seed, length):
        topo = Topology(num_racks=6, servers_per_rack=6)
        script = _random_script(topo, seed, length)
        _apply_script(topo, script, advance_ms=250.0)
