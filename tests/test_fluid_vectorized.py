"""Vectorized fluid-engine equivalence harness.

The array-resident engine (``vectorized=True``, the default) must be an
*exact replay* of the scalar dict-of-dicts oracle — same rates, same event
sequence, same ``Metrics.summary()`` — not an approximation.  These tests
pin that contract:

  - allocator parity on random job/link incidences (seeded always;
    hypothesis-driven when available), compared exactly;
  - end-to-end scenario equivalence: identical per-job iteration-time /
    ECN traces and identical summaries, on cheap scenarios always and on
    every registered scenario under the ``slow`` marker;
  - the allocation-cache invalidation rule: segment transitions of
    compute-only jobs must NOT trigger re-allocation;
  - the fluid invariants (capacity, ECN monotonicity, CUTOFF release) on
    a 64-rack fabric through the vectorized path.
"""

import copy
import random

import numpy as np
import pytest

from repro.cluster import FluidNetworkSim, Topology, snapshot_trace
from repro.cluster.job import JobState
from repro.engine.scenarios import _REGISTRY, get_scenario

MODELS = ["vgg19", "wideresnet101", "dlrm", "gpt2", "resnet50", "bert"]


# ------------------------------------------------------------------ #
# topology incidence layer
# ------------------------------------------------------------------ #
def test_topology_incidence_arrays():
    t = Topology.paper_testbed()
    placements = [(0, 1, 6), (2, 8), (3,)]
    inc = t.incidence(placements)
    assert inc.num_links == len(t.links)
    assert inc.capacities.shape == (len(t.links),)
    # rows mirror job_links exactly (same links, same order)
    for p, cols in zip(placements, inc.rows):
        names = [l.name for l in t.job_links(p)]
        assert [list(t.links)[c] for c in cols.tolist()] == names
    # single-GPU job: no network links
    assert inc.rows[2].size == 0
    m = inc.matrix
    assert m.shape == (3, len(t.links))
    assert m.sum() == sum(r.size for r in inc.rows)


def test_job_links_cache_returns_consistent_results():
    t = Topology.paper_testbed()
    a = t.job_links((0, 6, 1))
    b = t.job_links((1, 0, 6))  # same worker set, different order
    assert [l.name for l in a] == [l.name for l in b]
    # cached lists are defensive copies
    a.append(None)
    assert None not in t.job_links((0, 1, 6))


# ------------------------------------------------------------------ #
# allocator parity on random incidences
# ------------------------------------------------------------------ #
def _random_state(seed: int):
    """Random topology + contended running set, both engine flavours."""
    rng = random.Random(seed)
    topo_args = dict(
        num_racks=rng.choice((2, 3, 4, 8)),
        servers_per_rack=rng.choice((2, 4)),
        nic_gbps=rng.choice((25.0, 50.0)),
        oversubscription=rng.choice((1.0, 2.0, 4.0)),
    )
    n_jobs = rng.randint(2, 8)
    specs = [
        (rng.choice(MODELS), rng.randint(1, 4), None) for _ in range(n_jobs)
    ]
    jobs_pair = []
    for _ in range(2):
        topo = Topology(**topo_args)
        jobs = snapshot_trace(
            [(m, w, 1400 if m.startswith("vgg") else 8) for m, w, _ in specs],
            iters=10_000,
        )
        r = random.Random(seed + 1)
        for j in jobs:
            j.placement = tuple(
                r.sample(range(topo.num_gpus), j.num_workers)
            )
            j.state = JobState.RUNNING
        jobs_pair.append((topo, jobs))
    return jobs_pair


def _assert_engine_parity(seed: int, windows=(50.0, 400.0, 1500.0)):
    (topo_v, jobs_v), (topo_s, jobs_s) = _random_state(seed)
    sim_v = FluidNetworkSim(topo_v, vectorized=True, seed=seed)
    sim_s = FluidNetworkSim(topo_s, vectorized=False, seed=seed)
    sim_v.configure(jobs_v)
    sim_s.configure(jobs_s)
    t = 0.0
    for w in windows:
        t += w
        # exact dict parity at every probe point: same members, same floats
        assert sim_v._allocate() == sim_s._allocate()
        assert sim_v._mark_rates() == sim_s._mark_rates()
        sim_v.advance(t)
        sim_s.advance(t)
        assert sim_v.now_ms == sim_s.now_ms
    for jv, js in zip(jobs_v, jobs_s):
        assert jv.iter_times_ms == js.iter_times_ms
        assert jv.ecn_marks == js.ecn_marks
        assert jv.iters_done == js.iters_done


@pytest.mark.parametrize("seed", range(12))
def test_allocator_parity_seeded(seed):
    _assert_engine_parity(seed)


def test_allocator_parity_hypothesis():
    hyp = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(min_value=100, max_value=100_000))
    def run(seed):
        _assert_engine_parity(seed, windows=(120.0, 900.0))

    run()


# ------------------------------------------------------------------ #
# end-to-end scenario equivalence
# ------------------------------------------------------------------ #
def _assert_scenario_equivalent(
    name: str,
    scheduler: str,
    horizon_cap: float,
    incremental: bool | None = None,
):
    spec = get_scenario(name)
    horizon = min(spec.horizon_ms, horizon_cap)
    rv = spec.run(
        scheduler, horizon_ms=horizon, vectorized=True,
        incremental=incremental,
    )
    rs = spec.run(
        scheduler, horizon_ms=horizon, vectorized=False,
        incremental=incremental,
    )
    # identical event sequences: every job's recorded iteration history,
    # marks, state and completion time match exactly
    by_v = {j.job_id: j for j in rv.metrics.jobs}
    by_s = {j.job_id: j for j in rs.metrics.jobs}
    assert by_v.keys() == by_s.keys()
    for jid, jv in by_v.items():
        js = by_s[jid]
        assert jv.iter_times_ms == js.iter_times_ms, jid
        assert jv.ecn_marks == js.ecn_marks, jid
        assert (jv.state, jv.finish_ms) == (js.state, js.finish_ms), jid
    # identical Metrics.summary() — bit for bit, NaNs matching by position
    sv, ss = rv.metrics.summary(), rs.metrics.summary()
    assert sv.keys() == ss.keys()
    for key in sv:
        assert sv[key] == ss[key] or (
            np.isnan(sv[key]) and np.isnan(ss[key])
        ), key


@pytest.mark.parametrize(
    "name,scheduler",
    [
        ("fig2-interleave", "cassini"),
        ("multitenant-2", "fair-share"),
        ("arrival-burst", "themis"),
    ],
)
def test_scenario_equivalence_fast(name, scheduler):
    _assert_scenario_equivalent(name, scheduler, horizon_cap=600_000.0)


@pytest.mark.slow
@pytest.mark.parametrize(
    "name",
    sorted(n for n, s in _REGISTRY.items() if not s.incremental),
)
def test_scenario_equivalence_all_registered(name):
    """Every registered bit-exact scenario (first scheduler in its
    line-up) produces identical metrics with the vectorized engine and
    the scalar oracle.  Specs that opt into the incremental re-solver are
    tolerance-band equivalent, not bit-exact — their escape hatch is
    covered below and their tolerance parity in
    tests/test_fluid_incremental.py."""
    spec = get_scenario(name)
    _assert_scenario_equivalent(
        name, spec.scheduler_names()[0], horizon_cap=600_000.0
    )


@pytest.mark.slow
@pytest.mark.parametrize("racks,horizon", [(256, 20_000.0), (1024, 5_000.0)])
def test_rack_scaling_xl_escape_hatch_bit_exact(racks, horizon):
    """``incremental=False`` on the 256/1024-rack scenarios must stay
    bit-exact against the scalar oracle — the escape hatch the XL specs
    promise (short horizon: the oracle is the slow side here)."""
    name = f"rack-scaling-{racks}"
    spec = get_scenario(name)
    assert spec.incremental  # the XL specs opt in by default
    _assert_scenario_equivalent(
        name, spec.scheduler_names()[0], horizon_cap=horizon,
        incremental=False,
    )


# ------------------------------------------------------------------ #
# allocation cache invalidation
# ------------------------------------------------------------------ #
def test_compute_only_segment_churn_hits_alloc_cache():
    """The comm-competing set keys the allocation cache: a linkless job
    cycling through its compute segments must never force a re-solve."""
    t = Topology.paper_testbed()
    jobs = snapshot_trace(
        [("vgg19", 2, 1400), ("vgg19", 2, 1400), ("bert", 1, 8)], iters=250
    )
    jobs[0].placement = (0, 6)
    jobs[1].placement = (1, 7)   # same rack pair: contended uplink
    jobs[2].placement = (2,)     # single worker: no network links
    for j in jobs:
        j.state = JobState.RUNNING
    sim = FluidNetworkSim(t)
    sim.configure(jobs)
    sim.advance(120_000.0)
    # the linkless job iterated plenty (many compute-segment events) …
    assert jobs[2].iters_done > 100
    # … yet the distinct comm sets are just the on/off combinations of the
    # two comm jobs' segments: a handful of solves, not one per event
    assert sim.alloc_solves <= 8


def test_cutoff_flip_changes_comm_set_and_rates():
    """CUTOFF membership is part of the cache key: flipping a job's state
    must produce a fresh allocation where the survivor gets the link."""
    t = Topology.paper_testbed()
    jobs = snapshot_trace([("vgg19", 2, 1400)] * 2, iters=4000)
    jobs[0].placement = (0, 6)
    jobs[1].placement = (1, 7)
    for j in jobs:
        j.state = JobState.RUNNING
    sim = FluidNetworkSim(t)
    sim.configure(jobs)
    sim.advance(30_000.0)
    jobs[0].state = JobState.CUTOFF
    sim.advance(60_000.0)
    alloc = sim._allocate()
    assert jobs[0].job_id not in alloc
    post = jobs[1].iter_times_ms[-5:]
    assert sum(post) / len(post) == pytest.approx(jobs[1].solo_iter_ms, rel=0.02)


# ------------------------------------------------------------------ #
# fluid invariants at rack scale (vectorized path)
# ------------------------------------------------------------------ #
def _contending_jobs_64rack(n_per_uplink=3, iters=40):
    """Jobs chained across racks of a 64-rack hetero fabric so every other
    uplink carries ``n_per_uplink`` tenants."""
    topo = Topology(
        num_racks=64,
        servers_per_rack=4,
        nic_gbps=50.0,
        rack_nic_gbps=tuple(100.0 if r % 2 else 50.0 for r in range(64)),
        oversubscription=4.0,  # one uplink per rack: guaranteed sharing
    )
    jobs = snapshot_trace(
        [("vgg19", 4, 1400)] * (16 * n_per_uplink), iters=iters
    )
    for i, j in enumerate(jobs):
        rack = (i // n_per_uplink) * 4   # every 4th rack pair
        k = i % n_per_uplink
        j.placement = (
            4 * rack + k, 4 * rack + 3 - k if k < 2 else 4 * rack + 2,
            4 * (rack + 1) + k, 4 * (rack + 1) + 3 - k if k < 2 else 4 * (rack + 1) + 2,
        )
        j.placement = tuple(dict.fromkeys(j.placement))  # de-dup, keep order
        j.state = JobState.RUNNING
    return topo, jobs


def test_capacity_never_exceeded_vectorized_64rack():
    topo, jobs = _contending_jobs_64rack()
    sim = FluidNetworkSim(topo)
    assert sim.vectorized
    sim.configure(jobs)
    probes = 0
    while sim.now_ms < 8_000.0 and sim._execs:
        rates = sim._allocate()
        per_link: dict[str, float] = {}
        for jid, ex in sim._execs.items():
            for l in ex.links:
                per_link[l.name] = per_link.get(l.name, 0.0) + rates.get(jid, 0.0)
        for lname, total in per_link.items():
            assert total <= topo.links[lname].capacity_gbps + 1e-6, lname
        probes += sum(1 for r in rates.values() if r > 0)
        sim.advance(sim.now_ms + 40.0)
    assert probes > 0


def test_ecn_monotone_vectorized_64rack():
    def marks_job0(n):
        topo, jobs = _contending_jobs_64rack(n_per_uplink=n, iters=25)
        sim = FluidNetworkSim(topo)
        sim.configure(jobs)
        sim.advance(200_000.0)
        assert jobs[0].iters_done == 25
        return sum(jobs[0].ecn_marks)

    two, three = marks_job0(2), marks_job0(3)
    assert two > 0
    assert three >= two


def test_cutoff_releases_share_vectorized_64rack():
    topo, jobs = _contending_jobs_64rack(n_per_uplink=2, iters=600)
    sim = FluidNetworkSim(topo)
    sim.configure(jobs)
    sim.advance(30_000.0)
    survivor = jobs[1]
    assert sum(survivor.iter_times_ms) / len(survivor.iter_times_ms) > (
        survivor.solo_iter_ms * 1.10
    )
    jobs[0].state = JobState.CUTOFF
    recorded = len(survivor.iter_times_ms)
    frozen_iters = jobs[0].iters_done
    sim.advance(90_000.0)
    assert jobs[0].job_id not in sim._allocate()
    assert jobs[0].iters_done == frozen_iters
    assert jobs[0].state is JobState.CUTOFF and jobs[0].finish_ms is None
    post = survivor.iter_times_ms[recorded + 2:]
    assert post, "survivor must keep iterating after the cutoff"
    assert sum(post) / len(post) == pytest.approx(survivor.solo_iter_ms, rel=0.02)


# ------------------------------------------------------------------ #
# per-component water-filling
# ------------------------------------------------------------------ #
def _hub_fabric():
    """16 racks of 4 servers at 4:1 (one uplink per rack), NICs
    alternating 50/100 Gbps."""
    return Topology(
        num_racks=16,
        servers_per_rack=4,
        nic_gbps=50.0,
        rack_nic_gbps=tuple(100.0 if r % 2 else 50.0 for r in range(16)),
        oversubscription=4.0,
    )


def _hub_server(group, k, leaf=False):
    """Server ``k`` of group ``group``'s hub rack (4g+1) or leaf rack
    (4g+3): both 100 Gbps, so a 3-tenant group shares 88 Gbps three ways
    while another group's 15 Gbps caps freeze first — a split the
    whole-fabric fill rounds differently from the group's own."""
    return 4 * (4 * group + (3 if leaf else 1)) + k


def _hub_jobs(groups=4, per_group=3, iters=400, tag="hub"):
    """``per_group`` tenants in each of ``groups`` hub groups: tenant k
    holds server k of its group's hub and leaf racks, so the tenants of a
    group share its two uplinks and no link with another group."""
    models = [MODELS[i % len(MODELS)] for i in range(groups * per_group)]
    jobs = snapshot_trace(
        [(m, 2, 1400 if m == "vgg19" else 8) for m in models], iters=iters
    )
    for i, j in enumerate(jobs):
        g, k = divmod(i, per_group)
        j.job_id = f"{tag}{i}-{j.model}"
        j.placement = (_hub_server(g, k), _hub_server(g, k, leaf=True))
        j.state = JobState.RUNNING
    return jobs


def _assert_alloc_equal(sim_v, sim_s):
    assert sim_v._allocate() == sim_s._allocate()
    assert sim_v._mark_rates() == sim_s._mark_rates()


def test_components_follow_hub_groups():
    topo = _hub_fabric()
    sim = FluidNetworkSim(topo)
    sim.configure(_hub_jobs())
    parts = sim._components().jobs
    assert [len(p) for p in parts] == [3, 3, 3, 3]
    # a tenant bridging group 0's hub and group 3's leaf rack joins them
    bridge = _hub_jobs(groups=1, per_group=1, tag="bridge")[0]
    bridge.placement = (_hub_server(0, 3), _hub_server(3, 3, leaf=True))
    sim.add_job(bridge)
    parts = sim._components().jobs
    assert sorted(len(p) for p in parts) == [3, 3, 7]
    # each component lists its jobs in running (slot) order
    order = list(sim._execs)
    for p in parts:
        assert p == sorted(p, key=order.index)
    sim.remove_job(bridge.job_id)
    assert [len(p) for p in sim._components().jobs] == [3, 3, 3, 3]


def test_component_engine_matches_component_oracle_under_churn():
    """Vectorized per-component fills and the scalar oracle's per-component
    loop agree bit for bit through arrivals, departures and a tenant that
    joins two groups into one component and leaves again."""
    sims, populations = [], []
    for vectorized in (True, False):
        topo = _hub_fabric()
        jobs = _hub_jobs()
        sim = FluidNetworkSim(topo, vectorized=vectorized, seed=5)
        assert sim.configure_incremental(list(jobs)) == "delta"
        sims.append(sim)
        populations.append(jobs)
    sim_v, sim_s = sims
    arrivals = [_hub_jobs(groups=1, per_group=2, tag="new") for _ in sims]
    solve = sim_v._cached_solve
    split = 0

    def counting(mask):
        # how often filling the whole comm set at once would round
        # otherwise: the parity below then holds only per component
        nonlocal split
        rates, marks, pos = solve(mask)
        split += not np.array_equal(rates, sim_v._solve_alloc(mask)[0])
        return rates, marks, pos

    sim_v._cached_solve = counting

    def step(ms):
        for sim in sims:
            sim.advance(sim.now_ms + ms)
        assert sim_v.now_ms == sim_s.now_ms
        _assert_alloc_equal(sim_v, sim_s)

    def reconfigure(edit):
        for side, (sim, jobs) in enumerate(zip(sims, populations)):
            edit(side, jobs)
            sim.configure_incremental(list(jobs))
        _assert_alloc_equal(sim_v, sim_s)

    _assert_alloc_equal(sim_v, sim_s)
    step(700.0)
    # a departure from group 1, an arrival into group 2
    reconfigure(lambda side, jobs: jobs.pop(4))
    step(900.0)

    def arrive(side, jobs):
        new = arrivals[side][0]
        new.placement = (_hub_server(2, 3), _hub_server(2, 3, leaf=True))
        jobs.append(new)

    reconfigure(arrive)
    step(1100.0)

    # a bridge from group 0's hub to group 3's leaf merges two components
    def bridge(side, jobs):
        new = arrivals[side][1]
        new.placement = (_hub_server(0, 3), _hub_server(3, 3, leaf=True))
        jobs.append(new)

    reconfigure(bridge)
    assert len(sim_v._components().jobs) == 3
    step(1300.0)
    reconfigure(lambda side, jobs: jobs.pop())
    assert len(sim_v._components().jobs) == 4
    step(2000.0)
    assert sim_v.alloc_comp_hits > 0 and sim_v.alloc_comp_solves > 0
    assert split > 0
    for jv, js in zip(*populations):
        assert jv.iter_times_ms == js.iter_times_ms
        assert jv.ecn_marks == js.ecn_marks
        assert jv.iters_done == js.iters_done > 0


def _all_comm(sim):
    mask = sim._alive.copy()
    mask &= np.array([bool(ex.links) for ex in sim._slots])
    return mask


def test_segment_change_resolves_only_its_component():
    """A change in one component's comm set costs at most one component
    solve; every other component keeps its solved rates untouched, and a
    component returning to a seen state hits the component cache."""
    sim = FluidNetworkSim(_hub_fabric())
    sim.configure(_hub_jobs())
    comps = sim._components()
    g0, g1 = comps.slots[0], comps.slots[1]
    full = _all_comm(sim)
    r_full, m_full, _ = sim._cached_solve(full)
    assert sim.alloc_comp_solves == 4

    def solve(mask):
        solves, hits = sim.alloc_comp_solves, sim.alloc_comp_hits
        rates, marks, _ = sim._cached_solve(mask)
        return rates, marks, sim.alloc_comp_solves - solves, sim.alloc_comp_hits - hits

    off0 = full.copy()
    off0[g0[0]] = False
    r, m, solved, hit = solve(off0)
    assert (solved, hit) == (1, 0)
    rest = np.setdiff1d(np.arange(len(full)), g0)
    assert np.array_equal(r[rest], r_full[rest])
    assert np.array_equal(m[rest], m_full[rest])
    # the component's own rates are its comm jobs solved alone
    sub = np.zeros_like(off0)
    sub[g0] = off0[g0]
    alone_r, alone_m = sim._solve_alloc(sub)
    assert np.array_equal(r[g0], alone_r[g0])
    assert np.array_equal(m[g0], alone_m[g0])

    off1 = full.copy()
    off1[g1[0]] = False            # group 0 back, group 1 changed
    r, m, solved, hit = solve(off1)
    assert (solved, hit) == (1, 1)
    assert np.array_equal(r[g0], r_full[g0])
    assert sim.alloc_solves == 3 and sim.alloc_hits == 0
    solve(off1)                    # the whole-fabric key answers first
    assert sim.alloc_hits == 1


def test_single_component_matches_whole_set_solve():
    """Where every running job shares one hub uplink, the per-component
    engine is the whole-fabric solve, bit for bit, at every event."""
    topo = _hub_fabric()
    jobs = _hub_jobs(groups=1, per_group=4)
    for k, j in enumerate(jobs):   # one hub rack, a leaf rack each
        j.placement = (k, 4 * (k + 1), 4 * (k + 1) + 1)
    sim = FluidNetworkSim(topo)
    sim.configure(jobs)
    assert len(sim._components().jobs) == 1
    solve = sim._cached_solve
    checked = 0

    def checking(mask):
        nonlocal checked
        rates, marks, pos = solve(mask)
        whole_r, whole_m = sim._solve_alloc(mask)
        assert np.array_equal(rates, whole_r)
        assert np.array_equal(marks, whole_m)
        checked += 1
        return rates, marks, pos

    sim._cached_solve = checking
    sim.advance(20_000.0)
    assert checked > 100 and sim.alloc_solves > 1


@pytest.mark.parametrize("change", ["migrate", "resize", "capacity"])
def test_cache_invalidation_forces_component_resolve(change):
    """A migrated or resized ``update_job`` and ``set_link_capacity`` change
    what a component key maps to: both caches drop, every component is
    solved again, and the rates are a fresh engine's."""
    topo = _hub_fabric()
    jobs = _hub_jobs()
    sim = FluidNetworkSim(topo)
    sim.configure_incremental(list(jobs))
    mask = _all_comm(sim)
    sim._cached_solve(mask)
    solves = sim.alloc_comp_solves
    sim._cached_solve(mask)
    assert sim.alloc_comp_solves == solves   # warm: nothing re-solved
    if change == "migrate":
        # group 0's tenant onto group 3's leaf rack
        jobs[1].placement = (_hub_server(0, 1), _hub_server(3, 1, leaf=True))
        sim.configure_incremental(list(jobs))
    elif change == "resize":
        jobs[1].num_workers = 3
        jobs[1].placement = (
            _hub_server(0, 1), _hub_server(0, 1, leaf=True),
            _hub_server(0, 2, leaf=True),
        )
        sim.configure_incremental(list(jobs))
    else:
        sim.set_link_capacity(sim._execs[jobs[0].job_id].links[1].name, 25.0)
    assert not sim._comp_cache and not sim._alloc_cache
    mask = _all_comm(sim)
    rates, marks, _ = sim._cached_solve(mask)
    n_comps = len(sim._components().jobs)
    assert sim.alloc_comp_solves == solves + n_comps
    fresh = FluidNetworkSim(topo)
    fresh.configure(copy.deepcopy(jobs))
    want_r, want_m, _ = fresh._cached_solve(mask)
    assert np.array_equal(rates, want_r)
    assert np.array_equal(marks, want_m)
