"""Shared helpers for the benchmark harness."""

from __future__ import annotations

import statistics
import time
from typing import Callable

from repro.cluster import ClusterSimulator, nearest_rank
from repro.engine.scenarios import default_scheduler_factories

# the paper's scheduler line-up, shared with the scenario registry
SCHEDULERS: dict[str, Callable] = default_scheduler_factories()

# ONE percentile definition repo-wide: the benchmarks report the same
# nearest-rank statistic Metrics does (the seed had a subtly different
# floor-indexed copy here)
pct = nearest_rank


def run_trace(topo, jobs, sched, *, epoch_ms=300_000.0, jitter=0.005,
              horizon_ms=7_200_000.0, seed=0):
    sim = ClusterSimulator(topo, sched, epoch_ms=epoch_ms,
                           compute_jitter=jitter, seed=seed)
    t0 = time.time()
    metrics = sim.run(jobs, horizon_ms=horizon_ms)
    return metrics, time.time() - t0, sim


def timed(fn, *args, repeat=3, **kw):
    ts = []
    out = None
    for _ in range(repeat):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        ts.append((time.perf_counter() - t0) * 1e6)
    return out, statistics.median(ts)


def scoring_problems(num_links=24, jobs_per_link=2, capacity=50.0):
    """Synthetic k-job link problems for the batched-scoring benches.

    Every link carries ``jobs_per_link`` staggered single-phase jobs on a
    shared iteration time; at the default 5° precision a 3-job link lands
    on the batched exact product grid (the Algorithm-2 hot path for the
    paper's multi-tenant snapshots), and finer grids push the same
    problems onto the batched coordinate descent.
    """
    from repro.core.circle import CommPattern, Phase

    out = []
    for i in range(num_links):
        it = 300.0 + 10.0 * (i % 7)
        pats = []
        for k in range(jobs_per_link):
            start = (0.12 + 0.3 * k) % 1.0 * it
            dur = max(0.12, 0.42 - 0.06 * k) * it
            pats.append(
                CommPattern(it, (Phase(start, dur, 45.0 - 4.0 * k),),
                            name=f"l{i}j{k}")
            )
        out.append((pats, capacity))
    return out


def large_grid_k3_problems(num_links=8, capacity=50.0):
    """k=3 links that land on the batched exact grid with a *large* angle
    count — the regime where the ``(B, A)`` result round-trip dominated the
    PR-2 batched path.

    Each link carries one slow job (800 ms) and two fast ones (100 ms): at
    0.5° precision the unified circle has A = 720 angles (kernel-eligible)
    while the fast jobs wrap r = 8 times, so their admissible shift grids
    are 90 steps each — 8100 combinations, inside ``EXACT_GRID_LIMIT``, 90
    base-demand rows per link.  Half the links are lightly loaded (a
    zero-excess interleaving exists, so the fused kernel's early exit
    fires); half stay contended end to end.
    """
    from repro.core.circle import CommPattern, Phase

    out = []
    for i in range(num_links):
        light = i % 2 == 0
        scale = 0.55 if light else 1.0
        pats = [
            CommPattern(800.0, (Phase(60.0 + 35.0 * i, 260.0, 38.0 * scale),),
                        name=f"g{i}slow"),
            CommPattern(100.0, (Phase(12.0 + 3.0 * i, 34.0, 30.0 * scale),),
                        name=f"g{i}fast0"),
            CommPattern(100.0, (Phase(55.0 + 2.0 * i, 28.0, 34.0 * scale),),
                        name=f"g{i}fast1"),
        ]
        out.append((pats, capacity))
    return out


def mixed_angle_problems(wraps=(7, 11, 13, 17, 19, 23), links_per=4,
                         capacity=50.0):
    """k=2 link problems whose unified circles land on *different* angle
    counts — the heterogeneous-fabric regime the ragged launch targets.

    Each group pairs a slow job (period ``100·w`` ms) with a fast one
    (100 ms): at 0.5° precision the base 720-angle circle is rounded up to
    a multiple of ``lcm(wraps) = w``, so ``w ∈ {7, 11, 13, 17, 19, 23}``
    yields six distinct angle counts (721, 726, 728, 731, 722, 736 — all
    kernel-eligible).  The per-angle-count launch path pays one dispatch
    (and one under-filled 32-row block, scanned to its own shift bound)
    per group; the ragged path packs every row into ONE launch whose
    blocks share the scan.  Demands are kept contended so the zero-excess
    early exit does not shortcut either path.
    """
    from repro.core.circle import CommPattern, Phase

    out = []
    for wi, w in enumerate(wraps):
        for i in range(links_per):
            slow = CommPattern(
                100.0 * w,
                (Phase((5.0 + 9.0 * wi + 3.0 * i) * w, 38.0 * w, 44.0),),
                name=f"m{w}s{i}",
            )
            fast = CommPattern(
                100.0, (Phase(11.0 + 5.0 * i + 2.0 * wi, 41.0, 39.0),),
                name=f"m{w}f{i}",
            )
            out.append(([slow, fast], capacity))
    return out


def fluid_advance_case(racks, tenants=2):
    """A contended fluid-sim state from the ``rack-scaling-{racks}``
    scenario: ``tenants`` copies of its trace population in the shared
    :func:`repro.cluster.contended_snapshot` wrap-around pile-up — the
    allocator-bound multi-tenant regime the vectorized engine and the
    incremental re-solver target (the bench window never drains it)."""
    from repro.cluster import contended_snapshot
    from repro.engine.scenarios import get_scenario

    spec = get_scenario(f"rack-scaling-{racks}")
    topo = spec.topology()
    jobs = contended_snapshot(topo, lambda: spec.trace(topo), tenants=tenants)
    return topo, jobs


def sched_epoch_state(scenario_name="hetero-16rack", max_jobs=10):
    """A mid-simulation ``ClusterState`` for end-to-end epoch benches:
    the scenario's first ``max_jobs`` trace jobs, treated as running."""
    from repro.engine.scenarios import get_scenario
    from repro.sched.base import ClusterState

    spec = get_scenario(scenario_name)
    topo = spec.topology()
    jobs = spec.trace(topo)[:max_jobs]
    return ClusterState(topology=topo, now_ms=0.0, running=jobs, pending=[])


def sharded_fill_case(racks, window_ms):
    """The largest real rebuild-shaped water-filling union of the
    contended ``rack-scaling-{racks}`` state, captured while the
    incremental re-solver advances ``window_ms``.

    Returns ``(sim, union, comps, build_rows)``: ``union`` holds the
    captured ``(JR, binding, demand, live, mask)``, ``comps`` its
    independent components, and ``build_rows()`` their
    :func:`repro.cluster.shard.batched_fill` rows.  The capture-time
    member caps are restored on ``sim``, so the sharded fill, the fused
    host fill (``sim._wf_fill_core(JR, binding, demand, live)``) and the
    from-scratch solve all see the same instance.
    """
    import numpy as np

    from repro.cluster import FluidNetworkSim, contended_snapshot
    from repro.engine.scenarios import get_scenario

    spec = get_scenario(f"rack-scaling-{racks}")
    topo = spec.topology()
    jobs = contended_snapshot(topo, lambda: spec.trace(topo), tenants=2)
    sim = FluidNetworkSim(topo, vectorized=True, incremental=True)
    sim.configure(jobs)
    cap: dict = {}
    orig_rebuild = sim._wf_rebuild

    def probing_rebuild(comm_mask, caps_now):
        st = orig_rebuild(comm_mask, caps_now)
        rows_all, cols_all = sim._inc.flat_pairs
        bpair = st["binding"][cols_all] & comm_mask[rows_all]
        JR = np.unique(rows_all[bpair])
        if JR.size > cap.get("n", 0):
            cap.update(
                n=JR.size, JR=JR, mask=comm_mask.copy(),
                binding=st["binding"].copy(),
                demand=st["demand"].copy(), live=st["live"].copy(),
                caps=sim._cap_now.copy(),
            )
        return st

    sim._wf_rebuild = probing_rebuild
    sim.advance(window_ms)
    sim._wf_rebuild = orig_rebuild
    if not cap:
        raise RuntimeError(
            f"no rebuild-shaped fill captured at {racks} racks over "
            f"the {window_ms:g}ms window"
        )
    # sim._cap_now has drifted past the capture point by the end of the
    # advance: restore the capture-time snapshot
    sim._cap_now = cap["caps"]
    union = (cap["JR"], cap["binding"], cap["demand"], cap["live"], cap["mask"])
    JR, binding, demand, _, _ = union
    comps = sim._wf_components(JR, binding)
    cap_l = sim._inc.capacities

    def build_rows():
        rows = []
        for mem, lnks in comps:
            eff = np.where(
                demand[lnks] > cap_l[lnks] + 1e-9,
                sim.congested_efficiency, 1.0,
            )
            rows.append((
                sim._cap_now[mem],
                sim._inc.sub_incidence(mem, lnks),
                cap_l[lnks] * eff,
            ))
        return rows

    return sim, union, comps, build_rows
