#!/usr/bin/env python3
"""Prove that the CASSINI scheduler's device path runs on a TPU.

    python chip_smoke.py             # one chip: serve, kernel parity, fill
    python chip_smoke.py --chips 4   # four chips: the sharded fill only

One chip (the default) runs three phases in this one process:

  serve   hetero-16rack (64 servers, 16 racks, 50/100 Gbps NICs, 14
          Poisson jobs) replayed through ``SchedulerService`` with
          ``CassiniAugmented(ThemisScheduler(), precision_deg=0.5)``: the
          0.5° grid gives ≥ 512 angles, so the circle_score kernels score
          the links.  A few placement queries, then a drain to the
          scenario's horizon.  Kernel launches and device-reduced calls,
          summed over the run, must be > 0, with no degraded decision.
  parity  the link problems the served run solved, plus one 14-job
          fine-grid epoch: ``find_rotations_batched`` (ragged argmin
          kernel + float64 accept scan) must equal per-problem
          ``find_rotations`` (full-matrix kernel + host acceptance) bit
          for bit.
  fill    one ``batched_fill`` dispatch on one chip over the largest
          water-filling union of the contended rack-scaling-256 state,
          inside the 1e-9 band of the fused host fill.

``--chips 4`` runs only the sharded fill on a rack-scaling-256 union:
``batched_fill`` split over a 4-device mesh against the fused host fill
and the 1-device fill, inside the same band, with ``devices == 4``.

Earlier lines report per-phase wall time, compile counts, kernel
launches, decisions served and peak device memory.  The last line is one
JSON object naming the device.  Without a TPU the script exits non-zero
and prints no result line.  The compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``<repo>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback
from pathlib import Path

REPO = Path(__file__).resolve().parent
BAND = dict(rtol=1e-9, atol=1e-9)


def log(msg: str) -> None:
    print(msg, flush=True)


class Counters:
    """Backend compiles and persistent-cache hits, from JAX's monitoring
    events (registered before the first compile)."""

    def __init__(self, jax) -> None:
        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1
            elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)


def _add_stats(total, stats) -> None:
    for f in dataclasses.fields(total):
        setattr(total, f.name, getattr(total, f.name) + getattr(stats, f.name))


def phase_serve(ctx: dict) -> str:
    import repro.core.plugin as plugin
    from repro.core.compat import BatchStats
    from repro.engine.scenarios import get_scenario
    from repro.sched import CassiniAugmented, ThemisScheduler
    from repro.serve import JobArrival, QueryPlacement, SchedulerService

    spec = get_scenario("hetero-16rack")
    topo = spec.topology()
    sched = CassiniAugmented(ThemisScheduler(), precision_deg=0.5)
    totals = BatchStats()
    problems: list = []
    solve = plugin.find_rotations_batched

    # observe every batched solve of the run: sum its stats (the module
    # keeps only the last call's) and keep its link problems for parity
    def observed(batch, **kw):
        out = solve(batch, **kw)
        _add_stats(totals, kw["stats"])
        problems.extend(batch)
        return out

    plugin.find_rotations_batched = observed
    try:
        svc = SchedulerService(
            topo, sched, epoch_ms=spec.epoch_ms,
            compute_jitter=spec.compute_jitter, seed=spec.sim_seed,
        )
        with svc:
            queries = []
            for i, job in enumerate(spec.arrival_stream(topo)):
                svc.submit(JobArrival(job))
                if i % 4 == 3:
                    queries.append(
                        svc.submit(QueryPlacement(at_ms=job.arrival_ms))
                    )
            views = [q.result() for q in queries]
            metrics = svc.drain(spec.horizon_ms)
            tel = svc.telemetry()
    finally:
        plugin.find_rotations_batched = solve
    ctx["problems"] = problems
    decisions = len(svc.decisions)
    bad = {
        k: v for k, v in tel.items()
        if ("error" in k or k == "degraded_decisions") and v
    }
    assert not bad, f"the served run degraded: {bad}"
    assert views and all(v.placements for v in views), "empty placement query"
    assert totals.launches > 0 and totals.device_reduced > 0, totals
    assert totals.scalar_fallbacks == 0, totals
    jct = metrics.summary().get("avg_jct_ms", float("nan"))
    return (
        f"decisions={decisions} queries={len(views)} "
        f"kernel_launches={totals.launches} "
        f"device_reduced={totals.device_reduced} "
        f"batched_calls={totals.batched_calls} problems={totals.problems} "
        f"grid_rows={totals.grid_rows} descent_rows={totals.descent_rows} "
        f"avg_jct_ms={jct} "
        f"link_cache_hits={tel.get('link_cache_hits')} "
        f"schedule_p50_ms={tel.get('schedule_p50_ms')}"
    )


def phase_parity(ctx: dict) -> str:
    sys.path.insert(0, str(REPO))
    from benchmarks.common import sched_epoch_state
    import repro.core.plugin as plugin
    from repro.core.compat import BatchStats, find_rotations, find_rotations_batched
    from repro.sched import CassiniAugmented, ThemisScheduler

    deg = 0.5
    problems = list(ctx.get("problems", ()))
    served = len(problems)
    # one 14-job epoch adds denser links (k = 3 grids, a k = 4 descent)
    solve = plugin.find_rotations_batched

    def captured(batch, **kw):
        problems.extend(batch)
        return solve(batch, **kw)

    plugin.find_rotations_batched = captured
    try:
        CassiniAugmented(ThemisScheduler(), precision_deg=deg).schedule(
            sched_epoch_state("hetero-16rack", max_jobs=14)
        )
    finally:
        plugin.find_rotations_batched = solve
    stats = BatchStats()
    batched = find_rotations_batched(problems, precision_deg=deg, stats=stats)
    scalar = [find_rotations(p, c, precision_deg=deg) for p, c in problems]
    diff = [
        i for i, (b, s) in enumerate(zip(batched, scalar))
        if (b.shifts_steps, b.score, b.shifts_ms)
        != (s.shifts_steps, s.score, s.shifts_ms)
    ]
    assert not diff, (
        f"{len(diff)}/{len(problems)} problems differ from the scalar "
        f"search, first {diff[:5]}: "
        + "; ".join(
            f"{batched[i].shifts_steps}/{batched[i].score!r} vs "
            f"{scalar[i].shifts_steps}/{scalar[i].score!r}"
            for i in diff[:3]
        )
    )
    assert stats.device_reduced > 0 and stats.scalar_fallbacks == 0, stats
    angles = sorted({r.circle.num_angles for r in scalar})
    return (
        f"problems={len(problems)} (served={served}) bit_identical=True "
        f"grid={stats.grid_problems} descent={stats.descent_problems} "
        f"launches={stats.launches} device_reduced={stats.device_reduced} "
        f"angles={angles}"
    )


def _fill_case():
    import numpy as np

    sys.path.insert(0, str(REPO))
    from benchmarks.common import sharded_fill_case

    sim, union, comps, build_rows = sharded_fill_case(256, 1_200.0)
    JR, binding, demand, live, _ = union
    fused = sim._wf_fill_core(JR, binding, demand, live)
    n = len(sim._slots)

    def scatter(filled):
        rates = np.zeros(n)
        for (mem, _), vec in zip(comps, filled):
            rates[mem] = vec
        return rates[JR]

    return JR, comps, build_rows, fused, scatter


def phase_fill(ctx: dict) -> str:
    import numpy as np

    from repro.cluster import shard

    JR, comps, build_rows, fused, scatter = _fill_case()
    out, st = shard.batched_fill(build_rows(), ndev=1)
    rates = scatter(out)
    err = float(np.max(np.abs(rates - fused)))
    assert np.allclose(rates, fused, **BAND), f"max |sharded - fused| = {err}"
    assert st.devices == 1 and st.components == len(comps), st
    return (
        f"components={len(comps)} members={JR.size} "
        f"dispatches={st.dispatches} devices={st.devices} "
        f"max_abs_err_vs_fused={err!r}"
    )


def phase_fill4(ctx: dict) -> str:
    import numpy as np

    from repro.cluster import shard

    JR, comps, build_rows, fused, scatter = _fill_case()
    out4, st4 = shard.batched_fill(build_rows(), ndev=4)
    out1, st1 = shard.batched_fill(build_rows(), ndev=1)
    r4, r1 = scatter(out4), scatter(out1)
    err_f = float(np.max(np.abs(r4 - fused)))
    err_1 = float(np.max(np.abs(r4 - r1)))
    assert st4.devices == 4, st4
    assert np.allclose(r4, fused, **BAND), f"max |4-dev - fused| = {err_f}"
    assert np.allclose(r4, r1, **BAND), f"max |4-dev - 1-dev| = {err_1}"
    return (
        f"components={len(comps)} members={JR.size} "
        f"dispatches={st4.dispatches} devices={st4.devices} "
        f"padded_rows={st4.padded_rows} max_abs_err_vs_fused={err_f!r} "
        f"max_abs_err_vs_1dev={err_1!r}"
    )


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    import jax

    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(REPO / ".jax_cache"))
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        log(f"no TPU: jax.devices()[0].platform == {dev.platform!r}")
        return 2
    if len(devices) < args.chips:
        log(f"--chips {args.chips} needs {args.chips} devices, have {len(devices)}")
        return 2
    counters = Counters(jax)
    sys.path.insert(0, str(REPO / "src"))
    log(f"device: {dev.device_kind} x{len(devices)} "
        f"(jax {jax.__version__}, backend {jax.default_backend()})")

    phases = (
        [("fill4", phase_fill4)] if args.chips == 4
        else [("serve", phase_serve), ("parity", phase_parity),
              ("fill", phase_fill)]
    )
    ctx: dict = {}
    failed = []
    for name, fn in phases:
        c0, h0, t0 = counters.compiles, counters.cache_hits, time.perf_counter()
        try:
            detail = fn(ctx)
            status = "ok"
        except Exception as exc:
            failed.append(name)
            status = "FAILED"
            detail = f"{type(exc).__name__}: {exc}"
            traceback.print_exc()
        log(f"phase {name}: {status} wall_s={time.perf_counter() - t0:.3f} "
            f"compiles={counters.compiles - c0} "
            f"cache_hits={counters.cache_hits - h0} {detail}")
    mem = dev.memory_stats() or {}
    log(f"compiles_total={counters.compiles} "
        f"cache_hits_total={counters.cache_hits} "
        f"peak_device_bytes={mem.get('peak_bytes_in_use')}")
    if failed:
        log(f"FAILED phases: {failed}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
