"""Benchmark harness: one module per paper table/figure + the roofline.

    PYTHONPATH=src python -m benchmarks.run [--only fig2,roofline] \
        [--json [BENCH.json]]

Prints ``name,us_per_call,derived`` CSV rows; ``--json`` additionally
writes the rows as machine-readable JSON (name, us_per_call, speedup,
derived) — bare ``--json`` defaults to ``BENCH.json``, the artifact CI
uploads from the bench job and diffs against the committed baseline via
``benchmarks/compare.py`` (cross-PR regression gate).  A bench row's own
assertion failing after its measurement was flushed exits nonzero with a
one-line ``BENCH GATE FAILED`` reason, so the partial artifact can never
mask which gate tripped.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import traceback

ALL = [
    "fig2_interleave",
    "fig9_poisson",
    "fig10_dynamic",
    "fig11_modelpar",
    "table2_snapshots",
    "fig13_multigpu",
    "fig15_discretization",
    "ablations",
    "kernels",
    "arrival",
    "fluid_advance",
    "fluid_shard",
    "sched_epoch",
    "serve",
    "fault_replay",
    "roofline",
]


def _kernel_bench():
    """Micro-bench the three Pallas kernels (interpret mode) vs oracles.

    A generator (like every bench set here): rows reach the harness — and
    the ``--json`` artifact — as they complete, so a later assertion
    failure cannot swallow the measurements that explain it.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.circle_score.ops import circle_score
    from repro.kernels.circle_score.ref import circle_score_ref
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.ssd_scan.ops import ssd_scan

    from .common import timed

    rng = np.random.default_rng(0)
    base = jnp.asarray(rng.random((16, 720)) * 60, jnp.float32)
    cand = jnp.asarray(rng.random((16, 720)) * 60, jnp.float32)
    _, us_ref = timed(lambda: circle_score_ref(base, cand, 50.0).block_until_ready())
    _, us_k = timed(lambda: circle_score(base, cand, 50.0).block_until_ready())
    yield {"name": "kernels/circle_score(16x720)", "us_per_call": us_k,
           "derived": f"jnp_ref={us_ref:.0f}us (interpret-mode kernel; "
                      f"TPU target compiles Mosaic)"}
    q = jnp.asarray(rng.standard_normal((1, 512, 4, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((1, 512, 2, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((1, 512, 2, 64)), jnp.bfloat16)
    _, us_fa = timed(lambda: flash_attention(q, k, v).block_until_ready(), repeat=1)
    yield {"name": "kernels/flash_attention(512)", "us_per_call": us_fa,
           "derived": "blocked online-softmax; causal GQA"}
    x = jnp.asarray(rng.standard_normal((1, 256, 4, 32)), jnp.float32)
    dt = jnp.asarray(rng.random((1, 256, 4)) * 0.3 + 0.05, jnp.float32)
    al = jnp.asarray(rng.standard_normal(4) * 0.3, jnp.float32)
    Bm = jnp.asarray(rng.standard_normal((1, 256, 16)), jnp.float32)
    Cm = jnp.asarray(rng.standard_normal((1, 256, 16)), jnp.float32)
    _, us_ssd = timed(lambda: ssd_scan(x, dt, al, Bm, Cm, chunk=64).block_until_ready(),
                      repeat=1)
    yield {"name": "kernels/ssd_scan(256)", "us_per_call": us_ssd,
           "derived": "chunked SSD w/ VMEM state carry"}
    yield from _batched_scoring_bench()
    yield from _fused_reduction_bench()
    yield from _ragged_launch_bench()
    yield from _tuned_dispatch_bench()


def _batched_scoring_bench():
    """Batched candidate scoring (``find_rotations_batched``) vs the scalar
    per-link loop the seed scheduler ran — the Algorithm-2 hot path.

    Doubles as the CI smoke check for the batched paths: every
    configuration asserts (via ``BatchStats``) that no problem silently
    fell back to the scalar search, and the k=3 grid configuration asserts
    a >1x measured speedup over the scalar loop.
    """
    from repro.core.compat import BatchStats, find_rotations, find_rotations_batched

    from .common import scoring_problems, timed

    cases = (
        # (precision_deg, links, jobs/link, expected batched path, label)
        (5.0, 24, 2, "grid", "A~72 typical"),
        (0.5, 24, 2, "grid", "A~720 fine-grid"),
        (5.0, 12, 3, "grid", "A~72 k=3 product grid"),
        (0.5, 8, 3, "descent", "A~720 k=3 lockstep descent"),
    )
    for deg, links, k, path, label in cases:
        probs = scoring_problems(num_links=links, jobs_per_link=k)
        scalar = lambda: [
            find_rotations(p, c, precision_deg=deg, backend="numpy")
            for p, c in probs
        ]
        batched = lambda: find_rotations_batched(probs, precision_deg=deg)
        batched()  # warm up (jit compile on the pallas path)
        _, us_scalar = timed(scalar)
        _, us_batch = timed(batched)
        speedup = us_scalar / us_batch

        stats = BatchStats()
        find_rotations_batched(probs, precision_deg=deg, stats=stats)
        yield {
            "name": f"kernels/score_batched({links}x{k}job,{deg:g}deg)",
            "us_per_call": us_batch,
            "speedup": speedup,
            "derived": (
                f"scalar_loop={us_scalar:.0f}us speedup={speedup:.2f}x "
                f"({label}; batched {path} path, "
                f"{stats.grid_rows + stats.descent_rows} rows in "
                f"{stats.batched_calls} calls — pallas kernel for A>=512, "
                f"vectorized numpy below)"
            ),
        }
        # CI smoke assertions: the batched path must actually be taken.
        # (After the yield: a failing gate still leaves the measured row
        # in the --json artifact to explain itself.)
        if stats.scalar_fallbacks:
            raise RuntimeError(
                f"{stats.scalar_fallbacks}/{stats.problems} problems fell "
                f"back to the scalar path at {deg:g}deg k={k}: {stats}"
            )
        taken = stats.grid_problems if path == "grid" else stats.descent_problems
        if taken != len(probs):
            raise RuntimeError(
                f"expected all {len(probs)} problems on the batched {path} "
                f"path at {deg:g}deg k={k}, got {stats}"
            )
        if k == 3 and path == "grid" and speedup <= 1.0:
            raise RuntimeError(
                f"batched k=3 grid must beat the scalar loop: "
                f"{speedup:.2f}x (scalar={us_scalar:.0f}us batched={us_batch:.0f}us)"
            )



def _fused_reduction_bench():
    """Device-resident rotation search vs the PR-2 full-matrix round-trip.

    Large-grid k=3 problems (A=720, 90 product-grid rows per link) where
    the batched path previously shipped the whole ``(B, A)`` excess matrix
    to the host for ``np.argmin`` + acceptance.  With ``device_reduce``
    the fused ``circle_score_argmin`` / ``circle_score_segmin`` kernels
    keep the reduction on device and return O(problems) scalars.

    CI assertions: every chunk of the large-grid config must be device-
    reduced (zero ``(B, A)`` host transfers), the returned bytes must drop
    ≥ 100x vs the matrices, the fused path must be ≥ 2x faster than the
    PR-2 batched path, and the selected shifts must be bit-identical to
    the scalar search.
    """
    from repro.core.compat import BatchStats, find_rotations, find_rotations_batched

    from .common import large_grid_k3_problems, timed

    probs = large_grid_k3_problems(num_links=8)
    deg = 0.5

    fused = lambda: find_rotations_batched(
        probs, precision_deg=deg, device_reduce=True
    )
    matrix = lambda: find_rotations_batched(
        probs, precision_deg=deg, device_reduce=False
    )
    fused()    # warm both jit caches
    matrix()
    res_fused, us_fused = timed(fused)
    res_matrix, us_matrix = timed(matrix)
    speedup = us_matrix / us_fused

    stats = BatchStats()
    find_rotations_batched(probs, precision_deg=deg, stats=stats)
    scalar = [find_rotations(p, c, precision_deg=deg) for p, c in probs]
    # row first, gates after: a failing assertion below still leaves the
    # measured row in the --json artifact to explain itself
    yield {
        "name": "kernels/score_fused_argmin(8x3job,0.5deg)",
        "us_per_call": us_fused,
        "speedup": speedup,
        "derived": (
            f"full_matrix_roundtrip={us_matrix:.0f}us speedup={speedup:.2f}x "
            f"(A=720 grid; {stats.grid_rows} rows device-reduced in "
            f"{stats.batched_calls} calls, {stats.bytes_returned}B returned "
            f"vs {stats.bytes_matrix}B matrices = "
            f"{stats.reduction_ratio:.0f}x less; in-kernel argmin scans only "
            f"admissible shifts + exits at zero excess)"
        ),
    }
    if any(
        f.shifts_steps != s.shifts_steps or f.score != s.score
        for f, s in zip(res_fused, scalar)
    ):
        raise RuntimeError("fused reduction diverged from the scalar search")
    if any(
        f.shifts_steps != m.shifts_steps for f, m in zip(res_fused, res_matrix)
    ):
        raise RuntimeError("device_reduce on/off selected different shifts")
    if stats.device_reduced != stats.batched_calls or stats.batched_calls == 0:
        raise RuntimeError(
            f"large-grid chunks must all be device-reduced "
            f"(zero (B,A) host transfers), got {stats}"
        )
    if stats.reduction_ratio < 100.0:
        raise RuntimeError(
            f"bytes_returned must drop >=100x vs the full matrices: "
            f"{stats.reduction_ratio:.0f}x ({stats.bytes_returned}B vs "
            f"{stats.bytes_matrix}B)"
        )
    if speedup < 2.0:
        raise RuntimeError(
            f"fused k=3 large-grid reduction must be >=2x over the PR-2 "
            f"batched path: {speedup:.2f}x "
            f"(matrix={us_matrix:.0f}us fused={us_fused:.0f}us)"
        )


def _ragged_launch_bench():
    """Ragged single-launch rotation search vs the per-angle-count launch
    grouping it replaces (heterogeneous-fabric regime: links whose unified
    circles have different angle counts).

    CI assertions: the ragged path must issue exactly ONE kernel launch
    for the whole mixed-angle batch (``launches == batched_calls == 1``)
    where the grouped path pays one per distinct angle count, every row
    must ship ragged with bounded padding waste, the selected rotations
    must be bit-identical to both the per-group launches and the scalar
    search, and the single launch must be ≥ 1.5x faster than the grouped
    dispatch fan-out.
    """
    from repro.core.compat import BatchStats, find_rotations, find_rotations_batched

    from .common import mixed_angle_problems, timed

    probs = mixed_angle_problems()
    deg = 0.5
    scalar = [find_rotations(p, c, precision_deg=deg) for p, c in probs]
    num_groups = len({s.circle.num_angles for s in scalar})

    ragged_fn = lambda: find_rotations_batched(
        probs, precision_deg=deg, ragged=True
    )
    grouped_fn = lambda: find_rotations_batched(
        probs, precision_deg=deg, ragged=False
    )
    ragged_fn()    # warm both jit caches
    grouped_fn()
    res_ragged, us_ragged = timed(ragged_fn)
    res_grouped, us_grouped = timed(grouped_fn)
    speedup = us_grouped / us_ragged

    stats_r = BatchStats()
    find_rotations_batched(probs, precision_deg=deg, stats=stats_r, ragged=True)
    stats_g = BatchStats()
    find_rotations_batched(probs, precision_deg=deg, stats=stats_g, ragged=False)
    # row first, gates after: a failing assertion below still leaves the
    # measured row in the --json artifact to explain itself
    yield {
        "name": f"kernels/score_ragged_launch({len(probs)}x2job,{deg:g}deg)",
        "us_per_call": us_ragged,
        "speedup": speedup,
        "derived": (
            f"per_group_launches={us_grouped:.0f}us speedup={speedup:.2f}x "
            f"({num_groups} angle counts; ragged {stats_r.launches} launch "
            f"vs grouped {stats_g.launches}, {stats_r.ragged_rows} rows, "
            f"pad_fraction={stats_r.pad_fraction:.3f}; tournament-tree "
            f"argmin, per-row num_angles/valid masking)"
        ),
    }
    if any(
        r.shifts_steps != g.shifts_steps or r.shifts_steps != s.shifts_steps
        for r, g, s in zip(res_ragged, res_grouped, scalar)
    ):
        raise RuntimeError(
            "ragged launch diverged from the per-group/scalar search"
        )
    if not (stats_r.launches == stats_r.batched_calls == 1):
        raise RuntimeError(
            f"mixed-angle batch must ship as ONE ragged launch, got "
            f"launches={stats_r.launches} batched_calls={stats_r.batched_calls}"
        )
    if stats_g.launches != num_groups or num_groups < 4:
        raise RuntimeError(
            f"grouped comparison path must pay one launch per angle count "
            f"({num_groups}), got {stats_g.launches}"
        )
    if stats_r.ragged_rows != len(probs) or not 0.0 <= stats_r.pad_fraction < 0.5:
        raise RuntimeError(
            f"every row must ship ragged with bounded padding: "
            f"rows={stats_r.ragged_rows}/{len(probs)} "
            f"pad_fraction={stats_r.pad_fraction:.3f}"
        )
    if speedup < 1.5:
        raise RuntimeError(
            f"ragged single launch must be >=1.5x over per-group launches: "
            f"{speedup:.2f}x (grouped={us_grouped:.0f}us ragged={us_ragged:.0f}us)"
        )


def _tuned_dispatch_bench():
    """Tuned-table dispatch vs the untuned module defaults, on the exact
    production-shaped workloads the table was searched on (segmin = the
    tall grid-path launch, argmin = the short descent-path launch).

    CI assertions (after each row's yield): the tuned and untuned paths
    must return **bit-identical** (idx, val) outputs — the circle family's
    schedule parameters are provably output-inert — and the tuned dispatch
    must never be slower than the ``SHIFT_CHUNK=8`` / ``BLOCK_L=32``
    defaults beyond a 10% noise band (the search's 5% hysteresis ships
    defaults on near-ties, so this holds across machines).  After all
    rows: at least one fine-grid (A >= 512) bucket must be >= 1.15x
    faster tuned — the gate that keeps the committed table earning its
    keep; disarmed only if the loader fell back to defaults (no table
    entry for any fine-grid case), which the row text then states.
    """
    import numpy as np

    from repro.kernels import tune
    from repro.kernels.tune.search import make_workload

    def min_us(fn, reps=5):
        # min-of-N, interleaved by the caller: noise on a quiesced runner
        # is strictly additive, so the minimum is the stable statistic to
        # compare two near-identical launches with
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best * 1e6

    table = tune.get_table()
    cases = (
        # (variant, short label, workload rows, bucket, fine_grid)
        ("circle_score_segmin", "segmin", 384, 512, True),
        ("circle_score_segmin", "segmin", 384, 1024, True),
        ("circle_score_argmin", "argmin", 32, 1024, True),
        ("circle_score_argmin", "argmin", 32, 256, False),
    )
    best_fine = 0.0
    fine_armed = False
    for variant, label, rows, bucket, fine in cases:
        run = make_workload(variant, bucket)
        entry = table.entries.get(f"{variant}/{bucket}", {})
        want = run({})              # untuned defaults; warms that jit cache
        got = run({}, tuned=True)   # table dispatch; warms the other
        identical = all(np.array_equal(g, w) for g, w in zip(got, want))
        us_def, us_tuned = float("inf"), float("inf")
        for _ in range(2):  # interleave so drift hits both sides alike
            us_def = min(us_def, min_us(lambda: run({})))
            us_tuned = min(us_tuned, min_us(lambda: run({}, tuned=True)))
        speedup = us_def / us_tuned
        if fine and entry:
            fine_armed = True
            best_fine = max(best_fine, speedup)
        sched_txt = (
            "table " + ",".join(f"{k}={v}" for k, v in sorted(entry.items()))
            if entry else "no table entry — defaults"
        )
        yield {
            "name": f"kernels/score_tuned_{label}({rows}x{bucket})",
            "us_per_call": us_tuned,
            "speedup": speedup,
            "derived": (
                f"untuned_default={us_def:.0f}us speedup={speedup:.2f}x "
                f"({sched_txt}; bit_identical={identical})"
            ),
        }
        # gates after the yield: the measured row stays in the artifact
        if not identical:
            raise RuntimeError(
                f"tuned dispatch changed {variant}/{bucket} outputs — the "
                f"circle family's schedule parameters must be output-inert"
            )
        if us_tuned > us_def * 1.10:
            raise RuntimeError(
                f"tuned {variant}/{bucket} slower than the untuned "
                f"defaults: {us_tuned:.0f}us vs {us_def:.0f}us "
                f"({speedup:.2f}x, floor 0.91x with the 10% noise band)"
            )
    if fine_armed and best_fine < 1.15:
        raise RuntimeError(
            f"committed table must win >=1.15x on at least one fine-grid "
            f"(A>=512) bucket: best {best_fine:.2f}x"
        )


def _arrival_bench():
    """Registry-driven CASSINI-vs-host comparison under each arrival
    process (``arrival-{poisson,burst,diurnal}``): the paper's trace
    population, same RNG stream, only the arrival pattern varies.

    One row per pattern; ``speedup`` is Themis avg JCT over th+cassini
    avg JCT (>1 means the CASSINI augmentation helps).  CI assertion
    (after the burst row's yield): under clustered arrivals — the regime
    the paper's §5.2 dynamic experiments stress — the augmented scheduler
    must not lose to its host on average JCT.
    """
    from repro.engine.scenarios import ARRIVAL_SWEEP, get_scenario

    HORIZON_MS = 600_000.0
    for pat in ARRIVAL_SWEEP:
        spec = get_scenario(f"arrival-{pat}")
        runs = {
            name: spec.run(name, horizon_ms=HORIZON_MS)
            for name in ("themis", "th+cassini")
        }
        s_host = runs["themis"].metrics.summary()
        s_cas = runs["th+cassini"].metrics.summary()
        ratio = s_host["avg_jct_ms"] / s_cas["avg_jct_ms"]
        yield {
            "name": f"arrival/{pat}",
            "us_per_call": runs["th+cassini"].wall_s * 1e6,
            "speedup": ratio,
            "derived": (
                f"avg_jct th+cassini={s_cas['avg_jct_ms']:.0f}ms vs "
                f"themis={s_host['avg_jct_ms']:.0f}ms (jct_ratio="
                f"{ratio:.3f}x, ecn/iter {s_cas['ecn_per_iter']:.2f} vs "
                f"{s_host['ecn_per_iter']:.2f}, "
                f"{s_cas['jobs_finished']:.0f}/{s_host['jobs_finished']:.0f} "
                f"jobs finished, {HORIZON_MS:g}ms horizon)"
            ),
        }
        # gate after the yield: the measured row stays in the artifact
        if pat == "burst" and s_cas["avg_jct_ms"] > s_host["avg_jct_ms"]:
            raise RuntimeError(
                f"th+cassini must not lose to themis on avg JCT under "
                f"burst arrivals: {s_cas['avg_jct_ms']:.0f}ms vs "
                f"{s_host['avg_jct_ms']:.0f}ms"
            )


def _fluid_advance_bench():
    """Vectorized fluid-network engine vs the scalar per-event oracle.

    Each row advances the contended ``rack-scaling-{N}`` fluid state (the
    scenario's full trace population, wrap-around chained placements, no
    scheduler in the loop) through a fixed wall-clock window with the
    array-resident engine, and compares against the scalar dict-of-dicts
    progressive-filling loop on the *same* state.

    CI assertions: the two engines must produce identical iteration-time
    traces (the vectorized path is an exact replay, not an approximation),
    and at 64 racks the vectorized engine must be ≥ 5x faster — the gate
    that keeps rack-scale scenario sweeps affordable as the fluid model
    grows.

    The 256/1024-rack rows bench the *incremental re-solver*: the
    delta-maintained water-filling state with dirty-component refills
    against the per-set from-scratch solve, same vectorized event loop on
    both sides.  Gates: ≥ 3x at both sizes, and the two engines must
    complete the same total iteration count over the window (the
    incremental path is tolerance-band equivalent, so per-iteration float
    traces may differ in the last bits — the aggregate must not).
    """
    from repro.cluster import FluidNetworkSim

    from .common import fluid_advance_case, timed

    def run_engine(racks, vectorized, window_ms):
        topo, jobs = fluid_advance_case(racks)
        sim = FluidNetworkSim(topo, vectorized=vectorized)
        sim.configure(jobs)
        sim.advance(window_ms)
        return sim, jobs

    for racks, window_ms, gate in ((16, 15_000.0, None), (64, 6_000.0, 5.0)):
        (sim_v, jobs_v), us_vec = timed(
            lambda: run_engine(racks, True, window_ms), repeat=1
        )
        (_, jobs_s), us_scal = timed(
            lambda: run_engine(racks, False, window_ms), repeat=1
        )
        speedup = us_scal / us_vec
        iters = sum(j.iters_done for j in jobs_v)
        identical = all(
            a.iter_times_ms == b.iter_times_ms and a.ecn_marks == b.ecn_marks
            for a, b in zip(jobs_v, jobs_s)
        )
        yield {
            "name": f"fluid_advance/rack-scaling-{racks}",
            "us_per_call": us_vec,
            "speedup": speedup,
            "derived": (
                f"scalar_oracle={us_scal:.0f}us speedup={speedup:.2f}x "
                f"({len(jobs_v)} jobs, {racks} racks, {window_ms:g}ms window, "
                f"{iters} iterations; {sim_v.alloc_solves} allocation solves "
                f"(cached water-filling), identical={identical})"
            ),
        }
        # gates after the yield: the measured row stays in the artifact
        if not identical:
            raise RuntimeError(
                f"vectorized fluid engine diverged from the scalar oracle "
                f"at {racks} racks (iteration traces differ)"
            )
        if gate is not None and speedup < gate:
            raise RuntimeError(
                f"vectorized fluid advance must be >={gate:g}x over the "
                f"scalar allocator at {racks} racks: {speedup:.2f}x "
                f"(scalar={us_scal:.0f}us vectorized={us_vec:.0f}us)"
            )

    def run_incr(racks, incremental, window_ms):
        topo, jobs = fluid_advance_case(racks)
        sim = FluidNetworkSim(topo, vectorized=True, incremental=incremental)
        sim.configure(jobs)
        sim.advance(window_ms)
        return sim, jobs

    for racks, window_ms in ((256, 1_200.0), (1024, 350.0)):
        (sim_i, jobs_i), us_inc = timed(
            lambda: run_incr(racks, True, window_ms), repeat=1
        )
        (sim_s, jobs_s), us_scr = timed(
            lambda: run_incr(racks, False, window_ms), repeat=1
        )
        speedup = us_scr / us_inc
        iters_i = sum(j.iters_done for j in jobs_i)
        iters_s = sum(j.iters_done for j in jobs_s)
        yield {
            "name": f"fluid_advance/rack-scaling-{racks}",
            "us_per_call": us_inc,
            "speedup": speedup,
            "derived": (
                f"from_scratch={us_scr:.0f}us speedup={speedup:.2f}x "
                f"({len(jobs_i)} jobs, {racks} racks, {window_ms:g}ms "
                f"window, {iters_i} iterations; "
                f"{sim_i.alloc_delta_solves}/{sim_i.alloc_solves} delta "
                f"solves)"
            ),
        }
        # gates after the yield: the measured row stays in the artifact
        if iters_i != iters_s:
            raise RuntimeError(
                f"incremental fluid engine diverged from the from-scratch "
                f"solve at {racks} racks: {iters_i} vs {iters_s} total "
                f"iterations over the {window_ms:g}ms window"
            )
        if speedup < 3.0:
            raise RuntimeError(
                f"incremental re-solver must be >=3x over the per-set "
                f"from-scratch solve at {racks} racks: {speedup:.2f}x "
                f"(from_scratch={us_scr:.0f}us incremental={us_inc:.0f}us)"
            )


def _fluid_shard_bench():
    """Device-sharded component fills vs per-component device dispatch.

    Each row captures the *largest real rebuild-shaped fill* the
    incremental re-solver performs while advancing the contended
    ``rack-scaling-{256,1024}`` state: the dirty-component union at a
    ``_WF_REFRESH`` rebuild, partitioned into its independent
    water-filling components (tens of components at these sizes).  The
    measured quantity is the production sharded path — per-component
    slices padded into power-of-two buckets and dispatched as ONE
    vmap-batched fill per bucket, row axis split across ``jax.devices()``
    with shard_map — against the unbatched device path that keeps the
    same fills device-resident on the same fabric: one mesh dispatch per
    component.  Batching is exactly what the sharded path contributes on
    the device axis, so that is the pair the gate compares.

    CI assertions (gates raised after the yield):
    - >=1.5x for the bucketed sharded dispatch over per-component mesh
      dispatch, armed when >=4 devices are visible (the CI bench leg
      forces 8 host devices via XLA_FLAGS; on fewer devices the row
      still reports, gate disarmed);
    - the sharded rates must match the fused host fill
      (``_wf_fill_core`` over the union — the ``sharded=False``
      incremental path) within the documented 1e-9 tolerance band;
    - both must match the from-scratch ``_solve_alloc`` on the captured
      comm mask (the solve PR 5 pinned bit-exact against the scalar
      oracle) within the same band.

    The fused host fill time and the single-device per-component jit
    time are reported alongside for honesty: on a small-core CI runner
    the numpy cascade over the union is itself fast, and a lone
    pre-compiled single-row jit beats mesh traffic — the sharded path's
    win is amortising *mesh* dispatch across the component batch, which
    is what transfers to real multi-device hardware (the fused fill
    cannot leave the host at all).
    """
    import numpy as np

    from repro.cluster import shard as shard_mod

    from .common import sharded_fill_case, timed

    ndev = shard_mod.device_count()

    for racks, window_ms in ((256, 1_200.0), (1024, 350.0)):
        sim, union, comps, build_rows = sharded_fill_case(racks, window_ms)
        JR, binding, demand, live, mask = union
        if len(comps) < shard_mod.MIN_COMPONENTS:
            raise RuntimeError(
                f"captured fill at {racks} racks has only {len(comps)} "
                f"components — below the sharding threshold; the bench "
                f"needs a component batch to measure"
            )

        rows = build_rows()
        # warm the jit caches for every bucket shape on every path
        out_b, stats = shard_mod.batched_fill(rows, ndev=ndev)
        for row in rows:
            shard_mod.batched_fill([row], ndev=ndev)
            shard_mod.batched_fill([row], ndev=1)

        (out_b, stats), us_shard = timed(
            lambda: shard_mod.batched_fill(build_rows(), ndev=ndev),
            repeat=3,
        )

        def sequential(dev):
            return [
                shard_mod.batched_fill([row], ndev=dev)[0][0]
                for row in build_rows()
            ]

        out_s, us_seq = timed(lambda: sequential(ndev), repeat=1)
        _, us_seq1 = timed(lambda: sequential(1), repeat=1)
        _, us_fused = timed(
            lambda: sim._wf_fill_core(JR, binding, demand, live), repeat=3
        )
        fused = sim._wf_fill_core(JR, binding, demand, live)

        n = len(sim._slots)
        rates_b = np.zeros(n)
        rates_q = np.zeros(n)
        for (mem, _), vb, vq in zip(comps, out_b, out_s):
            rates_b[mem] = vb
            rates_q[mem] = vq
        rates_f = np.zeros(n)
        rates_f[JR] = fused
        scratch, _ = sim._solve_alloc(mask)
        band = dict(rtol=1e-9, atol=1e-9)
        ok_fused = np.allclose(rates_b[JR], rates_f[JR], **band)
        ok_seq = np.allclose(rates_q[JR], rates_b[JR], **band)
        ok_scratch = np.allclose(
            rates_b[JR], scratch[JR], **band
        ) and np.allclose(rates_f[JR], scratch[JR], **band)
        speedup = us_seq / us_shard
        armed = ndev >= 4
        yield {
            "name": f"fluid_shard/rack-scaling-{racks}",
            "us_per_call": us_shard,
            "speedup": speedup,
            "derived": (
                f"per_comp_mesh_dispatch={us_seq:.0f}us "
                f"speedup={speedup:.2f}x "
                f"({len(comps)} components, {JR.size} members, "
                f"{stats.dispatches} bucket dispatches over {ndev} "
                f"device(s), {stats.padded_rows} padded rows; reference: "
                f"per_comp 1-device jit={us_seq1:.0f}us, fused host "
                f"fill={us_fused:.0f}us; parity vs fused="
                f"{ok_fused} vs from-scratch={ok_scratch}; gate "
                f"{'armed' if armed else 'disarmed (<4 devices)'})"
            ),
        }
        # gates after the yield: the measured row stays in the artifact
        if not (ok_fused and ok_seq and ok_scratch):
            raise RuntimeError(
                f"sharded fill diverged at {racks} racks: vs fused="
                f"{ok_fused} vs sequential={ok_seq} vs from-scratch="
                f"{ok_scratch} (tolerance band rtol=atol=1e-9)"
            )
        if armed and speedup < 1.5:
            raise RuntimeError(
                f"bucketed sharded dispatch must be >=1.5x over "
                f"per-component mesh dispatch at {racks} racks on "
                f"{ndev} devices: {speedup:.2f}x "
                f"(sequential={us_seq:.0f}us sharded={us_shard:.0f}us)"
            )


def _sched_epoch_bench():
    """End-to-end scheduler-level rows: one full ``SchedulingPipeline.cassini``
    epoch (Allocate → Propose → Score → Align) on the hetero-16rack
    scenario, so kernel-level scoring wins stay visible where they matter.

    Four rows: the paper-default 5° epoch (A=72 circles — numpy grids,
    device reduction not eligible), and fine-grid 0.5° epochs (A≥720
    circles: the scoring stage actually runs through the device-resident
    rotation search) with the fused ragged reduction on, the per-group
    launch fan-out, and the full-matrix round-trip.

    CI assertion (ragged fine-grid row): every grid chunk / descent step
    of the epoch must ship as exactly ONE kernel launch
    (``BatchStats.launches == batched_calls``) with every row ragged —
    the heterogeneous 16-rack fabric no longer pays a dispatch per
    angle-count group.
    """
    from repro.sched import CassiniAugmented, ThemisScheduler

    from .common import sched_epoch_state, timed

    cases = (
        # (precision_deg, device_reduce, ragged, label)
        (5.0, True, True, "paper default"),
        (0.5, True, True, "fine grid, ragged single-launch"),
        (0.5, True, False, "fine grid, per-group launches"),
        (0.5, False, False, "fine grid, full-matrix round-trip"),
    )
    state = sched_epoch_state("hetero-16rack", max_jobs=10)
    for deg, device_reduce, ragged, label in cases:
        def one_epoch():
            # fresh module each call: epoch cost includes every link solve,
            # not a pure cache-hit replay
            s = CassiniAugmented(
                ThemisScheduler(), precision_deg=deg,
                device_reduce=device_reduce, ragged=ragged,
            )
            return s.schedule(state)
        one_epoch()  # warm the jit caches
        _, us_epoch = timed(one_epoch, repeat=3)
        sched = CassiniAugmented(
            ThemisScheduler(), precision_deg=deg,
            device_reduce=device_reduce, ragged=ragged,
        )
        sched.schedule(state)
        score_stage = next(
            s for s in sched.pipeline.stages if s.name == "score"
        )
        stats = score_stage.last_batch_stats
        yield {
            "name": f"sched_epoch/hetero-16rack({deg:g}deg,"
                    f"device_reduce={device_reduce},ragged={ragged})",
            "us_per_call": us_epoch,
            "derived": (
                f"full cassini epoch, 10 jobs, 16 racks ({label}); "
                f"batch={stats}"
            ),
        }
        if deg == 0.5 and device_reduce and ragged:
            # acceptance gate: one kernel launch per grid/descent step on
            # the heterogeneous fabric, all rows through the ragged path
            if stats.launches != stats.batched_calls or stats.launches == 0:
                raise RuntimeError(
                    f"hetero-16rack fine-grid epoch must issue exactly one "
                    f"kernel launch per grid/descent step: launches="
                    f"{stats.launches} batched_calls={stats.batched_calls}"
                )
            if stats.ragged_rows != stats.grid_rows + stats.descent_rows:
                raise RuntimeError(
                    f"every fine-grid row must ship ragged: "
                    f"{stats.ragged_rows} vs "
                    f"{stats.grid_rows + stats.descent_rows} ({stats})"
                )

    # end-to-end rack-scale row: one full cassini epoch on the 64-rack
    # scaling scenario — the candidate/scoring cost the scaling sweeps pay
    # at every scheduling trigger, measured where the fabric is largest
    state64 = sched_epoch_state("rack-scaling-64", max_jobs=12)

    def one_epoch_64():
        s = CassiniAugmented(ThemisScheduler(), precision_deg=5.0)
        return s.schedule(state64)

    one_epoch_64()  # warm the jit caches
    _, us_64 = timed(one_epoch_64, repeat=3)
    yield {
        "name": "sched_epoch/rack-scaling-64(5deg)",
        "us_per_call": us_64,
        "derived": "full cassini epoch, 12 jobs, 64 racks (paper-default "
                   "grid; end-to-end Allocate->Propose->Score->Align)",
    }


def _serve_bench():
    """Online serving rows: the latency SLO + delta-update gates.

    ``serve_query/multitenant-8`` replays the multitenant-8 arrival trace
    through :class:`SchedulerService`, stepping the stream watermark with
    256 placement queries spread across the horizon and draining to the
    end.  ``us_per_call`` is the full replay wall time; the SLO gate is on
    the measured p99 *query service latency* against a fixed budget — two
    orders of magnitude above the worst contended pump (which includes a
    scheduling decision), so heterogeneous CI runners cannot trip it, but
    an accidental O(replay) scan or rebuild-per-query regression will.
    The replay must also reconfigure exclusively through the delta path
    (zero rebuilds) and hit the prefetch-warmed link cache.

    ``serve_delta_update/rack-scaling-64`` times one arrival + one
    departure applied to the contended 112-job 64-rack fluid state via
    the slot-delta primitives (``add_job``/``remove_job``) against the
    same membership change done as full ``configure`` rebuilds.  Gates:
    the delta path must be ≥ 3x faster, and it must *retain* the
    water-filling allocation cache the rebuild path throws away.
    """
    from repro.cluster import FluidNetworkSim
    from repro.engine.scenarios import get_scenario
    from repro.serve import JobArrival, SchedulerService

    from .common import fluid_advance_case, timed

    # ---- serve_query: multitenant-8 arrival replay ------------------- #
    SLO_P99_MS = 100.0
    NUM_QUERIES = 256
    spec = get_scenario("multitenant-8")

    def replay():
        topo = spec.topology()
        svc = SchedulerService(
            topo, spec.make_scheduler("cassini"), epoch_ms=spec.epoch_ms,
            compute_jitter=spec.compute_jitter, vectorized=spec.vectorized,
            seed=spec.sim_seed,
        )
        with svc:
            for job in spec.arrival_stream(topo):
                svc.submit(JobArrival(job))
            for k in range(1, NUM_QUERIES + 1):
                svc.query(at_ms=k * spec.horizon_ms / NUM_QUERIES)
            svc.drain(spec.horizon_ms)
            return svc, svc.telemetry()

    (svc, tel), us_replay = timed(replay, repeat=1)
    pct = svc.metrics.percentiles("QueryPlacement")
    yield {
        "name": "serve_query/multitenant-8",
        "us_per_call": us_replay,
        "derived": (
            f"query p50={pct['p50']:.3f}ms p95={pct['p95']:.3f}ms "
            f"p99={pct['p99']:.3f}ms (SLO p99<={SLO_P99_MS:g}ms, "
            f"{NUM_QUERIES} queries); {tel['decisions']:.0f} decisions, "
            f"configure_delta={tel.get('configure_delta', 0):.0f} "
            f"rebuild={tel.get('configure_rebuild', 0):.0f}, "
            f"prefetch_launched={tel.get('prefetch_launched', 0):.0f}, "
            f"link_cache {tel.get('link_cache_hits', 0):.0f} hits / "
            f"{tel.get('link_cache_misses', 0):.0f} misses"
        ),
    }
    # gates after the yield: the measured row stays in the artifact
    if pct["p99"] > SLO_P99_MS:
        raise RuntimeError(
            f"serve_query p99 latency SLO violated: {pct['p99']:.3f}ms > "
            f"{SLO_P99_MS:g}ms budget (p50={pct['p50']:.3f}ms "
            f"p95={pct['p95']:.3f}ms)"
        )
    if tel.get("configure_rebuild", 0) or (
        tel.get("configure_delta", 0) != tel["decisions"]
    ):
        raise RuntimeError(
            f"the multitenant-8 replay must reconfigure exclusively "
            f"through the delta path: delta="
            f"{tel.get('configure_delta', 0):.0f} "
            f"rebuild={tel.get('configure_rebuild', 0):.0f} of "
            f"{tel['decisions']:.0f} decisions"
        )
    if not tel.get("link_cache_hits", 0):
        raise RuntimeError(
            f"the served replay must hit the (prefetch-warmed) link "
            f"cache, got {tel.get('link_cache_hits', 0):.0f} hits"
        )

    # ---- serve_delta_update: 64-rack add/remove vs rebuild ---------- #
    GATE = 3.0
    CYCLES = 8  # add/remove pairs per timed call (stabilizes the median)
    topo, jobs = fluid_advance_case(64)
    base, extra = jobs[:-1], jobs[-1]

    delta_sim = FluidNetworkSim(topo, vectorized=True)
    delta_sim.configure(base)
    delta_sim.advance(200.0)  # populate the water-filling cache mid-flight
    cache_before = len(delta_sim._alloc_cache)

    def delta_cycle():
        for _ in range(CYCLES):
            delta_sim.add_job(extra)
            delta_sim.remove_job(extra.job_id)

    rebuild_sim = FluidNetworkSim(topo, vectorized=True)
    rebuild_sim.configure(base)
    rebuild_sim.advance(200.0)

    def rebuild_cycle():
        for _ in range(CYCLES):
            rebuild_sim.configure(base + [extra])
            rebuild_sim.configure(base)

    delta_cycle()  # warm both paths
    rebuild_cycle()
    _, us_delta = timed(delta_cycle)
    _, us_rebuild = timed(rebuild_cycle)
    us_delta /= CYCLES
    us_rebuild /= CYCLES
    speedup = us_rebuild / us_delta
    retained = len(delta_sim._alloc_cache)
    yield {
        "name": "serve_delta_update/rack-scaling-64",
        "us_per_call": us_delta,
        "speedup": speedup,
        "derived": (
            f"full_rebuild={us_rebuild:.0f}us speedup={speedup:.1f}x "
            f"({len(base)} jobs, 64 racks; arrival+departure as slot "
            f"deltas vs two configure() rebuilds; water-filling cache "
            f"retained {retained}/{cache_before} entries vs "
            f"{len(rebuild_sim._alloc_cache)} after rebuild)"
        ),
    }
    if speedup < GATE:
        raise RuntimeError(
            f"delta update must be >={GATE:g}x over rebuild at 64 racks: "
            f"{speedup:.2f}x (rebuild={us_rebuild:.0f}us "
            f"delta={us_delta:.0f}us)"
        )
    if not cache_before or retained != cache_before:
        raise RuntimeError(
            f"delta ops must retain the allocation cache: "
            f"{retained}/{cache_before} entries survived"
        )


def _fault_replay_bench():
    """Chaos rows: fault-replay parity + the degraded-mode overhead gate.

    ``fault_replay/churn-linkfail`` runs the seeded link-churn scenario
    (6 capacity incidents mid-trace, each triggering re-alignment) through
    the batch simulator and replays the same arrivals + fault schedule
    through :class:`SchedulerService`.  Gates: the served run must match
    the batch run decision for decision (timestamps, placements,
    time-shifts) and metric for metric — a fault schedule is part of the
    deterministic replay contract, not a tolerance band — and the healthy
    CASSINI pipeline must never have fallen back
    (``degraded_decisions == 0``).

    ``fault_replay/degraded_overhead`` measures what the graceful-
    degradation wrapper (exception trap + fallback decision path around
    every ``scheduler.schedule``) costs when nothing is failing: the same
    multitenant-4 replay drained with ``fallback`` on vs off.  Gate: the
    healthy-path overhead must stay under 5% (plus a small absolute slack
    so sub-second replays on noisy CI runners cannot trip it).
    """
    from repro.engine.scenarios import get_scenario
    from repro.serve import JobArrival, SchedulerService

    from .common import timed

    # ---- fault_replay/churn-linkfail: batch vs serve ---------------- #
    spec = get_scenario("churn-linkfail")
    built = spec.build("th+cassini")
    t0 = time.time()
    m_batch = built.simulator.run(built.jobs, horizon_ms=spec.horizon_ms)
    batch_s = time.time() - t0
    d_batch = built.simulator.decisions
    chaos = built.simulator.chaos

    def serve_replay():
        topo = spec.topology()
        jobs = list(spec.arrival_stream(topo))
        svc = SchedulerService(
            topo, spec.make_scheduler("th+cassini"), epoch_ms=spec.epoch_ms,
            compute_jitter=spec.compute_jitter, vectorized=spec.vectorized,
            seed=spec.sim_seed,
            fault_schedule=spec.make_fault_schedule(topo, jobs),
        )
        with svc:
            for job in jobs:
                svc.submit(JobArrival(job))
            metrics = svc.drain(spec.horizon_ms)
            return metrics, svc.decisions, svc.telemetry()

    (m_serve, d_serve, tel), us_serve = timed(serve_replay, repeat=1)
    tuples = lambda ds: [
        (t, d.placements, d.time_shifts_ms) for t, d in ds
    ]
    identical = (
        m_batch.summary() == m_serve.summary()
        and tuples(d_batch) == tuples(d_serve)
    )
    yield {
        "name": "fault_replay/churn-linkfail",
        "us_per_call": us_serve,
        "derived": (
            f"batch={batch_s * 1e6:.0f}us; {len(d_serve)} decisions, "
            f"{chaos.applied_count} faults applied "
            f"({chaos.skipped} skipped), "
            f"degraded={tel.get('degraded_decisions', 0):.0f}, "
            f"identical={identical} (serve replay matches batch decision "
            f"for decision under link churn)"
        ),
    }
    # gates after the yield: the measured row stays in the artifact
    if not identical:
        raise RuntimeError(
            "served churn-linkfail replay diverged from the batch run "
            "(decisions or metrics differ under the same fault schedule)"
        )
    if tel.get("degraded_decisions", 0):
        raise RuntimeError(
            f"healthy pipeline must never fall back: "
            f"{tel['degraded_decisions']:.0f} degraded decisions"
        )
    if not chaos.applied_count:
        raise RuntimeError(
            "churn-linkfail applied zero faults — the schedule no longer "
            "overlaps the trace; the parity gate is vacuous"
        )

    # ---- fault_replay/degraded_overhead: healthy-path cost ---------- #
    OVERHEAD_GATE = 1.05
    SLACK_US = 500_000.0  # 0.5s: sub-second replays on noisy runners
    mt = get_scenario("multitenant-4")

    def drain_replay(fallback):
        topo = mt.topology()
        svc = SchedulerService(
            topo, mt.make_scheduler("cassini"), epoch_ms=mt.epoch_ms,
            compute_jitter=mt.compute_jitter, vectorized=mt.vectorized,
            seed=mt.sim_seed, fallback=fallback,
        )
        with svc:
            for job in mt.arrival_stream(topo):
                svc.submit(JobArrival(job))
            svc.drain(mt.horizon_ms)
            return svc.telemetry()

    drain_replay(True)  # warm imports / jit caches
    tel_on, us_on = timed(lambda: drain_replay(True))
    tel_off, us_off = timed(lambda: drain_replay(False))
    ratio = us_on / us_off
    yield {
        "name": "fault_replay/degraded_overhead",
        "us_per_call": us_on,
        "derived": (
            f"fallback_off={us_off:.0f}us ratio={ratio:.3f} "
            f"(degradation wrapper on the healthy path: exception trap + "
            f"timeout check per decision, {tel_on['decisions']:.0f} "
            f"decisions; gate <{(OVERHEAD_GATE - 1) * 100:.0f}%)"
        ),
    }
    if us_on > us_off * OVERHEAD_GATE + SLACK_US:
        raise RuntimeError(
            f"degraded-mode wrapper costs too much on the healthy path: "
            f"{us_on:.0f}us vs {us_off:.0f}us without fallback "
            f"({ratio:.3f}x, gate {OVERHEAD_GATE:g}x + {SLACK_US:.0f}us)"
        )
    if tel_on.get("degraded_decisions", 0) or tel_off.get(
        "degraded_decisions", 0
    ):
        raise RuntimeError("healthy multitenant-4 replay must not degrade")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(ALL))
    ap.add_argument("--json", nargs="?", const="BENCH.json", default=None,
                    metavar="PATH",
                    help="also write rows as JSON (machine-readable perf "
                         "trajectory; CI uploads it as an artifact and "
                         "diffs it against the committed baseline via "
                         "benchmarks/compare.py). Bare --json writes "
                         "BENCH.json")
    args = ap.parse_args()
    names = args.only.split(",") if args.only else ALL

    print("name,us_per_call,derived")
    all_rows: list[dict] = []
    t0 = time.time()

    def write_json(error: str | None = None) -> None:
        payload = [
            {
                "name": r["name"],
                "us_per_call": round(float(r["us_per_call"]), 1),
                "speedup": round(float(r["speedup"]), 3) if "speedup" in r else None,
                "derived": str(r["derived"]),
            }
            for r in all_rows
        ]
        doc = {"rows": payload, "wall_s": round(time.time() - t0, 1)}
        if error is not None:
            doc["failed"] = error
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")

    current = "?"
    try:
        for name in names:
            current = name
            if name == "kernels":
                rows = _kernel_bench()
            elif name == "arrival":
                rows = _arrival_bench()
            elif name == "fluid_advance":
                rows = _fluid_advance_bench()
            elif name == "fluid_shard":
                rows = _fluid_shard_bench()
            elif name == "sched_epoch":
                rows = _sched_epoch_bench()
            elif name == "serve":
                rows = _serve_bench()
            elif name == "fault_replay":
                rows = _fault_replay_bench()
            elif name == "roofline":
                from . import roofline

                rows = roofline.run()
            else:
                mod = __import__(f"benchmarks.{name}", fromlist=["run"])
                rows = mod.run()
            # bench sets are generators: consume row by row and rewrite the
            # JSON as each lands, so a bench failing its own assertion gate
            # still leaves every completed measurement in the artifact
            for r in rows:
                derived = str(r["derived"]).replace(",", ";")
                print(f"{r['name']},{r['us_per_call']:.1f},{derived}", flush=True)
                all_rows.append(r)
                if args.json:
                    write_json()
    except Exception as e:
        # the partial JSON artifact keeps every completed measurement AND
        # the failure, but a partial artifact alone can mask *which* gate
        # tripped — always exit nonzero with a one-line reason naming it
        # (traceback first, so unexpected crashes stay debuggable)
        reason = f"{type(e).__name__}: {e}"
        if args.json:
            write_json(error=reason)
        traceback.print_exc()
        print(
            f"BENCH GATE FAILED ({current}, after {len(all_rows)} rows): "
            f"{reason}",
            file=sys.stderr, flush=True,
        )
        raise SystemExit(1)
    if args.json:
        print(f"# wrote {len(all_rows)} rows to {args.json}", file=sys.stderr)
    print(f"# total wall: {time.time() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
