"""One run of one benchmark cell: set-up, measured window, correctness.

The cell, its configuration and its traffic mix are found by name: the
cell in ``BENCHMARK.json``, the configuration in the file the cell's
``configs`` entry names, the mix in ``mixes/<traffic>.json``, each
per-layer metric's reader in ``metrics/<metric>.py``, and the plain
reference in ``reference.py``, its loop rule replaced by the one of
``references/<configuration>.py`` where the configuration has that
file.  The configuration's ``host.kind`` names the host scheduler
(``fixed``: pinned placements; ``themis``: Themis with
``num_candidates`` candidates), and the mix's ``process`` the arrival
process (``stream.py``).

The loop is closed: a cluster's host scheduler calls CASSINI once per
trigger and waits, so one client replays the cell's seeded event stream
as fast as the service takes it.  Each round submits the events of one
instant (a departure and the arrival that takes its slot, or an arrival)
and then ``QueryPlacement(at_ms=t)``, and waits for its answer.  Every
trigger the service acts on (arrival, departure, epoch tick) is one
decision.

Set-up builds the fabric and the service, compiles every kernel shape
the cell's angle range and row counts can produce, and replays the
mix's ``warmup_rounds`` so the cluster is at steady occupancy and the
link cache holds what a long-running service holds.  The window then
runs for ``seconds``.  Afterwards the service is closed and the captured
decisions, link results and fluid log are compared with the plain
reference (``check.py``).
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import random
import shutil
import sys
import time
from pathlib import Path

from . import check, devtrace, probes, reference, stats, stream

CHIP = Path(__file__).resolve().parent
CHIP_REL = Path("benchmarks") / "chip"


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------- #
# discovery by name
# ---------------------------------------------------------------------- #
def load_cell(root: Path, name: str) -> dict:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = json.loads((root / configs[cell["config"]]["file"]).read_text())
    mix = json.loads((root / CHIP_REL / "mixes" / f"{cell['traffic']}.json").read_text())
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
    end_to_end = [m for m in bench["end_to_end"]
                  if name in m.get("workloads", [name])]
    return {"cell": cell, "config": cfg, "mix": mix, "per_layer": per_layer,
            "end_to_end": end_to_end, "root": root}


def metric_reader(root: Path, name: str):
    path = root / CHIP_REL / "metrics" / f"{name}.py"
    if not path.exists():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    return _module(path, f"chipbench_metric_{name}").read


def reference_for(root: Path, config: str):
    """The module whose loop rule, ``aligned_links``, the check takes: the
    configuration's own ``references/<config>.py`` where it exists, else
    ``reference``.  Nothing else of that module is used."""
    path = root / CHIP_REL / "references" / f"{config}.py"
    if not path.exists():
        return reference
    return _module(path, f"chipbench_reference_{config}")


def _module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------- #
# the system under test
# ---------------------------------------------------------------------- #
def build_service(cfg: dict, rec: probes.Recorder):
    from repro.cluster.topology import Topology
    from repro.sched import CassiniAugmented, ThemisScheduler
    from repro.sched.fixed import FixedPlacementScheduler
    from repro.serve import SchedulerService

    t = cfg["topology"]
    topo = Topology(
        num_racks=t["racks"], servers_per_rack=t["servers_per_rack"],
        gpus_per_server=t.get("gpus_per_server", 1),
        nic_gbps=float(t["rack_nic_gbps"][0]),
        rack_nic_gbps=tuple(float(x) for x in t["rack_nic_gbps"]),
        oversubscription=float(t["oversubscription"]),
    )
    h = cfg["host"]
    if h["kind"] == "fixed":
        host = FixedPlacementScheduler({})
    elif h["kind"] == "themis":
        host = ThemisScheduler(num_candidates=h["num_candidates"], seed=h["seed"])
    else:
        raise ValueError(f"unknown host scheduler {h['kind']!r}")
    c = cfg["cassini"]
    sched = CassiniAugmented(
        host, num_candidates=h["num_candidates"],
        precision_deg=c["precision_deg"], quantum_ms=c["quantum_ms"],
        pace_threshold=c["pace_threshold"], seed=c["seed"],
    )
    proxy = probes.DecisionProxy(sched, rec)
    s = cfg["service"]
    svc = SchedulerService(
        topo, proxy, epoch_ms=s["epoch_ms"], compute_jitter=s["compute_jitter"],
        migration_pause_ms=s["migration_pause_ms"],
        congested_efficiency=s["congested_efficiency"],
        vectorized=s["vectorized"], incremental=s["incremental"],
        sharded=s["sharded"], seed=s["sim_seed"], prefetch=s["prefetch"],
        queue_size=s["queue_size"],
    )
    return svc, proxy, host


def warm_kernels(cfg: dict, mix: dict) -> int:
    """Compile every kernel launch shape the cell can produce: each lane
    width bucket of its angle range, each row bucket up to a grid chunk,
    and each segment bucket of the accept scan.  Returns the launches."""
    import numpy as np

    from repro.core.compat import GRID_CHUNK_ROWS
    from repro.kernels.circle_score import ops

    lo = int(round(360.0 / cfg["cassini"]["precision_deg"]))
    if lo < 512:           # below the kernel cutoff scoring stays on the host
        return 0
    hi = mix["warm"]["max_angles"]
    widths = sorted({ops.bucket_width(a) for a in (lo, hi)}
                    | {w for w in (1024, 2048, 4096) if lo < w < hi})
    rows = [1]
    while rows[-1] < GRID_CHUNK_ROWS:
        rows.append(min(rows[-1] * 2, GRID_CHUNK_ROWS))
    segs = [s for s in (1, 3, 7, 15, 31, 63, 127) if s <= mix["warm"]["max_segments"]]
    n = 0
    for w in widths:
        for r in rows:
            base = np.zeros((r, w), np.float32)
            cap = np.ones((r,), np.float32)
            valid = np.ones((r,), np.int32)
            na = np.full((r,), w, np.int32)
            if r <= mix["warm"]["max_descent_rows"]:
                ops.circle_score_ragged_argmin(base, base, cap, valid, na)
                n += 1
            for s in segs:
                if s > r:
                    break
                seg = np.minimum(np.arange(r) * s // r, s - 1).astype(np.int32)
                ops.circle_score_ragged_segmin(
                    base, base, cap, valid, na, seg, np.full((s,), np.inf))
                n += 1
    return n


class Client:
    """The closed-loop client: one instant's events, then one query."""

    def __init__(self, svc, host, events, specs: dict) -> None:
        self.svc = svc
        self.host = host
        self.events = events
        self.specs = specs
        self._next = next(events)
        self.rounds = 0

    def round(self) -> float:
        from repro.cluster.job import Job
        from repro.serve import JobArrival, JobDeparture, QueryPlacement

        t = self._next[1]
        while self._next[1] == t:
            kind, _, what = self._next
            if kind == "arrival":
                self.specs[what.job_id] = what
                if what.placement is not None:
                    self.host.placements[what.job_id] = tuple(what.placement)
                self.svc.submit(JobArrival(Job(
                    job_id=what.job_id, model=what.model,
                    num_workers=what.workers, duration_iters=what.iters,
                    arrival_ms=t, batch_per_gpu=what.batch)))
            else:
                self.svc.submit(JobDeparture(job_id=what, at_ms=t))
            self._next = next(self.events)
        self.svc.submit(QueryPlacement(at_ms=t)).result()
        self.rounds += 1
        return t


# ---------------------------------------------------------------------- #
def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             device, t_start: float, counter, control: bool = False) -> dict:
    """Set up, measure and check one run of ``cell`` (from
    :func:`load_cell`) on ``device``, the first of the chips JAX found.
    ``t_start``: the process's start on ``time.perf_counter``;
    ``counter``: the compile counter.  ``control``: also read the
    control's numbers (``result["control"]``), for ``control.py``."""
    import jax

    t_import = time.perf_counter()
    cfg, mix = cell["config"], cell["mix"]
    rec = probes.Recorder(tracing=trace)
    svc, proxy, host = build_service(cfg, rec)
    probes.wrap_stages(svc.scheduler.pipeline, rec)
    undo = [probes.wrap_solver(rec)]
    if trace:
        undo.append(probes.wrap_kernels(rec))
        probes.wrap_handler(svc, rec)
    chk = mix["check"]
    racks = _fluid_racks(cfg)
    fluid_racks = set(random.Random(seed ^ 0xF1D).sample(
        racks, min(chk["fluid_groups"], len(racks))))
    probes.wrap_fluid(svc, rec)
    specs: dict = {}
    client = Client(svc, host, stream.events(cfg, mix, seed), specs)
    t_build = time.perf_counter()
    c0 = counter.compile_s
    warmed = warm_kernels(cfg, mix)
    t_warm = time.perf_counter()
    for _ in range(mix["warmup_rounds"]):
        client.round()
    t_replay = time.perf_counter()
    compile_setup_s = counter.compile_s - c0
    setup_s = t_replay - t_start
    log(f"setup_s={setup_s!r} import_s={t_import - t_start!r} "
        f"build_s={t_build - t_import!r} kernel_warm_s={t_warm - t_build!r} "
        f"replay_warm_s={t_replay - t_warm!r} compile_s={compile_setup_s!r} "
        f"warm_launches={warmed} warm_rounds={mix['warmup_rounds']} "
        f"compiles={counter.compiles} cache_hits={counter.cache_hits}")

    # ------------------------------ window ------------------------------ #
    module = svc.scheduler.module
    trace_dir = CHIP / "_out" / f"trace-{os.getpid()}"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    n_compile0 = counter.total()
    cache0 = (module.cache_hits, module.cache_misses)
    occ0 = _occupancy(svc)
    sim0 = svc.net.now_ms
    rec.in_window = True
    t0 = time.perf_counter()
    deadline = t0 + seconds
    mid = None
    win = jax.profiler.TraceAnnotation(devtrace.WINDOW) if trace else None
    if win is not None:
        win.__enter__()
    try:
        while time.perf_counter() < deadline:
            client.round()
            if mid is None and time.perf_counter() >= t0 + seconds / 2:
                mid = (time.perf_counter(), module.cache_hits, module.cache_misses)
    finally:
        t1 = time.perf_counter()
        rec.in_window = False
        if win is not None:
            win.__exit__(None, None, None)
    cache1 = (module.cache_hits, module.cache_misses)
    n_compile = counter.total() - n_compile0
    occ1 = _occupancy(svc)
    sim1 = svc.net.now_ms
    if trace:
        jax.profiler.stop_trace()
    mem = device.memory_stats() or {}
    peak = mem.get("peak_bytes_in_use")
    tel = svc.telemetry()
    svc.close()
    for u in undo:
        u()

    # ------------------------------ numbers ----------------------------- #
    window_s = t1 - t0
    lat = [(b - a) * 1e3 for a, b, w in rec.decisions if w and t0 <= b <= t1]
    n_dec = len(lat)
    halves = _halves(rec.decisions, t0, t1, mid, cache0, cache1)
    if lat:
        log("latency_ms " + " ".join(
            f"p{q}={stats.nearest_rank(lat, q)!r}" for q in (10, 25, 50, 75, 90, 99)))
    log(f"window_s={window_s!r} decisions={n_dec} rounds={client.rounds} "
        f"sim_ms=[{sim0!r}, {sim1!r}] compiles_in_window={n_compile} "
        f"occupancy_start={occ0} occupancy_end={occ1}")
    log("halves " + " ".join(f"{k}={v!r}" for k, v in halves.items()))
    log("telemetry " + " ".join(
        f"{k}={v!r}" for k, v in sorted(tel.items())
        if k.startswith(("reschedule_", "configure_", "prefetch", "degraded",
                         "pipeline_", "alloc_cache", "link_cache"))))
    if rec.batch is not None:
        b = rec.batch
        log(f"batch problems={b.problems} grid={b.grid_problems} "
            f"descent={b.descent_problems} trivial={b.trivial} "
            f"launches={b.launches} grid_rows={b.grid_rows} "
            f"descent_rows={b.descent_rows} solves={rec.solves}")
    if n_dec == 0:
        raise RuntimeError("no decision completed in the window")
    metrics = {}
    e2e = {m["name"]: m for m in cell["end_to_end"]}
    values = {
        "decision_p50_ms": stats.nearest_rank(lat, 50),
        "decision_p95_ms": stats.nearest_rank(lat, 95),
        "decisions_per_s": n_dec / window_s,
        "setup_s": setup_s,
    }
    if not trace:
        for k, v in values.items():
            if k in e2e:
                metrics[k] = {"value": v, "unit": e2e[k]["unit"]}

    # ------------------------------ trace ------------------------------- #
    dev_info = {"platform": device.platform, "kind": device.device_kind,
                "count": len(jax.devices()), "memory_peak_bytes": peak}
    breakdown = None
    if trace:
        t_tr = time.perf_counter()
        names = {devtrace.WINDOW} | {s.name for s in rec.spans}
        events = devtrace.load(str(trace_dir), names)
        w0 = min(e[3] for e in events if e[2] == devtrace.WINDOW)
        worker = [(s.name, w0 + (s.t0 - t0) * 1e9, (s.t1 - s.t0) * 1e9)
                  for s in rec.spans if s.thread == "serve-worker"]
        reduced = devtrace.reduce(events, KERNEL_GROUPS, worker)
        shutil.rmtree(trace_dir, ignore_errors=True)
        dev_info["busy_s"] = reduced["busy_s"]
        dev_info["window_s"] = reduced["window_s"]
        breakdown = {"device_ops": reduced["device_ops"],
                     "idle_gaps": reduced["idle_gaps"]}
        run = {"decisions": n_dec, "window_s": window_s, "spans": rec.spans,
               "batch": rec.batch, "launches": rec.launches, "trace": reduced,
               "device_kind": device.device_kind}
        for m in cell["per_layer"]:
            v = metric_reader(cell["root"], m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        log(f"trace events={len(events)} kernel_modules={reduced['kernel_events']} "
            f"argmin_events={reduced['argmin_events']} argmin_s={reduced['argmin_s']!r} "
            f"launches={len(rec.launches)} kernel_s={reduced['kernel_s']!r} "
            f"prefetch_spans_s={_thread_s(rec.spans, 'serve-prefetch')!r} "
            f"read_s={time.perf_counter() - t_tr!r}")

    # ------------------------------ correctness ------------------------- #
    t_ref = time.perf_counter()
    ref = reference_for(cell["root"], cfg["name"])
    judge = check.Judge(cfg, specs, rule=ref.aligned_links)
    outputs = dict(proxy.outputs)
    got = check.readings(judge, rec.scored, outputs, rec.fluid, chk, seed,
                         fluid_racks)
    limits = cfg["limits"]
    degraded = int(tel.get("degraded_decisions", 0))
    nums = got["numbers"]
    correct = check.verdict(got, degraded, limits)
    log(f"reference_s={time.perf_counter() - t_ref!r} "
        f"decisions_checked={got['decisions']} links_checked={got['links']} "
        f"reference={Path(ref.__file__).name} fluid_racks={sorted(fluid_racks)} "
        f"fluid_states={got['fluid_states']} fluid_chains={got['fluid_chains']} "
        f"fluid_near={got['fluid_near']} fluid_cut={got['fluid_cut']} "
        f"fluid_jobs={got['fluid_jobs']} "
        f"candidates={got['candidates']} discarded={got['discarded']} "
        f"faults={got['faults']}")
    log(f"worst_link {judge.worst!r}")
    checks = {k: {"value": nums[k], "limit": limits[k]} for k in check.NUMBERS}
    checks["degraded_decisions"] = {"value": degraded, "limit": 0}
    result = {"correct": bool(correct), "attempted": n_dec, "failed": degraded,
              "metrics": metrics, "device": dev_info}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    if control:
        ctl = check.readings(judge, rec.scored, outputs, rec.fluid, chk,
                             seed, fluid_racks, control=True)
        result["control"] = dict(ctl, correct=check.verdict(ctl, degraded, limits))
    return result


# the scoring kernels as the trace shows them: their jitted modules (the
# Pallas kernel with its operand copies, and the accept scan, whose ops
# carry generic names), and the argmin kernel's own custom call
KERNEL_GROUPS = {
    "kernel": ("XLA Modules", ("jit_circle_score", "jit__accept_scan")),
    "argmin": ("XLA Ops", ("circle_score_argmin_pallas",)),
}


def _occupancy(svc) -> str:
    running = list(svc.net._execs.values())
    gpus = sum(len(ex.job.placement) for ex in running)
    return f"{len(running)}jobs/{gpus}gpus"


def _fluid_racks(cfg: dict) -> list[int]:
    """The racks the fluid check seeds its watched set from: the hub racks
    of a ``hub_leaf`` layout, else every rack."""
    if cfg["layout"]["kind"] == "hub_leaf":
        spr = cfg["topology"]["servers_per_rack"]
        return sorted({slot[0] // spr for slot in stream.slot_layout(cfg)})
    return list(range(cfg["topology"]["racks"]))


def _thread_s(spans, thread: str) -> float:
    """Seconds of the host stages' spans on the threads whose name starts
    with ``thread`` (a pool numbers its threads: ``serve-prefetch_0``)."""
    return sum(s.t1 - s.t0 for s in spans
               if s.thread.startswith(thread)
               and s.name in ("allocate", "propose", "score"))


def _halves(decisions, t0, t1, mid, cache0, cache1) -> dict:
    tm = mid[0] if mid else 0.5 * (t0 + t1)
    c_mid = mid[1:] if mid else cache1
    first = sum(1 for a, b, w in decisions if w and t0 <= b < tm)
    second = sum(1 for a, b, w in decisions if w and tm <= b <= t1)

    def miss(c_a, c_b):
        hits, misses = c_b[0] - c_a[0], c_b[1] - c_a[1]
        return misses / (hits + misses) if hits + misses else math.nan

    return {"decisions_per_s_1": first / max(tm - t0, 1e-9),
            "decisions_per_s_2": second / max(t1 - tm, 1e-9),
            "link_miss_share_1": miss(cache0, c_mid),
            "link_miss_share_2": miss(c_mid, cache1)}


def print_checks(result: dict) -> None:
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
