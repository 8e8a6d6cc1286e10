"""Cells whose host places the tenants: the Themis host, the check's watched
set and moves, and a configuration's own reference found by name."""

import ast
import math
import re
from types import SimpleNamespace

import chipbench_support as sup
import pytest

from benchmarks.chip import bench, check, probes, reference, stream
from benchmarks.chip.probes import Span


def _open_cfg():
    return sup.small_open_cell()["config"]


def test_build_service_builds_a_themis_host():
    from repro.sched import ThemisScheduler

    svc, proxy, host = bench.build_service(_open_cfg(), probes.Recorder())
    try:
        assert isinstance(host, ThemisScheduler) and host.num_candidates == 3
        assert proxy.pipeline.stages[1].num_candidates == 3
    finally:
        svc.close()
    cfg = _open_cfg()
    cfg["host"]["kind"] = "pollux"
    with pytest.raises(ValueError):
        bench.build_service(cfg, probes.Recorder())


# servers 0-3 are rack 0, 4-7 rack 1, 8-11 rack 2; one uplink a rack at 4:1
LOOPED = {"a": (0, 4), "b": (5, 8), "c": (1, 9)}   # racks 0-1, 1-2, 0-2
FOREST = {"a": (0, 4), "b": (1, 5), "c": (8, 9)}   # a and b share both uplinks
# a configuration's reference: its loop rule keeps every link; whatever
# else it states is never used
TOY = "from benchmarks.chip.reference import *  # noqa: F403\n\n\n" \
      "def aligned_links(links):\n    return links\n\n\n" \
      "def link_score(*args):\n    raise AssertionError('not the loop rule')\n\n\n" \
      "def FluidRef(*args, **kw):\n    raise AssertionError('not the loop rule')\n"


def _judge(rule=reference.aligned_links):
    cfg = sup.small_cell("dense64-fine.churn")["config"]
    cfg["host"] = {"kind": "themis", "num_candidates": 2, "seed": 0}
    specs = {j: stream.JobSpec(j, "resnet50", 2, 300) for j in "abc"}
    return check.Judge(cfg, specs, rule=rule)


def _chose_looped(judge):
    """The Score stage's output over LOOPED and FOREST (FOREST's link at
    the reference optimum), and a decision that took LOOPED."""
    links = reference.contended(judge.fabric, FOREST)
    prog = {rep: SimpleNamespace(
        shifts_steps=judge.optimum(judge._jobs(js, FOREST), cap), score=1.0,
        shifts_ms=(0.0,) * len(js)) for js, (cap, rep) in links.items()}
    scored = SimpleNamespace(placements=[LOOPED, FOREST],
                             evaluated=[(None, None, {}), (None, None, prog)])
    return judge.decision(scored, SimpleNamespace(placements=LOOPED,
                                                  time_shifts_ms={}))


def test_a_configurations_own_reference_is_found_and_used(tmp_path):
    refs = tmp_path / "benchmarks" / "chip" / "references"
    refs.mkdir(parents=True)
    (refs / "toy.py").write_text(TOY)
    toy = bench.reference_for(tmp_path, "toy")
    assert toy.aligned_links is not reference.aligned_links
    assert bench.reference_for(tmp_path, "other") is reference
    assert math.isfinite(_judge(toy.aligned_links)._ref_score(LOOPED))
    # without it, Algorithm 2 discards the looped candidate
    assert _judge()._ref_score(LOOPED) == -math.inf
    assert math.isfinite(_judge()._ref_score(FOREST))
    got = _chose_looped(_judge())
    assert got["align_gap"] == 1.0
    assert any("loop" in f for f in got["faults"])


@pytest.mark.parametrize("toy", [False, True], ids=["algorithm2", "toy_reference"])
def test_an_open_themis_poisson_cell_runs_and_is_judged(tmp_path, capsys, toy):
    """A whole small run of a Themis-placed, Poisson-fed cell at a load
    its cluster holds without shrinking a job; a configuration reference
    that discards every candidate turns its verdict."""
    cell = sup.small_open_cell(load=0.5)
    cell["mix"]["max_workers"] = 6
    if toy:
        refs = tmp_path / "benchmarks" / "chip" / "references"
        refs.mkdir(parents=True)
        (refs / "small-open.py").write_text(
            TOY.replace("return links", "return None"))
        cell["root"] = tmp_path
    # a seed whose window has Themis take a candidate past its first
    res = sup.run_small("", seed=2**31 + 8, seconds=3.0, cell=cell)
    out = capsys.readouterr().out
    assert res["attempted"] > 0 and res["failed"] == 0
    assert int(re.search(r"fluid_chains=(\d+)", out).group(1)) >= 1
    assert f"reference={'small-open' if toy else 'reference'}.py" in out
    assert res["correct"] is (not toy), res["checks"]


def test_the_watched_set_is_closed_under_link_sharing():
    cfg = sup.small_cell("dense64-fine.churn")["config"]
    fabric = reference.Fabric(cfg["topology"])   # one uplink a rack
    # a, b and c chain racks 0-1, 1-2 and 2-3; d stays in rack 5
    chain = {"a": (0, 4), "b": (5, 8), "c": (9, 12), "d": (20, 21), "e": (24, 28)}
    assert check.closure({"a"}, chain, fabric) == {"a", "b", "c"}
    assert check.closure({"d"}, chain, fabric) == {"d"}
    # on a hub-and-leaf layout: the tenants of the seeded hubs
    slots = {f"t{i}": s for i, s in enumerate(stream.slot_layout(cfg))}
    hub = {j for j, s in slots.items() if s[0] // 4 == 0}
    assert len(hub) == 3 and check.closure(hub, slots, fabric) == hub


def test_a_moved_job_restarts_as_the_program_restarts_it():
    """The reference's move against the program's fluid engine: a 3-worker
    job moved to other servers (same worker count) after 5 s."""
    from repro.cluster.job import Job
    from repro.cluster.network import FluidNetworkSim
    from repro.cluster.topology import Topology
    from repro.engine.plan import JobAlignment

    cfg = _open_cfg()
    t = cfg["topology"]
    net = FluidNetworkSim(
        Topology(num_racks=t["racks"], servers_per_rack=t["servers_per_rack"],
                 nic_gbps=t["rack_nic_gbps"][0],
                 rack_nic_gbps=tuple(t["rack_nic_gbps"]),
                 oversubscription=t["oversubscription"]),
        migration_pause_ms=1000.0, congested_efficiency=0.88)
    job = Job(job_id="a", model="vgg16", num_workers=3, duration_iters=900,
              batch_per_gpu=1400)
    fabric = reference.Fabric(cfg["topology"])
    sim = reference.FluidRef(cfg, fabric)
    before, after = (0, 4, 8), (1, 12, 20)
    job.placement = before
    job.apply_directive(JobAlignment(shift_ms=10.0))
    net.configure_incremental([job])
    sim.configure({"a": (10.0, False, None)},
                  {"a": ("vgg16", 3, 1400, before, 900)})
    net.advance(5000.0)
    sim.advance(5000.0)
    assert not reference.move(sim, "a", ("vgg16", 3, 1400, before, 900), None, 1e3)
    job.placement = after
    job.clear_directive()
    net.configure_incremental([job])
    assert reference.move(sim, "a", ("vgg16", 3, 1400, after, 900), None, 1e3)
    sim.configure({"a": (None, False, None)},
                  {"a": ("vgg16", 3, 1400, after, 900)})
    ex = net._execs["a"]
    assert sim.jobs["a"]["delay"] == ex.delay_ms == 1010.0
    net.advance(9000.0)
    sim.advance(9000.0)
    ex, j = net._execs["a"], sim.jobs["a"]
    prog = dict(j, iters_done=ex.job.iters_done, seg=ex.seg_idx,
                remaining=ex.remaining)
    assert j["iters_done"] > 0
    assert reference.position(prog, j["segs"]) == pytest.approx(
        reference.position(j, j["segs"]), abs=1e-9)


def test_prefetch_seconds_count_the_pools_thread():
    spans = [Span("score", "serve-prefetch_0", 0.0, 1.5),
             Span("fluid.advance", "serve-prefetch_0", 0.0, 9.0),
             Span("allocate", "serve-worker", 0.0, 2.0)]
    assert bench._thread_s(spans, "serve-prefetch") == 1.5


def _imports(path):
    """Every name a file imports, relative imports resolved within
    ``benchmarks.chip`` and each imported member named in full."""
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                base = "benchmarks.chip" + ("." + base if base else "")
            yield from (f"{base}.{a.name}" for a in node.names)


@pytest.mark.parametrize("path", [
    sup.ROOT / "benchmarks/chip/reference.py",
    sup.ROOT / "benchmarks/chip/check.py",
    *sorted((sup.ROOT / "benchmarks/chip/references").glob("*.py"))],
    ids=lambda p: p.name)
def test_the_reference_imports_nothing_of_the_program(path):
    """The plain reference, the comparison and every configuration's own
    reference import nothing of the program, directly or through the
    harness's modules that do: of the benchmark, only ``reference``."""
    def under(name, mod):
        return name == mod or name.startswith(mod + ".")

    for name in _imports(path):
        assert not under(name, "repro") and not under(name, "importlib"), name
        assert not under(name, "benchmarks") or under(
            name, "benchmarks.chip.reference"), name
