"""Shared set-up of the benchmark's CPU tests: import paths and small
cells (a few racks, 5 degree circles) that a test run can hold."""

from __future__ import annotations

import copy
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from benchmarks.chip import bench, probes  # noqa: E402


def small_cell(name: str) -> dict:
    """The cell ``name`` cut to 8 racks (two hubs of three tenants) at its
    own 0.5 degree circles, so a run takes seconds on the CPU."""
    cell = copy.deepcopy(bench.load_cell(ROOT, name))
    cfg, mix = cell["config"], cell["mix"]
    cfg["topology"]["racks"] = 8
    cfg["topology"]["rack_nic_gbps"] = cfg["topology"]["rack_nic_gbps"][:8]
    cfg["layout"]["groups"] = [[2, 3]]
    mix["warmup_rounds"] = 3
    # both hubs watched, so the reference steps where the program does
    # and one chain can span the whole window
    mix["check"] = dict(mix["check"], decisions=4, fluid_groups=2,
                        fluid_chain_ms=1e9)
    return cell


def small_open_cell(load: float = 0.9) -> dict:
    """The churn cell's configuration cut to 8 racks at 2:1, its tenants
    placed by a Themis host with 3 candidates on an open layout, fed by a
    Poisson process from the same deck at ``load``."""
    cell = small_cell("dense64-fine.churn")
    cfg, mix = cell["config"], cell["mix"]
    cfg["name"] = "small-open"
    cfg["topology"]["oversubscription"] = 2.0
    cfg["host"] = {"kind": "themis", "num_candidates": 3, "seed": 0}
    cfg["layout"] = {"kind": "open"}
    mix.update(process="poisson", load=load, min_workers=1, block=8,
               warmup_rounds=6)
    mix["check"] = dict(mix["check"], fluid_groups=2)
    return cell


_COUNTER = None


def run_small(name: str, seed: int = 2**31 + 7, seconds: float = 1.5,
              trace: bool = False, control: bool = False,
              cell: dict | None = None) -> dict:
    """One run of the small cell on this process's first JAX device,
    without the harness's look for a chip.  The rotation search takes the
    host's numpy path (bit-identical to the kernels, which the CPU would
    only interpret), so there is no kernel shape to warm up."""
    global _COUNTER
    import jax

    from repro.core import compat

    if _COUNTER is None:
        _COUNTER = probes.CompileCounter(jax)
    eligible, warm = compat._kernel_eligible, bench.warm_kernels
    compat._kernel_eligible = lambda backend, num_angles: False
    bench.warm_kernels = lambda cfg, mix: 0
    try:
        return bench.run_cell(cell or small_cell(name), seed, seconds, trace,
                              jax.devices()[0], time.perf_counter(), _COUNTER,
                              control=control)
    finally:
        compat._kernel_eligible, bench.warm_kernels = eligible, warm
