"""A whole run of a small cell on the CPU, without the harness's look for a
chip: the result line, the traced run, and the comparison that decides
``correct`` against faults planted under the timed path."""

import dataclasses
import json

import chipbench_support as sup
import pytest

from benchmarks.chip import check


def test_result_line():
    res = sup.run_small("dense64-fine.churn")
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(res)[-1] == "checks"
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    spec = json.loads((sup.ROOT / "BENCHMARK.json").read_text())
    assert set(res["metrics"]) == {m["name"] for m in spec["end_to_end"]}
    for name, m in res["metrics"].items():
        assert m["value"] > 0 and m["unit"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(res["device"])
    for k in check.NUMBERS:
        assert res["checks"][k]["value"] <= res["checks"][k]["limit"]
    json.dumps(res)


def test_traced_run_reports_per_layer_metrics():
    res = sup.run_small("dense64-fine.churn", trace=True)
    assert res["correct"] is True, res["checks"]
    assert res["device"]["window_s"] > 0 and "busy_s" in res["device"]
    got = set(res["metrics"])
    assert {"score_ms_per_decision", "fluid_ms_per_decision",
            "alloc_propose_ms_per_decision", "serve_self_ms_per_decision",
            "device_idle", "launches_per_decision"} <= got
    assert not got & {"decision_p50_ms", "setup_s"}
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


def _alter(results):
    """Every link answer altered: job 1 rotated by half its period."""
    out = []
    for r in results:
        s = list(r.shifts_steps)
        if len(s) > 1:
            g = r.circle.shift_grid(1)
            s[1] = (s[1] + g // 2) % g
        out.append(dataclasses.replace(r, shifts_steps=tuple(s)))
    return out


def _plant_altered(monkeypatch):
    import repro.core.plugin as plugin

    solve = plugin.find_rotations_batched
    monkeypatch.setattr(plugin, "find_rotations_batched",
                        lambda batch, **kw: _alter(solve(batch, **kw)))


def _plant_half(monkeypatch):
    """Half the link problems left unsolved (every other one, counted
    across calls, since a served decision often solves one): they come
    back with the unrotated shifts."""
    import itertools

    import repro.core.plugin as plugin
    from repro.core import compat

    solve = plugin.find_rotations_batched
    count = itertools.count()

    def half(batch, **kw):
        out = solve(batch, **kw)
        for i, (p, c) in enumerate(batch):
            if next(count) % 2:
                circle = compat._build_circle(
                    p, precision_deg=kw["precision_deg"],
                    quantum_ms=kw["quantum_ms"], dilate_steps=1)
                out[i] = compat._finalize(circle, (0,) * len(p), c)
        return out

    monkeypatch.setattr(plugin, "find_rotations_batched", half)


def _plant_frozen(monkeypatch):
    from repro.cluster.network import FluidNetworkSim

    def frozen(self, until_ms, **kw):
        self.now_ms = max(self.now_ms, until_ms)
        return []

    monkeypatch.setattr(FluidNetworkSim, "advance", frozen)


def _plant_unshifted(monkeypatch):
    """A running job's change of time-shift is never applied."""
    from repro.cluster.job import Job

    monkeypatch.setattr(Job, "apply_directive",
                        lambda self, d: setattr(self, "alignment", d))


@pytest.mark.parametrize("plant", [_plant_altered, _plant_half, _plant_frozen,
                                   _plant_unshifted],
                         ids=["answer_altered", "half_the_batch", "state_unchanged",
                              "shift_not_applied"])
def test_a_broken_timed_path_is_not_correct(monkeypatch, plant):
    plant(monkeypatch)
    res = sup.run_small("dense64-fine.churn", seconds=2.0)
    assert res["correct"] is False, res["checks"]


def test_control_fails_where_the_program_passes():
    res = sup.run_small("dense64-fine.churn", seconds=3.0, control=True)
    assert res["correct"] is True, res["checks"]
    ctl = res["control"]["numbers"]
    limits = {k: v["limit"] for k, v in res["checks"].items()}
    assert any(ctl[k] > limits[k] for k in check.NUMBERS), (ctl, limits)
    assert ctl["rotation_gap"] > 3 * res["checks"]["rotation_gap"]["value"]
    assert ctl["fluid_gap"] > 3 * res["checks"]["fluid_gap"]["value"]
    # the harness's own verdict, as control.py reports it
    assert res["control"]["correct"] is False
