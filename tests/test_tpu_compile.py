"""Compile the scheduler's device programs for a described TPU v5e.

Nothing runs: each case lowers and compiles for the first chip of a
``v5e:2x2`` topology description, which the installed TPU compiler can
target without a chip attached.  Mosaic refuses what interpret mode
accepts (unaligned dynamic lane slices, single-lane dynamic stores), so
these cases guard the kernels' TPU lowering at the shipped schedule and
real widths.  Every kernel case must show ``tpu_custom_call`` and compile
well inside :data:`COMPILE_BUDGET_S`: every cold run on the chip pays
each compile once per width and row bucket.

The topology is described inside a module fixture, never at import:
only the worker that runs these tests loads the TPU library.
"""

import time

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import tune
from repro.kernels.circle_score.kernel import (
    circle_score_argmin_pallas,
    circle_score_pallas,
)
from repro.kernels.circle_score.ops import _accept_scan

# the largest grid chunk the rotation search ships (compat.GRID_CHUNK_ROWS)
ROWS = 4096
COMPILE_BUDGET_S = 10.0


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        # a compile for a described chip can be written to the persistent
        # cache but never read back here: keep the cache out of it
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            from jax.experimental import topologies

            try:
                topo = topologies.get_topology_desc(
                    platform="tpu", topology_name="v5e:2x2"
                )
            except Exception as e:
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **static):
    t0 = time.perf_counter()
    compiled = fn.lower(*args, **static).compile()
    return compiled, time.perf_counter() - t0


@pytest.mark.parametrize("width", [128, 512, 1024, 2048])
@pytest.mark.parametrize("ragged", [False, True], ids=["uniform", "ragged"])
def test_argmin_kernel_compiles(one_chip, width, ragged):
    f32 = lambda *s: _spec(s, jnp.float32, one_chip)  # noqa: E731
    i32 = lambda *s: _spec(s, jnp.int32, one_chip)  # noqa: E731
    args = [f32(ROWS, width), f32(ROWS, width), f32(ROWS), i32(ROWS)]
    if ragged:
        args.append(i32(ROWS))
    sched = dict(tune.DEFAULTS["circle_score_segmin"])
    compiled, secs = _compile(
        circle_score_argmin_pallas, *args, interpret=False, **sched
    )
    assert "tpu_custom_call" in compiled.as_text()
    assert secs < COMPILE_BUDGET_S, f"{secs:.1f}s at width {width}"


def test_full_matrix_kernel_compiles(one_chip):
    f32 = lambda *s: _spec(s, jnp.float32, one_chip)  # noqa: E731
    compiled, secs = _compile(
        circle_score_pallas, f32(64, 512), f32(64, 512), f32(64),
        interpret=False, **tune.DEFAULTS["circle_score"],
    )
    assert "tpu_custom_call" in compiled.as_text()
    assert secs < COMPILE_BUDGET_S, f"{secs:.1f}s"


def test_accept_scan_compiles_under_x64(one_chip):
    with jax.enable_x64(True):
        compiled, secs = _compile(
            _accept_scan,
            _spec((ROWS,), jnp.float32, one_chip),
            _spec((ROWS,), jnp.int32, one_chip),
            _spec((ROWS,), jnp.int32, one_chip),
            _spec((64,), jnp.float64, one_chip),
        )
    assert "f64" in compiled.as_text()
    assert secs < COMPILE_BUDGET_S, f"{secs:.1f}s"
