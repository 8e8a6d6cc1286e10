#!/usr/bin/env python3
"""Run one benchmark cell once on the chip this machine holds.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

``--trace 0`` measures the cell's end-to-end metrics; ``--trace 1`` runs
the same window under the profiler and reports its per-layer metrics.
Earlier lines report set-up split into import, build, kernel warm-up and
replay warm-up (with compile seconds), the window's decision count,
half-window rates and link-cache miss shares, compilations inside the
window, and the correctness readings.  The last line of standard output
is one JSON object; the last lines of standard error repeat each number
compared beside its limit.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.  JAX's compile cache lives where
``JAX_COMPILATION_CACHE_DIR`` says, else in ``<checkout>/.jax_cache``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench_file = ROOT / "BENCHMARK.json"
    if not (ROOT / "src" / "repro").is_dir() or not bench_file.exists():
        print(f"no system under test at {ROOT}: needs src/repro and "
              "BENCHMARK.json", file=sys.stderr)
        return 2
    cells = {w["name"]: w for w in json.loads(bench_file.read_text())["workloads"]}
    if args.workload not in cells:
        print(f"unknown workload {args.workload!r}; have {sorted(cells)}",
              file=sys.stderr)
        return 2
    chips = cells[args.workload]["chips"]

    import jax

    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    # every program goes to the cache, so only a checkout's first run compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from benchmarks.chip import bench, probes

    counter = probes.CompileCounter(jax)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: jax.devices()[0].platform == {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"cell {args.workload} needs {chips} chips, JAX found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    bench.log(f"device: {devices[0].device_kind} x{len(devices)} "
              f"(jax {jax.__version__})")
    result = bench.run_cell(bench.load_cell(ROOT, args.workload), args.seed,
                            args.seconds, bool(args.trace), devices[0],
                            T_START, counter)
    bench.print_checks(result)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
