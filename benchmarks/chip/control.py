#!/usr/bin/env python3
"""Read the correctness numbers of the program and of the control on
several seeds of one cell, in one process (set-up compiles once).

    python3 benchmarks/chip/control.py --workload <cell> \\
        --seeds 11,12,13 --seconds 20 [--out control.json]

Each seed is one run of the cell (a fresh service and stream) with a
window of ``--seconds``; afterwards the program's numbers and the
control's (``check.py``: rotations searched on half the angles, fluid
model in float32) are read from the same captured decisions.  Prints
one JSON line per seed, with ``correct`` for the program and
``control_correct`` for the control, both by the harness's own verdict,
and writes them all to ``--out``.  The limits in
the configuration files are set from these readings (PERF.md gives
them).  Needs a TPU, like ``run.py``.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(ROOT))
    from benchmarks.chip import bench, probes

    counter = probes.CompileCounter(jax)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: {dev.platform!r}", file=sys.stderr)
        return 2
    rows = []
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        res = bench.run_cell(bench.load_cell(ROOT, args.workload), seed,
                             args.seconds, False, dev, t0, counter, control=True)
        row = {"seed": seed, "correct": res["correct"],
               "control_correct": res["control"]["correct"],
               "program": {k: v["value"] for k, v in res["checks"].items()},
               "control": res["control"]["numbers"],
               "control_faults": res["control"]["faults"],
               "metrics": {k: v["value"] for k, v in res["metrics"].items()}}
        print(json.dumps(row), flush=True)
        rows.append(row)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
