"""Peaks of the chips the benchmark runs on, and the least time a
``circle_score`` launch can take on them.

The argmin kernel scores, for every row ``l``, each admissible rotation
``s`` of the candidate job against the placed demand:
``Σ_α max(0, base[l, α] + cand[l, (α - s) mod A_l] - C_l)`` - per angle an
add, a subtract, a max and an accumulate: 4 operations.  A row needs its
shifts up to the first zero-excess one (nothing can beat zero, and the
kernel may stop there), else every admissible one.  It reads its two
demand rows (4-byte floats) and three 4-byte scalars (capacity,
admissible shifts, angle count) and writes an index and a value.

v5e publishes no peak for its vector unit, which does this work; the
compute bound is divided by the one published compute peak, bf16 on the
matrix unit, so the share is a lower bound of what the vector unit could
reach.
"""

from __future__ import annotations

# device_kind -> published peaks.  Source: Google Cloud TPU documentation,
# "TPU v5e" (per chip: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at
# 819 GB/s).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "bytes_per_s": 819e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}

OPS_PER_ANGLE = 4


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def launch_cost(num_angles, needed) -> tuple[float, float]:
    """Operations and bytes one launch needs, from its rows' angle counts
    and the shifts each row needed."""
    ops = sum(OPS_PER_ANGLE * a * s for a, s in zip(num_angles, needed))
    nbytes = sum(2 * 4 * a + 3 * 4 + 2 * 4 for a in num_angles)
    return float(ops), float(nbytes)


def least_time(launches, device_kind: str) -> tuple[float, str]:
    """Least seconds the chip needs for ``launches`` (each launch bound by
    the larger of its operations over peak FLOP/s and its bytes over peak
    bandwidth), and which bound set most of it."""
    pk = peaks(device_kind)
    total = t_compute = t_memory = 0.0
    for launch in launches:
        o, b = launch_cost(launch["num_angles"], launch["needed"])
        c, m = o / pk["flops"], b / pk["bytes_per_s"]
        total += max(c, m)
        t_compute += c if c >= m else 0.0
        t_memory += m if m > c else 0.0
    return total, ("compute" if t_compute >= t_memory else "memory")
