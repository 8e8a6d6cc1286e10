"""Shape-derived operation and byte counts, and the peaks table."""

import chipbench_support  # noqa: F401
import pytest

from benchmarks.chip import roofline


def test_launch_cost_by_hand():
    # two rows: A = 720 needing 90 shifts, A = 1440 needing all 360
    ops, nbytes = roofline.launch_cost([720, 1440], [90, 360])
    assert ops == 4 * 720 * 90 + 4 * 1440 * 360          # 259200 + 2073600
    assert ops == 2_332_800
    # per row: base and cand rows (4 bytes an angle), capacity, admissible
    # shifts and angle count in, index and value out
    assert nbytes == (8 * 720 + 12 + 8) + (8 * 1440 + 12 + 8) == 17_320


def test_least_time_and_its_bound():
    pk = roofline.peaks("TPU v5 lite")
    launch = {"num_angles": [720], "needed": [720]}
    t, bound = roofline.least_time([launch], "TPU v5 lite")
    ops, nbytes = roofline.launch_cost([720], [720])
    assert t == pytest.approx(max(ops / pk["flops"], nbytes / pk["bytes_per_s"]))
    assert bound == "compute"
    one = {"num_angles": [720], "needed": [1]}
    assert roofline.least_time([one], "TPU v5 lite")[1] == "memory"


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
