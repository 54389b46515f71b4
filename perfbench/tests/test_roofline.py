"""The roofline's work function on a hand-built three-leaf tree."""
import inspect
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import roofline

# 1000 rows; the root splits 700 | 300 (leaf 1); the 700 split 450 | 250
TREE = {"left_child": np.array([1, -1]), "right_child": np.array([-2, -3]),
        "internal_count": np.array([1000, 700]),
        "leaf_count": np.array([450, 300, 250])}


def test_rows_bytes_and_time_by_hand():
    # root 1000 + smaller child of the root 300 + smaller child below 250
    assert roofline.hist_rows(1000, [TREE]) == 1550
    # 67 features at 6 bits (63 bins) + an int8 gradient pair
    assert roofline.hist_bytes_per_row(67, 63, 1) == 67 * 6 / 8 + 2
    assert roofline.hist_bytes_per_row(67, 255, 1) == 67 + 2    # criteo-67
    assert roofline.state_bytes_per_row(1) == 4 + 4 + 2 + 8
    secs, bound = roofline.least_seconds("TPU v5 lite", 1000, 67, 63, 1,
                                         [TREE], "histogram")
    assert bound == "hbm"
    assert secs == pytest.approx(1550 * 52.25 / 819e9)
    step, _ = roofline.least_seconds("TPU v5 lite", 1000, 67, 63, 1,
                                     [TREE], "step")
    assert step == pytest.approx((1550 * 52.25 + 1000 * 18) / 819e9)


def test_an_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        roofline.least_seconds("TPU v9", 1000, 67, 63, 1, [TREE], "histogram")


def test_no_argument_names_a_kernel_property():
    banned = ("block", "tile", "strip", "onehot", "one_hot", "pack", "lane",
              "kernel", "pallas", "vmem", "frontier")
    for fn in (roofline.hist_rows, roofline.hist_bytes_per_row,
               roofline.state_bytes_per_row, roofline.least_seconds):
        for arg in inspect.signature(fn).parameters:
            assert not any(b in arg.lower() for b in banned), (fn.__name__, arg)
