"""The least time the chip needs for the work of the trees that were
grown.  Every term comes from the configuration's shapes and from the
trees themselves: nothing here knows a block size, a one-hot width, a
strip count or any other property of the program's kernels, so that a
rewritten kernel is read against the same work.

The algorithm is the reference's: a tree's root histogram reads all N
rows; each later split reads only the rows of its smaller child, and the
larger child's histogram is parent minus smaller (LightGBM
serial_tree_learner.cpp, smaller-leaf + subtraction).
"""
import json
import math
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind):
    with open(_PEAKS) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json: "
                       "add it with its source, there is no default")
    return table[device_kind]


def hist_rows(rows, trees):
    """Rows the histogram step must read: per tree, N for the root plus
    the smaller child of every split (from the tree's own counts)."""
    total = 0
    for t in trees:
        total += rows
        for node in range(len(t["left_child"])):
            kids = []
            for child in (t["left_child"][node], t["right_child"][node]):
                kids.append(t["internal_count"][child] if child >= 0
                            else t["leaf_count"][~child])
            total += min(kids)
    return total


def hist_bytes_per_row(features, max_bin, grad_bytes):
    """One row's share of the bin matrix at the fewest bits ``max_bin``
    needs, plus its gradient and hessian at the stated width."""
    bits = max(1, math.ceil(math.log2(max_bin + 1)))
    return features * bits / 8.0 + 2 * grad_bytes


def state_bytes_per_row(grad_bytes, score_bytes=4, label_bytes=4):
    """One tree's pass over the per-row state: read score and label,
    write the gradient pair, read and write the score for the update."""
    return score_bytes + label_bytes + 2 * grad_bytes + 2 * score_bytes


def least_seconds(device_kind, rows, features, max_bin, grad_bytes, trees,
                  work):
    """(seconds, "hbm" or "ops") for ``work`` = "histogram" or "step"."""
    pk = peaks(device_kind)
    hrows = hist_rows(rows, trees)
    nbytes = hrows * hist_bytes_per_row(features, max_bin, grad_bytes)
    nops = hrows * features * 2.0          # one add each into G and H
    if work == "step":
        nbytes += len(trees) * rows * state_bytes_per_row(grad_bytes)
    elif work != "histogram":
        raise ValueError(f"unknown work {work!r}")
    by_bytes = nbytes / pk["hbm_bytes_per_s"]
    by_ops = nops / pk["int8_ops_per_s"]
    return max(by_bytes, by_ops), ("hbm" if by_bytes >= by_ops else "ops")
