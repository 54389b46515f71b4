"""The readers of a cell over several chips, on a hand-built trace of
two device planes."""
import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import readers
import readers_dp

MS = 1_000_000          # ns
with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))), "metrics", "exchange_ms_per_tree.json")) as _f:
    EXCHANGE = json.load(_f)["params"]       # the metric's own patterns
PLANES = {
    # chip 0: a 40 ms kernel, a 2 ms all-reduce under the name the TPU
    # compiler gave it on the v5e (after the primitive), 8 ms of fusions
    "/device:TPU:0": {"XLA Ops": [
        ["%compute_group_histograms_fused_tiled.5 = (s32[]) custom-call()", 0, 40 * MS],
        ["%psum.44 = s32[2,42,67,255,3]{1,3,2,4,0:T(8,128)S(1)} "
         "all-reduce(%pad_add_fusion.8), channel_id=1", 40 * MS, 2 * MS],
        ["%fusion.7 = f32[] fusion()", 42 * MS, 8 * MS]]},
    # chip 1: the same kernel, a 4 ms all-reduce (it waited), 6 ms of fusions
    "/device:TPU:1": {"XLA Ops": [
        ["%compute_group_histograms_fused_tiled.5 = (s32[]) custom-call()", 0, 40 * MS],
        ["%all-reduce.3 = s32[2,42,67,255,3] all-reduce(...)", 40 * MS, 4 * MS],
        ["%fusion.7 = f32[] fusion()", 44 * MS, 4 * MS]]},
    "/host:CPU": {},
}
TREE = {"left_child": [1, -1], "right_child": [-2, -3],
        "internal_count": [1000, 700], "leaf_count": [450, 300, 250]}


def ctx(planes=PLANES, mesh=(2,)):
    c = {"trace_planes": planes, "trace_cache": {}, "n_trees": 1,
         "trees": [TREE], "window_s": 0.05, "device_kind": "TPU v5 lite",
         "config": {"rows": 1000, "features": 67,
                    "params": {"max_bin": 255, "mesh_shape": list(mesh)},
                    "reference": {"grad_bytes": 1}}}
    values = {"exchange_ms_per_tree": lambda: readers.xplane_events_matching(
                  c, EXCHANGE),
              "hist_ms_per_tree": lambda: readers.xplane_events_matching(
                  c, {"patterns": ["^%?compute_group_histograms"], "per": "tree",
                      "scale": 1e3})}
    c["value_of"] = lambda name: values[name]()
    return c


def test_exchange_time_is_the_chips_mean():
    assert ctx()["value_of"]("exchange_ms_per_tree") == pytest.approx(3.0)


def test_exchange_share_of_the_interconnect():
    # 3 histograms x 67 x 255 x 8 B x 1/2 out of a chip, at 200 GB/s, over 3 ms
    least = 3 * 67 * 255 * 8 * 0.5 / 200e9
    assert readers_dp.exchange_ici_share(
        ctx(), {"over_metric": "exchange_ms_per_tree"}) == \
        pytest.approx(100 * least / 3e-3)


def test_rooflines_divide_by_the_chips():
    one = readers.roofline_share(ctx(), {"work": "histogram",
                                         "over_metric": "hist_ms_per_tree"})
    assert readers_dp.roofline_share_chips(
        ctx(), {"work": "histogram", "over_metric": "hist_ms_per_tree"}) == \
        pytest.approx(one / 2)
    step = readers.roofline_share(ctx(), {"work": "step", "over": "window"})
    assert readers_dp.roofline_share_chips(
        ctx(mesh=(4,)), {"work": "step", "over": "window"}) == \
        pytest.approx(step / 4)


def test_busy_skew_over_the_planes():
    # 50 ms and 48 ms busy: (50 - 48) / 49
    assert readers_dp.chip_busy_skew(ctx(), {}) == pytest.approx(100 * 2 / 49)


def test_a_program_without_the_exchange_reads_none():
    """The parent's trace, or one chip's: no all-reduce, one plane."""
    alone = {"/device:TPU:0": {"XLA Ops": [PLANES["/device:TPU:0"]["XLA Ops"][0]]},
             "/host:CPU": {}}
    c = ctx(alone, mesh=(1,))
    assert c["value_of"]("exchange_ms_per_tree") is None
    assert readers_dp.exchange_ici_share(
        c, {"over_metric": "exchange_ms_per_tree"}) is None
    assert readers_dp.chip_busy_skew(c, {}) is None
    c["trace_planes"] = None
    assert readers_dp.chip_busy_skew(c, {}) is None
