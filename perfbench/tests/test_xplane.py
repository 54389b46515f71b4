"""The trace reader on a quarter second cut from a real trace of
criteo_train's first tree (TPU v5 lite, my chip run, PR 25)."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import xplane

FIXTURE = os.path.join(HERE, "criteo_quarter_second.json.gz")
HIST = [r"^%?compute_group_histograms"]


def test_busy_time_and_kernel_sum():
    r = xplane.reduce(xplane.load(FIXTURE), HIST)
    assert r["devices"] == 1
    assert r["busy_s"] == pytest.approx(0.249832212, rel=1e-9)
    assert r["matched_s"] == pytest.approx(0.243491909, rel=1e-9)
    assert r["device_ops"][0][0] == "compute_group_histograms_fused_tiled"
    assert r["device_ops"][0][1] == pytest.approx(r["matched_s"])
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
    # gaps and busy time tile the span from the first event to the last
    ops = xplane.leaves(xplane.load(FIXTURE)["/device:TPU:0"]["XLA Ops"])
    span = (max(e[1] + e[2] for e in ops) - min(e[1] for e in ops)) / 1e9
    assert r["busy_s"] + sum(g[1] for g in r["idle_gaps"]) == pytest.approx(span)


def test_containers_are_not_counted_twice():
    events = [["%while.1 = ...", 0, 100], ["%fusion.1 = ...", 10, 20],
              ["%cond.2 = ...", 40, 50], ["%compute_group_histograms.3 = ...", 45, 30]]
    r = xplane.reduce({"/device:TPU:0": {"XLA Ops": events}}, HIST)
    assert r["busy_s"] == pytest.approx(50e-9)
    assert r["matched_s"] == pytest.approx(30e-9)


@pytest.mark.parametrize("planes", [
    {"/host:CPU": {"python": [["f", 0, 10]]}},
    {"/device:TPU:0": {"XLA Ops": []}},
])
def test_no_device_event_is_an_error_not_a_zero(planes):
    with pytest.raises(xplane.NoDeviceTrace):
        xplane.reduce(planes)


def test_short_name_drops_the_instruction_number():
    assert xplane.short_name("%fusion.431 = (s32[3]) fusion(...)") == "fusion"
    assert xplane.short_name("%cond.31.clone.10 = (f32[2])") == "cond"
