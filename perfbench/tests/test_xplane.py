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


def _host_label_by_scan(host_lines, lo, hi):
    """The labeller as it was: every host event scanned for every gap."""
    best = None
    for line, events in host_lines.items():
        for name, start, dur in events:
            ov = min(hi, start + dur) - max(lo, start)
            if ov <= 0:
                continue
            key = (-ov, dur)
            if best is None or key < best[0]:
                best = (key, f"{line}: {name}")
    return best[1][:120] if best else "no host span in the trace"


# one gap each: a span and the shorter one nested in it over the whole gap
# (the shorter wins), three alike (the first in line order wins: tie_a), a
# tie in another line whose event starts first (line order still wins:
# late), a zero-length event, one longer than the label's cut, and one that
# lives through every gap (it wins where nothing shorter covers a gap)
SYNTHETIC = {
    "/device:TPU:0": {"XLA Ops": [
        ["%a.1 = x", 0, 10], ["%b.2 = x", 30, 10], ["%c.3 = x", 60, 5],
        ["%d.4 = x", 100, 10], ["%e.5 = x", 140, 10], ["%f.6 = x", 200, 1]]},
    "/host:CPU": {
        "python": [["late", 110, 32], ["outer", 5, 30], ["inner", 10, 20],
                   ["tie_a", 40, 20], ["tie_b", 40, 20], ["zero", 50, 0],
                   ["x" * 300, 150, 50]],
        "worker": [["tie_c", 40, 20], ["early", 108, 32], ["nested", 66, 30],
                   ["nested_in", 70, 20]],
        "main": [["background", 0, 1000]]},
}
LABELS = ["python: inner", "python: tie_a", "main: background",
          "python: late", ("python: " + "x" * 300)[:120]]


@pytest.mark.parametrize("trace", [
    os.path.join(os.path.dirname(os.path.dirname(HERE)), "tests",
                 "criteo_phase_trace.json.gz"),
    FIXTURE,
    "synthetic",
])
def test_the_sweep_labels_every_gap_as_the_scan_did(trace, monkeypatch):
    if trace == "synthetic":
        planes = SYNTHETIC
    elif trace.endswith("criteo_phase_trace.json.gz"):
        import gzip
        import json
        with gzip.open(trace, "rt") as f:
            planes = json.load(f)["planes"]
    else:
        planes = xplane.load(trace)
    host = planes[xplane.HOST_PLANE]
    for name, lines in planes.items():
        if xplane.DEVICE_PLANE.match(name):
            spans = xplane.merged([e[1], e[1] + e[2]] for e in
                                  xplane.leaves(lines[xplane.OPS_LINE]))
            gaps = [(a[1], b[0]) for a, b in zip(spans, spans[1:])]
            assert xplane._host_labels(host, gaps) == [
                _host_label_by_scan(host, lo, hi) for lo, hi in gaps]
    got = xplane.reduce(planes, HIST)
    monkeypatch.setattr(xplane, "_host_labels", lambda host, gaps: [
        _host_label_by_scan(host, lo, hi) for lo, hi in gaps])
    want = xplane.reduce(planes, HIST)
    assert got == want
    assert len(want["idle_gaps"]) > 1
    if trace == "synthetic":
        assert sorted(k for k, _ in got["idle_gaps"]) == sorted(LABELS)
