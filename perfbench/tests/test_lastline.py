"""The last-line validator accepts a good line of each mode and rejects
each way the line of PR 22 could have failed."""
import copy
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import lastline

E2E = {"train_ms_per_tree": "ms/tree", "setup_s": "s"}
LAYER = {"hist_ms_per_tree": "ms/tree", "device_idle_pct": "%"}
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
          "memory_peak_bytes": 2898270208}


def good(traced):
    names = LAYER if traced else E2E
    line = {"correct": True, "attempted": 32, "failed": 0,
            "metrics": {n: {"value": 1.5, "unit": u} for n, u in names.items()},
            "device": dict(DEVICE),
            "compared": {"score_gap": {"value": 1e-7, "limit": 1e-4}}}
    if traced:
        line["device"].update(busy_s=21.9, window_s=22.0)
        line["breakdown"] = {"device_ops": [["fusion", 0.5]], "idle_gaps": []}
    return line, names


@pytest.mark.parametrize("traced", [False, True])
def test_good_line_passes(traced):
    line, names = good(traced)
    assert lastline.problems(line, names, traced) == []
    assert list(__import__("json").loads(lastline.render(line)))[-1] == "compared"


def _drop_metric(line):
    line["metrics"].pop(next(iter(line["metrics"])))


def _extra_only(line):
    for k in list(line):
        del line[k]
    line.update(ok=True, report={"auc": 0.9})


BREAKS = {
    "metric_missing": (True, _drop_metric),
    "metric_without_unit": (False, lambda l: l["metrics"]["setup_s"].pop("unit")),
    "metric_not_finite": (False, lambda l: l["metrics"]["setup_s"].update(value=float("nan"))),
    "busy_zero": (True, lambda l: l["device"].update(busy_s=0.0)),
    "busy_over_window": (True, lambda l: l["device"].update(busy_s=23.0)),
    "busy_missing": (True, lambda l: l["device"].pop("busy_s")),
    "memory_missing": (False, lambda l: l["device"].pop("memory_peak_bytes")),
    "memory_zero": (False, lambda l: l["device"].update(memory_peak_bytes=0)),
    "extra_keys_only": (True, _extra_only),
    "cpu": (False, lambda l: l["device"].update(platform="cpu")),
    "wrong_count": (False, lambda l: l["device"].update(count=4)),
    "correct_not_bool": (False, lambda l: l.update(correct="yes")),
    "failed_over_attempted": (False, lambda l: l.update(failed=33)),
    "breakdown_too_long": (True, lambda l: l["breakdown"].update(
        device_ops=[["x", 1.0]] * 11)),
    "unknown_top_key": (False, lambda l: l.update(report={})),
}


@pytest.mark.parametrize("name", sorted(BREAKS))
def test_broken_line_is_refused(name):
    traced, breaker = BREAKS[name]
    line, names = good(traced)
    line = copy.deepcopy(line)
    breaker(line)
    assert lastline.problems(line, names, traced), name
