"""The contract of a run's last stdout line, and the check the runner
makes against it before it prints.

The sentence it is built to (ledger, PR 22's refusal): "a JSON object
with the keys correct, attempted, failed, metrics and device, where
metrics gives each metric of this workload as its value and unit, and
device gives platform, kind, count, memory_peak_bytes and, in a traced
run, window_s and busy_s (above 0, at most window_s)".
"""
import json
import math

TOP_KEYS = ("correct", "attempted", "failed", "metrics", "device")
OPTIONAL_KEYS = ("breakdown", "compared")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
TRACE_DEVICE_KEYS = ("busy_s", "window_s")


def _number(x):
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def problems(line, expected, traced, chips=1):
    """Every way ``line`` (a dict) departs from the contract; [] if none.

    ``expected`` maps the metric names this run has to report to their
    units."""
    bad = []
    if not isinstance(line, dict):
        return ["the line is not a JSON object"]
    for k in TOP_KEYS:
        if k not in line:
            bad.append(f"key {k!r} is missing")
    extra = set(line) - set(TOP_KEYS) - set(OPTIONAL_KEYS)
    if extra:
        bad.append(f"keys the driver does not read: {sorted(extra)}")
    if bad:
        return bad
    if not isinstance(line["correct"], bool):
        bad.append("correct is not true or false")
    for k in ("attempted", "failed"):
        if not (isinstance(line[k], int) and not isinstance(line[k], bool)
                and line[k] >= 0):
            bad.append(f"{k} is not a count")
    if not bad and line["failed"] > line["attempted"]:
        bad.append("failed is more than attempted")
    metrics = line["metrics"]
    if not isinstance(metrics, dict):
        return bad + ["metrics is not an object"]
    for name, unit in expected.items():
        m = metrics.get(name)
        if m is None:
            bad.append(f"metric {name!r} is missing")
            continue
        if not isinstance(m, dict) or not _number(m.get("value")):
            bad.append(f"metric {name!r} has no finite value")
        elif m.get("unit") != unit:
            bad.append(f"metric {name!r} has unit {m.get('unit')!r}, "
                       f"BENCHMARK.json says {unit!r}")
    for name in set(metrics) - set(expected):
        bad.append(f"metric {name!r} is not one of this run's")
    device = line["device"]
    if not isinstance(device, dict):
        return bad + ["device is not an object"]
    for k in DEVICE_KEYS:
        if k not in device:
            bad.append(f"device.{k} is missing")
    if device.get("platform") != "tpu":
        bad.append(f"device.platform is {device.get('platform')!r}, not 'tpu'")
    if "kind" in device and not (isinstance(device["kind"], str)
                                 and device["kind"]):
        bad.append("device.kind is not a name")
    if "count" in device and device["count"] != chips:
        bad.append(f"device.count is {device.get('count')!r}, "
                   f"the cell asks for {chips}")
    mem = device.get("memory_peak_bytes")
    if "memory_peak_bytes" in device and not (
            isinstance(mem, int) and not isinstance(mem, bool) and mem > 0):
        bad.append("device.memory_peak_bytes is not a positive byte count")
    if traced:
        for k in TRACE_DEVICE_KEYS:
            if not _number(device.get(k)):
                bad.append(f"device.{k} is missing from a traced run")
        if not bad:
            if not device["busy_s"] > 0:
                bad.append("device.busy_s is not above 0")
            if device["busy_s"] > device["window_s"]:
                bad.append("device.busy_s is more than device.window_s")
        bd = line.get("breakdown")
        if bd is not None:
            for k in ("device_ops", "idle_gaps"):
                rows = bd.get(k) if isinstance(bd, dict) else None
                if not isinstance(rows, list) or len(rows) > 10 or any(
                        not (isinstance(r, list) and len(r) == 2
                             and isinstance(r[0], str) and _number(r[1]))
                        for r in rows):
                    bad.append(f"breakdown.{k} is not a list of at most "
                               "10 [name, seconds]")
    elif "breakdown" in line:
        bad.append("breakdown belongs to a traced run")
    return bad


def render(line):
    """The line as printed: ``compared`` last, as the contract asks."""
    ordered = {k: line[k] for k in TOP_KEYS}
    for k in OPTIONAL_KEYS:
        if k in line:
            ordered[k] = line[k]
    return json.dumps(ordered)
