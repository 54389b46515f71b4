"""The readers a per-layer metric's data file may name.

A metric is ``perfbench/metrics/<name>.json``: ``{"reader": <one of
READERS>, "params": {...}}``.  A reader takes the run's context and its
parameters and returns a number, or None where it finds nothing to read;
the harness then leaves the metric out of the line.  A new metric over an
existing reader is a new data file and nothing else.
"""
import roofline
import xplane


def _per(ctx, value, per):
    if per == "tree":
        return value / ctx["n_trees"]
    if per is None:
        return value
    raise ValueError(f"unknown 'per': {per!r}")


def counter(ctx, p):
    """A telemetry counter's growth over the window (``counter``), or a
    gauge's last value (``gauge``)."""
    if "gauge" in p:
        v = ctx["gauges"].get(p["gauge"])
    else:
        v = ctx["counters"].get(p["counter"])
    if v is None:
        return None
    return _per(ctx, float(v), p.get("per")) * p.get("scale", 1.0)


def host_clock(ctx, p):
    """Seconds of one of the harness's own host spans."""
    v = ctx["clocks"].get(p["span"])
    return None if v is None else v * p.get("scale", 1.0)


def monitoring_events(ctx, p):
    """jax.monitoring duration events named ``event`` that ended during
    ``phase`` (setup | window): their ``stat`` (sum of seconds | count)."""
    t0, t1 = ctx["t_window"]
    inside = {"setup": lambda t: t < t0, "window": lambda t: t0 <= t <= t1}
    hits = [s for name, t, s in ctx["monitoring"]
            if name == p["event"] and inside[p["phase"]](t)]
    return float(sum(hits)) if p["stat"] == "sum" else float(len(hits))


def _trace(ctx, patterns=()):
    if ctx["trace_planes"] is None:
        return None
    key = tuple(patterns)
    if key not in ctx["trace_cache"]:
        ctx["trace_cache"][key] = xplane.reduce(ctx["trace_planes"], patterns)
    return ctx["trace_cache"][key]


def xplane_events_matching(ctx, p):
    """Device seconds of the leaf events whose name matches one of
    ``patterns`` (``of``: matched), or of all the others (unmatched)."""
    r = _trace(ctx, p["patterns"])
    if r is None:
        return None
    secs = r["matched_s"] if p.get("of", "matched") == "matched" \
        else r["busy_s"] - r["matched_s"]
    if p.get("of", "matched") == "matched" and secs == 0.0:
        return None                      # no such kernel ran: nothing to read
    return _per(ctx, secs, p.get("per")) * p.get("scale", 1.0)


def device_idle(ctx, p):
    """100 * (1 - busy / window) of the traced window."""
    r = _trace(ctx)
    if r is None:
        return None
    return 100.0 * (1.0 - r["busy_s"] / ctx["window_s"])


def roofline_share(ctx, p):
    """100 * least seconds for ``work`` / seconds taken, where the time
    taken is another metric's value (``over_metric``, in ms per tree) or
    the window's wall clock (``over``: window)."""
    cfg = ctx["config"]
    least, _bound = roofline.least_seconds(
        ctx["device_kind"], cfg["rows"], cfg["features"],
        cfg["params"]["max_bin"], cfg["reference"]["grad_bytes"],
        ctx["trees"], p["work"])
    if "over_metric" in p:
        other = ctx["value_of"](p["over_metric"])
        if other is None:
            return None
        taken = other / 1e3 * ctx["n_trees"]
    else:
        taken = ctx["window_s"]
    return 100.0 * least / taken


READERS = {
    "counter": counter,
    "host_clock": host_clock,
    "monitoring_events": monitoring_events,
    "xplane_events_matching": xplane_events_matching,
    "device_idle": device_idle,
    "roofline": roofline_share,
}
