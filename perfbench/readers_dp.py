"""Readers for cells that run over several chips, named by a metric's
data file as ``readers_dp:<function>``.  Like ``readers.py``'s, each
takes the run's context and its parameters and returns a number, or
None where it finds nothing to read (a program without the exchange, a
run without a trace)."""
import math

import readers
import roofline_dp
import xplane


def _chips(ctx):
    return math.prod(ctx["config"]["params"].get("mesh_shape") or [1])


def roofline_share_chips(ctx, p):
    """``readers.roofline_share`` for work spread over the cell's chips:
    roofline.py's least time for all the rows is one chip's; the chips
    together have ``chips`` times its peaks, and the time taken (a
    per-chip mean from the trace, or the window) is every chip's."""
    one = readers.roofline_share(ctx, p)
    return None if one is None else one / _chips(ctx)


def exchange_ici_share(ctx, p):
    """100 * least seconds a chip needs on the interconnect for the
    histograms the trees' growth had to sum across chips
    (roofline_dp.py) / seconds a chip spent in the exchange
    (``over_metric``, in ms per tree)."""
    cfg = ctx["config"]
    other = ctx["value_of"](p["over_metric"])
    if other is None:
        return None
    least = roofline_dp.least_exchange_seconds(
        ctx["device_kind"], cfg["features"], cfg["params"]["max_bin"],
        ctx["trees"], _chips(ctx))
    return 100.0 * least / (other / 1e3 * ctx["n_trees"])


def chip_busy_skew(ctx, p):
    """100 * (max - min) / mean of the chips' busy seconds (union of a
    device plane's leaf events), over the device planes of the trace."""
    planes = ctx["trace_planes"]
    if planes is None:
        return None
    busy = []
    for name, lines in planes.items():
        if xplane.DEVICE_PLANE.match(name):
            lv = xplane.leaves(lines.get(xplane.OPS_LINE, []))
            busy.append(sum(hi - lo for lo, hi in xplane.merged(
                [e[1], e[1] + e[2]] for e in lv)) / 1e9)
    if len(busy) < 2 or not sum(busy):
        return None
    return 100.0 * (max(busy) - min(busy)) / (sum(busy) / len(busy))
