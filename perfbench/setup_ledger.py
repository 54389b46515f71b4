"""Set-up, read from the program's own counters.

Set-up lies before the profiler starts, so no device trace covers it: what
the program says of it are the ``setup_<stage>_ms`` counters of
``TELEMETRY.stage`` (docs/OBSERVABILITY.md), which tile
``Dataset.construct`` and the first ``lgb.train``.  The readers here are
named by data files as ``setup_ledger:<function>``.

A counter is read *before the window*: the process's total less what the
window added (``ctx["counters"]``), so a chunk program built inside the
window is a stall there and not set-up here.  A counter the program never
raised reads 0.0, as a scrape of it would, and never None: a program
older than a stage still prints a line.
"""


def _before_window(ctx):
    from lightgbm_tpu.telemetry import TELEMETRY
    window = ctx["counters"]
    return {name: total - window.get(name, 0.0)
            for name, total in TELEMETRY.counters().items()}


def stage_seconds(ctx, p):
    """Seconds of the named counters (milliseconds each) before the
    window, summed."""
    before = _before_window(ctx)
    return sum(before.get(name, 0.0) for name in p["counters"]) / 1e3


def unattributed(ctx, p):
    """Seconds of the harness's clocks around ``Dataset.construct``
    (``prep``) and the first ``lgb.train`` (``first_dispatch``) that no
    stage under the two entry points took.  Every ``setup_*_ms`` counter
    is a stage's own time and is subtracted, but for those the data file
    lists: the entry points' own (``own``: what they could not hand to a
    stage is the remainder itself), the stages outside the two calls
    (``outside``) and the counters that are a part of other stages' time
    (``within``)."""
    left_in = set(p["own"]) | set(p["outside"]) | set(p.get("within", ()))
    staged_ms = sum(ms for name, ms in _before_window(ctx).items()
                    if name.startswith("setup_") and name.endswith("_ms")
                    and name not in left_in)
    clocks = ctx["clocks"]
    return clocks["prep"] + clocks["first_dispatch"] - staged_ms / 1e3
