"""Profile a training chunk on top of the runtime telemetry subsystem.

Round 9 rewrite: this used to be a standalone one-off with private
timers; it now drives the SAME instrumentation a production run uses
(``telemetry=trace`` — docs/OBSERVABILITY.md):

1. trains a warm-up + a measured chunk under telemetry trace mode
   (host spans, device fence, named-scope phase annotation),
2. exports the telemetry Perfetto file + newline-JSON events
   (load the ``.perfetto.json`` in ui.perfetto.dev),
3. prints the counter snapshot (host-dispatch vs device-wait per
   tree — the ROOFLINE headroom #3 split), and
4. when a jax profiler xplane is available, aggregates device-op time
   by telemetry phase (the ``tel.histogram`` / ``tel.split_finder`` /
   ... named scopes the trace mode stamps into the HLO metadata) plus
   the top ops, as before.

Usage: python scripts/profile_train.py [rows] [iters] [out_prefix]
  out_prefix default: /tmp/lgbtpu_profile/telemetry
  env: BENCH_PARAMS='{...}' param overrides (as in bench.py)
"""
import glob
import os
import sys
from collections import defaultdict

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.abspath(__file__)), ".."))

import numpy as np


def device_op_table(tdir):
    """Aggregate device-plane op durations from the newest xplane in
    ``tdir``, grouped by telemetry phase (named-scope prefix ``tel.``)
    and by op name.  Returns (phase_ms, op_ms, op_calls, total_ms) or
    None when no device plane exists (CPU seam without an xplane)."""
    import jax

    pbs = sorted(glob.glob(os.path.join(
        tdir, "**", "*.xplane.pb"), recursive=True))
    if not pbs:
        return None
    data = jax.profiler.ProfileData.from_file(pbs[-1])
    phase = defaultdict(float)
    agg = defaultdict(float)
    cnt = defaultdict(int)
    total = 0.0
    for plane in data.planes:
        if "TPU" not in plane.name and "/device" not in plane.name:
            continue
        for line in plane.lines:
            if "Ops" not in line.name:
                continue
            for ev in line.events:
                dur = ev.duration_ns / 1e6
                agg[ev.name] += dur
                cnt[ev.name] += 1
                total += dur
                # telemetry trace mode stamps jax.named_scope("tel.X")
                # into op metadata; xplane op names carry the scope
                # path, so a substring match attributes the op
                name = ev.name
                tag = "(unattributed)"
                if "tel." in name:
                    # scope path "…/tel.<phase>/…" -> "tel.<phase>"
                    tag = "tel." + name.split("tel.", 1)[1].split(
                        "/", 1)[0]
                phase[tag] += dur
    if not agg:
        return None
    return phase, agg, cnt, total


def main():
    rows = int(sys.argv[1]) if len(sys.argv) > 1 else 1_000_000
    iters = int(sys.argv[2]) if len(sys.argv) > 2 else 20
    out = (sys.argv[3] if len(sys.argv) > 3
           else "/tmp/lgbtpu_profile/telemetry")
    os.environ.setdefault("BENCH_ROWS", str(rows))
    import jax

    import bench
    import lightgbm_tpu as lgb
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.telemetry import TELEMETRY

    # trace mode BEFORE the first compile: the named-scope phase
    # annotation is stamped at trace time
    TELEMETRY.configure("trace", out=out)

    X, y, w = bench.make_data(rows, bench.BENCH_FEATURES)
    params = {
        "objective": "binary", "num_leaves": 255, "max_bin": 63,
        "learning_rate": 0.1, "verbose": -1, "min_data_in_leaf": 1,
        "min_sum_hessian_in_leaf": 100.0,
        "hist_compute_dtype": "bfloat16", "quantized_grad": True,
    }
    extra = os.environ.get("BENCH_PARAMS")
    if extra:
        import json
        params.update(json.loads(extra))
    cfg = Config.from_params(params)
    core = lgb.Dataset(X, label=y).construct(cfg)
    g = GBDT(cfg, core)
    span = TELEMETRY.start_span("profile_warm")
    g.train_chunk(iters)          # compile + warm
    np.asarray(g.scores[:, :8])
    TELEMETRY.end_span(span)

    tdir = "/tmp/lgbtpu_profile"
    import shutil
    shutil.rmtree(os.path.join(tdir, "plugins"), ignore_errors=True)
    span = TELEMETRY.start_span("profile_measure")
    try:
        with jax.profiler.trace(tdir):
            g.train_chunk(iters)
            np.asarray(g.scores[:, :8])
        profiled = True
    except Exception as e:  # profiler availability is env-dependent
        print(f"jax profiler unavailable ({type(e).__name__}: {e}); "
              "telemetry-only run", file=sys.stderr)
        g.train_chunk(iters)
        np.asarray(g.scores[:, :8])
        profiled = False
    TELEMETRY.end_span(span)

    snap = TELEMETRY.snapshot()
    paths = TELEMETRY.export(out)
    print(f"telemetry: {paths[0]}")
    print(f"perfetto:  {paths[1]}  (load in ui.perfetto.dev)")
    d = snap.get("derived", {})
    print(f"\n== host wall over {2 * iters} trees "
          f"({rows // 1000}k rows) ==")
    print(f"host_dispatch {d.get('host_dispatch_ms_per_tree', 0):.3f} "
          f"ms/tree, device_wait "
          f"{d.get('device_wait_ms_per_tree', 0):.3f} ms/tree")
    for k in sorted(snap["counters"]):
        if k.startswith("phase_"):
            print(f"  {k} = {snap['counters'][k]:.1f}")

    table = device_op_table(tdir) if profiled else None
    if table is None:
        print("\n(no device xplane — per-op attribution needs a chip "
              "or a profiler-enabled backend; telemetry spans above "
              "are the host-side record)")
        return
    phase, agg, cnt, total = table
    print(f"\n== device time by telemetry phase ==")
    for tag, ms in sorted(phase.items(), key=lambda kv: -kv[1]):
        print(f"{ms / iters:9.3f} ms/tree {100 * ms / total:5.1f}%  "
              f"{tag}")
    print(f"\n== device op time over {iters} trees ==")
    print(f"{'ms/tree':>9} {'pct':>6} {'calls':>7}  op")
    for name, ms in sorted(agg.items(), key=lambda kv: -kv[1])[:25]:
        print(f"{ms / iters:9.3f} {100 * ms / total:5.1f}% "
              f"{cnt[name]:7d}  {name[:90]}")
    print(f"{total / iters:9.3f} 100.0%          TOTAL device")


if __name__ == "__main__":
    main()
