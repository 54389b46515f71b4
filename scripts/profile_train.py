"""Profile a training chunk on top of the runtime telemetry subsystem.

Drives the SAME instrumentation a production run uses
(``telemetry=spans`` — docs/OBSERVABILITY.md):

1. trains a warm-up + a measured chunk under telemetry spans mode
   (host spans, device fence; the ``tel.<phase>`` scopes are on at
   every mode),
2. exports the telemetry Perfetto file + newline-JSON events
   (load the ``.perfetto.json`` in ui.perfetto.dev),
3. prints the counter snapshot (host-dispatch vs device-wait per
   tree — the ROOFLINE headroom #3 split), and
4. takes a jax profiler trace of the measured chunk and reduces it
   with the benchmark's own readers: ``perfbench/xplane.py`` (busy
   time, top ops, idle gaps) and ``perfbench/program_trace.py``
   (device time per ``tel.<phase>``) — written against real traces;
   this script no longer keeps a reduction of its own.

Usage: python scripts/profile_train.py [rows] [iters] [out_prefix]
  out_prefix default: /tmp/lgbtpu_profile/telemetry
  env: BENCH_PARAMS='{...}' param overrides (as in bench.py)
"""
import os
import sys

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path[:0] = [REPO, os.path.join(REPO, "perfbench")]

import numpy as np


def device_report(tdir, iters):
    """Device ms/tree by phase and the top ops of the newest xplane
    under ``tdir``; nothing where the backend left no device plane
    (the CPU seam)."""
    import program_trace
    import xplane
    try:
        pb = xplane.newest_xplane(tdir)
        planes = xplane.load(pb)
        base = xplane.reduce(planes)
    except xplane.NoDeviceTrace as e:
        print(f"\n(no device trace: {e}; the spans above are the "
              "host-side record)")
        return
    phases = program_trace.phase_seconds(
        planes, program_trace.book(program_trace.hlo_instructions(pb)))
    print(f"\n== device, {pb} ==")
    print(f"busy {1e3 * base['busy_s'] / iters:.3f} ms/tree")
    if phases is not None:
        rows = dict(phases["phases"], kernel=phases["kernel"],
                    unscoped=phases["unscoped"])
        for tag, sec in sorted(rows.items(), key=lambda kv: -kv[1]):
            print(f"{1e3 * sec / iters:9.3f} ms/tree  {tag}")
    for name, sec in base["device_ops"]:
        print(f"{1e3 * sec / iters:9.3f} ms/tree  op {name}")


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

    TELEMETRY.configure("spans", out=out)

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

    if profiled:
        device_report(tdir, iters)


if __name__ == "__main__":
    main()
