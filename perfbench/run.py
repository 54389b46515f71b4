"""One run of one cell of BENCHMARK.json.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, one traffic mix, one
per-layer metric or one cell's limits is a data file found by the name in
BENCHMARK.json (see README.md).  The last line of standard output is the
result, and it is printed only after ``lastline.problems`` finds nothing
wrong with it.
"""
import time
T_START = time.perf_counter()             # set-up counts from here

import argparse
import gc
import importlib
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [HERE, ROOT]

import lastline
import readers
import xplane

OUT_DIR = os.path.join(ROOT, ".perfbench_out")      # in .gitignore


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def resolve(table, name):
    """The function a data file names: one of ``table``'s, or
    ``module:function`` for one that a later PR brings in a new file of this
    directory, so that no file that is here needs an edit."""
    if name in table:
        return table[name]
    module, _, function = name.partition(":")
    if not function:
        raise SystemExit(f"{name!r} is none of {sorted(table)} and not "
                         "module:function")
    return getattr(importlib.import_module(module), function)


def load_cell(workload):
    """The cell with every data file it names, from BENCHMARK.json."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def mine(m):
        return workload in m.get("workloads", [workload])
    return {
        "cell": cell,
        "config": load_json(ROOT, cfg_entry["file"]),
        "traffic": load_json(HERE, "traffic", cell["traffic"] + ".json"),
        "limits": load_json(HERE, "limits", workload + ".json"),
        "end_to_end": [m for m in bench["end_to_end"] if mine(m)],
        "per_layer": [m for m in bench["per_layer"] if mine(m)],
    }


class Monitor:
    """jax.monitoring duration events with the time they ended (a copy of
    chip_smoke.py's CompileWatch that keeps each event)."""

    def __init__(self):
        import jax.monitoring
        self.events = []
        jax.monitoring.register_event_duration_secs_listener(self._dur)

    def _dur(self, event, secs, **kw):
        self.events.append((event, time.perf_counter(), float(secs)))


class Tracer:
    """Marks the window: where set-up ends it snapshots the program's
    counters and, in a traced run, starts the profiler; where the window
    closes it stops the profiler and reads the devices' memory peak,
    before anything else is put on them."""

    def __init__(self, jax, telemetry, on, directory, devices):
        self.jax, self.telemetry, self.on, self.dir = jax, telemetry, on, directory
        self.devices = devices
        self.counters_before = {}
        self.memory_peak_bytes = 0

    def start(self):
        self.counters_before = dict(self.telemetry.counters())
        gc.collect()
        gc.disable()          # as timeit does: no collection of the harness's
        if self.on:
            shutil.rmtree(self.dir, ignore_errors=True)
            os.makedirs(self.dir)
            self.jax.profiler.start_trace(self.dir)
        return time.perf_counter()

    def stop(self):
        gc.enable()           # own garbage inside the window
        if self.on:
            self.jax.profiler.stop_trace()
        self.memory_peak_bytes = int(max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in self.devices))


def require_chips(jax, chips):
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(f"this cell needs {chips} TPU chip(s); JAX found "
                         f"{len(devs)} x {devs[0].platform}")
    return devs[:chips]


def judge(numbers, limits):
    """[(name, value, limit, ok)] for every number that has a limit."""
    rows = []
    for name, limit in limits["limits"].items():
        value = numbers[name]
        value = value if isinstance(value, int) else float(value)  # no numpy repr
        rows.append((name, value, limit, bool(value <= limit)))
    return rows


def run_cell(loaded, seed, seconds, trace, devices, interpret=False,
             workload_dir="cell"):
    """Drives one run and returns (line, info): the result line as a dict,
    not yet checked, and what else is worth printing."""
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.telemetry import TELEMETRY

    import datagen
    import reference
    import traffic as traffic_kinds

    cfg, traffic = loaded["config"], loaded["traffic"]
    monitor = Monitor()
    clocks = {"startup": time.perf_counter() - T_START}   # imports, the chip

    def clocked(name, fn):
        t = time.perf_counter()
        out = fn()
        clocks[name] = time.perf_counter() - t
        return out

    # -- set-up: data from the seed, binning, upload, compile, warm chunk
    data = clocked("datagen", lambda: resolve(datagen.GENERATORS, cfg["generator"])(
        seed, cfg["rows"], cfg["features"]))
    params = dict(cfg["params"], telemetry="counters")
    if interpret:
        params["force_pallas_interpret"] = True
    ds = lgb.Dataset(data[0], label=data[1])
    clocked("prep", lambda: ds.construct(Config.from_params(params)))
    tracer = Tracer(jax, TELEMETRY, trace,
                    os.path.join(OUT_DIR, "trace_" + workload_dir), devices)
    t_kind = time.perf_counter()
    got = resolve(traffic_kinds.KINDS, traffic["kind"])(
        lgb, jax, traffic, params, ds, seconds, tracer)
    del ds                                # the program's state goes
    gc.collect()
    clocks["first_dispatch"] = got["t_setup_end"] - t_kind  # upload, compile, warm
    t0, t1 = got["t_window"]
    window_s = t1 - t0
    setup_s = got["t_setup_end"] - T_START
    counters = {k: v - tracer.counters_before.get(k, 0.0)
                for k, v in TELEMETRY.counters().items()}
    gauges = dict(TELEMETRY.gauges())
    for name, want in cfg.get("expect_gauges", {}).items():
        if not interpret and gauges.get(name) != want:
            raise SystemExit(f"the run took another path than the "
                             f"configuration states: gauge {name} is "
                             f"{gauges.get(name)!r}, not {want!r}")

    # -- correct: against the plain reference, after the peak was read
    compare = dict(loaded["limits"]["compare"])
    t_ref = time.perf_counter()
    numbers = resolve(reference.COMPARISONS, compare.pop("kind"))(
        got["answer"], data, cfg, seed,
        resolve(reference.OBJECTIVES, cfg["reference"]["objective"]), **compare)
    clocks["reference"] = time.perf_counter() - t_ref
    verdict = judge(numbers, loaded["limits"])
    control = judge(numbers["control"], loaded["limits"])
    correct = all(ok for *_, ok in verdict) and got["failed"] == 0

    # -- metrics
    kind = devices[0].device_kind
    ctx = dict(got["work"], **{
        "config": cfg, "device_kind": kind, "window_s": window_s,
        "counters": counters,
        "gauges": gauges, "clocks": clocks, "monitoring": monitor.events,
        "t_window": (t0, t1), "trace_planes": None, "trace_cache": {}})
    line_metrics = {}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": tracer.memory_peak_bytes}
    line = {"correct": correct, "attempted": got["attempted"],
            "failed": got["failed"], "metrics": line_metrics, "device": device}
    if trace:
        ctx["trace_planes"] = xplane.load(xplane.newest_xplane(tracer.dir))
        base = xplane.reduce(ctx["trace_planes"])       # raises if empty
        ctx["trace_cache"][()] = base
        device["busy_s"], device["window_s"] = base["busy_s"], window_s
        line["breakdown"] = {"device_ops": base["device_ops"],
                             "idle_gaps": base["idle_gaps"]}
        specs = {m["name"]: (m, load_json(HERE, "metrics", m["name"] + ".json"))
                 for m in loaded["per_layer"]}
        done = {}

        def value_of(name):
            if name not in done:
                _, f = specs[name]
                done[name] = resolve(readers.READERS, f["reader"])(
                    ctx, f.get("params", {}))
            return done[name]
        ctx["value_of"] = value_of
        for name, (m, _) in specs.items():
            v = value_of(name)
            if v is not None:
                line_metrics[name] = {"value": float(v), "unit": m["unit"]}
    else:
        e2e = dict(got["end_to_end"], setup_s=setup_s)
        for m in loaded["end_to_end"]:
            line_metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                       "unit": m["unit"]}
    line["compared"] = {name: {"value": value, "limit": limit}
                        for name, value, limit, _ in verdict}
    info = {"clocks": clocks, "setup_s": setup_s, "window_s": window_s,
            "gauges": {k: v for k, v in gauges.items() if k.startswith("grower.")},
            "counters": counters, "setup_counters": tracer.counters_before,
            "numbers": numbers, "verdict": verdict,
            "control_correct": all(ok for *_, ok in control)}
    return line, info


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    loaded = load_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # else libtpu logs to /tmp
    import jax
    devices = require_chips(jax, loaded["cell"]["chips"])
    line, info = run_cell(loaded, args.seed, args.seconds, bool(args.trace),
                          devices, workload_dir=args.workload)
    expected = {m["name"]: m["unit"] for m in
                (loaded["per_layer"] if args.trace else loaded["end_to_end"])}
    bad = lastline.problems(line, expected, bool(args.trace),
                            chips=loaded["cell"]["chips"])
    print(json.dumps({"info": info}, default=str), flush=True)
    if bad:
        print("the result line is not one the driver reads: "
              + "; ".join(bad), flush=True)
        return 1
    for name, value, limit, ok in info["verdict"]:
        print(f"compared {name} {value!r} limit {limit!r} "
              f"{'ok' if ok else 'OVER'}", file=sys.stderr, flush=True)
    print(lastline.render(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
