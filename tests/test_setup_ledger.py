"""Set-up accounted for from inside the program (PR 37).

The program's side: the ``setup_<stage>_ms`` counters of
``TELEMETRY.stage`` tile ``Dataset.construct`` and the first
``lgb.train`` (two entry-point stages whose own time is the unattributed
remainder), the chunk program's build is split into trace / lower /
compile from jax.monitoring's durations, the package's import is clocked
whatever the mode and published by the first ``configure``.

The benchmark's side: ``perfbench/setup_ledger.py``'s two readers over a
made-up ``ctx``, and the data files that name them.
"""
import json
import os
import sys
import threading
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry
from lightgbm_tpu.config import Config
from lightgbm_tpu.telemetry import TELEMETRY, Telemetry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PERFBENCH = os.path.join(ROOT, "perfbench")
if PERFBENCH not in sys.path:
    sys.path.insert(0, PERFBENCH)

import setup_ledger  # noqa: E402  (perfbench/, a directory of scripts)

ENTRY_POINTS = ("construct", "train")
# counters named setup_*_ms that are no stage under the two calls
NOT_UNDER_THE_CALLS = ("setup_import_ms", "setup_import_sklearn_ms",
                       "setup_fence_ms")
NEW_METRICS = ("import_s", "sample_s", "chunk_trace_s", "chunk_lower_s",
               "first_chunk_wait_s", "setup_fence_s", "setup_unattributed_s",
               "upload_s", "grower_init_s", "chunk_program_build_s",
               "fit_mappers_s", "bin_s", "booster_init_s",
               "chunk_compile_s")


@pytest.fixture(autouse=True)
def _clean_telemetry():
    TELEMETRY.configure("off")
    TELEMETRY.reset()
    yield
    TELEMETRY.configure("off")
    TELEMETRY.reset()


def _table(rows=20000, features=10, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(rows, features).astype(np.float32)
    return X, (X[:, 0] * 1.5 - X[:, 1] > 0).astype(float)


PARAMS = {"objective": "binary", "num_leaves": 7, "verbose": -1,
          "min_data_in_leaf": 5, "dispatch_chunk": 2,
          "telemetry": "counters"}


@pytest.fixture(scope="module")
def job():
    """One small construct + train at telemetry=counters: the counters
    it left and the wall of the two calls."""
    TELEMETRY.configure("off")
    TELEMETRY.reset()
    X, y = _table()
    ds = lgb.Dataset(X, label=y)
    cfg = Config.from_params(PARAMS)           # counters on from here
    t0 = time.perf_counter()
    ds.construct(cfg)
    t1 = time.perf_counter()
    lgb.train(PARAMS, ds, 2, verbose_eval=False)
    t2 = time.perf_counter()
    out = {"counters": TELEMETRY.counters(), "gauges": TELEMETRY.gauges(),
           "construct_ms": (t1 - t0) * 1e3, "train_ms": (t2 - t1) * 1e3}
    TELEMETRY.configure("off")
    return out


def _stages_under(counters, construct):
    """Own time of the stages under one of the two calls, the call's
    own among them.  Which is which: the construct side's are closed
    when the call returns, so they are what the job had by then."""
    names = ("construct", "binning", "sample", "fit_mappers", "bin", "pack")
    side = {k: v for k, v in counters.items()
            if k.startswith("setup_") and k.endswith("_ms")
            and k not in NOT_UNDER_THE_CALLS
            and (k[6:-3] in names) == construct}
    return side


@pytest.mark.parametrize("call", ENTRY_POINTS)
def test_stage_counters_add_up_to_the_calls_wall(job, call):
    side = _stages_under(job["counters"], call == "construct")
    wall = job[f"{call}_ms"]
    assert sum(side.values()) == pytest.approx(wall, rel=0.02), side


@pytest.mark.parametrize("call", ENTRY_POINTS)
def test_entry_points_own_time_is_small(job, call):
    own = job["counters"][f"setup_{call}_ms"]
    assert 0 <= own < 0.05 * job[f"{call}_ms"], job["counters"]


@pytest.mark.parametrize("stage", [
    "construct", "binning", "sample", "fit_mappers", "bin", "pack",
    "train", "booster_init", "import_boosting", "upload", "grower_init", "chunk_build",
    "chunk_trace", "chunk_lower", "chunk_compile", "hist_pool",
    "first_chunk_wait"])
def test_job_leaves_every_stage(job, stage):
    assert job["counters"][f"setup_{stage}_ms"] >= 0


def test_chunk_build_parts_stay_inside_it(job):
    c = job["counters"]
    parts = sum(c[f"setup_chunk_{k}_ms"]
                for k in ("trace", "lower", "compile"))
    assert parts > 0
    # the stage runs from train_chunk's entry to the dispatch's return,
    # the window chunk_program_build_ms times
    wall = parts + c["setup_chunk_build_ms"] + c["setup_hist_pool_ms"]
    assert parts <= wall <= c["chunk_program_build_ms"] * 1.001 + 1.0
    assert wall == pytest.approx(c["chunk_program_build_ms"], rel=0.02)


def test_fence_time_is_part_of_the_stages(job):
    c = job["counters"]
    assert 0 <= c["setup_fence_ms"] <= c["setup_upload_ms"] + 1.0


def test_construct_gauge_comes_from_the_stage(job):
    rate = job["gauges"]["construct_rows_per_s"]
    assert rate == pytest.approx(20000 / job["construct_ms"] * 1e3, rel=0.05)


def test_nothing_is_recorded_at_off():
    X, y = _table(rows=500, features=4)
    lgb.train({"objective": "binary", "num_leaves": 4, "verbose": -1,
               "min_data_in_leaf": 5, "dispatch_chunk": 2},
              lgb.Dataset(X, label=y), 2, verbose_eval=False)
    assert TELEMETRY.counters() == {}
    assert TELEMETRY.gauges() == {}
    assert TELEMETRY.current_stage() is None


def test_import_is_published_once_by_the_first_configure():
    assert telemetry._IMPORT["ms"] > 0
    tm = Telemetry()
    tm.configure("off")
    assert tm.counters() == {}
    tm.configure("counters")
    first = tm.counters()
    total = first["setup_import_ms"] + first["setup_import_sklearn_ms"]
    assert total == pytest.approx(telemetry._IMPORT["ms"])
    assert first["setup_import_ms"] >= 0
    assert tm.gauges()["rss_mb_after_import"] \
        >= tm.gauges()["rss_mb_before_import"] > 0
    tm.configure("spans")
    tm.configure("counters")
    assert tm.counters() == first


def _nested_jits_program():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def inner(x):
        return jnp.sin(x) * 2.0

    @jax.jit
    def middle(x):
        return inner(x) + inner(x + 1.0)

    def outer(x):
        return middle(x).sum() + inner(x).sum()
    return jax.jit(outer), jnp.arange(8.0)


def test_nested_jits_are_counted_once():
    """jax reports the trace of every jit nested in a program, each
    before the one that holds it: the parts of a build never add up to
    more than its wall."""
    telemetry.watch_compile_cache()
    TELEMETRY.configure("counters")
    fn, x = _nested_jits_program()
    with TELEMETRY.stage("demo_build", compiles="demo") as stage:
        fn(x).block_until_ready()
    c = TELEMETRY.counters()
    parts = sum(c[f"setup_demo_{k}_ms"] for k in ("trace", "lower", "compile"))
    assert c["setup_demo_trace_ms"] > 0 and c["setup_demo_compile_ms"] > 0
    assert parts <= stage.wall_ms
    assert parts + c["setup_demo_build_ms"] == pytest.approx(stage.wall_ms)
    # outside a stage that asks for them the durations go nowhere
    fn2, x = _nested_jits_program()
    fn2(x).block_until_ready()
    assert TELEMETRY.counters()["setup_demo_trace_ms"] \
        == c["setup_demo_trace_ms"]


def test_a_duration_inside_one_already_booked_is_not_added_again():
    TELEMETRY.configure("counters")
    with TELEMETRY.stage("demo_build", compiles="demo") as stage:
        time.sleep(0.02)
        stage.split.told("trace", 0.01)        # an inner jit's trace
        time.sleep(0.01)
        stage.split.told("trace", 0.025)       # the outer one, holding it
        stage.split.told("compile", 10.0)      # longer than the stage
    c = TELEMETRY.counters()
    assert c["setup_demo_trace_ms"] == pytest.approx(25.0, abs=3.0)
    parts = c["setup_demo_trace_ms"] + c["setup_demo_compile_ms"]
    assert parts <= stage.wall_ms + 1e-6
    assert c["setup_demo_build_ms"] >= 0


@pytest.mark.parametrize("workers", [1, 4])
def test_stages_on_worker_threads_hand_their_time_to_the_waiting_stage(
        workers):
    TELEMETRY.configure("counters")

    def block(parent):
        with TELEMETRY.stage_of(parent):
            with TELEMETRY.stage("bin"):
                time.sleep(0.03)

    with TELEMETRY.stage("binning") as parent:
        threads = [threading.Thread(target=block, args=(parent,))
                   for _ in range(workers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive()
    c = TELEMETRY.counters()
    assert c["setup_bin_ms"] >= 30.0 * workers * 0.9
    if workers == 1:
        # counted once: the waiting stage's own time is not the wait
        assert 0 <= c["setup_binning_ms"] < 15.0
    else:
        # side by side they hand it more than its wall: floored at 0
        assert c["setup_binning_ms"] == 0.0
    assert TELEMETRY.current_stage() is None


def test_deadline_worker_runs_under_the_callers_stage():
    from lightgbm_tpu.reliability.watchdog import run_with_deadline
    TELEMETRY.configure("counters")

    def ingest():
        with TELEMETRY.stage("bin"):
            time.sleep(0.03)
        return 7

    with TELEMETRY.stage("binning"):
        assert run_with_deadline(ingest, 30.0, "shard_ingest") == 7
    c = TELEMETRY.counters()
    assert c["setup_bin_ms"] >= 27.0
    assert 0 <= c["setup_binning_ms"] < 15.0


def test_since_counts_a_stage_from_an_earlier_reading():
    TELEMETRY.configure("counters")
    t0 = time.perf_counter()
    time.sleep(0.02)
    with TELEMETRY.stage("outer_stage"):
        pass
    with TELEMETRY.stage("late_stage", since=t0) as stage:
        pass
    assert stage.wall_ms >= 20.0
    assert TELEMETRY.counters()["setup_late_stage_ms"] >= 20.0


# ---------------------------------------------------------------------------
# the readers, on a made-up ctx
# ---------------------------------------------------------------------------
def _ctx(totals, window=None, prep=10.0, first_dispatch=15.0):
    """The process's counters are ``totals``; the window added ``window``."""
    TELEMETRY.configure("counters")
    TELEMETRY.reset()
    for name, value in totals.items():
        TELEMETRY.add(name, value)
    return {"counters": dict(window or {}),
            "clocks": {"prep": prep, "first_dispatch": first_dispatch}}


def _params(name):
    with open(os.path.join(PERFBENCH, "metrics", name + ".json")) as f:
        return json.load(f)["params"]


def test_reader_reads_an_absent_counter_as_zero():
    ctx = _ctx({"setup_upload_ms": 1500.0})
    got = setup_ledger.stage_seconds(ctx, {"counters": ["setup_sample_ms"]})
    assert got == 0.0 and isinstance(got, float)
    assert setup_ledger.stage_seconds(ctx, _params("upload_s")) == 1.5


def test_reader_leaves_out_what_the_window_added():
    ctx = _ctx({"chunk_program_build_ms": 9000.0, "host_dispatch_ms": 9100.0},
               window={"chunk_program_build_ms": 2000.0,
                       "host_dispatch_ms": 2100.0})
    assert setup_ledger.stage_seconds(
        ctx, _params("chunk_program_build_s")) == 7.0


def test_unattributed_of_a_parent_like_program_is_positive():
    # PR 36's counters: no entry points, no sample, no build split
    ctx = _ctx({"setup_binning_ms": 1400.0, "setup_fit_mappers_ms": 900.0,
                "setup_bin_ms": 3300.0, "setup_pack_ms": 1.0,
                "setup_upload_ms": 1100.0, "setup_binsT_ms": 800.0,
                "setup_grower_init_ms": 40.0,
                "chunk_program_build_ms": 6800.0, "host_dispatch_ms": 6900.0},
               prep=5.98, first_dispatch=14.7)
    got = setup_ledger.unattributed(ctx, _params("setup_unattributed_s"))
    assert got == pytest.approx(5.98 + 14.7 - 7.541)
    for name in NEW_METRICS:
        p = _params(name)
        if "counters" in p:
            assert setup_ledger.stage_seconds(ctx, p) >= 0.0


def test_unattributed_of_a_tiled_program_is_the_entry_points_own():
    ctx = _ctx({"setup_import_ms": 400.0, "setup_import_sklearn_ms": 3300.0,
                "setup_construct_ms": 20.0, "setup_binning_ms": 80.0,
                "setup_sample_ms": 1400.0, "setup_fit_mappers_ms": 900.0,
                "setup_bin_ms": 3300.0, "setup_train_ms": 150.0,
                "setup_booster_init_ms": 500.0, "setup_upload_ms": 1100.0,
                "setup_fence_ms": 1000.0, "setup_chunk_build_ms": 300.0,
                "setup_chunk_trace_ms": 4000.0,
                "setup_first_chunk_wait_ms": 2700.0,
                # a stage that ran again inside the window is not set-up
                "setup_hist_pool_ms": 30.0},
               window={"setup_hist_pool_ms": 10.0},
               prep=5.7, first_dispatch=8.77)
    got = setup_ledger.unattributed(ctx, _params("setup_unattributed_s"))
    # the calls' walls less the stages' own: construct's and train's own
    assert got == pytest.approx(0.02 + 0.15)


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_has_its_file_and_a_reader_that_resolves(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = {m["name"]: m for m in bench["per_layer"]}[name]
    assert entry["unit"] == "s" and entry["better"] == "lower"
    assert entry["source"] == "program_counter"
    assert entry["moves"] == "setup_s"
    assert entry["workloads"] == [w["name"] for w in bench["workloads"]]
    with open(os.path.join(PERFBENCH, "metrics", name + ".json")) as f:
        spec = json.load(f)
    module, _, function = spec["reader"].partition(":")
    assert module == "setup_ledger"
    reader = getattr(setup_ledger, function)
    value = reader(_ctx({}), spec["params"])
    assert isinstance(value, float)
