"""Telemetry subsystem tests (round-9 tentpole).

Covers the hard requirements from the issue:
- span nesting/reentrancy and thread safety of the global registry,
- counters EXACT under the interpret seam (trees dispatched ==
  num_iterations; serving bucket hit/miss against the
  test_predict_cache compile-count ground truth),
- schema-valid Perfetto + newline-JSON export,
- the ``telemetry=off`` HLO-identity pin: enabling counters/spans
  changes NO lowered program (same compiler-seam style as
  tests/test_carry_hlo.py), and trace mode — which adds named-scope
  METADATA only — still trains byte-identical trees,
- the retrace sentinel (runtime promotion of the compile-count lint),
- config.verbosity -> Log level wiring in engine.train and cli.run,
- the host/device wall split accounting for the measured wall (the
  bench-vs-runtime equivalence the bench consumes).
"""
import json
import threading
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.telemetry import TELEMETRY
from lightgbm_tpu.utils.log import Log


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Every test starts and ends at telemetry=off with empty state,
    and the process-global Log level is restored (engine.train now
    routes config.verbosity into it)."""
    level = Log.level
    TELEMETRY.configure("off")
    TELEMETRY.set_fence(False)
    TELEMETRY.reset()
    yield
    TELEMETRY.configure("off")
    TELEMETRY.set_fence(False)
    TELEMETRY.reset()
    Log.set_level(level)


def _train(n=300, iters=8, seed=0, f=6, callbacks=None, **params):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = X[:, 0] - 0.4 * X[:, 1]
    p = {"objective": "regression", "verbose": -1, "num_leaves": 7,
         "min_data_in_leaf": 5, **params}
    return lgb.train(p, lgb.Dataset(X, label=y), iters,
                     verbose_eval=False, callbacks=callbacks), X


# ---------------------------------------------------------------------------
# core: spans
# ---------------------------------------------------------------------------
def test_span_nesting_and_reentrancy():
    TELEMETRY.configure("spans")
    with TELEMETRY.span("outer"):
        time.sleep(0.002)
        with TELEMETRY.span("inner", k=1):
            time.sleep(0.002)
            with TELEMETRY.span("inner"):     # same-name reentrancy
                pass
    events = TELEMETRY.events_snapshot()
    by_depth = {}
    for name, ts, dur, tid, depth, attrs in events:
        by_depth.setdefault(name, []).append((depth, dur))
    assert by_depth["outer"][0][0] == 0
    assert [d for d, _ in by_depth["inner"]] == [2, 1]  # inner exits first
    outer_dur = by_depth["outer"][0][1]
    assert all(dur <= outer_dur for _, dur in by_depth["inner"])
    # a span recorded after the stack unwound starts at depth 0 again
    with TELEMETRY.span("outer"):
        pass
    assert TELEMETRY.events_snapshot()[-1][4] == 0


def test_span_stack_survives_exceptions():
    TELEMETRY.configure("spans")
    with pytest.raises(RuntimeError):
        with TELEMETRY.span("outer"):
            raise RuntimeError("boom")
    with TELEMETRY.span("after"):
        pass
    assert TELEMETRY.events_snapshot()[-1][4] == 0


def test_thread_safety():
    TELEMETRY.configure("spans")
    n_threads, per_thread = 8, 150
    errors = []

    def work(i):
        try:
            for j in range(per_thread):
                with TELEMETRY.span("t_outer"):
                    with TELEMETRY.span("t_inner"):
                        TELEMETRY.add("t_counter")
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=work, args=(i,))
               for i in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert TELEMETRY.counters()["t_counter"] == n_threads * per_thread
    events = TELEMETRY.events_snapshot()
    assert len(events) == 2 * n_threads * per_thread
    # nesting is per-thread: every inner span sits at depth 1, every
    # outer at 0 — interleaving across threads must not corrupt it
    for name, ts, dur, tid, depth, attrs in events:
        assert depth == (1 if name == "t_inner" else 0), (name, depth)


# ---------------------------------------------------------------------------
# counters exact under the interpret seam
# ---------------------------------------------------------------------------
def test_counters_exact_over_training():
    TELEMETRY.configure("counters")
    iters = 13          # chunked 10 + 3 per-iteration tail
    _train(iters=iters)
    c = TELEMETRY.counters()
    assert c["trees_dispatched"] == iters
    assert c["iterations"] == iters
    assert c["trees_flushed"] == iters
    assert c["chunks_dispatched"] >= 1
    assert c["host_dispatch_ms"] > 0
    # counters mode never fences: no device_wait attribution
    assert "device_wait_ms" not in c
    snap = TELEMETRY.snapshot()
    assert snap["derived"]["host_dispatch_ms_per_tree"] > 0
    assert snap["gauges"]["rss_mb_peak"] > 0
    assert "gbdt.fused_chunk" in snap["retraces"]


def test_config_param_enables_telemetry():
    """The telemetry knob rides the normal params dict."""
    _train(iters=3, telemetry="counters")
    assert TELEMETRY.on
    assert TELEMETRY.counters()["trees_dispatched"] == 3


def test_serving_bucket_hit_miss_counters():
    """Ground truth from test_predict_cache: 5 batch sizes inside one
    16-row bucket = ONE compile -> 1 miss + 4 hits; the next bucket
    is one more miss; returning inside is a hit.  Pad-row accounting
    must equal the bucket rounding exactly."""
    bst, X = _train(n=220, iters=5, seed=3, f=9, num_leaves=13)
    TELEMETRY.configure("counters")
    TELEMETRY.reset()
    sizes = (3, 5, 9, 13, 16)
    for n in sizes:
        bst.predict(X[:n], device=True)
    c = TELEMETRY.counters()
    assert c["predict_bucket_miss"] == 1, c
    assert c["predict_bucket_hit"] == 4, c
    assert c["predict_rows"] == sum(sizes)
    assert c["predict_pad_rows"] == sum(16 - n for n in sizes)
    bst.predict(X[:17], device=True)      # next bucket: one more miss
    bst.predict(X[:13], device=True)      # back inside: hit
    c = TELEMETRY.counters()
    assert c["predict_bucket_miss"] == 2
    assert c["predict_bucket_hit"] == 5
    assert c["predict_requests"] == 7
    waste = TELEMETRY.snapshot()["derived"]["predict_tail_waste"]
    assert 0 < waste < 1


def test_telemetry_snapshot_callback():
    dest = {}
    TELEMETRY.configure("counters")
    _train(iters=4, callbacks=[lgb.telemetry_snapshot(dest)])
    assert dest["iterations"] == [1, 2, 3, 4]
    trees = [s["counters"]["trees_dispatched"] for s in dest["snapshots"]]
    assert trees == [1, 2, 3, 4]   # per-iteration path: one tree each


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------
def test_export_perfetto_and_jsonl(tmp_path):
    TELEMETRY.configure("spans")
    with TELEMETRY.span("alpha", rows=7):
        with TELEMETRY.span("beta"):
            pass
    TELEMETRY.add("some_counter", 3)
    TELEMETRY.gauge("some_gauge", 1.5)
    TELEMETRY.gauge("str_gauge", "xla")
    jsonl, perfetto = TELEMETRY.export(str(tmp_path / "run"))

    with open(perfetto) as f:
        trace = json.load(f)            # schema-valid JSON
    evs = trace["traceEvents"]
    assert isinstance(evs, list) and evs
    for ev in evs:
        assert "ph" in ev and "name" in ev and "pid" in ev
        if ev["ph"] == "X":
            assert isinstance(ev["ts"], (int, float))
            assert isinstance(ev["dur"], (int, float))
    xnames = {e["name"] for e in evs if e["ph"] == "X"}
    assert {"alpha", "beta"} <= xnames
    cnames = {e["name"] for e in evs if e["ph"] == "C"}
    assert "some_counter" in cnames and "some_gauge" in cnames
    args = next(e for e in evs if e["name"] == "alpha")["args"]
    assert args["rows"] == 7

    lines = [json.loads(ln) for ln in open(jsonl)]
    assert lines[-1]["type"] == "snapshot"
    assert lines[-1]["counters"]["some_counter"] == 3
    spans = [ln for ln in lines if ln["type"] == "span"]
    assert {s["name"] for s in spans} == {"alpha", "beta"}
    beta = next(s for s in spans if s["name"] == "beta")
    assert beta["depth"] == 1


def test_training_run_exports_loadable_trace(tmp_path):
    """The acceptance-criteria path: a telemetry=trace training run +
    a serving predict emit a Perfetto-loadable trace and a JSON
    counter dump carrying the per-tree host/device split."""
    TELEMETRY.configure("trace")
    bst, X = _train(iters=12, seed=5)
    bst.predict(X[:4], device=True)
    jsonl, perfetto = TELEMETRY.export(str(tmp_path / "train"))
    trace = json.load(open(perfetto))
    names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert {"train", "train_chunk", "host_dispatch", "device_wait",
            "predict", "predict_dispatch"} <= names
    snap = json.loads(open(jsonl).read().splitlines()[-1])
    d = snap["derived"]
    assert d["host_dispatch_ms_per_tree"] > 0
    assert d["device_wait_ms_per_tree"] >= 0
    assert snap["counters"]["trees_dispatched"] == 12


# ---------------------------------------------------------------------------
# the off-mode identity pin (the issue's hard requirement)
# ---------------------------------------------------------------------------
def _lowered_chunk_text(chunk=4):
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config
    import jax.numpy as jnp

    rng = np.random.RandomState(7)
    X = rng.randn(512, 6)
    y = (X[:, 0] + 0.4 * X[:, 1] > 0).astype(float)
    cfg = Config.from_params({"objective": "binary", "num_leaves": 7,
                              "verbose": -1, "min_data_in_leaf": 5})
    core = lgb.Dataset(X, label=y).construct(cfg)
    g = GBDT(cfg, core)
    fn = g._build_fused_chunk(chunk)
    keys = jnp.zeros((chunk, 2), jnp.uint32)
    fmasks = jnp.ones((chunk, g.num_class, g.grower.num_features), bool)
    fresh = jnp.zeros(chunk, bool)
    low = fn.lower(g.scores, tuple(), g._full_counts > 0, keys, fmasks,
                   fresh)
    return low.as_text()


def test_off_mode_hlo_identity():
    """Every telemetry mode lowers byte-identical StableHLO for the
    fused training chunk: all instrumentation lives at host seams, and
    the ``tel.<phase>`` named scopes, which are on at EVERY mode (off
    included), write op locations only, which ``as_text()`` does not
    print.  A hook that reaches into a jitted body with more than
    metadata (io_callback, a debug print, a mode-dependent op) breaks
    this test instead of silently de-optimizing production."""
    TELEMETRY.configure("off")
    base = _lowered_chunk_text()
    assert "tel." not in base
    for mode in ("counters", "spans", "trace"):
        TELEMETRY.configure(mode)
        assert _lowered_chunk_text() == base, (
            f"telemetry={mode} changed the lowered fused chunk")


def _model_text(mode):
    TELEMETRY.configure(mode)
    TELEMETRY.reset()
    rng = np.random.RandomState(11)
    X = rng.randn(600, 6)
    y = (X[:, 0] - 0.5 * X[:, 2] > 0).astype(float)
    bst = lgb.train({"objective": "binary", "num_leaves": 7, "verbose": -1,
                     "min_data_in_leaf": 5, "dispatch_chunk": 3},
                    lgb.Dataset(X, label=y), 6)
    return bst.model_to_string()


@pytest.mark.parametrize("mode", ["counters", "spans", "trace"])
def test_every_mode_trains_identical_trees(mode):
    """The modes differ in what the host records, never in the model."""
    assert _model_text(mode) == _model_text("off")


def _lowered_collective_text():
    """Lower a shard_map program through the INSTRUMENTED Collectives
    wrappers (round 13: they record bytes/calls at trace time)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    from lightgbm_tpu.learner.grower import _get_shard_map
    from lightgbm_tpu.parallel.collectives import Collectives

    mesh = Mesh(np.asarray(jax.devices()[:8]), ("data",))
    comm = Collectives("data")
    shard_map = _get_shard_map()

    def step(x):
        y = comm.reduce_scatter(comm.all_gather(x))
        return y + comm.allreduce_sum(jnp.sum(x)) \
            + comm.global_max(jnp.max(x))

    fn = jax.jit(shard_map(step, mesh=mesh, in_specs=P("data"),
                           out_specs=P("data")))
    return fn.lower(jnp.zeros(64, jnp.float32)).as_text()


def test_off_mode_hlo_identity_collectives():
    """The round-13 acceptance extension: the instrumented collective
    wrappers record ONLY trace-time Python (counter adds from abstract
    shapes), so telemetry=off/counters/spans lower byte-identical
    StableHLO for a program built from every instrumented collective
    kind."""
    TELEMETRY.configure("off")
    base = _lowered_collective_text()
    TELEMETRY.configure("counters")
    assert _lowered_collective_text() == base, (
        "telemetry=counters changed the lowered collective program")
    assert TELEMETRY.counters()["collective_allgather_calls"] == 1
    TELEMETRY.configure("spans")
    assert _lowered_collective_text() == base, (
        "telemetry=spans changed the lowered collective program")


def _lowered_serving_text():
    import jax.numpy as jnp

    from lightgbm_tpu.ops import predict as P
    from lightgbm_tpu.tree import flatten_ensemble

    rng = np.random.RandomState(9)
    X = rng.randn(200, 5)
    bst = lgb.train({"objective": "regression", "verbose": -1,
                     "num_leaves": 7, "min_data_in_leaf": 5},
                    lgb.Dataset(X, label=X[:, 0]), 3,
                    verbose_eval=False)
    flat = flatten_ensemble(bst.models, 1)
    depth = int(flat.pop("depth"))
    stack = P.LevelEnsemble(**{k: jnp.asarray(v)
                               for k, v in flat.items()})
    x2 = jnp.zeros((16, 10), jnp.float32)
    return P.predict_level_ensemble.lower(stack, x2,
                                          depth=depth).as_text()


def test_off_mode_hlo_identity_serving():
    """The serving program (the bucketed level-ensemble descent) must
    also lower byte-identically across off/counters/spans — the
    round-13 latency histograms live at the host seam around the
    dispatch, never inside it."""
    TELEMETRY.configure("off")
    base = _lowered_serving_text()
    TELEMETRY.configure("counters")
    assert _lowered_serving_text() == base, (
        "telemetry=counters changed the lowered serving program")
    TELEMETRY.configure("spans")
    assert _lowered_serving_text() == base, (
        "telemetry=spans changed the lowered serving program")


def test_trace_mode_trees_byte_identical():
    """trace mode adds named-scope METADATA only: the trained model
    must be byte-identical to an off-mode run."""
    TELEMETRY.configure("off")
    bst_off, _ = _train(iters=5, seed=11)
    TELEMETRY.configure("trace")
    bst_tr, _ = _train(iters=5, seed=11)
    assert bst_off.model_to_string() == bst_tr.model_to_string()


# ---------------------------------------------------------------------------
# retrace sentinel
# ---------------------------------------------------------------------------
def test_retrace_sentinel_warns_once(capsys):
    Log.set_level(0)
    TELEMETRY.retrace_warn = 2
    for i in range(5):
        TELEMETRY.note_trace("test.fn", (i, 16))
    TELEMETRY.note_trace("test.fn", (0, 16))     # repeat: not distinct
    err = capsys.readouterr().err
    assert err.count("test.fn") == 1, "sentinel must warn ONCE per fn"
    assert "telemetry_retrace_warn" in err
    assert TELEMETRY.retraces()["test.fn"] == 5
    # counted even at telemetry=off ("exported either way")
    assert not TELEMETRY.on


def test_retrace_sentinel_threshold_via_config(capsys):
    """telemetry_retrace_warn rides Config; bucket-off serving with
    many batch sizes is exactly the shape churn the sentinel exists
    to flag."""
    bst, X = _train(n=220, iters=4, seed=7, f=9)
    lgb.Config.from_params({"telemetry_retrace_warn": 2, "verbose": -1})
    Log.set_level(0)
    for n in (3, 5, 7, 11, 15):
        bst.predict(X[:n], device=True)
    # bucketed serving: 5 sizes -> ONE shape; no warning
    assert "predict.level_ensemble" not in capsys.readouterr().err
    cfg = lgb.Config.from_params({"predict_bucket": "off",
                                  "verbose": -1,
                                  "telemetry_retrace_warn": 2})
    raw = lgb.Booster(config=cfg, model_str=bst.model_to_string())
    for n in (3, 5, 7, 11, 15):
        raw.predict(X[:n], device=True)
    err = capsys.readouterr().err
    assert err.count("predict.level_ensemble has now traced") == 1, err


# ---------------------------------------------------------------------------
# satellite: config.verbosity -> Log level wiring
# ---------------------------------------------------------------------------
def test_engine_routes_verbosity_to_log_level():
    rng = np.random.RandomState(0)
    X = rng.randn(200, 4)
    # the `verbosity` ALIAS must reach the global Log level through
    # engine.train (the satellite fix: it used to work only in cli.py)
    lgb.train({"objective": "regression", "num_leaves": 4,
               "min_data_in_leaf": 5, "verbosity": 2},
              lgb.Dataset(X, label=X[:, 0]), 2, verbose_eval=False)
    assert Log.level == 2
    _train(iters=2)                      # verbose=-1 in _train defaults
    assert Log.level == -1


def test_cli_routes_verbosity_to_log_level(tmp_path):
    from lightgbm_tpu.cli import run
    rng = np.random.RandomState(0)
    data = tmp_path / "train.csv"
    arr = np.column_stack([rng.rand(80) > 0.5, rng.randn(80, 4)])
    np.savetxt(data, arr, delimiter=",", fmt="%.6g")
    model = tmp_path / "model.txt"
    run([f"data={data}", "objective=binary", "num_iterations=2",
         "num_leaves=4", "min_data_in_leaf=2", f"output_model={model}",
         "verbosity=2", "label_column=0"])
    assert Log.level == 2
    assert model.exists()


# ---------------------------------------------------------------------------
# host/device split accounting (bench-vs-runtime equivalence)
# ---------------------------------------------------------------------------
def test_fenced_split_accounts_for_wall():
    """With the fence on (what bench.py enables), host_dispatch_ms +
    device_wait_ms must account for the dispatch wall the same way
    timed_chunks reads it — the two consumers share one code path, so
    the split can never drift from the wall it decomposes."""
    import jax

    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config

    rng = np.random.RandomState(2)
    X = rng.randn(600, 6)
    y = X[:, 0] - 0.2 * X[:, 2]
    cfg = Config.from_params({"objective": "regression", "verbose": -1,
                              "num_leaves": 7, "min_data_in_leaf": 5})
    core = lgb.Dataset(X, label=y).construct(cfg)
    g = GBDT(cfg, core)
    g.train_chunk(4)                     # compile outside the window
    jax.block_until_ready(g.scores)
    TELEMETRY.configure("counters", fence=True)
    TELEMETRY.reset()
    t0 = time.perf_counter()
    for _ in range(3):
        g.train_chunk(4)
    jax.block_until_ready(g.scores)
    wall = time.perf_counter() - t0
    c = TELEMETRY.counters()
    split = (c["host_dispatch_ms"] + c["device_wait_ms"]) / 1e3
    assert c["trees_dispatched"] == 12
    assert split <= wall * 1.05 + 0.01
    # the split covers the dispatch wall minus python glue between
    # chunks — the 10% agreement bound of the acceptance criteria,
    # relaxed for tiny-shape jitter on shared CI hosts
    assert split >= wall * 0.5, (split, wall)


def test_tune_dispatch_chunk_suspends_fence():
    """The auto-chunk probe times the raw async enqueue; the telemetry
    fence must not fold device wall into its dispatch estimate."""
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config

    rng = np.random.RandomState(4)
    X = rng.randn(600, 6)
    y = X[:, 0]
    cfg = Config.from_params({"objective": "regression", "verbose": -1,
                              "num_leaves": 7, "min_data_in_leaf": 5})
    core = lgb.Dataset(X, label=y).construct(cfg)
    g = GBDT(cfg, core)
    TELEMETRY.configure("spans")         # fence on
    assert TELEMETRY.fence_active
    with TELEMETRY.suspend_fence():
        assert not TELEMETRY.fence_active
    chunk, info = g.tune_dispatch_chunk(probes=(2, 4), cmin=2, cmax=8)
    assert info["iters_used"] == 12
    assert 2 <= chunk <= 8
    # fence restored after the probe
    assert TELEMETRY.fence_active


if __name__ == "__main__":
    import sys
    sys.exit(pytest.main([__file__, "-v"]))
