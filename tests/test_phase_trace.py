"""The program names its own phases (ISSUE 26): ``tel.<phase>`` scopes over
the whole fused chunk, pinned kernel names, host spans as profiler
annotations, set-up stages as counters, and the reduction from a real
trace (perfbench/program_trace.py) on a fixture recorded on a TPU v5e."""
import glob
import os
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax._src import core as jax_core

import lightgbm_tpu as lgb
from lightgbm_tpu import telemetry as telemetry_mod
from lightgbm_tpu.telemetry import TELEMETRY

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "perfbench"))
import program_trace  # noqa: E402
import xplane  # noqa: E402

FIXTURE = os.path.join(HERE, "criteo_phase_trace.json.gz")
FAST = {"force_pallas_interpret": True, "hist_compute_dtype": "bfloat16",
        "quantized_grad": True, "quant_stochastic_rounding": 1}


@pytest.fixture(autouse=True)
def _clean_telemetry():
    TELEMETRY.configure("off")
    TELEMETRY.reset()
    yield
    TELEMETRY.configure("off")
    TELEMETRY.reset()


def _gbdt(params, classes=1, n=1024, f=6):
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config
    rng = np.random.RandomState(3)
    X = rng.randn(n, f)
    y = ((X[:, 0] + 0.4 * X[:, 1] > 0).astype(float) if classes == 1
         else rng.randint(0, classes, n).astype(float))
    cfg = Config.from_params(dict(params, verbose=-1, min_data_in_leaf=5))
    return GBDT(cfg, lgb.Dataset(X, label=y).construct(cfg))


def _chunk_args(g, n_iters):
    keys = jnp.zeros((n_iters, 2), jnp.uint32)
    fmasks = jnp.ones((n_iters, g.num_class, g.grower.num_features), bool)
    return (g.scores, tuple(), g._full_counts > 0, keys, fmasks,
            jnp.zeros(n_iters, bool), g.grower.ohb, g._build_captives())


# ---------------------------------------------------------------------------
# (1) every equation of the fused chunk lies under a tel.<phase> scope
# ---------------------------------------------------------------------------
def _outside_every_phase(jaxpr, prefix=""):
    """Equations that do work (no sub-jaxpr of their own) and whose name
    stack, composed the way XLA composes ``op_name`` through calls and
    loops, has no ``tel.`` in it."""
    out = []
    for eqn in jaxpr.eqns:
        stack = f"{prefix}/{eqn.source_info.name_stack}"
        subs = list(jax_core.jaxprs_in_params(eqn.params))
        for sub in subs:
            out += _outside_every_phase(sub, stack)
        if not subs and "tel." not in stack:
            out.append((eqn.primitive.name, stack))
    return out


@pytest.mark.parametrize("name,params,classes", [
    ("binary", {"objective": "binary", "num_leaves": 7}, 1),
    ("binary_fast_path", dict(FAST, objective="binary", num_leaves=7), 1),
    ("multiclass3", {"objective": "multiclass", "num_class": 3,
                     "num_leaves": 7}, 3),
    ("bagging_l1", {"objective": "regression_l1", "num_leaves": 7,
                    "bagging_freq": 1, "bagging_fraction": 0.5}, 1),
])
def test_every_equation_of_the_chunk_is_under_a_phase(name, params, classes):
    g = _gbdt(params, classes)
    jaxpr = jax.make_jaxpr(g._build_fused_chunk(2))(*_chunk_args(g, 2))
    assert _outside_every_phase(jaxpr.jaxpr) == []
    text = g._build_fused_chunk(2).lower(*_chunk_args(g, 2)).as_text(
        debug_info=True)
    for phase in ("gradients", "sampling", "init_state", "histogram",
                  "split_finder", "apply_split", "finalize_tree",
                  "score_update", "tree_record"):
        assert f"tel.{phase}/" in text, phase
    assert ("tel.route/" in text) == ("fast" in name)
    assert ("tel.quantize/" in text) == ("fast" in name)


def test_phase_is_a_named_scope_at_every_mode():
    for mode in telemetry_mod.MODES:
        TELEMETRY.configure(mode)
        jaxpr = jax.make_jaxpr(lambda x: _scoped(x))(1.0)
        assert "tel.demo" in str(jaxpr.eqns[0].source_info.name_stack), mode


def _scoped(x):
    with TELEMETRY.phase("demo"):
        return x + 1.0


def test_trace_mode_is_spans():
    TELEMETRY.configure("trace")
    assert TELEMETRY.level == "spans" and TELEMETRY.spans_on
    assert TELEMETRY.fence_active
    from lightgbm_tpu.config import Config
    assert Config.from_params({"telemetry": "trace"}).telemetry == "trace"


# ---------------------------------------------------------------------------
# (3) every pallas_call site carries its pinned name
# ---------------------------------------------------------------------------
N, G, B, L, W = 512, 4, 16, 8, 6


def _s(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _kernel_cases():
    from lightgbm_tpu.ops import histogram as H
    from lightgbm_tpu.ops.partition import ROUTE_FIXED_COLS
    bins, binsT = _s((N, G), jnp.uint8), _s((G, N), jnp.uint8)
    f32n, leaf = _s((N,), jnp.float32), _s((N,), jnp.int32)
    w, wT = _s((N, 3), jnp.float32), _s((3, N), jnp.int32)
    wTf = _s((3, N), jnp.float32)
    scales, slots = _s((3,), jnp.float32), _s((W,), jnp.int32)
    ohb = _s((N, G * B), jnp.int8)
    tab = _s((L, ROUTE_FIXED_COLS + (B + 7) // 8), jnp.float32)
    kw = dict(max_group_bin=B, block=256, interpret=True)
    return [
        (H.compute_group_histograms_pallas,
         (bins, f32n, f32n, f32n, leaf), dict(kw, num_leaves=L)),
        (H.compute_group_histograms_pre,
         (ohb, w, leaf), dict(kw, num_leaves=L)),
        (H.compute_group_histograms_pre_packed,
         (ohb, w, leaf, slots), kw),
        (H.compute_group_histograms_fused,
         (ohb, binsT, wTf, leaf, tab, slots), kw),
        (H.compute_group_histograms_fused_tiled,
         (binsT, wT, scales, leaf, tab, slots), kw),
        (H.route_apply_tiled, (binsT, leaf, tab, _s((L,), jnp.float32)),
         dict(block=256, interpret=True)),
    ] + [   # the factored rungs: 256-lane tiles only, one kernel a rung
        (H.compute_group_histograms_fused_factored,
         (binsT, wT, scales, leaf,
          _s((L, ROUTE_FIXED_COLS + 32), jnp.float32),
          _s((126,), jnp.int32)),
         dict(max_group_bin=255, block=256, interpret=True, k_cap=k_cap,
              a=a))
        for k_cap, a, _ in H.FACTORED_RUNGS]


def _pallas_names(jaxpr):
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        else:
            for sub in jax_core.jaxprs_in_params(eqn.params):
                out += _pallas_names(sub)
    return out


KERNEL_NAMES = [          # what a device trace showed before they were pinned
    "compute_group_histograms_pallas",
    "compute_group_histograms_pre",
    "compute_group_histograms_pre_packed",
    "compute_group_histograms_fused",
    "compute_group_histograms_fused_tiled",
    "route_apply_tiled",
    "compute_group_histograms_fused_factored_k2_a4",    # PR 27: pinned
    "compute_group_histograms_fused_factored_k10_a2",   # from the start
    "compute_group_histograms_fused_factored_k16_a2",
    "compute_group_histograms_fused_factored_k32_a2",
    "compute_group_histograms_fused_factored_k64_a2",   # PR 29: the wide
    "compute_group_histograms_fused_factored_k126_a2",  # passes' rungs
]


@pytest.mark.parametrize("i", range(len(KERNEL_NAMES)))
def test_pallas_call_carries_its_pinned_name(i):
    """The name of a kernel in a device trace is pinned: what
    perfbench/metrics/hist_ms_per_tree.json matches must not move with
    the rename of a function or a scope around the call."""
    fn, args, kw = _kernel_cases()[i]
    want = KERNEL_NAMES[i]
    jaxpr = jax.make_jaxpr(lambda *a: fn(*a, **kw))(*args)
    assert _pallas_names(jaxpr.jaxpr) == [want]
    if i in (0, 3, 4, 5):         # and in the lowered text's locations
        text = jax.jit(lambda *a: fn(*a, **kw)).lower(*args).as_text(
            debug_info=True)
        assert f"{want}/" in text


def test_histogram_kernel_names_match_the_benchmarks_pattern():
    """perfbench/metrics/hist_ms_per_tree.json books device time to the
    histogram pass by kernel name: a histogram kernel whose name falls
    outside its pattern would be booked to nonhist_device_ms_per_tree
    and hist_roofline would read a kernel that no longer runs."""
    import json
    import re
    with open(os.path.join(os.path.dirname(HERE), "perfbench", "metrics",
                           "hist_ms_per_tree.json")) as f:
        patterns = [re.compile(p) for p in json.load(f)["params"]["patterns"]]
    from lightgbm_tpu.ops.histogram import FACTORED_RUNGS
    hist = [n for n in KERNEL_NAMES if not n.startswith("route_")]
    assert len(hist) == len(KERNEL_NAMES) - 1
    assert sum("factored" in n for n in hist) == len(FACTORED_RUNGS) > 0
    for name in hist:
        # an event is named by the instruction's whole text (xplane.py)
        for event in (name, f"%{name}.5 = (s32[17,96,128]{{2,1,0}}, ..."):
            assert any(r.search(event) for r in patterns), event
    for name in set(KERNEL_NAMES) - set(hist):
        assert not any(r.search(name) for r in patterns), name


def test_predict_kernel_carries_its_name():
    from lightgbm_tpu.ops import predict as P
    t, m, k = 3, 7, 1
    i32, f32 = jnp.int32, jnp.float32
    stack = P.LevelEnsemble(
        feat2=_s((t * m,), i32), thr_hi=_s((t * m,), f32),
        thr_lo=_s((t * m,), f32), dtype_=_s((t * m,), i32),
        left=_s((t * m,), i32), right=_s((t * m,), i32),
        leaf_value=_s((t * 8,), f32), cat_words=_s((t * m,), i32),
        root=_s((t,), i32), cls_onehot=_s((t, k), f32))
    jaxpr = jax.make_jaxpr(lambda s, x: P.predict_level_ensemble_pallas(
        s, x, depth=3, tile=64, interpret=True))(stack, _s((128, 10), f32))
    assert _pallas_names(jaxpr.jaxpr) == ["predict_level_ensemble_pallas"]


# ---------------------------------------------------------------------------
# (4) the reduction, on a quarter second of criteo_train from the chip
# ---------------------------------------------------------------------------
METRICS = {      # ISSUE 26's six device metrics, as their data files would be
    "split_ms_per_tree": ["split_finder"],
    "route_ms_per_tree": ["apply_split", "partition", "route"],
    "objective_ms_per_tree": ["gradients", "sampling", "quantize"],
    "score_update_ms_per_tree": ["score_update", "finalize_tree",
                                 "tree_record", "init_state"],
    "hist_glue_ms_per_tree": ["histogram"],
    "unscoped_device_ms_per_tree": None,
}


@pytest.fixture(scope="module")
def recorded():
    planes, hlo = program_trace.load_fixture(FIXTURE)
    return planes, hlo, program_trace.book(hlo)


def _ctx(planes, reduced):
    return {"trace_planes": planes, "n_trees": 1,
            "trace_cache": {"program_trace": reduced}}


def test_phases_partition_busy_minus_kernel(recorded):
    planes, _, booked = recorded
    r = program_trace.phase_seconds(planes, booked)
    base = xplane.reduce(planes, [program_trace.KERNEL.pattern])
    assert r["busy"] == pytest.approx(base["busy_s"], rel=1e-12)
    assert r["kernel"] == pytest.approx(base["matched_s"], rel=1e-12)
    values = {name: program_trace.device_phase(
        _ctx(planes, r), {"phases": phases, "per": "tree", "scale": 1e3})
        for name, phases in METRICS.items()}
    assert sum(values.values()) == pytest.approx(
        1e3 * (base["busy_s"] - base["matched_s"]), rel=1e-9)
    # what this quarter second holds (TPU v5 lite, my chip run, PR 26)
    assert values["objective_ms_per_tree"] == pytest.approx(3.105677)
    assert values["split_ms_per_tree"] == pytest.approx(1.961643)
    assert values["unscoped_device_ms_per_tree"] == pytest.approx(0.207198)
    assert values["unscoped_device_ms_per_tree"] < 0.05 * sum(values.values())


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_program_without_scopes_reads_none(recorded, name):
    planes, hlo, _ = recorded
    stripped = [[i, n, None, c, ops, called] for i, n, _, c, ops, called in hlo]
    r = program_trace.phase_seconds(planes, program_trace.book(stripped))
    assert r is None
    assert program_trace.device_phase(
        _ctx(planes, r), {"phases": METRICS[name], "per": "tree"}) is None


def test_untraced_run_reads_none():
    assert program_trace.device_phase(
        {"trace_planes": None, "trace_cache": {}}, {"phases": None}) is None


def test_compiler_made_instructions_are_booked_by_the_rules(recorded):
    _, hlo, booked = recorded
    own = {n: p for _, n, p, *_ in hlo}
    made = [n for n, p in own.items() if p is None and booked[n]]
    assert len(made) > 1000              # most of them have no metadata
    # the two-level reduce-window of the bin prefix sums: rule 2, through
    # the conditional of the finder's ladder that calls its computation
    rw = [n for n in made if n.startswith("reduce-window")]
    assert rw and {booked[n] for n in rw} == {"split_finder"}


def test_booking_rules_on_a_small_graph():
    rows = [
        # id, name, own phase, computation, operands, called computations
        [1, "p", None, 10, [], []],
        [2, "made_by_a", "a", 10, [1], []],
        [3, "copy_of_a", None, 10, [2], []],            # rule 3, operands
        [4, "feeds_b", None, 10, [1], []],              # rule 3, users
        [5, "b", "b", 10, [4], []],
        [6, "cond", "c", 10, [3], [20]],
        [7, "in_branch", None, 20, [], []],             # rule 2
        [8, "inner", "d", 20, [7], []],                 # rule 1
        [9, "alone", None, 30, [], []],                 # rule 4
    ]
    assert program_trace.book(rows) == {
        "p": "a", "made_by_a": "a", "copy_of_a": "a", "feeds_b": "b",
        "b": "b", "cond": "c", "in_branch": "c", "inner": "d",
        "alone": None}
    twice = rows + [[1 << 40, "b", "other", 1 << 41, [], []]]
    assert program_trace.book(twice)["b"] is None    # two programs disagree


def _msg(*fields):
    """A protobuf message from (number, int | bytes) pairs."""
    def varint(x):
        out = bytearray()
        while True:
            out.append((x & 0x7F) | (0x80 if x > 0x7F else 0))
            x >>= 7
            if not x:
                return bytes(out)
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += varint(number << 3) + varint(value)
        else:
            out += varint(number << 3 | 2) + varint(len(value)) + value
    return out


def test_hlo_instructions_from_a_raw_xplane(tmp_path):
    instr = _msg((1, b"fusion.7"), (2, b"fusion"),
                 (7, _msg((1, b"add"), (2, b"jit(f)/tel.outer/tel.inner/add"))),
                 (35, 7), (36, b"\x03"), (38, 12))     # operands: packed
    bare = _msg((1, b"copy.3"), (35, 3))
    comp = _msg((1, b"main"), (2, instr), (2, bare), (5, 11))
    proto = _msg((1, _msg((1, b"jit_f"), (3, comp))))
    plane = _msg(
        (2, b"/host:metadata"),
        (5, _msg((1, 9), (2, _msg((1, 9), (2, b"Hlo Proto"))))),
        (4, _msg((1, 1), (2, _msg((1, 1), (2, b"jit_f(1)"),
                                  (5, _msg((1, 9), (6, proto))))))))
    other = _msg((2, b"/device:TPU:0"))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_msg((1, other), (1, plane)))
    rows = program_trace.hlo_instructions(str(path))
    assert rows == [[7, "fusion.7", "inner", 11, [3], [12]],
                    [3, "copy.3", None, 11, [], []]]
    assert program_trace.book(rows) == {"fusion.7": "inner",
                                        "copy.3": "inner"}


def test_idle_time_goes_to_the_programs_own_spans(recorded):
    planes, _, _ = recorded
    idle = program_trace.idle_by_span(planes)
    assert set(idle) <= {"outside", "ltpu.train_chunk", "ltpu.chunk_prep",
                         "ltpu.host_dispatch", "ltpu.chunk_commit"}
    assert "ltpu.host_dispatch" in idle
    base = xplane.reduce(planes)
    assert sum(idle.values()) == pytest.approx(
        sum(g[1] for g in base["idle_gaps"]), rel=1e-9)
    host = planes[xplane.HOST_PLANE]
    names = [e[0] for events in host.values() for e in events]
    for span in ("ltpu.train_chunk", "ltpu.chunk_prep", "ltpu.host_dispatch",
                 "ltpu.chunk_commit"):
        assert span in names


# ---------------------------------------------------------------------------
# host spans on the profiler's clock; set-up stages; the probe's gauges
# ---------------------------------------------------------------------------
def test_spans_land_in_a_profiler_trace_at_counters_mode(tmp_path):
    from jax.profiler import ProfileData
    TELEMETRY.configure("counters")
    jax.profiler.start_trace(str(tmp_path))
    try:
        with TELEMETRY.span("outer", iters=2):
            token = TELEMETRY.start_span("inner")
            TELEMETRY.end_span(token)
        with TELEMETRY.stage("demo_stage"):
            pass
    finally:
        jax.profiler.stop_trace()
    assert TELEMETRY.events_snapshot() == []     # recorded only at spans
    pb = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    events = {e.name: dict(e.stats)
              for plane in ProfileData.from_file(pb).planes
              if plane.name == "/host:CPU"
              for line in plane.lines for e in line.events
              if e.name.startswith("ltpu.")}
    assert set(events) == {"ltpu.outer", "ltpu.inner", "ltpu.demo_stage"}
    assert events["ltpu.outer"]["iters"] == 2


def test_stage_books_its_own_time_and_the_resident_set():
    TELEMETRY.configure("counters")
    with TELEMETRY.stage("outer_stage"):
        with TELEMETRY.stage("inner_stage"):
            sum(range(200000))
    c, g = TELEMETRY.counters(), TELEMETRY.gauges()
    assert c["setup_inner_stage_ms"] > 0
    assert 0 <= c["setup_outer_stage_ms"] < c["setup_inner_stage_ms"]
    assert g["rss_mb_after_inner_stage"] > 0
    assert g["rss_mb_before_outer_stage"] > 0
    assert g["rss_mb_peak"] >= g["rss_mb_after_outer_stage"]


def test_off_mode_does_nothing_new(monkeypatch):
    """At telemetry=off no span, stage or train_chunk opens an annotation,
    reads a clock for telemetry, or leaves a counter or gauge."""
    calls = []
    monkeypatch.setattr(telemetry_mod, "_annotation",
                        lambda name, attrs: calls.append(name))
    TELEMETRY.configure("off")
    with TELEMETRY.span("x"), TELEMETRY.stage("y"):
        TELEMETRY.end_span(TELEMETRY.start_span("z"))
    assert TELEMETRY.start_span("z") is None
    g = _gbdt({"objective": "binary", "num_leaves": 7})
    g.train_chunk(2)
    assert calls == []
    assert TELEMETRY.counters() == {} and TELEMETRY.gauges() == {}
    assert TELEMETRY.events_snapshot() == []


def test_training_leaves_stage_counters_and_the_build_time():
    TELEMETRY.configure("counters")
    g = _gbdt({"objective": "binary", "num_leaves": 7})
    g.train_chunk(2)
    first = TELEMETRY.counters()
    for stage in ("fit_mappers", "bin", "pack", "binning", "upload",
                  "grower_init"):
        assert first[f"setup_{stage}_ms"] >= 0, stage
        assert TELEMETRY.gauges()[f"rss_mb_after_{stage}"] > 0
    assert "setup_binsT_ms" not in first         # no transposed copy here
    assert 0 < first["chunk_program_build_ms"] <= first["host_dispatch_ms"]
    g.train_chunk(2)                             # same program: no build
    assert TELEMETRY.counters()["chunk_program_build_ms"] \
        == first["chunk_program_build_ms"]
    g.train_chunk(1)                             # another length: a build
    assert TELEMETRY.counters()["chunk_program_build_ms"] \
        > first["chunk_program_build_ms"]
    ctx = {}
    assert program_trace.setup_counter(
        ctx, {"counters": ["setup_upload_ms"], "scale": 1e-3}) \
        == pytest.approx(first["setup_upload_ms"] / 1e3)
    assert program_trace.setup_counter(
        ctx, {"counters": ["setup_upload_ms", "setup_binsT_ms"]}) is None


def test_fast_path_books_the_transposed_copy():
    TELEMETRY.configure("counters")
    _gbdt(dict(FAST, objective="binary", num_leaves=7))
    assert TELEMETRY.counters()["setup_binsT_ms"] > 0
    assert TELEMETRY.gauges()["rss_mb_after_binsT"] > 0


def test_tune_dispatch_chunk_leaves_its_gauges():
    TELEMETRY.configure("counters")
    g = _gbdt({"objective": "binary", "num_leaves": 7})
    chunk, info = g.tune_dispatch_chunk(probes=(1, 2))
    gauges = TELEMETRY.gauges()
    assert gauges["dispatch_probe_ms_per_tree_1"] == pytest.approx(
        info["probe_per_tree_s"][1] * 1e3)
    assert gauges["dispatch_probe_ms_per_tree_2"] == pytest.approx(
        info["probe_per_tree_s"][2] * 1e3)
    assert gauges["dispatch_probe_return_ms"] == pytest.approx(
        info["dispatch_s"] * 1e3)
    assert gauges["dispatch_chunk_slope_ms"] == pytest.approx(
        info["slope_s_per_iter"] * 1e3)
    assert gauges["dispatch_chunk_base_ms"] == pytest.approx(
        info["base_s"] * 1e3)
    assert chunk == info["chunk"]
