"""More rows on a device than one int32 accumulator sums: the ladder's
kernels accumulate a row segment at a time (``histogram.
QUANT_SEGMENT_ROWS``, 2^24, patched here to a few thousand rows), write
one accumulator a segment, and the grower folds the segments exactly
through ``collectives.exchange_int_histograms`` — the arithmetic of the
mesh's cross-shard sum — and carries int32 row counts.  So the trees do
not depend on how many segments hold the rows, as they do not depend on
how many shards do.

On the CPU with the Pallas kernels on the interpret seam (the same grower
wiring the chip runs) and conftest.py's 8 virtual devices.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.ops import histogram as H
from lightgbm_tpu.ops.hist_plan import LADDER_WIDTH, resolve_hist_plan
from lightgbm_tpu.ops.partition import ROUTE_FIXED_COLS
from lightgbm_tpu.parallel import collectives
from lightgbm_tpu.telemetry import TELEMETRY
from lightgbm_tpu.utils.log import Log

N, F = 8192, 12
FAST = {"objective": "binary", "num_leaves": 31, "max_bin": 255,
        "verbose": -1, "min_data_in_leaf": 5,
        "hist_compute_dtype": "bfloat16", "quantized_grad": True,
        "quant_stochastic_rounding": 1, "force_pallas_interpret": True,
        "dispatch_chunk": 2, "telemetry": "counters"}
TREES = 3


@pytest.fixture(autouse=True)
def _clean_telemetry():
    level = Log.level
    yield
    TELEMETRY.configure("off")
    TELEMETRY.reset()
    Log.set_level(level)


@pytest.fixture(scope="module")
def table():
    rng = np.random.RandomState(0)
    X = np.exp(rng.randn(N, F)).astype(np.float32)
    y = (X[:, 0] * 1.5 - X[:, 1] + 0.5 * rng.randn(N) > 0.5)
    return X, y.astype(np.float32)


def grow(table, rows=N, segment=0, **params):
    """(model text, booster) of ``rows`` rows with a segment of
    ``segment`` rows (0: the module's own 2^24, one segment)."""
    X, y = table
    with pytest.MonkeyPatch.context() as mp:
        if segment:
            mp.setattr(H, "QUANT_SEGMENT_ROWS", segment)
        bst = lgb.train({**FAST, **params},
                        lgb.Dataset(X[:rows], label=y[:rows]), TREES,
                        verbose_eval=False, keep_training_booster=True)
    return bst.model_to_string(), bst


@pytest.fixture(scope="module")
def one_segment(table):
    cache = {}

    def get(rows=N, **params):
        key = (rows, tuple(sorted(params.items())))
        if key not in cache:
            text, bst = grow(table, rows, **params)
            assert bst.gbdt.grower.plan.row_segments == 1
            assert not bst.gbdt.grower.plan.int_counts
            cache[key] = text
        return cache[key]
    return get


# -- the constant, and the plan ------------------------------------------
def test_the_segment_is_the_bound_as_a_power_of_two():
    seg = H.QUANT_SEGMENT_ROWS
    assert seg == 1 << 24 and seg & (seg - 1) == 0
    assert H.quant_rows_ok(seg) and not H.quant_rows_ok(2 * seg)
    assert H.quant_row_segments(seg) == (1, seg)
    assert H.quant_row_segments(seg + 1024) == (2, seg)
    assert H.quant_row_segments(3 * seg) == (3, seg)
    assert H.quant_row_segments(4096) == (1, 4096)


def _plan(rows, **params):
    return resolve_hist_plan(
        Config.from_params({"verbose": -1, "hist_compute_dtype": "bfloat16",
                            "quantized_grad": True, **params}),
        on_tpu=True, mesh_axes=None, row_axis=None, cols_sharded=False,
        multihost=False, rows_padded=rows, num_groups=67, max_group_bin=255,
        packed_groups=0, frontier=LADDER_WIDTH)


def test_the_plan_past_the_bound_keeps_the_ladder_in_segments():
    past = _plan((1 << 24) + 1024)
    assert past.tier == "ladder" and past.quantized
    assert (past.row_segments, past.segment_rows) == (2, 1 << 24)
    assert past.int_counts and not past.mesh_kernels
    assert past.warnings == () and past.group_chunks == 1
    # at the bound: one segment, and the plan the cells had before the
    # segment's two fields, field for field (tests/test_hist_plan.py)
    at = dataclasses.asdict(_plan(1 << 24))
    assert (at.pop("row_segments"), at.pop("segment_rows")) == (1, 1 << 24)
    assert at.pop("compact_rungs") == H.COMPACT_RUNGS
    assert at == dict(
        tier="ladder", interpret=False, row_axis=None, row_shards=1,
        local_rows=1 << 24, mesh_kernels=False, exchange_limbs=0,
        hist_exchange="f32", fused=True, onehot_pack=0, block_float=2048,
        block_tiled=2048, block_factored=4096, group_chunk=67,
        num_groups=67, factored_rungs=H.FACTORED_RUNGS, finder="fused",
        warnings=())
    assert not _plan(1 << 24).int_counts
    # the new cell's shape: two whole segments, the blocks of one
    r25 = _plan(1 << 25)
    assert (r25.row_segments, r25.block_factored, r25.block_tiled) \
        == (2, 4096, 2048)


def test_tiered_past_the_old_bound_no_longer_raises():
    plan = _plan(1 << 25, quantized_grad=False, hist_precision="tiered")
    assert plan.tier == "ladder" and plan.row_segments == 2
    # the bound is still loud where it is asked: of a segment
    with pytest.raises(ValueError, match="int32 histogram accumulator"):
        H.check_quant_rows(2 * H.QUANT_SEGMENT_ROWS)


def test_a_block_lies_in_one_segment(monkeypatch):
    monkeypatch.setattr(H, "QUANT_SEGMENT_ROWS", 2048)
    plan = _plan(8192)
    assert (plan.row_segments, plan.segment_rows) == (4, 2048)
    assert plan.segment_rows % plan.block_factored == 0
    assert plan.segment_rows % plan.block_tiled == 0
    uneven = _plan(5120)
    assert (uneven.row_segments, uneven.block_factored) == (3, 1024)


# -- the kernels: one accumulator a segment ------------------------------
def _pass_inputs(rows, groups=5, bins=255, slots=6, leaves=8, active=2):
    rng = np.random.RandomState(3)
    binsT = rng.randint(0, bins, size=(groups, rows)).astype(np.uint8)
    wT = np.stack([rng.randint(-127, 128, rows), rng.randint(0, 128, rows),
                   np.ones(rows)]).astype(np.int32)
    leaf = rng.randint(0, leaves, rows).astype(np.int32)
    route = np.zeros((leaves, ROUTE_FIXED_COLS + (bins + 7) // 8), np.float32)
    frontier = np.full(slots, -1, np.int32)
    frontier[:active] = ([5, 2] + [i for i in range(leaves)
                                   if i not in (5, 2)])[:active]
    return binsT, wT, leaf, route, frontier


@pytest.mark.parametrize("kernel,shape", [
    (functools.partial(H.compute_group_histograms_fused_tiled, block=1024,
                       strips=1), {}),
    (functools.partial(H.compute_group_histograms_fused_factored, k_cap=2,
                       a=4, block=1024), {}),
    (functools.partial(H.compute_group_histograms_fused_factored, k_cap=10,
                       a=2, block=1024, group_chunk=32),
     dict(groups=40, slots=12, leaves=16, active=7)),
], ids=["tiled_pass", "factored_rung", "factored_rung_group_chunks"])
def test_a_pass_writes_one_accumulator_a_segment(kernel, shape):
    """5 blocks in segments of 2: the accumulators of rows [0, 2048),
    [2048, 4096) and the uneven rest, each what a pass over those rows
    alone gives, and the route as it was."""
    rows, seg = 5120, 2048
    binsT, wT, leaf, route, active = _pass_inputs(rows, **shape)
    run = functools.partial(kernel, max_group_bin=255, interpret=True,
                            dequantize=False)
    whole, leaf_whole = run(binsT, wT, None, leaf, route, active)
    parts, leaf_seg = run(binsT, wT, None, leaf, route, active,
                          segment_rows=seg)
    assert parts.dtype == jnp.int32 and parts.shape == (3,) + whole.shape
    assert np.array_equal(np.asarray(leaf_seg), np.asarray(leaf_whole))
    for s in range(3):
        rows_s = slice(s * seg, min((s + 1) * seg, rows))
        alone, _ = run(binsT[:, rows_s], wT[:, rows_s], None, leaf[rows_s],
                       route, active)
        assert np.array_equal(np.asarray(parts[s]), np.asarray(alone)), s
    assert np.array_equal(np.asarray(parts, np.int64).sum(axis=0),
                          np.asarray(whole, np.int64))
    # a segment of every row is the kernel as it always was
    same, _ = run(binsT, wT, None, leaf, route, active, segment_rows=rows)
    assert same.shape == whole.shape
    with pytest.raises(ValueError, match="dequantize=False"):
        kernel(binsT, wT, jnp.ones(3), leaf, route, active,
               max_group_bin=255, interpret=True, segment_rows=seg)


# -- the fold --------------------------------------------------------------
@pytest.mark.parametrize("segments", [2, 5])
def test_the_fold_is_the_python_int_sum(segments):
    """Accumulators that overflow int32 when added plainly: the fold's
    float32 is the one nearest the exact total, its counts the exact
    int32."""
    rng = np.random.RandomState(7)
    top = 2 ** 31 - 1
    acc = rng.randint(-top, top, size=(segments, 64, 3), dtype=np.int64)
    acc[:, 0] = top                         # every segment at the ceiling
    acc[:, 1] = -top
    acc[:, 2, :2] = [[65535, 65536]] * segments
    acc[:, 3, :2] = [[-65537, -1]] * segments
    # the count channel is rows: a segment's at most 2^24
    acc[..., 2] = rng.randint(0, (1 << 24) + 1, size=(segments, 64))
    acc[:, 4, 2] = 1 << 24
    acc[:, 5, 2] = (1 << 24) - 1
    exact = [[sum(int(v) for v in acc[:, i, c]) for c in range(3)]
             for i in range(64)]            # Python ints
    assert max(abs(v) for row in exact for v in row) > 2 ** 31
    plain = acc.astype(np.int32).sum(axis=0, dtype=np.int32)
    assert (plain != np.asarray(exact)).any()     # int32 wrapped
    total, rows = jax.jit(functools.partial(
        collectives.exchange_int_histograms, axis_name=None,
        global_rows=segments << 24, segments=segments))(
            jnp.asarray(acc, jnp.int32))
    assert total.dtype == jnp.float32 and rows.dtype == jnp.int32
    want = np.asarray([[np.float32(v) for v in row] for row in exact])
    assert np.array_equal(np.asarray(total), want)
    assert np.array_equal(np.asarray(rows),
                          np.asarray([row[2] for row in exact]))
    assert (np.asarray(rows).astype(np.float32) != np.asarray(rows)).any()


# -- the trees -------------------------------------------------------------
@pytest.mark.parametrize("rows,segment,params", [
    (8192, 2048, {}),
    (5120, 2048, {}),
    (8192, 2048, {"max_bin": 63}),
], ids=["four_segments", "uneven_last_segment", "four_segments_strips"])
def test_segments_grow_the_one_segment_model(table, one_segment, rows,
                                             segment, params):
    """Byte for byte: 255 bins run the factored rungs, 63 the strips."""
    text, bst = grow(table, rows, segment, **params)
    gauges = dict(TELEMETRY.gauges())
    plan = bst.gbdt.grower.plan
    assert plan.tier == "ladder"
    assert plan.row_segments == -(-rows // segment) and plan.int_counts
    assert bool(plan.factored_rungs) == ("max_bin" not in params)
    assert text == one_segment(rows, **params)
    assert gauges["grower.hist_row_segments"] == plan.row_segments
    assert gauges["grower.hist_segment_rows"] == segment
    assert gauges["grower.int_counts"] == 1
    assert gauges["grower.quantized"] == 1


def test_tiered_trains_in_segments(table, one_segment):
    text, bst = grow(table, N, 4096, quantized_grad=False,
                     hist_precision="tiered")
    assert bst.gbdt.grower.plan.row_segments == 2
    assert text == one_segment(N)


def test_counts_are_whole_rows_in_int32(table):
    """Leaf and node counts of the model are the rows routed there, and
    they travel as int32 from the fold to the tree."""
    X, _ = table
    _, bst = grow(table, N, 2048)
    grower = bst.gbdt.grower
    assert grower.plan.int_counts
    state = jax.eval_shape(
        grower._init_state, *(jax.ShapeDtypeStruct((grower.n_padded,),
                                                   jnp.float32),) * 3)
    assert state.leaf_count.dtype == jnp.int32
    assert state.hist_cache[1].dtype == jnp.int32
    leaves = bst.predict(X, pred_leaf=True)
    for t, tree in enumerate(bst.gbdt.models):
        rows = np.bincount(leaves[:, t], minlength=tree.num_leaves)
        assert np.array_equal(rows, tree.leaf_count[:tree.num_leaves])

        def under(child):
            return rows[~child] if child < 0 \
                else under(tree.left_child[child]) \
                + under(tree.right_child[child])
        for node in range(tree.num_leaves - 1):
            assert tree.internal_count[node] == under(node)


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="needs four (virtual) devices")
@pytest.mark.parametrize("shards,segment", [(4, 2048), (2, 4096), (2, 2048)],
                         ids=["4_segments_as_4_shards",
                              "2_segments_as_2_shards",
                              "2_shards_of_2_segments"])
def test_segments_grow_the_trees_of_a_row_mesh(table, one_segment, shards,
                                               segment):
    """One device with S segments, an S-shard row mesh of one segment a
    shard, and a mesh whose shards hold two segments each: one model."""
    on_one, bst = grow(table, N, segment)
    assert bst.gbdt.grower.plan.row_segments == N // segment
    mesh = {"tree_learner": "data", "mesh_shape": [shards],
            "mesh_axes": ["data"], "hist_kernel": "pallas"}
    on_mesh, bst = grow(table, N, segment, **mesh)
    plan = bst.gbdt.grower.plan
    assert plan.mesh_kernels and plan.row_shards == shards
    assert plan.row_segments == N // shards // segment
    assert plan.exchange_limbs == (2 if plan.row_segments > 1 else 1)
    assert on_one == on_mesh == one_segment(N)
