"""``tree_learner=data`` on a one-axis row mesh runs the single-device
kernel plan: every row shard runs the quantized fused ladder inside
``shard_map`` and the shards' int32 accumulators are added exactly, so
the trees do not depend on how many devices hold the rows.

On the 8 virtual CPU devices of conftest.py, with the Pallas kernels on
the interpret seam (the same grower wiring the chip runs).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.ops import histogram as H
from lightgbm_tpu.ops.partition import ROUTE_FIXED_COLS
from lightgbm_tpu.parallel import collectives
from lightgbm_tpu.telemetry import TELEMETRY
from lightgbm_tpu.utils.log import Log

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs four (virtual) devices")

N, F = 8192, 12
FAST = {"objective": "binary", "num_leaves": 31, "max_bin": 255,
        "verbose": -1, "min_data_in_leaf": 5,
        "hist_compute_dtype": "bfloat16", "quantized_grad": True,
        "quant_stochastic_rounding": 1, "force_pallas_interpret": True,
        "dispatch_chunk": 2, "telemetry": "counters"}


def mesh_params(shards, **extra):
    return {**FAST, "tree_learner": "data", "mesh_shape": [shards],
            "mesh_axes": ["data"], "hist_kernel": "pallas", **extra}


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


def trees_of(bst):
    return bst.model_to_string()


@pytest.fixture(scope="module")
def serial_trees(table):
    X, y = table
    return trees_of(lgb.train(FAST, lgb.Dataset(X, label=y), 4,
                              verbose_eval=False))


# -- (a) the shards' int32 histograms add up to the one-shard histogram --
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
    # a wide rung: 50 slots live of the 64 it holds, the frontier's 126
    (functools.partial(H.compute_group_histograms_fused_factored, k_cap=64,
                       a=2, block=1024),
     dict(slots=126, leaves=70, active=50)),
], ids=["tiled_pass", "factored_rung", "factored_wide_rung"])
def test_shard_accumulators_add_up_to_the_whole(kernel, shape):
    rows = 4096
    binsT, wT, leaf, route, active = _pass_inputs(rows, **shape)
    run = functools.partial(kernel, max_group_bin=255, interpret=True,
                            dequantize=False)
    whole, leaf_whole = run(binsT, wT, None, leaf, route, active)
    assert whole.dtype == jnp.int32 and int(jnp.abs(whole).max()) > 0
    q = rows // 4
    parts = [run(binsT[:, i * q:(i + 1) * q], wT[:, i * q:(i + 1) * q],
                 None, leaf[i * q:(i + 1) * q], route, active)
             for i in range(4)]
    total = sum(np.asarray(p[0], np.int64) for p in parts)
    assert np.array_equal(total, np.asarray(whole, np.int64))
    assert np.array_equal(np.concatenate([np.asarray(p[1]) for p in parts]),
                          np.asarray(leaf_whole))
    # and dequantized, the kernel is what it was
    scales = jnp.asarray([0.5, 0.25, 1.0], jnp.float32)
    deq, _ = kernel(binsT, wT, scales, leaf, route, active,
                    max_group_bin=255, interpret=True)
    assert np.array_equal(np.asarray(deq),
                          np.asarray(whole.astype(jnp.float32) * scales))


@pytest.mark.parametrize("k_cap,active", [(32, 20), (64, 50), (126, 126)])
def test_compacting_rung_under_shard_map(k_cap, active):
    """A compacting rung inside ``shard_map``, a shard a block of two
    units: every shard compacts its own rows, and the sum of the shards'
    accumulators and the routed leaf ids are those of the uncompacted
    formulation under the same mesh, and of one device."""
    from lightgbm_tpu.learner.grower import _get_shard_map
    rows = 4096
    inputs = _pass_inputs(rows, slots=126, leaves=140, active=active)
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    cols, by_row, rep = P(None, "data"), P("data"), P()

    def run(compact, sharded=True):
        kernel = functools.partial(
            H.compute_group_histograms_fused_factored, k_cap=k_cap, a=2,
            block=1024, max_group_bin=255, interpret=True,
            dequantize=False, compact=compact)

        def shard(binsT, wT, leaf, route, slots):
            acc, leaf2 = kernel(binsT, wT, None, leaf, route, slots)
            return jax.lax.psum(acc, "data"), leaf2
        if not sharded:
            return kernel(inputs[0], inputs[1], None, *inputs[2:])
        return jax.jit(_get_shard_map()(
            shard, mesh=mesh, in_specs=(cols, cols, by_row, rep, rep),
            out_specs=(rep, by_row)))(*inputs)
    got, plain, one = run((512, 128)), run(()), run((512, 128), False)
    assert got[0].dtype == jnp.int32 and int(jnp.abs(got[0]).max()) > 0
    for other in (plain, one):
        for g, o in zip(got, other):
            assert np.array_equal(np.asarray(g), np.asarray(o))


# -- (b) the same trees for 1, 2 and 4 row shards, and for serial -------
@pytest.mark.parametrize("shards", [1, 2, 4])
def test_model_text_does_not_depend_on_the_shards(table, serial_trees,
                                                  shards):
    X, y = table
    bst = lgb.train(mesh_params(shards), lgb.Dataset(X, label=y), 4,
                    verbose_eval=False, keep_training_booster=True)
    g = bst.gbdt.grower
    assert g.plan.tier == "ladder" and g.plan.mesh_kernels
    assert g.plan.factored_rungs          # 255 bins: the rungs are in force
    gauges = TELEMETRY.gauges()
    assert gauges["grower.quantized"] == 1
    assert gauges["grower.hist_kernel"] == "fused_tiled"
    assert gauges["grower.row_shards"] == shards
    assert gauges["grower.local_rows"] == N // shards
    assert gauges["mesh_devices"] == shards
    assert gauges["grower.hist_exchange_bytes_widest"] == \
        g.frontier * g.num_groups * g.max_group_bin * 12
    assert len({s.device for s in g.binsT.addressable_shards}) == shards
    assert trees_of(bst) == serial_trees
    counters = TELEMETRY.counters()
    assert counters["collective_allreduce_calls"] > 0
    assert counters["collective_hist_exchange_bytes"] > 0


def test_row_shards_as_a_list_train_the_same_trees(table, serial_trees):
    """``lgb.Dataset([X0, X1, ...])``: binned shard by shard, placed
    shard by shard, same mappers, same bins, same trees."""
    X, y = table
    cuts = [0, 3000, 3001, 6500, N]
    parts = [X[a:b] for a, b in zip(cuts, cuts[1:])]
    ds = lgb.Dataset(parts, label=y)
    assert ds.num_data() == N and ds.num_feature() == F
    core = ds.construct(Config.from_params(mesh_params(4)))
    whole = lgb.Dataset(X, label=y).construct(Config.from_params(FAST))
    assert [b.shape[0] for b in core.shard_bins] == [3000, 1, 3499, 1692]
    assert np.array_equal(np.concatenate(core.shard_bins), whole.group_bins)
    bst = lgb.train(mesh_params(4), lgb.Dataset(parts, label=y), 4,
                    verbose_eval=False)
    assert trees_of(bst) == serial_trees


# -- (c) the limb sum is exact where an int32 psum would overflow -------
def _exchange(acc, global_rows):
    mesh = Mesh(np.array(jax.devices()[:4]), ("data",))
    fn = jax.shard_map(
        lambda a: collectives.exchange_int_histograms(
            a[0], "data", global_rows=global_rows),
        mesh=mesh, in_specs=P("data"), out_specs=P(), check_vma=False)
    total, rows = jax.jit(fn)(jax.device_put(
        acc, NamedSharding(mesh, P("data"))))
    return np.asarray(total), np.asarray(rows)


def test_limb_sum_is_exact_beyond_int32():
    rng = np.random.RandomState(5)
    top = 2 ** 31 - 1
    acc = rng.randint(-top, top, size=(4, 64, 3), dtype=np.int64)
    acc[:, 0] = top                       # every shard at the ceiling
    acc[:, 1] = -top
    acc[:, 2] = [[top], [top], [-top], [1]]
    acc[:, 3] = [[65535], [65536], [-65537], [-1]]
    # the count channel is rows: a shard's at most 2^24, whole numbers
    # that float32 no longer counts once four shards are added
    acc[..., 2] = rng.randint(0, (1 << 24) + 1, size=(4, 64))
    acc[:, 4, 2] = 1 << 24
    acc[:, 5, 2] = [(1 << 24) - 1, (1 << 24) - 2, 3, 1]
    exact = acc.sum(axis=0)
    assert np.abs(exact).max() > 2 ** 32  # an int32 psum would have wrapped
    got, rows = _exchange(acc.astype(np.int32), global_rows=1 << 26)
    assert got.dtype == np.float32 and rows.dtype == np.int32
    assert np.array_equal(got, exact.astype(np.float32))
    assert np.array_equal(rows, exact[:, 2])
    assert (rows.astype(np.float32) != rows).any()    # float32 rounds them
    # under the bound one int32 psum carries it, to the same float
    small = rng.randint(-2 ** 28, 2 ** 28, size=(4, 64, 3))
    got, rows = _exchange(small.astype(np.int32), global_rows=1 << 22)
    assert np.array_equal(got, small.sum(axis=0).astype(np.float32))
    assert np.array_equal(rows, small.sum(axis=0)[:, 2])


def test_the_bound_between_the_two_sums_is_the_kernels():
    assert collectives.int_exchange_fits_int32(1 << 24)
    assert not collectives.int_exchange_fits_int32(1 << 25)
    with pytest.raises(TypeError, match="int32"):
        collectives.exchange_int_histograms(jnp.zeros(3), None,
                                            global_rows=8)


# -- row counts stay integers past float32's 2^24 -------------------------
def test_finder_counts_in_integers_beyond_float32():
    """A node of 2^26 rows: with the int32 count channel the finder's
    left counts, and so parent minus left, are the exact ones, and
    ``min_data_in_leaf`` is tested on them; float32 prefix sums round."""
    from lightgbm_tpu.ops.split import CAND_THRESHOLD, find_best_split_block
    rng = np.random.RandomState(11)
    W, Fh, B = 2, 3, 64
    cnt = rng.multinomial((1 << 26) - 7, np.ones(B) / B,
                          size=(W, Fh)).astype(np.int64)
    cnt[1, :, -1] = 3                     # a right side under min_data
    n = cnt.sum(axis=2)[:, 0]
    cnt[:, 1:, 0] += (n[:, None] - cnt.sum(axis=2)[:, 1:])
    g = rng.randn(W, Fh, B) * np.sqrt(cnt) + 0.002 * cnt * np.linspace(
        -1, 1, B)
    hist = np.stack([g, cnt * 0.25, cnt], axis=-1).astype(np.float32)
    meta = dict(cfg=dict(lambda_l1=0.0, lambda_l2=0.0, max_delta_step=0.0,
                         min_data_in_leaf=20.0, min_sum_hessian_in_leaf=1e-3,
                         min_gain_to_split=0.0),
                f_num_bin=jnp.full(Fh, B, jnp.int32),
                f_missing=jnp.zeros(Fh, jnp.int32),
                f_default_bin=jnp.zeros(Fh, jnp.int32),
                f_monotone=jnp.zeros(Fh, jnp.int32),
                f_is_cat=jnp.zeros(Fh, bool),
                feature_mask=jnp.ones(Fh, bool), has_categorical=False)
    sg = jnp.asarray(hist[:, 0, :, 0].sum(axis=1))
    sh = jnp.asarray(hist[:, 0, :, 1].sum(axis=1))
    lo, hi = jnp.full(W, -jnp.inf), jnp.full(W, jnp.inf)
    block, left = find_best_split_block(
        jnp.asarray(hist), sg, sh, jnp.asarray(n, jnp.int32), lo, hi,
        feat_count=jnp.asarray(cnt, jnp.int32), **meta)
    assert left.dtype == jnp.int32
    feat = np.asarray(block[:, 1]).astype(int)
    thr = np.asarray(block[:, CAND_THRESHOLD]).astype(int)
    for w in range(W):
        exact = cnt[w, feat[w], :thr[w] + 1].sum()
        assert int(left[w]) == exact
        assert min(exact, n[w] - exact) >= 20
    assert (np.asarray(left) > 1 << 24).any()
    # without the int32 counts: the block alone, as every other path has it
    as_f32 = find_best_split_block(
        jnp.asarray(hist), sg, sh, jnp.asarray(n, jnp.float32), lo, hi,
        **meta)
    assert as_f32.shape == block.shape and as_f32.dtype == jnp.float32


@pytest.mark.parametrize("shards", [2, 4])
def test_tree_counts_are_whole_rows_under_the_mesh(table, shards):
    """Leaf and node counts of the model are the rows routed there."""
    X, y = table
    bst = lgb.train(mesh_params(shards), lgb.Dataset(X, label=y), 2,
                    verbose_eval=False, keep_training_booster=True)
    assert bst.gbdt.grower.plan.int_counts
    leaves = bst.predict(X, pred_leaf=True)
    for t, tree in enumerate(bst.gbdt.models):
        rows = np.bincount(leaves[:, t], minlength=tree.num_leaves)
        assert np.array_equal(tree.leaf_count, rows)
        assert tree.internal_count[0] == N


# -- (e) what the mesh cannot honour still raises ------------------------
@pytest.mark.parametrize("extra,match", [
    (dict(tree_learner="feature", mesh_shape=[4], mesh_axes=["feature"]),
     "hist_kernel=pallas cannot run here"),
    (dict(mesh_shape=[2, 2], mesh_axes=["data", "feature"]),
     "hist_kernel=pallas cannot run here"),
    (dict(tree_learner="voting"), "hist_kernel=pallas cannot run here"),
    (dict(hist_exchange="q16"), "hist_exchange=q16 cannot run here"),
], ids=["feature_mesh", "two_axis_mesh", "voting", "codec"])
def test_unhonourable_requests_raise(table, extra, match):
    X, y = table
    with pytest.raises(ValueError, match=match):
        lgb.train(mesh_params(4, **extra), lgb.Dataset(X, label=y), 1,
                  verbose_eval=False)


def test_auto_keeps_the_xla_path_where_the_ladder_cannot_run(table):
    """Without quantized_grad there is no integer sum: ``auto`` under a
    mesh keeps the XLA formulation, as before."""
    X, y = table
    params = mesh_params(4)
    params.update(hist_kernel="auto", quantized_grad=False)
    bst = lgb.train(params, lgb.Dataset(X, label=y), 1, verbose_eval=False,
                    keep_training_booster=True)
    g = bst.gbdt.grower
    assert g.plan.tier == "xla" and not g.plan.mesh_kernels
    assert TELEMETRY.gauges()["grower.hist_kernel"] == "xla"
    assert TELEMETRY.gauges()["grower.hist_exchange_bytes_widest"] == 0


def test_auto_keeps_the_codec_on_the_xla_path(table):
    """``hist_exchange=q16`` with ``hist_kernel=auto`` ran on the XLA
    path before the ladder came under the mesh, and still does; only an
    explicit ``hist_kernel=pallas`` makes the codec an error."""
    X, y = table
    params = mesh_params(4, hist_exchange="q16")
    params.update(hist_kernel="auto")
    bst = lgb.train(params, lgb.Dataset(X, label=y), 1, verbose_eval=False,
                    keep_training_booster=True)
    g = bst.gbdt.grower
    assert g.plan.tier == "xla" and not g.plan.int_counts
    assert g.plan.hist_exchange == "q16"
    assert TELEMETRY.gauges()["grower.hist_kernel"] == "xla"


# -- tracing: the exchange has its own phase, and nothing lies outside ---
def test_the_exchange_is_a_phase_of_the_mesh_chunk(table):
    from jax._src import core as jax_core
    X, y = table
    bst = lgb.train(mesh_params(4), lgb.Dataset(X, label=y), 2,
                    verbose_eval=False, keep_training_booster=True)
    g = bst.gbdt
    args = (g.scores, tuple(), g._full_counts > 0,
            jnp.zeros((2, 2), jnp.uint32),
            jnp.ones((2, 1, g.grower.num_features), bool),
            jnp.zeros(2, bool), g.grower.ohb, g._build_captives())
    jaxpr = jax.make_jaxpr(g._build_fused_chunk(2))(*args)

    def walk(jaxpr, prefix=""):
        for eqn in jaxpr.eqns:
            stack = f"{prefix}/{eqn.source_info.name_stack}"
            subs = list(jax_core.jaxprs_in_params(eqn.params))
            if eqn.primitive.name == "pallas_call":
                subs = []                 # a kernel is one piece of work
            for sub in subs:
                yield from walk(sub, stack)
            if not subs:
                yield eqn.primitive.name, stack
    eqns = list(walk(jaxpr.jaxpr))
    assert [e for e in eqns if "tel." not in e[1]] == []
    psums = [s for name, s in eqns if name == "psum"]
    assert psums and all("tel.hist_exchange" in s for s in psums)
    kernels = [s for name, s in eqns if name == "pallas_call"]
    assert kernels and all("tel.hist_exchange" not in s for s in kernels)
