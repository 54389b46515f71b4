"""The wide table's kernels compiled for a v5e chip that is described,
not attached (nothing runs, no time is read): the TPU's own compiler says
whether a group chunk of the plan fits VMEM at the Epsilon cell's shape,
and a row segment's accumulator at the 2^25-row cell's, which the
interpret seam cannot.  One file, so that one worker loads the
TPU's library; the topology is described inside a fixture."""
import functools
import os

import pytest

ROWS, GROUPS, BINS, LEAVES = 98 * 4096, 2000, 255, 255


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def plan():
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.ops.hist_plan import LADDER_WIDTH, resolve_hist_plan
    return resolve_hist_plan(
        Config.from_params({"verbose": -1, "hist_compute_dtype": "bfloat16",
                            "quantized_grad": True}),
        on_tpu=True, mesh_axes=None, row_axis=None, cols_sharded=False,
        multihost=False, rows_padded=ROWS, num_groups=GROUPS,
        max_group_bin=BINS, packed_groups=0, frontier=LADDER_WIDTH)


def _shapes(one_chip):
    import jax
    import jax.numpy as jnp

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    route_cols = 15 + (BINS + 7) // 8
    return dict(binsT=s((GROUPS, ROWS), jnp.uint8),
                wT=s((3, ROWS), jnp.int32), scales=s((3,), jnp.float32),
                leaf=s((ROWS,), jnp.int32),
                route=s((LEAVES, route_cols), jnp.float32),
                slots=s((126,), jnp.int32), values=s((LEAVES,), jnp.float32))


@pytest.mark.parametrize("k_cap,a", [(126, 2), (2, 4)])
def test_factored_rung_compiles_in_group_chunks(one_chip, plan, k_cap, a):
    """The widest rung and the narrowest (two groups a tile) at 2,000
    groups x 401,408 rows, in the plan's chunks, under the VMEM limit the
    kernel asks for."""
    from lightgbm_tpu.ops.histogram import (
        compact_shape, compute_group_histograms_fused_factored)
    sh = _shapes(one_chip)
    assert plan.group_chunks > 1
    compiled = compute_group_histograms_fused_factored.lower(
        sh["binsT"], sh["wT"], sh["scales"], sh["leaf"], sh["route"],
        sh["slots"], max_group_bin=BINS, k_cap=k_cap, a=a,
        block=plan.block_factored, group_chunk=plan.group_chunk,
        compact=compact_shape(k_cap, plan.block_factored)).compile()
    text = compiled.as_text()
    assert f"compute_group_histograms_fused_factored_k{k_cap}_a{a}" in text
    # the route's split rows, and no copy of the table for them
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 * GROUPS * ROWS


def test_exit_route_compiles_over_split_rows(one_chip, plan):
    import jax
    from lightgbm_tpu.ops.histogram import (gather_split_rows,
                                            route_apply_tiled)
    sh = _shapes(one_chip)

    def route(binsT, leaf, tab, values):
        rowsT, tab = gather_split_rows(binsT, tab)
        return route_apply_tiled(rowsT, leaf, tab, values,
                                 block=plan.block_tiled)
    compiled = jax.jit(route).lower(sh["binsT"], sh["leaf"], sh["route"],
                                    sh["values"]).compile()
    assert "route_apply_tiled" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 256 << 20


@pytest.mark.parametrize("rows,features,scans,int_counts", [
    (252, GROUPS, 1, False), (252, 67, 2, True), (4, GROUPS, 1, False),
    (20, GROUPS, 1, False), (32, GROUPS, 1, False), (4, 67, 2, True)],
    ids=["epsilon_widest_refresh", "two_scans_int32_counts",
         "epsilon_rung_2", "epsilon_rung_10", "epsilon_rung_16",
         "rung_2_int32_counts"])
def test_fused_split_finder_compiles_in_its_block(one_chip, rows, features,
                                                  scans, int_counts):
    """The fused finder at the widest refresh of the cells (252 leaf rows
    x 255 bins): the block ``hist_plan.finder_block`` sizes fits the VMEM
    the kernel asks for (two scans with int32 counts hold the most), and
    at 2,000 features the channel-major view of the ``(R, F, B, 3)``
    operand is no copy (no temporary the size of the histogram)."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.split_kernel import find_numerical_splits_fused
    cfg = dict(lambda_l1=0.0, lambda_l2=0.0, max_delta_step=0.0,
               min_data_in_leaf=1.0, min_sum_hessian_in_leaf=100.0,
               min_gain_to_split=0.0)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    leaf, meta = s((rows,), jnp.float32), s((features,), jnp.int32)
    count = jnp.int32 if int_counts else jnp.float32

    def finder(hist, sg, sh, nd, nb, ms, db, mo, mc, xc, hc=None):
        return find_numerical_splits_fused(hist, sg, sh, nd, nb, ms, db, mo,
                                           mc, xc, cfg, hist_count=hc,
                                           scans=scans)
    args = [s((rows, features, BINS, 3), jnp.float32), leaf, leaf,
            s((rows,), count), meta, meta, meta, meta, leaf, leaf]
    if int_counts:
        args.append(s((rows, features, BINS), jnp.int32))
    compiled = jax.jit(finder).lower(*args).compile()
    assert f"find_numerical_splits_fused_r{rows}" in compiled.as_text()
    if features == GROUPS:
        assert compiled.memory_analysis().temp_size_in_bytes \
            < rows * features * BINS * 4


@pytest.mark.parametrize("k_cap,a", [(126, 2), (2, 4)])
def test_factored_rung_compiles_in_row_segments(one_chip, k_cap, a):
    """The widest rung and the narrowest at the cell past the int8
    ceiling (67 groups x 2^25 rows, two row segments): a segment's
    accumulator is a pipelined block of the kernel's scoped VMEM, in
    its two buffers (2 x 26 MB at 126 slots), inside the limit the
    kernel then asks for; one accumulator a segment comes back."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.ops.hist_plan import LADDER_WIDTH, resolve_hist_plan
    from lightgbm_tpu.ops.histogram import (
        compact_shape, compute_group_histograms_fused_factored)
    rows, groups = 1 << 25, 67
    plan = resolve_hist_plan(
        Config.from_params({"verbose": -1, "hist_compute_dtype": "bfloat16",
                            "quantized_grad": True}),
        on_tpu=True, mesh_axes=None, row_axis=None, cols_sharded=False,
        multihost=False, rows_padded=rows, num_groups=groups,
        max_group_bin=BINS, packed_groups=0, frontier=LADDER_WIDTH)
    assert (plan.row_segments, plan.group_chunks) == (2, 1)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    args = (s((groups, rows), jnp.uint8), s((3, rows), jnp.int32), None,
            s((rows,), jnp.int32),
            s((LEAVES, 15 + (BINS + 7) // 8), jnp.float32),
            s((126,), jnp.int32))
    static = dict(max_group_bin=BINS, k_cap=k_cap, a=a,
                  block=plan.block_factored, group_chunk=plan.group_chunk,
                  dequantize=False, segment_rows=plan.segment_rows,
                  compact=compact_shape(k_cap, plan.block_factored))
    compiled = compute_group_histograms_fused_factored.lower(
        *args, **static).compile()
    assert f"compute_group_histograms_fused_factored_k{k_cap}_a{a}" \
        in compiled.as_text()
    out = jax.eval_shape(functools.partial(
        compute_group_histograms_fused_factored, **static), *args)
    assert out[0].shape == (2, k_cap, groups, BINS, 3)
    assert out[0].dtype == jnp.int32


@pytest.mark.parametrize("k_cap", [32, 64, 126])
def test_compacting_rung_compiles_at_the_criteo_shape(one_chip, k_cap):
    """The compacting rungs at 67 groups x 2^24 rows, one chunk of one
    segment, as the plan shapes them (units of 1,024 rows, steps of 128
    columns): Mosaic takes the lane rotations of the prefix sum, the
    int8 permutation product, the units' counts in SMEM and the ladder
    of dots at dynamic sublanes — what the interpret seam cannot say."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import (
        COMPACT_STEP, COMPACT_UNIT, compact_shape,
        compute_group_histograms_fused_factored)
    import jax
    rows, groups = 1 << 24, 67
    assert compact_shape(k_cap, 4096) == (COMPACT_UNIT, COMPACT_STEP)

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    compiled = compute_group_histograms_fused_factored.lower(
        s((groups, rows), jnp.uint8), s((3, rows), jnp.int32), None,
        s((rows,), jnp.int32),
        s((LEAVES, 15 + (BINS + 7) // 8), jnp.float32),
        s((126,), jnp.int32), max_group_bin=BINS, k_cap=k_cap, a=2,
        block=4096, dequantize=False,
        compact=compact_shape(k_cap, 4096)).compile()
    assert f"compute_group_histograms_fused_factored_k{k_cap}_a2" \
        in compiled.as_text()


def test_tree_program_refreshes_at_the_width_of_its_rung(one_chip,
                                                         monkeypatch):
    """One tree's program at a wide shape, lowered for the chip (PR 36):
    one histogram kernel and one finder kernel a rung, the finder named
    by the rung's 2w leaf rows; nothing pads a histogram to the frontier
    cap W or to 2W, nothing concatenates W slots, and the only 2W-slot
    concatenate is the widest rung's own finder operand (its halves) —
    the glue between a pass and its finder is as wide as the pass."""
    import re
    import jax
    import jax.numpy as jnp
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.learner import grower as grower_module
    from lightgbm_tpu.learner.grower import TreeGrower
    from lightgbm_tpu.ops.histogram import FACTORED_RUNGS
    rows, groups, W = 4096, 256, 126
    monkeypatch.setattr(grower_module, "on_tpu", lambda: True)
    rng = np.random.RandomState(0)
    X = rng.lognormal(size=(rows, groups)).astype(np.float32)
    params = {"objective": "binary", "num_leaves": LEAVES, "max_bin": BINS,
              "verbose": -1, "hist_compute_dtype": "bfloat16",
              "quantized_grad": True, "quant_stochastic_rounding": 1}
    config = Config.from_params(params)
    gr = TreeGrower(lgb.Dataset(X, label=(X[:, 0] > 1).astype(np.float32),
                                params=params).construct(config), config)
    assert gr.plan.tier == "ladder" and not gr.plan.interpret
    assert (gr.frontier, gr.num_groups) == (W, groups)

    def s(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    f32 = jax.ShapeDtypeStruct((gr.n_padded,), jnp.float32,
                               sharding=one_chip)

    def tree(g, h, c, fm, bins, binsT, valid, key, pool):
        return gr._train_tree_impl(g, h, c, fm, None, bins, binsT, valid,
                                   key, pool)
    text = jax.jit(tree, donate_argnums=8).lower(
        f32, f32, f32, s(np.ones(gr.num_features, bool)), s(gr.bins),
        s(gr.binsT), s(gr._row_valid), s(jax.random.PRNGKey(0)),
        s(jax.eval_shape(gr._zero_hist_cache))).as_text()
    for k_cap, a, _ in FACTORED_RUNGS:
        assert len(re.findall(
            rf"compute_group_histograms_fused_factored_k{k_cap}_a{a}\b",
            text)) == 1
        assert len(re.findall(
            rf"find_numerical_splits_fused_r{2 * k_cap}\b", text)) == 1
    assert len(set(re.findall(r"find_numerical_splits_fused_r\d+",
                              text))) == len(FACTORED_RUNGS)

    def wide(op, slots):
        return re.findall(rf"stablehlo\.{op}.*-> "
                          rf"tensor<{slots}x{groups}x{BINS}x3xf32>", text)
    assert not wide("pad", W) and not wide("pad", 2 * W)
    assert not wide("concatenate", W)
    assert len(wide("concatenate", 2 * W)) == 1
