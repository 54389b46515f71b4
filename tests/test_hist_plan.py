"""The histogram kernel plan (lightgbm_tpu/ops/hist_plan.py): every
decision ``resolve_hist_plan`` makes, from plain facts — no Dataset, no
device array, no training run."""
import dataclasses

import pytest

from lightgbm_tpu.config import Config
from lightgbm_tpu.ops import hist_plan
from lightgbm_tpu.ops.hist_plan import (LADDER_WIDTH, ONEHOT_BUDGET_MB,
                                        factored_vmem_bytes,
                                        resolve_hist_plan)
from lightgbm_tpu.ops.histogram import CHUNK_VMEM_LIMIT, FACTORED_RUNGS

FAST = {"hist_compute_dtype": "bfloat16", "quantized_grad": True}
BF16 = {"hist_compute_dtype": "bfloat16"}
SEAM = {"force_pallas_interpret": True}
TPU = {"on_tpu": True}
HIGGS = {"num_groups": 28, "max_group_bin": 63, "rows_padded": 1 << 20}
CRITEO = {"num_groups": 67, "max_group_bin": 255, "rows_padded": 1 << 24}
EPSILON = {"num_groups": 2000, "max_group_bin": 255,
           "rows_padded": 98 * 4096}
ROW_MESH = {"mesh_axes": (("data", 4),), "row_axis": "data"}
DATA = {"tree_learner": "data"}

FACTS = dict(on_tpu=False, mesh_axes=None, row_axis=None,
             cols_sharded=False, multihost=False, packed_groups=0,
             frontier=LADDER_WIDTH, **HIGGS)

# (id, params, facts, what the plan must show); in ``want``, "raises" /
# "warns" are substrings of the error / of one warning each, "silent"
# asks for no warning at all, everything else is an attribute's value
CASES = [
    ("cpu_defaults", {}, {},
     dict(tier="xla", kernel="xla", quantized=False, fused=False,
          row_shards=1, exchange_limbs=0, silent=True)),
    ("seam_bf16_quant", {**FAST, **SEAM}, {},
     dict(tier="ladder", kernel="fused_tiled", interpret=True,
          quantized=True, fused=True, int_counts=False, silent=True)),
    ("tpu_quant_cell_shape", FAST, {**TPU, **CRITEO},
     dict(tier="ladder", interpret=False, block_tiled=2048,
          block_factored=4096, factored_rungs=FACTORED_RUNGS,
          local_rows=1 << 24, group_chunk=67, group_chunks=1,
          silent=True)),
    # 2,000 groups: the group axis is a grid axis, in whole tiles of
    # uint8 sublanes, and the route kernel's block holds the split rows
    ("tpu_quant_wide_table", FAST, {**TPU, **EPSILON},
     dict(tier="ladder", block_factored=4096, block_tiled=2048,
          factored_rungs=FACTORED_RUNGS, group_chunk=96, group_chunks=21,
          num_groups=2000, silent=True)),
    # narrower tiles have no rung, so no chunk: A12's mechanism
    ("tpu_quant_wide_table_63_bins", FAST,
     {**TPU, **EPSILON, "max_group_bin": 63},
     dict(tier="ladder", factored_rungs=(), group_chunk=2000,
          group_chunks=1)),
    # rows * 127 < 2^31: 16513 blocks of 1024 rows fit one int32
    # accumulator, 16514 do not; past 2^24 rows either is summed in two
    # segments of at most 2^24, and the ladder stays (PR 35)
    ("tpu_quant_last_block_inside_int32", FAST,
     {**TPU, **CRITEO, "rows_padded": 16513 * 1024},
     dict(tier="ladder", block_factored=1024, row_segments=2,
          silent=True)),
    ("tpu_quant_one_block_past_int32", FAST,
     {**TPU, **CRITEO, "rows_padded": 16514 * 1024},
     dict(tier="ladder", quantized=True, row_segments=2,
          segment_rows=1 << 24, int_counts=True, block_factored=2048,
          silent=True)),
    ("tpu_bf16_onehot_inside_budget", BF16, {**TPU},
     dict(tier="float", kernel="fused_streamed", fused=True,
          onehot_pack=4, block_float=2048, factored_rungs=(),
          silent=True)),
    ("tpu_bf16_onehot_over_budget", BF16, {**TPU, **CRITEO},
     dict(tier="float", kernel="pallas", fused=False, onehot_pack=0,
          warns=[f"exceeds the {ONEHOT_BUDGET_MB} MB budget"])),
    ("tpu_bf16_wide_frontier_streams_unfused", BF16,
     {**TPU, "frontier": 200},
     dict(tier="float", kernel="pre_onehot", fused=False, onehot_pack=4)),
    ("tpu_bf16_packed_bins_over_budget", BF16,
     {**TPU, "num_groups": 67, "max_group_bin": 15, "packed_groups": 67,
      "rows_padded": 1 << 24},
     dict(tier="xla", warns=[f"exceeds the {ONEHOT_BUDGET_MB} MB budget",
                             "no nibble-packed input path"])),
    ("tpu_float32_operands", {"quantized_grad": True}, {**TPU},
     dict(tier="xla", quantized=False, silent=True)),
    ("hist_kernel_xla_over_fast_params", {**FAST, "hist_kernel": "xla"},
     {**TPU}, dict(tier="xla", silent=True)),
    ("unknown_hist_kernel_is_auto", {**FAST, "hist_kernel": "paired"},
     {**TPU}, dict(tier="ladder", warns=["unknown hist_kernel='paired'"])),
    ("pallas_off_chip", {"hist_kernel": "pallas"}, {},
     dict(raises="hist_kernel=pallas cannot run here")),
    ("rows_not_1024_auto", FAST, {**TPU, "rows_padded": 8192 + 512},
     dict(tier="xla", silent=True)),
    ("rows_not_1024_pallas", {**FAST, "hist_kernel": "pallas"},
     {**TPU, "rows_padded": 8192 + 512},
     dict(raises="rows padded to 1024 a shard")),
    ("row_mesh_quant", {**FAST, **DATA},
     {**TPU, **ROW_MESH, **CRITEO, "rows_padded": 1 << 26},
     dict(tier="ladder", row_axis="data", row_shards=4,
          local_rows=1 << 24, mesh_kernels=True, int_counts=True,
          exchange_limbs=2, block_factored=4096, group_chunks=1,
          silent=True)),
    ("row_mesh_quant_one_limb", {**FAST, **DATA},
     {**TPU, **ROW_MESH, **CRITEO},
     dict(tier="ladder", local_rows=1 << 22, exchange_limbs=1)),
    ("row_mesh_serial_learner_qualifies", FAST,
     {**TPU, **ROW_MESH}, dict(tier="ladder", mesh_kernels=True)),
    ("row_mesh_no_quant_auto", {**BF16, **DATA}, {**TPU, **ROW_MESH},
     dict(tier="xla", row_shards=4, mesh_kernels=False,
          int_counts=False, exchange_limbs=0, silent=True)),
    ("row_mesh_no_quant_pallas", {**BF16, **DATA, "hist_kernel": "pallas"},
     {**TPU, **ROW_MESH},
     dict(raises="under a mesh only the quantized fused ladder runs")),
    ("row_mesh_codec_auto", {**FAST, **DATA, "hist_exchange": "q16"},
     {**TPU, **ROW_MESH},
     dict(tier="xla", hist_exchange="q16", silent=True)),
    ("row_mesh_codec_pallas",
     {**FAST, **DATA, "hist_exchange": "q16", "hist_kernel": "pallas"},
     {**TPU, **ROW_MESH}, dict(raises="hist_exchange=q16 cannot run here")),
    ("row_mesh_wide_frontier_tiered",
     {**BF16, **DATA, "hist_precision": "tiered"},
     {**TPU, **ROW_MESH, "frontier": 200},
     dict(raises="under a mesh only the quantized fused ladder runs")),
    ("feature_mesh", {**FAST, "tree_learner": "feature"},
     {**TPU, "mesh_axes": (("feature", 4),), "cols_sharded": True},
     dict(tier="xla", row_axis=None, row_shards=1, silent=True)),
    ("voting_mesh", {**FAST, "tree_learner": "voting"},
     {**TPU, **ROW_MESH}, dict(tier="xla", row_axis=None, silent=True)),
    ("two_axis_mesh", {**FAST, **DATA},
     {**TPU, "mesh_axes": (("data", 2), ("feature", 2)),
      "row_axis": "data"}, dict(tier="xla", row_shards=1, silent=True)),
    ("multihost_row_mesh", {**FAST, **DATA},
     {**TPU, **ROW_MESH, "multihost": True},
     dict(tier="xla", row_axis=None, silent=True)),
    ("feature_mesh_pallas",
     {**FAST, "tree_learner": "feature", "hist_kernel": "pallas"},
     {**TPU, "mesh_axes": (("feature", 4),), "cols_sharded": True},
     dict(raises="hist_kernel=pallas cannot run here")),
    ("tiered_without_the_kernel_path", {"hist_precision": "tiered"}, {},
     dict(raises="hist_precision=tiered cannot run here")),
    ("tiered_is_quantized_grad", {**BF16, **SEAM,
                                  "hist_precision": "tiered"}, {},
     dict(tier="ladder", quantized=True, silent=True)),
    ("tiered_past_int32", {**BF16, "hist_precision": "tiered"},
     {**TPU, "rows_padded": 1 << 25},
     dict(tier="ladder", quantized=True, row_segments=2,
          segment_rows=1 << 24, int_counts=True, mesh_kernels=False,
          exchange_limbs=0, silent=True)),
    ("f32_over_quantized_grad", {**FAST, "hist_precision": "f32"}, {**TPU},
     dict(tier="float", quantized=False,
          warns=["hist_precision=f32: quantized_grad ignored"])),
    ("nibble_packed_quant", FAST,
     {**TPU, "max_group_bin": 15, "packed_groups": 28},
     dict(tier="ladder", factored_rungs=(), silent=True)),
    ("bins_63_no_rungs", FAST, {**TPU},
     dict(tier="ladder", factored_rungs=(), block_tiled=8192,
          block_factored=4096)),
    ("bins_255_six_rungs", FAST, {**TPU, "max_group_bin": 255},
     dict(tier="ladder", factored_rungs=FACTORED_RUNGS)),
    ("wide_frontier_quant_auto", FAST, {**TPU, "frontier": 200},
     dict(tier="xla", quantized=False,
          warns=["quantized_grad with frontier_width=200"])),
    ("wide_frontier_quant_pallas", {**FAST, "hist_kernel": "pallas"},
     {**TPU, "frontier": 200},
     dict(raises=f"serves at most {LADDER_WIDTH} splits a round")),
    ("wide_frontier_tiered", {**BF16, "hist_precision": "tiered"},
     {**TPU, "frontier": 200},
     dict(raises=f"serves at most {LADDER_WIDTH} splits a round")),
    ("blocks_follow_the_shard_rows", FAST,
     {**TPU, "rows_padded": 3 * 1024},
     dict(tier="ladder", block_float=1024, block_tiled=1024,
          block_factored=1024)),
]


@pytest.mark.fast
@pytest.mark.parametrize("params,facts,want", [c[1:] for c in CASES],
                         ids=[c[0] for c in CASES])
def test_resolve_hist_plan(params, facts, want):
    config = Config.from_params({"verbose": -1, **params})
    facts = {**FACTS, **facts}
    want = dict(want)
    if "raises" in want:
        with pytest.raises(ValueError, match=want["raises"]):
            resolve_hist_plan(config, **facts)
        return
    plan = resolve_hist_plan(config, **facts)
    for text in want.pop("warns", []):
        assert any(text in w for w in plan.warnings), plan.warnings
    if want.pop("silent", False):
        assert plan.warnings == ()
    for name, value in want.items():
        assert getattr(plan, name) == value, (name, plan)
    # what holds for every plan
    assert plan.tier in ("xla", "float", "ladder")
    assert plan.quantized == (plan.tier == "ladder")
    assert plan.int_counts == (plan.mesh_kernels or plan.row_segments > 1)
    assert plan.row_segments == 1 or plan.tier == "ladder"
    assert (plan.row_segments - 1) * plan.segment_rows < plan.local_rows \
        <= plan.row_segments * plan.segment_rows
    assert plan.fused or plan.tier != "ladder"
    assert bool(plan.onehot_pack) <= (plan.tier == "float")
    assert plan.local_rows * plan.row_shards == facts["rows_padded"]
    assert plan.num_groups == facts["num_groups"]
    assert plan.group_chunk * plan.group_chunks >= plan.num_groups \
        > plan.group_chunk * (plan.group_chunks - 1)
    assert plan.group_chunks == 1 or plan.group_chunk % 32 == 0
    assert plan.finder == ("xla" if plan.tier == "xla" else "fused")
    with pytest.raises(AttributeError):     # immutable
        plan.tier = "xla"


def _plan(**facts):
    return resolve_hist_plan(Config.from_params({"verbose": -1, **FAST}),
                             **{**FACTS, **TPU, **facts})


@pytest.mark.fast
def test_criteo_plan_is_the_one_before_the_group_chunk():
    """67 groups x 2^24 rows resolve to ONE chunk of ONE row segment
    and, the group chunk's and the segment's two fields each apart, to
    the plan the cells had before them, field for field."""
    got = dataclasses.asdict(_plan(**CRITEO))
    assert (got.pop("group_chunk"), got.pop("num_groups")) == (67, 67)
    assert (got.pop("row_segments"), got.pop("segment_rows")) \
        == (1, 1 << 24)
    assert got.pop("finder") == "fused"
    assert got.pop("compact_rungs") == (32, 64, 126)
    assert got == dict(
        tier="ladder", interpret=False, row_axis=None, row_shards=1,
        local_rows=1 << 24, mesh_kernels=False, exchange_limbs=0,
        hist_exchange="f32", fused=True, onehot_pack=0, block_float=2048,
        block_tiled=2048, block_factored=4096,
        factored_rungs=FACTORED_RUNGS, warnings=())


@pytest.mark.fast
@pytest.mark.parametrize("groups", [200, 2000, 5000])
def test_group_chunk_fits_the_budget_the_plan_states(groups, monkeypatch):
    """More than one chunk where the widest rung's whole accumulator
    passes the budget; a chunk's own VMEM bytes lie under the budget,
    the budget under what the kernel asks of the compiler, and another
    32 groups would not fit.  The budget is the module's constant: a
    smaller one gives smaller chunks, down to one tile of sublanes."""
    widest = FACTORED_RUNGS[-1]
    plan = _plan(**{**EPSILON, "num_groups": groups})
    assert plan.group_chunks > 1
    assert factored_vmem_bytes(widest, groups, plan.block_factored,
                               False) > hist_plan.CHUNK_VMEM_BUDGET

    def cost(chunk):
        return factored_vmem_bytes(widest, chunk, plan.block_factored, True)
    assert cost(plan.group_chunk) <= hist_plan.CHUNK_VMEM_BUDGET \
        < cost(plan.group_chunk + 32)
    assert hist_plan.CHUNK_VMEM_BUDGET < CHUNK_VMEM_LIMIT <= 128 << 20
    monkeypatch.setattr(hist_plan, "CHUNK_VMEM_BUDGET", 40 << 20)
    assert _plan(**{**EPSILON, "num_groups": groups}).group_chunk == 32
    monkeypatch.setattr(hist_plan, "CHUNK_VMEM_BUDGET", 1 << 20)
    assert _plan(**{**EPSILON, "num_groups": groups}).group_chunk == 32
    assert _plan(**CRITEO).group_chunk == 32


# -- the compacting rungs: the rung table's, and counted in VMEM ----------
@pytest.mark.fast
@pytest.mark.parametrize("params,facts,want", [
    (FAST, {**TPU, **CRITEO}, (32, 64, 126)),
    (FAST, {**TPU, **EPSILON}, (32, 64, 126)),
    ({**FAST, **SEAM}, CRITEO, (32, 64, 126)),
    ({**FAST, **DATA}, {**TPU, **ROW_MESH, **CRITEO,
                        "rows_padded": 4 << 24}, (32, 64, 126)),
    # no rung, nothing to compact: narrow tiles, the float and xla tiers
    (FAST, {**TPU, **EPSILON, "max_group_bin": 63}, ()),
    (BF16, {**TPU}, ()),
    ({}, {}, ()),
], ids=["criteo", "epsilon", "seam", "row_mesh", "63_bins", "float_tier",
        "xla_tier"])
def test_compacting_rungs_follow_from_the_rung_table(params, facts, want,
                                                     monkeypatch):
    """Which rungs compact is the rung table's fact: the rungs in force
    whose slot cap ``histogram.COMPACT_RUNGS`` names — no parameter
    reaches it (every ``Config`` field may vary: the plans above differ
    in tier, mesh and seam) — and an empty constant leaves a plan that
    differs in nothing else."""
    from lightgbm_tpu.ops import histogram
    config = Config.from_params({"verbose": -1, **params})
    plan = resolve_hist_plan(config, **{**FACTS, **facts})
    assert plan.compact_rungs == want
    assert set(want) <= {k for k, _, _ in plan.factored_rungs}
    assert not [f for f in dataclasses.fields(Config)
                if "compact" in f.name]
    monkeypatch.setattr(histogram, "COMPACT_RUNGS", ())
    bare = resolve_hist_plan(config, **{**FACTS, **facts})
    assert bare == dataclasses.replace(plan, compact_rungs=())


@pytest.mark.fast
def test_compaction_scratch_is_counted_and_keeps_the_cells_chunks():
    """``factored_vmem_bytes`` counts what a compacting rung holds
    besides (the weight rows, a unit's permutation and moved rows): more
    than the uncompacted body's, 67 groups still in one chunk (in row
    segments too) and ``epsilon-2000``'s chunk, 96 groups as before,
    under the budget."""
    from lightgbm_tpu.ops import histogram
    widest = FACTORED_RUNGS[-1]
    assert histogram.compact_shape(widest[0], 4096) \
        == (histogram.COMPACT_UNIT, histogram.COMPACT_STEP)
    assert histogram.compact_shape(widest[0], 512) \
        == (512, histogram.COMPACT_STEP)
    assert histogram.compact_shape(16, 4096) == ()
    for groups, chunked, segmented in [(67, False, False),
                                       (67, False, True), (96, True, False)]:
        cost = factored_vmem_bytes(widest, groups, 4096, chunked, segmented)
        assert cost <= hist_plan.CHUNK_VMEM_BUDGET
    assert _plan(**CRITEO).group_chunk == 67
    assert _plan(**{**CRITEO, "rows_padded": 1 << 25}).group_chunk == 67
    assert _plan(**EPSILON).group_chunk == 96
    # the narrow rungs' bytes are what they were
    assert factored_vmem_bytes(FACTORED_RUNGS[2], 67, 4096, False) \
        == 67 * 4 * 24 * 128 * 4 + 67 * 30 * 4096


def _right_child_rows_by_depth(tree):
    """[(splits, rows of their right children)] a depth of one tree of
    ``dump_model()``."""
    levels = {}

    def walk(node, depth):
        if "left_child" in node:
            right = node["right_child"]
            n, rows = levels.get(depth, (0, 0))
            levels[depth] = (n + 1, rows + right.get(
                "internal_count", right.get("leaf_count")))
            walk(node["left_child"], depth + 1)
            walk(right, depth + 1)
    walk(tree["tree_structure"], 0)
    return [levels[d] for d in sorted(levels)]


def test_compaction_gauges_are_published_and_are_the_trees_counts():
    """``grower.hist_compact_rungs`` at grower set-up, and, when the
    trees reach the host, ``hist_active_row_share``: rows of the new
    right children over rows streamed, in the passes of the compacting
    rungs — on a small table, where a depth is a round, the trees' own
    right-child counts (a depth of 17-32 splits is a pass of the rung
    at 32 slots, of 33-64 the one at 64; the last depth has no pass,
    the tree ended on its leaf budget)."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.telemetry import TELEMETRY
    import numpy as np
    rng = np.random.RandomState(3)
    X = rng.lognormal(size=(2048, 7)).astype(np.float32)
    y = (X[:, 0] * X[:, 1] - X[:, 2] + 0.3 * rng.randn(2048)
         > 0.5).astype(float)
    try:
        TELEMETRY.reset()
        bst = lgb.train(
            {"objective": "binary", "verbose": -1, "num_leaves": 100,
             "min_data_in_leaf": 2, "max_bin": 255, **FAST, **SEAM,
             "quant_stochastic_rounding": 1, "telemetry": "counters"},
            lgb.Dataset(X, label=y), 3, verbose_eval=False)
        gauges, counters = TELEMETRY.gauges(), TELEMETRY.counters()
    finally:
        TELEMETRY.configure("off")
        TELEMETRY.reset()
    # (the plan's, as grower.hist_factored_rungs: a frontier of 99 slots
    # traces the rungs up to 64 and then strips, which do not compact)
    assert gauges["grower.hist_compact_rungs"] == "32,64,126"
    active = streamed = 0
    for tree in bst.dump_model()["tree_info"]:
        assert tree["num_leaves"] == 100
        levels = _right_child_rows_by_depth(tree)
        assert levels[0] == (1, levels[0][1]) and len(levels) >= 7
        for splits, rows in levels[:-1]:
            if 16 < splits <= 64:
                active, streamed = active + rows, streamed + 2048
    assert streamed >= 3 * 2048
    assert counters["hist_compact_active_rows"] == active
    assert counters["hist_compact_streamed_rows"] == streamed
    assert 0 < gauges["hist_active_row_share"] == active / streamed < 1


# -- the split finder's form: decided here, once ------------------------
@pytest.mark.fast
@pytest.mark.parametrize("params,facts,finder,interpret", [
    ({}, {}, "xla", False),
    ({**FAST, **SEAM}, {}, "fused", True),
    (FAST, {**TPU, **CRITEO}, "fused", False),
    (FAST, {**TPU, **EPSILON}, "fused", False),
    (BF16, {**TPU}, "fused", False),
    ({**FAST, **DATA}, {**TPU, **ROW_MESH, **CRITEO}, "fused", False),
    ({**FAST, "hist_kernel": "xla"}, {**TPU}, "xla", False),
    ({**FAST, "tree_learner": "voting"}, {**TPU, **ROW_MESH}, "xla", False),
    ({**FAST, "tree_learner": "feature"},
     {**TPU, "mesh_axes": (("feature", 4),), "cols_sharded": True},
     "xla", False),
], ids=["cpu_defaults", "seam", "criteo_cell", "epsilon_cell", "float_tier",
        "row_mesh", "hist_kernel_xla", "voting_mesh", "feature_mesh"])
def test_finder_form_follows_the_tier(params, facts, finder, interpret):
    """Fused wherever the plan runs Pallas kernels, with their interpret
    seam; the XLA form on the ``xla`` tier.  No option names it."""
    plan = resolve_hist_plan(Config.from_params({"verbose": -1, **params}),
                             **{**FACTS, **facts})
    assert (plan.finder, plan.interpret) == (finder, interpret)
    assert not [f for f in Config.__dataclass_fields__ if "finder" in f
                and f != "split_finder_ladder"]


def _grower(X, y, categorical_feature="auto", **params):
    import lightgbm_tpu as lgb
    from lightgbm_tpu.learner.grower import TreeGrower
    config = Config.from_params({"objective": "binary", "verbose": -1,
                                 "min_data_in_leaf": 5, **params})
    return TreeGrower(lgb.Dataset(
        X, label=y, params=params,
        categorical_feature=categorical_feature).construct(config), config)


def _table(rows=600, features=6, seed=0):
    import numpy as np
    rng = np.random.RandomState(seed)
    X = rng.randn(rows, features).astype(np.float32)
    return X, (X[:, 0] - X[:, 1] > 0).astype(np.float32)


def _identity_case(name, tmp_path):
    """(grower, whether its finder may read the group histogram)."""
    import json
    import numpy as np
    X, y = _table()
    if name == "plain_dense_table":
        return _grower(X, y), True
    if name == "efb_bundles":
        # mutually exclusive sparse columns share a group, their
        # defaults collapsed into its slot 0
        rng = np.random.RandomState(1)
        X = np.zeros((600, 6), np.float32)
        X[np.arange(600), rng.randint(0, 6, 600)] = rng.rand(600) + 1.0
        grower = _grower(X, y, enable_bundle=True)
        assert grower.num_groups < grower.num_features
        assert (np.asarray(grower.fix_bin) >= 0).any()
        return grower, False
    if name == "categorical_feature":
        X[:, 2] = np.random.RandomState(2).randint(0, 5, 600)
        grower = _grower(X, y, categorical_feature=[2])
        assert grower.has_categorical
        return grower, False
    assert name == "forced_splits"
    fn = str(tmp_path / "forced.json")
    with open(fn, "w") as f:
        json.dump({"feature": 0, "threshold": 0.0}, f)
    grower = _grower(X, y, forcedsplits_filename=fn)
    assert grower.forced_count == 1
    return grower, False


@pytest.mark.parametrize("name", ["plain_dense_table", "efb_bundles",
                                  "categorical_feature", "forced_splits"])
def test_finder_identity_map_is_read_off_the_table(name, tmp_path,
                                                   monkeypatch):
    """Where every feature is its own group, bin for bin, the finder
    reads the group histogram and ``expand_feature_histograms`` is not
    traced: a property of the table, found at set-up."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.telemetry import TELEMETRY
    TELEMETRY.configure("counters")
    try:
        grower, want = _identity_case(name, tmp_path)
        gauges = TELEMETRY.gauges()
    finally:
        TELEMETRY.configure("off")
        TELEMETRY.reset()
    assert grower.finder_identity is want
    assert gauges["grower.finder_identity_map"] == int(want)
    assert gauges["grower.split_finder"] == "xla"
    assert gauges["grower.finder_scans"] in (1, 2)
    from lightgbm_tpu.learner import grower as grower_module
    expands = []

    def expand(*args):
        expands.append(args[0].shape)
        return grower_module.histogram_expand(*args)
    monkeypatch.setattr(grower_module, "histogram_expand",
                        grower_module.expand_feature_histograms,
                        raising=False)
    monkeypatch.setattr(grower_module, "expand_feature_histograms", expand)
    ones = jnp.ones(grower.n_padded, jnp.float32)
    jax.make_jaxpr(grower._train_tree_impl)(
        ones * 0.5, ones, ones, jnp.ones(grower.num_features, bool))
    assert bool(expands) != want


@pytest.mark.parametrize("params,leaves,want", [
    ({"max_bin": 255}, 255, None),              # the rungs' caps
    ({"max_bin": 255}, 100, "2,10,16,32,64,84,99"),
    ({"max_bin": 63}, 255, "42,84,126"),        # strips alone
    ({"max_bin": 255, "split_finder_ladder": False}, 255,
     ",".join(["126"] * 6)),
    ({"max_bin": 255, "quantized_grad": False,
      "force_pallas_interpret": False}, 255, "42,84,126"),
], ids=["rungs", "rungs_then_strips", "strips", "ladder_off", "xla_tier"])
def test_refresh_widths_are_the_widths_of_the_pass(params, leaves, want):
    """One ladder (PR 36): the widths a round's refresh can take are the
    slot caps of the passes the plan has — the factored rungs', then the
    strips' — and the gauge ``grower.refresh_widths`` says so when the
    tree program is traced; with the ladder off every rung refreshes at
    the frontier cap."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.telemetry import TELEMETRY
    X, y = _table(rows=1024)
    TELEMETRY.configure("counters")
    try:
        grower = _grower(X, y, **{
            "num_leaves": leaves, "quantized_grad": True,
            "hist_compute_dtype": "bfloat16",
            "force_pallas_interpret": True, **params})
        ones = jnp.ones(grower.n_padded, jnp.float32)
        jax.make_jaxpr(grower._train_tree_impl)(
            ones * 0.5, ones, ones, jnp.ones(grower.num_features, bool),
            qkey=jax.random.PRNGKey(0))
        gauges = TELEMETRY.gauges()
    finally:
        TELEMETRY.configure("off")
        TELEMETRY.reset()
    if want is None:
        assert grower.plan.factored_rungs == FACTORED_RUNGS
        want = ",".join(str(k) for k, _, _ in FACTORED_RUNGS)
        assert gauges["grower.hist_factored_rungs"].split(",")[-1] \
            .startswith(f"{grower.frontier}:")
    assert gauges["grower.refresh_widths"] == want
    assert int(want.split(",")[-1]) == grower.frontier


@pytest.mark.fast
def test_finder_identity_map_cases_of_the_map_itself():
    import numpy as np
    from lightgbm_tpu.ops.hist_plan import finder_identity_map
    own = np.arange(4)[:, None] * 10 + np.arange(8)[None, :]
    none = np.full(4, -1)
    ragged = np.where(np.arange(8)[None, :] < np.array([8, 3, 5, 2])[:, None],
                      own, -1)
    ok = dict(num_groups=4, max_group_bin=10, has_categorical=False,
              forced_splits=False)
    assert finder_identity_map(own, none, **ok)
    assert finder_identity_map(ragged, none, **ok)
    # a collapsed default: its bin is rebuilt from the leaf's totals
    assert not finder_identity_map(ragged, np.array([-1, 0, -1, -1]), **ok)
    # a bundle member: its bins sit at an offset in another's group
    shifted = own.copy()
    shifted[2] = own[1] + 3
    assert not finder_identity_map(shifted, none, **ok)
    assert not finder_identity_map(own, none, **{**ok, "num_groups": 3})
    assert not finder_identity_map(own, none, **{**ok, "max_group_bin": 7})
    assert not finder_identity_map(own, none,
                                   **{**ok, "has_categorical": True})
    assert not finder_identity_map(own, none, **{**ok, "forced_splits": True})


LEARNERS = {
    "serial": {**FAST, **SEAM},
    "data_parallel_kernels": {**FAST, **SEAM, **DATA, "mesh_shape": [4],
                              "mesh_axes": ["data"],
                              "hist_kernel": "pallas"},
    "voting": {"tree_learner": "voting", "top_k": 4},
    "feature_parallel": {"tree_learner": "feature"},
}


@pytest.mark.parametrize("learner", list(LEARNERS))
def test_every_learner_grows_the_same_trees_with_either_finder(
        learner, monkeypatch):
    """One small job a learner, its finder's form forced each way in the
    plan (no option does that): the same split features and thresholds
    on a table whose gains are well apart."""
    import re
    import jax
    import numpy as np
    import lightgbm_tpu as lgb
    from lightgbm_tpu.learner import grower as grower_module
    if learner != "serial" and len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    rng = np.random.RandomState(5)
    X = np.exp(rng.randn(4096, 8)).astype(np.float32)
    y = (np.log(X[:, 0]) * 2 - np.log(X[:, 1]) + np.log(X[:, 2]) * 0.5
         + 0.05 * rng.randn(4096) > 0).astype(np.float32)
    params = {"objective": "binary", "num_leaves": 8, "max_bin": 63,
              "verbose": -1, "min_data_in_leaf": 20,
              "quant_stochastic_rounding": 1, **LEARNERS[learner]}
    seen = []

    def plan_with(form):
        def resolve(*args, **kwargs):
            plan = resolve_hist_plan(*args, **kwargs)
            seen.append((learner, plan.tier, form))
            return dataclasses.replace(
                plan, finder=form, interpret=plan.interpret
                or form == "fused")
        return resolve

    def splits(form):
        monkeypatch.setattr(grower_module, "resolve_hist_plan",
                            plan_with(form))
        bst = lgb.train(params, lgb.Dataset(X, label=y), 2,
                        keep_training_booster=True)
        assert bst.gbdt.grower.finder.form == form
        text = bst.model_to_string()
        return (re.findall(r"^split_feature=.*$", text, re.M),
                [np.array(t.split("=")[1].split(), float) for t in
                 re.findall(r"^threshold=.*$", text, re.M)])

    (feat_x, thr_x), (feat_f, thr_f) = splits("xla"), splits("fused")
    assert feat_x == feat_f and len(feat_x) == 2
    for a, b in zip(thr_x, thr_f):
        np.testing.assert_array_equal(a, b)
    assert {s[2] for s in seen} == {"xla", "fused"}
