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
    # rows * 127 < 2^31: 16513 blocks of 1024 rows fit, 16514 do not
    ("tpu_quant_last_block_inside_int32", FAST,
     {**TPU, **CRITEO, "rows_padded": 16513 * 1024},
     dict(tier="ladder", block_factored=1024, silent=True)),
    ("tpu_quant_one_block_past_int32", FAST,
     {**TPU, **CRITEO, "rows_padded": 16514 * 1024},
     dict(tier="float", quantized=False,
          warns=["quantized_grad disabled: dataset exceeds the int32"])),
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
     dict(raises="can overflow the int32 histogram accumulator")),
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
    assert plan.int_counts == plan.mesh_kernels
    assert plan.fused or plan.tier != "ladder"
    assert bool(plan.onehot_pack) <= (plan.tier == "float")
    assert plan.local_rows * plan.row_shards == facts["rows_padded"]
    assert plan.num_groups == facts["num_groups"]
    assert plan.group_chunk * plan.group_chunks >= plan.num_groups \
        > plan.group_chunk * (plan.group_chunks - 1)
    assert plan.group_chunks == 1 or plan.group_chunk % 32 == 0
    with pytest.raises(AttributeError):     # immutable
        plan.tier = "xla"


def _plan(**facts):
    return resolve_hist_plan(Config.from_params({"verbose": -1, **FAST}),
                             **{**FACTS, **TPU, **facts})


@pytest.mark.fast
def test_criteo_plan_is_the_one_before_the_group_chunk():
    """67 groups x 2^24 rows resolve to ONE chunk and, the group chunk's
    two fields apart, to the plan the cells had before it, field for
    field."""
    got = dataclasses.asdict(_plan(**CRITEO))
    assert (got.pop("group_chunk"), got.pop("num_groups")) == (67, 67)
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
