"""Byte-identical-tree parity for the packed single-buffer tree carry
(round 7), on the interpret-mode CPU seam — the container-side half of
the protocol whose on-chip half is the chunk-90 A/B flag
(dispatch_chunk / docs/ROOFLINE.md round 7).

The packed carry changes the fused dispatch scan's OUTPUT layout (one
uint8 record stack vs 18 per-field stacks) and the chunk length
changes how many iterations share one device program; neither may
change a single tree byte (the identical-trees pattern of
tests/test_histogram_kernel.py)."""
import numpy as np
import pytest

import lightgbm_tpu as lgb

BASE = {"objective": "binary", "num_leaves": 7, "verbose": -1,
        "quantized_grad": True, "hist_compute_dtype": "bfloat16",
        "force_pallas_interpret": True, "min_data_in_leaf": 2,
        # small shapes: interpret-mode kernels pay per (row, bin) on
        # the CPU seam and this file trains 90 rounds seven times —
        # parity is about byte layout, not statistical capacity
        "max_bin": 63}
ROUNDS = 90          # enough that dispatch_chunk=90 runs as ONE chunk


def _data():
    rng = np.random.RandomState(3)
    X = rng.randn(256, 5)
    y = (X[:, 0] + 0.5 * X[:, 1] * X[:, 2] > 0).astype(float)
    return X, y


def _model(X, y, **over):
    m = lgb.train(dict(BASE, **over), lgb.Dataset(X, label=y), ROUNDS,
                  verbose_eval=False)
    return m.model_to_string()


@pytest.fixture(scope="module")
def ref_model():
    """The dispatch_chunk=1 packed-carry model every parity test
    compares against (trained once for the module)."""
    X, y = _data()
    return X, y, _model(X, y, dispatch_chunk=1)


def test_packed_vs_legacy_carry_single_point(ref_model):
    """The fast tier-1 pin: packed vs the legacy 18-array carry at the
    default chunking grows byte-identical models (the full six-way
    (carry, chunk) sweep is the slow-tier test below)."""
    X, y, ref = ref_model
    assert _model(X, y, dispatch_chunk=10,
                  packed_tree_carry="off") == ref


# re-tiered slow (tier-1 wall budget): five extra trainings sweeping
# redundant (carry, chunk) combinations; the unique packed-vs-legacy
# pin stays fast in test_packed_vs_legacy_carry_single_point
@pytest.mark.slow
def test_packed_vs_legacy_carry_across_chunk_sizes(ref_model):
    """All six (carry, chunk) combinations grow byte-identical models:
    packed vs the legacy 18-array carry, across dispatch_chunk 1 / 10 /
    90 (one-iteration chunks, the default, and one 90-iteration fused
    program)."""
    X, y, ref = ref_model
    for chunk in (10, 90):
        assert _model(X, y, dispatch_chunk=chunk) == ref, \
            f"packed carry drifted at dispatch_chunk={chunk}"
    for chunk in (1, 10, 90):
        assert _model(X, y, dispatch_chunk=chunk,
                      packed_tree_carry="off") == ref, \
            f"legacy carry drifted at dispatch_chunk={chunk}"


def test_packed_record_roundtrip_is_exact():
    """Host unpack of a device-packed record reproduces every grower
    field bit-for-bit (the pack/unpack pair the chunked path rides)."""
    import jax.numpy as jnp

    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config

    X, y = _data()
    cfg = Config.from_params(dict(BASE))
    core = lgb.Dataset(X, label=y).construct(cfg)
    g = GBDT(cfg, core)
    g.train_chunk(3)
    assert g._pending and g._pending[0][0] == "rstack"
    recs = np.asarray(g._pending[0][1])          # (3, K, record_size)
    layout = g.grower.record_layout
    assert recs.shape[-1] == layout.record_size

    # the same record unpacked host-side and device-side must agree
    from lightgbm_tpu.ops.predict import unpack_tree_records_device
    host = layout.unpack_tree_record(recs[0, 0])
    dev = unpack_tree_records_device(jnp.asarray(recs[0, 0]),
                                     cfg.num_leaves,
                                     g.grower.max_feature_bin)
    for name, h in host.items():
        d = np.asarray(getattr(dev, name))
        assert np.array_equal(np.asarray(h), d.astype(
            np.asarray(h).dtype)), f"field {name} drifted"
    assert int(host["num_leaves"]) > 1


# the rung ladder (PR 36): at max_bin=255 a frontier of 47 slots is served
# by the factored rungs of 2, 10, 16 and 32 slots and the strips of 42 and
# 47, and the round's whole refresh (pass, parent - right, the cache's
# update, the finder) runs at the width of the rung
RUNGS = dict(BASE, max_bin=255, num_leaves=48, quant_stochastic_rounding=1,
             dispatch_chunk=2, telemetry="counters")
RUNG_WIDTHS = "2,10,16,32,42,47"
FORCED = {"feature": 0, "threshold": 0.1,
          "left": {"feature": 1, "threshold": -0.2}}
LADDER_CASES = {
    "strips": None,                       # the module's 7-leaf model
    "rungs": {},
    "rungs_two_row_segments": {},         # int32 counts, QUANT_SEGMENT_ROWS
    "rungs_row_mesh": {"tree_learner": "data", "mesh_shape": [4],
                       "mesh_axes": ["data"], "hist_kernel": "pallas"},
    "rungs_no_cache": {"histogram_pool_size": 0.001},
    "rungs_forced_split": {"forcedsplits_filename": FORCED},
}


@pytest.mark.parametrize("case", list(LADDER_CASES))
def test_split_finder_ladder_parity(case, request, tmp_path, monkeypatch):
    """One ladder for the pass, the glue and the finder must pick
    identical splits to the refresh at the frontier cap
    (``split_finder_ladder=False``, the parent's behaviour) — the knob
    changes shapes, not semantics.  ``strips``: compared against the
    shared chunk-1 reference (the ladder-ON chunk-10 model is byte-
    identical to it by the test above).  The others on a rung-ladder
    shape: one device, two row segments with int32 counts, a four-shard
    row mesh, no histogram cache, a forced split."""
    if case == "strips":
        X, y, ref = request.getfixturevalue("ref_model")
        assert _model(X, y, dispatch_chunk=10,
                      split_finder_ladder=False) == ref
        return
    import json

    import jax
    from lightgbm_tpu.ops import histogram as H
    from lightgbm_tpu.telemetry import TELEMETRY
    if case == "rungs_row_mesh" and len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    params = dict(RUNGS, **LADDER_CASES[case])
    if case == "rungs_forced_split":
        fn = tmp_path / "forced.json"
        fn.write_text(json.dumps(FORCED))
        params["forcedsplits_filename"] = str(fn)
    if case == "rungs_two_row_segments":
        monkeypatch.setattr(H, "QUANT_SEGMENT_ROWS", 2048)
    rng = np.random.RandomState(5)
    X = rng.lognormal(size=(4096, 6)).astype(np.float32)   # 1024 a shard
    y = (X[:, 0] * X[:, 1] - X[:, 2] + 0.3 * rng.randn(4096)
         > 0.5).astype(float)

    def grow(**over):
        TELEMETRY.reset()
        bst = lgb.train(dict(params, **over), lgb.Dataset(X, label=y), 2,
                        verbose_eval=False, keep_training_booster=True)
        return (bst.model_to_string(), bst.gbdt.grower,
                TELEMETRY.gauges()["grower.refresh_widths"])
    try:
        on, grower, widths = grow()
        off, _, capped = grow(split_finder_ladder=False)
    finally:
        TELEMETRY.configure("off")
        TELEMETRY.reset()
    plan = grower.plan
    assert plan.tier == "ladder" and grower.frontier == 47
    assert widths == RUNG_WIDTHS
    assert capped == ",".join(["47"] * 6)
    assert (plan.row_segments == 2 and plan.int_counts) \
        == (case == "rungs_two_row_segments")
    assert plan.mesh_kernels == (case == "rungs_row_mesh")
    assert grower.use_hist_cache == (case != "rungs_no_cache")
    assert bool(grower.forced_count) == (case == "rungs_forced_split")
    assert on == off


def test_dispatch_chunk_param_validation():
    from lightgbm_tpu.config import Config
    with pytest.raises(ValueError):
        Config.from_params(dict(BASE, dispatch_chunk="sometimes"))
    with pytest.raises(ValueError):
        Config.from_params(dict(BASE, dispatch_chunk=0))
    with pytest.raises(ValueError):          # OverflowError escape
        Config.from_params(dict(BASE, dispatch_chunk="inf"))
    with pytest.raises(ValueError):
        Config.from_params(dict(BASE, packed_tree_carry="maybe"))
    assert str(Config.from_params(dict(BASE)).dispatch_chunk) == "auto"
