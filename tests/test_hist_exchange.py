"""Compressed histogram exchange (round 21): the ``hist_exchange``
codec in parallel/collectives.py.

Pins, per ISSUE acceptance:
  * tree BYTE-identity across hist_exchange=f32|q16|q8 on simulated
    2- and 4-shard data-parallel seams (the l1-family objectives have
    integer-valued histogram channels, which the codec's exact-integer
    grid ships verbatim — reconstruction is bit-exact),
  * codec round-trip error bounds on float-valued histograms,
  * the exchange byte counters (the wire payload genuinely shrinks
    2x / 4x),
  * the ``collectives.hist_exchange`` fault seam (named here for
    scripts/check_seam_coverage.py) fails fast like every collective.
"""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.boosting.gbdt import GBDT
from lightgbm_tpu.config import Config
from lightgbm_tpu.parallel.collectives import (HIST_EXCHANGE_MODES,
                                               host_exchange_histograms)
from lightgbm_tpu.reliability.faults import FAULTS
from lightgbm_tpu.telemetry import TELEMETRY


@pytest.fixture(autouse=True)
def _clean():
    FAULTS.reset()
    TELEMETRY.configure("off")
    yield
    FAULTS.reset()
    TELEMETRY.configure("off")


def _hists(world, L=3, G=4, B=16, seed=0, integer=False):
    rng = np.random.RandomState(seed)
    if integer:
        deltas = rng.randint(-15, 16, size=(world, L, G, B, 3))
        return [np.cumsum(d, axis=-2).astype(np.float32)
                for d in deltas]
    return [rng.randn(L, G, B, 3).astype(np.float32).cumsum(axis=-2)
            for _ in range(world)]


# ---------------------------------------------------------------------------
# host codec: round-trip bounds, exact-integer grid, byte counters
# ---------------------------------------------------------------------------
def test_codec_roundtrip_error_bounds():
    hs = _hists(4, seed=3)
    exact = np.sum(np.stack(hs), axis=0)
    ref = np.max(np.abs(exact))
    assert np.array_equal(host_exchange_histograms(hs, mode="f32"),
                          exact)
    for mode, tol in (("q16", 1e-3), ("q8", 1e-1)):
        err = np.max(np.abs(host_exchange_histograms(hs, mode=mode)
                            - exact)) / ref
        assert err <= tol, f"{mode} round-trip error {err} > {tol}"


def test_codec_exact_integer_channels():
    # integer-valued histograms whose bin deltas fit the quantizer
    # range ship verbatim (scale = unit grid) — reconstruction is
    # BIT-exact, the property the tree byte-identity below rides
    for world in (2, 4):
        hs = _hists(world, seed=world, integer=True)
        exact = np.sum(np.stack(hs), axis=0)
        for mode in ("q16", "q8"):
            out = host_exchange_histograms(hs, mode=mode)
            assert np.array_equal(out, exact), \
                f"{mode} world={world} integer exchange is not exact"


def test_codec_byte_counters_drop():
    hs = _hists(2, seed=5)
    nbytes_f32 = hs[0].nbytes * len(hs)
    TELEMETRY.configure("counters")
    got = {}
    for mode in HIST_EXCHANGE_MODES:
        TELEMETRY.reset()
        host_exchange_histograms(hs, mode=mode)
        c = TELEMETRY.counters()
        got[mode] = int(c.get("collective_hist_exchange_bytes", 0))
        if mode == "f32":
            assert "collective_hist_exchange_scale_bytes" not in c
        else:
            assert c.get("collective_hist_exchange_scale_bytes", 0) > 0
    assert got["f32"] == nbytes_f32
    assert got["q16"] == nbytes_f32 // 2
    assert got["q8"] == nbytes_f32 // 4


def test_codec_world_headroom_refused():
    # int8 leaves no quantization levels once the world-size summation
    # headroom eats the whole mantissa — loud error, not overflow
    hs = _hists(2, seed=1)
    with pytest.raises(ValueError, match="hist_exchange=q8"):
        host_exchange_histograms(hs * 100, mode="q8")


def test_codec_rejects_unknown_mode():
    with pytest.raises(ValueError, match="hist_exchange"):
        host_exchange_histograms(_hists(2), mode="bf16")


# ---------------------------------------------------------------------------
# fault seam: collectives fail fast (lockstep — no per-host retry)
# ---------------------------------------------------------------------------
def test_hist_exchange_seam_fails_fast():
    FAULTS.configure("collectives.hist_exchange:1:ConnectionError")
    with pytest.raises(ConnectionError, match="injected at seam"):
        host_exchange_histograms(_hists(2), mode="q16")
    FAULTS.reset()
    out = host_exchange_histograms(_hists(2), mode="q16")
    assert out.shape == (3, 4, 16, 3)


# ---------------------------------------------------------------------------
# tree byte-identity across the codec tiers on 2/4-shard meshes
# ---------------------------------------------------------------------------
def _l1_data():
    rng = np.random.RandomState(7)
    n, f = 512, 4
    X = rng.uniform(0, 1, (n, f))
    y = 2.0 * (X[:, 0] > 0.5) + (X[:, 1] > 0.25) + 0.01 * X[:, 2]
    return X, y


def _trees(X, y, shards=0, mode=None):
    params = {"objective": "regression_l1", "num_leaves": 5,
              "verbose": -1, "min_data_in_leaf": 5, "max_bin": 16}
    if shards:
        params.update(tree_learner="data", mesh_shape=(shards,),
                      mesh_axes=("data",))
    if mode is not None:
        params["hist_exchange"] = mode
    cfg = Config.from_params(params)
    g = GBDT(cfg, lgb.Dataset(X, label=y).construct(cfg))
    for _ in range(3):
        g.train_one_iter()
    g.flush_models(final=True)
    return "".join(t.to_string() for t in g.models)


# the 4-shard arm re-tiered slow (tier-1 wall budget): codec byte-
# identity is shard-count-independent; 2 shards keeps the pin fast
@pytest.mark.parametrize("shards", [
    2, pytest.param(4, marks=pytest.mark.slow)])
def test_tree_byte_identity_across_codecs(shards):
    X, y = _l1_data()
    serial = _trees(X, y)
    for mode in HIST_EXCHANGE_MODES:
        m = _trees(X, y, shards=shards, mode=mode)
        assert m == serial, (
            f"hist_exchange={mode} on {shards} shards diverged from "
            "the serial trees (integer-channel exchange must be exact)")


# ---------------------------------------------------------------------------
# precision-tiered accumulation (hist_precision)
# ---------------------------------------------------------------------------
def _tier_trees(**extra):
    rng = np.random.RandomState(11)
    X = rng.rand(700, 5)
    y = (X[:, 0] + 0.3 * X[:, 1] > 0.7).astype(np.float64)
    params = {"objective": "binary", "num_leaves": 6, "verbose": -1,
              "min_data_in_leaf": 5, "max_bin": 15, "num_iterations": 3,
              "force_pallas_interpret": True, "hist_kernel": "pallas"}
    params.update(extra)
    cfg = Config.from_params(params)
    g = GBDT(cfg, lgb.Dataset(X, label=y).construct(cfg))
    for _ in range(3):
        g.train_one_iter()
    g.flush_models(final=True)
    return "".join(t.to_string() for t in g.models), g.grower


def test_tiered_rides_quantized_kernel_path():
    # tiered accumulation IS the int32 quantized-weight kernel path
    # (quantize_gradients + the q kernels) — same trees as the
    # explicit quantized_grad opt-in, and the plan gauge says so
    TELEMETRY.configure("counters")
    TELEMETRY.reset()
    mq, gq = _tier_trees(quantized_grad=True)
    mt, gt = _tier_trees(hist_precision="tiered")
    assert gt.plan.quantized, "tiered did not engage the quantized kernels"
    assert mt == mq
    assert TELEMETRY.gauges().get("grower.hist_precision") == "tiered"
    # the f32 fix-up pass is accounted once per compiled trace
    assert TELEMETRY.counters().get("hist_quant_fixup", 0) >= 1


def test_hist_precision_f32_disables_quant():
    m32, g32 = _tier_trees(hist_precision="f32", quantized_grad=True)
    assert not g32.plan.quantized
    mref, _ = _tier_trees()
    assert m32 == mref, "hist_precision=f32 must match the default path"


def test_quant_rows_contract_is_loud():
    from lightgbm_tpu.ops.histogram import (check_quant_rows,
                                            quant_rows_ok)
    ok = (2 ** 31) // 127          # largest row count the bound admits
    assert quant_rows_ok(ok) and not quant_rows_ok(ok + 1)
    check_quant_rows(ok)
    with pytest.raises(ValueError, match="hist_precision=f32"):
        check_quant_rows(ok + 1, what="hist_precision=tiered")
