"""histogram_pool_size governance (reference config.h:216 + the LRU
HistogramPool, feature_histogram.hpp:653-823): over-budget configs drop
histogram subtraction and compute both children directly."""
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.learner.grower import TreeGrower


def _task(n=1500, f=12, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] - X[:, 1] > 0).astype(float)
    return X, y


def test_pool_size_disables_cache():
    X, y = _task()
    cfg = Config.from_params({"objective": "binary", "verbose": -1,
                              "num_leaves": 31,
                              "histogram_pool_size": 0.001})
    core = lgb.Dataset(X, label=y).construct(cfg)
    g = TreeGrower(core, cfg)
    assert not g.use_hist_cache
    cfg2 = Config.from_params({"objective": "binary", "verbose": -1,
                               "num_leaves": 31})
    g2 = TreeGrower(core, cfg2)
    assert g2.use_hist_cache


def test_no_cache_mode_trains_equivalently():
    """Direct-both-children mode must produce the same trees up to
    float summation order (subtraction vs direct accumulation)."""
    X, y = _task()
    base = {"objective": "binary", "verbose": -1, "num_leaves": 15,
            "min_data_in_leaf": 5}
    b1 = lgb.train(base, lgb.Dataset(X, label=y), 8, verbose_eval=False)
    b2 = lgb.train(dict(base, histogram_pool_size=0.001),
                   lgb.Dataset(X, label=y), 8, verbose_eval=False)
    p1, p2 = b1.predict(X), b2.predict(X)
    assert np.abs(p1 - p2).mean() < 1e-3
    assert (((p1 > 0.5) == (p2 > 0.5)).mean()) > 0.995


def test_wide_config_trains_with_bounded_cache():
    """A wide config (many features x 255 bins x 255 leaves) whose
    cache would be large trains under an explicit budget with the
    (1, G, B, 3) dummy cache."""
    rng = np.random.RandomState(1)
    X = rng.randn(800, 100)
    y = (X[:, 0] > 0).astype(float)
    params = {"objective": "binary", "verbose": -1, "num_leaves": 255,
              "max_bin": 255, "histogram_pool_size": 8.0,
              "min_data_in_leaf": 2}
    cfg = Config.from_params(params)
    core = lgb.Dataset(X, label=y).construct(cfg)
    g = TreeGrower(core, cfg)
    assert not g.use_hist_cache
    bst = lgb.train(params, lgb.Dataset(X, label=y), 3,
                    verbose_eval=False)
    assert (((bst.predict(X) > 0.5) == y).mean()) > 0.95


def _fast(**extra):
    return dict({"objective": "binary", "verbose": -1, "num_leaves": 15,
                 "max_bin": 255, "min_data_in_leaf": 5,
                 "quantized_grad": True, "hist_compute_dtype": "bfloat16",
                 "quant_stochastic_rounding": 1,
                 "force_pallas_interpret": True}, **extra)


@pytest.mark.parametrize("extra,shape", [
    ({}, (15, 12, 255, 3)), ({"histogram_pool_size": 0.001}, (1, 12, 255, 3))],
    ids=["cache", "no_cache"])
def test_hist_pool_is_kept_from_chunk_to_chunk(extra, shape):
    """On the kernel ladder the chunk program grows its trees in ONE
    histogram cache, handed from tree to tree and from chunk to chunk and
    never zeroed in between (a tree reads only the slots it has written):
    the model is the model of trees that each start from a zeroed
    cache."""
    X, y = _task()
    X = np.exp(X).astype(np.float32)

    def grow(chunk, keep):
        bst = lgb.train(_fast(dispatch_chunk=chunk, **extra),
                        lgb.Dataset(X, label=y), chunk, verbose_eval=False,
                        keep_training_booster=True)
        for _ in range(6 // chunk - 1):
            if not keep:
                bst.gbdt._hist_pool = None      # the next chunk zeroes one
            bst.gbdt.train_chunk(chunk)
        return bst.gbdt._hist_pool, bst.model_to_string()
    pool, text = grow(2, keep=True)
    assert pool.shape == shape and pool.dtype == np.float32
    assert np.asarray(pool).any() == (not extra)   # the last tree's sums
    assert text == grow(1, keep=False)[1]
    assert text.count("Tree=") == 6


def test_no_hist_pool_off_the_kernel_ladder():
    X, y = _task()
    cfg = Config.from_params({"objective": "binary", "verbose": -1})
    g = TreeGrower(lgb.Dataset(X, label=y).construct(cfg), cfg)
    assert g.plan.tier == "xla" and g.new_hist_pool() is None
