"""Workload integration tests — the analog of the reference's
tests/python_package_test/test_engine.py (binary :35, regression :82,
missing-value matrix :101-213, categorical :214-281, multiclass :282,
early stopping :330, continued training :361, cv :413, feature name
:437, save/load/pickle :450, SHAP :533, monotone :603)."""
import dataclasses
import pickle

import numpy as np
import pytest
from sklearn.datasets import load_breast_cancer, load_digits, make_regression
from sklearn.metrics import log_loss, mean_squared_error, roc_auc_score
from sklearn.model_selection import train_test_split

import lightgbm_tpu as lgb


def _binary_data():
    X, y = load_breast_cancer(return_X_y=True)
    return train_test_split(X, y, test_size=0.1, random_state=42)


def test_binary():
    X_train, X_test, y_train, y_test = _binary_data()
    params = {"objective": "binary", "metric": "binary_logloss",
              "verbose": -1}
    # 50-iter reference threshold trained HEADLESS (chunked, fast);
    # the evals_result bookkeeping is pinned by a short valid run
    bst = lgb.train(params, lgb.Dataset(X_train, label=y_train), 50,
                    verbose_eval=False)
    pred = bst.predict(X_test)
    ll = log_loss(y_test, pred)
    # reference threshold: logloss < 0.15 after 50 iters (test_engine.py:35)
    assert ll < 0.15
    ds = lgb.Dataset(X_train, label=y_train)
    er = {}
    b2 = lgb.train(params, ds, 8,
                   valid_sets=[lgb.Dataset(X_test, label=y_test,
                                           reference=ds)],
                   evals_result=er, verbose_eval=False)
    ll2 = log_loss(y_test, b2.predict(X_test))
    assert abs(er["valid_0"]["binary_logloss"][-1] - ll2) < 1e-3


def test_regression():
    X, y = make_regression(n_samples=500, n_features=10, noise=10.0,
                           random_state=42)
    X_train, X_test, y_train, y_test = train_test_split(
        X, y, test_size=0.1, random_state=42)
    params = {"objective": "regression", "metric": "l2", "verbose": -1}
    ds = lgb.Dataset(X_train, label=y_train)
    bst = lgb.train(params, ds, 50, verbose_eval=False)
    mse = mean_squared_error(y_test, bst.predict(X_test))
    base = mean_squared_error(y_test, np.full_like(y_test, y_train.mean()))
    assert mse < 0.2 * base


def test_rf():
    X_train, X_test, y_train, y_test = _binary_data()
    params = {"objective": "binary", "boosting": "rf",
              "bagging_freq": 1, "bagging_fraction": 0.5,
              "feature_fraction": 0.5, "verbose": -1}
    ds = lgb.Dataset(X_train, label=y_train)
    bst = lgb.train(params, ds, 30, verbose_eval=False)
    pred = bst.predict(X_test)
    assert roc_auc_score(y_test, pred) > 0.95


def test_dart():
    X_train, X_test, y_train, y_test = _binary_data()
    params = {"objective": "binary", "boosting": "dart", "verbose": -1}
    ds = lgb.Dataset(X_train, label=y_train)
    bst = lgb.train(params, ds, 20, verbose_eval=False)
    assert log_loss(y_test, bst.predict(X_test)) < 0.35


def test_goss():
    X_train, X_test, y_train, y_test = _binary_data()
    params = {"objective": "binary", "boosting": "goss", "verbose": -1,
              "learning_rate": 0.1}
    ds = lgb.Dataset(X_train, label=y_train)
    bst = lgb.train(params, ds, 20, verbose_eval=False)
    assert log_loss(y_test, bst.predict(X_test)) < 0.35


def test_multiclass():
    X, y = load_digits(n_class=5, return_X_y=True)
    X_train, X_test, y_train, y_test = train_test_split(
        X, y, test_size=0.1, random_state=42)
    params = {"objective": "multiclass", "num_class": 5,
              "metric": "multi_logloss", "verbose": -1}
    ds = lgb.Dataset(X_train, label=y_train)
    bst = lgb.train(params, ds, 12, verbose_eval=False)
    pred = bst.predict(X_test)
    assert pred.shape == (len(y_test), 5)
    acc = (np.argmax(pred, axis=1) == y_test).mean()
    assert acc > 0.9


def test_missing_value_nan():
    """Crafted missing-handling check (reference test_engine.py:101-140)."""
    rng = np.random.RandomState(0)
    x = rng.rand(200)
    X = np.column_stack([x, rng.rand(200)])
    y = (x > 0.5).astype(float)
    X[:20, 0] = np.nan
    y[:20] = 1.0   # NaN strongly predicts positive
    params = {"objective": "binary", "verbose": -1, "min_data_in_leaf": 1,
              "min_data_in_bin": 1}
    bst = lgb.train(params, lgb.Dataset(X, label=y), 30, verbose_eval=False)
    Xt = np.array([[np.nan, 0.5], [0.9, 0.5], [0.1, 0.5]])
    pred = bst.predict(Xt)
    assert pred[0] > 0.5      # NaN routes to the positive side
    assert pred[1] > 0.5
    assert pred[2] < 0.5


def test_missing_value_zero():
    rng = np.random.RandomState(0)
    x = rng.rand(200) + 0.5
    X = np.column_stack([x, rng.rand(200)])
    y = (x > 1.0).astype(float)
    X[:30, 0] = 0.0
    y[:30] = 1.0
    params = {"objective": "binary", "verbose": -1,
              "zero_as_missing": True, "min_data_in_leaf": 1,
              "min_data_in_bin": 1}
    bst = lgb.train(params, lgb.Dataset(X, label=y), 30, verbose_eval=False)
    pred = bst.predict(np.array([[0.0, 0.5], [0.6, 0.5], [1.4, 0.5]]))
    assert pred[0] > 0.5
    assert pred[2] > 0.5
    assert pred[1] < 0.5


def test_categorical_handling():
    """Crafted categorical splits (reference test_engine.py:214-281)."""
    rng = np.random.RandomState(0)
    cat = rng.randint(0, 8, size=600).astype(float)
    X = np.column_stack([cat, rng.rand(600)])
    # categories {1, 3, 5} are positive
    y = np.isin(cat, [1, 3, 5]).astype(float)
    params = {"objective": "binary", "verbose": -1, "min_data_in_leaf": 1,
              "max_cat_to_onehot": 1}  # force sorted-mode cat splits
    ds = lgb.Dataset(X, label=y, categorical_feature=[0])
    bst = lgb.train(params, ds, 30, verbose_eval=False)
    pred = bst.predict(np.column_stack(
        [np.arange(8), np.full(8, 0.5)]))
    for c in range(8):
        if c in (1, 3, 5):
            assert pred[c] > 0.5, c
        else:
            assert pred[c] < 0.5, c


def test_early_stopping():
    X_train, X_test, y_train, y_test = _binary_data()
    ds = lgb.Dataset(X_train, label=y_train)
    vs = lgb.Dataset(X_test, label=y_test, reference=ds)
    bst = lgb.train({"objective": "binary", "metric": "binary_logloss",
                     "verbose": -1}, ds, 500, valid_sets=[vs],
                    early_stopping_rounds=5, verbose_eval=False)
    assert bst.best_iteration > 0
    assert bst.num_trees() < 500


def test_continued_training():
    X_train, X_test, y_train, y_test = _binary_data()
    params = {"objective": "binary", "metric": "binary_logloss",
              "verbose": -1}
    ds = lgb.Dataset(X_train, label=y_train)
    bst1 = lgb.train(params, ds, 20, verbose_eval=False)
    ll1 = log_loss(y_test, bst1.predict(X_test))
    # continued training needs raw data (reference semantics: pass
    # free_raw_data=False explicitly)
    ds2 = lgb.Dataset(X_train, label=y_train, free_raw_data=False)
    bst2 = lgb.train(params, ds2, 20, init_model=bst1, verbose_eval=False)
    ll2 = log_loss(y_test, bst2.predict(X_test))
    assert bst2.num_trees() == 40
    assert ll2 < ll1


def test_cv():
    X_train, _, y_train, _ = _binary_data()
    ds = lgb.Dataset(X_train, label=y_train)
    res = lgb.cv({"objective": "binary", "metric": "binary_logloss",
                  "verbose": -1}, ds, 10, nfold=3)
    assert "binary_logloss-mean" in res
    assert len(res["binary_logloss-mean"]) == 10
    assert res["binary_logloss-mean"][-1] < res["binary_logloss-mean"][0]


def test_feature_names():
    X = np.random.RandomState(0).rand(100, 3)
    y = X[:, 0]
    names = ["alpha", "beta", "gamma"]
    ds = lgb.Dataset(X, label=y, feature_name=names)
    bst = lgb.train({"objective": "regression", "verbose": -1,
                     "min_data_in_leaf": 5}, ds, 5, verbose_eval=False)
    assert bst.feature_names == names
    assert "alpha" in bst.model_to_string()


def test_save_load_pickle_roundtrip():
    X_train, X_test, y_train, y_test = _binary_data()
    ds = lgb.Dataset(X_train, label=y_train)
    bst = lgb.train({"objective": "binary", "verbose": -1}, ds, 10,
                    verbose_eval=False)
    pred = bst.predict(X_test)
    s = bst.model_to_string()
    bst2 = lgb.Booster(model_str=s)
    assert np.allclose(pred, bst2.predict(X_test))
    bst3 = pickle.loads(pickle.dumps(bst))
    assert np.allclose(pred, bst3.predict(X_test))


def test_shap_contribs_sum():
    """SHAP contribs sum to raw prediction (reference test_engine.py:533)."""
    X_train, X_test, y_train, _ = _binary_data()
    ds = lgb.Dataset(X_train, label=y_train)
    bst = lgb.train({"objective": "binary", "verbose": -1}, ds, 10,
                    verbose_eval=False)
    contrib = bst.predict(X_test[:30], pred_contrib=True)
    raw = bst.predict(X_test[:30], raw_score=True)
    assert np.allclose(contrib.sum(axis=1), raw, atol=1e-6)


def test_monotone_constraints():
    """Scan the learned function for monotonicity
    (reference test_engine.py:603)."""
    rng = np.random.RandomState(0)
    n = 2000
    x_inc = rng.rand(n)
    x_dec = rng.rand(n)
    x_free = rng.rand(n)
    y = (5 * x_inc - 5 * x_dec + np.sin(10 * x_free)
         + 0.1 * rng.randn(n))
    X = np.column_stack([x_inc, x_dec, x_free])
    params = {"objective": "regression", "verbose": -1,
              "monotone_constraints": [1, -1, 0], "num_leaves": 31}
    bst = lgb.train(params, lgb.Dataset(X, label=y), 60, verbose_eval=False)
    # vary one monotone feature over a grid, others fixed
    grid = np.linspace(0.01, 0.99, 50)
    for col, sign in ((0, 1), (1, -1)):
        for trial in range(5):
            base = rng.rand(3)
            pts = np.tile(base, (50, 1))
            pts[:, col] = grid
            pred = bst.predict(pts)
            diffs = np.diff(pred) * sign
            assert np.all(diffs >= -1e-10), (col, sign)


def test_custom_objective_fobj():
    X_train, X_test, y_train, y_test = _binary_data()

    def logregobj(preds, dataset):
        labels = dataset.metadata.label[:dataset.num_data]
        p = 1.0 / (1.0 + np.exp(-preds))
        return p - labels, p * (1 - p)

    ds = lgb.Dataset(X_train, label=y_train)
    bst = lgb.train({"objective": "none", "verbose": -1}, ds, 30,
                    fobj=logregobj, verbose_eval=False)
    raw = bst.predict(X_test, raw_score=True)
    pred = 1.0 / (1.0 + np.exp(-raw))
    assert log_loss(y_test, pred) < 0.2


def test_reset_parameter_callback():
    X_train, _, y_train, _ = _binary_data()
    ds = lgb.Dataset(X_train, label=y_train)
    lrs = [0.1] * 5 + [0.05] * 5
    bst = lgb.train({"objective": "binary", "verbose": -1}, ds, 10,
                    callbacks=[lgb.reset_parameter(learning_rate=lrs)],
                    verbose_eval=False)
    assert bst.num_trees() == 10


def test_lambdarank_banded_gradients():
    """The length-bucketed layout, moved flat <-> bucketed by the banded
    permutation, must reproduce the direct per-query pairwise lambdas
    (reference rank_objective.hpp:83-170) exactly, on ragged query sizes
    with weights — the regime where a bucket's rows have real gaps."""
    import jax.numpy as jnp

    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset import Metadata
    from lightgbm_tpu.objectives import LambdarankNDCG

    rng = np.random.RandomState(3)
    sizes = rng.randint(1, 40, size=60)
    n = int(sizes.sum())
    label = rng.randint(0, 4, size=n).astype(np.float64)
    qweight = rng.rand(60).astype(np.float64) + 0.5
    weight = np.repeat(qweight, sizes)
    qb = np.concatenate([[0], np.cumsum(sizes)])

    cfg = Config.from_params({"objective": "lambdarank", "verbose": -1})
    obj = LambdarankNDCG(cfg)
    md = Metadata(n)
    md.set_label(label)
    md.set_weight(weight)
    md.set_group(sizes)
    obj.init(md, n)
    # queries of 1-39 rows: every ranked one shares a packed 128-slot row
    assert [b.packed for b in obj._buckets] == [True]
    assert obj._n_slots < 3 * n

    n_pad = ((n + 127) // 128) * 128
    score = np.zeros(n_pad, np.float32)
    score[:n] = rng.randn(n).astype(np.float32) * 2
    g, h = obj.get_gradients(jnp.asarray(score))
    g, h = np.asarray(g), np.asarray(h)
    assert g.shape == (n_pad,)
    assert np.all(g[n:] == 0) and np.all(h[n:] == 0)

    # direct numpy reference of the same math
    lg = obj.label_gain
    sig = obj.sigmoid
    g_ref = np.zeros(n)
    h_ref = np.zeros(n)
    for q in range(60):
        lo, hi = qb[q], qb[q + 1]
        s = score[lo:hi].astype(np.float64)
        lab = label[lo:hi].astype(np.int64)
        k = min(obj.optimize_pos_at, hi - lo)
        top = np.sort(lab)[::-1][:k]
        idcg = float(np.sum(lg[top] / np.log2(np.arange(2, k + 2))))
        inv = 1.0 / idcg if idcg > 0 else 0.0
        order = np.argsort(-s, kind="stable")
        rank = np.argsort(order, kind="stable")
        disc = 1.0 / np.log2(2.0 + rank)
        spread = s.max() != s.min() if hi > lo else False
        for i in range(hi - lo):
            for j in range(hi - lo):
                if lab[i] <= lab[j]:
                    continue
                ds = s[i] - s[j]
                dn = (lg[lab[i]] - lg[lab[j]]) * abs(disc[i] - disc[j]) \
                    * inv
                if spread:
                    dn /= 0.01 + abs(ds)
                pl = 2.0 / (1.0 + np.exp(2.0 * ds * sig))
                ph = pl * (2.0 - pl)
                g_ref[lo + i] += -pl * dn
                g_ref[lo + j] -= -pl * dn
                h_ref[lo + i] += 2.0 * ph * dn
                h_ref[lo + j] += 2.0 * ph * dn
    g_ref *= weight
    h_ref *= weight
    np.testing.assert_allclose(g[:n], g_ref, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(h[:n], h_ref, rtol=2e-4, atol=2e-5)


def test_lambdarank_ndcg():
    """Ranking end-to-end (reference test_engine.py lambdarank flow)."""
    rng = np.random.RandomState(0)
    n_q, per_q = 50, 20
    n = n_q * per_q
    X = rng.rand(n, 6)
    rel = (X[:, 0] * 2 + X[:, 1] * 2 + 0.3 * rng.randn(n)).clip(0, 3)
    rel = rel.astype(int)
    group = [per_q] * n_q
    params = {"objective": "lambdarank", "metric": "ndcg",
              "ndcg_eval_at": [1, 3, 5], "verbose": -1,
              "min_data_in_leaf": 5}
    ds = lgb.Dataset(X, label=rel.astype(float), group=group)
    er = {}
    bst = lgb.train(params, ds, 30, valid_sets=[ds], evals_result=er,
                    verbose_eval=False)
    ndcg3 = er["training"]["ndcg@3"]
    assert ndcg3[-1] > ndcg3[0]
    assert ndcg3[-1] > 0.8


def test_xentropy_objectives():
    rng = np.random.RandomState(0)
    X = rng.randn(500, 5)
    p = 1.0 / (1.0 + np.exp(-(X[:, 0] - X[:, 1])))
    y = p  # probabilistic labels in [0, 1]
    for obj in ("cross_entropy", "cross_entropy_lambda"):
        params = {"objective": obj, "verbose": -1}
        er = {}
        bst = lgb.train(params, lgb.Dataset(X, label=y), 20,
                        valid_sets=[lgb.Dataset(X, label=y)],
                        evals_result=er, verbose_eval=False)
        key = next(iter(er["valid_0"]))
        vals = er["valid_0"][key]
        assert vals[-1] < vals[0], obj


def _objectives_train_decreasing(cases):
    rng = np.random.RandomState(0)
    X = rng.randn(600, 5)
    y_pos = np.exp(X[:, 0] * 0.5 + 0.1 * rng.randn(600))
    for obj in cases:
        yy = y_pos if obj in ("poisson", "gamma", "tweedie") \
            else X[:, 0] * 2 + 0.2 * rng.randn(600)
        # the assertion is only "the metric decreases" — 8 iterations
        # at 15 leaves keep the 8-objective sweep cheap on 1 CPU core
        params = {"objective": obj, "verbose": -1, "metric": obj,
                  "num_leaves": 15}
        er = {}
        lgb.train(params, lgb.Dataset(X, label=yy), 8,
                  valid_sets=[lgb.Dataset(X, label=yy)],
                  evals_result=er, verbose_eval=False)
        key = next(iter(er["valid_0"]))
        vals = er["valid_0"][key]
        assert vals[-1] < vals[0], (obj, vals[0], vals[-1])


def test_regression_objectives_train():
    """Fast tier-1 pin: one asymmetric-loss objective + one positive-
    label objective train downhill (the full eight-objective sweep is
    the slow-tier test below; per-objective gradient math is pinned at
    unit level elsewhere)."""
    _objectives_train_decreasing(["huber", "poisson"])


# re-tiered slow (tier-1 wall budget): six further trainings sweeping
# the remaining objectives; the train-downhill pin stays fast above
@pytest.mark.slow
def test_regression_objectives_train_full_sweep():
    _objectives_train_decreasing(
        ["regression_l1", "fair", "quantile", "mape", "gamma",
         "tweedie"])


def test_prediction_early_stop():
    """reference test_engine.py:303 pred_early_stop."""
    X_train, X_test, y_train, _ = _binary_data()
    ds = lgb.Dataset(X_train, label=y_train)
    bst = lgb.train({"objective": "binary", "verbose": -1}, ds, 30,
                    verbose_eval=False)
    full = bst.predict(X_test, raw_score=True)
    es = bst.predict(X_test, raw_score=True, pred_early_stop=True,
                     pred_early_stop_freq=5, pred_early_stop_margin=1.5)
    # same sign (classification decision unchanged), values may differ
    assert np.all(np.sign(full) == np.sign(es))
    es_loose = bst.predict(X_test, raw_score=True, pred_early_stop=True,
                           pred_early_stop_freq=5,
                           pred_early_stop_margin=1e9)
    assert np.allclose(full, es_loose)


def test_pandas_dataframe_and_categorical():
    """Pandas input with categorical dtype (reference test_engine.py:482
    test_pandas_categorical)."""
    pd = pytest.importorskip("pandas")
    rng = np.random.RandomState(5)
    n = 800
    df = pd.DataFrame({
        "a": rng.randn(n),
        "b": pd.Categorical(rng.choice(["x", "y", "z"], n)),
        "c": rng.randint(0, 5, n),
    })
    y = ((df["b"].cat.codes.values == 1) | (df["a"].values > 0.5)) \
        .astype(float)
    ds = lgb.Dataset(df, label=y)
    bst = lgb.train({"objective": "binary", "verbose": -1,
                     "num_leaves": 15}, ds, 20, verbose_eval=False)
    pred = bst.predict(df)
    err = np.mean((pred > 0.5) != y)
    assert err < 0.1


def test_sliced_numpy_arrays():
    """Non-contiguous inputs must work (reference test_engine.py:553)."""
    rng = np.random.RandomState(6)
    big = rng.randn(1000, 12)
    X = big[::2, 1:9]                     # strided view
    ywide = np.column_stack([(big[:, 1] > 0).astype(float)] * 2)
    y = ywide[::2, 0]                     # genuinely strided label
    bst = lgb.train({"objective": "binary", "verbose": -1,
                     "num_leaves": 7}, lgb.Dataset(X, label=y), 10,
                    verbose_eval=False)
    p = bst.predict(np.asfortranarray(X))  # fortran-order predict input
    assert ((p > 0.5) == (y > 0.5)).mean() > 0.95


def test_dataset_reference_chain():
    """Validation Datasets share the training set's bin mappers
    (reference test_engine.py:523 test_reference_chain)."""
    rng = np.random.RandomState(7)
    X = rng.randn(600, 5)
    y = (X[:, 0] > 0).astype(float)
    dtrain = lgb.Dataset(X[:400], label=y[:400])
    dval = lgb.Dataset(X[400:], label=y[400:], reference=dtrain)
    er = {}
    lgb.train({"objective": "binary", "metric": "binary_logloss",
               "verbose": -1, "num_leaves": 7}, dtrain, 10,
              valid_sets=[dval], evals_result=er, verbose_eval=False)
    core_t, core_v = dtrain.construct(None), dval.construct(None)
    assert core_v.mappers is core_t.mappers   # shared, not re-fit
    assert len(er["valid_0"]["binary_logloss"]) == 10


def test_pandas_categorical_remap_on_predict():
    """Predict-time category order must not matter: codes are computed
    against the TRAIN-time categories persisted on the model (the
    reference's pandas_categorical attribute), surviving a save/load
    round trip; unseen categories behave as missing."""
    pd = pytest.importorskip("pandas")
    rng = np.random.RandomState(9)
    n = 600
    cats = ["red", "green", "blue"]
    col = rng.choice(cats, n)
    df = pd.DataFrame({"a": rng.randn(n), "b": pd.Categorical(col, cats)})
    y = (col == "green").astype(float)
    bst = lgb.train({"objective": "binary", "verbose": -1,
                     "num_leaves": 7}, lgb.Dataset(df, label=y), 20,
                    verbose_eval=False)
    # reversed category declaration: same values, different codes
    df2 = pd.DataFrame({"a": df["a"],
                        "b": pd.Categorical(col, cats[::-1])})
    np.testing.assert_allclose(bst.predict(df), bst.predict(df2))
    # round trip through the text model keeps the mapping
    import tempfile, os
    with tempfile.TemporaryDirectory() as d:
        p = os.path.join(d, "m.txt")
        bst.save_model(p)
        bst2 = lgb.Booster(model_file=p)
        assert bst2.pandas_categorical == [cats]
        np.testing.assert_allclose(bst.predict(df2), bst2.predict(df2))


def test_device_predict_matches_host():
    """Batched device prediction (binned input + scanned device trees)
    must match the host per-tree walk exactly (reference batch predict
    c_api.cpp:200; VERDICT weak #9)."""
    X_train, X_test, y_train, _ = _binary_data()
    ds = lgb.Dataset(X_train, label=y_train)
    bst = lgb.train({"objective": "binary", "verbose": -1,
                     "num_leaves": 15}, ds, 12, verbose_eval=False)
    host = bst.predict(X_test, device=False)
    dev = bst.predict(X_test, device=True)
    np.testing.assert_allclose(dev, host, atol=1e-6)
    host_raw = bst.predict(X_test, raw_score=True, device=False)
    dev_raw = bst.predict(X_test, raw_score=True, device=True)
    np.testing.assert_allclose(dev_raw, host_raw, atol=1e-6)
    # num_iteration slicing agrees too
    np.testing.assert_allclose(
        bst.predict(X_test, num_iteration=5, device=True),
        bst.predict(X_test, num_iteration=5, device=False), atol=1e-6)


def test_python_surface_tail_matches_reference_basic():
    """The reference python package's Dataset/Booster method tail
    (basic.py): add_valid + eval_train/eval_valid,
    set_train_data_name, attr/set_attr, get_leaf_output,
    reset_parameter, free_dataset, get_ref_chain,
    set_feature_name/set_reference/set_categorical_feature."""
    rng = np.random.RandomState(3)
    X = rng.randn(800, 6)
    y = (X[:, 0] > 0).astype(float)
    params = {"objective": "binary", "verbose": -1, "num_leaves": 15}

    train = lgb.Dataset(X[:600], label=y[:600])
    train.set_feature_name([f"f{i}" for i in range(6)])
    train.set_categorical_feature("auto")
    valid = lgb.Dataset(X[600:], label=y[600:]).set_reference(train)
    assert train in valid.get_ref_chain()

    bst = lgb.Booster(lgb.Config.from_params(params), train_set=train)
    bst.set_train_data_name("trn").add_valid(valid, "vld")
    for _ in range(5):
        bst.update()
    tr = bst.eval_train()
    va = bst.eval_valid()
    assert tr and all(r[0] == "trn" for r in tr)
    assert va and all(r[0] == "vld" for r in va)
    assert np.isfinite([r[2] for r in tr + va]).all()

    leaf0 = bst.get_leaf_output(0, 0)
    assert np.isfinite(leaf0)
    bst.set_attr(note="hello", extra="1").set_attr(extra=None)
    assert bst.attr("note") == "hello" and bst.attr("extra") is None

    bst.reset_parameter({"learning_rate": 0.05})
    assert bst.gbdt.shrinkage_rate == 0.05

    preds_before = bst.predict(X[600:])
    bst.free_dataset()
    np.testing.assert_allclose(bst.predict(X[600:]), preds_before)
    with pytest.raises(Exception):
        bst.update()


def test_unaligned_valid_sets_are_auto_referenced():
    """A lazy valid set passed without reference= must be bin-aligned
    to the training mappers (reference package train()/add_valid call
    set_reference) — own-mapper binning would evaluate train-space
    thresholds against foreign bins and yield silently wrong metrics."""
    rng = np.random.RandomState(11)
    X = rng.randn(1200, 6) * 3.0
    y = (X[:, 0] > 0).astype(float)
    params = {"objective": "binary", "verbose": -1, "num_leaves": 15}
    # shifted valid draw: misaligned bins would distort badly
    Xv = rng.randn(400, 6) * 3.0 + 0.5
    yv = (Xv[:, 0] > 0).astype(float)

    res = {}
    bst = lgb.train(params, lgb.Dataset(X, label=y), 10,
                    valid_sets=[lgb.Dataset(Xv, label=yv)],  # no ref
                    evals_result=res, verbose_eval=False)
    ll_engine = res["valid_0"]["binary_logloss"][-1]

    # explicit predict on raw features = ground truth
    p = np.clip(bst.predict(Xv), 1e-7, 1 - 1e-7)
    ll_true = -np.mean(yv * np.log(p) + (1 - yv) * np.log(1 - p))
    assert abs(ll_engine - ll_true) < 5e-3, (ll_engine, ll_true)

    # same auto-alignment through Booster.add_valid
    bst2 = lgb.Booster(lgb.Config.from_params(params),
                       train_set=lgb.Dataset(X, label=y))
    bst2.add_valid(lgb.Dataset(Xv, label=yv), "v")   # no reference
    for _ in range(10):
        bst2.update()
    (name, _m, ll_av, _b), = bst2.eval_valid()
    assert name == "v"
    p2 = np.clip(bst2.predict(Xv), 1e-7, 1 - 1e-7)
    ll2 = -np.mean(yv * np.log(p2) + (1 - yv) * np.log(1 - p2))
    assert abs(ll_av - ll2) < 5e-3, (ll_av, ll2)


def test_train_kwargs_reference_tail():
    """The four reference train() kwargs (engine.py:18-40):
    learning_rates, keep_training_booster, feature_name,
    categorical_feature."""
    rng = np.random.RandomState(11)
    X = rng.randn(600, 5)
    X[:, 2] = rng.randint(0, 4, 600)  # categorical-ish column
    y = (X[:, 0] + (X[:, 2] == 1) > 0.3).astype(float)

    # feature_name + categorical_feature applied pre-construct
    ds = lgb.Dataset(X, label=y, free_raw_data=False)
    bst = lgb.train({"objective": "binary", "verbose": -1}, ds, 5,
                    feature_name=[f"col{i}" for i in range(5)],
                    categorical_feature=["col2"])
    dumped = bst.dump_model()
    assert dumped["feature_names"] == [f"col{i}" for i in range(5)]
    assert any(t for t in dumped["tree_info"])

    # learning_rates: callable decay == explicit reset_parameter list
    lrs = [0.1 * (0.5 ** i) for i in range(6)]
    ds2 = lgb.Dataset(X, label=y, free_raw_data=False)
    a = lgb.train({"objective": "binary", "verbose": -1}, ds2, 6,
                  learning_rates=lambda it: 0.1 * (0.5 ** it))
    ds3 = lgb.Dataset(X, label=y, free_raw_data=False)
    b = lgb.train({"objective": "binary", "verbose": -1}, ds3, 6,
                  callbacks=[lgb.reset_parameter(learning_rate=lrs)])
    np.testing.assert_allclose(a.predict(X), b.predict(X), rtol=1e-6)

    # keep_training_booster: default False releases training state
    # (update() errors, predict works); True keeps it trainable
    ds4 = lgb.Dataset(X, label=y, free_raw_data=False)
    frozen = lgb.train({"objective": "binary", "verbose": -1}, ds4, 3)
    assert frozen.predict(X).shape == (600,)
    with pytest.raises(Exception):
        frozen.update()
    ds5 = lgb.Dataset(X, label=y, free_raw_data=False)
    live = lgb.train({"objective": "binary", "verbose": -1}, ds5, 3,
                     keep_training_booster=True)
    live.update()
    assert live.num_trees() == 4


def test_lambdarank_quantized_stochastic():
    """Stochastic int8 rounding (the v4 quantized-training recipe):
    deterministic rounding zeroes the long tail of small gradients
    (measured 0.33 vs 0.64 held-out NDCG@10 on the MS-LTR bench
    shape), stochastic rounding is unbiased in expectation.  Pins the
    quantizer's statistics and the objective-driven auto mode."""
    import jax
    import jax.numpy as jnp

    from lightgbm_tpu.ops.histogram import quantize_gradients

    rng = np.random.RandomState(0)
    # lambdarank-like skew: one large lambda, a long tail far below
    # the int8 step (max/127)
    grad = np.concatenate([[127.0], rng.rand(8191) * 0.25]) \
        .astype(np.float32)
    hess = np.abs(grad)
    cnt = np.ones_like(grad)
    wq_det, s_det = quantize_gradients(jnp.asarray(grad),
                                       jnp.asarray(hess),
                                       jnp.asarray(cnt))
    # deterministic: the whole tail (< step/2) rounds to zero
    assert float(jnp.sum(jnp.abs(wq_det[1:, 0]))) == 0.0
    wq_s, s_s = quantize_gradients(jnp.asarray(grad), jnp.asarray(hess),
                                   jnp.asarray(cnt),
                                   key=jax.random.PRNGKey(3))
    # stochastic: the dequantized tail SUM is preserved within
    # sampling noise (n=8191 draws, p~0.125-0.25)
    true_sum = float(grad[1:].sum())
    got_sum = float(jnp.sum(wq_s[1:, 0]) * s_s[0])
    assert abs(got_sum - true_sum) / true_sum < 0.05, (got_sum,
                                                      true_sum)

    # auto mode resolves per objective: lambdarank needs it, binary
    # does not (the grower's plan is replaced by a quantized one for the
    # check)
    import lightgbm_tpu as lgb
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config

    X = rng.randn(256, 4)
    y = (X[:, 0] > 0).astype(float)
    for obj, want in (("binary", False), ("lambdarank", True)):
        p = {"objective": obj, "verbose": -1}
        kw = {"label": y}
        if obj == "lambdarank":
            kw["group"] = [64, 64, 64, 64]
        cfg = Config.from_params(p)
        core = lgb.Dataset(X, **kw).construct(cfg)
        g = GBDT(cfg, core)
        g.grower.plan = dataclasses.replace(   # the CPU backend's is xla
            g.grower.plan, tier="ladder")
        assert g._quant_stochastic() is want, obj
        g.config.quant_stochastic_rounding = 1 - int(want)
        assert g._quant_stochastic() is (not want), obj
