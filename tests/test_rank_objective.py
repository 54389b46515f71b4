"""lambdarank's length buckets (objectives.rank_layout) and the lambdas
computed in them: the layout holds every ranked row once, in tiles the
banded permutation can move; the gradients equal a direct per-query
computation of rank_objective.hpp's pairs, with weights, on ragged
queries, one of them longer than a pair tile; and they equal the
benchmark's plain reference (perfbench/reference_rank.py)."""
import os
import sys

import numpy as np
import pytest

from lightgbm_tpu import objectives
from lightgbm_tpu.config import Config
from lightgbm_tpu.dataset import Metadata

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench")


def _sizes(seed):
    rng = np.random.RandomState(seed)
    sizes = np.concatenate([rng.randint(1, 70, 60), [700, 1, 2],
                            rng.randint(65, 300, 8)])
    rng.shuffle(sizes)
    return sizes


def _objective(sizes, label, weight=None):
    n = int(np.sum(sizes))
    obj = objectives.LambdarankNDCG(
        Config.from_params({"objective": "lambdarank", "verbose": -1}))
    md = Metadata(n)
    md.set_label(label)
    if weight is not None:
        md.set_weight(weight)
    md.set_group(sizes)
    obj.init(md, n)
    return obj


def _direct(obj, sizes, score, label, weight):
    """rank_objective.hpp's pair loop, a query at a time, float64."""
    qb = np.concatenate([[0], np.cumsum(sizes)])
    lg = obj.label_gain
    g = np.zeros(len(score))
    h = np.zeros(len(score))
    for lo, hi in zip(qb[:-1], qb[1:]):
        s = score[lo:hi].astype(np.float64)
        lab = label[lo:hi].astype(np.int64)
        k = min(obj.optimize_pos_at, hi - lo)
        top = np.sort(lab)[::-1][:k]
        idcg = float(np.sum(lg[top] / np.log2(np.arange(2, k + 2))))
        inv = 1.0 / idcg if idcg > 0 else 0.0
        rank = np.argsort(np.argsort(-s, kind="stable"), kind="stable")
        disc = 1.0 / np.log2(2.0 + rank)
        ok = lab[:, None] > lab[None, :]
        ds = s[:, None] - s[None, :]
        dn = np.abs((lg[lab][:, None] - lg[lab][None, :])
                    * (disc[:, None] - disc[None, :])) * inv
        if s.max() != s.min():
            dn = dn / (0.01 + np.abs(ds))
        p = 2.0 / (1.0 + np.exp(2.0 * obj.sigmoid * ds))
        lam = np.where(ok, -p * dn, 0.0)
        hes = np.where(ok, 2.0 * p * (2.0 - p) * dn, 0.0)
        g[lo:hi] = lam.sum(1) - lam.sum(0)
        h[lo:hi] = hes.sum(1) + hes.sum(0)
    return g * weight, h * weight


@pytest.mark.parametrize("tile,budget", [(objectives.RANK_TILE,
                                          objectives.RANK_PAIR_BUDGET),
                                         (128, 1 << 15)],
                         ids=["default", "small_tiles"])
@pytest.mark.parametrize("scores", ["random", "tied", "equal"])
def test_bucketed_lambdas_equal_the_direct_pairs(monkeypatch, tile, budget,
                                                 scores):
    """Every bucket kind: packed short queries, one query a row, and a
    query wider than a pair tile (700 rows; with 128-slot tiles every
    query over 128 is), several row chunks a bucket at the small budget."""
    import jax
    import jax.numpy as jnp
    monkeypatch.setattr(objectives, "RANK_TILE", tile)
    monkeypatch.setattr(objectives, "RANK_PAIR_BUDGET", budget)
    sizes = _sizes(4)
    n = int(sizes.sum())
    rng = np.random.RandomState(7)
    label = rng.randint(0, 5, n).astype(np.float64)
    weight = np.repeat(rng.rand(len(sizes)) + 0.5, sizes)
    obj = _objective(sizes, label, weight)
    assert any(b.packed for b in obj._buckets)
    assert any(b.k > 1 for b in obj._buckets)
    if budget < objectives.RANK_PAIR_BUDGET:
        assert any(b.steps > 1 for b in obj._buckets)
    score = {"random": rng.randn(n) * 2,
             "tied": np.round(rng.randn(n)),
             "equal": np.zeros(n)}[scores].astype(np.float32)
    n_pad = -(-n // 128) * 128 + 128
    g, h = jax.jit(obj.get_gradients)(
        jnp.asarray(np.pad(score, (0, n_pad - n))))
    g, h = np.asarray(g), np.asarray(h)
    assert g.shape == (n_pad,) and not g[n:].any() and not h[n:].any()
    g_ref, h_ref = _direct(obj, sizes, score, label, weight)
    scale = np.abs(g_ref).max()
    np.testing.assert_allclose(g[:n], g_ref, rtol=2e-5, atol=2e-6 * scale)
    np.testing.assert_allclose(h[:n], h_ref, rtol=2e-5,
                               atol=2e-6 * np.abs(h_ref).max())


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_objective_is_the_benchmarks_plain_reference(seed):
    """The program's lambdas against perfbench/reference_rank.py (which
    imports nothing of the program) on seeded scores and labels."""
    import jax
    import jax.numpy as jnp
    sys.path.insert(0, BENCH)
    import reference_rank
    sizes = _sizes(10 + seed)
    n = int(sizes.sum())
    rng = np.random.RandomState(seed)
    label = rng.randint(0, 5, n).astype(np.float64)
    score = (rng.randn(n) * (seed + 0.5)).astype(np.float32)
    obj = _objective(sizes, label)
    g, h = jax.jit(obj.get_gradients)(jnp.asarray(score))
    g_ref, h_ref, loss = jax.jit(reference_rank.lambdarank(sizes))(
        jnp.asarray(score), jnp.asarray(label, jnp.float32))
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-5,
                               atol=1e-6 * float(jnp.abs(g_ref).max()))
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), rtol=1e-5,
                               atol=1e-6 * float(jnp.abs(h_ref).max()))
    # the lambdas of a query cancel; the per-row loss is a logistic one
    assert abs(float(jnp.sum(g_ref))) < 1e-3 * float(jnp.abs(g_ref).sum())
    assert float(jnp.min(loss)) >= 0.0


def test_the_layout_holds_every_ranked_row_once():
    """Each row of a query of two or more rows has exactly one slot;
    every 128-slot tile reads fewer than 128 consecutive rows (the banded
    permutation's condition); widths are multiples of 128 and every
    bucket's rows divide into its steps."""
    sizes = _sizes(3)
    qb = np.concatenate([[0], np.cumsum(sizes)])
    buckets, pos, seg = objectives.rank_layout(qb)
    ranked = np.concatenate([np.arange(lo, hi) for lo, hi in
                             zip(qb[:-1], qb[1:]) if hi - lo >= 2])
    assert np.array_equal(np.sort(pos[pos >= 0]), ranked)
    tiles = pos.reshape(-1, 128)
    for t in tiles[(tiles >= 0).any(1)]:
        v = t[t >= 0]
        assert v.max() - v.min() < 128
    q_of = np.searchsorted(qb, np.arange(qb[-1]), side="right") - 1
    assert np.array_equal(seg[pos >= 0], q_of[pos[pos >= 0]])
    assert sum(b.size for b in buckets) == len(pos)
    for b in buckets:
        assert b.width % 128 == 0 and b.rows == b.steps * b.chunk
        assert b.tile <= objectives.RANK_TILE and b.width == b.k * b.tile


def test_istella_shaped_queries_pad_pairs_under_two():
    """At the benchmark's Istella-shaped query sizes (perfbench
    datagen_rank: 23,219 lognormal queries, 7,325,625 rows) the buckets
    evaluate under twice the pairs that exist, where one width for
    every query (the longest) evaluates over a hundred times as many."""
    sys.path.insert(0, BENCH)
    import datagen_rank
    sizes = datagen_rank.query_sizes(2147483659, 7325625)
    assert len(sizes) == 23219 and sizes.sum() == 7325625
    qb = np.concatenate([[0], np.cumsum(sizes)])
    buckets, _, _ = objectives.rank_layout(qb)
    pairs = float(np.sum(sizes.astype(np.float64) ** 2))
    evaluated = sum(b.rows * b.width ** 2 for b in buckets)
    assert evaluated / pairs < 2.0
    assert len(sizes) * float(sizes.max()) ** 2 / pairs > 100


def test_inverse_max_dcg_is_the_per_query_loop():
    rng = np.random.RandomState(2)
    sizes = rng.randint(1, 40, 50)
    qb = np.concatenate([[0], np.cumsum(sizes)])
    label = rng.randint(0, 5, qb[-1]).astype(np.float32)
    lg = np.asarray([2.0 ** i - 1 for i in range(31)])
    got = objectives.inverse_max_dcg(qb, label, lg, 20)
    for q in range(len(sizes)):
        lab = np.sort(label[qb[q]:qb[q + 1]])[::-1].astype(int)[:20]
        dcg = np.sum(lg[lab] / np.log2(np.arange(2, len(lab) + 2)))
        assert got[q] == pytest.approx(1.0 / dcg if dcg > 0 else 0.0)


def test_lambdarank_roots_its_trees_in_the_quantized_sums():
    """Under int8 quantized training a lambdarank tree's root totals are
    the sums of the quantized gradients and hessians, as every histogram
    is; a binary tree keeps the exact ones (ROADMAP A1)."""
    import jax
    import jax.numpy as jnp

    import lightgbm_tpu as lgb
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.ops.histogram import quantize_gradients
    rng = np.random.RandomState(0)
    X = rng.randn(1024, 4)
    for obj, want in (("lambdarank", True), ("binary", False)):
        cfg = Config.from_params({"objective": obj, "verbose": -1})
        core = lgb.Dataset(X, label=(X[:, 0] > 0).astype(float),
                           group=[256] * 4 if obj == "lambdarank" else None
                           ).construct(cfg)
        grower = GBDT(cfg, core).grower
        assert grower.quantized_root_totals is want, obj
    n = grower.n_padded
    grad = jnp.asarray(rng.randn(n).astype(np.float32) * 0.01)
    hess = jnp.asarray(rng.rand(n).astype(np.float32) ** 4)
    counts = jnp.ones(n, jnp.float32)
    wq, scales = quantize_gradients(grad, hess, counts,
                                    key=jax.random.PRNGKey(1))
    st = grower._init_state(grad, hess, counts, None, (wq.T, scales))
    valid = np.asarray(grower._row_valid)
    want_g = float(np.sum(np.asarray(wq[:, 0])[valid]) * scales[0])
    want_h = float(np.sum(np.asarray(wq[:, 1])[valid]) * scales[1])
    assert float(st.leaf_sum_grad[0]) == pytest.approx(want_g, rel=1e-5)
    assert float(st.leaf_sum_hess[0]) == pytest.approx(want_h, rel=1e-5)
    exact = grower._init_state(grad, hess, counts)
    assert float(exact.leaf_sum_hess[0]) == pytest.approx(
        float(jnp.sum(jnp.where(grower._row_valid, hess, 0.0))), rel=1e-5)
