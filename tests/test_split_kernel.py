"""The fused split finder (ops/split_kernel.py, Pallas interpret mode)
against ``split.find_numerical_splits``, the XLA form it replaces on the
Pallas tiers: the same contract, field for field.  Gains and sums agree
to 1e-5 relative; the choice (threshold, default direction) agrees
wherever the top two gains differ by more than that, and exactly where
the histograms are exact in float32 (the tie cases)."""
import numpy as np
import pytest

import jax.numpy as jnp

from lightgbm_tpu.ops.hist_plan import (FINDER_ROWS, FINDER_VMEM_BUDGET,
                                        FINDER_VMEM_LIMIT, finder_block,
                                        finder_vmem_bytes)
from lightgbm_tpu.ops.split import (MISSING_NAN, MISSING_NONE, MISSING_ZERO,
                                    find_numerical_splits)
from lightgbm_tpu.ops.split_kernel import (find_numerical_splits_fused,
                                           finder_scans)

CFG = dict(lambda_l1=0.0, lambda_l2=1.0, max_delta_step=0.0,
           min_data_in_leaf=5.0, min_sum_hessian_in_leaf=1e-3,
           min_gain_to_split=0.0)
TOL = 1e-5


def make_case(seed=0, R=12, F=11, B=24, missing="mixed", default_bin=None,
              num_bin=None, monotone=False, tight=False, exact=False,
              int_counts=False, count_scale=40):
    """Histograms of ``R`` leaves x ``F`` features whose bins add up to
    each leaf's totals, and the finder's other arguments.  ``exact``:
    gradients and hessians in quarters, so every sum is exact in float32
    whatever its order."""
    rng = np.random.RandomState(seed)
    nb = (rng.randint(2, B + 1, size=F) if num_bin is None
          else np.broadcast_to(num_bin, (F,))).astype(np.int32)
    nb[rng.randint(F)] = B          # the histogram's width is some feature's
    miss = (rng.randint(0, 3, size=F) if isinstance(missing, str)
            else np.broadcast_to(missing, (F,))).astype(np.int32)
    dbin = (np.array([rng.randint(0, n) for n in nb])
            if default_bin is None
            else np.minimum(default_bin, nb - 1)).astype(np.int32)
    mono = (rng.randint(-1, 2, size=F) if monotone
            else np.zeros(F)).astype(np.int32)
    # rows of each leaf, spread over every feature's bins, none empty
    # (an empty bin ties its threshold with its neighbour's)
    n_rows = rng.randint(count_scale, 6 * count_scale, size=R) + B
    hist = np.zeros((R, F, B, 3), np.float64)
    for f in range(F):
        p = rng.dirichlet(np.ones(nb[f]) * 0.7)
        for r in range(R):
            hist[r, f, :nb[f], 2] = 1 + rng.multinomial(n_rows[r] - nb[f], p)
    g = rng.randn(R, F, B)
    h = rng.rand(R, F, B) + 0.05
    if exact:
        g, h = np.round(g * 4) / 4, np.round(h * 4 + 1) / 4
    hist[..., 0] = g * hist[..., 2]
    hist[..., 1] = h * hist[..., 2]
    # one leaf's totals are every feature's: the remainder into bin 0
    sg, sh = hist[:, 0, :, 0].sum(1), hist[:, 0, :, 1].sum(1)
    hist[:, :, 0, 0] += sg[:, None] - hist[..., 0].sum(2)
    hist[:, :, 0, 1] += sh[:, None] - hist[..., 1].sum(2)
    min_c = np.full(R, -np.inf, np.float32)
    max_c = np.full(R, np.inf, np.float32)
    if tight:
        out = -sg / (sh + 1.0)
        min_c, max_c = (out - 0.05).astype(np.float32), \
            (out + 0.05).astype(np.float32)
    args = dict(
        hist=hist.astype(np.float32), sum_grad=sg.astype(np.float32),
        sum_hess=sh.astype(np.float32), num_data=n_rows.astype(np.float32),
        num_bin=nb, missing_type=miss, default_bin=dbin, monotone=mono,
        min_c=min_c, max_c=max_c)
    if int_counts:
        args["hist_count"] = hist[..., 2].astype(np.int32)
        args["num_data"] = n_rows.astype(np.int32)
    return args


def run_both(args, cfg=CFG, interpret=True):
    a = {k: jnp.asarray(v) for k, v in args.items()}
    ref = find_numerical_splits(**a, cfg=cfg)
    got = find_numerical_splits_fused(
        **a, cfg=cfg, interpret=interpret,
        scans=finder_scans(args["num_bin"], args["missing_type"]))
    return ref, got


def assert_same_splits(ref, got, exact_choice=False, tol=TOL,
                       min_same=0.95):
    ref = ref._replace(**{k: np.asarray(v) for k, v in ref._asdict().items()})
    got = got._replace(**{k: np.asarray(v) for k, v in got._asdict().items()})
    for name, r, g in zip(ref._fields, ref, got):
        assert r.shape == g.shape and r.dtype == g.dtype, name
    none = np.isneginf(ref.gain)
    assert np.array_equal(none, np.isneginf(got.gain))
    assert not np.isnan(got.gain).any()
    same = (ref.threshold == got.threshold) \
        & (ref.default_left == got.default_left)
    if exact_choice:
        assert same.all()
    # where the choice differs the two gains are the top two, a rounding
    # apart (an empty default bin makes both scans' thresholds one
    # partition); that is rare
    assert same.mean() > min_same, same.mean()
    scale = np.abs(np.where(none, 0.0, ref.gain)) + 1.0
    with np.errstate(invalid="ignore"):
        assert (np.abs(np.where(none, 0.0, ref.gain - got.gain))
                <= 50 * tol * scale).all()
    for name in ("gain", "left_sum_grad", "left_sum_hess", "left_count",
                 "left_output", "right_output"):
        r, g = getattr(ref, name)[same & ~none], \
            getattr(got, name)[same & ~none]
        np.testing.assert_allclose(g, r, rtol=tol, atol=tol * (
            np.abs(r).max() if r.size else 1.0), err_msg=name)
    # a leaf with no split keeps the reference's filler too
    for name in ("threshold", "default_left", "left_count"):
        assert np.array_equal(getattr(ref, name)[none],
                              getattr(got, name)[none]), name
    assert not got.cat_dir.any()


CASES = {
    "missing_none": dict(missing=MISSING_NONE),
    "missing_zero_default_bin_0": dict(missing=MISSING_ZERO, default_bin=0),
    "missing_zero_default_bin_gt_0": dict(missing=MISSING_ZERO),
    "missing_nan": dict(missing=MISSING_NAN),
    "two_bin_nan_feature": dict(missing=MISSING_NAN, num_bin=2, B=2, F=5),
    "ragged_num_bin_mixed_missing": dict(seed=3),
    "monotone_tight_bounds": dict(monotone=True, tight=True, seed=4),
    "int32_counts": dict(int_counts=True, seed=5),
    "features_not_a_block_multiple": dict(F=37, seed=6),
    "rows_not_a_block_multiple": dict(R=FINDER_ROWS + 9, F=9, B=12, seed=7),
    "bins_255": dict(R=5, F=9, B=255, seed=8),
    "bins_over_two_lane_tiles": dict(R=4, F=8, B=300, seed=9),
}


@pytest.mark.parametrize("name", list(CASES))
def test_fused_finder_matches_the_xla_form(name):
    ref, got = run_both(make_case(**CASES[name]))
    assert np.isfinite(np.asarray(ref.gain)).any()
    assert_same_splits(ref, got)


@pytest.mark.parametrize("cfg", [
    dict(lambda_l1=0.7), dict(max_delta_step=0.05),
    dict(lambda_l1=0.3, max_delta_step=0.1, lambda_l2=0.0,
         min_gain_to_split=0.5)],
    ids=["lambda_l1", "max_delta_step", "l1_mds_min_gain"])
def test_fused_finder_regularisation(cfg):
    ref, got = run_both(make_case(seed=11, monotone=True),
                        cfg={**CFG, **cfg})
    assert_same_splits(ref, got)


@pytest.mark.parametrize("cfg", [dict(min_data_in_leaf=1e6),
                                 dict(min_sum_hessian_in_leaf=1e9)],
                         ids=["min_data_in_leaf", "min_sum_hessian_in_leaf"])
def test_limits_that_reject_every_threshold(cfg):
    """No threshold passes: every gain is K_MIN_SCORE, and the fields
    are the reference's filler (its argmax of all -inf)."""
    ref, got = run_both(make_case(seed=12), cfg={**CFG, **cfg})
    assert np.isneginf(np.asarray(ref.gain)).all()
    assert_same_splits(ref, got, exact_choice=True)


def test_limits_that_reject_one_leaf():
    """``min_data_in_leaf`` over one leaf's rows and under the others'."""
    args = make_case(seed=13, count_scale=40)
    args["hist"][0] *= 0.1
    args["hist"][0, :, :, 2] = np.floor(args["hist"][0, :, :, 2])
    args["num_data"][0] = args["hist"][0, 0, :, 2].sum()
    args["sum_grad"][0] *= 0.1
    args["sum_hess"][0] *= 0.1
    ref, got = run_both(args, cfg={**CFG, "min_data_in_leaf": 30.0})
    gain = np.asarray(ref.gain)
    assert np.isneginf(gain[0]).all() and np.isfinite(gain[1:]).any()
    assert_same_splits(ref, got)


def test_int32_counts_above_2_to_24():
    """Counts that float32 cannot hold: the prefix sums, the
    ``min_data_in_leaf`` test and ``left_count`` are integers, exact."""
    args = make_case(seed=14, int_counts=True, R=6, F=7, B=16, count_scale=2)
    big = (1 << 24) + 1
    args["hist_count"] = args["hist_count"] * big + (
        np.arange(16, dtype=np.int32) % 3)[None, None, :] * (
            np.arange(16) < args["num_bin"][:, None])[None]
    args["num_data"] = args["hist_count"][:, 0].sum(1).astype(np.int32)
    # every feature's counts add up to the leaf's
    args["hist_count"][:, :, 0] += args["num_data"][:, None] \
        - args["hist_count"].sum(2)
    assert args["hist_count"].max() > 1 << 26 and args["num_data"].min() > 0
    ref, got = run_both(args, cfg={**CFG,
                                    "min_data_in_leaf": 5.0 * (1 << 24)})
    assert np.asarray(ref.left_count).dtype == np.int32
    assert np.array_equal(np.asarray(ref.left_count),
                          np.asarray(got.left_count))
    assert np.asarray(got.left_count).max() > 1 << 24
    assert_same_splits(ref, got)


def test_inert_rows():
    """Rows of the frontier that stand for no leaf (negative slots): an
    empty histogram under some other leaf's totals, or zero rows.  They
    get the reference's answer, and the rows beside them their own."""
    args = make_case(seed=15, R=10)
    args["hist"][[2, 7]] = 0.0
    args["hist"][4] = 0.0
    args["num_data"][4] = 0.0
    args["sum_grad"][4] = args["sum_hess"][4] = 0.0
    ref, got = run_both(args)
    assert_same_splits(ref, got)


def test_exact_ties_take_the_references_first_maximum():
    """Two features with one histogram, and flat stretches of empty bins
    (equal gains at neighbouring thresholds): the reference's order —
    the default-left scan first and in it the larger threshold, then the
    default-right scan's smaller — to the bit."""
    args = make_case(seed=16, R=8, F=10, B=32, exact=True, num_bin=32,
                     missing=np.array([0, 0, 1, 1, 2, 2, 0, 1, 2, 0]),
                     default_bin=np.array([0, 0, 3, 3, 0, 0, 5, 0, 9, 2]))
    hist = args["hist"]
    hist[:, :, 10:20] = 0.0                   # a flat stretch in every scan
    hist[:, :, 25:30] = 0.0
    hist[:, :, 0, 2] += args["num_data"][:, None] - hist[..., 2].sum(2)
    hist[:, :, 0, 0] += args["sum_grad"][:, None] - hist[..., 0].sum(2)
    hist[:, :, 0, 1] += args["sum_hess"][:, None] - hist[..., 1].sum(2)
    for a, b in ((0, 1), (2, 3), (4, 5)):     # twins, meta and all
        hist[:, b] = hist[:, a]
    ref, got = run_both(args, cfg={**CFG, "min_data_in_leaf": 1.0})
    assert_same_splits(ref, got, exact_choice=True)
    thr, gain = np.asarray(got.threshold), np.asarray(got.gain)
    for a, b in ((0, 1), (2, 3), (4, 5)):
        assert np.array_equal(thr[:, a], thr[:, b])
        assert np.array_equal(gain[:, a], gain[:, b])
    # a flat stretch was really chosen from somewhere
    flat = ((thr >= 9) & (thr < 20)) | ((thr >= 24) & (thr < 30))
    assert (flat & np.isfinite(gain)).any()


def test_one_scan_form_is_the_two_scan_form():
    """``scans=1`` (no used feature is two-scan) leaves out a scan whose
    gains are all K_MIN_SCORE: the same result as tracing it."""
    args = make_case(seed=17, missing=MISSING_NONE)
    assert finder_scans(args["num_bin"], args["missing_type"]) == 1
    a = {k: jnp.asarray(v) for k, v in args.items()}
    one = find_numerical_splits_fused(**a, cfg=CFG, scans=1, interpret=True)
    two = find_numerical_splits_fused(**a, cfg=CFG, scans=2, interpret=True)
    for name, x, y in zip(one._fields, one, two):
        assert np.array_equal(np.asarray(x), np.asarray(y)), name
    two_bin = dict(num_bin=np.array([2, 2, 7]),
                   missing_type=np.array([MISSING_NAN, MISSING_ZERO, 0]))
    assert finder_scans(**two_bin) == 1
    assert finder_scans(np.array([3]), np.array([MISSING_NAN])) == 2


@pytest.mark.parametrize("rows,scans,int_counts",
                         [(84, 1, False), (252, 1, False), (252, 2, True),
                          (510, 2, False), (6, 1, False)])
def test_finder_block_fits_the_budget_the_plan_states(rows, scans,
                                                      int_counts):
    """The block is sized by ``finder_vmem_bytes`` under the module's
    budget, not by a literal: leaf rows up to the output's lanes, whole
    sublane tiles of features, and the next size up would not fit (or is
    past the cap)."""
    r_blk, f_blk = finder_block(rows, 255, scans, int_counts)
    assert r_blk == min(rows, FINDER_ROWS) and f_blk % 8 == 0
    cost = finder_vmem_bytes(r_blk, f_blk, 256, scans, int_counts)
    assert cost <= FINDER_VMEM_BUDGET < FINDER_VMEM_LIMIT <= 128 << 20
    assert f_blk == 32 or finder_vmem_bytes(
        r_blk, 2 * f_blk, 256, scans, int_counts) > FINDER_VMEM_BUDGET
    assert finder_vmem_bytes(r_blk, f_blk, 256, 2, True) > cost \
        or (scans, int_counts) == (2, True)
