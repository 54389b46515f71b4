"""Best-split search over histograms, vectorized across (leaf, feature, bin).

TPU-native re-design of FeatureHistogram's per-feature scans
(reference: src/treelearner/feature_histogram.hpp:75-271 numerical +
categorical drivers, :503-643 FindBestThresholdSequence, :440-501 gain
math).  The reference walks bins sequentially per feature with
continue/break pruning; here every (leaf, feature, threshold, direction)
candidate is scored at once with cumulative sums and masks — the checks
are monotone along a scan so break/continue collapse to validity masks.

Because this framework stores full per-feature bin ranges (no collapsed
default slot), the reference's ``bias`` bookkeeping disappears; what
remains of missing handling is exactly:
  * MissingType::None  — single default-left scan over all thresholds.
  * MissingType::Zero  — two scans with the default(zero) bin excluded
    from directional accumulation (zeros ride the default direction).
  * MissingType::NaN   — two scans; the NaN bin (last) is excluded from
    the default-left accumulation and rides the default direction.
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

K_EPSILON = 1e-15          # reference meta.h:38
K_MIN_SCORE = -jnp.inf

MISSING_NONE = 0
MISSING_ZERO = 1
MISSING_NAN = 2


# ---------------------------------------------------------------------------
# Gain math (reference feature_histogram.hpp:439-501)
# ---------------------------------------------------------------------------
def threshold_l1(s, l1):
    reg = jnp.maximum(0.0, jnp.abs(s) - l1)
    return jnp.sign(s) * reg


def calculate_leaf_output(sum_grad, sum_hess, l1, l2, max_delta_step):
    ret = -threshold_l1(sum_grad, l1) / (sum_hess + l2)
    if max_delta_step <= 0.0:
        return ret
    return jnp.clip(ret, -max_delta_step, max_delta_step)


def _leaf_output_constrained(sum_grad, sum_hess, l1, l2, max_delta_step,
                             min_c, max_c):
    ret = calculate_leaf_output(sum_grad, sum_hess, l1, l2, max_delta_step)
    return jnp.clip(ret, min_c, max_c)


def leaf_gain_given_output(sum_grad, sum_hess, l1, l2, output):
    sg_l1 = threshold_l1(sum_grad, l1)
    return -(2.0 * sg_l1 * output + (sum_hess + l2) * output * output)


def leaf_split_gain(sum_grad, sum_hess, l1, l2, max_delta_step):
    out = calculate_leaf_output(sum_grad, sum_hess, l1, l2, max_delta_step)
    return leaf_gain_given_output(sum_grad, sum_hess, l1, l2, out)


def split_gains(sl_g, sl_h, sr_g, sr_h, l1, l2, max_delta_step,
                min_c, max_c, monotone):
    """Gain of a candidate split; zero when it violates a monotone
    constraint (reference feature_histogram.hpp:454-467)."""
    lo = _leaf_output_constrained(sl_g, sl_h, l1, l2, max_delta_step,
                                  min_c, max_c)
    ro = _leaf_output_constrained(sr_g, sr_h, l1, l2, max_delta_step,
                                  min_c, max_c)
    gain = (leaf_gain_given_output(sl_g, sl_h, l1, l2, lo)
            + leaf_gain_given_output(sr_g, sr_h, l1, l2, ro))
    violates = ((monotone > 0) & (lo > ro)) | ((monotone < 0) & (lo < ro))
    return jnp.where(violates, 0.0, gain)


# ---------------------------------------------------------------------------
# Packed per-leaf candidate layout (round 7).
#
# The serial grower caches each leaf's best split (the reference's
# best_split_per_leaf_, serial_tree_learner.h) — previously a struct of
# ELEVEN (L,)/(L, B) arrays refreshed with eleven separate scatters per
# round (plus eight more for forced splits).  The cache is now ONE
# (L, CAND_COLS + B) f32 array written with a single width-bounded
# scatter of the packed block find_best_split_block returns; columns
# hold int/bool payloads exactly (feature < 2^24, threshold < 256).
# ---------------------------------------------------------------------------
CAND_GAIN = 0
CAND_FEATURE = 1
CAND_THRESHOLD = 2
CAND_DEFAULT_LEFT = 3
CAND_LSG = 4
CAND_LSH = 5
CAND_LSC = 6
CAND_LOUT = 7
CAND_ROUT = 8
CAND_CAT_DIR = 9
CAND_COLS = 10            # + max_feature_bin cat-mask columns after these

FORCED_GAIN = 0
FORCED_THRESHOLD = 1
FORCED_DEFAULT_LEFT = 2
FORCED_LSG = 3
FORCED_LSH = 4
FORCED_LSC = 5
FORCED_LOUT = 6
FORCED_ROUT = 7
FORCED_COLS = 8


class SplitResult(NamedTuple):
    """Best split per (leaf, feature) — the SplitInfo analog
    (reference split_info.hpp:18-288) as a struct of arrays."""
    gain: jax.Array          # (L, F)
    threshold: jax.Array     # (L, F) int32; numerical bin thr, or cat pos
    default_left: jax.Array  # (L, F) bool
    left_sum_grad: jax.Array
    left_sum_hess: jax.Array
    left_count: jax.Array
    left_output: jax.Array   # (L, F) constrained left-leaf output
    right_output: jax.Array  # (L, F) constrained right-leaf output
    cat_dir: jax.Array       # (L, F) int32, sorted-scan direction (cat only)


# ---------------------------------------------------------------------------
def find_numerical_splits(hist: jax.Array, sum_grad: jax.Array,
                          sum_hess: jax.Array, num_data: jax.Array,
                          num_bin: jax.Array, missing_type: jax.Array,
                          default_bin: jax.Array, monotone: jax.Array,
                          min_c: jax.Array, max_c: jax.Array,
                          cfg: Dict[str, float],
                          hist_count: Optional[jax.Array] = None
                          ) -> SplitResult:
    """Vectorized FindBestThresholdNumerical over every (leaf, feature).

    Args:
      hist: (L, F, B, 3) per-feature histograms.
      sum_grad/sum_hess/num_data: (L,) leaf totals (raw; epsilon
        adjustments happen here, matching FindBestThreshold's
        ``sum_hessian + 2*kEpsilon``).
      hist_count: (L, F, B) int32 row counts, with ``num_data`` int32
        too: where a node can hold more rows than float32 counts
        (2^24), every count here — prefix sums, parent minus side, the
        ``min_data_in_leaf`` test, ``left_count`` — is integer
        arithmetic and exact; ``hist``'s own count channel is unread.
      num_bin/missing_type/default_bin/monotone: (F,) metadata.
      min_c/max_c: (L,) monotone output constraints of the leaf.
      cfg: scalars — lambda_l1, lambda_l2, max_delta_step,
        min_data_in_leaf, min_sum_hessian_in_leaf, min_gain_to_split.
    """
    L, F, B, _ = hist.shape
    l1 = cfg["lambda_l1"]
    l2 = cfg["lambda_l2"]
    mds = cfg["max_delta_step"]
    min_data = cfg["min_data_in_leaf"]
    min_hess = cfg["min_sum_hessian_in_leaf"]
    min_gain = cfg["min_gain_to_split"]

    total_h = sum_hess + 2 * K_EPSILON                      # (L,)
    gain_shift = leaf_split_gain(sum_grad, total_h, l1, l2, mds)
    min_gain_shift = gain_shift + min_gain                  # (L,)

    bins = jnp.arange(B, dtype=jnp.int32)
    h_g, h_h, h_c = hist[..., 0], hist[..., 1], hist[..., 2]

    def masked(h, mask_fb):
        return h * (1.0 - mask_fb[None, :, :])

    masked_c = masked
    if hist_count is not None:
        h_c = hist_count
        min_data = jnp.ceil(min_data).astype(jnp.int32)

        def masked_c(c, mask_fb):
            return jnp.where(mask_fb[None, :, :], 0, c)

    is_default = bins[None, :] == default_bin[:, None]       # (F, B)
    is_nan_bin = bins[None, :] == (num_bin - 1)[:, None]     # (F, B)
    two_scan = (num_bin > 2) & (missing_type != MISSING_NONE)  # (F,)
    m_zero = missing_type == MISSING_ZERO
    m_nan = missing_type == MISSING_NAN

    # ---- scan A: default-right (dir=+1); only for two-scan features ----
    excl_a = jnp.where(m_zero[:, None], is_default, jnp.zeros_like(is_default))
    left_g_a = jnp.cumsum(masked(h_g, excl_a), axis=2)
    left_h_a = jnp.cumsum(masked(h_h, excl_a), axis=2) + K_EPSILON
    left_c_a = jnp.cumsum(masked_c(h_c, excl_a), axis=2)
    # valid thresholds: t <= nb-2; Zero: t != default_bin
    t_ok_a = (bins[None, :] <= (num_bin - 2)[:, None])
    t_ok_a &= ~(m_zero[:, None] & is_default)
    t_ok_a &= two_scan[:, None]

    # ---- scan B: default-left (dir=-1) ----
    excl_b = jnp.where(m_zero[:, None], is_default,
                       jnp.where((m_nan & two_scan)[:, None], is_nan_bin,
                                 jnp.zeros_like(is_default)))
    cum_g_b = jnp.cumsum(masked(h_g, excl_b), axis=2)
    cum_h_b = jnp.cumsum(masked(h_h, excl_b), axis=2)
    cum_c_b = jnp.cumsum(masked_c(h_c, excl_b), axis=2)
    tot_g_b = cum_g_b[:, :, -1:]
    tot_h_b = cum_h_b[:, :, -1:]
    tot_c_b = cum_c_b[:, :, -1:]
    right_g_b = tot_g_b - cum_g_b
    right_h_b = tot_h_b - cum_h_b + K_EPSILON
    right_c_b = tot_c_b - cum_c_b
    left_g_b = sum_grad[:, None, None] - right_g_b
    left_h_b = total_h[:, None, None] - right_h_b
    left_c_b = num_data[:, None, None] - right_c_b
    # valid thresholds: t <= nb-2 (None/Zero), t <= nb-3 (NaN two-scan);
    # Zero with default_bin d > 0: t != d-1
    last_b = jnp.where(m_nan & two_scan, num_bin - 3, num_bin - 2)
    t_ok_b = bins[None, :] <= last_b[:, None]
    t_ok_b &= ~(m_zero[:, None]
                & (bins[None, :] == (default_bin - 1)[:, None])
                & (default_bin > 0)[:, None])

    def candidate_gain(lg, lh, lc, t_ok):
        rg = sum_grad[:, None, None] - lg
        rh = total_h[:, None, None] - lh
        rc = num_data[:, None, None] - lc
        ok = (t_ok[None, :, :]
              & (lc >= min_data) & (rc >= min_data)
              & (lh >= min_hess) & (rh >= min_hess))
        g = split_gains(lg, lh, rg, rh, l1, l2, mds,
                        min_c[:, None, None], max_c[:, None, None],
                        monotone[None, :, None])
        g = jnp.where(ok & (g > min_gain_shift[:, None, None]), g,
                      K_MIN_SCORE)
        return g

    gain_a = candidate_gain(left_g_a, left_h_a, left_c_a, t_ok_a)  # (L,F,B)
    gain_b = candidate_gain(left_g_b, left_h_b, left_c_b, t_ok_b)

    # Selection order replicates the reference: the default-left scan
    # runs first and ties keep the first-seen maximum; within it larger
    # thresholds are seen first (right-to-left walk).
    gain_b_rev = gain_b[:, :, ::-1]
    all_gains = jnp.concatenate([gain_b_rev, gain_a], axis=2)  # (L,F,2B)
    best_idx = jnp.argmax(all_gains, axis=2)                   # (L, F)
    # jnp.max == value at argmax; extracted values use a one-hot
    # masked-sum instead of take_along_axis — TPU's gather lowering ran
    # at ~1.6 GiB/s in profiles (7 x 84 us per refresh) while these
    # reduce fusions run at HBM speed
    best_gain = jnp.max(all_gains, axis=2)
    from_b = best_idx < B
    thr = jnp.where(from_b, B - 1 - best_idx, best_idx - B).astype(jnp.int32)
    oh_thr = (bins[None, None, :]
              == jnp.clip(thr, 0, B - 1)[:, :, None])          # (L,F,B)

    def pick(arr_a, arr_b):
        sel = jnp.where(from_b[:, :, None], arr_b, arr_a)
        return jnp.sum(jnp.where(oh_thr, sel, jnp.zeros_like(sel)), axis=2)

    lg = pick(left_g_a, left_g_b)
    lh = pick(left_h_a, left_h_b)
    lc = pick(left_c_a, left_c_b)

    default_left = from_b
    # two-bin NaN features force default-right (feature_histogram.hpp:100-103)
    force_right = (~two_scan & m_nan)[None, :]
    default_left = jnp.where(force_right, False, default_left)

    valid = best_gain > K_MIN_SCORE
    final_gain = jnp.where(valid, best_gain - min_gain_shift[:, None],
                           K_MIN_SCORE)
    mc = min_c[:, None]
    xc = max_c[:, None]
    left_out = _leaf_output_constrained(lg, lh, l1, l2, mds, mc, xc)
    right_out = _leaf_output_constrained(sum_grad[:, None] - lg,
                                         total_h[:, None] - lh,
                                         l1, l2, mds, mc, xc)
    return SplitResult(
        gain=final_gain,
        threshold=thr,
        default_left=default_left,
        left_sum_grad=lg,
        left_sum_hess=lh - K_EPSILON,
        left_count=lc,
        left_output=left_out,
        right_output=right_out,
        cat_dir=jnp.zeros_like(thr),
    )


# ---------------------------------------------------------------------------
def find_categorical_splits(hist: jax.Array, sum_grad: jax.Array,
                            sum_hess: jax.Array, num_data: jax.Array,
                            num_bin: jax.Array, missing_type: jax.Array,
                            min_c: jax.Array, max_c: jax.Array,
                            cfg: Dict[str, float]) -> SplitResult:
    """Vectorized FindBestThresholdCategorical
    (reference feature_histogram.hpp:110-271): one-hot splits for small
    cardinality, otherwise categories sorted by grad/hess ratio and
    scanned from both ends.

    ``threshold`` in the result is the number of sorted categories going
    left minus one (onehot: the single bin); ``cat_dir`` is +1/-1 for the
    scan direction (0 = onehot mode).  ``build_cat_bitset`` reconstructs
    the explicit category set for the chosen feature.
    """
    L, F, B, _ = hist.shape
    l1 = cfg["lambda_l1"]
    l2_base = cfg["lambda_l2"]
    mds = cfg["max_delta_step"]
    min_data = cfg["min_data_in_leaf"]
    min_hess = cfg["min_sum_hessian_in_leaf"]
    min_gain = cfg["min_gain_to_split"]
    cat_smooth = cfg["cat_smooth"]
    cat_l2 = cfg["cat_l2"]
    max_cat_threshold = int(cfg["max_cat_threshold"])
    max_cat_to_onehot = int(cfg["max_cat_to_onehot"])
    min_data_per_group = cfg["min_data_in_group"]

    total_h = sum_hess + 2 * K_EPSILON
    gain_shift = leaf_split_gain(sum_grad, total_h, l1, l2_base, mds)
    min_gain_shift = gain_shift + min_gain                    # (L,)

    is_full = missing_type == MISSING_NONE                    # (F,)
    used_bin = num_bin - 1 + is_full.astype(jnp.int32)        # (F,)
    bins = jnp.arange(B, dtype=jnp.int32)
    in_range = bins[None, :] < used_bin[:, None]              # (F, B)

    h_g, h_h, h_c = hist[..., 0], hist[..., 1], hist[..., 2]

    # ---------------- one-hot mode ----------------
    lg1 = h_g
    lh1 = h_h + K_EPSILON
    lc1 = h_c
    rg1 = sum_grad[:, None, None] - lg1
    rh1 = total_h[:, None, None] - lh1   # = sum_h - h_h - eps + 2eps... matches
    rc1 = num_data[:, None, None] - lc1
    ok1 = (in_range[None, :, :]
           & (h_c >= min_data) & (rc1 >= min_data)
           & (h_h >= min_hess)
           & (rh1 >= min_hess))
    g1 = split_gains(rg1, rh1, lg1, lh1, l1, l2_base, mds,
                     min_c[:, None, None], max_c[:, None, None], 0)
    # note: reference computes gain(other, this) — order matters only for
    # monotone (cats have none), but keep the same operand order.
    g1 = jnp.where(ok1 & (g1 > min_gain_shift[:, None, None]), g1,
                   K_MIN_SCORE)
    best1_t = jnp.argmax(g1, axis=2).astype(jnp.int32)
    best1_gain = jnp.take_along_axis(g1, best1_t[:, :, None], axis=2)[:, :, 0]
    best1_lg = jnp.take_along_axis(lg1, best1_t[:, :, None], axis=2)[:, :, 0]
    best1_lh = jnp.take_along_axis(lh1, best1_t[:, :, None], axis=2)[:, :, 0]
    best1_lc = jnp.take_along_axis(lc1, best1_t[:, :, None], axis=2)[:, :, 0]

    # ---------------- sorted mode ----------------
    l2s = l2_base + cat_l2
    eligible = in_range[None, :, :] & (h_c >= cat_smooth)      # (L, F, B)
    score = h_g / (h_h + cat_smooth)
    sort_key = jnp.where(eligible, score, jnp.inf)
    order = jnp.argsort(sort_key, axis=2)                      # (L, F, B)
    n_used = eligible.sum(axis=2).astype(jnp.int32)            # (L, F)

    sg_s = jnp.take_along_axis(h_g, order, axis=2)
    sh_s = jnp.take_along_axis(h_h, order, axis=2)
    sc_s = jnp.take_along_axis(h_c, order, axis=2)

    max_num_cat = jnp.minimum(max_cat_threshold, (n_used + 1) // 2)  # (L,F)

    def direction_scan(gs, hs, cs):
        """Prefix scan from the front of a sorted order, with the
        min_data_in_group grouping chain (sequential over positions)."""
        cum_g = jnp.cumsum(gs, axis=2)
        cum_h = jnp.cumsum(hs, axis=2) + K_EPSILON
        cum_c = jnp.cumsum(cs, axis=2)
        pos = jnp.arange(B, dtype=jnp.int32)
        within = (pos[None, None, :] < max_num_cat[:, :, None]) \
            & (pos[None, None, :] < n_used[:, :, None])
        rc = num_data[:, None, None] - cum_c
        rh = total_h[:, None, None] - cum_h
        base_ok = (within
                   & (cum_c >= min_data) & (cum_h >= min_hess)
                   & (rc >= min_data) & (rc >= min_data_per_group)
                   & (rh >= min_hess))
        # grouping chain: candidate evaluated only when count since the
        # last evaluated candidate >= min_data_in_group
        def chain(carry, x):
            cnt_cur = carry
            c_i, ok_i = x
            cnt_cur = cnt_cur + c_i
            eval_i = ok_i & (cnt_cur >= min_data_per_group)
            cnt_cur = jnp.where(eval_i, 0.0, cnt_cur)
            return cnt_cur, eval_i
        _, evals = jax.lax.scan(
            chain, jnp.zeros((L, F)),
            (jnp.moveaxis(cs, 2, 0), jnp.moveaxis(base_ok, 2, 0)))
        ok = jnp.moveaxis(evals, 0, 2)
        rg = sum_grad[:, None, None] - cum_g
        g = split_gains(cum_g, cum_h, rg, rh, l1, l2s, mds,
                        min_c[:, None, None], max_c[:, None, None], 0)
        g = jnp.where(ok & (g > min_gain_shift[:, None, None]), g,
                      K_MIN_SCORE)
        return g, cum_g, cum_h, cum_c

    g_fwd, cgf, chf, ccf = direction_scan(sg_s, sh_s, sc_s)
    g_bwd, cgb, chb, ccb = direction_scan(
        _shift_used(sg_s, n_used),
        _shift_used(sh_s, n_used), _shift_used(sc_s, n_used))

    def best_of(g):
        t = jnp.argmax(g, axis=2).astype(jnp.int32)
        return t, jnp.take_along_axis(g, t[:, :, None], axis=2)[:, :, 0]

    tf, gf = best_of(g_fwd)
    tb, gb = best_of(g_bwd)
    use_fwd = gf >= gb
    sorted_gain = jnp.where(use_fwd, gf, gb)
    sorted_t = jnp.where(use_fwd, tf, tb)
    sorted_dir = jnp.where(use_fwd, 1, -1).astype(jnp.int32)

    def gather3(cg, ch, cc, t):
        return (jnp.take_along_axis(cg, t[:, :, None], axis=2)[:, :, 0],
                jnp.take_along_axis(ch, t[:, :, None], axis=2)[:, :, 0],
                jnp.take_along_axis(cc, t[:, :, None], axis=2)[:, :, 0])

    lgf, lhf, lcf = gather3(cgf, chf, ccf, tf)
    lgb, lhb, lcb = gather3(cgb, chb, ccb, tb)
    sorted_lg = jnp.where(use_fwd, lgf, lgb)
    sorted_lh = jnp.where(use_fwd, lhf, lhb)
    sorted_lc = jnp.where(use_fwd, lcf, lcb)

    use_onehot = (num_bin <= max_cat_to_onehot)[None, :]       # (1, F)
    gain = jnp.where(use_onehot, best1_gain, sorted_gain)
    # net gain (reference: output->gain = best_gain - min_gain_shift)
    gain = jnp.where(gain > K_MIN_SCORE, gain - min_gain_shift[:, None],
                     K_MIN_SCORE)
    thr = jnp.where(use_onehot, best1_t, sorted_t)
    lg = jnp.where(use_onehot, best1_lg, sorted_lg)
    lh = jnp.where(use_onehot, best1_lh, sorted_lh)
    lc = jnp.where(use_onehot, best1_lc, sorted_lc)
    cat_dir = jnp.where(use_onehot, 0, sorted_dir)

    # leaf outputs use the mode's effective l2 (plain for one-hot,
    # +cat_l2 for sorted — reference's `l2` variable mutation)
    l2_eff = jnp.where(use_onehot, l2_base, l2s)
    mc = min_c[:, None]
    xc = max_c[:, None]
    left_out = _leaf_output_constrained(lg, lh, l1, l2_eff, mds, mc, xc)
    right_out = _leaf_output_constrained(sum_grad[:, None] - lg,
                                         total_h[:, None] - lh,
                                         l1, l2_eff, mds, mc, xc)

    return SplitResult(
        gain=gain, threshold=thr,
        default_left=jnp.zeros_like(gain, dtype=bool),
        left_sum_grad=lg, left_sum_hess=lh - K_EPSILON, left_count=lc,
        left_output=left_out, right_output=right_out,
        cat_dir=cat_dir)


def gather_split_at_threshold(hist_f: jax.Array, threshold: jax.Array,
                              sum_grad: jax.Array, sum_hess: jax.Array,
                              num_data: jax.Array, num_bin: jax.Array,
                              missing_type: jax.Array, default_bin: jax.Array,
                              is_cat: jax.Array,
                              cfg: Dict[str, float],
                              hist_count: Optional[jax.Array] = None):
    """Split info at a GIVEN (feature, threshold) per leaf — the forced
    -split evaluation (reference feature_histogram.hpp:273-413
    GatherInfoForThresholdNumerical/Categorical).

    Numerical semantics follow the reference: missing always rides left
    (``default_left=True``), the right side accumulates bins
    ``> threshold`` skipping the default bin for Zero-missing and the
    NaN bin for NaN-missing; gain not exceeding ``min_gain_shift``
    yields -inf (the forced split is then aborted).  Categorical forced
    splits are one-hot at the threshold bin.

    Args:
      hist_f: (L, B, 3) histograms of each leaf's FORCED feature.
      threshold: (L,) int32 bin threshold (categorical: the bin).
      sum_grad/sum_hess/num_data: (L,) leaf totals (sum_hess raw).
      num_bin/missing_type/default_bin/is_cat: (L,) forced-feature meta.
      hist_count: (L, B) int32 counts with ``num_data`` int32, as in
        :func:`find_numerical_splits`; ``left_count`` is then int32.

    Returns: (gain, left_sum_grad, left_sum_hess(+eps removed),
              left_count, left_output, right_output, default_left) —
      all (L,); gain already has min_gain_shift subtracted.
    """
    L, B, _ = hist_f.shape
    l1 = cfg["lambda_l1"]
    l2 = cfg["lambda_l2"]
    mds = cfg["max_delta_step"]
    min_gain = cfg["min_gain_to_split"]

    total_h = sum_hess + 2 * K_EPSILON
    gain_shift = leaf_split_gain(sum_grad, total_h, l1, l2, mds)
    min_gain_shift = gain_shift + min_gain

    bins = jnp.arange(B, dtype=jnp.int32)
    h_g, h_h, h_c = hist_f[..., 0], hist_f[..., 1], hist_f[..., 2]
    if hist_count is not None:
        h_c = hist_count

    # ---- numerical: right side = bins > threshold, minus skips ----
    m_zero = missing_type == MISSING_ZERO
    skip = jnp.where(m_zero[:, None], bins[None, :] == default_bin[:, None],
                     bins[None, :] == (num_bin - 1)[:, None])
    right_sel = (bins[None, :] > threshold[:, None]) \
        & (bins[None, :] <= (num_bin - 1)[:, None]) & ~skip
    rg = jnp.sum(h_g * right_sel, axis=1)
    rh = jnp.sum(h_h * right_sel, axis=1) + K_EPSILON
    rc = jnp.sum(h_c * right_sel, axis=1)
    n_lg = sum_grad - rg
    n_lh = total_h - rh
    n_lc = num_data - rc

    # ---- categorical one-hot at the threshold bin ----
    onehot = bins[None, :] == threshold[:, None]
    c_lg = jnp.sum(h_g * onehot, axis=1)
    c_lh = jnp.sum(h_h * onehot, axis=1) + K_EPSILON
    c_lc = jnp.sum(h_c * onehot, axis=1)
    is_full = missing_type == MISSING_NONE
    used_bin = num_bin - 1 + is_full.astype(jnp.int32)
    cat_ok = threshold < used_bin

    lg = jnp.where(is_cat, c_lg, n_lg)
    lh = jnp.where(is_cat, c_lh, n_lh)
    lc = jnp.where(is_cat, c_lc, n_lc)
    rg2 = sum_grad - lg
    rh2 = total_h - lh
    gain = (leaf_split_gain(lg, lh, l1, l2, mds)
            + leaf_split_gain(rg2, rh2, l1, l2, mds))
    ok = (gain > min_gain_shift) & ~jnp.isnan(gain) \
        & (~is_cat | cat_ok)
    gain = jnp.where(ok, gain - min_gain_shift, K_MIN_SCORE)
    left_out = calculate_leaf_output(lg, lh, l1, l2, mds)
    right_out = calculate_leaf_output(rg2, rh2, l1, l2, mds)
    return (gain, lg, lh - K_EPSILON, lc, left_out, right_out, ~is_cat)


def run_split_finders(hist: jax.Array, sum_grad: jax.Array,
                      sum_hess: jax.Array, count: jax.Array,
                      min_c: jax.Array, max_c: jax.Array,
                      cfg: Dict[str, float], f_num_bin: jax.Array,
                      f_missing: jax.Array, f_default_bin: jax.Array,
                      f_monotone: jax.Array, f_is_cat: jax.Array,
                      feature_mask: jax.Array,
                      has_categorical: bool,
                      hist_count: Optional[jax.Array] = None,
                      finder=None) -> Tuple[SplitResult, jax.Array]:
    """Per-(leaf-row, feature) finder pass shared by every best-split
    path: numerical finders, the categorical overlay where-merged by
    `f_is_cat`, and the feature-mask gain fill.  Leaf-shaped args are
    aligned with hist's first axis.  Returns (res, gains) with gains
    masked to K_MIN_SCORE outside `feature_mask`.  With ``hist_count``
    (int32, ``count`` too) the numerical finder counts in integers and
    ``res.left_count`` is int32; the categorical finder still reads
    ``hist``'s float32 count channel (exact to 2^24 rows a node).
    ``finder``: the numerical finder's form, a
    ``split_kernel.Finder`` (``HistPlan.finder``; None is the XLA
    form)."""
    numerical = find_numerical_splits
    if finder is not None and finder.form == "fused":
        from .split_kernel import find_numerical_splits_fused
        numerical = functools.partial(
            find_numerical_splits_fused, scans=finder.scans,
            interpret=finder.interpret)
    num_res = numerical(
        hist, sum_grad, sum_hess, count, f_num_bin, f_missing,
        f_default_bin, f_monotone, min_c, max_c, cfg,
        hist_count=hist_count)
    if has_categorical:
        cat_res = find_categorical_splits(
            hist, sum_grad, sum_hess, count.astype(jnp.float32),
            f_num_bin, f_missing, min_c, max_c, cfg)
        if hist_count is not None:
            cat_res = cat_res._replace(left_count=jnp.round(
                cat_res.left_count).astype(jnp.int32))
        icat = f_is_cat[None, :]
        res = SplitResult(*[jnp.where(icat, c, n) for c, n
                            in zip(cat_res, num_res)])
    else:
        res = num_res
    gains = jnp.where(feature_mask[None, :], res.gain, K_MIN_SCORE)
    return res, gains


def find_best_split_block(feat_hist: jax.Array, sum_grad: jax.Array,
                          sum_hess: jax.Array, count: jax.Array,
                          min_c: jax.Array, max_c: jax.Array,
                          cfg: Dict[str, float], f_num_bin: jax.Array,
                          f_missing: jax.Array, f_default_bin: jax.Array,
                          f_monotone: jax.Array, f_is_cat: jax.Array,
                          feature_mask: jax.Array,
                          has_categorical: bool,
                          feat_count: Optional[jax.Array] = None,
                          finder=None):
    """Best split per FRONTIER leaf as one packed candidate block.

    Every shape here is bounded by the frontier width W' the caller
    chose (the grower's lax.cond ladder passes the narrowest packed-
    strip width covering the active frontier) — never by the padded
    leaf count.  The per-feature finders run, the best feature is
    reduced with a SINGLE stacked one-hot masked-sum (one fused
    reduction instead of nine take_along_axis gathers — TPU gather
    lowering ran ~1.6 GiB/s in profiles while these reduce fusions run
    at HBM speed), and the result is packed into the (W', CAND_COLS+B)
    block the grower scatters into its candidate cache in one write.

    Args:
      feat_hist: (W', F, B, 3) per-feature histograms of the frontier.
      sum_grad/sum_hess/count/min_c/max_c: (W',) leaf totals/bounds.
      f_*: (F,) feature metadata; feature_mask: (F,) bool.
      feat_count: (W', F, B) int32 counts, with ``count`` int32 (see
        :func:`find_numerical_splits`).
      finder: as :func:`run_split_finders`.
    Returns: (W', CAND_COLS + B) f32 packed candidate rows; with
      ``feat_count`` a pair of them and the winners' (W',) int32 left
      counts, which a float32 column would round above 2^24.
    """
    W, F, B, _ = feat_hist.shape
    res, gains = run_split_finders(
        feat_hist, sum_grad, sum_hess, count, min_c, max_c, cfg,
        f_num_bin, f_missing, f_default_bin, f_monotone, f_is_cat,
        feature_mask, has_categorical, hist_count=feat_count,
        finder=finder)

    best_fc = jnp.argmax(gains, axis=1).astype(jnp.int32)       # (W',)
    best_gain = jnp.max(gains, axis=1)     # == value at argmax
    # one masked-sum over the stacked payload extracts every per-
    # feature field of the winner at once (exact: one-hot of exact
    # values; ints < 2^24 round-trip through f32)
    payload = jnp.stack(
        [res.threshold.astype(jnp.float32),
         res.default_left.astype(jnp.float32),
         res.left_sum_grad, res.left_sum_hess,
         res.left_count.astype(jnp.float32),
         res.left_output, res.right_output,
         res.cat_dir.astype(jnp.float32)], axis=2)              # (W',F,8)
    oh = (jnp.arange(F, dtype=jnp.int32)[None, :]
          == best_fc[:, None])                                  # (W',F)
    sel = jnp.sum(jnp.where(oh[:, :, None], payload, 0.0), axis=1)
    thr = sel[:, 0].astype(jnp.int32)
    cat_dir = sel[:, 7].astype(jnp.int32)
    if has_categorical:
        hist_chosen = jnp.take_along_axis(
            feat_hist, best_fc[:, None, None, None], axis=1)[:, 0]
        cat_mask = build_cat_bitset(
            hist_chosen, thr, cat_dir, f_num_bin[best_fc],
            f_missing[best_fc], cfg)
    else:
        cat_mask = jnp.zeros((W, B), bool)
    block = jnp.concatenate(
        [best_gain[:, None], best_fc.astype(jnp.float32)[:, None],
         sel, cat_mask.astype(jnp.float32)], axis=1)
    if feat_count is None:
        return block
    return block, jnp.sum(jnp.where(oh, res.left_count, 0), axis=1)


def forced_split_block(feat_hist: jax.Array, spec: jax.Array,
                       forced_feature: jax.Array, forced_thr: jax.Array,
                       sum_grad: jax.Array, sum_hess: jax.Array,
                       count: jax.Array, f_num_bin: jax.Array,
                       f_missing: jax.Array, f_default_bin: jax.Array,
                       f_is_cat: jax.Array,
                       cfg: Dict[str, float],
                       feat_count: Optional[jax.Array] = None):
    """Forced-split evaluation of the frontier as one packed
    (W', FORCED_COLS) block (gather_split_at_threshold per leaf at its
    spec node's (feature, threshold); rows with no spec get -inf
    gain).  ``spec`` is the (W',) forced-spec index (-1 = none);
    forced_feature/forced_thr the flat spec arrays.  With
    ``feat_count`` (int32, ``count`` too): the block and the (W',)
    int32 left counts, as :func:`find_best_split_block`."""
    n_spec = forced_feature.shape[0]
    s_node = jnp.clip(spec, 0, n_spec - 1)
    ff = forced_feature[s_node]
    ft = forced_thr[s_node]
    hist_ff = jnp.take_along_axis(
        feat_hist, ff[:, None, None, None], axis=1)[:, 0]
    cnt_ff = None if feat_count is None else jnp.take_along_axis(
        feat_count, ff[:, None, None], axis=1)[:, 0]
    (fgain, flg, flh, flc, flo, fro, fdl) = gather_split_at_threshold(
        hist_ff, ft, sum_grad, sum_hess, count, f_num_bin[ff],
        f_missing[ff], f_default_bin[ff], f_is_cat[ff], cfg,
        hist_count=cnt_ff)
    fgain = jnp.where(spec >= 0, fgain, K_MIN_SCORE)
    fblock = jnp.stack(
        [fgain, ft.astype(jnp.float32), fdl.astype(jnp.float32),
         flg, flh, flc.astype(jnp.float32), flo, fro], axis=1)
    return fblock if feat_count is None else (fblock, flc)


def _shift_used(arr, n_used):
    """Reverse the first n_used entries of each (l, f) row so a forward
    prefix scan over the result walks the sorted order from the back
    (the dir=-1 scan).  Entries past n_used are zero-padded."""
    L, F, B = arr.shape
    pos = jnp.arange(B, dtype=jnp.int32)
    idx = n_used[:, :, None] - 1 - pos[None, None, :]
    valid = idx >= 0
    idx = jnp.clip(idx, 0, B - 1)
    out = jnp.take_along_axis(arr, idx, axis=2)
    return jnp.where(valid, out, 0.0)


def build_cat_bitset(hist_f: jax.Array, threshold: jax.Array,
                     cat_dir: jax.Array, num_bin: jax.Array,
                     missing_type: jax.Array,
                     cfg: Dict[str, float]) -> jax.Array:
    """Reconstruct the left-going category-bin mask for chosen
    categorical splits (reference feature_histogram.hpp:252-262).

    Args:
      hist_f: (L, B, 3) histogram of the CHOSEN feature per leaf.
      threshold/cat_dir: (L,) from SplitResult for the chosen feature.
      num_bin/missing_type: (L,) metadata of the chosen feature.
    Returns: (L, B) bool — True = this feature-bin goes left.
    """
    L, B, _ = hist_f.shape
    bins = jnp.arange(B, dtype=jnp.int32)
    is_full = missing_type == MISSING_NONE
    used_bin = num_bin - 1 + is_full.astype(jnp.int32)
    in_range = bins[None, :] < used_bin[:, None]
    h_g, h_h, h_c = hist_f[..., 0], hist_f[..., 1], hist_f[..., 2]
    eligible = in_range & (h_c >= cfg["cat_smooth"])
    score = h_g / (h_h + cfg["cat_smooth"])
    sort_key = jnp.where(eligible, score, jnp.inf)
    order = jnp.argsort(sort_key, axis=1)          # (L, B)
    n_used = eligible.sum(axis=1).astype(jnp.int32)
    pos = jnp.arange(B, dtype=jnp.int32)
    # onehot mode: mask = {threshold}
    onehot_mask = bins[None, :] == threshold[:, None]
    # sorted mode fwd: first (threshold+1) of order; bwd: last (threshold+1)
    k = threshold + 1
    fwd_sel = pos[None, :] < k[:, None]
    bwd_sel = (pos[None, :] >= (n_used - k)[:, None]) \
        & (pos[None, :] < n_used[:, None])
    sel = jnp.where((cat_dir == 1)[:, None], fwd_sel,
                    jnp.where((cat_dir == -1)[:, None], bwd_sel, False))
    # scatter selected sorted positions back to bin space
    sorted_mask = jnp.zeros((L, B), dtype=bool)
    sorted_mask = jnp.take_along_axis(
        sel.astype(jnp.int32),
        jnp.argsort(order, axis=1), axis=1).astype(bool)
    return jnp.where((cat_dir == 0)[:, None], onehot_mask, sorted_mask)
