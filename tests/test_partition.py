"""Row-routing correctness for the XLA split router.

Regression coverage for the >256-feature-group case: the leaf table
packs feat_group hi/lo into two bf16 byte columns (a single bf16 column
is exact only up to 256 — group ids >= 257 would decode wrong and rows
would read a different group's bins).
"""
import numpy as np
import jax.numpy as jnp

from lightgbm_tpu.ops.partition import (MISSING_NAN, MISSING_NONE,
                                        MISSING_ZERO, apply_splits)


def _route_numpy(bins, leaf_id, split_mask, feat_group, fb_lo, fb_hi,
                 fb_shift, fb_oor, is_cat, threshold, default_left,
                 missing_type, default_bin, num_bin, cat_mask, right_slot):
    """Scalar reference of the routing semantics."""
    out = leaf_id.copy()
    for r in range(len(leaf_id)):
        leaf = leaf_id[r]
        if leaf < 0 or not split_mask[leaf]:
            continue
        g = feat_group[leaf]
        gb = int(bins[r, g])
        if fb_lo[leaf] <= gb < fb_hi[leaf]:
            fbin = gb - fb_shift[leaf]
        else:
            fbin = fb_oor[leaf]
        if is_cat[leaf]:
            left = bool(cat_mask[leaf, fbin])
        elif missing_type[leaf] == MISSING_NAN and fbin == num_bin[leaf] - 1:
            left = bool(default_left[leaf])
        elif missing_type[leaf] == MISSING_ZERO and fbin == default_bin[leaf]:
            left = bool(default_left[leaf])
        else:
            left = fbin <= threshold[leaf]
        out[r] = leaf if left else right_slot[leaf]
    return out


def _make_case(rng, n=512, num_groups=300, L=8, B=16):
    """Synthetic split state: leaves 0..3 split, on groups straddling
    the 256 boundary; a mix of missing types and one categorical."""
    bins = rng.randint(0, B, (n, num_groups)).astype(np.uint8)
    leaf_id = rng.randint(-1, 6, n).astype(np.int32)
    split_mask = np.zeros(L, bool)
    split_mask[:4] = True
    feat_group = np.array([3, 257, 290, 299, 0, 0, 0, 0], np.int32)
    fb_lo = np.zeros(L, np.int32)
    fb_hi = np.full(L, B, np.int32)
    fb_shift = np.zeros(L, np.int32)
    fb_oor = np.full(L, B - 1, np.int32)
    is_cat = np.array([0, 0, 0, 1, 0, 0, 0, 0], bool)
    threshold = np.array([7, 3, 11, 5, 0, 0, 0, 0], np.int32)
    default_left = np.array([1, 0, 1, 0, 0, 0, 0, 0], bool)
    missing_type = np.array([MISSING_NONE, MISSING_ZERO, MISSING_NAN, 0,
                             0, 0, 0, 0], np.int32)
    default_bin = np.array([0, 2, 0, 0, 0, 0, 0, 0], np.int32)
    num_bin = np.full(L, B, np.int32)
    cat_mask = rng.rand(L, B) > 0.5
    right_slot = np.array([8, 9, 10, 11, 0, 0, 0, 0], np.int32)
    return (bins, leaf_id, split_mask, feat_group, fb_lo, fb_hi, fb_shift,
            fb_oor, is_cat, threshold, default_left, missing_type,
            default_bin, num_bin, cat_mask, right_slot)


def test_apply_splits_matches_reference_over_256_groups(rng):
    args = _make_case(rng)
    want = _route_numpy(*args)
    got = np.asarray(apply_splits(*[jnp.asarray(a) for a in args]))
    np.testing.assert_array_equal(got, want)
