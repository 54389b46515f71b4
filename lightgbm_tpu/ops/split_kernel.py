"""The numerical threshold sweep as one fused pass over the histogram.

``find_numerical_splits_fused`` has ``split.find_numerical_splits``'s
contract, field for field, and reads each histogram element once: a
Pallas kernel holds a block of ``(leaf rows, features, bins)`` in VMEM
and does everything between the histogram and the per-(leaf, feature)
best split there — the exclusion masks, the prefix sums of both scans,
right = total - left, the ``min_data`` / ``min_hess`` / threshold tests,
``split_gains``, the reference's selection order and the winner's sums
and outputs.  ``find_numerical_splits`` stays as the XLA form and as
what the tests compare this against.

Operand contract (docs/ROOFLINE.md, "The split finder's operand
contract"): three planes ``(R, F, lanes)`` — gradient, hessian, count
(int32 where the caller passes ``hist_count``) — bins on the 128-lane
axis (255 read as 256 lanes, the last zeroed here), features on sublanes,
``R`` leaf rows.  They are the caller's channel-minor ``(R, F, B, 3)``
operand seen as ``(R, 3, F, B)``: on the TPU XLA keeps such an array
with the channel outside ``(F, B)`` tiles already, so the view moves no
byte; a histogram layer that keeps channel-major planes can hand them
over as they are.

Inside a grid step of ``(r_blk, f_blk, lanes)``: the prefix sums of the
whole block are products with an upper-triangular 0/1 matrix on the
otherwise idle MXU (float32 at ``Precision.HIGHEST``; int32 counts as
two 16-bit limbs, each exact in float32), then a loop over the block's
leaf rows scores one ``(f_blk, lanes)`` slab at a time with the leaf's
scalars read from SMEM, and drops its per-feature winners into lane
``r`` of ``(f_blk, r_blk)`` accumulators: the outputs are written
feature-major ``(F, R)``, leaves on the lanes, and turned in XLA (1/255
of the input).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .split import (K_EPSILON, K_MIN_SCORE, MISSING_NAN, MISSING_NONE,
                    MISSING_ZERO, SplitResult, _leaf_output_constrained,
                    leaf_split_gain, split_gains)

#: leaf rows a trip of the kernel's row loop scores: their reductions
#: along the lanes are independent chains the scheduler interleaves
ROW_UNROLL = 2

# rows of the per-leaf scalar table, columns of the per-feature one
_SG, _TH, _MIN_C, _MAX_C, _MGS = range(5)
_NUM_BIN, _MISSING, _DEFAULT_BIN, _MONOTONE = range(4)


class Finder(NamedTuple):
    """Which form of the numerical finder a grower runs
    (``HistPlan.finder``), and the two static facts the fused form is
    specialised on."""
    form: str = "xla"        # "fused" | "xla"
    scans: int = 2           # 1: no used feature is two-scan, so the
    # default-right scan's gains are all K_MIN_SCORE and it is not traced
    interpret: bool = False  # Pallas interpret mode (the CPU seam)



def finder_scans(num_bin, missing_type) -> int:
    """Scans the finder traces, from host metadata (numpy): 2 where any
    used feature is two-scan (``num_bin > 2`` and a missing type)."""
    return 2 if bool(((num_bin > 2) & (missing_type != MISSING_NONE)).any()) \
        else 1


def _finder_kernel(leaf_s, nd_s, g_ref, h_ref, c_ref, meta_ref, leaf_v_ref,
                   gain_ref, thr_ref, dleft_ref, lsg_ref, lsh_ref, lsc_ref,
                   lout_ref, rout_ref, *cum_refs, cfg, num_bins, rows,
                   scans, int_counts):
    r_blk, f_blk, lanes = g_ref.shape
    out_lanes = gain_ref.shape[1]
    m = r_blk * f_blk
    l1, l2, mds = cfg["lambda_l1"], cfg["lambda_l2"], cfg["max_delta_step"]
    min_hess = cfg["min_sum_hessian_in_leaf"]
    min_data = cfg["min_data_in_leaf"]
    if int_counts:
        min_data = math.ceil(min_data)
    i = pl.program_id(0)

    # ---- per-feature masks, once a grid step --------------------------
    def meta(col, width):
        return jnp.broadcast_to(meta_ref[:, col:col + 1], (f_blk, width))

    nb, miss = meta(_NUM_BIN, lanes), meta(_MISSING, lanes)
    dbin, mono = meta(_DEFAULT_BIN, lanes), meta(_MONOTONE, lanes)
    bins = jax.lax.broadcasted_iota(jnp.int32, (f_blk, lanes), 1)
    bins_f = bins.astype(jnp.float32)
    m_zero = miss == MISSING_ZERO
    m_nan = miss == MISSING_NAN
    two_scan = (nb > 2) & (miss != MISSING_NONE)
    is_default = bins == dbin
    # scan B: default-left
    excl_b = (m_zero & is_default) | (m_nan & two_scan & (bins == nb - 1))
    last_b = jnp.where(m_nan & two_scan, nb - 3, nb - 2)
    t_ok_b = (bins <= last_b) & ~(m_zero & (bins == dbin - 1) & (dbin > 0))
    # scan A: default-right, two-scan features only
    excl_a = m_zero & is_default
    t_ok_a = (bins <= nb - 2) & ~excl_a & two_scan

    # ---- prefix sums of the whole block, on the MXU -------------------
    tri = (jax.lax.broadcasted_iota(jnp.int32, (lanes, lanes), 0)
           <= jax.lax.broadcasted_iota(jnp.int32, (lanes, lanes), 1)
           ).astype(jnp.float32)

    def prefix(x):
        return jax.lax.dot_general(
            x.reshape(m, lanes), tri, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    def scan_into(excl, cum_g, cum_h, cum_c):
        # the lanes past the histogram's bins hold whatever the block's
        # buffer held
        drop = (excl | (bins >= num_bins))[None]
        cum_g[...] = prefix(jnp.where(drop, 0.0, g_ref[...]))
        cum_h[...] = prefix(jnp.where(drop, 0.0, h_ref[...]))
        if int_counts:
            # two 16-bit limbs: every partial sum is an integer under
            # 2^24, exact in float32 whatever the order it is added in
            c = jnp.where(drop, 0, c_ref[...])
            lo = prefix((c & 0xFFFF).astype(jnp.float32))
            hi = prefix((c >> 16).astype(jnp.float32))
            cum_c[...] = (hi.astype(jnp.int32) << 16) + lo.astype(jnp.int32)
        else:
            cum_c[...] = prefix(jnp.where(drop, 0.0, c_ref[...]))

    scan_into(excl_b, *cum_refs[:3])
    if scans == 2:
        scan_into(excl_a, *cum_refs[3:])

    # ---- one leaf row at a time ---------------------------------------
    out_lane = jax.lax.broadcasted_iota(jnp.int32, (f_blk, out_lanes), 1)

    def leaf_row(r, accs):
        at = jnp.minimum(i * r_blk + r, rows - 1)
        sg, th = leaf_s[_SG, at], leaf_s[_TH, at]
        mc, xc, mgs = leaf_s[_MIN_C, at], leaf_s[_MAX_C, at], leaf_s[_MGS, at]
        nd = nd_s[at]
        slab = pl.ds(pl.multiple_of(jnp.minimum(r, r_blk - 1) * f_blk,
                                    f_blk), f_blk)

        def candidate_gain(lg, lh, lc, t_ok):
            rg, rh, rc = sg - lg, th - lh, nd - lc
            ok = (t_ok & (lc >= min_data) & (rc >= min_data)
                  & (lh >= min_hess) & (rh >= min_hess))
            g = split_gains(lg, lh, rg, rh, l1, l2, mds, mc, xc, mono)
            return jnp.where(ok & (g > mgs), g, K_MIN_SCORE)

        cum_g, cum_h, cum_c = (ref[slab, :] for ref in cum_refs[:3])
        last = slice(num_bins - 1, num_bins)
        lg = sg - (cum_g[:, last] - cum_g)
        lh = th - (cum_h[:, last] - cum_h + K_EPSILON)
        lc = nd - (cum_c[:, last] - cum_c)
        gain = candidate_gain(lg, lh, lc, t_ok_b)
        # the reference's order: the default-left scan first and in it
        # the larger threshold first, then the default-right scan, the
        # smaller first; the first maximum wins
        best = jnp.max(gain, axis=1, keepdims=True)
        thr = jnp.max(jnp.where((gain == best) & (bins < num_bins),
                                bins_f, -1.0), axis=1, keepdims=True)
        from_b = jnp.ones_like(best)
        if scans == 2:
            lg_a = cum_refs[3][slab, :]
            lh_a = cum_refs[4][slab, :] + K_EPSILON
            lc_a = cum_refs[5][slab, :]
            gain_a = candidate_gain(lg_a, lh_a, lc_a, t_ok_a)
            best_a = jnp.max(gain_a, axis=1, keepdims=True)
            thr_a = jnp.min(jnp.where(gain_a == best_a, bins_f,
                                      float(lanes)), axis=1, keepdims=True)
            from_b = (best >= best_a).astype(jnp.float32)
            thr = jnp.where(from_b > 0, thr, thr_a)
            best = jnp.maximum(best, best_a)
            pick_b = jnp.broadcast_to(from_b, (f_blk, lanes)) > 0
            lg = jnp.where(pick_b, lg, lg_a)
            lh = jnp.where(pick_b, lh, lh_a)
            lc = jnp.where(pick_b, lc, lc_a)
        at_thr = bins_f == thr

        def pick(x):
            return jnp.sum(jnp.where(at_thr, x, jnp.zeros_like(x)),
                           axis=1, keepdims=True)

        put = out_lane == r
        return tuple(
            jnp.where(put, col, acc) for col, acc in zip(
                (best, thr, from_b, pick(lg), pick(lh), pick(lc)), accs))

    def leaf_rows(k, accs):
        # a row past the block's last is scored and lands on no lane
        for u in range(ROW_UNROLL):
            accs = leaf_row(k * ROW_UNROLL + u, accs)
        return accs

    zeros = jnp.zeros((f_blk, out_lanes), jnp.float32)
    valid_rows = jnp.minimum(r_blk, rows - i * r_blk)
    best, thr, from_b, lg, lh, lc = jax.lax.fori_loop(
        0, (valid_rows + ROW_UNROLL - 1) // ROW_UNROLL, leaf_rows,
        (zeros, zeros, zeros, zeros, zeros,
         zeros.astype(lsc_ref.dtype)))

    # ---- the (feature, leaf) tail: leaves on the lanes ----------------
    sg, th = leaf_v_ref[_SG:_SG + 1, :], leaf_v_ref[_TH:_TH + 1, :]
    mc, xc = leaf_v_ref[_MIN_C:_MIN_C + 1, :], \
        leaf_v_ref[_MAX_C:_MAX_C + 1, :]
    mgs = leaf_v_ref[_MGS:_MGS + 1, :]
    nb, miss = meta(_NUM_BIN, out_lanes), meta(_MISSING, out_lanes)
    # two-bin NaN features force default-right
    force_right = ~((nb > 2) & (miss != MISSING_NONE)) & (miss == MISSING_NAN)
    gain_ref[...] = jnp.where(best > K_MIN_SCORE, best - mgs, K_MIN_SCORE)
    thr_ref[...] = thr.astype(jnp.int32)
    dleft_ref[...] = ((from_b > 0) & ~force_right).astype(jnp.int32)
    lsg_ref[...] = lg
    lsh_ref[...] = lh - K_EPSILON
    lsc_ref[...] = lc
    lout_ref[...] = _leaf_output_constrained(lg, lh, l1, l2, mds, mc, xc)
    rout_ref[...] = _leaf_output_constrained(sg - lg, th - lh, l1, l2, mds,
                                             mc, xc)


def find_numerical_splits_fused(hist: jax.Array, sum_grad: jax.Array,
                                sum_hess: jax.Array, num_data: jax.Array,
                                num_bin: jax.Array, missing_type: jax.Array,
                                default_bin: jax.Array, monotone: jax.Array,
                                min_c: jax.Array, max_c: jax.Array,
                                cfg: Dict[str, float],
                                hist_count: Optional[jax.Array] = None,
                                *, scans: int = 2,
                                interpret: bool = False) -> SplitResult:
    """``split.find_numerical_splits`` as one Pallas kernel (its
    arguments, its result).  ``scans`` is :func:`finder_scans` of the
    job's features; the kernel is named by its leaf rows,
    ``find_numerical_splits_fused_r<rows>``."""
    from .hist_plan import FINDER_VMEM_LIMIT, finder_block, finder_lanes
    R, F, B, _ = hist.shape
    int_counts = hist_count is not None
    r_blk, f_blk = finder_block(R, B, scans, int_counts)
    lanes = finder_lanes(B)
    # channel-major planes, bins on the lanes.  Where the caller's
    # operand is already laid out so (XLA keeps an (R, F, B, 3) array
    # with the channel outside (F, B) tiles) this is no copy; a block
    # reads whole lanes, past the last bin too
    planes = jnp.moveaxis(hist, 3, 1)                     # (R, 3, F, B)
    meta = jnp.stack([num_bin, missing_type, default_bin, monotone],
                     axis=1).astype(jnp.int32)            # (F, 4)

    total_h = sum_hess + 2 * K_EPSILON
    min_gain_shift = leaf_split_gain(
        sum_grad, total_h, cfg["lambda_l1"], cfg["lambda_l2"],
        cfg["max_delta_step"]) + cfg["min_gain_to_split"]
    leaf = jnp.stack([sum_grad, total_h, min_c, max_c,
                      min_gain_shift]).astype(jnp.float32)       # (5, R)
    count_dtype = jnp.int32 if int_counts else jnp.float32

    def plane(c):
        return pl.BlockSpec((r_blk, None, f_blk, lanes),
                            lambda i, j, *_: (i, c, j, 0))

    out_spec = pl.BlockSpec((f_blk, r_blk), lambda i, j, *_: (j, i))
    f32 = jax.ShapeDtypeStruct((F, R), jnp.float32)
    i32 = jax.ShapeDtypeStruct((F, R), jnp.int32)
    outs = pl.pallas_call(
        functools.partial(_finder_kernel, cfg=cfg, num_bins=B, rows=R,
                          scans=scans, int_counts=int_counts),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(-(-R // r_blk), -(-F // f_blk)),
            in_specs=[
                plane(0), plane(1),
                pl.BlockSpec((r_blk, f_blk, lanes),
                             lambda i, j, *_: (i, j, 0))
                if int_counts else plane(2),
                pl.BlockSpec((f_blk, 4), lambda i, j, *_: (j, 0)),
                pl.BlockSpec((5, r_blk), lambda i, j, *_: (0, i)),
            ],
            out_specs=[out_spec] * 8,
            scratch_shapes=[
                pltpu.VMEM((r_blk * f_blk, lanes), dt)
                for _ in range(scans)
                for dt in (jnp.float32, jnp.float32, count_dtype)],
        ),
        out_shape=[f32, i32, i32, f32, f32,
                   jax.ShapeDtypeStruct((F, R), count_dtype), f32, f32],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=FINDER_VMEM_LIMIT),
        interpret=interpret, name=f"find_numerical_splits_fused_r{R}",
    )(leaf, num_data.astype(count_dtype), planes, planes,
      hist_count if int_counts else planes, meta, leaf)
    gain, thr, dleft, lsg, lsh, lsc, lout, rout = (o.T for o in outs)
    return SplitResult(
        gain=gain, threshold=thr, default_left=dleft > 0,
        left_sum_grad=lsg, left_sum_hess=lsh, left_count=lsc,
        left_output=lout, right_output=rout, cat_dir=jnp.zeros_like(thr))
