"""Row partition: apply chosen splits to the per-row leaf assignment.

TPU-native replacement for DataPartition's index-permutation split
(reference: src/treelearner/data_partition.hpp:109-161) and the
per-bin routing rules of DenseBin::Split / SplitCategorical
(reference: src/io/dense_bin.hpp:191-283).  Instead of compacting row
indices into contiguous per-leaf ranges, every row carries a ``leaf_id``
and one vectorized pass re-labels the rows of every leaf split this
round — recompute-with-masks beats in-place permutation on TPU.

Routing semantics (feature-bin space after the group->feature affine
map; the reference's min_bin/max_bin/bias adjustments collapse into the
(lo, hi, shift, oor) scalars):
  * NaN-missing: NaN bin (last) rides ``default_left``; other bins
    (including the zero/default bin) compare ``bin <= threshold``.
  * Zero-missing: the default(zero) bin rides ``default_left``; other
    bins compare.
  * None: plain compare.
  * Categorical: bit ``featbin`` of the packed left-set decides.

Implementation note: arbitrary per-row gathers are slow on TPU and a
per-(leaf, group-bin) decision table costs an (N, GB) intermediate, so
instead ONLY per-leaf scalars are broadcast to rows — one
``(N, L) @ (L, ~20)`` exact-f32 matmul (the one-hot picks a single
row, so every output is one table value, bit-exact under
Precision.HIGHEST) — and the routing decision is evaluated per row
with elementwise ops.  The group->feature bin map is affine per leaf:
``featbin = gb - shift if lo <= gb < hi else oor`` (see
TreeGrower._build_g2f_affine), which is what lets the (L, GB) table
disappear.  Categorical left-sets ride along as ceil(B/8) packed byte
columns.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..packing import logical_groups, packed_bytes, spec_crumb, spec_packed

MISSING_NONE = 0
MISSING_ZERO = 1
MISSING_NAN = 2


def pack_mask_bytes(mask: jax.Array) -> jax.Array:
    """(L, B) bool -> (L, ceil(B/8)) packed little-endian byte floats
    (each < 256, exact in f32)."""
    L, B = mask.shape
    nb = (B + 7) // 8
    pad = nb * 8 - B
    if pad:
        mask = jnp.concatenate(
            [mask, jnp.zeros((L, pad), bool)], axis=1)
    bits = mask.reshape(L, nb, 8).astype(jnp.float32)
    weights = (2.0 ** jnp.arange(8, dtype=jnp.float32))
    return jnp.einsum("lnb,b->ln", bits, weights)


# fixed route-table column layout (shared by the XLA router below and
# the fused Pallas histogram kernel's routing prologue):
#   0 fg_hi, 1 fg_lo, 2 threshold, 3 default_left, 4 missing_type,
#   5 default_bin, 6 num_bin, 7 is_cat, 8 rs_hi, 9 rs_lo,
#   10 active(split_mask), 11 fb_lo, 12 fb_hi, 13 fb_shift, 14 fb_oor,
#   15.. cat bytes (ceil(B/8) packed little-endian)
ROUTE_FIXED_COLS = 15


def build_route_table(split_mask: jax.Array, feat_group: jax.Array,
                      fb_lo: jax.Array, fb_hi: jax.Array,
                      fb_shift: jax.Array, fb_oor: jax.Array,
                      is_cat: jax.Array, threshold: jax.Array,
                      default_left: jax.Array, missing_type: jax.Array,
                      default_bin: jax.Array, num_bin: jax.Array,
                      cat_mask: jax.Array,
                      right_slot: jax.Array) -> jax.Array:
    """(L, 15 + ceil(B/8)) f32 per-leaf routing table.

    Every column is an integer < 256 — exact in bf16 (right_slot AND
    feat_group are split hi/lo: feature groups are unbounded up to the
    hi byte's own bf16 limit of 65536, asserted by apply_splits), so a
    leaf one-hot can broadcast the table to rows on the fast bf16 MXU
    path."""
    def col(v):
        return v.astype(jnp.float32)[:, None]

    rs = right_slot.astype(jnp.int32)
    fg = feat_group.astype(jnp.int32)
    cat_bytes = pack_mask_bytes(cat_mask)            # (L, nb)
    return jnp.concatenate([
        col(fg // 256), col(fg % 256), col(threshold), col(default_left),
        col(missing_type), col(default_bin), col(num_bin),
        col(is_cat), col(rs // 256), col(rs % 256), col(split_mask),
        col(fb_lo), col(fb_hi), col(fb_shift), col(fb_oor),
        cat_bytes,
    ], axis=1)


def route_rows(rows, leaf_id, gb, with_decision=False):
    """Routing decision of the XLA router: ``rows`` is the per-row
    broadcast of the route table ((N, 15+nb) f32), ``gb`` the per-row
    bin of the chosen group.  Returns the updated leaf id (plus the
    went-right mask when ``with_decision``).

    NOTE: ops/histogram.py _route_prologue_T is the TRANSPOSED in-kernel
    duplicate of this logic, shared by every fused Pallas kernel
    (scalars live as (K, C) rows there; Mosaic can't share this
    row-orientation code) — any semantic change here MUST be mirrored
    there; tests/test_histogram_kernel.py's fused parity test pins the
    two together."""
    nb = rows.shape[-1] - ROUTE_FIXED_COLS

    def icol(i):
        return rows[..., i].astype(jnp.int32)

    thr_row = icol(2)
    dleft_row = rows[..., 3] > 0.5
    mtype_row = icol(4)
    dbin_row = icol(5)
    nbin_row = icol(6)
    iscat_row = rows[..., 7] > 0.5
    rs_row = icol(8) * 256 + icol(9)
    active = (rows[..., 10] > 0.5) & (leaf_id >= 0)
    lo_row, hi_row = icol(11), icol(12)
    shift_row, oor_row = icol(13), icol(14)

    fbin = jnp.where((gb >= lo_row) & (gb < hi_row), gb - shift_row,
                     oor_row)                        # feature-bin space

    # numerical routing
    is_nan_bin = fbin == nbin_row - 1
    is_def_bin = fbin == dbin_row
    cmp_left = fbin <= thr_row
    num_left = jnp.where(
        (mtype_row == MISSING_NAN) & is_nan_bin, dleft_row,
        jnp.where((mtype_row == MISSING_ZERO) & is_def_bin, dleft_row,
                  cmp_left))

    # categorical routing: extract bit fbin of the packed byte columns
    byte_idx = fbin[..., None] // 8
    bsel = byte_idx == jnp.arange(nb, dtype=jnp.int32)
    byte_val = jnp.sum(
        jnp.where(bsel, rows[..., ROUTE_FIXED_COLS:], 0.0),
        axis=-1).astype(jnp.int32)
    cat_left = ((byte_val >> (fbin % 8)) & 1) == 1

    go_left = jnp.where(iscat_row, cat_left, num_left)
    new_id = jnp.where(go_left, leaf_id, rs_row)
    routed = jnp.where(active, new_id, leaf_id).astype(jnp.int32)
    if with_decision:
        return routed, active & ~go_left
    return routed


def _split3_bf16(v: jax.Array) -> list:
    """f32 (L,) -> three bf16-exact f32 columns summing to v within
    ~2^-21 relative (the leaf_value_broadcast trick, ops/histogram.py).

    Built with BITMASK truncation, NOT f32->bf16->f32 dtype
    round-trips: this runtime compiles with
    ``--xla_allow_excess_precision``, under which XLA cancels the
    convert pairs inside jit and the mid/lo columns silently become
    zero — measured as exit-route row values collapsing to bf16
    (0.015 absolute on unit-scale leaf values).  Masking the low 16
    mantissa bits produces the same bf16-exact components through
    arithmetic the simplifier must preserve."""
    mask = jnp.uint32(0xFFFF0000)

    def trunc(x):
        b = jax.lax.bitcast_convert_type(x.astype(jnp.float32),
                                         jnp.uint32)
        return jax.lax.bitcast_convert_type(b & mask, jnp.float32)

    hi = trunc(v)
    r1 = v - hi
    mid = trunc(r1)
    lo = trunc(r1 - mid)
    return [hi[:, None], mid[:, None], lo[:, None]]


def extend_table_with_values(table: jax.Array,
                             values: jax.Array) -> jax.Array:
    """Append the exit-route leaf-VALUE columns to a route table: the
    keep-slot and right-child values, each as three bf16-split columns
    so the bf16 one-hot broadcast dot reassembles exact f32.  The ONE
    definition shared by the XLA router (apply_route_table) and the
    Pallas exit-route kernel (ops/histogram.py route_apply_tiled) —
    both read columns [ncols, ncols+6) by this layout."""
    rs_l = (table[:, 8].astype(jnp.int32) * 256
            + table[:, 9].astype(jnp.int32))
    v_right = values[jnp.clip(rs_l, 0, values.shape[0] - 1)]
    return jnp.concatenate(
        [table] + _split3_bf16(values) + _split3_bf16(v_right), axis=1)


def packed_select_params(grp, packed_groups: int):
    """Storage-byte index, crumb/nibble shift and width mask for
    logical group ids ``grp`` (any int32 array) under the packing.py
    layout — the ONE jnp form of
    ``BinLayout.byte_of/shift_of/width_mask``, shared by every device
    gather site (``apply_route_table`` here,
    ``ops/predict.predict_binned``, ``ops/histogram
    _route_prologue_T``).  ``packed_groups`` is the static pack spec
    (plain P when crumb-free — the legacy two-way select below is then
    emitted unchanged).  Extract with ``(byte >> shift) & mask``."""
    P, C = spec_packed(packed_groups), spec_crumb(packed_groups)
    pb = packed_bytes(packed_groups)
    if C == 0:
        is_p = grp < P
        byte_idx = jnp.where(is_p, grp // 2, pb + grp - P)
        shift = jnp.where(is_p, (grp % 2) * 4, 0)
        mask = jnp.where(is_p, 15, 255)
        return byte_idx, shift, mask
    cb = (C + 3) // 4
    is_c = grp < C
    is_n = jnp.logical_and(grp >= C, grp < P)
    byte_idx = jnp.where(
        is_c, grp // 4,
        jnp.where(is_n, cb + (grp - C) // 2, pb + grp - P))
    shift = jnp.where(is_c, (grp % 4) * 2,
                      jnp.where(is_n, ((grp - C) % 2) * 4, 0))
    mask = jnp.where(is_c, 3, jnp.where(is_n, 15, 255))
    return byte_idx, shift, mask


def apply_route_table(bins: jax.Array, leaf_id: jax.Array,
                      table: jax.Array, values=None,
                      packed_groups: int = 0):
    """Re-label rows from a packed (L, 15+nb) route table (XLA form:
    the one-hot broadcast dot materializes; the fused Pallas histogram
    kernel runs the same table in VMEM).

    With ``values`` ((L,) f32 leaf values) the POST-route per-row value
    rides the same one-hot dot as six extra bf16-split columns (keep
    and right-child variants), fusing the score update's separate
    (N, L) leaf_value_broadcast into this pass — one (N, L) one-hot
    materialization instead of two per tree.  Returns
    ``(new_leaf, row_value)`` then (row_value 0.0 on padded rows).

    ``packed_groups`` > 0 marks ``bins`` as the nibble-packed storage
    matrix (lightgbm_tpu/packing.py): the chosen group's storage BYTE
    is selected, then its nibble extracted with a per-row variable
    shift — the packed matrix is never widened in HBM."""
    n, cols = bins.shape
    num_groups = logical_groups(cols, packed_groups) if packed_groups \
        else cols
    if num_groups >= 65536:  # fg // 256 must stay bf16-exact
        raise ValueError(
            "apply_route_table (split routing) supports at most 65535 "
            f"feature groups, got {num_groups} — the route table encodes "
            "the group index as two bf16-exact bytes (hi/lo)")
    L = table.shape[0]
    ncols = table.shape[1]
    if values is not None:
        table = extend_table_with_values(table, values)
    safe_l = jnp.clip(leaf_id, 0, L - 1)
    ohl = (safe_l[:, None]
           == jnp.arange(L, dtype=jnp.int32)[None, :]).astype(jnp.bfloat16)
    rows_all = jnp.dot(ohl, table.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
    rows = rows_all[:, :ncols]

    grp_row = (rows[:, 0].astype(jnp.int32) * 256
               + rows[:, 1].astype(jnp.int32))
    if packed_groups:
        byte_idx, shift, mask = packed_select_params(grp_row,
                                                     packed_groups)
        bsel = byte_idx[:, None] == jnp.arange(cols,
                                               dtype=jnp.int32)[None, :]
        byte = jnp.sum(jnp.where(bsel, bins.astype(jnp.int32), 0),
                       axis=1)
        gb = (byte >> shift) & mask
    else:
        # chosen-group bin per row (masked sum, not a gather; G small)
        gsel = grp_row[:, None] == jnp.arange(num_groups,
                                              dtype=jnp.int32)[None, :]
        gb = jnp.sum(jnp.where(gsel, bins.astype(jnp.int32), 0), axis=1)
    if values is None:
        return route_rows(rows, leaf_id, gb)
    new_leaf, went_right = route_rows(rows, leaf_id, gb,
                                      with_decision=True)
    vk = (rows_all[:, ncols] + rows_all[:, ncols + 1]
          + rows_all[:, ncols + 2])
    vr = (rows_all[:, ncols + 3] + rows_all[:, ncols + 4]
          + rows_all[:, ncols + 5])
    row_value = jnp.where(went_right, vr, vk)
    row_value = jnp.where(leaf_id >= 0, row_value, 0.0)
    return new_leaf, row_value


def apply_splits(bins: jax.Array, leaf_id: jax.Array,
                 split_mask: jax.Array, feat_group: jax.Array,
                 fb_lo: jax.Array, fb_hi: jax.Array, fb_shift: jax.Array,
                 fb_oor: jax.Array, is_cat: jax.Array,
                 threshold: jax.Array, default_left: jax.Array,
                 missing_type: jax.Array, default_bin: jax.Array,
                 num_bin: jax.Array, cat_mask: jax.Array,
                 right_slot: jax.Array,
                 packed_groups: int = 0) -> jax.Array:
    """Re-label rows of splitting leaves.

    Args:
      bins: (N, G) uint8 group-bin matrix.
      leaf_id: (N,) int32, negative = padded row (left untouched).
      split_mask: (L,) bool — leaves splitting this round.
      feat_group: (L,) int32 — group column of the chosen feature.
      fb_lo/fb_hi/fb_shift/fb_oor: (L,) int32 — the chosen feature's
        affine group-bin -> feature-bin map: ``gb - fb_shift`` inside
        [fb_lo, fb_hi), else ``fb_oor``.
      is_cat/threshold/default_left/missing_type/default_bin/num_bin:
        (L,) chosen-split metadata gathered per leaf.
      cat_mask: (L, B) bool — categorical left-set in feature-bin space.
      right_slot: (L,) int32 — leaf slot assigned to the right child.

    Returns: updated (N,) leaf_id (left child keeps the parent slot).
    """
    table = build_route_table(
        split_mask, feat_group, fb_lo, fb_hi, fb_shift, fb_oor, is_cat,
        threshold, default_left, missing_type, default_bin, num_bin,
        cat_mask, right_slot)
    return apply_route_table(bins, leaf_id, table,
                             packed_groups=packed_groups)

