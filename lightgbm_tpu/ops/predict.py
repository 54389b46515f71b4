"""Device prediction over the binned matrix and over raw features.

Replaces the reference's per-row pointer-chasing tree walk
(reference: tree.h:212-295 DecisionInner, gbdt_prediction.cpp) with a
vectorized level-synchronous traversal: every row advances one level per
step, all rows in lockstep, over the fixed-size TreeArrays produced by
the grower.  Used for validation-score updates during training and for
DART's dropped-tree score subtraction — the binned matrix stays resident
in HBM, so a traversal is a handful of gathers per level.

The RAW-feature path (stack_host_trees / predict_raw_ensemble) serves
models with no live training session — file-loaded, multiclass,
init_model-merged, DART-renormalized — the device analog of the
reference's OMP batch predict over every model kind (c_api.cpp:177-211).
Thresholds are f64 midpoints; the device compares in TWO-FLOAT (hi+lo
f32) arithmetic so the `value <= threshold` decision matches the host's
float64 semantics for any f32-representable data (the f32-rounded
threshold alone would misroute rows equal to the upper neighbour of a
midpoint).
"""
from __future__ import annotations

import functools
from typing import List, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .partition import packed_select_params

MISSING_NONE = 0
MISSING_ZERO = 1
MISSING_NAN = 2

K_ZERO_THRESHOLD = 1e-35
K_CATEGORICAL_MASK = 1
K_DEFAULT_LEFT_MASK = 2


def unpack_tree_records_device(records: jax.Array, num_leaves: int,
                               max_feature_bin: int):
    """Packed tree record(s) -> TreeArrays, on device.

    ``records`` is uint8 with the record bytes in the LAST axis
    (tree.TreeRecordLayout layout); any leading batch axes are
    preserved, so a (T, record_size) stack unpacks to a TreeArrays
    whose leaves carry a leading T — the shape predict scans expect.
    Static-offset slices + bitcasts only: unpacking a chunk's worth of
    trees costs no gathers."""
    from ..tree import TreeRecordLayout
    from ..learner.grower import TreeArrays

    layout = TreeRecordLayout(num_leaves, max_feature_bin)
    lead = records.shape[:-1]
    out = {}
    for name, (off, nbytes, dt, shape) in layout.fields.items():
        raw = jax.lax.slice_in_dim(records, off, off + nbytes,
                                   axis=records.ndim - 1)
        kind = np.dtype(dt).kind
        if kind == "u":
            arr = raw.astype(bool)
        else:
            tgt = jnp.int32 if kind == "i" else jnp.float32
            arr = jax.lax.bitcast_convert_type(
                raw.reshape(lead + (nbytes // 4, 4)), tgt)
        out[name] = arr.reshape(lead + shape)
    return TreeArrays(**out)


def predict_binned(tree, bins: jax.Array, f_group: jax.Array,
                   g2f_lut: jax.Array, f_missing: jax.Array,
                   f_default_bin: jax.Array, f_num_bin: jax.Array,
                   max_steps: int, packed_groups: int = 0) -> jax.Array:
    """Evaluate one grown tree on a binned matrix.

    Args:
      tree: TreeArrays (bin-space thresholds/cat masks).
      bins: (N, G) uint8 — or the (N, cols) nibble-packed storage
        matrix when ``packed_groups`` > 0 (lightgbm_tpu/packing.py):
        the chosen group's storage byte is gathered and its nibble
        extracted in-register.
      f_group/(F,): group column per inner feature.
      g2f_lut: (F, GB) group-bin -> feature-bin map.
      f_missing/f_default_bin/f_num_bin: (F,) metadata.
      max_steps: static bound on tree depth (num_leaves - 1).

    Returns: (N,) f32 leaf values (unshrunk).
    """
    n = bins.shape[0]
    gb_dim = g2f_lut.shape[1]
    b_dim = tree.node_cat_mask.shape[1]

    def body(node):
        # node >= 0: internal node index; negative: settled leaf
        is_internal = node >= 0
        nid = jnp.maximum(node, 0)
        feat = tree.node_feature[nid]
        grp = f_group[feat]
        if packed_groups:
            byte_idx, shift, mask = packed_select_params(
                grp.astype(jnp.int32), packed_groups)
            byte = jnp.take_along_axis(
                bins, byte_idx[:, None], axis=1)[:, 0].astype(jnp.int32)
            gb = (byte >> shift) & mask
        else:
            gb = jnp.take_along_axis(bins,
                                     grp[:, None].astype(jnp.int32),
                                     axis=1)[:, 0].astype(jnp.int32)
        fb = g2f_lut[feat, gb]
        thr = tree.node_threshold[nid]
        dleft = tree.node_default_left[nid]
        mtype = f_missing[feat]
        dbin = f_default_bin[feat]
        nb = f_num_bin[feat]
        is_cat = tree.node_is_cat[nid]

        is_nan_bin = fb == (nb - 1)
        is_def_bin = fb == dbin
        cmp_left = fb <= thr
        num_left = jnp.where(
            (mtype == MISSING_NAN) & is_nan_bin, dleft,
            jnp.where((mtype == MISSING_ZERO) & is_def_bin, dleft, cmp_left))
        cat_left = tree.node_cat_mask.reshape(-1)[
            nid * b_dim + jnp.clip(fb, 0, b_dim - 1)]
        go_left = jnp.where(is_cat, cat_left, num_left)
        nxt = jnp.where(go_left, tree.node_left[nid], tree.node_right[nid])
        return jnp.where(is_internal, nxt, node)

    node0 = jnp.where(tree.num_leaves > 1,
                      jnp.zeros(n, jnp.int32),
                      jnp.full(n, -1, jnp.int32))
    del max_steps  # depth-synchronous walk exits when every row settles
    node = jax.lax.while_loop(lambda nd: jnp.any(nd >= 0), body, node0)
    leaf = -node - 1
    return tree.leaf_value[jnp.clip(leaf, 0, tree.leaf_value.shape[0] - 1)]


# ---------------------------------------------------------------------------
# Ensemble-vectorized level-synchronous descent (serving predictor).
#
# The per-tree scan above this round (predict_raw_ensemble) walked one
# tree at a time, and each node step gathered from the full (N, F)
# feature matrix TWICE (hi + lo) — 2·T·depth big gathers per batch, the
# exact pattern the round-5 profiles measured at ~1.6 GiB/s.  Here ALL
# T trees advance one level per step over the whole row tile: the node
# state is one (N, T) array over tree.flatten_ensemble's flat node
# axis, the per-level feature fetch is ONE take_along_axis of (N, 2T)
# indices into the interleaved (N, 2F) hi/lo matrix (feat2 is
# pre-doubled so the hi and lo parts ride the same gather), and the
# remaining per-level gathers hit only the small flat node tables.
# The loop is depth-bounded (static max tree depth, no jnp.any exit
# sync), so the program is one fori_loop + one class-matmul.
# ---------------------------------------------------------------------------

# serving-predictor telemetry: ``traces`` counts jit retraces (== XLA
# compilations per process modulo the persistent cache), ``dispatches``
# device calls, ``buckets`` the padded row-bucket shapes served.  The
# bench's compile-count line and the cache lint read these.
PREDICT_TELEMETRY = {"traces": 0, "dispatches": 0, "rows": 0,
                     "buckets": set()}


def reset_predict_telemetry() -> None:
    PREDICT_TELEMETRY.update(traces=0, dispatches=0, rows=0, buckets=set())


class LevelEnsemble(NamedTuple):
    """Flat SoA node tensors of a whole ensemble (tree.flatten_ensemble
    layout): node axis = t*M + i, leaf axis = t*L + l, child pointers
    pre-resolved into those spaces, feat2 pre-doubled for the
    interleaved hi/lo gather."""
    feat2: jax.Array        # (T*M,) int32 = 2 * feature
    thr_hi: jax.Array       # (T*M,) f32
    thr_lo: jax.Array       # (T*M,) f32 residual (finite, r7 inf guard)
    dtype_: jax.Array       # (T*M,) int32 decision_type bitfield
    left: jax.Array         # (T*M,) int32 flat child (negative = leaf)
    right: jax.Array        # (T*M,) int32
    leaf_value: jax.Array   # (T*L,) f32
    cat_words: jax.Array    # (T*M*W,) int32 per-node category bitset
    root: jax.Array         # (T,) int32 initial node (stumps settled)
    cls_onehot: jax.Array   # (T, K) f32 tree -> class accumulator


def _two_float_left(fhi, flo, thr_hi, thr_lo):
    """Exact f64 ``fv <= thr`` for f32-representable data, including
    equal-hi pairs where both parts are +-inf (inf - inf is NaN and
    would misroute; the host walk's ``inf <= inf`` is True)."""
    d = jnp.where(fhi == thr_hi, flo - thr_lo,
                  (fhi - thr_hi) + (flo - thr_lo))
    return d <= 0.0


def _level_step(stack: LevelEnsemble, X2: jax.Array, node: jax.Array,
                T: int, W: int) -> jax.Array:
    """Advance every (row, tree) pair one level.  ``node`` is (N, T)
    flat node ids; negative = settled leaf (kept as-is)."""
    nid = jnp.maximum(node, 0)
    f2 = stack.feat2[nid]                               # (N, T)
    idx = jnp.concatenate([f2, f2 + 1], axis=1)         # (N, 2T)
    v = jnp.take_along_axis(X2, idx, axis=1)            # ONE X gather
    vhi, vlo = v[:, :T], v[:, T:]
    dt = stack.dtype_[nid]
    is_cat = (dt & K_CATEGORICAL_MASK) > 0
    dleft = (dt & K_DEFAULT_LEFT_MASK) > 0
    mtype = (dt >> 2) & 3
    nan_mask = jnp.isnan(vhi)
    conv = nan_mask & (mtype != MISSING_NAN)
    fhi = jnp.where(conv, 0.0, vhi)
    flo = jnp.where(conv, 0.0, vlo)
    is_zero = (fhi > -K_ZERO_THRESHOLD) & (fhi <= K_ZERO_THRESHOLD)
    use_default = ((mtype == MISSING_ZERO) & is_zero) | \
                  ((mtype == MISSING_NAN) & jnp.isnan(fhi))
    num_left = jnp.where(use_default, dleft,
                         _two_float_left(fhi, flo, stack.thr_hi[nid],
                                         stack.thr_lo[nid]))
    v_int = jnp.where(nan_mask, -1, fhi.astype(jnp.int32))
    in_range = (v_int >= 0) & (v_int < W * 32)
    word = stack.cat_words[nid * W + jnp.clip(v_int // 32, 0, W - 1)]
    bit = jnp.bitwise_and(
        jax.lax.shift_right_logical(word, v_int % 32), 1)
    cat_left = in_range & (bit > 0)
    go_left = jnp.where(is_cat, cat_left, num_left)
    nxt = jnp.where(go_left, stack.left[nid], stack.right[nid])
    return jnp.where(node >= 0, nxt, node)


@functools.partial(jax.jit, static_argnames=("depth", "unroll"))
def predict_level_ensemble(stack: LevelEnsemble, X2: jax.Array, *,
                           depth: int, unroll: int = 1) -> jax.Array:
    """All-trees level descent over an interleaved (N, 2F) hi/lo
    matrix -> (N, K) f32 class-accumulated raw scores (f32 matmul
    accumulation — the documented device-predict precision).

    ``depth`` (static) is the ensemble's max tree depth: after that
    many levels every row has settled, so there is no per-level
    ``jnp.any`` device sync.  Module-level jit: one compilation per
    (ensemble shape, row bucket) serves every Booster in the process,
    and the persistent compile cache serves it across processes."""
    PREDICT_TELEMETRY["traces"] += 1
    from ..telemetry import TELEMETRY
    TELEMETRY.note_trace("predict.level_ensemble",
                         (X2.shape, stack.root.shape[0]))
    T = stack.root.shape[0]
    W = stack.cat_words.shape[0] // stack.feat2.shape[0]
    n = X2.shape[0]
    node = jnp.broadcast_to(stack.root[None, :], (n, T))
    if depth > 0:
        node = jax.lax.fori_loop(
            0, depth, lambda i, nd: _level_step(stack, X2, nd, T, W),
            node, unroll=unroll)
    leaf = jnp.clip(-node - 1, 0, stack.leaf_value.shape[0] - 1)
    vals = stack.leaf_value[leaf]                       # (N, T)
    return jnp.dot(vals, stack.cls_onehot)              # (N, K)


@functools.partial(jax.jit, static_argnames=("depth", "segments",
                                             "unroll"))
def predict_level_ensemble_cobatch(stack: LevelEnsemble, X2: jax.Array,
                                   *, depth: int,
                                   segments: tuple,
                                   unroll: int = 1) -> jax.Array:
    """Multi-model co-batched level descent: ``stack`` holds SEVERAL
    ensembles' trees concatenated along the tree axis, ``segments``
    is a static tuple of ``(tree_offset, tree_count, class_offset,
    class_count)`` — one per member model — and the output is the
    (N, sum K_g) column-stacked raw scores of every member on every
    row.  ONE compiled program per (group composition, row bucket)
    replaces one program per member model.

    Byte-identity contract (the co-batch parity pin): the descent is
    exact integer walking — running a shallow member's trees for the
    fused max depth is a no-op because settled (negative) node ids
    stay settled — and each member's class accumulation is a SEPARATE
    ``jnp.dot`` over exactly its own (N, T_g) x (T_g, K_g) slice, the
    same reduction shape its solo program runs, so per-member columns
    are byte-identical to that member's own
    :func:`predict_level_ensemble`."""
    PREDICT_TELEMETRY["traces"] += 1
    from ..telemetry import TELEMETRY
    TELEMETRY.note_trace("predict.level_cobatch",
                         (X2.shape, stack.root.shape[0], segments))
    T = stack.root.shape[0]
    W = stack.cat_words.shape[0] // stack.feat2.shape[0]
    n = X2.shape[0]
    node = jnp.broadcast_to(stack.root[None, :], (n, T))
    if depth > 0:
        node = jax.lax.fori_loop(
            0, depth, lambda i, nd: _level_step(stack, X2, nd, T, W),
            node, unroll=unroll)
    leaf = jnp.clip(-node - 1, 0, stack.leaf_value.shape[0] - 1)
    vals = stack.leaf_value[leaf]                       # (N, T_total)
    outs = [jnp.dot(vals[:, t0:t0 + tn],
                    stack.cls_onehot[t0:t0 + tn, k0:k0 + kn])
            for (t0, tn, k0, kn) in segments]
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=1)


@functools.partial(jax.jit,
                   static_argnames=("depth", "tile", "interpret"))
def predict_level_ensemble_pallas(stack: LevelEnsemble, X2: jax.Array,
                                  *, depth: int, tile: int,
                                  interpret: bool = False) -> jax.Array:
    """Row-tile Pallas form of the level descent: the grid walks (tile,
    2F) row blocks while every ensemble table is a full-array VMEM
    block — the stacked ensemble stays chip-resident across the whole
    batch instead of re-streaming from HBM per level.  Validated on the
    interpret seam only.  On a v5e (jax 0.9.0 / libtpu 0.0.34, PR 21)
    the Pallas TPU lowering refuses the kernel — the 1-D table gathers
    of ``_level_step`` raise ``NotImplementedError: Only 2D gather is
    supported`` — so ``predict_kernel=pallas`` fails loudly there; it
    never degrades to another kernel.  ROADMAP D1 decides its fate."""
    PREDICT_TELEMETRY["traces"] += 1
    from ..telemetry import TELEMETRY
    TELEMETRY.note_trace("predict.level_ensemble_pallas",
                         (X2.shape, stack.root.shape[0]))
    from jax.experimental import pallas as pl

    n, f2_dim = X2.shape
    T = stack.root.shape[0]
    K = stack.cls_onehot.shape[1]
    W = stack.cat_words.shape[0] // stack.feat2.shape[0]
    if n % tile != 0:
        raise ValueError(f"row count {n} must be a multiple of the "
                         f"predict tile {tile} (buckets are powers of "
                         "two; the serving predictor pads)")

    def kernel(f2_ref, thi_ref, tlo_ref, dt_ref, l_ref, r_ref, lv_ref,
               cw_ref, root_ref, c1h_ref, x2_ref, out_ref):
        local = LevelEnsemble(
            feat2=f2_ref[:], thr_hi=thi_ref[:], thr_lo=tlo_ref[:],
            dtype_=dt_ref[:], left=l_ref[:], right=r_ref[:],
            leaf_value=lv_ref[:], cat_words=cw_ref[:], root=root_ref[:],
            cls_onehot=c1h_ref[:])
        X2t = x2_ref[:]
        node = jnp.broadcast_to(local.root[None, :], (tile, T))
        if depth > 0:
            node = jax.lax.fori_loop(
                0, depth,
                lambda i, nd: _level_step(local, X2t, nd, T, W), node)
        leaf = jnp.clip(-node - 1, 0, local.leaf_value.shape[0] - 1)
        vals = local.leaf_value[leaf]
        out_ref[:] = jnp.dot(vals, local.cls_onehot,
                             preferred_element_type=jnp.float32)

    def full(a):
        return pl.BlockSpec(a.shape, lambda i: (0,) * a.ndim)

    fields = [stack.feat2, stack.thr_hi, stack.thr_lo, stack.dtype_,
              stack.left, stack.right, stack.leaf_value,
              stack.cat_words, stack.root, stack.cls_onehot]
    return pl.pallas_call(
        kernel,
        grid=(n // tile,),
        in_specs=[full(a) for a in fields]
        + [pl.BlockSpec((tile, f2_dim), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((tile, K), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, K), jnp.float32),
        interpret=interpret,
        name="predict_level_ensemble_pallas")(*fields, X2)


class RawTreeStack(NamedTuple):
    """T host trees stacked into fixed-shape device arrays for the
    raw-feature batch predict (padded to the batch max node/leaf/cat
    counts; empty node slots route to leaf 0 of an all-zero pad)."""
    num_leaves: jax.Array   # (T,) int32
    feature: jax.Array      # (T, M) int32 real feature idx
    thr_hi: jax.Array       # (T, M) f32 threshold high part
    thr_lo: jax.Array       # (T, M) f32 threshold residual
    dtype_: jax.Array       # (T, M) int32 decision_type bitfield
    left: jax.Array         # (T, M) int32 (negative = ~leaf)
    right: jax.Array        # (T, M) int32
    leaf_value: jax.Array   # (T, L) f32
    cat_words: jax.Array    # (T, M, W) int32 per-node category bitset


def stack_host_trees(models: List) -> RawTreeStack:
    """Upload a host Tree list as one RawTreeStack (leaf values carry
    shrinkage/DART renormalization already — host semantics)."""
    from ..tree import (ensemble_cat_width, split_threshold_parts,
                        tree_cat_words)
    T = len(models)
    M = max(max(t.num_leaves - 1 for t in models), 1)
    L = M + 1
    W = ensemble_cat_width(models)
    nl = np.zeros(T, np.int32)
    feat = np.zeros((T, M), np.int32)
    thr = np.zeros((T, M), np.float64)
    dt = np.zeros((T, M), np.int32)
    left = np.zeros((T, M), np.int32)
    right = np.zeros((T, M), np.int32)
    lv = np.zeros((T, L), np.float64)
    cw = np.zeros((T, M, W), np.uint32)
    for k, t in enumerate(models):
        m = t.num_leaves - 1
        nl[k] = t.num_leaves
        if m <= 0:
            lv[k, 0] = t.leaf_value[0] if len(t.leaf_value) else 0.0
            continue
        feat[k, :m] = t.split_feature[:m]
        thr[k, :m] = t.threshold[:m]
        dt[k, :m] = t.decision_type[:m]
        left[k, :m] = t.left_child[:m]
        right[k, :m] = t.right_child[:m]
        lv[k, :t.num_leaves] = t.leaf_value[:t.num_leaves]
        cw[k, :m] = tree_cat_words(t, W)
    hi, lo = split_threshold_parts(thr)
    return RawTreeStack(
        num_leaves=jnp.asarray(nl), feature=jnp.asarray(feat),
        thr_hi=jnp.asarray(hi), thr_lo=jnp.asarray(lo),
        dtype_=jnp.asarray(dt), left=jnp.asarray(left),
        right=jnp.asarray(right),
        leaf_value=jnp.asarray(lv.astype(np.float32)),
        cat_words=jnp.asarray(cw.view(np.int32)))


def split_hi_lo(X: np.ndarray):
    """float64 matrix -> (hi, lo) f32 pair with hi + lo == X to ~48
    mantissa bits (enough to reproduce f64 threshold decisions on any
    f32-representable data)."""
    X = np.asarray(X, dtype=np.float64)
    hi = X.astype(np.float32)
    with np.errstate(invalid="ignore"):
        lo = (X - hi.astype(np.float64)).astype(np.float32)
    return hi, np.where(np.isnan(lo), np.float32(0), lo)


def _walk_raw(tree: RawTreeStack, Xhi: jax.Array, Xlo: jax.Array
              ) -> jax.Array:
    """One stacked tree (unbatched slices) over raw features: the
    device form of Tree.predict_leaf (tree.py:136-179; reference
    tree.h:212-295 Numerical/CategoricalDecision)."""
    n = Xhi.shape[0]
    W = tree.cat_words.shape[-1]

    def body(node):
        is_internal = node >= 0
        nid = jnp.maximum(node, 0)
        feat = tree.feature[nid]
        vhi = jnp.take_along_axis(Xhi, feat[:, None], axis=1)[:, 0]
        vlo = jnp.take_along_axis(Xlo, feat[:, None], axis=1)[:, 0]
        dt = tree.dtype_[nid]
        is_cat = (dt & K_CATEGORICAL_MASK) > 0
        dleft = (dt & K_DEFAULT_LEFT_MASK) > 0
        mtype = (dt >> 2) & 3
        nan_mask = jnp.isnan(vhi)
        conv = nan_mask & (mtype != MISSING_NAN)
        fhi = jnp.where(conv, 0.0, vhi)
        flo = jnp.where(conv, 0.0, vlo)
        is_zero = (fhi > -K_ZERO_THRESHOLD) & (fhi <= K_ZERO_THRESHOLD)
        use_default = ((mtype == MISSING_ZERO) & is_zero) | \
                      ((mtype == MISSING_NAN) & jnp.isnan(fhi))
        # two-float comparison: exact f64 `fv <= thr` for
        # f32-representable data (see module docstring)
        num_left = jnp.where(use_default, dleft,
                             _two_float_left(fhi, flo, tree.thr_hi[nid],
                                             tree.thr_lo[nid]))
        # categorical: int truncation of the raw value, then bitset
        v_int = jnp.where(nan_mask, -1, fhi.astype(jnp.int32))
        in_range = (v_int >= 0) & (v_int < W * 32)
        word = tree.cat_words.reshape(-1)[
            nid * W + jnp.clip(v_int // 32, 0, W - 1)]
        bit = jnp.bitwise_and(
            jax.lax.shift_right_logical(word, v_int % 32), 1)
        cat_left = in_range & (bit > 0)
        go_left = jnp.where(is_cat, cat_left, num_left)
        nxt = jnp.where(go_left, tree.left[nid], tree.right[nid])
        return jnp.where(is_internal, nxt, node)

    node0 = jnp.where(tree.num_leaves > 1,
                      jnp.zeros(n, jnp.int32),
                      jnp.full(n, -1, jnp.int32))
    node = jax.lax.while_loop(lambda nd: jnp.any(nd >= 0), body, node0)
    leaf = -node - 1
    return tree.leaf_value[jnp.clip(leaf, 0, tree.leaf_value.shape[0] - 1)]


@jax.jit
def predict_raw_ensemble(stack: RawTreeStack, Xhi: jax.Array,
                         Xlo: jax.Array, cls: jax.Array,
                         k_total: jax.Array) -> jax.Array:
    """Scan every stacked tree over raw features, accumulating each
    tree's output into its class row.  ``cls`` is the (T,) class index
    per tree (tree t -> t % num_class, reference gbdt_prediction.cpp),
    ``k_total`` a (K, 1) broadcastable zero init (K = num_class).
    Returns (K, N) raw scores (f32 accumulation — the documented
    device-predict precision)."""
    def body(carry, xs):
        tree, c = xs
        pv = _walk_raw(tree, Xhi, Xlo)
        return carry.at[c].add(pv), None

    out, _ = jax.lax.scan(body, k_total, (stack, cls))
    return out
