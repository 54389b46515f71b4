"""Pallas histogram kernel vs XLA formulation parity (the analog of the
reference's GPU_DEBUG_COMPARE CPU-vs-GPU histogram comparator,
gpu_tree_learner.cpp:1020-1044)."""
import functools
import re

import numpy as np
import pytest
import jax.numpy as jnp

from lightgbm_tpu.ops.histogram import (compute_group_histograms,
                                        compute_group_histograms_pallas)


def test_pallas_kernel_matches_einsum_interpret():
    rng = np.random.RandomState(0)
    N, G, B, L = 2048, 5, 16, 7
    bins = jnp.asarray(rng.randint(0, B, (N, G)).astype(np.uint8))
    grad = jnp.asarray(rng.randn(N).astype(np.float32))
    hess = jnp.asarray(np.abs(rng.randn(N)).astype(np.float32))
    cnt = jnp.asarray((rng.rand(N) > 0.3).astype(np.float32))
    leaf = jnp.asarray(rng.randint(-1, L, N).astype(np.int32))
    ref = compute_group_histograms(bins, grad, hess, cnt, leaf,
                                   num_leaves=L, max_group_bin=B,
                                   chunk=1024)
    out = compute_group_histograms_pallas(bins, grad, hess, cnt, leaf,
                                          num_leaves=L, max_group_bin=B,
                                          block=512, interpret=True)
    # the kernel uses bf16 operands (same as XLA's default TPU matmul
    # precision) with f32 accumulation — tolerance covers the operand
    # rounding
    scale = float(jnp.max(jnp.abs(ref))) + 1.0
    assert float(jnp.max(jnp.abs(ref - out))) / scale < 5e-3
    # count channel is exact (integers are bf16-exact here)
    assert float(jnp.max(jnp.abs(ref[..., 2] - out[..., 2]))) == 0.0


def test_fused_route_hist_matches_composition_interpret():
    """Fused route+histogram kernel == apply_route_table followed by
    the XLA histogram, on a case with numerical (all missing types)
    and categorical splits."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import (
        compute_group_histograms_fused, precompute_bin_onehot)
    from lightgbm_tpu.ops.partition import (MISSING_NAN, MISSING_NONE,
                                            MISSING_ZERO,
                                            apply_route_table,
                                            build_route_table)

    rng = np.random.RandomState(1)
    N, G, B, L = 1024, 6, 16, 12
    bins = rng.randint(0, B, (N, G)).astype(np.uint8)
    grad = rng.randn(N).astype(np.float32)
    hess = np.abs(rng.randn(N)).astype(np.float32)
    cnt = (rng.rand(N) > 0.2).astype(np.float32)
    leaf = rng.randint(-1, 6, N).astype(np.int32)

    sm = np.zeros(L, bool)
    sm[:4] = True
    tab = build_route_table(
        jnp.asarray(sm),
        jnp.asarray(np.array([0, 2, 5, 3] + [0] * 8, np.int32)),  # group
        jnp.zeros(L, jnp.int32), jnp.full(L, B, jnp.int32),       # lo, hi
        jnp.zeros(L, jnp.int32), jnp.full(L, B - 1, jnp.int32),   # shift, oor
        jnp.asarray(np.array([0, 0, 0, 1] + [0] * 8, bool)),      # is_cat
        jnp.asarray(np.array([7, 3, 11, 5] + [0] * 8, np.int32)),  # thr
        jnp.asarray(np.array([1, 0, 1, 0] + [0] * 8, bool)),      # dleft
        jnp.asarray(np.array([MISSING_NONE, MISSING_ZERO, MISSING_NAN, 0]
                             + [0] * 8, np.int32)),
        jnp.asarray(np.array([0, 2, 0, 0] + [0] * 8, np.int32)),  # dbin
        jnp.full(L, B, jnp.int32),                                # num_bin
        jnp.asarray(rng.rand(L, B) > 0.5),                        # cat_mask
        jnp.asarray(np.array([6, 7, 8, 9] + [0] * 8, np.int32)))  # right

    want_leaf = np.asarray(apply_route_table(
        jnp.asarray(bins), jnp.asarray(leaf), tab))
    slots = jnp.asarray(np.array([6, 7, 8, 9, 0, 1, -1, 3], np.int32))
    want_hist = compute_group_histograms(
        jnp.asarray(bins), jnp.asarray(grad), jnp.asarray(hess),
        jnp.asarray(cnt), jnp.asarray(want_leaf), num_leaves=L,
        max_group_bin=B, chunk=512, slots=slots)

    ohb = precompute_bin_onehot(jnp.asarray(bins), max_group_bin=B)
    wT = jnp.stack([jnp.asarray(grad), jnp.asarray(hess),
                    jnp.asarray(cnt)], axis=0)
    got_hist, got_leaf = compute_group_histograms_fused(
        ohb, jnp.asarray(bins.T), wT, jnp.asarray(leaf), tab,
        slots, max_group_bin=B, block=256, strips=1, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_leaf), want_leaf)
    got = np.asarray(got_hist)[:slots.shape[0]]
    ref = np.asarray(want_hist)
    scale = np.abs(ref).max() + 1.0
    assert np.abs(ref - got).max() / scale < 5e-3
    assert np.abs(ref[..., 2] - got[..., 2]).max() == 0.0


def test_subbyte_packed_onehot_matches_full():
    """precompute_bin_onehot_packed planes widen back to the exact
    full-width one-hot (planar layout + lane padding)."""
    from lightgbm_tpu.ops.histogram import (precompute_bin_onehot,
                                            precompute_bin_onehot_packed)
    rng = np.random.RandomState(4)
    N, G, B = 300, 4, 8
    gb = G * B
    bins = jnp.asarray(rng.randint(0, B, (N, G)).astype(np.uint8))
    full = np.asarray(precompute_bin_onehot(bins, max_group_bin=B))
    for pack in (2, 4):
        gbp = gb // pack
        gbp_pad = ((gbp + 127) // 128) * 128
        packed = np.asarray(precompute_bin_onehot_packed(
            bins, max_group_bin=B, pack=pack))
        assert packed.shape == (N, gbp_pad)
        bits = 8 // pack
        for p in range(pack):
            plane = (packed.astype(np.int32) >> (p * bits)) & 1
            np.testing.assert_array_equal(
                plane[:, :gbp], full[:, p * gbp:(p + 1) * gbp])
            assert (plane[:, gbp:] == 0).all()


def test_subbyte_streamed_kernels_match_pack1_interpret():
    """pre / pre_packed / fused kernels give identical histograms from
    the sub-byte packed one-hot.  The weights are the int8 levels as
    floats (exact in bf16, their sums exact in float32), so every
    comparison is to the bit — the int8 tiled kernel's accumulators
    among them."""
    from lightgbm_tpu.ops.histogram import (
        compute_group_histograms_fused,
        compute_group_histograms_fused_tiled,
        compute_group_histograms_pre, compute_group_histograms_pre_packed,
        precompute_bin_onehot, precompute_bin_onehot_packed,
        quantize_gradients)
    rng = np.random.RandomState(6)
    N, G, B, L = 512, 4, 8, 10
    bins = rng.randint(0, B, (N, G)).astype(np.uint8)
    grad = rng.randn(N).astype(np.float32)
    hess = np.abs(rng.randn(N)).astype(np.float32)
    cnt = np.ones(N, np.float32)
    leaf = rng.randint(-1, 8, N).astype(np.int32)
    wq, scales = quantize_gradients(jnp.asarray(grad), jnp.asarray(hess),
                                    jnp.asarray(cnt))
    w = wq.astype(jnp.float32)
    slots = jnp.asarray(np.array([0, 3, 5, -1, 7, 2], np.int32))
    tab = jnp.zeros((L, 15 + (B + 7) // 8), jnp.float32)
    ohb1 = precompute_bin_onehot(jnp.asarray(bins), max_group_bin=B)
    ref_pre = None
    ref_pp = None
    ref_fu = None
    for pack in (1, 2, 4):
        ohb = (ohb1 if pack == 1 else precompute_bin_onehot_packed(
            jnp.asarray(bins), max_group_bin=B, pack=pack))
        h_pre = np.asarray(compute_group_histograms_pre(
            ohb, w, jnp.asarray(leaf), num_leaves=L,
            max_group_bin=B, block=256, slots=slots,
            interpret=True, pack=pack, num_groups=G))
        h_pp = np.asarray(compute_group_histograms_pre_packed(
            ohb, w, jnp.asarray(leaf), slots, max_group_bin=B,
            block=256, strips=1, interpret=True, pack=pack,
            num_groups=G))[:slots.shape[0]]
        h_fu, lf = compute_group_histograms_fused(
            ohb, jnp.asarray(bins.T), w.T, jnp.asarray(leaf),
            tab, slots, max_group_bin=B, block=256, strips=1,
            interpret=True, pack=pack, num_groups=G)
        h_fu = np.asarray(h_fu)[:slots.shape[0]]
        np.testing.assert_array_equal(np.asarray(lf), leaf)
        if pack == 1:
            ref_pre, ref_pp, ref_fu = h_pre, h_pp, h_fu
        else:
            np.testing.assert_array_equal(h_pre, ref_pre)
            np.testing.assert_array_equal(h_pp, ref_pp)
            np.testing.assert_array_equal(h_fu, ref_fu)
    # the three kernel families agree with each other (all outputs are
    # slot-ordered; negative slots are zero rows everywhere)
    np.testing.assert_array_equal(ref_pp, ref_pre)
    np.testing.assert_array_equal(ref_fu, ref_pre)
    # ... and with the plain sum of the levels
    want = np.zeros((slots.shape[0], G, B, 3))
    wqn = np.asarray(wq)
    for r in range(N):
        for k, s_ in enumerate(np.asarray(slots)):
            if s_ >= 0 and leaf[r] == s_:
                for g in range(G):
                    want[k, g, bins[r, g]] += wqn[r]
    np.testing.assert_array_equal(ref_pre, want)

    # the tiled-iota kernel (no resident one-hot at all) joins the
    # family: its int32 accumulators are the same integers
    h_ft, lf_t = compute_group_histograms_fused_tiled(
        jnp.asarray(bins.T), wq.T, None, jnp.asarray(leaf), tab, slots,
        max_group_bin=B, block=256, strips=1, interpret=True,
        dequantize=False)
    np.testing.assert_array_equal(np.asarray(lf_t), leaf)
    np.testing.assert_array_equal(
        np.asarray(h_ft)[:slots.shape[0]], ref_fu)


def test_fused_grower_wiring_interpret_matches_xla_path():
    """The TPU-only fused-route grower wiring (route_tab round-carry,
    exit-time apply_route_table, quantized weight transpose) runs on
    CPU via interpret-mode Pallas and must reproduce the plain XLA
    path's model."""
    import lightgbm_tpu as lgb

    rng = np.random.RandomState(5)
    X = rng.randn(500, 8)
    y = (X[:, 0] - 0.5 * X[:, 1] + 0.1 * rng.randn(500) > 0).astype(float)
    base = {"objective": "binary", "verbose": -1, "num_leaves": 15,
            "min_data_in_leaf": 5, "hist_compute_dtype": "bfloat16"}
    fused = dict(base, force_pallas_interpret=True, quantized_grad=True)
    b_xla = lgb.train(base, lgb.Dataset(X, label=y), 4,
                      verbose_eval=False)
    b_fused = lgb.train(fused, lgb.Dataset(X, label=y), 4,
                        verbose_eval=False)
    p_xla = b_xla.predict(X)
    p_fused = b_fused.predict(X)
    # quantization perturbs gains slightly; structure-level agreement +
    # close predictions is the wiring gate (a dropped exit-route or a
    # missing transpose corrupts leaf assignments catastrophically)
    assert np.abs(p_xla - p_fused).mean() < 0.02
    acc = ((p_fused > 0.5) == y).mean()
    assert acc > 0.9


def test_route_apply_tiled_matches_xla_interpret():
    """Pallas exit-route kernel (route_apply_tiled) == XLA
    apply_route_table(values=...): leaf ids exactly AND the bf16-split
    leaf-value columns reassemble the same f32 row values — pins the
    column layout contract of extend_table_with_values on both sides."""
    import jax.numpy as jnp
    from lightgbm_tpu.ops.histogram import route_apply_tiled
    from lightgbm_tpu.ops.partition import (MISSING_NAN, MISSING_NONE,
                                            MISSING_ZERO,
                                            apply_route_table,
                                            build_route_table)

    rng = np.random.RandomState(4)
    N, G, B, L = 1024, 6, 16, 12
    bins = rng.randint(0, B, (N, G)).astype(np.uint8)
    leaf = rng.randint(-1, 6, N).astype(np.int32)
    values = rng.randn(L).astype(np.float32) * 3

    sm = np.zeros(L, bool)
    sm[:4] = True
    tab = build_route_table(
        jnp.asarray(sm),
        jnp.asarray(np.array([0, 2, 5, 3] + [0] * 8, np.int32)),
        jnp.zeros(L, jnp.int32), jnp.full(L, B, jnp.int32),
        jnp.zeros(L, jnp.int32), jnp.full(L, B - 1, jnp.int32),
        jnp.asarray(np.array([0, 0, 0, 1] + [0] * 8, bool)),
        jnp.asarray(np.array([7, 3, 11, 5] + [0] * 8, np.int32)),
        jnp.asarray(np.array([1, 0, 1, 0] + [0] * 8, bool)),
        jnp.asarray(np.array([MISSING_NONE, MISSING_ZERO, MISSING_NAN, 0]
                             + [0] * 8, np.int32)),
        jnp.asarray(np.array([0, 2, 0, 0] + [0] * 8, np.int32)),
        jnp.full(L, B, jnp.int32),
        jnp.asarray(rng.rand(L, B) > 0.5),
        jnp.asarray(np.array([6, 7, 8, 9] + [0] * 8, np.int32)))

    want_leaf, want_val = apply_route_table(
        jnp.asarray(bins), jnp.asarray(leaf), tab,
        values=jnp.asarray(values))
    got_leaf, got_val = route_apply_tiled(
        jnp.asarray(bins.T), jnp.asarray(leaf), tab,
        jnp.asarray(values), block=256, interpret=True)
    np.testing.assert_array_equal(np.asarray(got_leaf),
                                  np.asarray(want_leaf))
    np.testing.assert_array_equal(np.asarray(got_val),
                                  np.asarray(want_val))


# ---------------------------------------------------------------------------
# factored rungs (ops/histogram.py FACTORED_RUNGS): same integers as the
# tiled kernel on the strips the pass had before, from a dot whose
# one-hot is b lanes a group
# ---------------------------------------------------------------------------
_FACT_N, _FACT_G = 512, 19    # 19 groups: no pack divides it, and the
#                                kernel's loop makes a trip
_FACT_SLOTS = [21, 3, -1, 9, 0, 14, 6, 11, 2, 17, 5, 8, 19, 1, 12, 4]
#: the wide rungs' frontier: 126 slots over 140 leaves, two of them idle
_FACT_SLOTS_WIDE = [int(v) for v in
                    np.random.RandomState(7).permutation(140)[:126]]
_FACT_SLOTS_WIDE[2] = _FACT_SLOTS_WIDE[70] = -1
#: active slots a wide rung is tried at: the fewest it serves, the most,
#: and one between
_FACT_WIDE_KS = {64: (33, 64), 126: (65, 100, 126)}


def _factored_rung_cases():
    from lightgbm_tpu.ops.histogram import FACTORED_RUNGS
    narrow = [(k_cap, a, b, k, B, True)
              for k_cap, a, b in FACTORED_RUNGS if k_cap <= 32
              for k in (1, 2, 3, 4, 8, 16) if k <= k_cap
              for B in (255, 256)]
    wide = [(k_cap, a, b, k, B, dequantize)
            for k_cap, a, b in FACTORED_RUNGS if k_cap > 32
            for k in _FACT_WIDE_KS[k_cap]
            for B in (255, 256)
            for dequantize in (True, False)]
    return narrow + wide


@functools.lru_cache(maxsize=None)
def _factored_inputs(B, strips=1, dequantize=True, G=_FACT_G,
                     split_groups=(0, 2, 5, 3), N=_FACT_N, rows="any",
                     segment_rows=0):
    """One table a bin width and frontier: padded rows (leaf -1), a route
    table that moves rows by ``split_groups``' bins, quantized weights,
    and the tiled kernel's answer on ``strips`` strips for every slot of
    ``_FACT_SLOTS`` (one strip) or of as many of ``_FACT_SLOTS_WIDE`` as
    the strips hold.  ``rows``: where the ``N`` rows start — in ``any``
    leaf (some padded), ``slots``: every row in a leaf of the frontier,
    or ``head``: the first 300 rows there and the rest in no leaf of it."""
    from lightgbm_tpu.ops.histogram import (
        PACKED_STRIP, compute_group_histograms_fused_tiled,
        quantize_gradients)
    from lightgbm_tpu.ops.partition import (MISSING_NAN, MISSING_NONE,
                                            MISSING_ZERO,
                                            build_route_table)
    rng = np.random.RandomState(B)
    # leaves the rows start in, the four that split, and the table's rows
    leaves, L = (20, 40) if strips == 1 else (140, 160)
    slots = _FACT_SLOTS if strips == 1 \
        else _FACT_SLOTS_WIDE[:strips * PACKED_STRIP]
    bins = rng.randint(0, B, (N, G)).astype(np.uint8)
    leaf = rng.randint(-1, leaves, N).astype(np.int32)
    if strips > 1:
        leaf[-8:] = -1
    split = [0, 1, 2, 3] if strips == 1 else [7, 31, 64, 120]
    if rows != "any":
        # (the frontier's rows stay where they start: in no split leaf)
        front = np.setdiff1d([v for v in slots if v >= 0], split)
        rest = np.setdiff1d(np.arange(leaves), slots)
        leaf = front[rng.randint(0, len(front), N)].astype(np.int32)
        if rows == "head":
            leaf[300:] = rest[rng.randint(0, len(rest), N - 300)]
    wq, scales = quantize_gradients(
        jnp.asarray(rng.randn(N).astype(np.float32)),
        jnp.asarray(np.abs(rng.randn(N)).astype(np.float32)),
        jnp.asarray((rng.rand(N) > 0.2).astype(np.float32)))
    sm = np.zeros(L, bool)
    sm[split] = True

    def col(values, dtype=np.int32):
        out = np.zeros(L, dtype)
        out[split] = values
        return jnp.asarray(out)
    tab = build_route_table(
        jnp.asarray(sm), col(list(split_groups)),
        jnp.zeros(L, jnp.int32), jnp.full(L, B, jnp.int32),
        jnp.zeros(L, jnp.int32), jnp.full(L, B - 1, jnp.int32),
        col([0, 0, 0, 1], dtype=bool), col([70, 30, 110, 50]),
        col([1, 0, 1, 0], dtype=bool),
        col([MISSING_NONE, MISSING_ZERO, MISSING_NAN, 0]),
        col([0, 2, 0, 0]),
        jnp.full(L, B, jnp.int32),
        jnp.asarray(rng.rand(L, B) > 0.5),
        col([leaves + i for i in range(4)]))
    args = (jnp.asarray(bins.T), wq.T, scales, jnp.asarray(leaf), tab)
    want_h, want_leaf = compute_group_histograms_fused_tiled(
        *args, jnp.asarray(np.array(slots, np.int32)),
        max_group_bin=B, block=256, strips=strips, interpret=True,
        dequantize=dequantize, segment_rows=segment_rows)
    want_leaf = np.asarray(want_leaf)
    if rows == "any":
        assert (want_leaf != leaf).sum() > (20 if strips == 1 else 5)
        assert (want_leaf == -1).sum() > 5      # padded rows stay out
    return args, slots, np.asarray(want_h), want_leaf


@pytest.mark.parametrize("k_cap,a,b,k,B,dequantize", _factored_rung_cases())
def test_factored_rung_equals_one_strip_tiled_interpret(k_cap, a, b, k, B,
                                                        dequantize):
    """Every rung, at every active-slot count it serves (the wide rungs:
    the fewest, the most and one between): the histogram and the routed
    leaf ids equal the tiled kernel's exactly, on the one strip, the two
    or the three that the pass had before the rung (the int32 sums are
    the same integers; ``dequantize=False`` hands them over as they
    are, which is what a row shard gives the cross-chip sum)."""
    from lightgbm_tpu.ops.histogram import (
        PACKED_STRIP, compute_group_histograms_fused_factored)
    assert a * b == 256
    strips = -(-k_cap // PACKED_STRIP)          # the pass's strips before
    args, all_slots, want_h, want_leaf = _factored_inputs(B, strips,
                                                          dequantize)
    slots = np.full(126, -1, np.int32)
    slots[:k] = all_slots[:k]
    got_h, got_leaf = compute_group_histograms_fused_factored(
        *args, jnp.asarray(slots), max_group_bin=B, block=256,
        k_cap=k_cap, a=a, interpret=True, dequantize=dequantize)
    got_h = np.asarray(got_h)
    assert got_h.shape == (k_cap, _FACT_G, B, 3)
    assert got_h.dtype == (np.float32 if dequantize else np.int32)
    np.testing.assert_array_equal(np.asarray(got_leaf), want_leaf)
    np.testing.assert_array_equal(got_h[:k], want_h[:k])
    assert not got_h[k:].any()                  # invalid slots: zero rows
    assert got_h[:k].any(axis=(1, 2, 3)).sum() > k // 2    # rows came


# ---------------------------------------------------------------------------
# the group axis as a grid axis (group_chunk): a table wider than one
# chunk of the factored kernel
# ---------------------------------------------------------------------------
_CHUNK_G, _CHUNK_B = 72, 255                   # 72 = 32 + 32 + 8 groups
#: the four pending splits read a group of the first chunk of 32, of
#: the second (twice) and of the third: every chunk routes rows by
#: groups it does not hold
_CHUNK_SPLITS = (3, 40, 70, 33)


def _chunk_inputs():
    return _factored_inputs(_CHUNK_B, 1, False, _CHUNK_G, _CHUNK_SPLITS)


@pytest.mark.parametrize("k_cap,a,k", [(2, 4, 2), (16, 2, 11)],
                         ids=["pack2", "pack1"])
def test_factored_group_chunks_equal_one_chunk_interpret(k_cap, a, k):
    """Two rungs (two groups to a 128-row tile, and one) over 72 groups
    in chunks of 32 — the last chunk holds 8, the rest of its block is
    stale — with every pending split's group in another chunk than two
    of the three being accumulated: the int32 sums and the routed leaf
    ids are the one-chunk call's, the tiled kernel's, and the XLA
    contraction's integers."""
    from lightgbm_tpu.ops.histogram import (
        compute_group_histograms, compute_group_histograms_fused_factored)
    args, all_slots, want_h, want_leaf = _chunk_inputs()
    binsT, wqT = args[0], args[1]
    slots = np.full(126, -1, np.int32)
    slots[:k] = all_slots[:k]           # slot 0 is a right child: leaf 21

    def run(group_chunk):
        return compute_group_histograms_fused_factored(
            *args, jnp.asarray(slots), max_group_bin=_CHUNK_B, block=256,
            k_cap=k_cap, a=a, interpret=True, dequantize=False,
            group_chunk=group_chunk)
    one_h, one_leaf = run(0)
    got_h, got_leaf = (np.asarray(v) for v in run(32))
    assert got_h.shape == (k_cap, _CHUNK_G, _CHUNK_B, 3)
    assert got_h.dtype == np.int32
    np.testing.assert_array_equal(got_leaf, np.asarray(one_leaf))
    np.testing.assert_array_equal(got_leaf, want_leaf)
    np.testing.assert_array_equal(got_h, np.asarray(one_h))
    np.testing.assert_array_equal(got_h[:k], want_h[:k])
    # a chunk as wide as the table, or wider, is the one-chunk call
    np.testing.assert_array_equal(np.asarray(run(96)[0]), got_h)
    want = compute_group_histograms(
        binsT.T, *(wqT[c].astype(jnp.float32) for c in range(3)),
        jnp.asarray(got_leaf), num_leaves=40, max_group_bin=_CHUNK_B,
        chunk=_FACT_N, slots=jnp.asarray(slots[:k]))
    np.testing.assert_array_equal(got_h[:k],
                                  np.asarray(want).astype(np.int32))
    assert got_h[0].any()               # the routed-to child has rows


# ---------------------------------------------------------------------------
# the compacting rungs (ops/histogram.py COMPACT_RUNGS): a unit's rows
# of an active slot brought to its front before the dot
# ---------------------------------------------------------------------------
#: rows of the compaction's table: two blocks of 1,024 rows, a block two
#: units of 512, a unit's dots over 256, 384 or 512 columns
_COMPACT_N, _COMPACT = 2048, (512, 128)
#: id -> (k_cap, active slots, the slots' own places in the frontier,
#: where the rows start, groups, group_chunk, segment_rows)
_COMPACT_CASES = {
    # no row is in a slot of the pass: the frontier's two idle slots
    "share0": (64, 2, (2, 70), "any", _FACT_G, 0, 0),
    # 65 of 140 leaves: counts of 220-260 a unit, no multiple of a step
    "half": (126, 65, None, "any", _FACT_G, 0, 0),
    # every row active: every unit takes its full width
    "share1": (126, 126, None, "slots", _FACT_G, 0, 0),
    # the active rows lead the first unit; three units hold none
    "one_unit": (126, 126, None, "head", _FACT_G, 0, 0),
    # a frontier far narrower than the rung's cap
    "narrow": (126, 3, None, "any", _FACT_G, 0, 0),
    "k32": (32, 32, None, "any", _FACT_G, 0, 0),
    # the group axis a grid axis: a unit is compacted once a chunk
    "chunks": (32, 20, None, "any", _CHUNK_G, 32, 0),
    # one accumulator a row segment of one block
    "segments": (64, 40, None, "any", _FACT_G, 0, 1024),
}


def _compact_case(case):
    """A case's arguments, its slots, and the tiled kernel's int32
    accumulators and leaf ids on the strips the pass had."""
    k_cap, k, places, rows, G, _, segment_rows = _COMPACT_CASES[case]
    args, all_slots, want_h, want_leaf = _factored_inputs(
        255, 3, False, G, (0, 2, 5, 3), _COMPACT_N, rows, segment_rows)
    slots = np.full(126, -1, np.int32)
    slots[:k] = [all_slots[p] for p in places or range(k)]
    if places:                          # the tiled answer follows places
        want_h = want_h[..., list(places), :, :, :]
    return args, slots, want_h[..., :k, :, :, :], want_leaf


@pytest.mark.parametrize("case", list(_COMPACT_CASES))
def test_compacting_rung_equals_tiled_and_uncompacted_interpret(case):
    """A compacting rung returns the integers of the tiled kernel and of
    its own uncompacted formulation (the parent's), and the same leaf
    ids: whatever share of a unit's rows is active, wherever they lie,
    whatever the count leaves of a step, under group chunks and in row
    segments."""
    from lightgbm_tpu.ops.histogram import (
        COMPACT_RUNGS, compute_group_histograms_fused_factored)
    k_cap, k, _, _, G, group_chunk, segment_rows = _COMPACT_CASES[case]
    assert k_cap in COMPACT_RUNGS
    args, slots, want_h, want_leaf = _compact_case(case)

    def run(compact):
        h, leaf = compute_group_histograms_fused_factored(
            *args, jnp.asarray(slots), max_group_bin=255, block=1024,
            k_cap=k_cap, a=2, interpret=True, dequantize=False,
            group_chunk=group_chunk, segment_rows=segment_rows,
            compact=compact)
        return np.asarray(h), np.asarray(leaf)
    got_h, got_leaf = run(_COMPACT)
    plain_h, plain_leaf = run(())
    assert got_h.dtype == np.int32
    assert got_h.shape == ((2,) if segment_rows else ()) \
        + (k_cap, G, 255, 3)
    np.testing.assert_array_equal(got_leaf, want_leaf)
    np.testing.assert_array_equal(got_leaf, plain_leaf)
    np.testing.assert_array_equal(got_h, plain_h)
    np.testing.assert_array_equal(got_h[..., :k, :, :, :], want_h)
    active = np.isin(want_leaf, slots[slots >= 0]).reshape(
        -1, _COMPACT[0]).sum(1)
    if case == "share0":
        assert not active.any() and not got_h.any()
    elif case == "share1":
        assert (active == _COMPACT[0]).all()
    elif case == "one_unit":
        assert active[0] == 300 and not active[1:].any()
    else:
        assert (active % _COMPACT[1]).all() and got_h.any()


def test_route_apply_split_rows_equal_whole_table_interpret():
    """The exit route of a chunked table: ``route_apply_tiled`` over the
    split rows of ``gather_split_rows`` gives the leaf ids and the row
    values of the kernel over every group's rows."""
    from lightgbm_tpu.ops.histogram import (ROUTE_ROWS, gather_split_rows,
                                            route_apply_tiled)
    (binsT, _, _, leaf, tab), _, _, want_leaf = _chunk_inputs()
    values = jnp.asarray(np.random.RandomState(5).randn(
        tab.shape[0]).astype(np.float32))
    want = route_apply_tiled(binsT, leaf, tab, values, block=256,
                             interpret=True)
    rowsT, tab_rows = gather_split_rows(binsT, tab)
    assert rowsT.shape == (ROUTE_ROWS, _FACT_N)
    # the active leaves 0..3 read their split groups, in slot order
    np.testing.assert_array_equal(
        np.asarray(rowsT[:4]), np.asarray(binsT)[list(_CHUNK_SPLITS)])
    got = route_apply_tiled(rowsT, leaf, tab_rows, values, block=256,
                            interpret=True)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    np.testing.assert_array_equal(np.asarray(got[0]), want_leaf)


def _all_route_rows(binsT, route_tab):
    """The split-row gather as it was: every one of the ``ROUTE_ROWS``
    rows copied, an inactive leaf's from group 0 (the reference of the
    active-rows-only gather)."""
    import jax
    from lightgbm_tpu.ops.histogram import ROUTE_ROWS
    active = route_tab[:, 10] > 0.5
    grp = (route_tab[:, 0] * 256 + route_tab[:, 1]).astype(jnp.int32)
    rank = jnp.cumsum(active.astype(jnp.int32)) - 1
    row_group = jnp.zeros(ROUTE_ROWS, jnp.int32).at[
        jnp.where(active, rank, ROUTE_ROWS)].set(grp, mode="drop")
    rowsT = jax.lax.map(
        lambda g: jax.lax.dynamic_index_in_dim(binsT, g, 0, False),
        row_group)
    tab = route_tab.at[:, 0].set(0.0).at[:, 1].set(
        jnp.where(active, rank, 0).astype(jnp.float32))
    return rowsT, tab


def _route_tab_with(active):
    """The chunked table's route table grown to 168 leaves with
    ``active`` of them splitting: leaves 0..3 as the table has them
    (groups ``_CHUNK_SPLITS``), then leaves past the rows' own, each
    with leaf 3's split on a group of its own or, for the fifth, on
    leaf 1's group (40) — two active leaves on one group."""
    (_, _, _, _, tab), _, _, _ = _chunk_inputs()
    tab = np.asarray(tab)
    out = np.zeros((168, tab.shape[1]), np.float32)
    out[:4] = tab[:4]
    for i in range(4, active):
        g = 40 if i == 4 else (7 * i) % _CHUNK_G
        out[36 + i] = tab[3]
        out[36 + i, :2] = divmod(g, 256)
    out[active:4, 10] = 0.0
    assert (out[:, 10] > 0.5).sum() == active
    return jnp.asarray(out)


@pytest.mark.parametrize("active", [0, 1, 7, 128])
def test_split_row_gather_copies_the_active_rows_interpret(active,
                                                           monkeypatch):
    """The split-row gather copies the rows of the active leaves alone:
    active rank ``r`` gets its split group's row of the table (where two
    leaves split on one group, each its own copy), the rows past them
    are zeros, the route table is the one of the gather that copied
    every route row, and through either gather the chunked factored pass
    gives the same int32 histogram and leaf ids to the bit, as does the
    exit route over the split rows and over the whole table."""
    import jax
    from lightgbm_tpu.ops import histogram as H
    args, all_slots, _, _ = _chunk_inputs()
    binsT = args[0]
    tab = _route_tab_with(active)
    rowsT, tab_rows = jax.jit(H.gather_split_rows)(binsT, tab)
    want_rows, want_tab = _all_route_rows(binsT, tab)
    assert rowsT.shape == (H.ROUTE_ROWS, _FACT_N) \
        and rowsT.dtype == binsT.dtype
    np.testing.assert_array_equal(np.asarray(tab_rows),
                                  np.asarray(want_tab))
    groups = np.asarray(tab[:, 0] * 256 + tab[:, 1]).astype(int)[
        np.asarray(tab[:, 10]) > 0.5]
    np.testing.assert_array_equal(np.asarray(rowsT[:active]),
                                  np.asarray(binsT)[groups])
    np.testing.assert_array_equal(np.asarray(rowsT[:active]),
                                  np.asarray(want_rows[:active]))
    assert not np.asarray(rowsT[active:]).any()

    slots = np.full(126, -1, np.int32)
    slots[:11] = all_slots[:11]
    fused = H.compute_group_histograms_fused_factored.__wrapped__

    def chunked_pass(gather):
        monkeypatch.setattr(H, "gather_split_rows", gather)
        return jax.jit(functools.partial(
            fused, max_group_bin=_CHUNK_B, block=256, k_cap=16, a=2,
            interpret=True, dequantize=False, group_chunk=32))(
            *args[:4], tab, jnp.asarray(slots))
    got = chunked_pass(H.gather_split_rows)
    want = chunked_pass(_all_route_rows)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    moved = (np.asarray(got[1]) != np.asarray(args[3])).sum()
    assert moved > 0 if active else moved == 0

    values = jnp.asarray(np.random.RandomState(5).randn(
        tab.shape[0]).astype(np.float32))
    route = functools.partial(H.route_apply_tiled, block=256,
                              interpret=True)
    for g, w in zip(route(rowsT, args[3], tab_rows, values),
                    route(binsT, args[3], tab, values)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_split_rows_copied_are_the_trees_splits(monkeypatch):
    """A short quantized training on a table whose groups are chunked
    (40 groups, chunks of 32): the counter ``route_split_rows_copied``,
    read off the committed trees, is the trees' splits, and the rows
    and gathers it books are those the device ran (counted in each call
    of the every-row gather patched in); that gather grows the same
    model text."""
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops import hist_plan
    from lightgbm_tpu.ops import histogram as H
    from lightgbm_tpu.telemetry import TELEMETRY

    rng = np.random.RandomState(11)
    X = rng.lognormal(size=(2048, 40)).astype(np.float32)
    y = (X[:, 0] * X[:, 1] - X[:, 2] + X[:, 35]
         + 0.3 * rng.randn(2048) > 1.0).astype(float)
    monkeypatch.setattr(hist_plan, "CHUNK_VMEM_BUDGET", 1 << 20)
    calls = []

    def counted(binsT, route_tab):
        jax.debug.callback(lambda n: calls.append(int(n)),
                           jnp.sum(route_tab[:, 10] > 0.5))
        return _all_route_rows(binsT, route_tab)

    def model():
        # the kernels' jit caches hold the gather they were traced with
        jax.clear_caches()
        TELEMETRY.reset()
        bst = lgb.train(_fast_255(15, telemetry="counters"),
                        lgb.Dataset(X, label=y), 3, verbose_eval=False,
                        keep_training_booster=True)
        assert bst.gbdt.grower.plan.group_chunks == 2
        return (bst.model_to_string(), dict(TELEMETRY.counters()),
                dict(TELEMETRY.gauges()))

    try:
        text, counters, gauges = model()
        monkeypatch.setattr(H, "gather_split_rows", counted)
        ref_text, _, _ = model()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
        TELEMETRY.configure("off")
        TELEMETRY.reset()
    splits = sum(int(v) - 1 for v in re.findall(r"^num_leaves=(\d+)$",
                                                text, re.M))
    assert splits > 3 * 4
    assert counters["route_split_rows_copied"] == splits == sum(calls)
    assert counters["route_split_row_gathers"] == len(calls)
    assert gauges["route_split_row_share"] == splits / (
        len(calls) * H.ROUTE_ROWS)
    assert text == ref_text


def _fast_255(leaves, **extra):
    return dict({"objective": "binary", "num_leaves": leaves,
                 "max_bin": 255, "verbose": -1, "min_data_in_leaf": 2,
                 "quantized_grad": True, "hist_compute_dtype": "bfloat16",
                 "quant_stochastic_rounding": 1,
                 "force_pallas_interpret": True}, **extra)


def _splits_by_depth(tree):
    """Internal nodes at each depth of one tree of ``dump_model()``.  The
    grower splits every leaf it can in a round, up to the frontier's
    width, so while the levels above are full, the count at depth d is
    the number of active slots of the tree's pass d + 1."""
    counts = {}

    def walk(node, depth):
        if "left_child" in node:
            counts[depth] = counts.get(depth, 0) + 1
            walk(node["left_child"], depth + 1)
            walk(node["right_child"], depth + 1)
    walk(tree["tree_structure"], 0)
    return [counts[d] for d in sorted(counts)]


@pytest.mark.parametrize("leaves,extra", [
    (31, {}), (255, {}), (31, {"histogram_pool_size": 0.001})],
    ids=["31", "255", "31_no_cache"])
def test_factored_rungs_grow_identical_trees(leaves, extra, monkeypatch):
    """At max_bin=255 the factored rungs serve the passes of every tree
    (frontier one strip wide at 31 leaves; three at 255, where the rungs
    serve every pass and no strip is traced; with no histogram cache the
    parents pass too) and the model is the model of the strips ladder
    alone, byte for byte."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops import histogram as H
    from lightgbm_tpu.telemetry import TELEMETRY

    rng = np.random.RandomState(3)
    X = rng.lognormal(size=(3000, 7)).astype(np.float32)
    y = (X[:, 0] * X[:, 1] - X[:, 2] + 0.3 * rng.randn(3000)
         > 0.5).astype(float)

    def model(rungs):
        monkeypatch.setattr(H, "FACTORED_RUNGS", rungs)
        TELEMETRY.reset()
        bst = lgb.train(_fast_255(leaves, telemetry="counters", **extra),
                        lgb.Dataset(X, label=y), 3, verbose_eval=False)
        gauge = TELEMETRY.gauges()["grower.hist_factored_rungs"]
        return bst, gauge

    try:
        bst, gauge = model(H.FACTORED_RUNGS)
        with_rungs = bst.model_to_string()
        assert gauge == ",".join(f"{k}:{a}x{b}"
                                 for k, a, b in H.FACTORED_RUNGS) != ""
        assert gauge.endswith(",32:2x128,64:2x128,126:2x128")
        without, gauge = model(())
        assert gauge == ""
    finally:
        TELEMETRY.configure("off")
        TELEMETRY.reset()
    assert with_rungs == without.model_to_string()
    if leaves == 255:
        # the last tree's passes had 1, 2, 4, 8, 16, 32, then 33..64 and
        # 65..126 active slots: all six rungs ran, the two wide ones too
        splits = _splits_by_depth(bst.dump_model()["tree_info"][-1])
        assert splits[:6] == [1, 2, 4, 8, 16, 32]
        assert 33 <= splits[6] <= 64 and 65 <= splits[7] <= 126


def test_compacting_rungs_grow_identical_trees(monkeypatch):
    """The same seed grows the same model, byte for byte, with the
    rungs at 32, 64 and 126 slots compacting their blocks' rows and with
    ``COMPACT_RUNGS`` patched empty (every row through the dot, the
    parent's formulation): 255 leaves, so all three ran — the gauges say
    which compact, and the share of rows they put through their dots is
    a share."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops import histogram as H
    from lightgbm_tpu.telemetry import TELEMETRY

    rng = np.random.RandomState(3)
    X = rng.lognormal(size=(3000, 7)).astype(np.float32)
    y = (X[:, 0] * X[:, 1] - X[:, 2] + 0.3 * rng.randn(3000)
         > 0.5).astype(float)

    def model(compacting):
        monkeypatch.setattr(H, "COMPACT_RUNGS", compacting)
        TELEMETRY.reset()
        bst = lgb.train(_fast_255(255, telemetry="counters"),
                        lgb.Dataset(X, label=y), 3, verbose_eval=False,
                        keep_training_booster=True)
        assert bst.gbdt.grower.plan.compact_rungs == compacting
        return bst, TELEMETRY.gauges()

    try:
        bst, gauges = model(H.COMPACT_RUNGS)
        assert gauges["grower.hist_compact_rungs"] == "32,64,126"
        assert 0.2 < gauges["hist_active_row_share"] < 0.8
        plain, gauges = model(())
        assert gauges["grower.hist_compact_rungs"] == ""
        assert "hist_active_row_share" not in gauges
    finally:
        TELEMETRY.configure("off")
        TELEMETRY.reset()
    assert bst.model_to_string() == plain.model_to_string()
    splits = _splits_by_depth(bst.dump_model()["tree_info"][-1])
    assert splits[5] == 32 and 33 <= splits[6] <= 64 < splits[7] <= 126


def test_factored_rungs_leave_narrow_tiles_alone(monkeypatch):
    """max_bin=63 (every tile 128 lanes) and nibble-packed bins: no
    rung in force, the gauge is empty and the lowered tree program is the
    program of the strips ladder alone, letter for letter.  max_bin=255:
    where the rungs reach the frontier's width no strip kernel is traced;
    where they do not, the strips keep the passes above the last rung."""
    import jax
    import lightgbm_tpu as lgb
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.ops import histogram as H
    from lightgbm_tpu.telemetry import TELEMETRY

    assert H.factored_rungs(255) == H.factored_rungs(129) \
        == H.FACTORED_RUNGS
    assert H.factored_rungs(128) == H.factored_rungs(63) == ()
    assert H.factored_rungs(255, packed_groups=3) == ()

    rng = np.random.RandomState(3)
    X = rng.randn(1024, 6)
    y = (X[:, 0] + 0.4 * X[:, 1] > 0).astype(float)

    def tree_program(max_bin, leaves=255, **extra):
        TELEMETRY.configure("counters")
        TELEMETRY.reset()
        cfg = Config.from_params(dict(_fast_255(leaves), max_bin=max_bin,
                                      telemetry="counters", **extra))
        gr = GBDT(cfg, lgb.Dataset(X, label=y).construct(cfg)).grower
        gauge = TELEMETRY.gauges()["grower.hist_factored_rungs"]
        n = gr.n_padded
        f32 = jax.ShapeDtypeStruct((n,), np.float32)
        text = jax.jit(gr._train_tree_impl).lower(
            f32, f32, f32, np.ones(gr.num_features, bool), None, gr.bins,
            gr.binsT, gr._row_valid, jax.random.PRNGKey(0)).as_text()
        return gauge, text, gr

    def kernels(text):
        """Fused histogram functions defined in the program, by kernel:
        one a strip count, one a rung (interpret mode drops the pinned
        ``name=``; tests/test_phase_trace.py holds those)."""
        found = re.findall(r"func\.func private @compute_group_histograms_"
                           r"fused_(tiled|factored)(?:_\d+)?\(", text)
        return {name: found.count(name) for name in set(found)}

    narrow = [(63, {}), (15, {"bin_packing": "4bit"})]
    try:
        texts = []
        for max_bin, extra in narrow:
            gauge, text, gr = tree_program(max_bin, **extra)
            assert gauge == "" and gr.plan.tier == "ladder"
            assert bool(gr.pack_P) == bool(extra)
            assert kernels(text) == {"tiled": 3}
            texts.append(text)
        gauge, text, _ = tree_program(255)
        assert gauge != ""
        assert kernels(text) == {"factored": len(H.FACTORED_RUNGS)}
        _, text, _ = tree_program(255, leaves=100)      # frontier 99
        # one ladder (PR 36): past the 64-slot rung only the strips of
        # 84 and 99 slots can be taken, the one-strip kernel is not traced
        assert kernels(text) == {"tiled": 2, "factored": 5}
        # with no rung in the table at all, the narrow programs are the same
        monkeypatch.setattr(H, "FACTORED_RUNGS", ())
        for (max_bin, extra), text in zip(narrow, texts):
            assert tree_program(max_bin, **extra)[1] == text
    finally:
        TELEMETRY.configure("off")
        TELEMETRY.reset()
