"""REAL-CHIP Pallas kernel parity — the analog of the reference's
GPU_DEBUG_COMPARE CPU-vs-GPU histogram comparator
(gpu_tree_learner.cpp:1020-1044).  The interpret-mode tests in
test_histogram_kernel.py pin kernel SEMANTICS on CPU; these pin the
Mosaic-compiled numerics on actual TPU hardware.  Skipped on CPU CI;
run manually on a chip (`LGBM_TPU_ONCHIP=1 pytest tests/test_tpu_onchip.py`
— the env var stops conftest from forcing the CPU backend); last
recorded run in PARITY.md.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from lightgbm_tpu.backend import on_tpu

if not on_tpu():
    pytest.skip("needs a real TPU chip", allow_module_level=True)

from lightgbm_tpu.ops.histogram import (  # noqa: E402
    compute_group_histograms, compute_group_histograms_fused,
    compute_group_histograms_pallas, precompute_bin_onehot,
    quantize_gradients)
from lightgbm_tpu.ops.partition import (apply_route_table,  # noqa: E402
                                        build_route_table)


@pytest.fixture(scope="module")
def case():
    rng = np.random.RandomState(0)
    N, G, B, L = 8192, 12, 63, 31
    bins = jnp.asarray(rng.randint(0, B, (N, G)).astype(np.uint8))
    grad = jnp.asarray(rng.randn(N).astype(np.float32))
    hess = jnp.asarray(np.abs(rng.randn(N)).astype(np.float32))
    cnt = jnp.asarray((rng.rand(N) > 0.2).astype(np.float32))
    leaf = jnp.asarray(rng.randint(-1, L, N).astype(np.int32))
    ref = compute_group_histograms(bins, grad, hess, cnt, leaf,
                                   num_leaves=L, max_group_bin=B,
                                   compute_dtype="float32", chunk=8192)
    return bins, grad, hess, cnt, leaf, ref, (N, G, B, L)


def _close(ref, got, tol=5e-3):
    scale = float(jnp.max(jnp.abs(ref))) + 1.0
    return float(jnp.max(jnp.abs(ref - got))) / scale < tol


def test_onchip_pallas_expansion_kernel(case):
    bins, grad, hess, cnt, leaf, ref, (N, G, B, L) = case
    got = compute_group_histograms_pallas(
        bins, grad, hess, cnt, leaf, num_leaves=L, max_group_bin=B,
        block=1024)
    assert _close(ref, got)
    # count channel exact (0/1 weights are bf16-exact)
    assert float(jnp.max(jnp.abs(ref[..., 2] - got[..., 2]))) == 0.0


def test_onchip_fused_route_hist(case):
    """Fused kernel on chip: routing BIT-IDENTICAL to the XLA router,
    histogram within bf16 operand tolerance."""
    bins, grad, hess, cnt, leaf, ref, (N, G, B, L) = case
    rng = np.random.RandomState(1)
    sm = np.zeros(L, bool)
    sm[:6] = True
    tab = build_route_table(
        jnp.asarray(sm),
        jnp.asarray(rng.randint(0, G, L).astype(np.int32)),
        jnp.zeros(L, jnp.int32), jnp.full(L, B, jnp.int32),
        jnp.zeros(L, jnp.int32), jnp.full(L, B - 1, jnp.int32),
        jnp.asarray(np.array([0, 1] * 15 + [0], bool)),
        jnp.asarray(rng.randint(0, B, L).astype(np.int32)),
        jnp.asarray(rng.rand(L) > 0.5),
        jnp.asarray(rng.randint(0, 3, L).astype(np.int32)),
        jnp.asarray(rng.randint(0, 4, L).astype(np.int32)),
        jnp.full(L, B, jnp.int32),
        jnp.asarray(rng.rand(L, B) > 0.5),
        jnp.asarray((np.arange(L) + 40).astype(np.int32)))
    want_leaf = apply_route_table(bins, leaf, tab)
    want = compute_group_histograms(
        bins, grad, hess, cnt, want_leaf, num_leaves=128,
        max_group_bin=B, compute_dtype="float32", chunk=8192)

    ohb = precompute_bin_onehot(bins, max_group_bin=B)
    wT = jnp.stack([grad, hess, cnt], axis=0)
    slots = jnp.arange(42, dtype=jnp.int32)
    got_hist, got_leaf = compute_group_histograms_fused(
        ohb, jnp.asarray(np.asarray(bins).T), wT, leaf, tab,
        slots, max_group_bin=B, block=1024, strips=1)
    np.testing.assert_array_equal(np.asarray(got_leaf),
                                  np.asarray(want_leaf))
    assert _close(want[:42], got_hist)


def test_onchip_fused_tiled_kernel(case):
    """Fused route + tiled-iota kernel — the kernel the DEFAULT
    training path executes on 128-lane tiles (the ladder's strips).
    Routing bit-identical to the XLA router; int32 accumulation is
    exact, so the histogram is the XLA formulation's sum of the int8
    levels after routing, to the bit."""
    from lightgbm_tpu.ops.histogram import \
        compute_group_histograms_fused_tiled
    bins, grad, hess, cnt, leaf, ref, (N, G, B, L) = case
    rng = np.random.RandomState(1)
    sm = np.zeros(L, bool)
    sm[:6] = True
    tab = build_route_table(
        jnp.asarray(sm),
        jnp.asarray(rng.randint(0, G, L).astype(np.int32)),
        jnp.zeros(L, jnp.int32), jnp.full(L, B, jnp.int32),
        jnp.zeros(L, jnp.int32), jnp.full(L, B - 1, jnp.int32),
        jnp.asarray(np.array([0, 1] * 15 + [0], bool)),
        jnp.asarray(rng.randint(0, B, L).astype(np.int32)),
        jnp.asarray(rng.rand(L) > 0.5),
        jnp.asarray(rng.randint(0, 3, L).astype(np.int32)),
        jnp.asarray(rng.randint(0, 4, L).astype(np.int32)),
        jnp.full(L, B, jnp.int32),
        jnp.asarray(rng.rand(L, B) > 0.5),
        jnp.asarray((np.arange(L) + 40).astype(np.int32)))
    want_leaf = apply_route_table(bins, leaf, tab)
    wq, scales = quantize_gradients(grad, hess, cnt)
    slots = jnp.arange(42, dtype=jnp.int32)
    # the levels (|q| <= 127) are exact in bf16 and their sums over
    # 8192 rows in float32
    wf = wq.astype(jnp.float32)
    want = compute_group_histograms(
        bins, wf[:, 0], wf[:, 1], wf[:, 2], want_leaf, num_leaves=128,
        max_group_bin=B, compute_dtype="float32", chunk=8192,
        slots=slots) * scales[None, None, None, :]
    for strips in (1, 2):
        s = jnp.arange(42 * strips, dtype=jnp.int32)
        got_hist, got_leaf = compute_group_histograms_fused_tiled(
            jnp.asarray(np.asarray(bins).T), wq.T, scales, leaf, tab, s,
            max_group_bin=B, block=2048, strips=strips)
        np.testing.assert_array_equal(np.asarray(got_leaf),
                                      np.asarray(want_leaf),
                                      err_msg=str(strips))
        np.testing.assert_array_equal(np.asarray(want),
                                      np.asarray(got_hist)[:42],
                                      err_msg=str(strips))


def test_onchip_route_apply_tiled(case):
    """Pallas exit-route kernel (the r5 DEFAULT tree-exit path):
    (new_leaf, row_value) bit-identical to the XLA apply_route_table
    on chip."""
    from lightgbm_tpu.ops.histogram import route_apply_tiled
    bins, grad, hess, cnt, leaf, ref, (N, G, B, L) = case
    rng = np.random.RandomState(2)
    sm = np.zeros(L, bool)
    sm[:8] = True
    tab = build_route_table(
        jnp.asarray(sm),
        jnp.asarray(rng.randint(0, G, L).astype(np.int32)),
        jnp.zeros(L, jnp.int32), jnp.full(L, B, jnp.int32),
        jnp.zeros(L, jnp.int32), jnp.full(L, B - 1, jnp.int32),
        jnp.asarray(np.array([0, 1] * 15 + [1], bool)),
        jnp.asarray(rng.randint(0, B, L).astype(np.int32)),
        jnp.asarray(rng.rand(L) > 0.5),
        jnp.asarray(rng.randint(0, 3, L).astype(np.int32)),
        jnp.asarray(rng.randint(0, 4, L).astype(np.int32)),
        jnp.full(L, B, jnp.int32),
        jnp.asarray(rng.rand(L, B) > 0.5),
        jnp.asarray((np.arange(L) + 40).astype(np.int32)))
    values = jnp.asarray(rng.randn(L).astype(np.float32) * 2)
    want_leaf, want_val = apply_route_table(bins, leaf, tab,
                                            values=values)
    got_leaf, got_val = route_apply_tiled(
        jnp.asarray(np.asarray(bins).T), leaf, tab, values, block=2048)
    np.testing.assert_array_equal(np.asarray(got_leaf),
                                  np.asarray(want_leaf))
    np.testing.assert_array_equal(np.asarray(got_val),
                                  np.asarray(want_val))


def _factored_onchip_inputs(N, G, L, leaves):
    """A table, leaf ids below ``leaves``, and a route table that moves
    six of them to leaves ``leaves``..: the fused kernels' inputs."""
    rng = np.random.RandomState(2)
    B = 255
    binsT = jnp.asarray(rng.randint(0, B, (G, N)).astype(np.uint8))
    leaf = jnp.asarray(rng.randint(-1, leaves, N).astype(np.int32))
    wq, scales = quantize_gradients(
        jnp.asarray(rng.randn(N).astype(np.float32)),
        jnp.asarray(np.abs(rng.randn(N)).astype(np.float32)),
        jnp.asarray((rng.rand(N) > 0.2).astype(np.float32)))
    sm = np.zeros(L, bool)
    sm[:6] = True
    tab = build_route_table(
        jnp.asarray(sm),
        jnp.asarray(rng.randint(0, G, L).astype(np.int32)),
        jnp.zeros(L, jnp.int32), jnp.full(L, B, jnp.int32),
        jnp.zeros(L, jnp.int32), jnp.full(L, B - 1, jnp.int32),
        jnp.asarray(np.array([0, 1] * (L // 2), bool)),
        jnp.asarray(rng.randint(0, B, L).astype(np.int32)),
        jnp.asarray(rng.rand(L) > 0.5),
        jnp.asarray(rng.randint(0, 3, L).astype(np.int32)),
        jnp.asarray(rng.randint(0, 4, L).astype(np.int32)),
        jnp.full(L, B, jnp.int32),
        jnp.asarray(rng.rand(L, B) > 0.5),
        jnp.asarray((np.arange(L) + leaves).astype(np.int32) % L))
    order = rng.permutation(leaves).astype(np.int32)
    return (binsT, wq.T, scales, leaf, tab), order


def test_onchip_fused_factored_kernel():
    """Every factored rung (ops/histogram.py FACTORED_RUNGS) against the
    fused tiled kernel on the strips its pass had, 255 bins: the same
    leaf ids and the same histogram to the bit.  The rungs build their
    int8 operands four rows to a 32-bit word (pltpu.bitcast), whose byte
    order only the chip can pin.  The two wide rungs run at the 67
    groups and the row block of the benchmark's cells: their
    accumulators (13 and 26 MB there) live in VMEM, and whether the
    chip's compiler grants that no interpreter can say."""
    from lightgbm_tpu.ops.histogram import (
        FACTORED_RUNGS, PACKED_STRIP,
        compute_group_histograms_fused_factored,
        compute_group_histograms_fused_tiled)
    narrow = _factored_onchip_inputs(16384, 13, 40, 36)
    wide = _factored_onchip_inputs(16384, 67, 160, 140)
    for k_cap, a, _ in FACTORED_RUNGS:
        args, order = wide if k_cap > 32 else narrow
        slots = np.full(126, -1, np.int32)
        slots[:k_cap] = order[:k_cap]
        if k_cap > 2:
            slots[1] = -1
        slots = jnp.asarray(slots)
        want, want_leaf = compute_group_histograms_fused_tiled(
            *args, slots, max_group_bin=255, block=2048,
            strips=-(-k_cap // PACKED_STRIP))
        for dequantize in (True, False) if k_cap > 32 else (True,):
            got, got_leaf = compute_group_histograms_fused_factored(
                *args, slots, max_group_bin=255, block=4096, k_cap=k_cap,
                a=a, dequantize=dequantize)
            np.testing.assert_array_equal(
                np.asarray(got_leaf), np.asarray(want_leaf),
                err_msg=str(k_cap))
            if not dequantize:
                assert got.dtype == jnp.int32
                got = got.astype(jnp.float32) * args[2]
            np.testing.assert_array_equal(np.asarray(got),
                                          np.asarray(want)[:k_cap],
                                          err_msg=str(k_cap))
            assert float(jnp.abs(got).sum()) > 0


@pytest.mark.parametrize("case", [
    dict(R=252, F=40, B=255, seed=21, missing=0),
    dict(R=168, F=67, B=255, seed=22, int_counts=True, count_scale=2),
    dict(R=84, F=24, B=255, seed=23, monotone=True, tight=True),
    dict(R=31, F=28, B=63, seed=24, exact=True)],
    ids=["one_scan_r252", "int32_counts_above_2_to_24", "two_scans_monotone",
         "exact_sums"])
def test_onchip_fused_split_finder(case):
    """The fused finder (ops/split_kernel.py) compiled by Mosaic against
    the XLA form on the chip, at the leaf rows and the 255 bins of the
    benchmark's cells: its prefix sums are float32 products with a 0/1
    matrix on the MXU (``Precision.HIGHEST``) and its int32 counts two
    16-bit limbs, and only the chip says whether those are the sums
    (tests/test_split_kernel.py pins the rest in interpret mode).  The
    tolerance is the XLA form's own distance from float64 here: its
    two-level ``reduce-window`` prefix sums read 3x further from it than
    the kernel's (7.6e-4 against 2.5e-4 at sums of 2,770: my chip run,
    PR 34), and a gain divides by a small hessian sum."""
    from test_split_kernel import (CFG, assert_same_splits, make_case,
                                   run_both)
    exact = case.pop("exact", False)
    args = make_case(exact=exact, **case)
    cfg = CFG
    if "hist_count" in args:
        args["hist_count"] = args["hist_count"] * ((1 << 24) + 1)
        args["num_data"] = args["hist_count"][:, 0].sum(1).astype(np.int32)
        args["hist_count"][:, :, 0] += args["num_data"][:, None] \
            - args["hist_count"].sum(2)
        assert args["hist_count"].max() > 1 << 26
        cfg = {**CFG, "min_data_in_leaf": 5.0 * (1 << 24)}
    ref, got = run_both(args, cfg=cfg, interpret=False)
    assert_same_splits(ref, got, exact_choice=exact, tol=3e-4, min_same=0.85)
    if "hist_count" in args:
        assert np.array_equal(np.asarray(ref.left_count),
                              np.asarray(got.left_count))
