"""Histogram construction — the hot loop of the framework.

TPU-native replacement for DenseBin::ConstructHistogram /
OrderedSparseBin::ConstructHistogram and the OpenCL histogram kernels
(reference: src/io/dense_bin.hpp:66-131, src/treelearner/ocl/histogram256.cl).

Design: instead of per-leaf gather + scatter-add with atomics, ALL
active leaves' histograms are built in one data pass as a single MXU
matmul per row-chunk:

    hist[(l,c), (g,b)] = sum_r onehot(leaf[r]==l) * w_c[r] * onehot(bin[r,g]==b)

i.e. ``(3L x C) @ (C x G*B)`` with both one-hot operands generated
on-the-fly per chunk.  The leaf dimension rides the MXU's systolic rows
(padding that a per-leaf formulation would waste), so histograms for up
to ~128 leaves cost the same as one leaf.  This also deletes the
reference's smaller/larger-leaf scheduling and histogram-subtraction
machinery (serial_tree_learner.cpp:505-507) — every leaf is always
computed directly from global data, and FixHistogram-style default-bin
reconstruction (dataset.cpp:776-795) is only needed for EFB bundles.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .partition import (MISSING_NAN, MISSING_ZERO, ROUTE_FIXED_COLS,
                        packed_select_params)

# ---------------------------------------------------------------------------
# Sub-byte-packed bin-matrix support (lightgbm_tpu/packing.py layout):
# the storage matrix carries the first ``C`` logical groups four-per-
# byte (2-bit crumbs), groups ``C..P`` two-per-byte (group C+2j in the
# low nibble of its storage byte, C+2j+1 in the high nibble), followed
# by one byte per wide group.  Every kernel that reads bins takes a
# static ``packed_groups`` PACK SPEC (``packing.pack_spec(P, C)`` —
# numerically the plain packed-group count when there is no crumb
# section; 0 = legacy 8-bit matrix, which keeps the EXACT pre-packing
# lowering) and widens crumbs/nibbles in-register — shift+mask VPU
# ops — so HBM only ever streams the packed bytes.
# ---------------------------------------------------------------------------


# layout arithmetic lives in packing.py (the one home for the packed
# layout); re-exported here so kernel call sites and tests use one name
from ..packing import (logical_groups, packed_bytes, spec_crumb,  # noqa: F401
                       spec_packed)
from ..packing import storage_cols as packed_cols  # noqa: F401


def unpack_bins_cols(bins: jax.Array, *, num_groups: int,
                     packed_groups: int) -> jax.Array:
    """(n, cols) storage block -> (n, G) logical bins (XLA form — the
    Pallas kernels widen per-row/per-tile instead; see _bin_row_T).
    ``packed_groups`` is the static pack spec; identity when 0."""
    if packed_groups == 0:
        return bins
    P, C = spec_packed(packed_groups), spec_crumb(packed_groups)
    cb = (C + 3) // 4
    pb = packed_bytes(packed_groups)
    parts = []
    if C:
        ck = bins[:, :cb].astype(jnp.int32)
        planes = [(ck >> (2 * k)) & 3 for k in range(4)]
        parts.append(jnp.stack(planes, axis=2).reshape(
            bins.shape[0], 4 * cb)[:, :C])
    if P > C:
        pk = bins[:, cb:pb].astype(jnp.int32)
        lo = pk & 15
        hi = (pk >> 4) & 15
        parts.append(jnp.stack([lo, hi], axis=2).reshape(
            bins.shape[0], 2 * (pb - cb))[:, :P - C])
    wide = bins[:, pb:].astype(jnp.int32)
    if wide.shape[1]:
        parts.append(wide)
    out = jnp.concatenate(parts, axis=1) if len(parts) > 1 else parts[0]
    return out.astype(bins.dtype)


def _bin_row_T(binb, g: int, packed_groups: int):
    """Logical group ``g``'s (1, C) bin row out of a TRANSPOSED
    (storage_rows, C) int32 block — a static slice plus a static
    crumb/nibble shift/mask; the Mosaic-friendly per-group access the
    tiled kernels are built from.  ``packed_groups`` is the static
    pack spec."""
    P, C = spec_packed(packed_groups), spec_crumb(packed_groups)
    if packed_groups and g < C:
        r = binb[g // 4:g // 4 + 1, :]
        sh = 2 * (g % 4)
        if sh:
            r = r >> sh
        return r & 3
    if packed_groups and g < P:
        cb = (C + 3) // 4
        r = binb[cb + (g - C) // 2:cb + (g - C) // 2 + 1, :]
        if (g - C) % 2:
            r = r >> 4
        return r & 15
    j = g if not packed_groups \
        else packed_bytes(packed_groups) + (g - P)
    return binb[j:j + 1, :]


def _pick_chunk(n: int, num_groups: int, max_group_bin: int,
                itemsize: int, target_bytes: int = 1 << 26,
                min_chunk: int = 4096) -> int:
    """Row-chunk size bounding the materialized one-hot to ~64 MB.

    ``min_chunk`` also sets the padding granularity when the grower
    calls this: 8192 on real TPU (every Pallas block size up to 8192 —
    the tiled-iota kernels' preferred block — must divide the padded
    row count), 1024 elsewhere — a 569-row test dataset padded to
    8192 rows pays 14x the row work on the CPU backend for nothing.
    The signature default (4096) only serves the standalone XLA
    histogram path's internal chunking, where no padding invariant
    rides on it."""
    per_row = max(num_groups * max_group_bin * itemsize, 1)
    chunk = max(min_chunk, min(n, target_bytes // per_row))
    return int(max(min_chunk, (chunk // min_chunk) * min_chunk))


@functools.partial(
    jax.jit,
    static_argnames=("num_leaves", "max_group_bin", "compute_dtype",
                     "chunk", "packed_groups"))
def compute_group_histograms(bins: jax.Array, grad: jax.Array,
                             hess: jax.Array, counts: jax.Array,
                             leaf_id: jax.Array, *, num_leaves: int,
                             max_group_bin: int,
                             compute_dtype: str = "float32",
                             chunk: Optional[int] = None,
                             slots: Optional[jax.Array] = None,
                             packed_groups: int = 0) -> jax.Array:
    """Build per-leaf histograms for every feature group in one pass.

    Args:
      bins: (N, G) uint8 packed group-bin matrix (N padded to a chunk
        multiple; padded rows must carry ``leaf_id < 0``).
      grad, hess: (N,) float32 gradients/hessians (zero for out-of-bag
        or padded rows).
      counts: (N,) float32 1.0 for in-bag rows else 0.0 (the ``cnt``
        histogram channel; bagging masks flow through here).
      leaf_id: (N,) int32 current leaf of each row; negative = ignore.
      num_leaves: static L — number of leaf slots (ignored when
        ``slots`` is given).
      max_group_bin: static B — bins per group column.
      slots: optional (W,) int32 — restrict to these leaf ids (negative
        entries match nothing); output leaf axis then follows ``slots``
        order.  This is the frontier path: only newly created leaves
        are histogrammed, their siblings come from parent subtraction.

    Distributed note: under a row-sharded mesh, call this INSIDE
    shard_map on the local shard (learner/grower.py
    _hist_xla_rowsharded) — GSPMD propagation through the chunk-scan
    reshape produces involuntary full rematerializations (row-scale
    all-gathers) otherwise.

    Returns:
      (L|W, G, B, 3) float32: sum_grad, sum_hess, count per
      (leaf, group, bin).
    """
    n, cols = bins.shape
    num_groups = logical_groups(cols, packed_groups) if packed_groups \
        else cols
    cdt = jnp.dtype(compute_dtype)
    if chunk is None:
        chunk = _pick_chunk(n, num_groups, max_group_bin, cdt.itemsize)
    if n % chunk != 0:
        raise ValueError(f"N ({n}) must be padded to a multiple of chunk ({chunk})")
    num_chunks = n // chunk

    if slots is None:
        leaf_iota = jnp.arange(num_leaves, dtype=jnp.int32)
    else:
        # negative slot entries must match nothing, including the
        # negative leaf ids of padded rows
        leaf_iota = jnp.where(slots >= 0, slots, -2)
        num_leaves = slots.shape[0]
    bin_iota = jnp.arange(max_group_bin, dtype=jnp.int32)

    def body(acc, xs):
        bins_c, grad_c, hess_c, cnt_c, leaf_c = xs
        # nibble-packed matrix: the chunk stays packed in HBM and
        # widens here in registers (elementwise shift/mask — no
        # scatter, no dtype widening past int32; pinned by the
        # compact-bins jaxpr test)
        bins_c = unpack_bins_cols(bins_c, num_groups=num_groups,
                                  packed_groups=packed_groups)
        # (C, L) leaf one-hot; negative leaf ids match nothing
        ohl = (leaf_c[:, None] == leaf_iota[None, :]).astype(cdt)
        w = jnp.stack([grad_c, hess_c, cnt_c], axis=1).astype(cdt)  # (C, 3)
        lhs = (ohl[:, :, None] * w[:, None, :]).reshape(chunk, num_leaves * 3)
        # (C, G, B) bin one-hot, generated on the fly; contracted as ONE
        # (3L x C) @ (C x G*B) dot — a grouped einsum would make XLA
        # re-read the (C, 3L) operand once per group (G x the HBM
        # traffic, measured ~10x slower on v5e)
        ohb = (bins_c.astype(jnp.int32)[:, :, None]
               == bin_iota[None, None, :]).astype(cdt)
        rhs = ohb.reshape(chunk, num_groups * max_group_bin)
        contrib = jnp.einsum(
            "cm,cx->mx", lhs, rhs,
            preferred_element_type=jnp.float32)
        return acc + contrib.reshape(num_leaves * 3, num_groups,
                                     max_group_bin), None

    init = jnp.zeros((num_leaves * 3, num_groups, max_group_bin),
                     dtype=jnp.float32)
    xs = (bins.reshape(num_chunks, chunk, cols),
          grad.reshape(num_chunks, chunk),
          hess.reshape(num_chunks, chunk),
          counts.reshape(num_chunks, chunk),
          leaf_id.reshape(num_chunks, chunk))
    acc, _ = jax.lax.scan(body, init, xs)
    # (3L, G, B) -> (L, G, B, 3)
    hist = acc.reshape(num_leaves, 3, num_groups, max_group_bin)
    return jnp.transpose(hist, (0, 2, 3, 1))


def _hist_kernel_body(bins_ref, w_ref, leaf_ref, emat_ref, bcol_ref,
                      slots_ref, out_ref, *, num_leaves, max_group_bin,
                      m_pad):
    """Pallas TPU kernel: one row-block's histogram contribution.

    The analog of the OpenCL workgroup kernel
    (reference src/treelearner/ocl/histogram256.cl:345-824), redesigned
    for the MXU: both one-hot operands are generated in VMEM (never
    touching HBM — the XLA fallback materializes them) and the
    (3L, G*B) accumulator lives in VMEM across the whole grid, so HBM
    traffic is just the packed bin matrix + weights, ~17 bytes/row.

    Mosaic notes: no vector reshapes (unsupported).  The expensive
    "repeat each group's bin B times along lanes" broadcast is done on
    the MXU as ``bins @ E`` with a constant (G, G*B) 0/1 expansion
    matrix (bin values <= 255 are exact in bf16), followed by a single
    full-lane-width compare against the constant per-column bin index —
    the VPU does ~2 ops/element instead of ~6 at half lane width.
    The (C, 3L) leaf one-hot uses channel-major layout (three
    lane-aligned strips sharing one (C, m_leaf) one-hot).
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    c = bins_ref.shape[0]
    m_leaf = m_pad // 3

    leaf = leaf_ref[:]                                   # (C, 1) int32
    w = w_ref[:]                                         # (C, 3) f32
    ohl = leaf == slots_ref[0:1, :]                      # (C, m_leaf)
    zero = jnp.zeros((), jnp.float32)
    lhs = jnp.concatenate(
        [jnp.where(ohl, w[:, 0:1], zero),
         jnp.where(ohl, w[:, 1:2], zero),
         jnp.where(ohl, w[:, 2:3], zero)], axis=1).astype(jnp.bfloat16)

    binb = bins_ref[:].astype(jnp.int32).astype(jnp.bfloat16)  # exact <=255
    rep = jax.lax.dot_general(                           # (C, G*B)
        binb, emat_ref[:], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    ohb = (rep == bcol_ref[0:1, :]).astype(jnp.bfloat16)
    out_ref[:] += jax.lax.dot_general(
        lhs, ohb, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _slot_prep(num_leaves: int, slots: Optional[jax.Array]):
    """Shared leaf-strip padding + slot-row encoding for every Pallas
    histogram wrapper.  The leaf axis pads to a 128-lane multiple so the
    channel-major lhs splits into lane-aligned strips; -2 padding in
    the slot row matches neither real leaves nor padded rows (-1)."""
    if slots is not None:
        num_leaves = slots.shape[0]
    m_leaf = max(128, ((num_leaves + 127) // 128) * 128)
    if slots is None:
        slot_row = jnp.arange(m_leaf, dtype=jnp.int32)[None, :]
    else:
        slot_row = jnp.full(m_leaf, -2, jnp.int32) \
            .at[:num_leaves].set(jnp.where(slots >= 0, slots, -2))[None, :]
    return num_leaves, m_leaf, 3 * m_leaf, slot_row


def _run_hist_kernel(kern, bins, w, leaf_id, const_inputs, *, name, block,
                     m_leaf, m_pad, num_leaves, max_group_bin, out_dtype,
                     interpret):
    """Shared pallas_call plumbing: row-blocked (bins, w, leaf) inputs,
    VMEM-resident constants, one (m_pad, G*B) accumulator; returns the
    (L, G, B, 3) histogram view.  ``name`` pins the kernel's name in
    a device trace: the jitted wrapper's, which is what the lowering
    derived before and what perfbench/metrics/*.json match."""
    n, num_groups = bins.shape
    if n % block != 0:
        raise ValueError(f"N ({n}) must be a multiple of block ({block})")
    gb = num_groups * max_group_bin
    consts = [jnp.asarray(c) for c in const_inputs]
    out = pl.pallas_call(
        kern,
        grid=(n // block,),
        in_specs=[
            pl.BlockSpec((block, num_groups), lambda i: (i, 0)),
            pl.BlockSpec((block, w.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec((block, 1), lambda i: (i, 0)),
        ] + [pl.BlockSpec(c.shape, lambda i: (0, 0)) for c in consts],
        out_specs=pl.BlockSpec((m_pad, gb), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((m_pad, gb), out_dtype),
        interpret=interpret, name=name,
    )(bins, w, leaf_id[:, None], *consts)
    # (3*m_leaf, G*B) channel-major -> (L, G, B, 3)
    hist = out.reshape(3, m_leaf, num_groups, max_group_bin)[:, :num_leaves]
    return jnp.transpose(hist, (1, 2, 3, 0))


#: int32 histogram-accumulator headroom: quantized weights are int8
#: (|q| <= 127), so a bin that swallowed every row accumulates at most
#: N * 127 — the bound every quantized-path selector shares.
QUANT_WEIGHT_MAX = 127

#: rows a segment: the kernels accumulate int32 over at most this many
#: rows and write one accumulator a segment (the grower folds them
#: exactly, parallel/collectives.py ``exchange_int_histograms``).  The
#: most rows ``quant_rows_ok`` admits, as a power of two, so that every
#: row block of the kernels divides it: 2^24
QUANT_SEGMENT_ROWS = 1 << (
    ((2 ** 31 - 1) // QUANT_WEIGHT_MAX).bit_length() - 1)


def quant_rows_ok(n_rows: int) -> bool:
    """True when ``n_rows`` rows can NEVER overflow one int32 quantized
    histogram accumulator (``n_rows * 127 < 2^31``, ~16.9M rows): asked
    of a row segment, and of a mesh's global rows by the exact sum."""
    return int(n_rows) * QUANT_WEIGHT_MAX < 2 ** 31


def check_quant_rows(n_rows: int, what: str = "quantized histogram"
                     ) -> None:
    """Loud kernel-plan-time form of the :func:`quantize_gradients`
    caller contract: raises when ``n_rows`` could overflow the int32
    accumulator.  What the kernel plan (ops/hist_plan.py) asks of the
    segment it plans, so the bound lives in ONE place next to the
    kernel it protects."""
    if not quant_rows_ok(n_rows):
        raise ValueError(
            f"{what}: {int(n_rows)} rows can overflow the int32 "
            f"histogram accumulator (requires rows * "
            f"{QUANT_WEIGHT_MAX} < 2^31, i.e. <= "
            f"{(2 ** 31 - 1) // QUANT_WEIGHT_MAX} rows a segment); "
            "use hist_precision=f32 or shard the rows")


def quant_row_segments(n_rows: int):
    """``(segments, rows a segment)`` of a device's ``n_rows`` rows on
    the int8 path: ``QUANT_SEGMENT_ROWS`` rows each (the last holds what
    is left), one segment of every row up to that many."""
    seg = min(int(n_rows), QUANT_SEGMENT_ROWS)
    check_quant_rows(seg, what="a row segment of the int8 histogram")
    return max(1, -(-int(n_rows) // QUANT_SEGMENT_ROWS)), seg


def _segment_blocks(n: int, block: int, segment_rows: int,
                    dequantize: bool):
    """``(segments, row blocks a segment)`` of a kernel's ``n`` rows;
    ``segment_rows`` 0, or at least ``n``, is one segment: ``(1, 0)``,
    the kernel as it always lowered."""
    if not 0 < segment_rows < n:
        return 1, 0
    if segment_rows % block:
        raise ValueError(f"segment_rows ({segment_rows}) must be a "
                         f"multiple of block ({block})")
    if dequantize:
        raise ValueError("row segments come back as int32 accumulators: "
                         "dequantize=False")
    return -(-n // segment_rows), segment_rows // block


def _pipelined_acc_params(pipelined: bool) -> dict:
    """``pallas_call`` keywords of a pass whose accumulator block
    changes along the grid (with the group chunk, with the row segment):
    it is then a pipelined block of the kernel's scoped VMEM, in its two
    buffers, and no longer the whole-array output that XLA keeps
    outside it."""
    if not pipelined:
        return {}
    from jax.experimental.pallas import tpu as pltpu
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=CHUNK_VMEM_LIMIT)}


def quantize_gradients(grad: jax.Array, hess: jax.Array, counts: jax.Array,
                       key=None):
    """Per-channel symmetric int8 quantization (one scale per tree).
    Returns ((N, 3) int32 quantized weights, (3,) f32 scales).

    With ``key``, gradients and hessians round STOCHASTICALLY — the
    v4 quantized-training recipe (arXiv 2207.09682: rounding to the
    nearer level zeroes the long tail of small gradients whenever the
    distribution is skewed, and stochastic rounding restores the
    signal in expectation).  Measured on the MS-LTR lambdarank bench
    shape: deterministic rounding costs 0.31 held-out NDCG@10 vs the
    unquantized path (0.33 vs 0.64) because most pairwise lambdas are
    orders below the per-tree max; see tests/test_engine.py
    test_lambdarank_quantized_stochastic."""
    s_g = jnp.maximum(jnp.max(jnp.abs(grad)) / 127.0, 1e-30)
    s_h = jnp.maximum(jnp.max(jnp.abs(hess)) / 127.0, 1e-30)
    if key is None:
        qg = jnp.round(grad / s_g)
        qh = jnp.round(hess / s_h)
    else:
        kg, kh = jax.random.split(key)

        def sround(x, k):
            # clip AFTER rounding: f32 division can put the max-|grad|
            # row a few ulp above 127, and rounding UP there would
            # wrap to -128 at the kernels' int8 cast (sign-flipping
            # the largest gradient)
            f = jnp.floor(x)
            r = f + (jax.random.uniform(k, x.shape) < (x - f))
            return jnp.clip(r, -127.0, 127.0)

        qg = sround(grad / s_g, kg)
        qh = sround(hess / s_h, kh)
    wq = jnp.stack([qg, qh, counts], axis=1).astype(jnp.int32)
    scales = jnp.stack([s_g, s_h, jnp.float32(1.0)])
    return wq, scales


@functools.lru_cache(maxsize=None)
def _expansion_consts(num_groups: int, max_group_bin: int):
    """Constant (G, G*B) 0/1 expansion matrix (bf16) and (1, G*B)
    per-column bin index (f32)."""
    g, b = num_groups, max_group_bin
    emat = np.zeros((g, g * b), dtype=np.float32)  # lint: disable=TRC001(static-shape constant table, never touches traced values)
    for gg in range(g):
        emat[gg, gg * b:(gg + 1) * b] = 1.0
    bcol = np.tile(np.arange(b, dtype=np.float32), g)[None, :]  # lint: disable=TRC001(static-shape constant table, never touches traced values)
    return emat.astype(jnp.bfloat16), bcol


@functools.partial(
    jax.jit,
    static_argnames=("num_leaves", "max_group_bin", "block", "interpret"))
def compute_group_histograms_pallas(bins: jax.Array, grad: jax.Array,
                                    hess: jax.Array, counts: jax.Array,
                                    leaf_id: jax.Array, *, num_leaves: int,
                                    max_group_bin: int, block: int = 1024,
                                    interpret: bool = False,
                                    slots: Optional[jax.Array] = None
                                    ) -> jax.Array:
    """Pallas-kernel histogram with the same contract as
    :func:`compute_group_histograms` (N must be a multiple of
    ``block``), including the ``slots`` frontier restriction.
    Single-device only — the distributed learners keep the XLA
    formulation so GSPMD can insert the reduce-scatter."""
    num_groups = bins.shape[1]
    num_leaves, m_leaf, m_pad, slot_row = _slot_prep(num_leaves, slots)
    w = jnp.stack([grad, hess, counts], axis=1).astype(jnp.float32)
    emat, bcol = _expansion_consts(num_groups, max_group_bin)
    kern = functools.partial(_hist_kernel_body, num_leaves=num_leaves,
                             max_group_bin=max_group_bin, m_pad=m_pad)
    return _run_hist_kernel(
        kern, bins, w, leaf_id, [emat, bcol, slot_row], block=block,
        name="compute_group_histograms_pallas",
        m_leaf=m_leaf, m_pad=m_pad, num_leaves=num_leaves,
        max_group_bin=max_group_bin, out_dtype=jnp.float32,
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=("max_group_bin",
                                             "packed_groups"))
def precompute_bin_onehot(bins: jax.Array, *, max_group_bin: int,
                          packed_groups: int = 0) -> jax.Array:
    """(N, G) uint8 -> (N, G*B) int8 bin one-hot, HBM-resident.

    The bin matrix never changes during training, so the one-hot RHS of
    the histogram matmul can be materialized once per dataset and
    streamed — deleting the per-round in-kernel expansion matmul +
    compare (the dominant non-MXU cost).  Costs N*G*B bytes of HBM;
    the grower gates usage on a memory budget and falls back to
    on-the-fly generation for datasets where it doesn't fit."""
    n = bins.shape[0]
    g = logical_groups(bins.shape[1], packed_groups) if packed_groups \
        else bins.shape[1]
    bins = unpack_bins_cols(bins, num_groups=g,
                            packed_groups=packed_groups)
    biota = jnp.arange(max_group_bin, dtype=jnp.int32)
    oh = bins.astype(jnp.int32)[:, :, None] == biota[None, None, :]
    return oh.reshape(n, g * max_group_bin).astype(jnp.int8)


@functools.partial(jax.jit,
                   static_argnames=("max_group_bin", "pack", "gbp_pad",
                                    "num_groups", "packed_groups"))
def _packed_onehot_chunk(bc: jax.Array, gsel_d: jax.Array,
                         bval_d: jax.Array, *, max_group_bin: int,
                         pack: int, gbp_pad: int, num_groups: int = 0,
                         packed_groups: int = 0) -> jax.Array:
    """One fixed-shape row chunk of the planar packing (jitted per
    CHUNK shape, not per dataset size — XLA's compile time for the
    whole-N single-program formulation grew ~linearly with N, hitting
    minutes at HIGGS scale)."""
    if packed_groups:
        bc = unpack_bins_cols(bc, num_groups=num_groups,
                              packed_groups=packed_groups)
    bits = 8 // pack
    acc = None
    for p in range(pack):
        take = bc[:, gsel_d[p]].astype(jnp.int32)
        plane = (take == bval_d[p][None, :]).astype(jnp.int8)
        term = plane * jnp.int8(1 << (p * bits))
        acc = term if acc is None else acc + term
    return acc


def precompute_bin_onehot_packed(bins: jax.Array, *, max_group_bin: int,
                                 pack: int,
                                 packed_groups: int = 0) -> jax.Array:
    """(N, G) uint8 -> (N, G*B/pack) int8 PLANAR sub-byte one-hot.

    ``pack`` one-hot columns share each byte: byte j of a row carries
    full-column ``p*GBp + j`` in bit-field p (GBp = G*B/pack, field
    width 8/pack bits — each field holds 0 or 1).  The histogram
    kernels widen the planes back in VMEM with shift+mask (int ops the
    VPU does natively — the sub-byte MXU operands Mosaic rejects are
    never needed) and run one dot per plane into a lane-aligned output
    slice.  This cuts the streamed one-hot's HBM footprint AND
    bandwidth pack-x: the 17.2 GB full one-hot of a HIGGS-scale
    (10.5M x 28 x 63) dataset becomes 4.3 GB at pack=4 — it fits a
    16 GB v5e with room for the training state.  G*B must divide by
    pack (the grower's auto-selection guarantees it).

    The returned plane width is padded up to a 128-lane multiple with
    zero bytes so every widened plane — and every per-plane output
    slice in the kernels — is tile-aligned (Mosaic rejects unaligned
    lane slices)."""
    n = bins.shape[0]
    g = logical_groups(bins.shape[1], packed_groups) if packed_groups \
        else bins.shape[1]
    gb = g * max_group_bin
    if gb % pack:
        raise ValueError(f"pack ({pack}) must divide G*B ({gb})")
    gbp = gb // pack
    gbp_pad = _round_up(gbp, 128)
    bits = 8 // pack
    # per-plane column maps: packed byte column j carries full one-hot
    # column p*gbp + j = (group, bin); padding columns match nothing.
    # (Plain gather/compare/add formulation — an earlier int8 einsum
    # over (chunk, pack, gbp) sent XLA's LLVM backend into a ~4-minute
    # compile at 10.5M rows.)
    jcols = np.arange(gbp_pad)
    gsel = np.zeros((pack, gbp_pad), np.int32)
    bval = np.full((pack, gbp_pad), -1, np.int32)
    for p in range(pack):
        full = p * gbp + jcols[:gbp]
        gsel[p, :gbp] = full // max_group_bin
        bval[p, :gbp] = full % max_group_bin
    del bits  # consumed inside the chunk kernel
    gsel_d = jnp.asarray(gsel)
    bval_d = jnp.asarray(bval)
    # row-chunked so the transient per-plane intermediates stay ~100 MB;
    # the loop runs HOST-side over device slices so the jitted program
    # has a fixed, dataset-size-independent shape, and each chunk is
    # written into ONE donated output buffer (materializing chunk parts
    # + a concatenate would double the multi-GB resident footprint)
    chunk = max(1, (1 << 27) // max(gb, 1))
    chunk = min(n, max(256, (chunk // 256) * 256))
    bins = jnp.asarray(bins)
    out = jnp.zeros((n, gbp_pad), jnp.int8)
    for i in range(0, n, chunk):
        bc = bins[i:i + chunk]
        take = bc.shape[0]
        if take < chunk:
            bc = jnp.pad(bc, ((0, chunk - take), (0, 0)))
        part = _packed_onehot_chunk(
            bc, gsel_d, bval_d, max_group_bin=max_group_bin, pack=pack,
            gbp_pad=gbp_pad, num_groups=g,
            packed_groups=packed_groups)
        if take < chunk:
            part = part[:take]
        out = _write_packed_chunk(out, part, i)
    return out


@functools.partial(jax.jit, donate_argnums=(0,))
def _write_packed_chunk(out: jax.Array, part: jax.Array,
                        start) -> jax.Array:
    return jax.lax.dynamic_update_slice(
        out, part, (jnp.asarray(start, jnp.int32), jnp.int32(0)))


def _unpack_ohb_planes(pk: jax.Array, pack: int):
    """(C, GBp) planar-packed int8 block -> list of ``pack`` (bf16
    plane, shift) pairs.  The plane holds values {0, 2^shift} —
    extraction is a SINGLE int8 AND per element (the full 0/1 widen
    costs 3 VPU ops per element: and, !=0, cast — measured as the pass
    bottleneck once the stream is packed).  The caller divides the
    2^shift factor out of the post-dot (m_pad, GBp) result, ~4 orders of
    magnitude fewer elements."""
    if pack == 1:
        return [(pk.astype(jnp.bfloat16), 0)]
    bits = 8 // pack
    return [((pk & jnp.int8(1 << (p * bits))).astype(jnp.bfloat16),
             p * bits) for p in range(pack)]


def _descale_contrib(contrib: jax.Array, shift: int) -> jax.Array:
    """Divide the 2^shift plane scaling out of a post-dot block (an
    exact f32 multiply)."""
    if shift == 0:
        return contrib
    return contrib * jnp.float32(1.0 / (1 << shift))


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _hist_kernel_body_pre(ohb_ref, w_ref, leaf_ref, slots_ref, out_ref, *,
                          m_pad, pack=1):
    """Streamed-one-hot kernel body: HBM traffic is the (C, G*B[/pack])
    one-hot block (prefetched by the Pallas pipeline while the MXU
    works), and the only compute is the lhs build + one dot per plane
    (sub-byte planes widened in VMEM, see
    precompute_bin_onehot_packed)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    leaf = leaf_ref[:]                                   # (C, 1) int32
    w = w_ref[:]                                         # (C, 3) f32
    ohl = leaf == slots_ref[0:1, :]                      # (C, m_leaf)
    zero = jnp.zeros((), jnp.float32)
    lhs = jnp.concatenate(
        [jnp.where(ohl, w[:, 0:1], zero),
         jnp.where(ohl, w[:, 1:2], zero),
         jnp.where(ohl, w[:, 2:3], zero)], axis=1).astype(jnp.bfloat16)
    gbp_pad = ohb_ref.shape[1]
    for p, (plane, sh) in enumerate(_unpack_ohb_planes(ohb_ref[:], pack)):
        contrib = _descale_contrib(jax.lax.dot_general(
            lhs, plane, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32), sh)
        if pack == 1:
            out_ref[:] += contrib
        else:
            out_ref[:, p * gbp_pad:(p + 1) * gbp_pad] += contrib


def _hist_kernel_body_pre_packed(ohb_ref, w_ref, leaf_ref, slots_ref,
                                 out_ref, *, strip, strips, pack=1):
    """Channel-packed kernel: the three weight channels share each
    128-lane tile (lane = c*strip + l within a tile) instead of
    occupying three separate tiles, cutting the dot's output rows — and
    its MXU time — 3x for the same slot count.  ``strips`` tiles cover
    up to strips*strip slots; with the frontier capped at 3*42 = 126
    this kernel serves EVERY round of tree growth (the reference's
    one-leaf-at-a-time learner has no analog — width adapts to the
    frontier the way its smaller/larger-leaf trick adapts to leaf
    sizes, serial_tree_learner.cpp:505-507).

    ``pack`` > 1: ohb_ref is the planar sub-byte one-hot
    (precompute_bin_onehot_packed, plane width pre-padded to a lane
    multiple); each widened plane dots into its own aligned
    plane-width slice of out_ref."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    c = leaf_ref.shape[0]
    m_pad = 128 * strips
    leaf = leaf_ref[:]                                   # (C, 1) int32
    w = w_ref[:]                                         # (C, 3) f32
    # slots_ref tiles each strip's slot ids three times per 128-lane
    # tile; lane -> channel is a boundary select on lane mod 128
    ohl = leaf == slots_ref[0:1, :]                      # (C, m_pad)
    lane = jax.lax.broadcasted_iota(jnp.int32, (c, m_pad), 1) % 128
    wl = jnp.where(lane < strip, w[:, 0:1],
                   jnp.where(lane < 2 * strip, w[:, 1:2], w[:, 2:3]))
    lhs = jnp.where(ohl, wl,
                    jnp.zeros((), jnp.float32)).astype(jnp.bfloat16)
    gbp_pad = ohb_ref.shape[1]
    planes = _unpack_ohb_planes(ohb_ref[:], pack)
    for p, (plane, sh) in enumerate(planes):
        contrib = _descale_contrib(jax.lax.dot_general(
            lhs, plane, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32), sh)
        if pack == 1:
            out_ref[:] += contrib
        else:
            out_ref[:, p * gbp_pad:(p + 1) * gbp_pad] += contrib


def _run_hist_kernel_pre(kern, ohb, w, leaf_id, slot_row, *, name, block,
                         m_pad, interpret, out_cols=None):
    """pallas_call plumbing for the streamed-one-hot bodies: the (N,
    G*B[/pack]) one-hot is row-blocked like the weights; output is the
    (m_pad, out_cols) VMEM accumulator (out_cols = pack * plane
    width for packed inputs, else the one-hot width)."""
    n, gbc = ohb.shape
    if out_cols is None:
        out_cols = gbc
    if n % block != 0:
        raise ValueError(f"N ({n}) must be a multiple of block ({block})")
    slot_row = jnp.asarray(slot_row)
    out = pl.pallas_call(
        kern,
        grid=(n // block,),
        in_specs=[
            pl.BlockSpec((block, gbc), lambda i: (i, 0)),
            pl.BlockSpec((block, w.shape[1]), lambda i: (i, 0)),
            pl.BlockSpec((block, 1), lambda i: (i, 0)),
            pl.BlockSpec(slot_row.shape, lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((m_pad, out_cols), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((m_pad, out_cols), jnp.float32),
        interpret=interpret, name=name,
    )(ohb, w, leaf_id[:, None], slot_row)
    return out


def _departition_planes(out: jax.Array, pack: int, gb: int) -> jax.Array:
    """(m_pad, pack*gbp_pad) per-plane-sliced accumulator ->
    (m_pad, gb) full-width histogram (drops each plane's lane
    padding)."""
    if pack == 1:
        return out
    gbp = gb // pack
    gbp_pad = out.shape[1] // pack
    return jnp.concatenate(
        [out[:, p * gbp_pad:p * gbp_pad + gbp] for p in range(pack)],
        axis=1)


@functools.partial(
    jax.jit, static_argnames=("num_leaves", "max_group_bin", "block",
                              "interpret", "pack", "num_groups"))
def compute_group_histograms_pre(
        ohb: jax.Array, w: jax.Array, leaf_id: jax.Array, *,
        num_leaves: int, max_group_bin: int, block: int = 1024,
        interpret: bool = False, slots: Optional[jax.Array] = None,
        pack: int = 1, num_groups: Optional[int] = None) -> jax.Array:
    """Histogram from a precomputed (N, G*B[/pack]) one-hot (same
    output contract as :func:`compute_group_histograms`).  ``w`` is the
    (N, 3) float32 weight matrix (grad, hess, cnt).  ``pack`` > 1
    requires ``num_groups``."""
    if pack == 1:
        num_groups = ohb.shape[1] // max_group_bin
    elif num_groups is None:
        raise ValueError("num_groups is required when pack > 1")
    gb = num_groups * max_group_bin
    num_leaves, m_leaf, m_pad, slot_row = _slot_prep(num_leaves, slots)
    kern = functools.partial(_hist_kernel_body_pre, m_pad=m_pad,
                             pack=pack)
    out = _run_hist_kernel_pre(
        kern, ohb, w, leaf_id, slot_row, block=block, m_pad=m_pad,
        name="compute_group_histograms_pre", interpret=interpret,
        out_cols=None if pack == 1 else pack * ohb.shape[1])
    out = _departition_planes(out, pack, gb)
    hist = out.reshape(3, m_leaf, num_groups, max_group_bin)[:, :num_leaves]
    return jnp.transpose(hist, (1, 2, 3, 0))


PACKED_STRIP = 42  # 3 channels x 42 slots fit one 128-lane tile


def _pack_slot_tiles(slots: jax.Array, strips: int) -> jax.Array:
    """(W,) frontier slots -> (128*strips,) channel-packed tile layout:
    within tile s, the strip of slots [s*strip, (s+1)*strip) repeats
    three times (one per weight channel) followed by -2 padding; -2
    matches neither real leaves nor padded rows (-1)."""
    strip = PACKED_STRIP
    cap = strip * strips
    nslots = slots.shape[0]
    if nslots < cap:
        slots = jnp.concatenate(
            [slots, jnp.full(cap - nslots, -2, jnp.int32)])
    else:
        slots = slots[:cap]
    slots = jnp.where(slots >= 0, slots, -2)
    tiles = []
    pad2 = jnp.full(128 - 3 * strip, -2, jnp.int32)
    for s in range(strips):
        one = slots[s * strip:(s + 1) * strip]
        tiles += [one, one, one, pad2]
    return jnp.concatenate(tiles)


def _unpack_strip_channels(out: jax.Array, strips: int, num_groups: int,
                           max_group_bin: int) -> jax.Array:
    """(128*strips, G*B) packed kernel accumulator -> (cap, G, B, 3):
    within tile s, lanes [c*strip, (c+1)*strip) hold channel c of slots
    [s*strip, (s+1)*strip)."""
    strip = PACKED_STRIP
    cap = strip * strips
    per_ch = []
    for ch in range(3):
        rows = [out[s * 128 + ch * strip: s * 128 + (ch + 1) * strip]
                for s in range(strips)]
        per_ch.append(jnp.concatenate(rows) if strips > 1 else rows[0])
    hist = jnp.stack(per_ch)                             # (3, cap, G*B)
    hist = hist.reshape(3, cap, num_groups, max_group_bin)
    return jnp.transpose(hist, (1, 2, 3, 0))


def tiled_hist_width(num_groups: int, max_group_bin: int) -> int:
    """Lane width of the tiled-iota kernels' output block: ``per_tile``
    groups packed per 128-lane tile (the layout contract shared by
    _fused_kernel_body_q_tiled and the kernel plan's VMEM-aware
    block-size rule)."""
    b = max_group_bin
    per_tile = max(1, 128 // b)
    tile_w = 128 if b <= 128 else _round_up(b, 128)
    return ((num_groups + per_tile - 1) // per_tile) * tile_w


@functools.partial(
    jax.jit, static_argnames=("max_group_bin", "block", "strips",
                              "interpret", "pack", "num_groups"))
def compute_group_histograms_pre_packed(
        ohb: jax.Array, w: jax.Array, leaf_id: jax.Array,
        slots: jax.Array, *, max_group_bin: int, block: int = 1024,
        strips: int = 1, interpret: bool = False, pack: int = 1,
        num_groups: Optional[int] = None) -> jax.Array:
    """Channel-packed streamed-one-hot histogram: ``slots`` must hold
    at most strips*PACKED_STRIP valid entries; returns
    (strips*PACKED_STRIP, G, B, 3) with the slot axis following the
    (padded) ``slots`` order.  ``pack`` > 1 streams the planar
    sub-byte one-hot from :func:`precompute_bin_onehot_packed`
    (``num_groups`` is then required — the lane-padded plane width no
    longer encodes G)."""
    if pack == 1:
        num_groups = ohb.shape[1] // max_group_bin
    elif num_groups is None:
        raise ValueError("num_groups is required when pack > 1")
    gb = num_groups * max_group_bin
    slot_row = _pack_slot_tiles(slots, strips)[None, :]  # (1, 128*strips)
    kern = functools.partial(_hist_kernel_body_pre_packed,
                             strip=PACKED_STRIP, strips=strips, pack=pack)
    out = _run_hist_kernel_pre(
        kern, ohb, w, leaf_id, slot_row, block=block, m_pad=128 * strips,
        name="compute_group_histograms_pre_packed", interpret=interpret,
        out_cols=None if pack == 1 else pack * ohb.shape[1])
    out = _departition_planes(out, pack, gb)
    return _unpack_strip_channels(out, strips, num_groups, max_group_bin)


def _route_prologue_T(binb, leaf, routeT, *, num_groups, nb,
                      with_decision=False, packed_groups=0):
    """Shared transposed routing prologue of the fused kernels: apply
    the pending per-leaf route table to a block's rows.  ``binb`` is
    the (G, C) int32 bins block, ``leaf`` the (1, C) int32 leaf ids,
    ``routeT`` the (K, Lpad) transposed route table in VMEM.  Returns
    the (1, C) post-route leaf ids — plus ``(went_right, scal)`` when
    ``with_decision`` (the exit-route kernel reads its bf16-split
    leaf-value columns out of the same ``scal`` dot).

    This is the in-kernel transposed form of ops/partition.py
    route_rows — see the NOTE there: any semantic change MUST land in
    both places (tests/test_histogram_kernel.py pins them together)."""
    c = leaf.shape[1]
    l_pad = routeT.shape[1]
    liota = jax.lax.broadcasted_iota(jnp.int32, (l_pad, c), 0)
    ohl_route = (liota == leaf).astype(jnp.bfloat16)     # (Lpad, C)
    scal = jax.lax.dot_general(                          # (K, C) f32
        routeT.astype(jnp.bfloat16), ohl_route,
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    def irow(k):
        return scal[k:k + 1, :].astype(jnp.int32)        # (1, C)

    grp = irow(0) * 256 + irow(1)
    thr = irow(2)
    dleft = irow(3)
    mtype = irow(4)
    dbin = irow(5)
    nbin = irow(6)
    iscat = scal[7:8, :] > 0.5
    rs = irow(8) * 256 + irow(9)
    active = (scal[10:11, :] > 0.5) & (leaf >= 0)
    lo, hi = irow(11), irow(12)
    shift, oor = irow(13), irow(14)

    if packed_groups:
        # nibble-packed storage: select the chosen group's storage
        # BYTE row, then extract its nibble with a per-row variable
        # shift (the same vector-shift idiom as the categorical bit
        # test below); ops/partition packed_select_params is the one
        # jnp form of the packing.py byte_of/shift_of arithmetic
        byte_idx, nsh, msk = packed_select_params(grp, packed_groups)
        s_rows = binb.shape[0]
        siota = jax.lax.broadcasted_iota(jnp.int32, (s_rows, c), 0)
        bsel = siota == byte_idx                         # (S, C)
        byte = jnp.sum(jnp.where(bsel, binb, 0), axis=0,
                       keepdims=True)                    # (1, C)
        gb = (byte >> nsh) & msk
    else:
        giota = jax.lax.broadcasted_iota(jnp.int32, (num_groups, c), 0)
        gsel = giota == grp                              # (G, C)
        gb = jnp.sum(jnp.where(gsel, binb, 0), axis=0,
                     keepdims=True)                      # (1, C)
    fbin = jnp.where((gb >= lo) & (gb < hi), gb - shift, oor)

    is_nan_bin = fbin == nbin - 1
    is_def_bin = fbin == dbin
    cmp_left = (fbin <= thr).astype(jnp.int32)
    num_left = jnp.where(
        (mtype == MISSING_NAN) & is_nan_bin, dleft,
        jnp.where((mtype == MISSING_ZERO) & is_def_bin, dleft, cmp_left))

    byte_idx = fbin // 8
    niota = jax.lax.broadcasted_iota(jnp.int32, (nb, c), 0)
    bsel = niota == byte_idx
    byte_val = jnp.sum(
        jnp.where(bsel, scal[15:15 + nb, :], 0.0), axis=0,
        keepdims=True).astype(jnp.int32)
    cat_left = (byte_val >> (fbin % 8)) & 1

    go_left = jnp.where(iscat, cat_left, num_left)
    new_leaf = jnp.where(active, jnp.where(go_left > 0, leaf, rs), leaf)
    if with_decision:
        return new_leaf, active & (go_left <= 0), scal
    return new_leaf


def _tiled_lhs(leaf, w, slot_col, *, strip, strips):
    """Shared channel-packed lhs of the tiled kernels: slot one-hot ×
    strip-selected weight channel, int8 (m_pad, C).  ``leaf`` (1, C)
    int32, ``w`` (3, C) int32 quantized weights, ``slot_col``
    (m_pad, 1) from _pack_slot_tiles.  Layout contract pinned by
    _pack_slot_tiles / _unpack_strip_channels."""
    m_pad = 128 * strips
    ohl = slot_col == leaf                               # (m_pad, C)
    riota = jax.lax.broadcasted_iota(jnp.int32, (m_pad, 1), 0) % 128
    wl = jnp.where(riota < strip, w[0:1, :],
                   jnp.where(riota < 2 * strip, w[1:2, :], w[2:3, :]))
    return jnp.where(ohl, wl, jnp.zeros((), jnp.int32)).astype(jnp.int8)


def _tiled_onehot_dots(lhs, binb, out_ref, *, max_group_bin, num_groups,
                       packed_groups=0):
    """Shared tiled-iota histogram accumulate: rebuild the bin one-hot
    per 128-lane tile from the (G, C) int32 bins block and dot ``lhs``
    ((m_pad, C) int8) into the tile's output slice.  Everything is
    TRANSPOSED (per-row scalars are (1, C) lane vectors, one-hots are
    built (rows, C) by broadcasting an iota COLUMN against (1, C) rows —
    sublane broadcasts, no cross-lane shuffles).  A tile packs
    ``per_tile = 128 // B`` groups as SUBLANE ranges (tiled_hist_width);
    the wrapper reshuffles the tile layout to (slot, G, B, 3)."""
    b = max_group_bin
    c = binb.shape[1]
    per_tile = max(1, 128 // b)
    tile_w = 128 if b <= 128 else _round_up(b, 128)
    siota = jax.lax.broadcasted_iota(jnp.int32, (tile_w, c), 0)
    num_tiles = (num_groups + per_tile - 1) // per_tile
    for t in range(num_tiles):
        g0 = t * per_tile
        gs = min(per_tile, num_groups - g0)
        # target[s, r] = bins[r, g0 + s // B] + (s // B) * B, so a
        # single (target == siota) compare builds the whole tile
        # (_bin_row_T widens nibble-packed group rows in-register —
        # static shift+mask, identical code when packed_groups == 0)
        target = _bin_row_T(binb, g0, packed_groups)
        for k in range(1, gs):
            target = jnp.where(
                siota < k * b, target,
                _bin_row_T(binb, g0 + k, packed_groups) + k * b)
        if gs * b < tile_w:
            target = jnp.where(siota < gs * b, target, -1)
        oh = (target == siota).astype(jnp.int8)          # (tile_w, C)
        contrib = jax.lax.dot_general(
            lhs, oh, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32)
        out_ref[:, t * tile_w:(t + 1) * tile_w] += contrib


def _fused_kernel_body(ohb_ref, binsT_ref, wT_ref, leafT_ref, routeT_ref,
                       slots_ref, hist_ref, leaf_out_ref, *, strip,
                       strips, num_groups, nb, pack=1, packed_groups=0):
    """Route-then-histogram kernel: one row-block applies the PENDING
    per-leaf route table (the splits selected last round) to its rows,
    writes the new leaf ids, and accumulates the frontier histogram
    from the streamed one-hot block — the separate XLA routing pass
    (apply_route_table: a materialized (N, L) one-hot dot + an extra
    (N, G) bins read, ~2 ms/round at 1M rows) disappears into the
    histogram's own data stream.

    Transposed orientation throughout: per-row scalars are (1, C) lane
    vectors, one-hots are built (rows, C) by broadcasting an iota
    COLUMN against a (1, C) row — no in-kernel transposes, and the
    row-blocked inputs (leaf, weights, bins) arrive lane-major so XLA
    never copies them into sublane-padded (N, 1) layouts.

    Column layout of routeT_ref follows ops/partition.py
    ROUTE_FIXED_COLS (fg hi/lo, thr, dleft, mtype, dbin, nbin, iscat,
    rs hi/lo, active, fb lo/hi/shift/oor, cat bytes)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        hist_ref[:] = jnp.zeros_like(hist_ref)

    m_pad = 128 * strips

    leaf = leafT_ref[:]                                  # (1, C) int32
    new_leaf = _route_prologue_T(binsT_ref[:].astype(jnp.int32), leaf,
                                 routeT_ref[:], num_groups=num_groups,
                                 nb=nb, packed_groups=packed_groups)
    leaf_out_ref[:] = new_leaf

    # --- histogram (channel-packed lanes along ROWS) ----------------
    slot_col = slots_ref[:]                              # (m_pad, 1)
    ohl = slot_col == new_leaf                           # (m_pad, C)
    riota = jax.lax.broadcasted_iota(jnp.int32, (m_pad, 1), 0) % 128
    w = wT_ref[:]                                        # (3, C) f32
    wl = jnp.where(riota < strip, w[0:1, :],
                   jnp.where(riota < 2 * strip, w[1:2, :], w[2:3, :]))
    lhs = jnp.where(ohl, wl,
                    jnp.zeros((), jnp.float32)).astype(jnp.bfloat16)
    gbp_pad = ohb_ref.shape[1]
    for p, (plane, sh) in enumerate(_unpack_ohb_planes(ohb_ref[:], pack)):
        contrib = _descale_contrib(jax.lax.dot_general(
            lhs, plane, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32), sh)
        if pack == 1:
            hist_ref[:] += contrib
        else:
            hist_ref[:, p * gbp_pad:(p + 1) * gbp_pad] += contrib


@functools.partial(
    jax.jit, static_argnames=("max_group_bin", "block", "strips",
                              "interpret", "pack", "num_groups",
                              "packed_groups"))
def compute_group_histograms_fused(
        ohb: jax.Array, binsT: jax.Array, wT: jax.Array,
        leaf_id: jax.Array, route_tab: jax.Array, slots: jax.Array, *,
        max_group_bin: int, block: int = 2048, strips: int = 1,
        interpret: bool = False, pack: int = 1,
        num_groups: Optional[int] = None, packed_groups: int = 0):
    """Fused route+histogram: returns ``(hist, new_leaf)`` where
    ``hist`` is (strips*PACKED_STRIP, G, B, 3) following (padded)
    ``slots`` order and ``new_leaf`` the (N,) post-route leaf ids.

    Args:
      ohb: (N, G*B) int8 streamed bin one-hot, or its (N, G*B/pack)
        planar sub-byte packing when ``pack`` > 1 (``num_groups`` is
        then required).
      binsT: (G, N) uint8 TRANSPOSED packed bins (routing reads the
        chosen group's bin per row as a lane vector).
      wT: (3, N) float32 weight channels (grad, hess, cnt).
      leaf_id: (N,) int32 pre-route leaf ids.
      route_tab: (L, 15+ceil(B_f/8)) f32 route table from
        ops/partition.py build_route_table; an all-zero table routes
        nothing (active column = 0).
      slots: (W,) int32 frontier slots, W <= strips*PACKED_STRIP.
    """
    n, ohb_cols = ohb.shape
    if pack == 1:
        num_groups = ohb_cols // max_group_bin
    elif num_groups is None:
        raise ValueError("num_groups is required when pack > 1")
    gb = num_groups * max_group_bin
    out_cols = ohb_cols if pack == 1 else pack * ohb_cols
    if n % block != 0:
        raise ValueError(f"N ({n}) must be a multiple of block ({block})")
    slot_col = _pack_slot_tiles(slots, strips)[:, None]  # (128*strips, 1)

    routeT = _transpose_pad_route(route_tab)
    K = route_tab.shape[1]
    m_pad = 128 * strips

    kern = functools.partial(_fused_kernel_body, strip=PACKED_STRIP,
                             strips=strips,
                             num_groups=num_groups, nb=K - 15, pack=pack,
                             packed_groups=packed_groups)
    s_rows = binsT.shape[0]              # storage rows (== G unpacked)
    hist, leaf_out = pl.pallas_call(
        kern,
        grid=(n // block,),
        in_specs=[
            pl.BlockSpec((block, ohb_cols), lambda i: (i, 0)),
            pl.BlockSpec((s_rows, block), lambda i: (0, i)),
            pl.BlockSpec((3, block), lambda i: (0, i)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec(routeT.shape, lambda i: (0, 0)),
            pl.BlockSpec(slot_col.shape, lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((m_pad, out_cols), lambda i: (0, 0)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m_pad, out_cols), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
        ],
        interpret=interpret, name="compute_group_histograms_fused",
    )(ohb, binsT, wT, leaf_id[None, :], routeT, slot_col)
    hist = _departition_planes(hist, pack, gb)
    return _unpack_strip_channels(hist, strips, num_groups,
                                  max_group_bin), leaf_out[0]


def _fused_kernel_body_q_tiled(binsT_ref, wT_ref, leafT_ref, routeT_ref,
                               slots_ref, hist_ref, leaf_out_ref, *,
                               strip, strips, num_groups, nb,
                               max_group_bin, packed_groups=0,
                               segment_blocks=0):
    """Fused route + tiled-iota histogram: the pending route table is
    applied to the block's rows, then the histogram accumulates from a
    one-hot rebuilt per 128-lane tile in VMEM — HBM traffic is just the
    TRANSPOSED packed bins (~G bytes/row) + weights.  Replaces the
    streamed-one-hot fused kernel wherever quantized training runs:
    same per-pass speed (the dot floors both) with no multi-GB resident
    one-hot, no precompute, and no HBM budget gating.

    Routing prologue is the _fused_kernel_body one (see
    ops/partition.py route_rows for the semantics contract).
    ``segment_blocks`` > 0: ``hist_ref`` is the accumulator of the row
    segment the block lies in, begun every that many blocks."""
    i = pl.program_id(0)

    @pl.when((i % segment_blocks if segment_blocks else i) == 0)
    def _init():
        hist_ref[:] = jnp.zeros_like(hist_ref)

    leaf = leafT_ref[:]                                  # (1, C) int32
    binb = binsT_ref[:].astype(jnp.int32)                # (G|S, C)
    new_leaf = _route_prologue_T(binb, leaf, routeT_ref[:],
                                 num_groups=num_groups, nb=nb,
                                 packed_groups=packed_groups)
    leaf_out_ref[:] = new_leaf

    lhs = _tiled_lhs(new_leaf, wT_ref[:], slots_ref[:], strip=strip,
                     strips=strips)
    _tiled_onehot_dots(lhs, binb, hist_ref, max_group_bin=max_group_bin,
                       num_groups=num_groups,
                       packed_groups=packed_groups)


def _tiled_out_to_hist(out: jax.Array, strips: int, num_groups: int,
                       max_group_bin: int) -> jax.Array:
    """(m_pad, num_tiles*tile_w) tiled kernel accumulator ->
    (strips*PACKED_STRIP, G, B, 3) float32 (pre-scale)."""
    b = max_group_bin
    per_tile = max(1, 128 // b)
    tile_w = 128 if b <= 128 else _round_up(b, 128)
    num_tiles = (num_groups + per_tile - 1) // per_tile
    m_pad = out.shape[0]
    tiles = out.reshape(m_pad, num_tiles, tile_w)[:, :, :per_tile * b]
    full = tiles.reshape(m_pad, num_tiles * per_tile, b)[:, :num_groups]
    return _unpack_strip_channels(
        full.reshape(m_pad, num_groups * b), strips, num_groups, b)


@functools.partial(
    jax.jit, static_argnames=("max_group_bin", "block", "strips",
                              "interpret", "packed_groups",
                              "dequantize", "segment_rows"))
def compute_group_histograms_fused_tiled(
        binsT: jax.Array, wT: jax.Array, scales: jax.Array,
        leaf_id: jax.Array, route_tab: jax.Array, slots: jax.Array, *,
        max_group_bin: int, block: int = 2048, strips: int = 1,
        interpret: bool = False, packed_groups: int = 0,
        dequantize: bool = True, segment_rows: int = 0):
    """Fused route + tiled-iota int8 histogram: same contract as
    :func:`compute_group_histograms_fused` minus the ``ohb`` operand —
    the one-hot is rebuilt in VMEM from ``binsT``.  Quantized path only
    (wT is the (3, N) int32 quantized weights).  ``packed_groups`` > 0
    marks binsT as the (cols, N) nibble-packed storage — the HBM
    stream halves and nibbles widen in-register per tile.
    ``dequantize=False`` returns the int32 accumulators themselves
    (``scales`` unread): what a row shard hands to the exact cross-shard
    sum (parallel/collectives.py ``exchange_int_histograms``).

    ``segment_rows`` (0, or at least the rows: one segment, the kernel
    as it always lowered) bounds what one int32 accumulator sums: the
    rows are taken that many at a time, each segment into an accumulator
    of its own, and the histogram comes back with the segments as its
    leading axis, ``(segments, slots, G, B, 3)`` int32, for that same
    exact sum to fold (``dequantize=False`` only)."""
    num_groups = logical_groups(binsT.shape[0], packed_groups) \
        if packed_groups else binsT.shape[0]
    b = max_group_bin
    per_tile = max(1, 128 // b)
    tile_w = 128 if b <= 128 else _round_up(b, 128)
    num_tiles = (num_groups + per_tile - 1) // per_tile
    n = binsT.shape[1]
    if n % block != 0:
        raise ValueError(f"N ({n}) must be a multiple of block ({block})")
    segments, seg_blocks = _segment_blocks(n, block, segment_rows,
                                           dequantize)
    slot_col = _pack_slot_tiles(slots, strips)[:, None]  # (m_pad, 1)

    routeT = _transpose_pad_route(route_tab)
    K = route_tab.shape[1]
    m_pad = 128 * strips

    kern = functools.partial(_fused_kernel_body_q_tiled, strip=PACKED_STRIP,
                             strips=strips, num_groups=num_groups,
                             nb=K - 15, max_group_bin=b,
                             packed_groups=packed_groups,
                             segment_blocks=seg_blocks)
    s_rows = binsT.shape[0]              # storage rows (== G unpacked)
    acc = (m_pad, num_tiles * tile_w)
    out, leaf_out = pl.pallas_call(
        kern,
        grid=(n // block,),
        in_specs=[
            pl.BlockSpec((s_rows, block), lambda i: (0, i)),
            pl.BlockSpec((3, block), lambda i: (0, i)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec(routeT.shape, lambda i: (0, 0)),
            pl.BlockSpec(slot_col.shape, lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec(acc, lambda i: (0, 0)) if segments == 1
            else pl.BlockSpec((None,) + acc,
                              lambda i: (i // seg_blocks, 0, 0)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(acc if segments == 1
                                 else (segments,) + acc, jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
        ],
        **_pipelined_acc_params(segments > 1),
        interpret=interpret, name="compute_group_histograms_fused_tiled",
    )(binsT, wT, leaf_id[None, :], routeT, slot_col)
    unpack = functools.partial(_tiled_out_to_hist, strips=strips,
                               num_groups=num_groups, max_group_bin=b)
    if segments > 1:
        return jax.vmap(unpack)(out), leaf_out[0]
    hist = unpack(out)
    if not dequantize:
        return hist, leaf_out[0]
    hist = hist.astype(jnp.float32) * scales[None, None, None, :]
    return hist, leaf_out[0]


#: Factored rungs of the narrow-frontier ladder, ``(k_cap, a, b)`` by
#: rising ``k_cap``: a pass with at most ``k_cap`` active slots splits
#: each bin index as ``hi * b + lo`` (``a * b`` = the 256-lane tile) and
#: moves ``hi`` to the slot side of the dot, so the streamed one-hot is
#: ``b`` lanes a group instead of 256 and the other operand carries
#: ``3 * k_cap * a`` rows a group instead of a 128-row strip.  A pass
#: costs what that operand's rows cost the MXU, in steps of 32
#: (``_factored_rows``), so the caps sit where a step ends.  The two
#: wide rungs take the passes that two and three strips had, up to the
#: widest frontier (3 x PACKED_STRIP).  Settled on the chip: PERF.md,
#: PR 27 (the four narrow rungs) and PR 29 (the two wide ones).
FACTORED_RUNGS = ((2, 4, 64), (10, 2, 128), (16, 2, 128), (32, 2, 128),
                  (64, 2, 128), (126, 2, 128))

#: rows of the split-row input of the chunked kernels: the widest
#: frontier the ladder serves (3 x PACKED_STRIP), padded to a tile of
#: uint8 sublanes
ROUTE_ROWS = 128

#: scoped VMEM a chunked factored pass asks of the compiler (of a v5e
#: core's 128 MiB); ops/hist_plan.py sizes the group chunk under it
CHUNK_VMEM_LIMIT = 100 << 20

#: feature tiles a trip of the factored kernel's loop (Mosaic schedules
#: one trip's operand builds under the dots before them)
_FACTORED_UNROLL = 8

#: slot caps of the rungs that COMPACT a block's rows before the dot: a
#: pass builds the new right children only, so about half of a wide
#: round's rows are in none of its slots, and their columns of the
#: slot-side operand are zeros the MXU multiplies at full price.  These
#: rungs bring the rows of an active slot to the front of each
#: ``COMPACT_UNIT`` rows (a prefix sum and one permutation product) and
#: contract over those alone, in steps of ``COMPACT_STEP``.  The
#: narrower rungs are bound by their operand builds, not by the dot,
#: and keep the uncompacted body.  Settled on the chip: PERF.md, PR 38;
#: docs/ROOFLINE.md has the cost model and the sweep.
COMPACT_RUNGS = (32, 64, 126)
COMPACT_UNIT = 1024
COMPACT_STEP = 128


def factored_rungs(max_group_bin: int, packed_groups: int = 0):
    """The factored rung table in force for a bin matrix: the module's
    where a group fills a 256-lane tile (``max_group_bin`` > 128, which
    is ``max_bin=255``) of byte-wide bins, else empty — narrower tiles
    have no lanes to give back."""
    if packed_groups or tiled_hist_width(1, max_group_bin) != 256:
        return ()
    return FACTORED_RUNGS


def _factored_rows(k_cap: int, a: int):
    """32-bit word rows of the factored kernel's slot-side operand, four
    int8 rows (channel, slot, hi) to a word: ``(a channel, a group)`` —
    a channel starts a word, a group fills whole 8-sublane registers."""
    per_channel = -(-k_cap * a // 4)
    return per_channel, _round_up(3 * per_channel, 8)


def compact_shape(k_cap: int, block: int):
    """``(unit, step)`` of a rung that compacts its blocks' rows — rows
    a permutation product moves at once, and columns a step of the
    shortened contraction — or ``()`` for a rung that does not."""
    if k_cap not in COMPACT_RUNGS:
        return ()
    unit = min(COMPACT_UNIT, block)
    return unit, min(COMPACT_STEP, unit)


def _fused_kernel_body_q_factored(binsT_ref, *refs, k_cap, a, b,
                                  num_groups, nb, route_rows=0,
                                  segment_blocks=0, compact=()):
    """Fused route + FACTORED int8 histogram, a rung of ``FACTORED_RUNGS``.

        hist[slot, ch, g, hi, lo] =
            sum_r (w[ch, r] [leaf_r = slot] [hi_g,r = hi]) [lo_g,r = lo]

    The only one-hot-wide operand is ``[lo = .]``, ``b`` lanes a group,
    so ``pack = 128 // b`` groups share one 128-row tile; the other
    operand carries ``3 * k_cap * a`` rows a group (channel, slot, hi).
    One dot per ``pack`` groups, contraction over the block's rows as in
    the tiled kernel; its output holds the histogram on the blocks whose
    two groups agree (the wrapper takes that diagonal).  Same integers
    as the tiled kernel's.

    Both int8 operands are built four rows to a 32-bit word
    (``pltpu.bitcast``: int8 row 4s+q is byte q of word row s): a row's
    key names ONE word row and ONE byte of it, so an operand costs a
    compare and a select a word and nothing is converted to int8.  The
    per-group rows (word row, byte shift, for both operands) are made
    for the whole block at once and read back a row at a time.

    ``route_rows`` > 0 is the wide table's form (:func:`gather_split_rows`):
    the grid is (group chunks, row blocks), ``binsT_ref`` holds one
    chunk's ``num_groups`` groups of the block — past the table's last
    group a chunk holds stale rows, whose tiles lie outside the output
    and are dropped — the accumulator block is the chunk's, resident
    while its row blocks sweep, and the route reads the ``route_rows``
    split rows (a second, narrow input) and never another chunk's.

    ``segment_blocks`` > 0: ``hist_ref`` is the accumulator of the row
    segment the block lies in, begun every that many row blocks.

    ``compact`` = ``(unit, step)`` (:func:`compact_shape`): the rows of
    an active slot are brought to the front of every ``unit`` rows
    before the operands are built, the four scratches hold a unit a
    leading index, and a unit's dots contract over its count rounded up
    to ``step`` columns; two more scratches, the slot side's weight rows
    and the units' counts (SMEM).  The same sums: an int32 accumulator
    does not count the zero columns it is spared."""
    from jax.experimental.pallas import tpu as pltpu

    if route_rows:
        rowsT_ref, *refs = refs
    (wT_ref, leafT_ref, routeT_ref, slots_ref, hist_ref, leaf_out_ref,
     key4_ref, ksh_ref, lo4_ref, bit_ref, *compact_refs) = refs
    i = pl.program_id(1 if route_rows else 0)

    @pl.when((i % segment_blocks if segment_blocks else i) == 0)
    def _init():
        hist_ref[:] = jnp.zeros_like(hist_ref)

    leaf = leafT_ref[:]                                  # (1, C) int32
    # (a compacting rung of a chunked pass reads the block as bytes only)
    binb = None if compact and route_rows \
        else binsT_ref[:].astype(jnp.int32)              # (G, C)
    if route_rows:
        new_leaf = _route_prologue_T(
            rowsT_ref[:].astype(jnp.int32), leaf, routeT_ref[:],
            num_groups=route_rows, nb=nb)
    else:
        new_leaf = _route_prologue_T(binb, leaf, routeT_ref[:],
                                     num_groups=num_groups, nb=nb)
    leaf_out_ref[:] = new_leaf

    pack = 128 // b
    ch_w, rows_w = _factored_rows(k_cap, a)
    # slot index * a of each row; rows of no active slot get a key that
    # no (slot, hi) row carries
    sj = jax.lax.broadcasted_iota(jnp.int32, slots_ref.shape, 0) * a
    skey = jnp.max(jnp.where(slots_ref[:] == new_leaf, sj, -4096),
                   axis=0, keepdims=True)                # (1, C)
    w = wT_ref[:] & 0xFF                                 # (3, C) bytes
    # slot-side word row (ch, j // 4): channel ch's weight byte, moved
    # to byte j % 4 where the row's key is j; pad rows match no key
    sig = jax.lax.broadcasted_iota(jnp.int32, (rows_w, 1), 0)
    ch = jnp.where(sig < ch_w, 0, jnp.where(sig < 2 * ch_w, 1, 2))
    srow = jnp.where(sig < 3 * ch_w, sig - ch * ch_w, -7)
    liota = jax.lax.broadcasted_iota(jnp.int32, (b // 4, 1), 0)
    zero = jnp.zeros((), jnp.int32)
    one = jnp.ones((), jnp.int32)
    c = leaf.shape[1]

    def word_rows(binb, skey, w):
        """The four per-group word rows (slot-side word row and byte
        shift, lo-side word row and byte) and the slot side's weight
        rows, of rows whose bins, slot keys and weight bytes these are."""
        key = (binb >> (b.bit_length() - 1)) + skey      # slot*a + hi
        lo = binb & (b - 1)
        wsel = jnp.where(ch == 0, w[0:1, :],
                         jnp.where(ch == 1, w[1:2, :], w[2:3, :]))
        return (key >> 2, (key & 3) << 3, lo >> 2,
                jnp.left_shift(one, (lo & 3) << 3)), wsel

    def group_dot(t, gs, row, wsel, width):
        """Accumulate tile ``t``: groups [t*pack, t*pack + gs), over
        ``width`` rows, ``row(ref, g)`` the ``(1, width)`` row of group
        ``g`` in a scratch and ``wsel()`` the weight rows."""
        lhs, rhs = [], []
        for p in range(gs):
            g = t * pack + p
            lhs.append(jnp.where(
                srow == row(key4_ref, g),
                jnp.left_shift(wsel(), row(ksh_ref, g)), zero))
            rhs.append(jnp.where(liota == row(lo4_ref, g),
                                 row(bit_ref, g), zero))
        if gs < pack:
            lhs.append(jnp.zeros(((pack - gs) * rows_w, width), jnp.int32))
            rhs.append(jnp.zeros(((pack - gs) * (b // 4), width),
                                 jnp.int32))
        lhs = jnp.concatenate(lhs) if pack > 1 else lhs[0]
        rhs = jnp.concatenate(rhs) if pack > 1 else rhs[0]
        hist_ref[t] += jax.lax.dot_general(
            pltpu.bitcast(lhs, jnp.int8), pltpu.bitcast(rhs, jnp.int8),
            (((1,), (1,)), ((), ())), preferred_element_type=jnp.int32)

    full_tiles = num_groups // pack
    unroll = _FACTORED_UNROLL

    def group_loop(row, wsel, width):
        def trip(t, carry):
            for j in range(unroll):
                group_dot(t * unroll + j, pack, row, wsel, width)
            return carry

        jax.lax.fori_loop(0, full_tiles // unroll, trip, 0)
        for t in range(full_tiles // unroll * unroll, full_tiles):
            group_dot(t, pack, row, wsel, width)
        if num_groups % pack:
            group_dot(full_tiles, num_groups % pack, row, wsel, width)

    if not compact:
        words, wsel = word_rows(binb, skey, w)
        for ref, word in zip((key4_ref, ksh_ref, lo4_ref, bit_ref), words):
            ref[:] = word
        group_loop(lambda ref, g: ref[pl.ds(g, 1), :], lambda: wsel, c)
        return

    # -- the compacting rungs (COMPACT_RUNGS) ---------------------------
    # A unit's rows that are in an active slot are brought to its front:
    # their places are the exclusive prefix sum of ``active``, and the
    # move is ONE int8 product with the permutation P[j, r] = [r is
    # active and its place is j], built four rows j to a word as the
    # operands below are.  An output is one term, a byte times one, so
    # the product is exact: the compacted rows are the rows.  The moved
    # rows are the unit's bins and, above them, one word a row: its
    # three weight bytes and its slot key + 1 (0: a column past the
    # unit's count, which then matches no slot, as a padded row does).
    unit, step = compact
    wsel_ref, count_ref = compact_refs
    head = (w[0:1, :] | (w[1:2, :] << 8) | (w[2:3, :] << 16)
            | (jnp.where(skey >= 0, skey + 1, 0) << 24))  # (1, C)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, unit), 1)
    pio = jax.lax.broadcasted_iota(jnp.int32, (unit // 4, 1), 0)
    hio = jax.lax.broadcasted_iota(jnp.int32, (8, 1), 0)
    for u in range(c // unit):
        at = slice(u * unit, (u + 1) * unit)
        active = (skey[:, at] >= 0).astype(jnp.int32)
        place = active                   # inclusive, a doubling a step
        d = 1
        while d < unit:
            place = place + jnp.where(
                lane >= d, pltpu.roll(place, d, axis=1), 0)
            d *= 2
        count_ref[u] = jnp.sum(active)
        place = place - active
        perm = jnp.where(pio == jnp.where(active > 0, place >> 2, -1),
                         jnp.left_shift(one, (place & 3) << 3), zero)
        rows = jnp.concatenate([
            pltpu.bitcast(jnp.where(hio == 0, head[:, at], zero),
                          jnp.int8),                     # (32, unit)
            pltpu.bitcast(binsT_ref[:, at], jnp.int8)])
        moved = jax.lax.dot_general(
            rows, pltpu.bitcast(perm, jnp.int8),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32) & 0xFF     # (32 + G, unit)
        words, wsel = word_rows(
            moved[32:, :],
            jnp.where(moved[3:4, :] > 0, moved[3:4, :] - 1, -4096),
            moved[0:3, :])
        for ref, word in zip((key4_ref, ksh_ref, lo4_ref, bit_ref), words):
            ref[u] = word
        wsel_ref[u] = wsel

    def unit_dots(u, carry):
        # the dots contract over the unit's count, rounded up to a step
        # (the columns past the count are rows of no slot)
        n = count_ref[u]
        # (a row read at a dynamic sublane is two lane tiles at the least)
        first = max(step, min(256, unit))
        for width in range(first, unit + 1, step):
            @pl.when((n > (width - step if width > first else 0))
                     & (n <= width))
            def _dots(width=width):
                group_loop(
                    lambda ref, g: ref[u, pl.ds(g, 1), pl.ds(0, width)],
                    lambda: wsel_ref[u, :, pl.ds(0, width)], width)
        return carry

    jax.lax.fori_loop(0, c // unit, unit_dots, 0)


@functools.partial(
    jax.jit, static_argnames=("max_group_bin", "block", "k_cap", "a",
                              "interpret", "dequantize", "group_chunk",
                              "segment_rows", "compact"))
def compute_group_histograms_fused_factored(
        binsT: jax.Array, wT: jax.Array, scales: jax.Array,
        leaf_id: jax.Array, route_tab: jax.Array, slots: jax.Array, *,
        max_group_bin: int, k_cap: int, a: int, block: int = 2048,
        interpret: bool = False, dequantize: bool = True,
        group_chunk: int = 0, segment_rows: int = 0, compact=()):
    """Fused route + factored int8 histogram, one rung of
    ``FACTORED_RUNGS``: the contract of
    :func:`compute_group_histograms_fused_tiled` for at most ``k_cap``
    active slots, which lead ``slots``.  Returns ``(hist, new_leaf)``
    with ``hist`` (k_cap, G, B, 3) following ``slots[:k_cap]``, equal to
    the tiled kernel's to the bit.  Byte-wide bins in a 256-lane tile
    only (see :func:`factored_rungs`).  ``dequantize=False``: the int32
    accumulators, as in the tiled kernel.

    ``group_chunk`` (0, or at least the table's groups: one chunk, the
    kernel as it always lowered) makes the group axis a grid axis: a
    chunk of that many groups keeps its accumulator tiles in VMEM while
    the row blocks sweep under it, and the pending route reads the
    table's split rows (:func:`gather_split_rows`) and not the block's
    whole column.  The same integers and the same ``new_leaf``.

    ``segment_rows``: as in the tiled kernel — past that many rows the
    pass writes one accumulator a row segment (the row blocks of a
    segment follow each other, under a chunk too) and returns
    ``(segments, k_cap, G, B, 3)`` int32.

    ``compact``: ``(unit, step)`` (:func:`compact_shape`, for the rungs
    the plan names) — the block's rows of an active slot are brought to
    the front of every ``unit`` rows in VMEM and the dots contract over
    them alone, in steps of ``step`` columns — or ``()``, every row
    through the dot.  The same integers and the same ``new_leaf`` either
    way: an int32 sum does not count its zero terms."""
    from jax.experimental.pallas import tpu as pltpu

    num_groups, n = binsT.shape
    tile_w = tiled_hist_width(1, max_group_bin)
    b = tile_w // a
    pack = 128 // b
    if n % block != 0:
        raise ValueError(f"N ({n}) must be a multiple of block ({block})")
    chunked = 0 < group_chunk < num_groups
    if chunked and group_chunk % 32:
        raise ValueError(f"group_chunk ({group_chunk}) must be a multiple "
                         "of 32, a tile of uint8 sublanes")
    chunk = group_chunk if chunked else num_groups
    if compact and (block % compact[0] or compact[0] % compact[1]
                    or compact[1] % 128):
        raise ValueError(f"compact {compact}: a block ({block}) is whole "
                         "units, a unit whole steps of whole lane tiles")
    segments, seg_blocks = _segment_blocks(n, block, segment_rows,
                                           dequantize)
    kp = _round_up(k_cap, 8)
    slot_col = jnp.full(kp, -2, jnp.int32).at[:k_cap].set(
        jnp.where(slots[:k_cap] >= 0, slots[:k_cap], -2))[:, None]
    split_rows = []
    if chunked:
        rowsT, route_tab = gather_split_rows(binsT, route_tab)
        split_rows = [rowsT]
    routeT = _transpose_pad_route(route_tab)
    num_tiles = (num_groups + pack - 1) // pack
    ch_w, rows_w = _factored_rows(k_cap, a)
    rows = 4 * rows_w                          # int8 rows a group, padded
    kern = functools.partial(_fused_kernel_body_q_factored, k_cap=k_cap,
                             a=a, b=b, num_groups=chunk,
                             nb=route_tab.shape[1] - ROUTE_FIXED_COLS,
                             route_rows=ROUTE_ROWS if chunked else 0,
                             segment_blocks=seg_blocks, compact=compact)
    if compact:
        # a unit's rows lead its scratch; the weight rows and the
        # units' counts have scratches of their own
        units = (block // compact[0], )
        scratch = [pltpu.VMEM(units + (chunk, compact[0]), jnp.int32)
                   for _ in range(4)] + [
            pltpu.VMEM(units + (rows_w, compact[0]), jnp.int32),
            pltpu.SMEM(units, jnp.int32)]
    else:
        scratch = [pltpu.VMEM((chunk, block), jnp.int32)
                   for _ in range(4)]

    def at(by_chunk, by_rows):
        """Index map of a two-axis block that follows the grid's group
        chunk, its row block, both or neither: over ``(c, i)`` on the
        chunked grid, over ``(i,)`` on the one it always had."""
        if chunked:
            return lambda c, i: (c if by_chunk else 0, i if by_rows else 0)
        return lambda i: (0, i if by_rows else 0)

    acc = (-(-chunk // pack), pack * rows, 128)
    if segments > 1:
        # the segment of row block i, then the chunk's tiles
        acc_spec = pl.BlockSpec(
            (None,) + acc,
            (lambda c, i: (i // seg_blocks, c, 0, 0)) if chunked
            else (lambda i: (i // seg_blocks, 0, 0, 0)))
    else:
        acc_spec = pl.BlockSpec(acc, (lambda c, i: (c, 0, 0)) if chunked
                                else (lambda i: (0, 0, 0)))
    out, leaf_out = pl.pallas_call(
        kern,
        grid=((-(-num_groups // chunk),) if chunked else ()) + (n // block,),
        in_specs=[pl.BlockSpec((chunk, block), at(True, True))] + [
            pl.BlockSpec((ROUTE_ROWS, block), at(False, True))
            for _ in split_rows] + [
            pl.BlockSpec((3, block), at(False, True)),
            pl.BlockSpec((1, block), at(False, True)),
            pl.BlockSpec(routeT.shape, at(False, False)),
            pl.BlockSpec(slot_col.shape, at(False, False)),
        ],
        out_specs=[
            acc_spec,
            pl.BlockSpec((1, block), at(False, True)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(
                ((segments,) if segments > 1 else ())
                + (num_tiles, pack * rows, 128), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.int32),
        ],
        scratch_shapes=scratch,
        # one chunk, one segment: the whole-array accumulator, which XLA
        # keeps in VMEM outside the kernel's scoped allocation; chunks
        # or segments: theirs is a pipelined block inside it
        **_pipelined_acc_params(chunked or segments > 1),
        interpret=interpret,
        name=f"compute_group_histograms_fused_factored_k{k_cap}_a{a}",
    )(binsT, *split_rows, wT, leaf_id[None, :], routeT, slot_col)
    unpack = functools.partial(
        _factored_out_to_hist, scales=scales if dequantize else None,
        num_groups=num_groups, max_group_bin=max_group_bin, k_cap=k_cap,
        a=a)
    if segments > 1:
        return jax.vmap(unpack)(out), leaf_out[0]
    return unpack(out), leaf_out[0]


def _factored_out_to_hist(out, scales, *, num_groups, max_group_bin,
                          k_cap, a):
    """The factored kernel's ``(tiles, pack * rows, 128)`` int32
    accumulator -> ``(k_cap, G, B, 3)``: int32 as it is, or float32
    times ``scales``."""
    tile_w = tiled_hist_width(1, max_group_bin)
    b = tile_w // a
    pack = 128 // b
    num_tiles = out.shape[0]
    ch_w, rows_w = _factored_rows(k_cap, a)
    rows = 4 * rows_w
    # rows (group-in-tile, channel, slot, hi) x lanes (group-in-tile, lo):
    # the histogram is where the two groups agree
    o = out.reshape(num_tiles, pack, rows, 128)[:, :, :12 * ch_w]
    o = o.reshape(num_tiles, pack, 3, 4 * ch_w, 128)[:, :, :, :k_cap * a]
    o = o.reshape(num_tiles, pack, 3, k_cap, a, pack, b)
    diag = jnp.stack([o[:, p, :, :, :, p, :] for p in range(pack)], axis=1)
    full = diag.reshape(num_tiles * pack, 3, k_cap,
                        tile_w)[:num_groups, :, :, :max_group_bin]
    hist = jnp.transpose(full, (2, 0, 3, 1))
    if scales is None:
        return hist
    return hist.astype(jnp.float32) * scales[None, None, None, :]


def gather_split_rows(binsT: jax.Array, route_tab: jax.Array):
    """What a pending route reads of a wide table: the bin rows of the
    groups its active leaves split on, ``(ROUTE_ROWS, N)`` — active leaf
    number ``r`` (in slot order) gets row ``r`` — and the route table
    with that row in the place of the leaf's group, so that the kernels'
    route prologue selects among ``ROUTE_ROWS`` rows and not among every
    group of the table.  At most ``ROUTE_ROWS`` leaves are active: the
    ladder's frontier is narrower.

    Only the active leaves' rows are copied, so a call costs the splits
    it routes and not ``ROUTE_ROWS`` copies; the rows past them are
    zeros, which no leaf selects (an inactive leaf routes nothing)."""
    from ..telemetry import TELEMETRY
    with jax.named_scope("tel.route"):
        active = route_tab[:, 10] > 0.5
        grp = (route_tab[:, 0] * 256 + route_tab[:, 1]).astype(jnp.int32)
        rank = jnp.cumsum(active.astype(jnp.int32)) - 1
        row_group = jnp.zeros(ROUTE_ROWS, jnp.int32).at[
            jnp.where(active, rank, ROUTE_ROWS)].set(grp, mode="drop")
        with TELEMETRY.phase("split_rows"):
            # a row at a time: XLA's gather of whole rows copies the table
            def copy(r, rowsT):
                row = jax.lax.dynamic_index_in_dim(binsT, row_group[r], 0)
                return jax.lax.dynamic_update_index_in_dim(rowsT, row, r, 0)
            rowsT = jax.lax.fori_loop(
                0, jnp.sum(active, dtype=jnp.int32), copy,
                jnp.zeros((ROUTE_ROWS, binsT.shape[1]), binsT.dtype))
        tab = route_tab.at[:, 0].set(0.0).at[:, 1].set(
            jnp.where(active, rank, 0).astype(jnp.float32))
        return rowsT, tab


def _transpose_pad_route(table: jax.Array) -> jax.Array:
    """(L, K) route table -> (K, l_pad) transposed, zero-padded to a
    128-multiple leaf axis — the in-VMEM orientation every fused/route
    kernel consumes (an all-zero column routes nothing)."""
    L, K = table.shape
    l_pad = max(128, ((L + 127) // 128) * 128)
    return jnp.zeros((K, l_pad), jnp.float32).at[:, :L].set(table.T)


def _route_value_kernel_body(binsT_ref, leafT_ref, routeT_ref,
                             leaf_out_ref, val_out_ref, *, num_groups,
                             nb, packed_groups=0):
    """Exit-route kernel: apply the final pending route table and emit
    each row's POST-route leaf value, with the one-hot broadcast in
    VMEM — the XLA form (ops/partition.py apply_route_table)
    materializes an (N, L_pad) bf16 one-hot plus (N, K) scalar rows in
    HBM, ~16 ms/tree at HIGGS scale.  Value columns ride the same
    scal dot as six bf16-split columns (exact f32 reassembly)."""
    leaf = leafT_ref[:]                                  # (1, C) int32
    new_leaf, went_right, scal = _route_prologue_T(
        binsT_ref[:].astype(jnp.int32), leaf, routeT_ref[:],
        num_groups=num_groups, nb=nb, with_decision=True,
        packed_groups=packed_groups)
    leaf_out_ref[:] = new_leaf
    k0 = ROUTE_FIXED_COLS + nb
    vk = scal[k0:k0 + 1] + scal[k0 + 1:k0 + 2] + scal[k0 + 2:k0 + 3]
    vr = scal[k0 + 3:k0 + 4] + scal[k0 + 4:k0 + 5] + scal[k0 + 5:k0 + 6]
    val = jnp.where(went_right, vr, vk)
    val_out_ref[:] = jnp.where(leaf >= 0, val, 0.0)


@functools.partial(
    jax.jit, static_argnames=("block", "interpret", "packed_groups"))
def route_apply_tiled(binsT: jax.Array, leaf_id: jax.Array,
                      route_tab: jax.Array, values: jax.Array, *,
                      block: int = 8192, interpret: bool = False,
                      packed_groups: int = 0):
    """Pallas exit-route: same contract as ops/partition.py
    apply_route_table(..., values=...) — returns ``(new_leaf,
    row_value)`` — but streams only binsT + leaf ids and builds the
    per-row table broadcast in VMEM."""
    from .partition import extend_table_with_values

    num_groups = logical_groups(binsT.shape[0], packed_groups) \
        if packed_groups else binsT.shape[0]
    if num_groups >= 65536:  # fg // 256 must stay bf16-exact
        raise ValueError(
            "route_apply_tiled supports at most 65535 feature groups, "
            f"got {num_groups} — the route table encodes the group "
            "index as two bf16-exact bytes (hi/lo)")
    n = binsT.shape[1]
    if n % block != 0:
        raise ValueError(f"N ({n}) must be a multiple of block ({block})")
    ncols = route_tab.shape[1]
    routeT = _transpose_pad_route(extend_table_with_values(route_tab,
                                                           values))

    kern = functools.partial(_route_value_kernel_body,
                             num_groups=num_groups,
                             nb=ncols - ROUTE_FIXED_COLS,
                             packed_groups=packed_groups)
    s_rows = binsT.shape[0]
    leaf_out, val_out = pl.pallas_call(
        kern,
        grid=(n // block,),
        in_specs=[
            pl.BlockSpec((s_rows, block), lambda i: (0, i)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec(routeT.shape, lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block), lambda i: (0, i)),
            pl.BlockSpec((1, block), lambda i: (0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, n), jnp.int32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        interpret=interpret, name="route_apply_tiled",
    )(binsT, leaf_id[None, :], routeT)
    return leaf_out[0], val_out[0]


def expand_feature_histograms(group_hist: jax.Array, bin_map: jax.Array,
                              fix_bin: jax.Array,
                              leaf_totals: jax.Array) -> jax.Array:
    """Per-feature view of group histograms.

    ``bin_map[f, b]`` is the flattened (group, group_bin) index holding
    feature f's bin b (or -1).  Entries flagged by ``fix_bin[f]`` are
    reconstructed from leaf totals — the FixHistogram path
    (reference dataset.cpp:776-795): the bundle's shared default slot
    count = leaf totals - sum of the feature's explicit bins.

    Args:
      group_hist: (L, G, B_g, 3)
      bin_map: (F, B_f) int32
      fix_bin: (F,) int32, -1 when no reconstruction needed
      leaf_totals: (L, 3) float32 (sum_grad, sum_hess, count) per leaf

    Returns: (L, F, B_f, 3) float32
    """
    num_leaves = group_hist.shape[0]
    flat = group_hist.reshape(num_leaves, -1, group_hist.shape[-1])
    valid = (bin_map >= 0)
    safe = jnp.where(valid, bin_map, 0)
    feat = flat[:, safe, :] * valid[None, :, :, None]
    needs_fix = (fix_bin >= 0)
    if True:  # static shape either way; cheap when no bundles exist
        missing = leaf_totals[:, None, :] - feat.sum(axis=2)  # (L, F, 3)
        onehot_fix = (jnp.arange(feat.shape[2], dtype=jnp.int32)[None, :]
                      == fix_bin[:, None]) & needs_fix[:, None]  # (F, B_f)
        feat = feat + (onehot_fix[None, :, :, None]
                       * missing[:, :, None, :])
    return feat


def leaf_value_broadcast(leaf_id: jax.Array, values: jax.Array) -> jax.Array:
    """Per-row lookup ``values[leaf_id]`` without a gather.

    Arbitrary-index gathers are slow on TPU; a leaf one-hot matmul hits
    the MXU instead.  Exactness: ``values`` is split into THREE
    bf16-exact terms via ops/partition.py _split3_bf16 (bitmask
    truncation — NOT dtype round-trips, which XLA's excess-precision
    simplification cancels inside jit, silently zeroing the residual
    terms; see _split3_bf16), covering 3x~8 mantissa bits — residual
    ~2^-21 relative.  The one-hot picks exactly one leaf per row so
    the f32-accumulated sum has no cross-term error.  Rows with
    negative leaf_id get 0.0.

    Args: leaf_id (N,) int32; values (L,) f32.  Returns (N,) f32.
    """
    from .partition import _split3_bf16

    L = values.shape[0]
    oh = (leaf_id[:, None]
          == jnp.arange(L, dtype=jnp.int32)[None, :]).astype(jnp.bfloat16)
    rhs = jnp.concatenate(_split3_bf16(values), axis=1)   # (L, 3)
    out = jnp.dot(oh, rhs.astype(jnp.bfloat16),
                  preferred_element_type=jnp.float32)
    return out[:, 0] + out[:, 1] + out[:, 2]


def compute_leaf_totals(grad: jax.Array, hess: jax.Array, counts: jax.Array,
                        leaf_id: jax.Array, num_leaves: int) -> jax.Array:
    """(L, 3) per-leaf (sum_grad, sum_hess, count) via one-hot matmul —
    the root/leaf sums of LeafSplits (reference leaf_splits.hpp:16-159)."""
    ohl = (leaf_id[:, None]
           == jnp.arange(num_leaves, dtype=jnp.int32)[None, :])
    w = jnp.stack([grad, hess, counts], axis=1)  # (N, 3)
    return jnp.einsum("nl,nc->lc", ohl.astype(jnp.float32), w,
                      preferred_element_type=jnp.float32)
