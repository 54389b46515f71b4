"""Which histogram kernels a grower runs: one decision, made once.

``resolve_hist_plan`` is the only reader of ``hist_kernel``,
``hist_precision`` and ``hist_exchange``.  It takes facts (plain ints,
strings and booleans: no ``Dataset``, no device array) and returns one
immutable :class:`HistPlan`; the grower, ``boosting/gbdt.py`` and
``analysis/programs.py`` read the plan.  Three tiers:

``xla``
    the one-hot contraction of ``ops/histogram.compute_group_histograms``
    (CPU, float32 operand parity, feature / voting / multi-axis /
    multi-host meshes, where a sharded contraction lowers to a
    reduce-scatter, and whatever the other two cannot honour under
    ``hist_kernel=auto``);
``float``
    the bf16 Pallas family on one device: the resident streamed one-hot
    (``_fused`` with the route riding the pass, ``_pre`` / ``_pre_packed``
    beyond the strip ladder's width) or, over the one-hot's HBM budget,
    the expansion kernel (``_pallas``);
``ladder``
    the int8 fused ladder (``_fused_tiled`` strips, ``_fused_factored``
    rungs, ``route_apply_tiled`` at the tree's end) on one device or on
    every row shard of a one-axis data mesh, whose int32 accumulators
    are summed exactly — as are those of a device's row segments, where
    it holds more rows than one int32 accumulator sums
    (``histogram.QUANT_SEGMENT_ROWS``).

An explicit request that cannot be honoured (``hist_kernel=pallas``,
``hist_precision=tiered``) raises; under ``hist_kernel=auto`` the XLA
formulation runs instead.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

from .histogram import (CHUNK_VMEM_LIMIT, PACKED_STRIP, ROUTE_ROWS,
                        _factored_rows, _round_up, compact_shape,
                        factored_rungs, quant_row_segments, quant_rows_ok,
                        tiled_hist_width)

#: frontier slots the fused kernels serve: three packed strips, and the
#: widest factored rung
LADDER_WIDTH = 3 * PACKED_STRIP

#: HBM the float tier's resident one-hot may take.  6 GB leaves ~9 GB of
#: a 16 GB v5e for bins, scores, gradients and temporaries; HIGGS scale
#: (10.5M x 28 x 63) needs 5.4 GB at pack=4
ONEHOT_BUDGET_MB = 6144

#: VMEM a pass of the factored kernel is planned into: what the chunked
#: kernel asks of the compiler, less room for what Mosaic keeps besides
#: the blocks counted in ``factored_vmem_bytes``
CHUNK_VMEM_BUDGET = CHUNK_VMEM_LIMIT - (12 << 20)

#: VMEM the fused split finder asks of the compiler, and what a grid
#: step of it is planned into (``finder_vmem_bytes``), the rest left to
#: what Mosaic keeps besides
FINDER_VMEM_LIMIT = 96 << 20
FINDER_VMEM_BUDGET = FINDER_VMEM_LIMIT - (24 << 20)

#: leaf rows a grid step of the fused finder holds: they are the lanes
#: of its (features, leaf rows) outputs
FINDER_ROWS = 128

#: rows a block of the streamed-one-hot kernels (their 3.6 MB/block DMA
#: pipeline prefers 2048 on a v5e: 4096 benched 16% slower)
FLOAT_BLOCK = 2048


@dataclass(frozen=True)
class HistPlan:
    """What ``resolve_hist_plan`` decided."""
    tier: str                     # "xla" | "float" | "ladder"
    interpret: bool               # Pallas interpret mode (the CPU seam)
    row_axis: Optional[str]       # the one-axis row mesh's axis, where
    # its shards qualify for the kernels (whatever tier then runs)
    row_shards: int               # that mesh's size, else 1
    local_rows: int               # padded rows a shard: every size rule
    # of the kernels is a shard's
    mesh_kernels: bool            # the ladder runs once per row shard
    # inside shard_map, and the shards' int32 accumulators are summed
    exchange_limbs: int           # int32 limbs of that sum (0: no sum)
    row_segments: int             # int32 accumulators a pass of the
    # ladder writes on a device, one a row segment, folded by that same
    # exact sum (1: the pass as it always was, and no fold)
    segment_rows: int             # rows a segment (the last: the rest)
    hist_exchange: str            # codec of the XLA row-sharded psum
    fused: bool                   # the pending route rides the pass
    onehot_pack: int              # float tier: one-hot columns a stored
    # byte of the resident one-hot; 0 = none (the expansion kernel)
    block_float: int              # rows a block: _pre, _pre_packed, _fused
    block_tiled: int              # ... _fused_tiled, route_apply_tiled
    block_factored: int           # ... _fused_factored
    group_chunk: int              # groups a grid step of _fused_factored
    # holds: every group (one chunk, the kernel's one-axis grid), or a
    # multiple of 32 under them (the group axis is a grid axis, and the
    # routes read the table's split rows: histogram.gather_split_rows)
    num_groups: int               # the table's groups
    factored_rungs: Tuple[Tuple[int, int, int], ...]  # rungs in force
    compact_rungs: Tuple[int, ...]  # slot caps of those that bring a
    # block's active rows to the front before the dot
    # (histogram.COMPACT_RUNGS: the rung table's, no option reaches it)
    finder: str                   # "fused" | "xla": the numerical split
    # finder's form (ops/split_kernel.py), fused with the Pallas tiers
    warnings: Tuple[str, ...]     # for the caller to log, in order

    @property
    def quantized(self) -> bool:
        """int8 gradients, int32 sums: the ladder, and nothing else."""
        return self.tier == "ladder"

    @property
    def int_counts(self) -> bool:
        """Row counts are int32 from the exact sum to the tree: float32
        counts integers to 2^24, one segment's rows, and a mesh's
        shards or a device's segments hold more between them."""
        return self.mesh_kernels or self.row_segments > 1

    @property
    def group_chunks(self) -> int:
        """Group chunks a pass of the factored kernel sweeps."""
        return -(-self.num_groups // self.group_chunk)

    @property
    def kernel(self) -> str:
        """The ``grower.hist_kernel`` gauge."""
        if self.tier == "ladder":
            return "fused_tiled"
        if self.tier == "float":
            if self.fused:
                return "fused_streamed"
            return "pre_onehot" if self.onehot_pack else "pallas"
        return "xla"


def _tiled_block(num_groups: int, max_group_bin: int,
                 local_rows: int) -> int:
    """Rows a block of the tiled-iota kernels.  They stream ~G bytes a
    row and not the G*B-byte one-hot, so their fixed cost a block (route
    decode, iota rebuild) wants far larger blocks than the streamed
    kernels' DMA-tuned 2048 — but the (m_pad, hist_width) int32 output
    block lives in scoped VMEM, so wide-G shapes shrink it again.
    Measured on v5e: G*B_pad=1792 (28 feats, 63 bins) wants 8192 (25.9 vs
    26.5 ms/tree); 8704 (136 feats) wants 2048 (288 vs 308 ms/tree).
    block*width stays near the 8192*1792 sweet spot, clamped to [2048,
    8192], then the largest power of two dividing ``local_rows``: the
    shard's rows, or what divides them and their segment."""
    width = tiled_hist_width(num_groups, max_group_bin)
    want = 2048
    while want < 8192 and (2 * want) * width <= 8192 * 1792 * 2:
        want *= 2
    for cand in (want, 8192, 4096, 2048, 1024):
        if cand <= local_rows and local_rows % cand == 0:
            return cand
    return 1024


def factored_vmem_bytes(rung: Tuple[int, int, int], groups: int,
                        block: int, chunked: bool,
                        segmented: bool = False) -> int:
    """VMEM a grid step of a factored rung holds for ``groups`` groups
    of a ``block``-row block: the accumulator tiles (a pipelined block
    where the group axis is chunked or the rows are segmented, so
    twice; the whole array, once, where neither), the four scratch rows
    a group, the uint8 block in its two buffers and its int32 copies
    (bins, key, lo), and a chunked pass's split rows; on a compacting
    rung also the slot side's weight rows (a scratch there), the
    permutation of a unit and the unit's rows before and after the
    move."""
    k_cap, a, b = rung
    pack = 128 // b
    rows_w = _factored_rows(k_cap, a)[1]
    acc = -(-groups // pack) * pack * 4 * rows_w * 128 * 4
    if chunked or segmented:
        acc *= 2
    route = ROUTE_ROWS * (2 + 4) if chunked else 0
    compact = compact_shape(k_cap, block)
    if not compact:
        return acc + (groups * (4 * 4 + 2 + 3 * 4) + route) * block
    # the bins' int32 copy is the one-chunk route's alone, key and lo
    # are a unit's, and so are the moved rows (bytes, and int32 twice)
    # and the permutation (its words: the int8 operand is their bitcast)
    unit = compact[0]
    per_block = groups * (4 * 4 + 2 + (0 if chunked else 4)) + rows_w * 4
    per_unit = groups * 2 * 4 + (32 + groups) * (1 + 2 * 4) + unit
    return acc + (per_block + route) * block + per_unit * unit


def _group_chunk(rungs, num_groups: int, block: int,
                 segmented: bool) -> int:
    """Groups a grid step of the factored kernel holds: all of them
    where the widest rung in force then fits ``CHUNK_VMEM_BUDGET`` (67
    groups take 35 MB of it, 61 MB in row segments), else the most
    whole tiles of uint8 sublanes (32 groups) that fit as a chunk, and
    one at the least."""
    if not rungs:
        return num_groups
    widest = max(rungs, key=lambda r: _factored_rows(r[0], r[1])[1]
                 * (128 // r[2]))
    if factored_vmem_bytes(widest, num_groups, block, False,
                           segmented) <= CHUNK_VMEM_BUDGET:
        return num_groups
    chunk = 32
    while chunk + 32 < num_groups and factored_vmem_bytes(
            widest, chunk + 32, block, True) <= CHUNK_VMEM_BUDGET:
        chunk += 32
    return chunk


def finder_vmem_bytes(r_blk: int, f_blk: int, lanes: int, scans: int,
                      int_counts: bool) -> int:
    """VMEM a grid step of the fused split finder
    (``ops/split_kernel.py``) holds for a block of ``r_blk`` leaf rows x
    ``f_blk`` features x ``lanes`` bins: the three planes in their two
    buffers, the three prefix sums of each scan, one prefix product's
    operand and result (twice where int32 counts go as two limbs), and
    the triangular matrix."""
    plane = r_blk * f_blk * lanes * 4
    return (6 + 3 * scans + (4 if int_counts else 2)) * plane \
        + lanes * lanes * 4


def finder_lanes(bins: int) -> int:
    """Lanes a row of ``bins`` bins takes in the fused finder's blocks:
    whole 128-lane tiles (255 bins read as 256)."""
    return _round_up(bins, 128)


def finder_block(rows: int, bins: int, scans: int,
                 int_counts: bool) -> Tuple[int, int]:
    """``(r_blk, f_blk)`` of the fused split finder for ``rows`` leaf
    rows of ``bins`` bins: every row up to ``FINDER_ROWS`` of them, and
    the most features (whole sublane tiles of 8, at most 32: a block of
    a few MB already hides a grid step's fixed cost) that fit
    ``FINDER_VMEM_BUDGET``."""
    r_blk = min(rows, FINDER_ROWS)
    lanes = finder_lanes(bins)
    f_blk = 8
    while f_blk < 32 and finder_vmem_bytes(
            r_blk, 2 * f_blk, lanes, scans,
            int_counts) <= FINDER_VMEM_BUDGET:
        f_blk *= 2
    return r_blk, f_blk


def finder_identity_map(bin_map, fix_bin, num_groups: int,
                        max_group_bin: int, has_categorical: bool,
                        forced_splits: bool) -> bool:
    """The finder may read the group histogram itself: every feature
    alone in its own group with no collapsed default (``bin_map[f, b] ==
    f * max_group_bin + b`` wherever it is set, no ``fix_bin``), no
    categorical feature and no forced split.  ``bin_map`` / ``fix_bin``:
    numpy, ``Dataset.feature_bin_maps``.  A bin past a feature's
    ``num_bin`` holds no row, and the finder's threshold test excludes
    it."""
    import numpy as np
    features, feature_bins = bin_map.shape
    if (has_categorical or forced_splits or features != num_groups
            or feature_bins > max_group_bin or (fix_bin >= 0).any()):
        return False
    own = (np.arange(features, dtype=np.int64)[:, None] * max_group_bin
           + np.arange(feature_bins)[None, :])
    return bool(((bin_map < 0) | (bin_map == own)).all())


def _onehot_pack(rows: int, gb: int) -> Tuple[int, int]:
    """(pack, bytes) of the float tier's resident one-hot: the pack with
    the fewest resident/streamed bytes; ties break toward the SMALLER
    pack (less 128-lane plane padding — for small G*B packing is a
    pessimization and this reduces to pack=1).  Sub-byte packing stores
    ``pack`` one-hot columns a byte (planar, widened in VMEM): at 10.5M
    x 28 x 63 the full one-hot is 17.2 GB, pack=4 is 4.3 GB."""
    def size(p):
        return rows * (gb if p == 1 else _round_up(gb // p, 128))
    pack = min((p for p in (1, 2, 4) if gb % p == 0),
               key=lambda p: (size(p), p))
    return pack, size(pack)


def resolve_hist_plan(config, *, on_tpu: bool,
                      mesh_axes: Optional[Tuple[Tuple[str, int], ...]],
                      row_axis: Optional[str], cols_sharded: bool,
                      multihost: bool, rows_padded: int, num_groups: int,
                      max_group_bin: int, packed_groups: int,
                      frontier: int) -> HistPlan:
    """The kernel plan of one grower.

    ``mesh_axes``: ``((name, size), ...)`` of the device mesh, None for
    one device; ``row_axis``: the axis the policy shards rows on, if
    any; ``cols_sharded``: the bin matrix is column-sharded (the feature
    learner's vertical partition); ``rows_padded``: global rows after
    padding; ``packed_groups``: the sub-byte pack spec (0 = byte-wide
    bins); ``frontier``: the most splits a round applies."""
    warnings = []
    hk = config.hist_kernel
    if hk not in ("auto", "pallas", "xla"):
        warnings.append(f"unknown hist_kernel={hk!r}; using 'auto'")
        hk = "auto"
    # test seam: interpret-mode Pallas on CPU exercises the SAME grower
    # wiring (fused route carry, quant transpose, exit-time route
    # application) the real chip runs
    interpret = bool(config.force_pallas_interpret)
    on_mesh = mesh_axes is not None
    # the kernels run on one TPU device, or on the row shards of a
    # one-axis data mesh of one host
    kernel_axis = None
    if (on_mesh and len(mesh_axes) == 1 and row_axis is not None
            and not cols_sharded
            and config.tree_learner in ("data", "serial")
            and not multihost):
        kernel_axis = mesh_axes[0][0]
    row_shards = mesh_axes[0][1] if kernel_axis is not None else 1
    local_rows = rows_padded // row_shards
    pallas_ok = ((not on_mesh or kernel_axis is not None)
                 and (on_tpu or interpret)
                 and rows_padded % (1024 * row_shards) == 0)
    unhonourable = (
        f"hist_kernel={hk} cannot run here: it needs a single TPU "
        "device or a one-axis data mesh of them, and rows padded "
        "to 1024 a shard — use hist_kernel=auto, or "
        "force_pallas_interpret for the CPU test seam")
    if hk == "pallas" and not pallas_ok:
        # an explicit kernel request that cannot be honoured is an
        # error, not a quiet XLA run under the Pallas kernel's name
        raise ValueError(unhonourable)
    # float32 operands keep to the XLA formulation (the kernels run bf16
    # or int8 operands, the analog of the reference GPU learner's
    # single-precision default, gpu_tree_learner.cpp:73-77)
    use_pallas = pallas_ok and (
        hk == "pallas"
        or (hk == "auto" and config.hist_compute_dtype == "bfloat16"))
    # precision tier: "tiered" forces the int32 quantized-weight
    # accumulation (and is a loud error where it cannot run), "f32"
    # forces float32 accumulation, "auto" follows quantized_grad.  The
    # overflow bound lives in ONE place, check_quant_rows, next to the
    # kernel it protects: it bounds a row segment, and a shard of more
    # rows is accumulated in as many segments as it takes
    precision = str(config.hist_precision).lower()
    exchange = str(config.hist_exchange).lower()
    segments, segment_rows = quant_row_segments(local_rows)
    want_quant = bool(config.quantized_grad) or precision == "tiered"
    if precision == "f32":
        if want_quant:
            warnings.append("hist_precision=f32: quantized_grad ignored "
                            "— histograms accumulate float32")
        want_quant = False
    quant = use_pallas and want_quant
    if precision == "tiered" and not quant:
        raise ValueError(
            "hist_precision=tiered cannot run here: the quantized "
            "accumulation tier needs the Pallas histogram path "
            "(hist_compute_dtype=bfloat16 or hist_kernel=pallas on "
            "a single TPU device or a one-axis row mesh); use "
            "hist_precision=auto or f32")
    explicit = hk == "pallas" or precision == "tiered"

    tier = "xla"
    if use_pallas:
        ladder = quant and frontier <= LADDER_WIDTH
        if on_mesh:
            # a row mesh runs ONE kernel plan, the quantized fused
            # ladder: its accumulators are integers, so the shards' sum
            # is exact and the trees are the single device's
            if not ladder and explicit:
                raise ValueError(
                    unhonourable + "; under a mesh only the "
                    "quantized fused ladder runs (quantized_grad, "
                    "byte-wide bins, frontier_width <= "
                    f"{LADDER_WIDTH})")
            if ladder and exchange != "f32" and explicit:
                raise ValueError(
                    f"hist_exchange={exchange} cannot run "
                    "here: the kernel path under a mesh sums int32 "
                    "accumulators exactly and has no codec — drop it, "
                    "or use hist_kernel=xla")
            # hist_kernel=auto: what the ladder cannot honour runs on
            # the XLA path, where the codec lives
            if ladder and exchange == "f32":
                tier = "ladder"
        elif ladder:
            tier = "ladder"
        elif quant:
            wide = (f"quantized_grad with frontier_width={frontier}: the "
                    "int8 histogram ladder serves at most "
                    f"{LADDER_WIDTH} splits a round")
            if explicit:
                raise ValueError(unhonourable + "; " + wide)
            warnings.append(wide + "; using the XLA histogram "
                            "formulation (float32 accumulation)")
        else:
            tier = "float"

    onehot_pack = 0
    fused = tier == "ladder"
    if tier == "float":
        # the (N, G*B) int8 bin one-hot is constant for the whole run:
        # materialized once and streamed, where it fits the budget
        pack, ohb_bytes = _onehot_pack(rows_padded,
                                       num_groups * max_group_bin)
        if ohb_bytes <= ONEHOT_BUDGET_MB << 20:
            onehot_pack = pack
            # the fused route+histogram kernel needs a frontier that
            # fits the packed strip ladder
            fused = frontier <= LADDER_WIDTH
        else:
            warnings.append(
                f"resident one-hot ({ohb_bytes >> 20} MB at pack="
                f"{pack}) exceeds the {ONEHOT_BUDGET_MB} MB budget; "
                "using the slower on-the-fly rebuild "
                "(see docs/ROOFLINE.md regime table)")
            if packed_groups:
                # the expansion kernel rebuilds its one-hot straight
                # from byte-wide group columns; the XLA formulation
                # widens sub-byte bins per chunk
                warnings.append(
                    "bin_packing: the selected Pallas histogram kernel "
                    "has no nibble-packed input path; using the XLA "
                    "histogram formulation for this packed dataset"
                    " (different f32 accumulation order than the "
                    "selected kernel — trees may differ in ulps from "
                    "this config under bin_packing=8bit)")
                tier = "xla"

    mesh_kernels = on_mesh and tier == "ladder"
    if tier != "ladder":
        segments, segment_rows = 1, local_rows
    # a row block lies in one segment: it divides the segment as it
    # divides the shard (2^24 rows a segment: any block does)
    block_rows = math.gcd(local_rows, segment_rows)
    rungs = (factored_rungs(max_group_bin, packed_groups)
             if tier == "ladder" else ())
    # the factored kernel's accumulator is a whole-array output block,
    # which XLA keeps in VMEM outside the kernel's scoped allocation
    # (26 MB at 126 slots x 67 groups), so it takes the row block the
    # strips cannot (v5e, 2^24 x 67 x 255 bins: 4096 is 6-9% a pass
    # under 2048 on the narrow rungs and 1-2% on the wide ones; 8192
    # adds under 2% up to 64 slots and loses 10% at 126)
    block_tiled = _tiled_block(num_groups, max_group_bin, block_rows)
    block_factored = 4096 if block_rows % 4096 == 0 else block_tiled
    group_chunk = _group_chunk(rungs, num_groups, block_factored,
                               segments > 1)
    if group_chunk < num_groups:
        # the route kernel's block holds the split rows, not every group
        block_tiled = _tiled_block(ROUTE_ROWS, max_group_bin, block_rows)
    return HistPlan(
        tier=tier, interpret=interpret, row_axis=kernel_axis,
        row_shards=row_shards, local_rows=local_rows,
        mesh_kernels=mesh_kernels,
        # what one shard puts into the cross-shard sum: int32, twice as
        # two limbs where the global rows could leave int32
        exchange_limbs=(0 if not mesh_kernels
                        else 1 if quant_rows_ok(rows_padded)
                        and segments == 1 else 2),
        row_segments=segments, segment_rows=segment_rows,
        hist_exchange=exchange, fused=fused, onehot_pack=onehot_pack,
        block_float=(FLOAT_BLOCK if local_rows % FLOAT_BLOCK == 0
                     else 1024),
        block_tiled=block_tiled,
        block_factored=block_factored,
        group_chunk=group_chunk, num_groups=num_groups,
        # in force only where a group fills a 256-lane tile
        factored_rungs=rungs,
        compact_rungs=tuple(k for k, _, _ in rungs
                            if compact_shape(k, block_factored)),
        finder="xla" if tier == "xla" else "fused",
        warnings=tuple(warnings))
