"""On-device tree growth: the TPU-native serial tree learner.

Re-design of SerialTreeLearner's leaf-wise loop
(reference: src/treelearner/serial_tree_learner.cpp:156-220 Train,
:700-774 Split) for XLA's static-shape world.  One jitted function grows
a whole tree: a ``lax.while_loop`` over frontier rounds where each round
  1. refreshes the leaves created LAST round (queued in pend_*): builds
     histograms ONLY for the new right children in one MXU pass
     (ops/histogram.py, frontier-restricted), derives each left child
     as parent-minus-right — the reference's histogram subtraction
     trick (serial_tree_learner.cpp:505-507) with the histogram pool's
     role played by a fixed (L, G, B, 3) HBM cache — and runs the split
     finder on those 2*W leaves only, caching their best candidates
     (the best_split_per_leaf_ analog),
  2. splits every leaf whose cached candidate clears the gain bar
     (gain-ordered within the remaining leaf budget, so slot/node
     numbering matches the reference's sequential best-first allocation
     whenever the budget doesn't bind).  DOCUMENTED deviation: when the
     num_leaves cap truncates a round, batched selection can admit a
     leaf whose not-yet-grown nephew would have out-gained it under
     one-split-at-a-time best-first; exact order would cost num_leaves
     histogram passes per tree.  Growth ended by gain/min_data
     exhaustion is width-invariant (bit-identical trees), and the cap
     effect is metric-bounded at the bench config by
     tests/test_reference_parity.py::test_bench_config_255_leaf_parity,
  3. re-labels rows (ops/partition.py) and queues the new children for
     the next round — so the final round's children are never
     histogrammed at all (the while_loop exits first).
Zero host round-trips inside a tree; the boosting loop stays on device
too and only syncs for metric printing/early stopping.

Tree state is a fixed-size struct of arrays (the reference's Tree,
include/LightGBM/tree.h:352-391, is already array-of-nodes — here the
arrays live in HBM and are scattered into with `mode='drop'`).

The voting-parallel learner keeps the full-frontier formulation (every
active leaf re-histogrammed per round) because its per-round top-k
feature election is a collective over freshly built local histograms.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..backend import on_tpu
from ..config import Config
from ..dataset import Dataset
from ..ops.hist_plan import (LADDER_WIDTH, finder_block, finder_identity_map,
                              resolve_hist_plan)
from ..ops.histogram import (PACKED_STRIP, compute_group_histograms,
                             compute_group_histograms_fused,
                             compute_group_histograms_pallas,
                             compute_group_histograms_pre,
                             compute_group_histograms_pre_packed,
                             compute_leaf_totals, expand_feature_histograms,
                             precompute_bin_onehot,
                             precompute_bin_onehot_packed,
                             quantize_gradients)
from ..ops.partition import (apply_route_table, apply_splits,
                             build_route_table)
from ..ops.split import (CAND_CAT_DIR, CAND_COLS, CAND_DEFAULT_LEFT,
                         CAND_FEATURE, CAND_GAIN, CAND_LOUT, CAND_LSC,
                         CAND_LSG, CAND_LSH, CAND_ROUT, CAND_THRESHOLD,
                         FORCED_COLS, FORCED_DEFAULT_LEFT, FORCED_GAIN,
                         FORCED_LOUT, FORCED_LSC, FORCED_LSG, FORCED_LSH,
                         FORCED_ROUT, FORCED_THRESHOLD,
                         build_cat_bitset, find_best_split_block,
                         forced_split_block, run_split_finders)
from ..ops.split_kernel import Finder, finder_scans
from ..telemetry import TELEMETRY
from ..tree import TreeRecordLayout

NEG_INF = -jnp.inf


def _rows_i32(count):
    """A row count as the tree stores it: int32 (a float32 count is a
    whole number already, exact to 2^24)."""
    return jnp.round(count).astype(jnp.int32)


def _fit_slots(hist, width):
    """``hist`` (slots, G, B, 3), or its pair with the int32 counts,
    cut or zero-padded to ``width`` slots."""
    def fit(h):
        if h.shape[0] >= width:
            return h[:width]
        pad = jnp.zeros((width - h.shape[0],) + h.shape[1:], h.dtype)
        return jnp.concatenate([h, pad])
    return jax.tree_util.tree_map(fit, hist)


def _strip_widths(width):
    """Slots of one, two, ... packed strips, the last cut (or, past
    three strips, stretched) to the frontier's ``width``."""
    return [s for s in (PACKED_STRIP, 2 * PACKED_STRIP)
            if s < width] + [width]


def _get_row(hist, i):
    """Row ``i`` of ``hist`` (or of each of its pair), as (1, ...)."""
    return jax.tree_util.tree_map(
        lambda h: jax.lax.dynamic_index_in_dim(h, i, 0, keepdims=True),
        hist)


def _set_rows(hist, rows, i):
    """``hist`` with ``rows`` (of ``_get_row``'s shape, or more of them)
    written from row ``i`` on."""
    return jax.tree_util.tree_map(
        lambda h, r: jax.lax.dynamic_update_index_in_dim(h, r, i, 0),
        hist, rows)


def _scaled(hist, scales):
    """The dequantize multiply on ``hist``'s float32 part."""
    if isinstance(hist, tuple):
        return (hist[0] * scales[None, None, None, :],) + hist[1:]
    return hist * scales[None, None, None, :]


class TreeArrays(NamedTuple):
    """Device-side grown tree (fixed shapes; L leaf slots, M=L-1 nodes)."""
    num_leaves: jax.Array        # scalar int32 — actual leaves used
    leaf_value: jax.Array        # (L,) f32
    leaf_weight: jax.Array       # (L,) f32 (sum_hessian)
    leaf_count: jax.Array        # (L,) int32 rows
    leaf_parent: jax.Array       # (L,) int32 — parent internal node (-1 root)
    leaf_depth: jax.Array        # (L,) int32
    node_feature: jax.Array      # (M,) int32 inner feature idx
    node_threshold: jax.Array    # (M,) int32 bin threshold / num-cats-1
    node_default_left: jax.Array  # (M,) bool
    node_is_cat: jax.Array       # (M,) bool
    node_cat_mask: jax.Array     # (M, B) bool — feature-bin left set
    node_gain: jax.Array         # (M,) f32
    node_value: jax.Array        # (M,) f32 internal output
    node_weight: jax.Array       # (M,) f32
    node_count: jax.Array        # (M,) int32 rows
    node_left: jax.Array         # (M,) int32 (neg = ~leaf)
    node_right: jax.Array        # (M,) int32


class GrowerState(NamedTuple):
    leaf_id: jax.Array
    num_leaves: jax.Array        # scalar int32
    round_idx: jax.Array
    done: jax.Array
    leaf_sum_grad: jax.Array
    leaf_sum_hess: jax.Array
    leaf_count: jax.Array        # (L,) f32, or int32 (plan.int_counts)
    leaf_min_c: jax.Array
    leaf_max_c: jax.Array
    leaf_is_left: jax.Array      # (L,) bool — side under its parent
    leaf_forced: jax.Array       # (L,) int32 forced-split spec idx (-1 none)
    tree: TreeArrays
    hist_cache: jax.Array        # (L, G, Bg, 3) f32 — per-leaf group hists
    # (under plan.int_counts hist_cache, each of hist_stage, cand and
    # forced_cand are a pair of the array named here and its int32 row
    # counts: (L, G, Bg), (W, G, Bg), (L,))
    hist_stage: Tuple[jax.Array, jax.Array]   # 2 x (W, G, Bg, 3) f32 — the
    # rows a round's refresh hands the cache: the left children's, the
    # right children's (_refresh)
    cand: jax.Array              # (L, CAND_COLS + Bf) f32 — the packed
    # best_split_per_leaf_ cache (reference serial_tree_learner.h +
    # SplitInfo, split_info.hpp:18-288); column layout in ops/split.py,
    # refreshed with ONE width-bounded scatter per round
    forced_cand: jax.Array       # (L, FORCED_COLS) f32 — cached forced-
    # split evaluation (ForceSplits, serial_tree_learner.cpp:543-698)
    pend_parents: jax.Array      # (W,) slots whose hist/cands are stale
    pend_rights: jax.Array       # (W,) — refreshed at the NEXT round's
    # start (so the final round's refresh is never computed at all)
    route_tab: jax.Array         # (L, 15+nb) f32 PENDING route table
    # (fused-kernel path: the splits selected this round re-label rows
    # lazily inside the next round's histogram kernel; all-zero = no-op)


def _get_shard_map():
    """``jax.shard_map`` with replication checking off — ONE
    definition for every learner path."""
    return functools.partial(jax.shard_map, check_vma=False)


def _encode_leaf(leaf_slot):
    """LightGBM child encoding: ~leaf (negative) marks a leaf index."""
    return -(leaf_slot + 1)


class TreeGrower:
    """Builds and caches the jitted per-tree training function for one
    Dataset + Config combination.

    Distributed modes (tree_learner=data/feature/voting) work through
    the ShardingPolicy: the bin matrix is placed sharded over the mesh
    and the histogram output constrained, after which XLA inserts the
    reduce-scatter/all-gather the reference's Network layer hand-codes
    (see parallel/mesh.py).  On a one-axis row mesh the data learner
    runs the single-device kernel plan instead: the quantized fused
    ladder once per row shard inside ``shard_map`` and an exact integer
    sum of the shards' accumulators (``_on_row_shards``), so its trees
    are the single device's."""

    def __init__(self, dataset: Dataset, config: Config, policy=None):
        # set-up stages (docs/OBSERVABILITY.md): "grower_init" is the
        # constructor less the "upload" and "binsT" stages inside it
        with TELEMETRY.stage("grower_init"):
            self._setup(dataset, config, policy)

    def _setup(self, dataset: Dataset, config: Config, policy) -> None:
        from ..parallel.mesh import ShardingPolicy, build_mesh
        if policy is None:
            policy = ShardingPolicy(config, build_mesh(config))
        self.policy = policy
        self.config = config
        self.num_leaves = config.num_leaves
        self.max_group_bin = dataset.max_group_bin
        self.max_feature_bin = dataset.max_feature_bin
        self.num_groups = dataset.num_groups
        self.num_features = dataset.num_features
        # sub-byte-packed bin matrix (lightgbm_tpu/packing.py): the
        # device matrix IS the storage matrix — kernels widen crumbs
        # and nibbles in-register, so HBM capacity AND the histogram
        # read stream shrink 2-4x for fully packed datasets.
        # ``pack_P`` carries the static PACK SPEC (pack_spec(P, C));
        # a crumb-free layout encodes to plain P and a 0 means the
        # legacy 8-bit layout — every pre-crumb code path (and its
        # compiled-cache key) lowers exactly as before.
        _lay = getattr(dataset, "bin_layout", None)
        self.pack_P = _lay.device_spec if _lay is not None else 0

        meta = dataset.feature_meta_arrays()
        self.f_num_bin = jnp.asarray(meta["num_bin"])
        self.f_default_bin = jnp.asarray(meta["default_bin"])
        self.f_missing = jnp.asarray(meta["missing_type"])
        self.f_is_cat = jnp.asarray(meta["is_categorical"])
        self.f_monotone = jnp.asarray(meta["monotone"])
        self.f_group = jnp.asarray(
            np.array([f.group for f in dataset.features], dtype=np.int32))
        self.has_categorical = bool(meta["is_categorical"].any())

        bin_map, fix_bin = dataset.feature_bin_maps()
        self.bin_map = jnp.asarray(bin_map)
        self.fix_bin = jnp.asarray(fix_bin)
        lo, hi, shift, oor, dense_g2f = self._build_g2f_affine(dataset)
        self.f_gb_lo = jnp.asarray(lo)
        self.f_gb_hi = jnp.asarray(hi)
        self.f_gb_shift = jnp.asarray(shift)
        self.f_gb_oor = jnp.asarray(oor)
        # dense (F, GB) form kept for the binned predict path
        self.g2f_lut = jnp.asarray(dense_g2f)

        self.cfg_scalars: Dict[str, float] = dict(
            lambda_l1=config.lambda_l1, lambda_l2=config.lambda_l2,
            max_delta_step=config.max_delta_step,
            min_data_in_leaf=float(config.min_data_in_leaf),
            min_sum_hessian_in_leaf=config.min_sum_hessian_in_leaf,
            min_gain_to_split=config.min_gain_to_split,
            cat_smooth=config.cat_smooth, cat_l2=config.cat_l2,
            max_cat_threshold=config.max_cat_threshold,
            max_cat_to_onehot=config.max_cat_to_onehot,
            min_data_in_group=float(config.min_data_in_group),
        )
        self.max_depth = config.max_depth
        # hard bound on frontier rounds (the while_loop exits early when
        # no leaf splits)
        self.max_rounds = config.num_leaves - 1
        # frontier width: max splits applied per round.  126 = 3 strips
        # of the channel-packed histogram kernel (3 x PACKED_STRIP).
        # 84 (2 strips) is ~0.7 ms/tree faster at the 1M binary bench
        # shape with AUC unchanged, but was measured to cost 0.06
        # held-out NDCG@10 at the MS-LTR bench shape (0.266 vs 0.328,
        # 255 leaves) — growth order near the leaf cap is quality-
        # neutral for the binary task but NOT for lambdarank, so the
        # default stays at the widest packed ladder and the knob is
        # left to users who know their task tolerates it.
        self.frontier = min(config.num_leaves - 1,
                            config.frontier_width or LADDER_WIDTH)
        # a round's whole refresh at the width of the rung that served
        # its histogram pass (_refresh; False: at the frontier cap)
        self.split_ladder = bool(getattr(config, "split_finder_ladder",
                                         True))
        # packed tree-record carry (round 7): fixed-offset byte layout
        # the fused dispatch scan carries as ONE output stack
        self.record_layout = TreeRecordLayout(self.num_leaves,
                                              self.max_feature_bin)

        # histogram memory governance (reference histogram_pool_size,
        # config.h:216 + HistogramPool LRU): when the per-leaf cache
        # exceeds the budget, drop histogram subtraction and compute
        # BOTH children of every split directly (2x histogram passes,
        # no (L, G, B, 3) cache)
        cache_mb = (self.num_leaves * self.num_groups *
                    self.max_group_bin * 3 * 4) / (1 << 20)
        pool = float(getattr(config, "histogram_pool_size", -1.0))
        self.use_hist_cache = pool < 0 or cache_mb <= pool
        if not self.use_hist_cache:
            from ..utils.log import Log as _Log
            _Log.warning(
                f"histogram cache ({cache_mb:.0f} MB) exceeds "
                f"histogram_pool_size ({pool:.0f} MB); disabling "
                "histogram subtraction (children computed directly — "
                "~2x histogram passes)")

        # forced splits (reference serial_tree_learner.cpp:543-698
        # ForceSplits): JSON tree flattened to spec arrays; leaves carry
        # a spec index through growth and split at the forced
        # (feature, threshold) with top priority before gain ordering
        self.forced_count = 0
        self._load_forced_splits(dataset, config)

        # pad rows to a histogram-chunk multiple once, host-side
        n = dataset.num_data
        from ..ops.histogram import _pick_chunk
        cdt = jnp.dtype(config.hist_compute_dtype)
        self.chunk = _pick_chunk(n, self.num_groups, self.max_group_bin,
                                 cdt.itemsize,
                                 min_chunk=8192 if on_tpu() else 1024)
        self.num_data = n
        # multi-host: this process holds only ITS row shard of the bin
        # matrix (parallel/distributed.py finalize_global); every host
        # pads its shard to a whole chunk multiple and the global
        # layout interleaves per-host padding blocks (host0 rows,
        # host0 pad, host1 rows, ...).  pad_rows() reproduces that
        # layout for global metadata arrays.
        self._mh_local: Optional[int] = getattr(
            dataset, "_mh_local_rows", None) if getattr(
                dataset, "_multihost", False) else None
        with TELEMETRY.stage("upload"):
            if self._mh_local is not None:
                self._mh_nproc = max(1, self.policy.nproc)
                per_host = ((self._mh_local + self.chunk - 1)
                            // self.chunk) * self.chunk
                self._mh_per_host = per_host
                self.n_padded = per_host * self._mh_nproc
                loc_pad = per_host - self._mh_local
                bins_local = np.concatenate(
                    [dataset.group_bins,
                     np.zeros((loc_pad, dataset.group_bins.shape[1]),
                              dtype=np.uint8)])
                self.bins = self.policy.place_local_rows(bins_local)
                self._row_valid = self.policy.place_local_rows(
                    np.concatenate([np.ones(self._mh_local, bool),
                                    np.zeros(loc_pad, bool)]))
            else:
                self.n_padded = ((n + self.chunk - 1)
                                 // self.chunk) * self.chunk
                pad = self.n_padded - n
                shard_bins = getattr(dataset, "shard_bins", None)
                if shard_bins:
                    # sharded-construct dataset (lightgbm_tpu/sharded/):
                    # per-participant shards are placed straight onto
                    # their mesh devices; the logical global layout (rows
                    # in order, tail pad) is identical to the
                    # single-matrix route, so the compiled program and
                    # the trained trees are byte-identical across routes
                    self.bins = self.policy.place_row_shards(shard_bins,
                                                             self.n_padded)
                else:
                    bins_np = dataset.group_bins
                    if pad:
                        bins_np = np.concatenate(
                            [bins_np,
                             np.zeros((pad, bins_np.shape[1]),
                                      dtype=np.uint8)])
                    self.bins = self.policy.place_bins(bins_np)
                self._row_valid = self.policy.place_rows(
                    np.concatenate([np.ones(n, bool), np.zeros(pad, bool)]))
            # the stage ends when the matrix is on the device;
            # nothing but Python overlaps the transfer today
            TELEMETRY.stage_fence((self.bins, self._row_valid))
        # which histogram kernels run: decided once, from facts, by
        # ops/hist_plan.py (the only reader of hist_kernel,
        # hist_precision and hist_exchange)
        from ..utils.log import Log
        mesh = self.policy.mesh
        self.plan = resolve_hist_plan(
            config, on_tpu=on_tpu(),
            mesh_axes=None if mesh is None else tuple(
                zip(mesh.axis_names, mesh.devices.shape)),
            row_axis=(self.policy.row_spec[0]
                      if mesh is not None
                      and self.policy.row_spec is not None else None),
            cols_sharded=getattr(self.policy, "bins_spec",
                                 None) is not None,
            multihost=(self._mh_local is not None
                       or self.policy.multihost),
            rows_padded=self.n_padded, num_groups=self.num_groups,
            max_group_bin=self.max_group_bin, packed_groups=self.pack_P,
            frontier=self.frontier)
        for message in self.plan.warnings:
            Log.warning(message)
        # the numerical split finder's form, the plan's, with the host
        # facts its fused form is specialised on; and whether it may
        # read the group histogram itself (a property of this table)
        self.finder = Finder(
            self.plan.finder,
            finder_scans(meta["num_bin"], meta["missing_type"]),
            self.plan.interpret)
        self.finder_identity = finder_identity_map(
            bin_map, fix_bin, self.num_groups, self.max_group_bin,
            self.has_categorical, bool(self.forced_count))
        # transposed on DEVICE from the already-uploaded bins: a host
        # transpose + second upload of the (N, G) matrix doubles the
        # host->device traffic at the 10.5M scale
        self.binsT = None
        if self.plan.fused:
            with TELEMETRY.stage("binsT"):
                self.binsT = jnp.transpose(self.bins)
                TELEMETRY.stage_fence(self.binsT)
        self._route_cols = 15 + (self.max_feature_bin + 7) // 8
        # the float tier's resident one-hot.  Trace-scoped override:
        # callers thread it through their jit boundary as an ARGUMENT (a
        # multi-hundred-MB closure constant sends XLA's constant-folding
        # passes into minutes of compile time); _train_tree_impl pins
        # the traced value here for the dynamic extent of its trace
        self.ohb = None
        self._ohb_arg = None
        if self.plan.onehot_pack == 1:
            self.ohb = precompute_bin_onehot(
                self.bins, max_group_bin=self.max_group_bin,
                packed_groups=self.pack_P)
        elif self.plan.onehot_pack:
            self.ohb = precompute_bin_onehot_packed(
                self.bins, max_group_bin=self.max_group_bin,
                pack=self.plan.onehot_pack, packed_groups=self.pack_P)
        self._is_voting = (self.policy.mesh is not None
                           and config.tree_learner == "voting")
        # feature-parallel shard_map path: vertical partition with a
        # SplitInfo-only election — needs the group count to divide
        # the mesh (otherwise the constraint-sharded fallback runs,
        # which exchanges histograms)
        # bins_spec presence means the policy actually took the
        # feature (vertical-partition) branch — a 'data'-axis mesh
        # with tree_learner=feature must NOT run the shard_map
        # election against row-sharded inputs
        self._is_feature_par = (
            self.policy.mesh is not None
            and config.tree_learner == "feature"
            and getattr(self.policy, "bins_spec", None) is not None
            and self.num_groups % self.policy.mesh.size == 0
            # vertical partition slices storage COLUMNS; a nibble-
            # packed byte straddles two logical groups, so packed
            # datasets take the constraint-sharded fallback instead
            and self.pack_P == 0)
        self._train_tree = jax.jit(self._train_tree_impl)
        if TELEMETRY.on:
            # the grower's resolved kernel plan as gauges: the fused
            # device phases cannot be host-timed per iteration (one
            # compiled program), so telemetry records WHAT was selected
            # — device time per phase comes from a profiler trace and
            # the tel.<phase> scopes (docs/OBSERVABILITY.md)
            plan = self.plan
            TELEMETRY.gauge("grower.hist_kernel", plan.kernel)
            TELEMETRY.gauge("grower.hist_factored_rungs", ",".join(
                f"{k}:{a}x{b}" for k, a, b in plan.factored_rungs))
            TELEMETRY.gauge("grower.hist_compact_rungs", ",".join(
                map(str, plan.compact_rungs)))
            TELEMETRY.gauge("grower.quantized", int(plan.quantized))
            # the split finder: its form, whether it reads the group
            # histogram itself, the scans it traces, and the fused
            # form's (leaf rows x features) block at the widest refresh
            TELEMETRY.gauge("grower.split_finder", plan.finder)
            TELEMETRY.gauge("grower.finder_identity_map",
                            int(self.finder_identity))
            TELEMETRY.gauge("grower.finder_scans", self.finder.scans)
            TELEMETRY.gauge("grower.finder_block", "x".join(map(
                str, finder_block(
                    2 * self.frontier, self.max_feature_bin,
                    self.finder.scans, plan.int_counts))))
            # the factored kernel's group axis: one chunk (every group a
            # grid step), or a grid axis of chunks
            TELEMETRY.gauge("grower.num_groups", int(self.num_groups))
            TELEMETRY.gauge("grower.hist_group_chunks",
                            int(plan.group_chunks))
            TELEMETRY.gauge("grower.hist_group_chunk",
                            int(plan.group_chunk))
            TELEMETRY.gauge("grower.hist_cache_mb", round(
                cache_mb if self.use_hist_cache else cache_mb
                / self.num_leaves, 3))
            TELEMETRY.gauge("grower.hist_precision",
                            "tiered" if plan.quantized else "f32")
            # resolved device bin-matrix footprint: rows_padded x
            # storage byte columns — THE gauge the compact-bins
            # acceptance measures (<= 0.55x of 8-bit at max_bin=15,
            # <= 0.30x for a fully crumb-packed 2-bit matrix)
            TELEMETRY.gauge("bin_matrix_bytes",
                            int(np.prod(self.bins.shape)))
            from ..packing import spec_crumb, spec_packed
            TELEMETRY.gauge("grower.bin_packed_groups",
                            spec_packed(self.pack_P))
            TELEMETRY.gauge("grower.bin_crumb_groups",
                            spec_crumb(self.pack_P))
            TELEMETRY.gauge("grower.split_finder_ladder",
                            int(self.split_ladder))
            TELEMETRY.gauge("grower.frontier_width", int(self.frontier))
            TELEMETRY.gauge("grower.rows_padded", int(self.n_padded))
            TELEMETRY.gauge("grower.row_shards", int(plan.row_shards))
            TELEMETRY.gauge("grower.local_rows", int(plan.local_rows))
            # int32 accumulators a pass writes on a device and the rows
            # of one (1: no fold), and whether row counts are int32
            TELEMETRY.gauge("grower.hist_row_segments",
                            int(plan.row_segments))
            TELEMETRY.gauge("grower.hist_segment_rows",
                            int(plan.segment_rows))
            TELEMETRY.gauge("grower.int_counts", int(plan.int_counts))
            # what one shard puts into the cross-shard sum of the
            # widest pass: (W, G, B, 3) int32, twice as two limbs
            TELEMETRY.gauge(
                "grower.hist_exchange_bytes_widest",
                int(self.frontier * self.num_groups * self.max_group_bin
                    * 3 * 4 * plan.exchange_limbs))

    # ------------------------------------------------------------------
    def _load_forced_splits(self, dataset: Dataset, config: Config) -> None:
        """Parse forcedsplits_filename into flat device spec arrays:
        feature (inner idx), threshold (bin), left/right child spec
        index.  Real-valued thresholds convert through the feature's
        BinMapper (the reference's Dataset::BinThreshold)."""
        fn = getattr(config, "forcedsplits_filename", "")
        if not fn:
            return
        import json as _json
        from ..utils.log import Log
        with open(fn) as f:
            spec = _json.load(f)
        if not spec:
            return
        if config.tree_learner == "voting":
            Log.warning("forced splits are not supported with "
                        "tree_learner=voting; ignoring %s" % fn)
            return
        real2inner = {f.feature_idx: j
                      for j, f in enumerate(dataset.features)}
        nodes: list = []

        def rec(node) -> int:
            real_f = int(node["feature"])
            j = real2inner.get(real_f)
            if j is None:
                Log.warning("forced split on unused feature %d ignored"
                            % real_f)
                return -1
            mapper = dataset.features[j].mapper
            thr_bin = int(np.asarray(mapper.value_to_bin(
                np.array([float(node["threshold"])]))).ravel()[0])
            idx = len(nodes)
            nodes.append([j, thr_bin, -1, -1])
            if isinstance(node.get("left"), dict):
                nodes[idx][2] = rec(node["left"])
            if isinstance(node.get("right"), dict):
                nodes[idx][3] = rec(node["right"])
            return idx

        if rec(spec) < 0:
            return
        arr = np.asarray(nodes, dtype=np.int32)
        self.forced_count = len(nodes)
        self.forced_feature = jnp.asarray(arr[:, 0])
        self.forced_thr = jnp.asarray(arr[:, 1])
        self.forced_left = jnp.asarray(arr[:, 2])
        self.forced_right = jnp.asarray(arr[:, 3])

    # ------------------------------------------------------------------
    @staticmethod
    def _build_g2f_affine(dataset: Dataset):
        """Per-feature affine group-bin -> feature-bin map
        ``fb = gb - shift if lo <= gb < hi else oor``.

        This is the scalar form of the reference's min_bin/max_bin/bias
        routing in DenseBin::Split (dense_bin.hpp:191-283): a feature's
        bins occupy one contiguous group-bin range (identity for a
        group it owns alone; offset for EFB bundle members whose
        default collapsed into the shared slot 0), everything else
        routes to the default bin.  Verified exhaustively against the
        dense (F, GB) table at construction.
        """
        F = dataset.num_features
        GB = dataset.max_group_bin
        lo = np.zeros(F, dtype=np.int32)
        hi = np.zeros(F, dtype=np.int32)
        shift = np.zeros(F, dtype=np.int32)
        oor = np.zeros(F, dtype=np.int32)
        for j, f in enumerate(dataset.features):
            if not f.collapsed_default:
                lo[j], hi[j] = 0, f.num_bin
                shift[j], oor[j] = 0, f.num_bin - 1
            else:
                adj = 1 if f.mapper.default_bin == 0 else 0
                lo[j] = f.offset
                hi[j] = f.offset + f.num_bin - adj
                shift[j] = f.offset - adj
                oor[j] = f.default_bin
        # cross-check against the dense table the affine form replaces
        gb_iota = np.arange(GB, dtype=np.int32)[None, :]
        affine = np.where(
            (gb_iota >= lo[:, None]) & (gb_iota < hi[:, None]),
            gb_iota - shift[:, None], oor[:, None])
        dense = np.zeros((F, GB), dtype=np.int32)
        for j, f in enumerate(dataset.features):
            if not f.collapsed_default:
                dense[j] = np.minimum(np.arange(GB), f.num_bin - 1)
            else:
                dense[j, :] = f.default_bin
                adj = 1 if f.mapper.default_bin == 0 else 0
                for b in range(f.num_bin):
                    if b == f.mapper.default_bin:
                        continue
                    gb = b + f.offset - adj
                    if gb < GB:
                        dense[j, gb] = b
        if not np.array_equal(affine, dense):  # pragma: no cover
            bad = np.argwhere(affine != dense)
            raise AssertionError(
                f"affine g2f map diverges from dense table at {bad[:5]}")
        return lo, hi, shift, oor, dense

    # ------------------------------------------------------------------
    def pad_rows(self, arr: np.ndarray, fill=0.0) -> np.ndarray:
        """Pad a global row array to n_padded.  Multi-host: padding is
        interleaved per host to match the assembled shard layout."""
        if self._mh_local is not None:
            nl, ph = self._mh_local, self._mh_per_host
            pad_shape = (ph - nl,) + tuple(arr.shape[1:])
            parts = []
            for h in range(self._mh_nproc):
                parts.append(arr[h * nl:(h + 1) * nl])
                parts.append(np.full(pad_shape, fill, dtype=arr.dtype))
            return np.concatenate(parts)
        pad = self.n_padded - self.num_data
        if pad == 0:
            return arr
        return np.concatenate([arr, np.full(pad, fill, dtype=arr.dtype)])

    # ------------------------------------------------------------------
    def train_tree(self, grad: jax.Array, hess: jax.Array,
                   counts: jax.Array, feature_mask: jax.Array,
                   qkey=None
                   ) -> Tuple[TreeArrays, jax.Array, Optional[jax.Array]]:
        """Grow one tree.  grad/hess/counts are (n_padded,) with zeros
        for out-of-bag and padded rows.  ``qkey`` enables stochastic
        quantization rounding (see quantize_gradients).  Returns
        (tree, final leaf_id, per-row post-route leaf value or None —
        see _train_tree_inner)."""
        return self._train_tree(grad, hess, counts, feature_mask,
                                self.ohb, self.bins, self.binsT,
                                self._row_valid, qkey)[:3]

    # ------------------------------------------------------------------
    def _hist_kernel(self, grad, hess, counts, leaf_id, slots):
        """The histogram pass of the plans that have no ladder of their
        own: the float tier's plain kernel on one chip, the XLA one-hot
        contraction under meshes / CPU simulation.  ``slots`` wide; it
        routes no row (``_pass_ladder`` holds the passes that do, and
        the packed ones)."""
        L = self.num_leaves
        if self.plan.tier == "float":
            return compute_group_histograms_pallas(
                self.bins, grad, hess, counts, leaf_id,
                num_leaves=L, max_group_bin=self.max_group_bin,
                slots=slots)
        if self.policy.mesh is not None \
                and self.policy.row_spec is not None:
            return self._hist_xla_rowsharded(grad, hess, counts,
                                             leaf_id, slots, L)
        return compute_group_histograms(
            self.bins, grad, hess, counts, leaf_id,
            num_leaves=L, max_group_bin=self.max_group_bin,
            compute_dtype=self.config.hist_compute_dtype,
            chunk=self.chunk, slots=slots, packed_groups=self.pack_P)

    # ------------------------------------------------------------------
    def _hist_xla_rowsharded(self, grad, hess, counts, leaf_id, slots, L):
        """Row-sharded histogram via shard_map: each shard runs the
        chunked local scan over ITS rows, then one hist-sized psum —
        the reference's Network::ReduceScatter of per-pass histograms
        (data_parallel_tree_learner.cpp:147-162).  Explicit collectives
        instead of GSPMD propagation: letting the partitioner chase the
        scan's (num_chunks, chunk, G) reshape over row-sharded inputs
        produced involuntary full rematerializations (round-3 verdict
        weak#2) — row-scale all-gathers inside the while body."""
        from jax.sharding import PartitionSpec as P
        shard_map = _get_shard_map()

        mesh = self.policy.mesh
        axis = self.policy.row_spec[0]
        nshards = mesh.shape[axis]
        local_n = self.n_padded // nshards
        # the largest chunk dividing the local rows that stays within
        # the one-hot working-set target
        target = max(1, self.chunk)
        k = max(1, -(-local_n // target))
        while local_n % k:
            k += 1
        chunk_local = local_n // k

        spec_rows = P(axis)

        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P(axis, None), spec_rows, spec_rows, spec_rows,
                      spec_rows, P()),
            out_specs=P())
        def inner(bins, g, h, c, lid, sl):
            local = compute_group_histograms(
                bins, g, h, c, lid, num_leaves=L,
                max_group_bin=self.max_group_bin,
                compute_dtype=self.config.hist_compute_dtype,
                chunk=chunk_local, slots=sl,
                packed_groups=self.pack_P)
            # per-pass cross-shard sum under the hist_exchange codec
            # (parallel/collectives.py): "f32" lowers to the exact
            # legacy psum; "q16"/"q8" ship delta-coded integers and
            # reconstruct the f32 histogram here, BEFORE the
            # FixHistogram / parent-subtraction step downstream
            from ..parallel.collectives import exchange_histograms
            return exchange_histograms(local, axis,
                                       mode=self.plan.hist_exchange,
                                       world=int(nshards))

        return inner(self.bins, grad, hess, counts, leaf_id, slots)

    # ------------------------------------------------------------------
    def _pass_ladder(self, st: "GrowerState", W, grad, hess, counts,
                     quant):
        """The histogram pass of a round as a ladder: ``[(w, kernel)]``,
        the widths ascending to ``W``.  ``kernel(leaf_id, slots)`` is one
        pass over the rows, ``(histogram of the first w slots — (w, G, B,
        3), or its pair with the int32 counts — , leaf ids after it)``;
        ``_refresh`` takes the narrowest width that covers a round's
        valid slots (the early rounds of EVERY tree have 1-2 new leaves)
        and does everything else of the round at that width too.

        Fused plans: the pending route (last round's splits) is applied
        INSIDE the kernel just before each row contributes, so the leaf
        ids come back re-labelled (a second pass re-applies the route,
        which is idempotent).  Their widths are the factored rungs'
        slot caps (256-lane tiles only: the bin index is split across
        both sides of the dot, so a pass streams the rows its active
        slots need and not whole strips; ops/histogram.py
        FACTORED_RUNGS) and, past the widest rung that fits the
        frontier, the packed strips'.  The float tier's streamed
        one-hot: the strips' widths, channel-packed while the frontier
        is narrow (3x fewer MXU rows), and its plain kernel past three
        strips.

        Returned with the ladder: the scales of a dequantize multiply
        that ``_refresh`` is to make itself, row by row beside
        parent - right, or None.  XLA:CPU contracts a multiply with the
        subtraction it is fused with; a frontier of one strip or less
        never had a ladder between the two, and its floats stay those."""
        B = self.max_group_bin
        plan = self.plan
        ohb = self._ohb_arg if self._ohb_arg is not None else self.ohb
        late = None
        strips = list(enumerate(_strip_widths(W), 1))   # (count, slots)

        if not plan.fused:
            w = jnp.stack([grad, hess, counts], axis=1)

            def packed(strips):
                def go(leaf_id, slots):
                    return compute_group_histograms_pre_packed(
                        ohb, w, leaf_id, slots, max_group_bin=B,
                        block=plan.block_float, strips=strips,
                        pack=plan.onehot_pack,
                        num_groups=self.num_groups), leaf_id
                return go

            def full(leaf_id, slots):
                return compute_group_histograms_pre(
                    ohb, w, leaf_id, num_leaves=self.num_leaves,
                    max_group_bin=B, block=plan.block_float,
                    slots=slots, pack=plan.onehot_pack,
                    num_groups=self.num_groups), leaf_id

            ladder = [(w, packed(n)) for n, w in strips]
            if W > LADDER_WIDTH:
                ladder[-1] = (W, full)
            return ladder, None

        if quant is not None:
            if TELEMETRY.on:
                # trace-time accounting (the _note_collective pattern):
                # every quantized histogram pass ends in an f32
                # dequantize fix-up before split finding — inside jit
                # this counts once per trace, i.e. "fix-up passes per
                # compiled step"
                TELEMETRY.add("hist_quant_fixup", 1)
            wT, scales = quant                          # (3, N) int32
            if W <= PACKED_STRIP:
                late, scales = scales, jnp.ones_like(scales)
        else:
            wT = jnp.stack([grad, hess, counts], axis=0)
            scales = None

        def strips_pass(strips):
            def go(leaf_id, slots):
                if plan.tier == "ladder":
                    from ..ops.histogram import \
                        compute_group_histograms_fused_tiled
                    return self._on_row_shards(
                        functools.partial(
                            compute_group_histograms_fused_tiled,
                            max_group_bin=B,
                            block=plan.block_tiled, strips=strips,
                            interpret=plan.interpret,
                            packed_groups=self.pack_P),
                        self.binsT, wT, scales, leaf_id,
                        st.route_tab, slots)
                return compute_group_histograms_fused(
                    ohb, self.binsT, wT, leaf_id,
                    st.route_tab, slots, max_group_bin=B,
                    block=plan.block_float, strips=strips,
                    interpret=plan.interpret, pack=plan.onehot_pack,
                    num_groups=self.num_groups,
                    packed_groups=self.pack_P)
            return go

        def factored_pass(k_cap, a):
            def go(leaf_id, slots):
                from ..ops.histogram import (
                    compact_shape, compute_group_histograms_fused_factored)
                return self._on_row_shards(
                    functools.partial(
                        compute_group_histograms_fused_factored,
                        max_group_bin=B, k_cap=k_cap, a=a,
                        block=plan.block_factored,
                        interpret=plan.interpret,
                        group_chunk=plan.group_chunk,
                        compact=compact_shape(k_cap, plan.block_factored)
                        if k_cap in plan.compact_rungs else ()),
                    self.binsT, wT, scales, leaf_id, st.route_tab,
                    slots)
            return go

        ladder = [(k_cap, factored_pass(k_cap, a))
                  for k_cap, a, _ in plan.factored_rungs if k_cap <= W]
        widest = ladder[-1][0] if ladder else 0
        # where the rungs serve every count of active slots there can
        # be, the strips are not traced
        return ladder + [(w, strips_pass(n)) for n, w in strips
                         if widest < w], late

    # ------------------------------------------------------------------
    def _on_row_shards(self, kernel, binsT, wT, scales, leaf_id,
                       route_tab, slots):
        """One fused pass of the ladder.  On one device of one row
        segment ``kernel`` (a ``compute_group_histograms_fused_*`` with
        its static arguments bound) is called as it always was.  Under
        a row mesh every shard runs the same ``pallas_call`` on its own
        columns of ``binsT`` / ``wT`` and its own leaf ids, and hands
        the int32 accumulators — not yet dequantized — to the exact
        cross-shard sum; a device that holds more rows than one int32
        accumulator sums (``plan.row_segments`` > 1, under a mesh or
        not) gets one accumulator a segment from the kernel and the
        same exact sum folds them.  The dequantize multiply then meets
        one histogram, replicated under a mesh, as does everything
        downstream of it, and the histogram comes back as a pair with
        the sum's int32 row counts (plan.int_counts).  ``route_tab`` and
        ``slots`` are replicated, so every shard takes the same rung of
        the ``lax.cond`` ladder this is called from."""
        plan = self.plan
        if not plan.int_counts:
            return kernel(binsT, wT, scales, leaf_id, route_tab, slots)
        from jax.sharding import PartitionSpec as P
        from ..parallel import collectives
        axis = plan.row_axis if plan.mesh_kernels else None

        def shard(bT, w, lid, rt, sl):
            acc, leaf2 = kernel(bT, w, None, lid, rt, sl,
                                dequantize=False,
                                segment_rows=plan.segment_rows)
            with (TELEMETRY.phase("hist_fold") if axis is None
                  else TELEMETRY.phase("hist_exchange")):
                # looked up on the module at trace time, so that a
                # fault planted there (perfbench/tests) is in the sum
                total, rows_i32 = collectives.exchange_int_histograms(
                    acc, axis, global_rows=self.n_padded,
                    segments=plan.row_segments)
            return total, rows_i32, leaf2

        if axis is not None:
            cols, rows, rep = P(None, axis), P(axis), P()
            shard = _get_shard_map()(
                shard, mesh=self.policy.mesh,
                in_specs=(cols, cols, rows, rep, rep),
                out_specs=(rep, rep, rows))
        total, rows_i32, leaf2 = shard(binsT, wT, leaf_id, route_tab,
                                       slots)
        return (_scaled(total, scales), rows_i32), leaf2

    # ------------------------------------------------------------------
    def _pass_rounds(self, arrs: Dict[str, np.ndarray]) -> List[List[int]]:
        """``[splits, rows of their right children]`` of every round of
        one grown tree that a histogram pass follows, read off the tree
        on the host (``arrs``: its ``TreeArrays`` as numpy).

        A round splits every leaf it can up to the frontier's width, in
        node order, so split ``s`` is in the round after its parent's,
        or in a later one where that one was full; a round's right
        children are the slots of the next round's pass, which is made
        unless the tree ended on its leaf budget; and a right child's
        rows are its count in the tree (the rows in the bag, where there
        is one)."""
        m = int(arrs["num_leaves"]) - 1
        if m <= 0:
            return []
        right = arrs["node_right"][:m]
        rows = np.where(right < 0, arrs["leaf_count"][~np.minimum(right, -1)],
                        arrs["node_count"][np.maximum(right, 0)])
        parent = np.full(m, -1)
        for side in (arrs["node_left"][:m], right):
            parent[side[side >= 0]] = np.nonzero(side >= 0)[0]
        rounds = []                     # [slots, their rows] a round
        round_of = np.zeros(m, np.int64)
        for s in range(m):
            r = 0 if s == 0 else max(round_of[s - 1],
                                     round_of[parent[s]] + 1)
            if r < len(rounds) and rounds[r][0] == self.frontier:
                r += 1
            if r == len(rounds):
                rounds.append([0, 0])
            round_of[s] = r
            rounds[r][0] += 1
            rounds[r][1] += int(rows[s])
        if m + 1 >= self.num_leaves:
            rounds.pop()                # no pass follows the budget's end
        return rounds

    def _factored_rung(self, slots: int):
        """The factored rung that serves a pass of ``slots`` slots, or
        None where the strips do."""
        return next((k for k, _, _ in self.plan.factored_rungs
                     if slots <= k <= self.frontier), None)

    def compact_pass_rows(self, arrs: Dict[str, np.ndarray]
                          ) -> Tuple[int, int]:
        """``(rows of an active slot, rows streamed)`` of one grown
        tree's passes on the compacting rungs (``plan.compact_rungs``),
        read off the tree on the host: what ``hist_active_row_share`` is
        made of.

        The kernel holds these counts a unit, but to carry them out of
        it takes an output a pass, an add a round and, under a mesh, a
        sum over the shards: device work in every pass for a number the
        committed tree already holds (:meth:`_pass_rounds`)."""
        if not self.plan.compact_rungs or self._is_voting \
                or self._is_feature_par:
            return 0, 0
        active = streamed = 0
        for slots, slot_rows in self._pass_rounds(arrs):
            if self._factored_rung(slots) in self.plan.compact_rungs:
                active += slot_rows
                streamed += self.n_padded
        return active, streamed

    def split_row_copies(self, arrs: Dict[str, np.ndarray]
                         ) -> Tuple[int, int]:
        """``(split rows copied, gathers)`` of one grown tree on a table
        whose groups are chunked: every chunked pass and the exit route
        call ``histogram.gather_split_rows`` once, which copies a row
        for each split it routes (``route_split_row_share`` is rows over
        ``gathers x ROUTE_ROWS``, what copying every route row would
        take).  Read off the committed tree for the reason
        :meth:`compact_pass_rows` gives: a count carried out of the
        loop costs device work in every call.

        The root's pass routes nothing; a later pass routes the splits
        of the round before it; the exit route routes the last round's,
        or nothing where a pass followed that round.  With no histogram
        cache a pass runs its kernel twice, over the same route table."""
        if self.plan.group_chunks <= 1 or self.plan.tier != "ladder" \
                or self._is_voting or self._is_feature_par:
            return 0, 0
        per_pass = 1 if self.use_hist_cache else 2
        splits = [k for k, _ in self._pass_rounds(arrs)]
        rows = gathers = 0
        # (slots, splits routed) of the root's pass and of every other
        for slots, routed in [(1, 0)] + [(k, k) for k in splits]:
            if self._factored_rung(slots) is not None:
                rows += per_pass * routed
                gathers += per_pass
        exit_routed = int(arrs["num_leaves"]) - 1 - sum(splits)
        return rows + exit_routed, gathers + 1

    # ------------------------------------------------------------------
    def emit_tree_record(self, tree: TreeArrays) -> jax.Array:
        """Serialize one grown tree into its packed byte record
        (tree.TreeRecordLayout): static-offset in-place dynamic-update-
        slice writes into one (record_size,) uint8 buffer.  The fused
        dispatch chunk stacks THIS as its only O(chunk) tree output
        (gbdt._build_fused_chunk) instead of 18 per-field stacks."""
        with TELEMETRY.phase("tree_record"):
            return self.record_layout.pack_tree_record(tree)

    # ------------------------------------------------------------------
    #: rows of one block of the root's float32 totals: a shard on the
    #: kernel path holds whole blocks (its rows are padded to 1024)
    ROOT_TOTAL_BLOCK = 1024
    #: take the root's totals from the quantized gradients and hessians,
    #: the sums every histogram holds, rather than from the exact ones
    #: (set from the objective's ``quantized_root_totals``).  Where they
    #: differ, a child's sums are its parent's less a sibling's quantized
    #: ones, so the whole table's rounding residue lands in one leaf a
    #: level (ROADMAP A1); it stays for the objectives that leave it
    #: unset, whose trees it would change
    quantized_root_totals = False

    def _root_totals(self, grad, hess, counts):
        """(1, 3) float32 root totals on the ladder's path, every
        addition written out so that neither the number of shards nor
        the compiler chooses the order: inside each fixed 1024-row block
        and then across the blocks, halves are added elementwise until
        one value is left.  A ``sum`` will not do: the TPU compiler
        orders a reduction by its operand's shape, so the same rows
        summed as one shard and as four give other last bits (measured
        on a v5e, PR 28: 170 of 192 block sums differ), a float32
        ``psum`` of per-shard sums likewise, and the trees with them."""
        def halved(x):
            # (..., 2^k) -> (...,): x[i] + x[i + half], level by level
            while x.shape[-1] > 1:
                half = x.shape[-1] // 2
                x = x[..., :half] + x[..., half:]
            return x[..., 0]

        def block_sums(x):
            # one row a block, the row its minor dimension: a (3, N)
            # stack of the channels is laid out with the 3 minor-most
            # under the mesh (3 padded to 128 lanes, a copy of every
            # level: 51 ms a tree at 2^24 rows a chip, PERF.md PR 28)
            return halved(x.reshape(-1, self.ROOT_TOTAL_BLOCK))

        def total(x):
            x = jnp.where(self._row_valid, x, 0.0)
            if self.plan.mesh_kernels:
                from jax.sharding import PartitionSpec as P
                axis = self.plan.row_axis
                part = _get_shard_map()(
                    lambda x: jax.lax.all_gather(block_sums(x), axis,
                                                 tiled=True),
                    mesh=self.policy.mesh, in_specs=P(axis),
                    out_specs=P())(x)
            else:
                part = block_sums(x)
            blocks = part.shape[0]
            pad = (1 << (blocks - 1).bit_length()) - blocks
            return halved(jnp.pad(part, (0, pad)))

        return jnp.stack([total(grad), total(hess), total(counts)])[None, :]

    def new_hist_pool(self):
        """The per-leaf histogram cache as an array of its own, zeroed
        (the reference's HistogramPool): ``_train_tree_impl`` grows a
        tree in the one it is handed and hands it back, so a caller that
        keeps it from tree to tree — the chunk program, which donates it
        — holds ONE cache for the job and no tree zeroes it (a tree reads
        only the slots it has written).  The kernel ladder's only, where
        the cache is one device's or replicated; None elsewhere: a
        partitioned program chooses its cache's sharding itself."""
        if self.plan.tier != "ladder":
            return None
        from jax.sharding import NamedSharding, PartitionSpec
        mesh = self.policy.mesh
        return jax.jit(self._zero_hist_cache, out_shardings=(
            None if mesh is None
            else NamedSharding(mesh, PartitionSpec())))()

    def _zero_hist_cache(self):
        hist_cache = jnp.zeros(
            (self.num_leaves if self.use_hist_cache else 1,
             self.num_groups, self.max_group_bin, 3), jnp.float32)
        if self.plan.int_counts:
            return (hist_cache, jnp.zeros(hist_cache.shape[:3], jnp.int32))
        return hist_cache

    def _init_state(self, grad, hess, counts, hist_pool=None, quant=None
                    ) -> GrowerState:
        """``quant`` (the tree's int8 weights and scales) where the
        root's totals are to be the sums of the quantized gradients and
        hessians the histograms hold (``quantized_root_totals``)."""
        L = self.num_leaves
        M = L - 1
        B = self.max_feature_bin
        leaf_id = jnp.where(self._row_valid, 0, -1).astype(jnp.int32)
        if quant is not None:
            wq, scales = quant
            grad = wq[0].astype(jnp.float32) * scales[0]
            hess = wq[1].astype(jnp.float32) * scales[1]
        if self.plan.tier == "ladder":
            totals = self._root_totals(grad, hess, counts)
        else:
            totals = compute_leaf_totals(grad, hess, counts, leaf_id, 1)
        leaf_sum_grad = jnp.zeros(L, jnp.float32).at[0].set(totals[0, 0])
        leaf_sum_hess = jnp.zeros(L, jnp.float32).at[0].set(totals[0, 1])
        root_rows = totals[0, 2]
        if self.plan.int_counts:
            # an integer sum has one value in any order, on any mesh
            root_rows = jnp.sum(jnp.where(
                self._row_valid, counts, 0.0).astype(jnp.int32))
        leaf_count = jnp.zeros(L, root_rows.dtype).at[0].set(root_rows)
        tree = TreeArrays(
            num_leaves=jnp.int32(1),
            leaf_value=jnp.zeros(L, jnp.float32),
            leaf_weight=jnp.zeros(L, jnp.float32).at[0].set(totals[0, 1]),
            leaf_count=jnp.zeros(L, jnp.int32).at[0].set(
                _rows_i32(root_rows)),
            leaf_parent=jnp.full(L, -1, jnp.int32),
            leaf_depth=jnp.zeros(L, jnp.int32),
            node_feature=jnp.zeros(M, jnp.int32),
            node_threshold=jnp.zeros(M, jnp.int32),
            node_default_left=jnp.zeros(M, bool),
            node_is_cat=jnp.zeros(M, bool),
            node_cat_mask=jnp.zeros((M, B), bool),
            node_gain=jnp.zeros(M, jnp.float32),
            node_value=jnp.zeros(M, jnp.float32),
            node_weight=jnp.zeros(M, jnp.float32),
            node_count=jnp.zeros(M, jnp.int32),
            node_left=jnp.zeros(M, jnp.int32),
            node_right=jnp.zeros(M, jnp.int32),
        )
        leaf_forced = jnp.full(L, -1, jnp.int32)
        if self.forced_count:
            leaf_forced = leaf_forced.at[0].set(0)
        cand = jnp.zeros((L, CAND_COLS + B), jnp.float32) \
            .at[:, CAND_GAIN].set(NEG_INF)
        forced_cand = jnp.zeros((L, FORCED_COLS), jnp.float32) \
            .at[:, FORCED_GAIN].set(NEG_INF)
        hist_cache = self._zero_hist_cache() if hist_pool is None \
            else hist_pool
        if self.plan.int_counts:
            cand = (cand, jnp.zeros(L, jnp.int32))
            forced_cand = (forced_cand, jnp.zeros(L, jnp.int32))
        W = self.frontier
        return GrowerState(
            route_tab=jnp.zeros((L, self._route_cols), jnp.float32),
            pend_parents=jnp.full((W,), -1, jnp.int32),
            # the root is the first "new leaf" awaiting refresh
            pend_rights=jnp.full((W,), -1, jnp.int32).at[0].set(0),
            leaf_id=leaf_id, num_leaves=jnp.int32(1),
            round_idx=jnp.int32(0), done=jnp.bool_(False),
            leaf_sum_grad=leaf_sum_grad, leaf_sum_hess=leaf_sum_hess,
            leaf_count=leaf_count,
            leaf_min_c=jnp.full(L, -jnp.inf, jnp.float32),
            leaf_max_c=jnp.full(L, jnp.inf, jnp.float32),
            leaf_is_left=jnp.zeros(L, bool),
            leaf_forced=leaf_forced,
            tree=tree,
            hist_cache=hist_cache,
            hist_stage=(jax.tree_util.tree_map(lambda c: jnp.zeros(
                (W if self.use_hist_cache else 1,) + c.shape[1:],
                c.dtype), hist_cache),) * 2,
            cand=cand, forced_cand=forced_cand)

    # ------------------------------------------------------------------
    def _train_tree_impl(self, grad, hess, counts, feature_mask,
                         ohb=None, bins=None, binsT=None,
                         row_valid=None, qkey=None, hist_pool=None):
        """(tree, final leaf ids, per-row leaf value or None,
        ``hist_pool`` as the tree left it: ``new_hist_pool``).
        ``ohb``/``bins``/``binsT``/``row_valid`` are the O(N) device
        arrays, threaded through the caller's jit boundary as ARGUMENTS
        and bound to their attributes for the dynamic extent of the
        trace.  Closing over them instead would inline each one as an
        MLIR constant — the serialized program then carries the whole
        matrix and XLA's compile time grows linearly with rows
        (measured ~80 s per million rows; a HIGGS-scale compile took
        25+ minutes before this)."""
        self._ohb_arg = ohb
        saved = (self.bins, self.binsT, self._row_valid)
        if bins is not None:
            self.bins = bins
        if binsT is not None:
            self.binsT = binsT
        if row_valid is not None:
            self._row_valid = row_valid
        try:
            return self._train_tree_inner(grad, hess, counts,
                                          feature_mask, qkey=qkey,
                                          hist_pool=hist_pool)
        finally:
            self._ohb_arg = None
            self.bins, self.binsT, self._row_valid = saved

    def _train_tree_inner(self, grad, hess, counts, feature_mask,
                          qkey=None, hist_pool=None):
        # every op of a tree lies under a tel.<phase> scope (innermost
        # wins where they nest), so a device trace splits by phase
        quant = None
        if not (self._is_voting or self._is_feature_par):
            # gradients are fixed for the whole tree, so the int8
            # quantization (one scale per channel) happens once here;
            # qkey enables the stochastic rounding the skewed-gradient
            # objectives need (see quantize_gradients)
            with TELEMETRY.phase("quantize"):
                if self.plan.quantized:
                    wq, scales = quantize_gradients(grad, hess, counts,
                                                    key=qkey)
                    # the ladder streams weights lane-major
                    quant = (wq.T, scales)                  # (3, N)
        with TELEMETRY.phase("init_state"):
            state = self._init_state(
                grad, hess, counts, hist_pool,
                quant if self.quantized_root_totals else None)
        if self._is_voting:
            def body_fn(st):
                # histograms and the vote run inside one shard_map
                with TELEMETRY.phase("split_finder"):
                    return self._round_voting(st, grad, hess, counts,
                                              feature_mask)
        elif self._is_feature_par:
            def body_fn(st):
                with TELEMETRY.phase("split_finder"):
                    return self._round_feature(st, grad, hess, counts,
                                               feature_mask)
        else:
            def body_fn(st):
                return self._round(st, grad, hess, counts, feature_mask,
                                   quant)

        def cond(st: GrowerState):
            # reads the flag apply_split left
            with TELEMETRY.phase("apply_split"):
                return ~st.done

        def body(st: GrowerState):
            return body_fn(st)

        final = jax.lax.while_loop(cond, body, state)
        with TELEMETRY.phase("route"):
            leaf_id, row_val = self._exit_route(final)
        tree = final.tree._replace(num_leaves=final.num_leaves)
        return (tree, leaf_id, row_val,
                None if hist_pool is None else final.hist_cache)

    def _exit_route(self, final: GrowerState):
        """(final leaf ids, per-row post-route leaf value or None)."""
        leaf_id = final.leaf_id
        row_val = None
        if self.plan.fused:
            # the last round's selected splits were never routed (the
            # loop exited before the next refresh) — apply them once,
            # and ride the per-row POST-route leaf value on the same
            # pass so the boosting score update needs no separate
            # leaf_value_broadcast (callers ignore row_val when
            # RenewTreeOutput will change leaf values).  Tiled path:
            # in-VMEM Pallas broadcast; the XLA form materializes an
            # (N, L_pad) bf16 one-hot + (N, K) rows in HBM (~16
            # ms/tree at HIGGS scale)
            if self.plan.tier == "ladder":
                from ..ops.histogram import (gather_split_rows,
                                             route_apply_tiled)
                kernel = functools.partial(
                    route_apply_tiled, block=self.plan.block_tiled,
                    interpret=self.plan.interpret,
                    packed_groups=self.pack_P)
                route = kernel
                if self.plan.group_chunks > 1:
                    # a wide table's route reads its split rows, as its
                    # histogram passes do
                    def route(binsT, leaf_id, route_tab, values):
                        rowsT, tab = gather_split_rows(binsT, route_tab)
                        return kernel(rowsT, leaf_id, tab, values)
                if self.plan.mesh_kernels:
                    # per-row work on replicated tables: each shard
                    # routes its own rows, nothing crosses
                    from jax.sharding import PartitionSpec as P
                    axis = self.plan.row_axis
                    rows = P(axis)
                    route = _get_shard_map()(
                        route, mesh=self.policy.mesh,
                        in_specs=(P(None, axis), rows, P(), P()),
                        out_specs=(rows, rows))
                leaf_id, row_val = route(
                    self.binsT, leaf_id, final.route_tab,
                    final.tree.leaf_value)
            else:
                leaf_id, row_val = apply_route_table(
                    self.bins, leaf_id, final.route_tab,
                    values=final.tree.leaf_value,
                    packed_groups=self.pack_P)
        return leaf_id, row_val

    # ------------------------------------------------------------------
    def _run_finders(self, hist, sum_grad, sum_hess, count, min_c, max_c,
                     cfg, f_num_bin, f_missing, f_default_bin, f_monotone,
                     f_is_cat, feature_mask):
        """Best split per (leaf-row, feature) from per-feature hists.
        All leaf-shaped args are (L',) aligned with hist's first axis."""
        return run_split_finders(
            hist, sum_grad, sum_hess, count, min_c, max_c, cfg,
            f_num_bin, f_missing, f_default_bin, f_monotone, f_is_cat,
            feature_mask, self.has_categorical, finder=self.finder)

    # ------------------------------------------------------------------
    def _refresh(self, st: GrowerState, parents, rights, grad, hess,
                 counts, feature_mask, quant=None) -> GrowerState:
        """Histogram + split-finder pass over the new leaves of a round,
        all of it at the width of the rung that serves the round's valid
        slots (``_pass_ladder``): ONE ladder, a branch a width.

        A branch of width ``w`` histograms ``rights[:w]`` directly from
        the data (one frontier-restricted MXU pass), takes
        parent-minus-right for each valid one of ``parents[:w]`` (the
        slot the left child inherited) a row of the per-leaf cache at a
        time, runs the finder on those 2w rows and scatters its results
        into the per-leaf candidate cache.  The histogram does not leave
        the branch, so nothing pads it to the frontier cap, re-lays it
        or cuts it back: the branch writes its halves over the heads of
        ``st.hist_stage``, and the one thing done after the switch is to
        move the valid rows from there into the cache.  (The TPU
        compiler keeps a buffer that a conditional hands on in place
        only where the branches do not read it: a cache updated inside
        them was copied whole on every pass, 1.6 GB at 2,000 groups; and
        it cuts the WHOLE operand of a gather or scatter into slabs
        first, so the cache is read and written a row at a time.)
        Negative slot entries are inert (their results drop, their lanes
        match no row), and valid slots lead both halves (``_round``
        queues them so).  ``split_finder_ladder=False``: every branch
        pads its pass to the frontier cap and runs the finder there (the
        parity tests' reference)."""
        W = parents.shape[0]
        tmap = jax.tree_util.tree_map     # a histogram, or its pair
        k = jnp.sum(rights >= 0)
        late = None                       # _pass_ladder: a late multiply

        def both(kernel):
            """(histogram of ``rights``, of ``parents`` where there is
            no cache to subtract from, leaf ids after the pass)."""
            right, leaf_id = kernel(st.leaf_id, rights)
            # no-cache mode: the parent slot now hosts the LEFT child's
            # rows (routing already applied), so a direct pass replaces
            # the subtraction
            left = None if self.use_hist_cache \
                else kernel(leaf_id, parents)[0]
            return right, left, leaf_id

        if self.plan.fused or (self.plan.tier == "float"
                               and self.plan.onehot_pack):
            ladder, late = self._pass_ladder(st, W, grad, hess, counts,
                                             quant)
            ladder = [(w, functools.partial(both, kernel))
                      for w, kernel in ladder]
        else:
            # a pass with no ladder is made once, in the open, and each
            # width (the packed strips') cuts its rows out of it
            done = both(lambda leaf_id, slots: (self._hist_kernel(
                grad, hess, counts, leaf_id, slots), leaf_id))
            ladder = [(w, lambda: done) for w in _strip_widths(W)]
        # a rung is taken by its pass's width; the rest of its refresh
        # runs there too, or at the frontier cap with the ladder off
        widths = [w if self.split_ladder else W for w, _ in ladder]
        if TELEMETRY.on:
            TELEMETRY.gauge("grower.refresh_widths",      # "2,10,16"
                            str(widths)[1:-1].replace(" ", ""))

        def at(w, passes):
            def go(_):
                right, left, leaf_id = passes()
                right = self._constrain_hist(_fit_slots(right, w))
                p_w = parents[:w]
                slots_w = jnp.concatenate([p_w, rights[:w]])    # (2w,)
                stage = st.hist_stage
                if left is None:
                    # parent - right a valid row at a time (the
                    # dequantize multiply was rounded to memory before
                    # the loop, on any backend: no compiler contracts
                    # the two), then both halves over the stage's heads
                    def minus(i, left):
                        r = _get_row(right, i)
                        if late is not None:
                            r = _scaled(r, late)
                        parent = _get_row(st.hist_cache,
                                          jnp.maximum(p_w[i], 0))
                        return _set_rows(
                            left, tmap(jnp.subtract, parent, r), i)
                    left = jax.lax.fori_loop(0, k, minus,
                                             tmap(jnp.zeros_like, right))
                    if late is not None:
                        right = _scaled(right, late)
                    stage = tuple(_set_rows(s, h, 0) for s, h
                                  in zip(stage, (left, right)))
                else:
                    left = self._constrain_hist(_fit_slots(left, w))
                    if late is not None:
                        left, right = (_scaled(h, late)
                                       for h in (left, right))
                h_w = tmap(lambda l, r: jnp.concatenate([l, r]),
                           left, right)
                cand, forced_cand = self._refresh_cand(
                    st, slots_w, h_w, feature_mask)
                return stage, cand, forced_cand, leaf_id
            return go

        branches = [at(w, passes)
                    for w, (_, passes) in zip(widths, ladder)]
        if len(branches) == 1:
            out = branches[0](None)
        else:
            out = jax.lax.switch(
                sum((k > w).astype(jnp.int32) for w, _ in ladder[:-1]),
                branches, None)
        stage, cand, forced_cand, leaf_id = out
        cache = st.hist_cache
        if self.use_hist_cache:
            def move(i, cache):
                left, right = (_get_row(s, i) for s in stage)
                # (the root is a right slot with no parent)
                child = parents[i] >= 0
                cache = _set_rows(
                    cache, tmap(lambda l, r: jnp.where(child, l, r),
                                left, right),
                    jnp.where(child, parents[i], rights[i]))
                return _set_rows(cache, right, rights[i])
            cache = jax.lax.fori_loop(0, k, move, cache)
        return st._replace(hist_cache=cache, hist_stage=stage, cand=cand,
                           forced_cand=forced_cand, leaf_id=leaf_id)

    # ------------------------------------------------------------------
    def _constrain_hist(self, hist):
        """The policy's feature-owned histogram constraint, except on
        the mesh's kernel path: there the exact sum leaves the
        histogram replicated and the split finder runs on every shard
        alike (reduce-scatter with feature-owned finding is a later
        optimisation: ROADMAP Speed 6)."""
        if self.plan.mesh_kernels:
            return hist
        return self.policy.constrain_hist(hist)

    # ------------------------------------------------------------------
    def _refresh_cand(self, st: GrowerState, slots_w, h_w, feature_mask):
        """Finder + candidate-cache update at ONE frontier width: every
        shape is bounded by ``slots_w``'s length (2·w, never L_pad) and
        the per-leaf cache update is a single packed-block scatter
        (plus one for forced splits) instead of the former 11+8
        per-field scatters.  Valid slots occupy a prefix of each half
        of ``slots_w`` (_round queues them that way); negative entries
        scatter to the dropped L row."""
        with TELEMETRY.phase("split_finder"):
            return self._refresh_cand_impl(st, slots_w, h_w,
                                           feature_mask)

    def _refresh_cand_impl(self, st, slots_w, h_w, feature_mask):
        L = self.num_leaves
        cfg = self.cfg_scalars
        safe = jnp.clip(slots_w, 0, L - 1)
        sg = st.leaf_sum_grad[safe]
        sh = st.leaf_sum_hess[safe]
        sc = st.leaf_count[safe]
        mc = st.leaf_min_c[safe]
        xc = st.leaf_max_c[safe]
        feat_count = None
        if self.plan.int_counts:
            h_w, feat_count = h_w
        if self.finder_identity:
            # every feature is its own group, bin for bin: the finder
            # reads the group histogram itself
            Bf = self.max_feature_bin
            feat_hist = h_w[:, :, :Bf]
            if feat_count is not None:
                feat_count = feat_count[:, :, :Bf]
        else:
            if feat_count is not None:
                # the counts' own FixHistogram, in integers
                feat_count = expand_feature_histograms(
                    feat_count[..., None], self.bin_map, self.fix_bin,
                    sc[:, None])[..., 0]
            totals = jnp.stack([sg, sh, sc.astype(jnp.float32)], axis=1)
            feat_hist = expand_feature_histograms(h_w, self.bin_map,
                                                  self.fix_bin, totals)

        def best_block(feat_hist, sg, sh, sc, mc, xc, feature_mask,
                       feat_count):
            return find_best_split_block(
                feat_hist, sg, sh, sc, mc, xc, cfg, self.f_num_bin,
                self.f_missing, self.f_default_bin, self.f_monotone,
                self.f_is_cat, feature_mask, self.has_categorical,
                feat_count=feat_count, finder=self.finder)

        if self.plan.mesh_kernels and self.finder.form == "fused":
            # the exact sum left the histogram replicated: every shard
            # runs the finder's kernel on its own copy
            from jax.sharding import PartitionSpec as P
            best_block = _get_shard_map()(
                best_block, mesh=self.policy.mesh, in_specs=P(),
                out_specs=P())
        block = best_block(feat_hist, sg, sh, sc, mc, xc, feature_mask,
                           feat_count)
        idx = jnp.where(slots_w >= 0, slots_w, L)
        tmap = jax.tree_util.tree_map       # a block, or its pair
        cand = tmap(lambda c, b: c.at[idx].set(b, mode="drop"),
                    st.cand, block)
        forced_cand = st.forced_cand
        if self.forced_count:
            fblock = forced_split_block(
                feat_hist, st.leaf_forced[safe], self.forced_feature,
                self.forced_thr, sg, sh, sc, self.f_num_bin,
                self.f_missing, self.f_default_bin, self.f_is_cat, cfg,
                feat_count=feat_count)
            forced_cand = tmap(lambda c, b: c.at[idx].set(b, mode="drop"),
                               st.forced_cand, fblock)
        return cand, forced_cand

    # ------------------------------------------------------------------
    def _apply_selection(self, st: GrowerState, do_split, rank, k,
                         best_gain, best_f, thr, dleft, lsg, lsh, lsc,
                         lout, rout, cat_mask, forced_valid=None
                         ) -> GrowerState:
        """Apply the selected splits: scatter new internal nodes, update
        child leaf state, propagate monotone constraints, re-label rows
        (shared by the cached and voting rounds; the reference's
        SerialTreeLearner::Split, serial_tree_learner.cpp:700-774).
        All per-leaf args are (L,) chosen-split values."""
        with TELEMETRY.phase("apply_split"):
            return self._apply_selection_impl(
                st, do_split, rank, k, best_gain, best_f, thr, dleft,
                lsg, lsh, lsc, lout, rout, cat_mask, forced_valid)

    def _apply_selection_impl(self, st, do_split, rank, k, best_gain,
                              best_f, thr, dleft, lsg, lsh, lsc, lout,
                              rout, cat_mask, forced_valid=None):
        L = self.num_leaves
        M = L - 1
        slot = jnp.arange(L, dtype=jnp.int32)
        right_slot = st.num_leaves + rank            # valid where do_split
        node_id = (st.num_leaves - 1) + rank

        f_is_cat_leaf = self.f_is_cat[best_f]
        f_missing_leaf = self.f_missing[best_f]
        f_dbin_leaf = self.f_default_bin[best_f]
        f_nb_leaf = self.f_num_bin[best_f]
        f_group_leaf = self.f_group[best_f]
        f_mono_leaf = self.f_monotone[best_f]

        # scatter new internal nodes (drop out-of-budget writes)
        nid = jnp.where(do_split, node_id, M)
        t = st.tree
        # internal_value = the leaf's output before it split (tree.cpp Split)
        parent_out = t.leaf_value
        tree = t._replace(
            node_feature=t.node_feature.at[nid].set(best_f, mode="drop"),
            node_threshold=t.node_threshold.at[nid].set(thr, mode="drop"),
            node_default_left=t.node_default_left.at[nid].set(
                dleft, mode="drop"),
            node_is_cat=t.node_is_cat.at[nid].set(f_is_cat_leaf,
                                                  mode="drop"),
            node_cat_mask=t.node_cat_mask.at[nid].set(cat_mask,
                                                      mode="drop"),
            node_gain=t.node_gain.at[nid].set(best_gain, mode="drop"),
            node_value=t.node_value.at[nid].set(parent_out, mode="drop"),
            node_weight=t.node_weight.at[nid].set(st.leaf_sum_hess,
                                                  mode="drop"),
            node_count=t.node_count.at[nid].set(_rows_i32(st.leaf_count),
                                                mode="drop"),
            node_left=t.node_left.at[nid].set(_encode_leaf(slot),
                                              mode="drop"),
            node_right=t.node_right.at[nid].set(_encode_leaf(right_slot),
                                                mode="drop"),
        )
        # parent child-pointer fixup: this leaf's slot in its parent now
        # points at the new internal node
        has_parent = do_split & (t.leaf_parent >= 0)
        p = jnp.where(has_parent, t.leaf_parent, M)
        pl = jnp.where(has_parent & st.leaf_is_left, p, M)
        pr = jnp.where(has_parent & ~st.leaf_is_left, p, M)
        tree = tree._replace(
            node_left=tree.node_left.at[pl].set(node_id, mode="drop"),
            node_right=tree.node_right.at[pr].set(node_id, mode="drop"),
        )

        # child leaf state (left keeps the slot, right takes right_slot)
        rsg = st.leaf_sum_grad - lsg
        rsh = st.leaf_sum_hess - lsh
        rsc = st.leaf_count - lsc
        new_depth = t.leaf_depth + 1
        rs = jnp.where(do_split, right_slot, L)

        def upd(arr, left_val, right_val):
            arr = arr.at[rs].set(right_val, mode="drop")
            return jnp.where(do_split, left_val, arr)

        leaf_sum_grad = upd(st.leaf_sum_grad, lsg, rsg)
        leaf_sum_hess = upd(st.leaf_sum_hess, lsh, rsh)
        leaf_count = upd(st.leaf_count, lsc, rsc)

        # monotone constraint propagation (serial_tree_learner.cpp:764-774)
        mid = (lout + rout) / 2.0
        is_num = ~f_is_cat_leaf
        lmin = jnp.where(is_num & (f_mono_leaf < 0), mid, st.leaf_min_c)
        lmax = jnp.where(is_num & (f_mono_leaf > 0), mid, st.leaf_max_c)
        rmin = jnp.where(is_num & (f_mono_leaf > 0), mid, st.leaf_min_c)
        rmax = jnp.where(is_num & (f_mono_leaf < 0), mid, st.leaf_max_c)
        leaf_min_c = upd(st.leaf_min_c, lmin, rmin)
        leaf_max_c = upd(st.leaf_max_c, lmax, rmax)

        tree = tree._replace(
            leaf_value=upd(t.leaf_value, lout, rout),
            leaf_weight=upd(t.leaf_weight, lsh, rsh),
            leaf_count=upd(t.leaf_count, _rows_i32(lsc), _rows_i32(rsc)),
            leaf_parent=upd(t.leaf_parent, node_id, node_id),
            leaf_depth=upd(t.leaf_depth, new_depth, new_depth),
        )
        leaf_is_left = upd(st.leaf_is_left,
                           jnp.ones(L, bool), jnp.zeros(L, bool))

        # forced-split inheritance: children of a forced split receive
        # the spec's left/right sub-nodes; any other split clears it
        if forced_valid is not None:
            s_node2 = jnp.clip(st.leaf_forced, 0, self.forced_count - 1)
            fap = do_split & forced_valid
            lf_left = jnp.where(fap, self.forced_left[s_node2], -1)
            lf_right = jnp.where(fap, self.forced_right[s_node2], -1)
            leaf_forced = upd(st.leaf_forced, lf_left, lf_right)
        else:
            leaf_forced = st.leaf_forced

        # row re-labeling.  Fused path (one device, or the row shards
        # of a data mesh): only BUILD the route table — the next
        # round's histogram kernel applies it in its own data stream
        # (the loop exit applies the last pending table in
        # _train_tree_inner).  Non-fused (CPU sim / feature, voting and
        # GSPMD meshes): the XLA router runs now.  A Pallas VMEM-one-hot
        # standalone router was benched on a v5e chip and lost to the
        # XLA form (142 vs 96 ms/tree at 1M rows), which is what
        # motivated fusing the routing into the histogram kernel instead.
        route_args = (do_split, f_group_leaf,
                      self.f_gb_lo[best_f], self.f_gb_hi[best_f],
                      self.f_gb_shift[best_f], self.f_gb_oor[best_f],
                      f_is_cat_leaf, thr, dleft, f_missing_leaf,
                      f_dbin_leaf, f_nb_leaf, cat_mask, right_slot)
        if self.plan.fused:
            leaf_id = st.leaf_id
            route_tab = build_route_table(*route_args)
        else:
            leaf_id = apply_splits(self.bins, st.leaf_id, *route_args,
                                   packed_groups=self.pack_P)
            route_tab = st.route_tab

        num_leaves = st.num_leaves + k
        round_idx = st.round_idx + 1
        done = (k == 0) | (num_leaves >= L) | (round_idx >= self.max_rounds)
        return GrowerState(
            leaf_id=leaf_id, num_leaves=num_leaves, round_idx=round_idx,
            done=done, leaf_sum_grad=leaf_sum_grad,
            leaf_sum_hess=leaf_sum_hess, leaf_count=leaf_count,
            leaf_min_c=leaf_min_c, leaf_max_c=leaf_max_c,
            leaf_is_left=leaf_is_left, leaf_forced=leaf_forced, tree=tree,
            hist_cache=st.hist_cache, hist_stage=st.hist_stage,
            cand=st.cand,
            forced_cand=st.forced_cand, route_tab=route_tab,
            pend_parents=st.pend_parents, pend_rights=st.pend_rights)

    # ------------------------------------------------------------------
    def _round(self, st: GrowerState, grad, hess, counts, feature_mask,
               quant=None) -> GrowerState:
        """One cached-candidate frontier round: refresh histograms +
        candidates for the leaves created LAST round (pend_*), then
        select/apply splits from the cache.  Refreshing at round start
        means the final round's new leaves are never histogrammed at
        all — the while_loop exits first."""
        L = self.num_leaves
        W = self.frontier
        with TELEMETRY.phase("histogram"):
            st = self._refresh(st, st.pend_parents, st.pend_rights,
                               grad, hess, counts, feature_mask,
                               quant)

        with TELEMETRY.phase("split_finder"):
            c, lsc_i32 = st.cand if self.plan.int_counts \
                else (st.cand, None)
            best_gain = c[:, CAND_GAIN]
            best_f = c[:, CAND_FEATURE].astype(jnp.int32)
            thr = c[:, CAND_THRESHOLD].astype(jnp.int32)
            dleft = c[:, CAND_DEFAULT_LEFT] > 0.5
            lsg, lsh, lsc = c[:, CAND_LSG], c[:, CAND_LSH], c[:, CAND_LSC]
            if self.plan.int_counts:
                lsc = lsc_i32
            lout, rout = c[:, CAND_LOUT], c[:, CAND_ROUT]
            cat_mask = c[:, CAND_COLS:] > 0.5

            forced_valid = None
            if self.forced_count:
                fc, flc = st.forced_cand if self.plan.int_counts \
                    else (st.forced_cand, st.forced_cand[:, FORCED_LSC])
                fc_gain = fc[:, FORCED_GAIN]
                fc_thr = fc[:, FORCED_THRESHOLD].astype(jnp.int32)
                s_node = jnp.clip(st.leaf_forced, 0, self.forced_count - 1)
                ff = self.forced_feature[s_node]
                forced_valid = (st.leaf_forced >= 0) & (fc_gain > NEG_INF)
                best_f = jnp.where(forced_valid, ff, best_f)
                best_gain = jnp.where(forced_valid, fc_gain, best_gain)
                thr = jnp.where(forced_valid, fc_thr, thr)
                dleft = jnp.where(forced_valid,
                                  fc[:, FORCED_DEFAULT_LEFT] > 0.5, dleft)
                lsg = jnp.where(forced_valid, fc[:, FORCED_LSG], lsg)
                lsh = jnp.where(forced_valid, fc[:, FORCED_LSH], lsh)
                lsc = jnp.where(forced_valid, flc, lsc)
                lout = jnp.where(forced_valid, fc[:, FORCED_LOUT], lout)
                rout = jnp.where(forced_valid, fc[:, FORCED_ROUT], rout)
                fmask = (jnp.arange(self.max_feature_bin,
                                    dtype=jnp.int32)[None]
                         == fc_thr[:, None])
                cat_mask = jnp.where(forced_valid[:, None], fmask, cat_mask)

            slot = jnp.arange(L, dtype=jnp.int32)
            active = slot < st.num_leaves
            depth_ok = (self.max_depth <= 0) | \
                (st.tree.leaf_depth < self.max_depth)
            cand_m = active & depth_ok & (best_gain > 0.0)
            if forced_valid is not None:
                forced_valid = forced_valid & active
                cand_m = cand_m | forced_valid

            key = jnp.where(cand_m, best_gain, NEG_INF)
            if forced_valid is not None:
                key = jnp.where(forced_valid, jnp.inf, key)
            # W-bounded selection (round 7): only the top W leaves — the
            # most a round can split — ever receive a rank, replacing two
            # full-L argsorts.  lax.top_k keeps the lower index first on
            # ties, exactly the stable argsort(-key) order it replaces.
            top_i = jax.lax.top_k(key, W)[1].astype(jnp.int32)
            rank = jnp.full(L, L, jnp.int32).at[top_i].set(
                jnp.arange(W, dtype=jnp.int32))
            budget = L - st.num_leaves
            do_split = cand_m & (rank < budget) & (rank < W)
            k = do_split.sum().astype(jnp.int32)

        st2 = self._apply_selection(st, do_split, rank, k, best_gain,
                                    best_f, thr, dleft, lsg, lsh, lsc,
                                    lout, rout, cat_mask, forced_valid)

        # queue this round's new leaves for the NEXT round's refresh:
        # top_i[w] is the leaf with split-rank w (its slot hosts the
        # left child); the matching right child is num_leaves_old + w
        with TELEMETRY.phase("apply_split"):
            w_iota = jnp.arange(W, dtype=jnp.int32)
            split_ok = w_iota < k
            parents = jnp.where(split_ok, top_i, -1)
            rights = jnp.where(split_ok, st.num_leaves + w_iota, -1)
        return st2._replace(pend_parents=parents, pend_rights=rights)

    # ==================================================================
    # voting-parallel path (full-frontier formulation)
    # ==================================================================
    def _voting_find_splits(self, st: GrowerState, grad, hess, counts,
                            feature_mask):
        """Voting-parallel split search (PV-Tree — reference
        voting_parallel_tree_learner.cpp): each shard builds LOCAL
        histograms, votes its top_k features by local gain, the votes
        are all-reduced, and only the globally top-2k voted features'
        histograms are exchanged.  Deviation from the reference: the
        per-leaf top-2k selection is a per-round UNION across the
        frontier (one static feature subset), which generalizes the
        reference's smaller/larger-leaf pair to frontier-parallel
        growth while keeping the same communication scale."""
        from functools import partial
        from jax.sharding import PartitionSpec as P
        shard_map = _get_shard_map()

        cfg = self.cfg_scalars
        L = self.num_leaves
        mesh = self.policy.mesh
        d = mesh.size
        axis = mesh.axis_names[0]
        k2 = min(2 * self.config.top_k, self.num_features)
        # local constraints scaled down (voting_parallel:55-56)
        cfg_local = dict(cfg)
        cfg_local["min_data_in_leaf"] = cfg["min_data_in_leaf"] / d
        cfg_local["min_sum_hessian_in_leaf"] = \
            cfg["min_sum_hessian_in_leaf"] / d

        spec_rows = P(axis)
        rep = P()

        @partial(shard_map, mesh=mesh,
                 in_specs=(spec_rows, spec_rows, spec_rows, spec_rows,
                           spec_rows, rep, rep, rep),
                 out_specs=(rep, rep))
        def inner(bins, g, h, c, leaf_id, mask, min_c, max_c):
            n_local = bins.shape[0]
            local_hist = compute_group_histograms(
                bins, g, h, c, leaf_id, num_leaves=L,
                max_group_bin=self.max_group_bin,
                compute_dtype=self.config.hist_compute_dtype,
                chunk=n_local, packed_groups=self.pack_P)
            local_totals = compute_leaf_totals(g, h, c, leaf_id, L)
            feat_hist = expand_feature_histograms(
                local_hist, self.bin_map, self.fix_bin, local_totals)
            _, local_gains = self._run_finders(
                feat_hist, local_totals[:, 0], local_totals[:, 1],
                local_totals[:, 2], min_c, max_c, cfg_local,
                self.f_num_bin, self.f_missing, self.f_default_bin,
                self.f_monotone, self.f_is_cat, mask)
            # per-leaf local top_k vote (GlobalVoting, :166-195)
            kth = jax.lax.top_k(local_gains,
                                min(self.config.top_k,
                                    self.num_features))[0][:, -1:]
            votes = ((local_gains >= kth)
                     & jnp.isfinite(local_gains)).astype(jnp.float32)
            global_votes = jax.lax.psum(votes, axis)          # (L, F)
            total_votes = global_votes.sum(axis=0)            # (F,)
            sel = jax.lax.top_k(total_votes, k2)[1].astype(jnp.int32)
            # exchange only the selected features' histograms
            compact = feat_hist[:, sel]                       # (L,k2,B,3)
            global_compact = jax.lax.psum(compact, axis)
            return global_compact, sel

        hist, sel = inner(self.bins, grad, hess, counts, st.leaf_id,
                          feature_mask, st.leaf_min_c, st.leaf_max_c)
        res, gains = self._run_finders(
            hist, st.leaf_sum_grad, st.leaf_sum_hess, st.leaf_count,
            st.leaf_min_c, st.leaf_max_c, cfg, self.f_num_bin[sel],
            self.f_missing[sel], self.f_default_bin[sel],
            self.f_monotone[sel], self.f_is_cat[sel], feature_mask[sel])
        return res, gains, hist, sel

    # ------------------------------------------------------------------
    def _feature_find_splits(self, st: GrowerState, grad, hess, counts,
                             feature_mask):
        """Feature-parallel split search (reference
        feature_parallel_tree_learner.cpp): the bin matrix is COLUMN-
        sharded over the mesh (the vertical partition), each shard
        histograms and searches ONLY its own feature groups, and the
        only cross-shard traffic is the per-leaf SplitInfo election
        (SyncUpGlobalBestSplit, parallel_tree_learner.h:184-207) —
        per-leaf scalars plus the winner's categorical bitset, never
        histograms.  Requires num_groups divisible by the mesh size
        (the grower falls back to the constraint-sharded path
        otherwise)."""
        from functools import partial
        shard_map = _get_shard_map()
        from jax.sharding import PartitionSpec as P

        cfg = self.cfg_scalars
        L = self.num_leaves
        mesh = self.policy.mesh
        d = mesh.size
        axis = mesh.axis_names[0]
        g_per = self.num_groups // d
        B = self.max_group_bin
        Bf = self.max_feature_bin
        rep = P()
        nout = 9      # payload members; +1 for the global best gain

        @partial(shard_map, mesh=mesh,
                 in_specs=(P(None, axis), rep, rep, rep, rep, rep,
                           rep, rep),
                 out_specs=tuple([rep] * (nout + 1)))
        def inner(bins_l, g, h, c, leaf_id, mask, min_c, max_c):
            sid = jax.lax.axis_index(axis)
            local_hist = compute_group_histograms(
                bins_l, g, h, c, leaf_id, num_leaves=L,
                max_group_bin=B,
                compute_dtype=self.config.hist_compute_dtype,
                chunk=bins_l.shape[0])                # (L, g_per, B, 3)
            totals = compute_leaf_totals(g, h, c, leaf_id, L)
            owned = (self.f_group // g_per) == sid    # (F,)
            bm = jnp.where(owned[:, None] & (self.bin_map >= 0),
                           self.bin_map - sid * g_per * B, -1)
            feat_hist = expand_feature_histograms(
                local_hist, bm, jnp.where(owned, self.fix_bin, -1),
                totals)
            res, gains = self._run_finders(
                feat_hist, totals[:, 0], totals[:, 1], totals[:, 2],
                min_c, max_c, cfg, self.f_num_bin, self.f_missing,
                self.f_default_bin, self.f_monotone, self.f_is_cat,
                mask)
            gains = jnp.where(owned[None, :], gains, NEG_INF)
            bf = jnp.argmax(gains, axis=1).astype(jnp.int32)  # (L,)
            bg = jnp.take_along_axis(gains, bf[:, None], axis=1)[:, 0]

            def al(a):
                return jnp.take_along_axis(a, bf[:, None], axis=1)[:, 0]

            if self.has_categorical:
                hist_chosen = jnp.take_along_axis(
                    feat_hist, bf[:, None, None, None], axis=1)[:, 0]
                cat_mask_l = build_cat_bitset(
                    hist_chosen, al(res.threshold), al(res.cat_dir),
                    self.f_num_bin[bf], self.f_missing[bf], cfg)
            else:
                cat_mask_l = jnp.zeros((L, Bf), bool)

            # SplitInfo election: all-gather per-leaf scalars only
            allg = jax.lax.all_gather(bg, axis)       # (d, L)
            best_shard = jnp.argmax(allg, axis=0)     # (L,)
            oh = (jnp.arange(d, dtype=jnp.int32)[:, None]
                  == best_shard[None, :])             # (d, L)

            def pick(p):
                pg = jax.lax.all_gather(p, axis)      # (d, L, ...)
                w = oh.reshape(oh.shape + (1,) * (pg.ndim - 2))
                return jnp.sum(jnp.where(w, pg, 0), axis=0)

            payload = (bf.astype(jnp.float32), al(res.threshold),
                       al(res.default_left).astype(jnp.float32),
                       al(res.left_sum_grad), al(res.left_sum_hess),
                       al(res.left_count), al(res.left_output),
                       al(res.right_output),
                       cat_mask_l.astype(jnp.float32))
            out = tuple(pick(p) for p in payload)
            return out + (jnp.max(allg, axis=0),)

        (bf_f, thr, dleft, lsg, lsh, lsc, lout, rout, cat_f,
         best_gain) = inner(self.bins, grad, hess, counts, st.leaf_id,
                            feature_mask, st.leaf_min_c, st.leaf_max_c)
        return (best_gain, bf_f.astype(jnp.int32), thr,
                dleft > 0.5, lsg, lsh, lsc, lout, rout, cat_f > 0.5)

    def _select_frontier(self, st: GrowerState, best_gain):
        """Full-frontier candidate selection shared by the voting and
        feature-parallel rounds: gain-ranked splits within the leaf
        budget (the cached serial `_round` layers forced-split and
        frontier-width terms on top of the same scheme).  Returns
        (do_split, rank, k)."""
        L = self.num_leaves
        slot = jnp.arange(L, dtype=jnp.int32)
        active = slot < st.num_leaves
        depth_ok = (self.max_depth <= 0) | \
            (st.tree.leaf_depth < self.max_depth)
        cand_m = active & depth_ok & (best_gain > 0.0)
        key = jnp.where(cand_m, best_gain, NEG_INF)
        order = jnp.argsort(-key)                   # best first, stable
        rank = jnp.argsort(order).astype(jnp.int32)
        budget = L - st.num_leaves
        do_split = cand_m & (rank < budget)
        return do_split, rank, do_split.sum().astype(jnp.int32)

    def _round_feature(self, st: GrowerState, grad, hess, counts,
                       feature_mask) -> GrowerState:
        """Full-frontier round for the feature-parallel learner —
        identical split selection to serial (exact global election),
        with only SplitInfo-scale collectives."""
        (best_gain, best_f, thr, dleft, lsg, lsh, lsc, lout, rout,
         cat_mask) = self._feature_find_splits(st, grad, hess, counts,
                                               feature_mask)
        do_split, rank, k = self._select_frontier(st, best_gain)
        return self._apply_selection(
            st, do_split, rank, k, best_gain, best_f, thr, dleft,
            lsg, lsh, lsc, lout, rout, cat_mask)

    # ------------------------------------------------------------------
    def _round_voting(self, st: GrowerState, grad, hess, counts,
                      feature_mask) -> GrowerState:
        """Full-frontier round for the voting learner: every active
        leaf's histogram is rebuilt and searched each round."""
        L = self.num_leaves
        M = L - 1
        B = self.max_feature_bin

        res, gains, hist, sel = self._voting_find_splits(
            st, grad, hess, counts, feature_mask)

        # per-leaf best feature & candidate selection
        best_fc = jnp.argmax(gains, axis=1).astype(jnp.int32)  # (L,)
        best_gain = jnp.take_along_axis(gains, best_fc[:, None],
                                        axis=1)[:, 0]
        best_f = best_fc if sel is None else sel[best_fc]
        do_split, rank, k = self._select_frontier(st, best_gain)

        def at_leaf(arr2d):
            # res arrays live in the (possibly compacted) finder space
            return jnp.take_along_axis(arr2d, best_fc[:, None],
                                       axis=1)[:, 0]

        thr = at_leaf(res.threshold)
        cat_dir = at_leaf(res.cat_dir)
        if self.has_categorical:
            hist_chosen = jnp.take_along_axis(
                hist, best_fc[:, None, None, None], axis=1)[:, 0]  # (L,B,3)
            cat_mask = build_cat_bitset(hist_chosen, thr, cat_dir,
                                        self.f_num_bin[best_f],
                                        self.f_missing[best_f],
                                        self.cfg_scalars)
        else:
            cat_mask = jnp.zeros((L, B), bool)

        return self._apply_selection(
            st, do_split, rank, k, best_gain, best_f, thr,
            at_leaf(res.default_left), at_leaf(res.left_sum_grad),
            at_leaf(res.left_sum_hess), at_leaf(res.left_count),
            at_leaf(res.left_output), at_leaf(res.right_output), cat_mask)
