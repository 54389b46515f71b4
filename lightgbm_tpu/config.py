"""Configuration layer: the key=value parameter namespace.

TPU-native re-design of the reference's config system
(reference: include/LightGBM/config.h:94-306 struct hierarchy,
:364-529 alias table + known-parameter set, src/io/config.cpp
CheckParamConflict).  One flat, typed ``Config`` dataclass replaces the
OverallConfig/IOConfig/BoostingConfig/TreeConfig nesting — everything
downstream (binning, grower, boosting, distributed) reads from it, and
the jit-facing subset is hashable so a Config change triggers a
recompile exactly when it must.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, List, Optional, Tuple

from .utils.log import Log

# ---------------------------------------------------------------------------
# Persistent compilation cache (reference analog: none — the CLI
# reference has zero warmup, application.cpp:203; here short jobs are
# compile-dominated: 37 s cold compile for 6.4 s of lambdarank
# training at the MS-LTR bench shape)
# ---------------------------------------------------------------------------
_COMPILE_CACHE_STATE = {"wired": False}

#: where the cache goes when nobody placed it from outside: a fixed
#: path inside the checkout (git-ignored).  Fixed, because the path is
#: part of jax's cache key — a directory that moves never hits.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def resolve_compile_cache_dir(cache_dir: str) -> Optional[str]:
    """The ONE rule for where compiled programs persist; returns the
    directory this program should set, or None when it must set none.

    ``JAX_COMPILATION_CACHE_DIR`` set (or an embedding application
    already configured ``jax_compilation_cache_dir``): the cache was
    placed from outside and the program sets no directory in code.
    Otherwise ``compile_cache_dir``: "auto" (the default) is
    ``<checkout>/.jax_cache``, any other value is that path, ""
    disables.  The test harness (tests/conftest.py) follows the same
    rule with its own default."""
    import jax
    if os.environ.get("JAX_COMPILATION_CACHE_DIR") \
            or jax.config.jax_compilation_cache_dir:
        return None
    if not cache_dir:
        return None
    if cache_dir == "auto":
        return DEFAULT_COMPILE_CACHE_DIR
    return os.path.expanduser(cache_dir)


def _setup_compile_cache(cache_dir: str) -> None:
    """Point jax at a persistent compilation cache, once per process
    (first Config wins: an explicit "" opt-out must stay disabled even
    if a later default-valued Config is constructed).  A cache
    directory that cannot be created is logged and non-fatal — a
    broken cache dir must never stop training; ``chip_smoke.py`` prints
    the hit/miss counters so a dead cache is visible."""
    if _COMPILE_CACHE_STATE["wired"]:
        return
    _COMPILE_CACHE_STATE["wired"] = True
    import jax

    # bridge jax's cache-hit/miss monitoring events into the
    # compile_cache_hits/compile_cache_misses telemetry counters —
    # the registry's warm-before-cutover guarantee is monitored on
    # the Prometheus surface, so the cache can't stay log-only
    from .telemetry import watch_compile_cache
    watch_compile_cache()
    path = resolve_compile_cache_dir(cache_dir)
    if path is None:
        return
    try:
        os.makedirs(path, exist_ok=True)
        entries = sum(1 for _ in os.scandir(path))
    except OSError as e:
        Log.warning(f"persistent compilation cache unavailable "
                    f"({type(e).__name__}: {e})")
        return
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 2)
    Log.info(
        f"persistent compilation cache: {path} "
        + (f"({entries} entries — warm start likely)" if entries
           else "(empty — cold compiles will be cached)"))


# ---------------------------------------------------------------------------
# Alias table (reference: include/LightGBM/config.h:364-457)
# ---------------------------------------------------------------------------
PARAM_ALIASES: Dict[str, str] = {
    "config": "config_file",
    "nthread": "num_threads",
    "num_thread": "num_threads",
    "random_seed": "seed",
    "boosting": "boosting_type",
    "boost": "boosting_type",
    "application": "objective",
    "app": "objective",
    "train_data": "data",
    "train": "data",
    "model_output": "output_model",
    "model_out": "output_model",
    "model_input": "input_model",
    "model_in": "input_model",
    "predict_result": "output_result",
    "prediction_result": "output_result",
    "valid": "valid_data",
    "test_data": "valid_data",
    "test": "valid_data",
    "is_sparse": "is_enable_sparse",
    "enable_sparse": "is_enable_sparse",
    "pre_partition": "is_pre_partition",
    "training_metric": "is_training_metric",
    "train_metric": "is_training_metric",
    "ndcg_at": "ndcg_eval_at",
    "eval_at": "ndcg_eval_at",
    "min_data_per_leaf": "min_data_in_leaf",
    "min_data": "min_data_in_leaf",
    "min_child_samples": "min_data_in_leaf",
    "min_sum_hessian_per_leaf": "min_sum_hessian_in_leaf",
    "min_sum_hessian": "min_sum_hessian_in_leaf",
    "min_hessian": "min_sum_hessian_in_leaf",
    "min_child_weight": "min_sum_hessian_in_leaf",
    "num_leaf": "num_leaves",
    "sub_feature": "feature_fraction",
    "colsample_bytree": "feature_fraction",
    "num_iteration": "num_iterations",
    "num_tree": "num_iterations",
    "num_round": "num_iterations",
    "num_trees": "num_iterations",
    "num_rounds": "num_iterations",
    "num_boost_round": "num_iterations",
    "n_estimators": "num_iterations",
    "sub_row": "bagging_fraction",
    "subsample": "bagging_fraction",
    "subsample_freq": "bagging_freq",
    "shrinkage_rate": "learning_rate",
    "tree": "tree_learner",
    "num_machine": "num_machines",
    "local_port": "local_listen_port",
    "two_round_loading": "use_two_round_loading",
    "two_round": "use_two_round_loading",
    "mlist": "machine_list_file",
    "is_save_binary": "is_save_binary_file",
    "save_binary": "is_save_binary_file",
    "early_stopping_rounds": "early_stopping_round",
    "early_stopping": "early_stopping_round",
    "verbosity": "verbose",
    "header": "has_header",
    "label": "label_column",
    "weight": "weight_column",
    "group": "group_column",
    "query": "group_column",
    "query_column": "group_column",
    "ignore_feature": "ignore_column",
    "blacklist": "ignore_column",
    "categorical_feature": "categorical_column",
    "cat_column": "categorical_column",
    "cat_feature": "categorical_column",
    "predict_raw_score": "is_predict_raw_score",
    "raw_score": "is_predict_raw_score",
    "leaf_index": "is_predict_leaf_index",
    "predict_leaf_index": "is_predict_leaf_index",
    "contrib": "is_predict_contrib",
    "predict_contrib": "is_predict_contrib",
    "min_split_gain": "min_gain_to_split",
    "topk": "top_k",
    "reg_alpha": "lambda_l1",
    "reg_lambda": "lambda_l2",
    "num_classes": "num_class",
    "unbalanced_sets": "is_unbalance",
    "bagging_fraction_seed": "bagging_seed",
    "workers": "machines",
    "nodes": "machines",
    "subsample_for_bin": "bin_construct_sample_cnt",
    "metric_freq": "output_freq",
    "mc": "monotone_constraints",
    "max_tree_output": "max_delta_step",
    "max_leaf_output": "max_delta_step",
}

_OBJECTIVE_ALIASES = {
    "regression_l2": "regression",
    "mean_squared_error": "regression",
    "mse": "regression",
    "l2": "regression",
    "l2_root": "regression",
    "root_mean_squared_error": "regression",
    "rmse": "regression",
    "mean_absolute_error": "regression_l1",
    "mae": "regression_l1",
    "l1": "regression_l1",
    "multiclassova": "multiclassova",
    "multiclass_ova": "multiclassova",
    "ova": "multiclassova",
    "ovr": "multiclassova",
    "softmax": "multiclass",
    "mean_absolute_percentage_error": "mape",
    "xentropy": "cross_entropy",
    "xentlambda": "cross_entropy_lambda",
}

OBJECTIVES = (
    "regression", "regression_l1", "huber", "fair", "poisson", "quantile",
    "mape", "gamma", "tweedie", "binary", "multiclass", "multiclassova",
    "lambdarank", "cross_entropy", "cross_entropy_lambda", "none",
)

BOOSTING_TYPES = ("gbdt", "dart", "goss", "rf")
TREE_LEARNERS = ("serial", "feature", "data", "voting")
DEVICE_TYPES = ("cpu", "tpu", "gpu")  # "gpu" accepted as alias for tpu
TASK_TYPES = ("train", "predict", "convert_model", "refit", "serve")

_TREE_LEARNER_ALIASES = {
    "serial": "serial",
    "feature": "feature", "feature_parallel": "feature",
    "data": "data", "data_parallel": "data",
    "voting": "voting", "voting_parallel": "voting",
}


def canonical_objective(name: str) -> str:
    name = name.lower()
    return _OBJECTIVE_ALIASES.get(name, name)


# ---------------------------------------------------------------------------
# Config dataclass
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Config:
    """Flat, typed parameter set (reference config.h:94-306)."""

    # -- core task --
    task: str = "train"
    objective: str = "regression"
    boosting_type: str = "gbdt"
    device: str = "tpu"
    tree_learner: str = "serial"
    num_threads: int = 0  # lint: disable=CFG002(compat-only: host work is numpy/native-threaded, device work is the TPU program)
    seed: int = 0
    num_machines: int = 1
    verbose: int = 1

    # -- boosting --
    num_iterations: int = 100
    learning_rate: float = 0.1
    num_class: int = 1
    early_stopping_round: int = 0
    output_freq: int = 1
    is_training_metric: bool = False
    snapshot_freq: int = -1
    snapshot_keep: int = 2    # rolling retention for the
    # <output_model>.snapshot_iter_N model snapshots: keep the newest
    # N and delete older ones after each write (long runs used to
    # accumulate unbounded snapshot files); 0 keeps everything
    sigmoid: float = 1.0
    boost_from_average: bool = True
    alpha: float = 0.9            # huber/quantile
    fair_c: float = 1.0
    poisson_max_delta_step: float = 0.7
    tweedie_variance_power: float = 1.5
    reg_sqrt: bool = False
    scale_pos_weight: float = 1.0
    is_unbalance: bool = False
    max_position: int = 20        # lambdarank truncation
    label_gain: Tuple[float, ...] = ()
    metric: Tuple[str, ...] = ()
    ndcg_eval_at: Tuple[int, ...] = (1, 2, 3, 4, 5)

    # -- tree --
    num_leaves: int = 31
    max_depth: int = -1
    min_data_in_leaf: int = 20
    min_sum_hessian_in_leaf: float = 1e-3
    lambda_l1: float = 0.0
    lambda_l2: float = 0.0
    min_gain_to_split: float = 0.0
    max_delta_step: float = 0.0
    feature_fraction: float = 1.0
    feature_fraction_seed: int = 2
    bagging_fraction: float = 1.0
    bagging_freq: int = 0
    bagging_seed: int = 3
    max_bin: int = 255
    min_data_in_bin: int = 3
    bin_construct_sample_cnt: int = 200000
    data_random_seed: int = 1
    monotone_constraints: Tuple[int, ...] = ()
    max_cat_threshold: int = 32
    cat_l2: float = 10.0
    cat_smooth: float = 10.0
    max_cat_to_onehot: int = 4
    top_k: int = 20               # voting parallel
    forcedsplits_filename: str = ""

    # -- dart --
    drop_rate: float = 0.1
    max_drop: int = 50
    skip_drop: float = 0.5
    xgboost_dart_mode: bool = False
    uniform_drop: bool = False
    drop_seed: int = 4

    # -- goss --
    top_rate: float = 0.2
    other_rate: float = 0.1

    # -- io --
    data: str = ""
    valid_data: Tuple[str, ...] = ()
    input_model: str = ""
    output_model: str = "LightGBM_model.txt"
    output_result: str = "LightGBM_predict_result.txt"
    convert_model: str = "gbdt_prediction.cpp"
    convert_model_language: str = ""
    has_header: bool = False
    label_column: str = ""
    weight_column: str = ""
    group_column: str = ""
    ignore_column: str = ""
    categorical_column: str = ""
    is_pre_partition: bool = False  # lint: disable=CFG002(distributed loaders always treat per-host shards as pre-partitioned; accepted for reference CLI parity)
    use_two_round_loading: bool = False
    streaming_chunk_rows: int = 65536  # rows per two-round/PushRows
    # text chunk (bounds peak float-row memory during streaming load;
    # two-round parsing overlaps binning via a bounded two-chunk
    # queue, so at most FOUR parsed chunks coexist — two queued, one
    # in the producer's hand, one being binned)
    construct_threads: str = "auto"  # host threads for dataset
    # construction: per-feature bin-mapper fitting, the native dense
    # binner's row blocks, and the CSC column loop all fan across a
    # thread pool (numpy sort/searchsorted and the native binner
    # release the GIL).  "auto" = host core count; an integer pins it;
    # 1 reproduces the serial path exactly — results are
    # byte-identical at EVERY setting (parallelism is across
    # features/row-blocks, never inside one reduction)
    bin_packing: str = "8bit"  # bin-matrix storage width
    # (lightgbm_tpu/packing.py): "8bit" stores one group per uint8
    # byte (legacy layout, every existing cache); "4bit" nibble-packs
    # two <=16-bin groups per byte end to end — host matrix, caches,
    # device HBM and the histogram kernels' read stream all halve
    # (requires max_bin <= 16; trees are byte-identical to the 8-bit
    # path on every packed-capable kernel route — tiled/fused/
    # streamed-one-hot/XLA, i.e. every default selection; the one
    # Pallas formulation without a packed input path, the float
    # tier's expansion kernel, falls back to XLA with a loud warning
    # and only f32-level parity); "auto" is adaptive precision — groups whose
    # fitted bin count fits 4 bits pack even when others don't, via a
    # two-section (packed + wide) layout, and <=2-bit groups tighten
    # further to crumbs; "2bit" crumb-packs four <=4-bin groups per
    # byte (requires max_bin <= 4) for a 4x read-stream cut — the
    # three-section (crumb + nibble + wide) layout.  The resolved
    # device matrix size is the bin_matrix_bytes telemetry gauge
    binary_cache_v2: bool = True  # save_binary writes the v2 container
    # (magic + schema version + pickled mapper/metadata header + a raw
    # np.memmap-able group_bins section): load_binary maps the bin
    # matrix zero-copy instead of unpickling a full in-RSS copy.
    # false restores the v1 pickle payload; v1 files always load, with
    # a deprecation warning
    is_save_binary_file: bool = False
    is_enable_sparse: bool = True
    enable_bundle: bool = True    # EFB
    max_conflict_rate: float = 0.0
    is_enable_bundle: bool = True
    min_data_in_group: int = 100
    use_missing: bool = True
    zero_as_missing: bool = False
    num_iteration_predict: int = -1
    is_predict_raw_score: bool = False
    is_predict_leaf_index: bool = False
    is_predict_contrib: bool = False
    pred_early_stop: bool = False
    pred_early_stop_freq: int = 10
    pred_early_stop_margin: float = 10.0

    # -- network --
    local_listen_port: int = 12400
    time_out: int = 120
    machine_list_file: str = ""
    machines: str = ""
    collective_transport: str = "auto"  # cross-process collective
    # backend: "xla" runs jax.distributed + cross-process XLA
    # collectives (pods); "tcp" runs the host-side TCP transport
    # (parallel/transport.py — the Linker analog: coordinator
    # rendezvous, persistent peer sockets, Bruck allgather + ring
    # allreduce over numpy buffers); "auto" picks tcp exactly when a
    # multi-process world is requested and cross-process XLA
    # collectives are unavailable (the CPU backend), xla otherwise
    # (docs/Parallel-Learning-Guide.md transport-selection matrix)
    transport_epoch_iters: int = 1  # boosting iterations between
    # elastic-membership epoch boundaries when a TCP transport is
    # active: every N iterations all participants tick the WorldLedger
    # coordinator, dead peers retire (degraded continuation per
    # sharded_allow_degraded), and waiting joiners are admitted with a
    # state + shard-cache handoff.  1 = a boundary after every
    # iteration (fastest re-join, one tiny control round each)
    transport_reconnect_retries: int = 3  # in-epoch reconnect dials
    # after a reset/EOF mid-collective before the peer is declared
    # TransportPeerLost (degrade path): a transient network blip heals
    # with an idempotent resend instead of permanently shrinking the
    # world; 0 disables reconnection (every reset degrades, the pre-
    # hardening behavior).  Each dial backs off exponentially inside
    # the armed collective deadline (docs/RELIABILITY.md
    # reconnect-vs-degrade row)

    # -- tpu-specific (new; no reference analog) --
    hist_compute_dtype: str = "float32"  # one-hot matmul input dtype
    # (bfloat16 roughly doubles MXU throughput at ~0.4% grad rounding;
    # opt in for benchmarks, keep float32 for reference parity.  The
    # ACCUMULATION dtype is deliberately not a knob: every histogram
    # matmul pins preferred_element_type=float32, and analysis rule
    # HLO001 pins the no-f64 side)
    frontier_width: int = 0         # max splits applied per frontier round
    # (0 = auto: min(126, num_leaves-1) — three 42-leaf strips of the
    # channel-packed histogram kernel.  84 is ~3% faster at the 1M
    # binary bench shape but measurably hurts lambdarank NDCG at 255
    # leaves; growth order near the leaf cap is a documented,
    # quality-bounded deviation from one-split-at-a-time)
    hist_kernel: str = "auto"       # auto | pallas | xla
    # (auto: what ops/hist_plan.py resolves from the backend, the mesh,
    # hist_compute_dtype and quantized_grad; pallas raises where the
    # Pallas kernels cannot run: a feature / voting / multi-axis mesh,
    # no TPU and no force_pallas_interpret, rows not padded to 1024 a
    # shard)
    quantized_grad: bool = False    # int8-MXU quantized histogram
    # construction (one grad/hess scale per tree; the TPU analog of
    # LightGBM v4 quantized training, arXiv 2207.09682) — TPU path
    # only.  Any number of rows a device: an int32 accumulator sums a
    # row segment of at most 2^24 rows (rows * 127 < 2^31) and the
    # segments are added exactly, as a mesh's shards are
    quant_stochastic_rounding: int = -1  # round the quantized
    # gradients stochastically (the v4 recipe, unbiased in
    # expectation): -1 = auto (the objective decides — lambdarank
    # REQUIRES it: deterministic rounding zeroes the long tail of
    # pairwise lambdas, measured 0.33 vs 0.64 held-out NDCG@10 at the
    # MS-LTR shape, while binary/regression gradients are well-spread
    # and skip the ~7% per-tree RNG cost), 0 = always deterministic,
    # 1 = always stochastic
    hist_precision: str = "auto"  # histogram accumulation precision
    # tier (the Booster-accelerator narrow-accumulate + late-widen
    # recipe, arXiv 2011.02022): "f32" always accumulates float32
    # (quantized_grad is ignored); "tiered" forces the int32
    # quantized-weight kernel path with its f32 fix-up (dequantize)
    # pass before split finding — a loud kernel-plan error when no
    # quantized kernel route exists (the row count is no cause: past
    # 2^24 rows a device the accumulation runs in row segments, each
    # inside the int32 bound rows * 127 < 2^31, folded exactly);
    # "auto" follows quantized_grad alone, so trees stay byte-identical
    # to the pre-tier behavior.  The chosen
    # tier is the grower.hist_precision telemetry gauge; fix-up passes
    # count in hist_quant_fixup
    hist_exchange: str = "f32"  # cross-shard histogram exchange codec
    # (data-parallel row sharding): "f32" psums raw float32 histograms
    # (legacy lowering, byte-identical trees); "q16"/"q8" delta-code
    # each (leaf, group) histogram along the bin axis and quantize to
    # int16/int8 with per-(leaf, group, channel) scales riding the
    # payload — the ICI exchange stream drops ~2x/4x (the
    # collective_hist_exchange_bytes counter) at bounded
    # reconstruction error; scales are psum'd exactly, int sums get
    # world-size headroom so the integer psum can never overflow
    histogram_pool_size: float = -1.0  # MB bound on the per-leaf
    # histogram cache (reference config.h:216 + the LRU HistogramPool,
    # feature_histogram.hpp:653-823).  -1 = unbounded.  When the
    # (num_leaves, G, B, 3) f32 cache exceeds the bound, the grower
    # drops histogram subtraction and computes BOTH children of every
    # split directly from the data (2x histogram passes, no cache).
    dispatch_chunk: str = "auto"    # boosting iterations fused into ONE
    # device program (lax.scan) during headless training stretches: an
    # integer pins the chunk length; "auto" re-fits the per-iteration
    # chunk slope from two timed probe chunks at run start and picks
    # the amortization point sqrt(dispatch_cost / slope) — larger
    # chunks amortize the measured per-dispatch host cost, while the
    # per-iteration carry cost grows with chunk length
    # (docs/ROOFLINE.md round-6/7).  The packed tree
    # carry (packed_tree_carry) is what makes long chunks cheap; this
    # knob is the one-flag on-chip A/B for chunk-90-at-chunk-10-speed
    packed_tree_carry: str = "auto"  # carry each finished tree through
    # the fused dispatch scan as ONE byte-packed record buffer
    # (tree.TreeRecordLayout) instead of 18 separate stacked output
    # arrays — the round-6 diagnosis traced the per-iteration chunk
    # penalty to the TPU backend's handling of the 18 O(chunk) loop-
    # carried output stacks.  auto = on; "off" restores the legacy
    # 18-array carry (byte-identical trees either way, pinned by test)
    split_finder_ladder: bool = True  # run a round's whole refresh —
    # parent-minus-right, the histogram cache's update, the best-split
    # finder and the candidate-cache scatter — at the width of the rung
    # that served its histogram pass (ONE ladder: the factored rungs'
    # slot caps, or the packed strips') instead of the full frontier
    # cap — early rounds of every tree have 1-2 new leaves, and the
    # glue and the finder's (2W, F, B) threshold sweep were the last
    # frontier-capped costs (ROOFLINE headroom #2; PERF.md PR 36).
    # False keeps the pass's rungs and does the rest at the cap
    predict_kernel: str = "auto"    # device predictor implementation:
    # "level" (default for auto) is the ensemble-vectorized
    # level-synchronous descent — all trees advance together over the
    # row tile, one feature gather per level across the whole ensemble;
    # "pallas" is its row-tile kernel form keeping the stacked ensemble
    # resident in VMEM — interpret-seam only: on a v5e the Pallas TPU
    # lowering REFUSES it ("Only 2D gather is supported", jax 0.9.0,
    # PR 21) and selecting it raises; "scan" restores the legacy
    # per-tree lax.scan node walk (two full-matrix gathers per node
    # step) for A/B
    predict_bucket: str = "auto"    # shape-bucketed predict compile
    # cache: batch sizes round UP to power-of-two row buckets with
    # masked (padded, discarded) tails, so micro-batch serving compiles
    # once per bucket instead of once per batch size.  auto = on;
    # "off" compiles per exact batch shape (legacy)
    predict_min_bucket_rows: int = 16  # smallest row bucket (single-row
    # serving calls share one compiled program up to this size)
    predict_chunk_rows: int = 0     # rows per device dispatch for bulk
    # scoring; batches above it stream in fixed full-bucket chunks with
    # at most two results in flight (double buffering), so HIGGS-scale
    # scoring never densifies the whole matrix on device.  0 = auto:
    # sized from the per-row device footprint against a ~256 MB
    # transient budget, clamped to [4096, 1M] rows
    predict_pallas_tile: int = 512  # rows per Pallas predict tile
    # (predict_kernel=pallas); shrinks to the bucket when smaller
    predict_warm_buckets: Tuple[int, ...] = ()  # serving warm-up:
    # batch sizes whose buckets are pre-compiled after train() /
    # on warm_predictor(), so the first request doesn't pay the
    # compile (a disk hit across processes via compile_cache_dir)
    compile_cache_dir: str = "auto"  # persistent XLA compilation
    # cache directory (jax_compilation_cache_dir): repeat processes
    # skip the multi-second cold compile.  "auto" = <checkout>/.jax_cache
    # (a fixed path: the path is part of the cache key); "" disables.
    # Applied by the first Config created in the process — and NOT AT
    # ALL when JAX_COMPILATION_CACHE_DIR is set or the embedding
    # application already configured a cache: a cache placed from
    # outside is the only one the program uses
    native_binning: bool = True     # dense numerical matrices: bin via
    # the native std::lower_bound loop (bit-identical to the numpy
    # searchsorted path, ~10x faster — numpy dominates large-matrix
    # prep otherwise)
    force_pallas_interpret: bool = False  # test seam: run the Pallas
    # kernel paths (incl. the fused-route grower wiring) in interpret
    # mode on CPU — slow, for CI coverage of the TPU-only code paths
    telemetry: str = "off"          # runtime telemetry subsystem
    # (docs/OBSERVABILITY.md): "off" records nothing; "counters" keeps
    # named counters and gauges (trees dispatched, compiles observed,
    # set-up stage times, serving bucket hit/miss, RSS watermark) with
    # zero device interference and shows its spans to an active
    # profiler session; "spans" adds nested timing spans in memory
    # plus a per-dispatch device fence that splits wall time into
    # host_dispatch_ms vs device_wait_ms (the r7 bench split, now
    # first-class); "trace" is an accepted alias of "spans".  Every
    # mode compiles the SAME programs: the tel.<phase> named scopes
    # over the chunk program are op metadata and are always on
    telemetry_out: str = ""         # export path prefix: on process
    # exit (and after each CLI task) telemetry writes <prefix>.jsonl
    # (newline-JSON events + a final counter snapshot) and
    # <prefix>.perfetto.json (Chrome trace_event, loadable in
    # ui.perfetto.dev); "" disables export (counters stay readable
    # in-process via lightgbm_tpu.telemetry.TELEMETRY.snapshot())
    telemetry_retrace_warn: int = 8  # retrace sentinel: warn (once
    # per function) when a jitted entry point has traced more than
    # this many DISTINCT shapes — each retrace is an XLA compilation,
    # so shape churn past the serving bucket ladder is a production
    # latency bug.  Counts are exported either way; the guard itself
    # is active even at telemetry=off (trace-time cost only)
    telemetry_prom_out: str = ""    # Prometheus text-format export
    # path (the node-exporter textfile-collector pattern): counters,
    # numeric gauges and the serving latency histograms are written
    # atomically at CLI task end / process exit so any scraper can
    # derive p50/p95/p99 from the cumulative buckets — no new
    # dependencies, stdlib only (docs/OBSERVABILITY.md, Prometheus
    # export).  "" disables
    telemetry_http_port: int = 0    # stdlib HTTP scrape endpoint on
    # 127.0.0.1: GET /metrics returns the Prometheus text format, GET
    # /healthz a JSON liveness body — the serving path becomes
    # scrapeable without a sidecar.  0 disables (the default); the
    # server is a daemon thread started by the first enabling Config
    flight_recorder_out: str = ""   # crash flight recorder
    # (docs/OBSERVABILITY.md): arm a bounded ring of recent
    # span/counter/log events that the reliability layer dumps to
    # <prefix>-<ns>.flight.json on injected faults, retry exhaustion,
    # OOM downshift or unhandled exception — the last-N telemetry
    # events correlated with the fault seam that fired.  "" disables
    slo_rules: str = ""             # SLO burn-rate engine
    # (lightgbm_tpu/slo.py, docs/OBSERVABILITY.md "SLO burn-rate
    # engine"): path to a JSON rules document (quantile / ratio / rate
    # / gauge bounds over the live metric registry) evaluated on a
    # timer with fast/slow burn windows; breaches publish ltpu_slo_*
    # gauges, journal an slo_breach event and dump the flight
    # recorder, and GET /slo on the shared listener answers the
    # verdict.  Parsed eagerly at Config time (a typo'd rules file
    # fails the run, the fault_plan contract).  "" disables
    slo_eval_interval_s: float = 10.0  # seconds between timer
    # evaluations of the armed slo_rules document (floor 0.5s); the
    # GET /slo route additionally evaluates on demand
    mesh_shape: Tuple[int, ...] = ()
    mesh_axes: Tuple[str, ...] = ()
    sharded_shards: int = 0         # mesh-sharded dataset construction
    # (lightgbm_tpu/sharded/, docs/Parallel-Learning-Guide.md "Sharded
    # construction"): split the training rows into this many disjoint
    # participant ranges, fit bin mappers DISTRIBUTED (per-range
    # boundary candidates allgathered + deterministically merged — the
    # reference DatasetLoader's bin-boundary sync), stream-ingest each
    # range into its own bin-matrix shard and place the shards
    # per-device over the mesh row axis.  Trees are byte-identical to
    # the single-matrix route.  0/1 disables (default: one host-
    # resident packed matrix)
    sharded_cache_dir: str = ""     # shard-cache v2 directory: after a
    # sharded construction the per-shard bin matrices are persisted as
    # one v2 binary-cache file each plus a manifest (world size, row
    # ranges, mapper fingerprint); a later run with a matching
    # sharded_shards reloads the shards zero-copy (memmap) and REFUSES
    # a world-size or fingerprint mismatch loudly.  "" disables
    sharded_sample_per_shard: int = 0  # per-participant boundary-
    # candidate sample quota for distributed bin finding; 0 derives
    # bin_construct_sample_cnt / sharded_shards (so the merged sample
    # matches the single-host sample budget)
    sharded_allow_degraded: bool = False  # degraded-mode continuation
    # for sharded construction: when a participant's binfind/ingest
    # seam dies (or hangs past watchdog_collective_s), EXCLUDE it —
    # log loudly, count sharded_degraded_exclusions — and continue on
    # the surviving participants with quota-rebalanced shards; the
    # degraded run's trees are byte-identical to a from-scratch run
    # on the surviving world (pinned by tests/test_chaos.py).  false
    # (default) keeps today's fail-fast: any participant failure
    # fails the construction loudly

    # -- serving (new; no reference analog) --
    serve_batch_deadline_ms: float = 2.0  # micro-batching scheduler
    # (lightgbm_tpu/serving/batcher.py): how long the dispatcher holds
    # the OLDEST queued request open to coalesce concurrent requests
    # into one power-of-two bucket dispatch.  0 dispatches immediately
    # (no coalescing window); larger values trade first-request
    # latency for batch fill under concurrent single-row traffic
    serve_shed_deadline_ms: float = 100.0  # admission control: a
    # request whose PROJECTED queue wait (batches ahead x the EWMA
    # dispatch wall) exceeds this is shed at submit time — the HTTP
    # frontend answers 503 with a Retry-After header instead of
    # letting the queue grow without bound (docs/SERVING.md)
    serve_queue_depth: int = 1024   # bounded request queue per served
    # model version: submissions beyond this many waiting requests are
    # shed (503) rather than queued — the memory bound on a stalled
    # serving process
    serve_max_batch_rows: int = 1024  # coalesced-dispatch row cap:
    # the batcher never merges requests past this many rows into one
    # dispatch (rounded up to the power-of-two bucket); a single
    # request larger than the cap dispatches alone and chunk-streams
    # inside the predictor
    serve_port: int = 0             # HTTP port for task=serve (the
    # /predict/<model> endpoint shares ONE listener with the
    # telemetry /metrics + /healthz daemon).  0 binds an ephemeral
    # port (logged at startup); when telemetry_http_port is set the
    # serving routes mount on that already-running listener instead
    serve_lanes: str = "auto"       # device lane fleet
    # (lightgbm_tpu/serving/lanes.py): how many parallel dispatch
    # streams the registry runs.  "auto" = one lane per local device
    # on a TPU backend and 1 on host backends; an
    # explicit N forces N lanes (sharing devices round-robin past the
    # device count — on a single device the N lanes are simulated,
    # unpinned workers, the CPU test seam).  1 lane keeps the r14
    # inline dispatch exactly; >= 2 builds the LanePool: round-robin
    # routing with work stealing, per-lane stall isolation, and
    # warm-before-cutover on EVERY lane's device (docs/SERVING.md)
    serve_cobatch: str = "off"      # multi-model co-batching
    # (lightgbm_tpu/serving/cobatch.py): "on" fuses served models
    # that share a feature width and bucket ladder into ONE compiled
    # program and one coalescing window — concurrent requests for
    # ANY member dispatch together, each request's answer is its
    # model's column segment of the fused output, byte-identical to
    # that model's solo predict (pinned by tests/test_serve_lanes.py).
    # Only level-descent-routed entries with no custom predict
    # kwargs fuse; everything else keeps its solo batcher.  "off"
    # (default) serves every model on its own batcher as before

    # -- model-quality observability (new; no reference analog) --
    quality: str = "auto"           # model-quality observability
    # (lightgbm_tpu/quality/, docs/MODEL_MONITORING.md): "on" captures
    # a QualityProfile at train time (per-feature bin-occupancy
    # histograms from the already-built bin matrix, the training
    # prediction-score histogram, per-tree leaf occupancy) persisted
    # beside the model file, and REQUIRES serving-side drift monitors
    # (warns when no profile is found); "auto" (default) captures
    # nothing at train time but arms serving monitors whenever a
    # profile sits beside the published model AND quality_sample_rate
    # is > 0; "off" disables everything — the serving path then does
    # ONE attribute check and lowers byte-identical StableHLO
    # (pinned by tests/test_quality.py)
    quality_sample_rate: float = 0.0  # serving-side drift monitors:
    # fraction of served rows the deterministic counter-strided
    # sampler feeds the monitors (no RNG — row k of the serving
    # stream is sampled iff k % round(1/rate) == 0, so replays sample
    # identical rows regardless of batch coalescing).  Sampled rows
    # bin host-side through the profile's frozen BinMapper tables;
    # predictions stay byte-identical.  0 disables the monitors
    quality_psi_warn: float = 0.2   # per-feature PSI threshold: past
    # it the monitor warns ONCE naming the top drifted features,
    # bumps quality_drift_warns and fires a flight-recorder event
    # (0.1 = minor shift, 0.2 = action-worthy drift — the standard
    # PSI rule of thumb; docs/MODEL_MONITORING.md runbook)
    quality_drift_refit_threshold: float = 0.0  # close the loop:
    # worst-feature PSI past this reports a serving-drift event into
    # the continuous lane's ledger-committed drift tally (the same
    # tally continuous_drift_refit_threshold reads), so LIVE drift —
    # not only ingest drift — can flip a continuous cycle to refit.
    # One report per breach episode (re-arms once PSI falls back
    # under half the threshold).  0 disables (the default)
    quality_profile_rows: int = 4096  # deterministic strided row cap
    # for the profile's leaf-occupancy pass (pred_leaf over every
    # stride-th training row) and for the raw-row sample retained
    # when free_raw_data would drop the matrix before profiling

    # -- continuous training (new; no reference analog) --
    continuous_mode: str = "continue"  # training lane per-cycle
    # strategy (docs/CONTINUOUS_TRAINING.md): "continue" boosts
    # continuous_iterations NEW trees per cycle from the last accepted
    # model (init_model semantics) over the base rows plus every
    # ingested slice; "refit" keeps the tree structures and refits
    # leaf values on the cycle's fresh labels (reference RefitTree
    # semantics via Booster.refit)
    continuous_ingest_dir: str = ""  # directory the ingest watcher
    # polls for new data slices (same text formats as `data`; a
    # MANIFEST file in the directory pins an explicit slice order
    # instead of sorted names).  Setting it arms the continuous lane
    # under task=serve; "" disables
    continuous_state_dir: str = ""  # continuous lane state directory
    # (ledger, per-cycle candidate models, mid-cycle checkpoints,
    # quarantine records); "" derives <continuous_ingest_dir>/.continuous
    continuous_poll_s: float = 5.0  # ingest watcher poll interval
    # (seconds) between directory scans when the lane runs threaded;
    # POST /continuous {"action": "force_cycle"} skips the wait
    continuous_iterations: int = 10  # boosting iterations added per
    # continue-mode cycle (ignored by refit mode, which grows no trees)
    continuous_eval_holdout: float = 0.2  # tail fraction of every
    # ingested slice held out of training and scored by the eval gate
    # (deterministic tail split — no RNG, so a killed cycle replays
    # the exact same train/eval rows).  0 disables the gate: every
    # candidate publishes
    continuous_publish_max_regression: float = 0.0  # eval gate: a
    # candidate may regress the gated metric by at most this much
    # against the currently published model on the same eval slice
    # (metric-direction aware); worse candidates are quarantined
    # instead of published.  The same bound guards the post-publish
    # live-metric hook — a live regression past it auto-rolls the
    # registry back
    continuous_drift_refit_threshold: int = 0  # drift-triggered
    # base-refit (docs/CONTINUOUS_TRAINING.md, drift semantics): once
    # this many slices have drifted (cumulative across cycles, tracked
    # in the ledger), the NEXT cycle runs a `refit` against the
    # slices' raw values — leaf values refreshed through the model's
    # REAL-VALUED thresholds, immune to the frozen mappers' edge-bin
    # clamping — instead of only warning, then the drift tally resets.
    # 0 disables (the default: drift warns and counts only)
    continuous_cycle_interval_s: float = 0.0  # scheduled (cron-style)
    # cycles beside the directory watcher: every this many seconds the
    # lane runs a cycle even when no new slices arrived (continue mode
    # trains continuous_iterations fresh trees over the accumulated
    # data, exactly like a force_cycle).  The next-due time is
    # LEDGER-COMMITTED, so a restarted daemon keeps the schedule
    # instead of firing immediately; the clock is injectable for
    # tests.  0 disables (the default: cycles fire on new slices or
    # force_cycle only)
    continuous_checkpoint_freq: int = 0  # mid-cycle crash-safe
    # checkpoint cadence (iterations) for continue-mode training
    # (docs/RELIABILITY.md machinery, per-cycle checkpoint files); 0
    # checkpoints nothing mid-cycle — a killed cycle then replays from
    # the cycle start, which stays byte-identical, just slower

    # -- reliability (new; no reference analog) --
    checkpoint_freq: int = -1   # save a crash-safe FULL-training-state
    # checkpoint every this many iterations (model + score cache +
    # bagging/GOSS RNG streams + eval history + early-stopping state —
    # docs/RELIABILITY.md): a run killed mid-train resumes from the
    # newest valid checkpoint and produces byte-identical trees to an
    # uninterrupted run.  -1 disables (the default); checkpoints are
    # written atomically (tmp + fsync + rename) with a rolling
    # retention of checkpoint_keep files
    checkpoint_path: str = ""   # checkpoint file prefix (files are
    # <prefix>_iter_N); "" derives <output_model>.ckpt
    checkpoint_keep: int = 2    # rolling checkpoint retention: the
    # newest N checkpoint files are kept, older ones deleted only
    # AFTER the new one is durable — a crash mid-save always leaves a
    # valid checkpoint behind
    resume: str = "auto"        # resume policy when checkpointing is
    # active: "auto" scans <checkpoint_path>_iter_* for the newest
    # VALID checkpoint whose config/dataset fingerprint matches and
    # continues from it (corrupt/truncated files are rejected loudly,
    # falling back to the previous valid one); "off" always starts
    # cold; an explicit file path resumes from exactly that checkpoint
    # (and errors loudly if it is invalid)
    dispatch_retries: int = 2   # bounded retries of TRANSIENT-
    # classified errors (connection/timeout/UNAVAILABLE — never OOM,
    # never real bugs) at the device-dispatch and distributed-init
    # seams, with exponential backoff + jitter from retry_backoff_s
    retry_backoff_s: float = 0.5  # base backoff delay; attempt k
    # sleeps min(30, retry_backoff_s * 2^k) * uniform(1, 1.25)
    oom_downshift: bool = True  # graceful degradation under
    # RESOURCE_EXHAUSTED: the serving predictor halves its row
    # bucket/chunk ladder and training halves the fused-chunk length
    # instead of crashing the request or the job (warned once,
    # counted in the oom_downshifts telemetry counter)
    fault_plan: str = ""        # deterministic fault-injection plan
    # (config-file form of the LTPU_FAULT_PLAN env var):
    # "seam:nth:action[:xCount];..." raises/kills/hangs on the Nth
    # call at a registered seam (actions: kill, oom, hang:<ms>,
    # slow:<ms>, or a builtin exception name) — the mechanism every
    # recovery test drives its failures through; the seeded
    # "chaos:<seed>:<n_faults>[:<seam_glob>]" form draws randomized
    # multi-fault plans replayable from the seed
    # (docs/RELIABILITY.md, fault-plan grammar + chaos testing)
    watchdog_dispatch_s: float = 0.0  # deadline watchdog
    # (reliability/watchdog.py): bound on the fused-chunk /
    # per-iteration dispatch enqueue — a dispatch that has not
    # returned within this many seconds dumps ALL-thread stacks to
    # the flight recorder and surfaces a classified StallError
    # through the retry machinery (transient: bounded retries apply).
    # 0 (default) leaves the dispatch unbounded
    watchdog_collective_s: float = 0.0  # deadline on blocking host
    # collectives (distributed._allgather, HostCollectives gathers)
    # and on each sharded-construct participant's binfind/ingest work
    # — the Network time_out analog for every collective op; with
    # sharded_allow_degraded=true a participant stalled past it is
    # EXCLUDED and construction continues on the surviving world.
    # When a TCP transport is active the deadline also arms PER
    # communication round (parallel/transport.py): a hung peer bounds
    # that round's socket waits and surfaces a retryable StallError.
    # 0 = unbounded
    watchdog_checkpoint_s: float = 0.0  # deadline on checkpoint/
    # ledger file IO (atomic writes + checkpoint reads): a wedged
    # filesystem surfaces as a StallError instead of freezing
    # training silently.  0 = unbounded
    watchdog_serve_s: float = 0.0  # deadline on each coalesced
    # serving dispatch (serving/batcher.py): a stalled dispatch fails
    # its batch with a StallError — the HTTP frontend answers 503 +
    # Retry-After (stall-classified, counted in ltpu_stalls_total /
    # serve_stalls) instead of letting every client time out
    # together.  0 = unbounded
    watchdog_continuous_s: float = 0.0  # deadline on each
    # continuous-lane cycle PHASE (ingest/train/eval/publish): the
    # monitor thread dumps all-thread stacks and counts a stall when
    # a phase exceeds it (observability — the phase itself is not
    # interrupted).  0 = unbounded

    # free-form passthrough of unrecognized params (warned, kept for
    # echo; consumed wholesale through to_dict/model-file echo, never
    # by attribute)
    extra: Dict[str, str] = dataclasses.field(default_factory=dict)  # lint: disable=CFG002(passthrough container, consumed wholesale via to_dict)

    # ------------------------------------------------------------------
    def __post_init__(self):
        self.objective = canonical_objective(self.objective)
        self.tree_learner = _TREE_LEARNER_ALIASES.get(self.tree_learner,
                                                      self.tree_learner)
        if self.device == "gpu":
            self.device = "tpu"
        self.telemetry = str(self.telemetry).lower()
        self.quality = str(self.quality).lower()
        self.check()
        _setup_compile_cache(self.compile_cache_dir)
        from .telemetry import apply_config as _telemetry_apply
        _telemetry_apply(self)
        from .reliability.faults import apply_config as _faults_apply
        _faults_apply(self)
        from .reliability.watchdog import apply_config as _wd_apply
        _wd_apply(self)
        if self.slo_rules:
            from .slo import apply_config as _slo_apply
            _slo_apply(self)

    # ------------------------------------------------------------------
    def check(self):
        """Parameter validation (reference: src/io/config.cpp CheckParamConflict)."""
        if self.objective not in OBJECTIVES:
            raise ValueError(f"Unknown objective: {self.objective}")
        if self.boosting_type not in BOOSTING_TYPES:
            raise ValueError(f"Unknown boosting_type: {self.boosting_type}")
        if self.tree_learner not in TREE_LEARNERS:
            raise ValueError(f"Unknown tree_learner: {self.tree_learner}")
        if self.num_leaves < 2:
            raise ValueError("num_leaves must be >= 2")
        if not (0.0 < self.feature_fraction <= 1.0):
            raise ValueError("feature_fraction must be in (0, 1]")
        if not (0.0 < self.bagging_fraction <= 1.0):
            raise ValueError("bagging_fraction must be in (0, 1]")
        if self.max_bin < 2:
            raise ValueError("max_bin must be >= 2")
        if self.max_bin > 256:
            raise ValueError(
                "max_bin must be <= 256 (bin_packing=8bit stores one "
                "group bin per uint8 byte; bin_packing=4bit/2bit/auto "
                "packs two <=16-bin (four <=4-bin) groups per byte but "
                "never widens past a byte)")
        if str(self.bin_packing).lower() not in ("auto", "8bit", "4bit",
                                                 "2bit"):
            raise ValueError("bin_packing must be auto/8bit/4bit/2bit, "
                             f"got {self.bin_packing!r}")
        if str(self.bin_packing).lower() == "4bit" and self.max_bin > 16:
            raise ValueError(
                f"bin_packing=4bit requires max_bin <= 16 (a nibble "
                f"holds 16 bins), got max_bin={self.max_bin} — lower "
                "max_bin or use bin_packing=auto, which packs only the "
                "feature groups that fit and keeps wide groups "
                "byte-wide")
        if str(self.bin_packing).lower() == "2bit" and self.max_bin > 4:
            raise ValueError(
                f"bin_packing=2bit requires max_bin <= 4 (a crumb "
                f"holds 4 bins), got max_bin={self.max_bin} — lower "
                "max_bin or use bin_packing=auto, which crumb-packs "
                "only the feature groups that fit and keeps wider "
                "groups nibble- or byte-wide")
        if str(self.hist_precision).lower() not in ("auto", "f32",
                                                    "tiered"):
            raise ValueError("hist_precision must be auto/f32/tiered, "
                             f"got {self.hist_precision!r}")
        if str(self.hist_exchange).lower() not in ("f32", "q16", "q8"):
            raise ValueError("hist_exchange must be f32/q16/q8, got "
                             f"{self.hist_exchange!r}")
        if str(self.collective_transport).lower() not in (
                "auto", "xla", "tcp"):
            raise ValueError("collective_transport must be "
                             "auto/xla/tcp, got "
                             f"{self.collective_transport!r}")
        if self.transport_epoch_iters < 1:
            raise ValueError("transport_epoch_iters must be >= 1, got "
                             f"{self.transport_epoch_iters}")
        if self.transport_reconnect_retries < 0:
            raise ValueError(
                "transport_reconnect_retries must be >= 0, got "
                f"{self.transport_reconnect_retries}")
        if self.objective in ("multiclass", "multiclassova") and self.num_class < 2:
            raise ValueError(f"num_class must be >= 2 for {self.objective}")
        if self.objective not in ("multiclass", "multiclassova") and self.num_class != 1:
            raise ValueError("num_class must be 1 for non-multiclass objectives")
        if self.boosting_type == "goss" and self.top_rate + self.other_rate > 1.0:
            raise ValueError("GOSS: top_rate + other_rate must be <= 1.0")
        if self.boosting_type == "rf" and (self.bagging_freq <= 0
                                           or self.bagging_fraction >= 1.0):
            raise ValueError("RF must use bagging "
                             "(bagging_freq > 0, bagging_fraction < 1)")
        if str(self.packed_tree_carry).lower() not in (
                "auto", "on", "off", "true", "false", "1", "0"):
            raise ValueError("packed_tree_carry must be auto/on/off, "
                             f"got {self.packed_tree_carry!r}")
        if str(self.predict_kernel).lower() not in (
                "auto", "level", "pallas", "scan"):
            raise ValueError("predict_kernel must be auto/level/pallas/"
                             f"scan, got {self.predict_kernel!r}")
        if str(self.predict_bucket).lower() not in (
                "auto", "on", "off", "true", "false", "1", "0"):
            raise ValueError("predict_bucket must be auto/on/off, "
                             f"got {self.predict_bucket!r}")
        if self.predict_min_bucket_rows < 1:
            raise ValueError("predict_min_bucket_rows must be >= 1")
        if self.predict_chunk_rows < 0:
            raise ValueError("predict_chunk_rows must be >= 0 (0 = auto)")
        if self.predict_pallas_tile < 1:
            raise ValueError("predict_pallas_tile must be >= 1")
        if str(self.telemetry).lower() not in ("off", "counters",
                                               "spans", "trace"):
            raise ValueError("telemetry must be off/counters/spans/"
                             f"trace, got {self.telemetry!r}")
        if self.telemetry_retrace_warn < 1:
            raise ValueError("telemetry_retrace_warn must be >= 1")
        if not (0 <= self.telemetry_http_port <= 65535):
            raise ValueError("telemetry_http_port must be in [0, "
                             "65535] (0 = disabled)")
        if self.serve_batch_deadline_ms < 0:
            raise ValueError("serve_batch_deadline_ms must be >= 0")
        if self.serve_shed_deadline_ms <= 0:
            raise ValueError("serve_shed_deadline_ms must be > 0")
        if self.serve_queue_depth < 1:
            raise ValueError("serve_queue_depth must be >= 1")
        if self.serve_max_batch_rows < 1:
            raise ValueError("serve_max_batch_rows must be >= 1")
        if not (0 <= self.serve_port <= 65535):
            raise ValueError("serve_port must be in [0, 65535] "
                             "(0 = ephemeral)")
        _lanes = str(self.serve_lanes).strip().lower()
        if _lanes not in ("auto", ""):
            try:
                _n = int(_lanes)
            except ValueError:
                raise ValueError("serve_lanes must be 'auto' or an "
                                 f"integer >= 1, got "
                                 f"{self.serve_lanes!r}")
            if _n < 1:
                raise ValueError("serve_lanes must be >= 1 when "
                                 f"numeric, got {_n}")
        if str(self.serve_cobatch).lower() not in ("off", "on"):
            raise ValueError("serve_cobatch must be off/on, got "
                             f"{self.serve_cobatch!r}")
        if str(self.quality).lower() not in ("off", "auto", "on"):
            raise ValueError("quality must be off/auto/on, got "
                             f"{self.quality!r}")
        if not (0.0 <= self.quality_sample_rate <= 1.0):
            raise ValueError("quality_sample_rate must be in [0, 1] "
                             "(0 = monitors off)")
        if self.quality_psi_warn <= 0:
            raise ValueError("quality_psi_warn must be > 0")
        if self.quality_drift_refit_threshold < 0:
            raise ValueError("quality_drift_refit_threshold must be "
                             ">= 0 (0 = never report to the lane)")
        if self.quality_profile_rows < 1:
            raise ValueError("quality_profile_rows must be >= 1")
        if self.continuous_cycle_interval_s < 0:
            raise ValueError("continuous_cycle_interval_s must be "
                             ">= 0 (0 = no scheduled cycles)")
        if self.continuous_mode not in ("continue", "refit"):
            raise ValueError("continuous_mode must be continue/refit, "
                             f"got {self.continuous_mode!r}")
        if self.continuous_poll_s <= 0:
            raise ValueError("continuous_poll_s must be > 0")
        if self.continuous_iterations < 1:
            raise ValueError("continuous_iterations must be >= 1")
        if not (0.0 <= self.continuous_eval_holdout < 1.0):
            raise ValueError("continuous_eval_holdout must be in "
                             "[0, 1)")
        if self.continuous_publish_max_regression < 0:
            raise ValueError("continuous_publish_max_regression must "
                             "be >= 0")
        if self.continuous_checkpoint_freq < 0:
            raise ValueError("continuous_checkpoint_freq must be >= 0 "
                             "(0 = cycle-start replay only)")
        if self.continuous_drift_refit_threshold < 0:
            raise ValueError("continuous_drift_refit_threshold must be "
                             ">= 0 (0 = drift warns only)")
        if self.sharded_shards < 0:
            raise ValueError("sharded_shards must be >= 0 "
                             "(0/1 = single-matrix construction)")
        if self.sharded_sample_per_shard < 0:
            raise ValueError("sharded_sample_per_shard must be >= 0 "
                             "(0 = derive from bin_construct_sample_cnt)")
        if self.snapshot_keep < 0:
            raise ValueError("snapshot_keep must be >= 0 (0 = keep all)")
        if self.checkpoint_keep < 1:
            raise ValueError("checkpoint_keep must be >= 1")
        if self.dispatch_retries < 0:
            raise ValueError("dispatch_retries must be >= 0")
        if self.retry_backoff_s < 0:
            raise ValueError("retry_backoff_s must be >= 0")
        for _wd_phase in ("dispatch", "collective", "checkpoint",
                          "serve", "continuous"):
            if getattr(self, f"watchdog_{_wd_phase}_s") < 0:
                raise ValueError(
                    f"watchdog_{_wd_phase}_s must be >= 0 "
                    "(0 = no deadline)")
        if self.fault_plan:
            # parse NOW so a typo'd plan fails the run instead of
            # silently never injecting (a vacuous recovery test)
            from .reliability.faults import parse_plan
            parse_plan(self.fault_plan)
        if self.slo_eval_interval_s <= 0:
            raise ValueError("slo_eval_interval_s must be > 0")
        if self.slo_rules:
            # parse NOW so a typo'd rules file fails the run instead
            # of silently never alerting (the fault_plan contract)
            from .slo import load_rules
            load_rules(self.slo_rules)
        ct = str(self.construct_threads).lower()
        if ct != "auto":
            try:
                f = float(ct)
                if not f.is_integer() or f < 0:
                    raise ValueError
            except ValueError:
                raise ValueError("construct_threads must be 'auto' or a "
                                 "non-negative integer (0 = auto), got "
                                 f"{self.construct_threads!r}") from None
        dc = str(self.dispatch_chunk).lower()
        if dc != "auto":
            try:
                # integral only — truncating "2.9" would silently train
                # with a different chunk than the user pinned (inf/nan
                # fail is_integer, so they land here too)
                f = float(dc)
                if not f.is_integer() or f < 1:
                    raise ValueError
            except ValueError:
                raise ValueError("dispatch_chunk must be 'auto' or a "
                                 f"positive integer, got "
                                 f"{self.dispatch_chunk!r}") from None
        # distributed learners force row pre-partition semantics
        if self.tree_learner != "serial" and self.num_machines == 1 \
                and not self.mesh_shape:
            Log.debug("parallel tree_learner with a single device; "
                      "running serial-equivalent path")

    # ------------------------------------------------------------------
    @property
    def num_tree_per_iteration(self) -> int:
        """Trees per boosting iteration (reference gbdt.cpp: K for softmax)."""
        if self.objective == "multiclass" or self.objective == "multiclassova":
            return self.num_class
        return 1

    @property
    def max_num_levels(self) -> int:
        """Static bound on frontier rounds for the jitted grower."""
        if self.max_depth > 0:
            return self.max_depth
        # leaf-wise frontier: at most num_leaves-1 rounds; balanced trees use
        # ~log2(num_leaves); pathological chains use more.  num_leaves-1 is
        # the hard bound and the while_loop exits early.
        return self.num_leaves - 1

    # ------------------------------------------------------------------
    def update(self, **kwargs) -> "Config":
        return dataclasses.replace(self, **kwargs)

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)

    # ------------------------------------------------------------------
    @classmethod
    def from_params(cls, params: Optional[Dict[str, Any]] = None, **kwargs) -> "Config":
        """Build a Config from a user parameter dict, resolving aliases
        with the reference's conflict rules (config.h:490-529): when an
        alias and its canonical key are both given, the canonical key
        wins; among aliases, the shortest (then lexicographically
        smallest) name wins."""
        params = dict(params or {})
        params.update(kwargs)
        field_names = {f.name for f in dataclasses.fields(cls)}
        canonical: Dict[str, Any] = {}
        alias_src: Dict[str, str] = {}
        # first pass: canonical keys
        for key, value in params.items():
            k = key.lower()
            if k in field_names:
                canonical[k] = value
        # second pass: aliases
        for key, value in params.items():
            k = key.lower()
            if k in field_names:
                continue
            target = PARAM_ALIASES.get(k)
            if target is None or target not in field_names:
                continue
            if target in canonical:
                if target not in alias_src:
                    continue  # canonical key given explicitly: it wins
                prev = alias_src[target]
                if len(prev) < len(k) or (len(prev) == len(k) and prev < k):
                    Log.warning(f"{target} is set by {prev}, ignoring {key}={value}")
                    continue
                Log.warning(f"{target} is set by {key}, overriding {prev}")
            canonical[target] = value
            alias_src[target] = k
        # leftovers
        extra = {}
        for key, value in params.items():
            k = key.lower()
            if k in field_names or PARAM_ALIASES.get(k) in field_names:
                continue
            Log.warning(f"Unknown parameter: {key}")
            extra[key] = str(value)

        coerced = {name: _coerce(cls, name, v) for name, v in canonical.items()}
        if extra:
            coerced["extra"] = extra
        return cls(**coerced)

    @classmethod
    def from_str(cls, text: str) -> "Config":
        """Parse ``key=value`` pairs (CLI string or config-file contents,
        ``#`` comments allowed — reference application.cpp:56-75)."""
        params: Dict[str, str] = {}
        for raw_line in text.replace("\r", "\n").split("\n"):
            for tok in raw_line.split():
                if tok.startswith("#"):
                    break
                if "=" in tok:
                    k, v = tok.split("=", 1)
                    params[k.strip()] = v.strip()
        return cls.from_params(params)


_TRUE = {"true", "1", "yes", "y", "t", "+"}
_FALSE = {"false", "0", "no", "n", "f", "-"}


def _coerce(cls, name: str, value: Any) -> Any:
    """Coerce a raw (often string) param value to the dataclass field type."""
    field = next(f for f in dataclasses.fields(cls) if f.name == name)
    t = field.type
    if isinstance(value, str):
        s = value.strip()
        if t in ("int", int):
            return int(float(s))
        if t in ("float", float):
            return float(s)
        if t in ("bool", bool):
            ls = s.lower()
            if ls in _TRUE:
                return True
            if ls in _FALSE:
                return False
            raise ValueError(f"Cannot parse bool param {name}={value}")
        if "Tuple[int" in str(t):
            return tuple(int(x) for x in s.split(",") if x != "")
        if "Tuple[float" in str(t):
            return tuple(float(x) for x in s.split(",") if x != "")
        if "Tuple[str" in str(t):
            return tuple(x for x in s.split(",") if x != "")
        return s
    if isinstance(value, bool):
        return value
    if t in ("int", int):
        return int(value)
    if t in ("float", float):
        return float(value)
    if t in ("bool", bool):
        return bool(value)
    if isinstance(value, (list, tuple)):
        if "Tuple[int" in str(t):
            return tuple(int(x) for x in value)
        if "Tuple[float" in str(t):
            return tuple(float(x) for x in value)
        return tuple(value)
    return value


def params_to_str(params: Dict[str, Any]) -> str:
    """Serialize a param dict to the key=value wire format
    (reference python-package basic.py:125 param_dict_to_str)."""
    parts = []
    for k, v in params.items():
        if isinstance(v, (list, tuple)):
            v = ",".join(str(x) for x in v)
        elif isinstance(v, bool):
            v = "true" if v else "false"
        parts.append(f"{k}={v}")
    return " ".join(parts)
