"""Booster: the user-facing trained-model handle.

The analog of the reference's C-API Booster + python Booster
(reference: src/c_api.cpp:29-311, python-package/lightgbm/basic.py:1264+)
— owns the boosting object during training and the host-side tree list
for prediction/serialization; model text format is interchangeable with
the reference's (gbdt_model_text.cpp:235-315).
"""
from __future__ import annotations

import functools
import re
from typing import Any, Dict, List, Optional, Union

import numpy as np

from .config import Config, canonical_objective
from .dataset import Dataset
from .tree import Tree
from .utils.log import Log

MODEL_VERSION = "v2"

_ACC_FN = None

# serializes the (trace-counter read, dispatch, compare) window that
# classifies a serving dispatch as bucket hit vs miss — without it a
# concurrent thread's compile lands inside another thread's window and
# a cached-program hit is misattributed as a miss.  Only taken when
# telemetry is on; the enqueue itself is sub-ms so serving threads
# contend only on the dispatch call, never on device execution.
_SERVING_CLASSIFY_LOCK = None


def _serving_lock():
    global _SERVING_CLASSIFY_LOCK
    if _SERVING_CLASSIFY_LOCK is None:
        import threading
        _SERVING_CLASSIFY_LOCK = threading.Lock()
    return _SERVING_CLASSIFY_LOCK


def _acc_fn():
    """Module-level jitted tree-stack accumulator for the device
    predict path: one compilation per (shapes, max_steps), shared by
    every Booster and every predict() call (a per-call closure would
    re-trace each time)."""
    global _ACC_FN
    if _ACC_FN is None:
        import jax
        from .ops.predict import predict_binned

        @functools.partial(jax.jit, static_argnames=("max_steps",
                                                     "packed_groups"))
        def acc(total, stack, shrink_arr, vbins, f_group, g2f_lut,
                f_missing, f_default_bin, f_num_bin, *, max_steps,
                packed_groups=0):
            from .telemetry import TELEMETRY
            TELEMETRY.note_trace("predict.binned_scan",
                                 (vbins.shape, max_steps))

            def body(carry, xs):
                tr, sh = xs
                pv = predict_binned(tr, vbins, f_group, g2f_lut,
                                    f_missing, f_default_bin, f_num_bin,
                                    max_steps=max_steps,
                                    packed_groups=packed_groups)
                return carry + sh * pv, None
            out, _ = jax.lax.scan(body, total, (stack, shrink_arr))
            return out
        _ACC_FN = acc
    return _ACC_FN


_PREDICT_CHUNK_BUDGET_BYTES = 256 << 20  # transient per-chunk device
# footprint bound for predict_chunk_rows=auto (two chunks in flight)


def round_up_bucket(m: int, min_bucket: int) -> int:
    """The serving bucket ladder: smallest power-of-two multiple of
    ``min_bucket`` covering ``m`` rows.  ONE definition shared by the
    predictor's dispatch rounding and the serving micro-batcher's
    fill metric (callers clamp to their own caps)."""
    b = max(1, int(min_bucket))
    while b < m:
        b <<= 1
    return b


class _ServingPredictor:
    """Shape-bucketed, chunk-streamed device predictor over one
    ensemble slice — the serving subsystem's compiled-program unit.

    Batch sizes round UP to power-of-two row buckets (masked tails:
    pad rows are scored and discarded), so micro-batch serving traffic
    compiles once per bucket instead of once per batch size; the
    module-level jit in ops/predict.py shares those compilations
    across every Booster in the process and `compile_cache_dir` across
    processes.  Batches above the chunk cap stream through the device
    in fixed full-bucket chunks with at most two chunks' results in
    flight (double buffering: the next chunk's upload/compute overlaps
    the previous one's D2H), so bulk scoring never densifies the whole
    matrix on device."""

    def __init__(self, models: List[Tree], num_class: int, config):
        import jax.numpy as jnp

        from .ops import predict as P
        from .tree import flatten_ensemble

        flat = flatten_ensemble(models, num_class)
        self.depth = int(flat.pop("depth"))
        self.stack = P.LevelEnsemble(
            **{k: jnp.asarray(v) for k, v in flat.items()})
        self.num_class = max(num_class, 1)
        kernel = str(getattr(config, "predict_kernel", "auto")).lower()
        self.kernel = "level" if kernel in ("auto", "") else kernel
        self.interpret = bool(getattr(config, "force_pallas_interpret",
                                      False))
        tile = max(1, int(getattr(config, "predict_pallas_tile", 512)))
        # power-of-two floor: the grid requires tile | rows, and both
        # buckets and chunk caps are powers of two
        self.tile = 1 << (tile.bit_length() - 1)
        self.bucketed = str(getattr(config, "predict_bucket", "auto")
                            ).lower() not in ("off", "false", "0")
        self.min_bucket = max(1, int(getattr(
            config, "predict_min_bucket_rows", 16)))
        self.chunk_rows = int(getattr(config, "predict_chunk_rows", 0))
        # OOM degradation ladder (docs/RELIABILITY.md): on
        # RESOURCE_EXHAUSTED the dispatch bucket halves and the
        # request retries at the smaller shape instead of failing;
        # the learned cap persists so later requests start degraded
        self.oom_downshift = bool(getattr(config, "oom_downshift",
                                          True))
        self._oom_cap: Optional[int] = None
        self._oom_warned = False

    # ------------------------------------------------------------------
    def _chunk_cap(self, two_f: int) -> int:
        if self.chunk_rows > 0:
            cap = self.chunk_rows
        else:
            t = int(self.stack.root.shape[0])
            # per-row transients: the (N, 2F) hi/lo matrix + the (N, T)
            # node state, (N, 2T) gather indices and (N, T) values
            bytes_per_row = 4 * (two_f + 8 * max(t, 1))
            cap = _PREDICT_CHUNK_BUDGET_BYTES // max(bytes_per_row, 1)
            cap = max(4096, min(1 << 20, cap))
        if self.bucketed:
            # power-of-two cap => every full chunk is ONE bucket shape
            cap = 1 << (max(cap, 1).bit_length() - 1)
        return cap

    def _bucket(self, m: int, cap: int) -> int:
        if not self.bucketed:
            return m
        return min(round_up_bucket(m, self.min_bucket), cap)

    # ------------------------------------------------------------------
    def _dispatch(self, x2_dev):
        from .ops import predict as P
        from .reliability.faults import FAULTS
        FAULTS.fault_point("predict.dispatch")
        if self.kernel == "pallas":
            # halve until the tile divides the batch (immediate for
            # power-of-two buckets; odd bucket-off batches degrade to
            # tile 1 rather than crash the grid)
            tile = self.tile
            while x2_dev.shape[0] % tile:
                tile >>= 1
            return P.predict_level_ensemble_pallas(
                self.stack, x2_dev, depth=self.depth, tile=max(tile, 1),
                interpret=self.interpret)
        return P.predict_level_ensemble(self.stack, x2_dev,
                                        depth=self.depth)

    def _recover_oom(self, e: BaseException, bucket_rows: int, pending,
                     tm, s: int) -> int:
        """Classify a failed dispatch OR a failed drain (on async
        backends a device OOM materializes at the result copy, not the
        enqueue): RESOURCE_EXHAUSTED halves the serving ladder (warn
        once, count the event) and returns the row index to restart
        from; anything else — or OOM at a single-row bucket, where
        there is nothing left to halve — re-raises.

        In-flight results are DISCARDED, not drained: draining a
        poisoned buffer would re-raise the same OOM from inside the
        handler, and dropping the references lets the backend free the
        buffers (the other half of the memory pressure).  Their slices
        rewind into the restart index and are re-dispatched at the
        smaller bucket."""
        from .reliability.retry import is_oom
        if not (self.oom_downshift and is_oom(e)) or bucket_rows <= 1:
            raise
        restart = min((slot[1] for slot in pending), default=s)
        pending.clear()
        self._oom_cap = max(1, bucket_rows // 2)
        tm.add("oom_downshifts", 1)
        tm.journal.emit("oom_downshift", seam="predict.dispatch",
                        bucket=bucket_rows, new_cap=self._oom_cap)
        tm.flight.dump("oom_downshift", seam="predict.dispatch",
                       bucket=bucket_rows, new_cap=self._oom_cap)
        if not self._oom_warned:
            self._oom_warned = True
            Log.warning(
                "RESOURCE_EXHAUSTED during serving dispatch at bucket "
                f"{bucket_rows} ({e}); downshifting to bucket "
                f"{self._oom_cap} and retrying the slice")
        return restart

    def __call__(self, data: np.ndarray) -> np.ndarray:
        """(n, F) float64 raw features -> (n, K) float64 raw scores
        (f32 device accumulation, identical routing to the host walk).

        Telemetry (docs/OBSERVABILITY.md): a ``predict`` span per call
        with a ``predict_dispatch``/``predict_drain`` child per chunk;
        counters count requests, scored vs masked-tail pad rows, and
        bucket hit/miss — a MISS is a dispatch that triggered a new jit
        trace (== an XLA compilation, the ``test_predict_cache`` ground
        truth), everything else is a compiled-program hit.  Latency
        lands in the fixed log-bucket histograms any scraper derives
        p50/p95/p99 from: ``predict_latency_ms`` (whole request),
        ``predict_drain_ms`` (per-chunk result wait — the double-buffer
        "bucket wait") and ``predict_queue_depth`` (chunks in flight at
        each dispatch)."""
        import time

        import jax.numpy as jnp

        from .ops import predict as P
        from .telemetry import TELEMETRY as tm

        data = np.asarray(data, dtype=np.float64)
        n = data.shape[0]
        if n == 0:
            return np.zeros((0, self.num_class))
        t0 = time.perf_counter() if tm.on else 0.0
        span = tm.start_span("predict", rows=n)
        try:
            return self._call_impl(data, n, jnp, P, tm)
        finally:
            # the ladder's re-raise paths (non-OOM errors, OOM at
            # bucket 1) must not leave the request span unrecorded
            tm.end_span(span)
            if tm.on:
                tm.observe("predict_latency_ms",
                           (time.perf_counter() - t0) * 1e3)

    def _call_impl(self, data, n, jnp, P, tm) -> np.ndarray:
        if tm.on:
            tm.add("predict_requests", 1)
        hi, lo = P.split_hi_lo(data)
        x2 = np.empty((n, 2 * data.shape[1]), np.float32)
        x2[:, 0::2] = hi
        x2[:, 1::2] = lo
        cap = self._chunk_cap(x2.shape[1])
        if self._oom_cap is not None:
            cap = max(1, min(cap, self._oom_cap))
        out = np.empty((n, self.num_class), np.float32)
        pending: list = []

        def drain(slot):
            import time
            dev, s, m = slot
            t0 = time.perf_counter() if tm.on else 0.0
            with tm.span("predict_drain"):
                out[s:s + m] = np.asarray(dev)[:m]
            if tm.on:
                # the double-buffer wait: on an async backend this is
                # where the request actually waits on the device
                tm.observe("predict_drain_ms",
                           (time.perf_counter() - t0) * 1e3)

        s = 0
        while s < n or pending:
            if pending and (s >= n or len(pending) >= 2):
                # double buffer: at most TWO chunks' results in flight
                # (what _PREDICT_CHUNK_BUDGET_BYTES sizes against).
                # The drain is inside the ladder too: on an async
                # backend a device OOM materializes HERE, at the
                # result copy, not at the enqueue.
                slot = pending[0]
                try:
                    drain(slot)
                except Exception as e:
                    s = self._recover_oom(e, int(slot[0].shape[0]),
                                          pending, tm, s)
                    cap = max(1, min(cap, self._oom_cap))
                    continue
                pending.pop(0)
                continue
            part = x2[s:s + cap]
            m = part.shape[0]
            b = self._bucket(m, cap)
            if m < b:
                part = np.concatenate(
                    [part, np.zeros((b - m, x2.shape[1]), np.float32)])
            try:
                if tm.on:
                    with _serving_lock():
                        traces0 = P.PREDICT_TELEMETRY["traces"]
                        with tm.span("predict_dispatch",
                                     bucket=int(part.shape[0])):
                            dev = self._dispatch(jnp.asarray(part))
                        miss = P.PREDICT_TELEMETRY["traces"] > traces0
                    tm.add("predict_dispatches", 1)
                    tm.add("predict_rows", m)
                    tm.add("predict_pad_rows", int(part.shape[0]) - m)
                    tm.add("predict_bucket_miss" if miss
                           else "predict_bucket_hit", 1)
                else:
                    dev = self._dispatch(jnp.asarray(part))
            except Exception as e:
                # RESOURCE_EXHAUSTED degradation ladder: halve the
                # dispatch bucket and retry from the earliest
                # un-drained slice at the smaller shape instead of
                # failing the request; the learned cap sticks so
                # later requests start degraded
                s = self._recover_oom(e, int(part.shape[0]), pending,
                                      tm, s)
                cap = max(1, min(cap, self._oom_cap))
                continue
            P.PREDICT_TELEMETRY["dispatches"] += 1
            P.PREDICT_TELEMETRY["rows"] += m
            P.PREDICT_TELEMETRY["buckets"].add(int(part.shape[0]))
            pending.append((dev, s, m))
            if tm.on:
                tm.gauge_max("predict_stream_depth", len(pending))
                from .telemetry import DEPTH_BOUNDS
                tm.observe("predict_queue_depth", len(pending),
                           bounds=DEPTH_BOUNDS)
            s += m
        if tm.on:
            tm.sample_memory()
        return out.astype(np.float64)


class Booster:
    def __init__(self, config: Optional[Config] = None,
                 train_set: Optional[Dataset] = None,
                 model_file: Optional[str] = None,
                 model_str: Optional[str] = None,
                 init_model=None, custom_objective: bool = False):
        self.config = config or Config()
        self.gbdt = None
        # set when host-side tree arrays are mutated after training
        # (refit): the device-resident stacks are then stale and the
        # batched device predict must not serve from them
        self._device_stale = False
        self.best_iteration = -1
        self.models: List[Tree] = []
        self.feature_names: List[str] = []
        self.feature_infos: List[str] = []
        self.num_class = 1
        self.num_tree_per_iteration = 1
        self.max_feature_idx = 0
        self.objective_str = "regression"
        self.average_output = False
        self._train_data_name = "training"
        self._attrs: Dict[str, str] = {}
        self._datasets_freed = False
        # reference QualityProfile attached by engine.train under
        # quality=on; save_model persists it beside the model file
        # (docs/MODEL_MONITORING.md)
        self.quality_profile = None

        if model_file is not None:
            with open(model_file) as f:
                self._load_from_string(f.read())
            return
        if model_str is not None:
            self._load_from_string(model_str)
            return
        if train_set is None:
            return

        # the reference python package accepts a lazy Dataset here
        # (basic.py Booster.__init__ constructs it); engine.train
        # passes an already-constructed core
        if hasattr(train_set, "construct") and \
                callable(train_set.construct):
            train_set = train_set.construct(self.config)

        from .telemetry import TELEMETRY
        with TELEMETRY.stage("import_boosting"):
            # a process's first training Booster imports the training
            # modules here: the grower, the kernels and with them
            # jax.experimental.pallas (over a second; a lookup after)
            from .boosting import create_boosting
        self.gbdt = create_boosting(self.config, train_set,
                                    custom_objective=custom_objective)
        self.average_output = getattr(self.gbdt, "average_output", False)
        self.models = self.gbdt.models      # shared list, grows in place
        self.num_class = self.config.num_class
        self.num_tree_per_iteration = self.config.num_tree_per_iteration
        self.feature_names = train_set.feature_names
        self.feature_infos = train_set.feature_infos()
        self.max_feature_idx = train_set.num_total_features - 1
        self.pandas_categorical = getattr(train_set, "pandas_categorical",
                                          None)
        self.objective_str = self._objective_to_string()
        if init_model is not None:
            base = (Booster(model_file=init_model)
                    if isinstance(init_model, str) else init_model)
            self._continue_from(base, train_set)

    # ------------------------------------------------------------------
    def _objective_to_string(self) -> str:
        o = self.config.objective
        if o == "binary":
            return f"binary sigmoid:{self.config.sigmoid:g}"
        if o in ("multiclass", "multiclassova"):
            s = f"{o} num_class:{self.config.num_class}"
            if o == "multiclassova":
                s += f" sigmoid:{self.config.sigmoid:g}"
            return s
        if o == "regression" and self.config.reg_sqrt:
            return "regression sqrt"
        if o == "lambdarank":
            return "lambdarank"
        return o

    # ------------------------------------------------------------------
    def _continue_from(self, base: "Booster", train_set: Dataset) -> None:
        """Continued training: seed scores with the old model's
        predictions (reference boosting.cpp:44-60 + gbdt.h MergeFrom)."""
        import jax.numpy as jnp
        raw = train_set._raw_data
        if raw is None:
            Log.fatal("Continued training (init_model) requires raw "
                      "data on the Dataset — construct it with "
                      "free_raw_data=False (reference semantics; "
                      "two_round streaming datasets never materialize "
                      "the matrix and cannot continue training)")
        base._sync_models()
        pred = base.predict(raw, raw_score=True)
        pred = pred.reshape(self.num_class, train_set.num_data) \
            if pred.ndim > 1 and self.num_class > 1 else \
            pred.reshape(1, -1) if pred.ndim == 1 else pred.T
        pad = self.gbdt.grower.n_padded - train_set.num_data
        pred = np.pad(pred.astype(np.float32), ((0, 0), (0, pad)))
        self.gbdt.scores = self.gbdt.scores + jnp.asarray(pred)
        for t in base.models:
            self.models.append(t)
            # register foreign trees in the lazy-materialization
            # bookkeeping so flush_models() indexes stay aligned
            self.gbdt._tree_scale.append(1.0)
            self.gbdt._applied_scale.append(1.0)
            self.gbdt._scale_offset += 1
        # note: models list order => merged model predicts old + new trees

    # ------------------------------------------------------------------
    def num_feature(self) -> int:
        """reference c_api LGBM_BoosterGetNumFeature."""
        return self.max_feature_idx + 1

    def feature_name(self) -> List[str]:
        """reference c_api LGBM_BoosterGetFeatureNames."""
        return list(self.feature_names)

    # ------------------------------------------------------------------
    def reset_training_data(self, train_set: Dataset) -> None:
        """Swap the training dataset, keeping the trained model
        (reference c_api.cpp ResetTrainingData): the new data must have
        the same feature count; existing trees' predictions seed the
        new training scores exactly like continued training."""
        from .boosting import create_boosting
        self._sync_models()
        old = None
        if self.models:
            old = Booster()
            old.config = self.config
            for k in ("num_class", "num_tree_per_iteration",
                      "objective_str", "average_output", "feature_names",
                      "feature_infos", "max_feature_idx"):
                setattr(old, k, getattr(self, k))
            old.models = list(self.models)
        nf = train_set.num_total_features if hasattr(
            train_set, "num_total_features") else train_set.num_feature()
        if self.models and nf != self.max_feature_idx + 1:
            Log.fatal("reset_training_data: feature count mismatch "
                      f"({nf} vs model's {self.max_feature_idx + 1})")
        old_iter = self.current_iteration
        self.gbdt = create_boosting(self.config, train_set)
        self.models = self.gbdt.models
        self.feature_names = train_set.feature_names
        self.feature_infos = train_set.feature_infos()
        self.max_feature_idx = nf - 1
        if old is not None and old.models:
            self._continue_from(old, train_set)
            # the reference keeps GetCurrentIteration across
            # ResetTrainingData (the model is retained)
            self.gbdt.iter_ = old_iter
        self._device_stale = False

    # ------------------------------------------------------------------
    def update(self, train_set=None, fobj=None) -> bool:
        if self.gbdt is None or self.gbdt.train_set is None:
            # reference contract: no training session (file-loaded
            # model, or free_dataset() ended it)
            Log.fatal("Cannot update: booster has no training session "
                      "(file-loaded model or datasets were freed)")
        if fobj is not None:
            score = self._current_train_scores()
            grad, hess = fobj(score, self.gbdt.train_set)
            return self.gbdt.train_one_iter(grad, hess)
        return self.gbdt.train_one_iter()

    def rollback_one_iter(self):
        if self.gbdt is None:
            Log.fatal("Cannot rollback: booster has no training "
                      "session (file-loaded model or datasets were "
                      "freed)")
        self.gbdt.rollback_one_iter()
        # a later update() can restore the same tree COUNT with a
        # different tree — a length-keyed stack cache would serve the
        # rolled-back ensemble
        self._raw_stack_cache = None
        self._predictor_cache = None

    def _sync_models(self) -> None:
        """Materialize any device-resident trees into self.models
        (one batched transfer; no-op for file-loaded models)."""
        if self.gbdt is not None:
            self.gbdt.flush_models()

    @property
    def current_iteration(self) -> int:
        return self.gbdt.iter_ if self.gbdt else \
            len(self.models) // max(self.num_tree_per_iteration, 1)

    def num_trees(self) -> int:
        self._sync_models()
        return len(self.models)

    def _current_train_scores(self) -> np.ndarray:
        s = np.asarray(self.gbdt.scores[:, :self.gbdt.num_data])
        if self.num_tree_per_iteration == 1:
            return s[0]
        return s.T.reshape(-1, order="F")  # class-major like reference

    # ------------------------------------------------------------------
    def predict(self, data: np.ndarray, num_iteration: int = -1,
                raw_score: bool = False, pred_leaf: bool = False,
                pred_contrib: bool = False,
                pred_early_stop: bool = False,
                pred_early_stop_freq: int = 10,
                pred_early_stop_margin: float = 10.0,
                device: Optional[bool] = None) -> np.ndarray:
        """Prediction on raw features (reference
        gbdt_prediction.cpp:9-100; SHAP via tree.PredictContrib;
        margin-based early stop prediction_early_stop.cpp:13-80).

        ``device``: None (auto) routes predictions through the
        accelerator when one is attached — large in-session batches
        through the binned scan (input binned with the training
        mappers, device-resident trees evaluated in one scanned
        program, the TPU analog of the reference's OMP batch predict,
        c_api.cpp:200), and everything else — any batch size,
        serving-shaped micro-batches included — through the bucketed
        level-descent serving predictor (_ServingPredictor: batch
        sizes round up to power-of-two buckets so small batches reuse
        one compiled program).  The device paths accumulate in float32
        (the host walk uses float64), so raw scores may differ at
        ~1e-6 relative.  True forces the device path, False forces the
        host walk."""
        from .basic import _is_sparse, _to_matrix
        if _is_sparse(data):
            # CSR prediction without whole-matrix densify (reference
            # c_api.h:574 PredictForCSR walks per-row sparse features;
            # the TPU answer keeps the batched vectorized walk but
            # stages dense chunks).  Wide-sparse matrices first drop to
            # the model's USED feature columns — a model over 10^6
            # columns references only the features it ever split on, so
            # staging is bounded by used width, not matrix width, and
            # chunks stay large.  Absent sparse entries are 0.0 either
            # way, so this is exact.
            csr = data.tocsr()
            width = csr.shape[1]
            compact = self._compact_for_sparse(num_iteration, width) \
                if not pred_contrib else None
            if compact is not None:
                bst, used_cols = compact
                csr = csr[:, used_cols]
                width = used_cols.size
                num_iteration = -1  # models already sliced
            else:
                bst = self
            chunk = max(1, (128 << 20) // max(8 * width, 1))
            parts = [bst.predict(
                np.asarray(csr[i:i + chunk].todense(), dtype=np.float64),
                num_iteration=num_iteration, raw_score=raw_score,
                pred_leaf=pred_leaf, pred_contrib=pred_contrib,
                pred_early_stop=pred_early_stop,
                pred_early_stop_freq=pred_early_stop_freq,
                pred_early_stop_margin=pred_early_stop_margin,
                device=device)
                for i in range(0, csr.shape[0], chunk)]
            return np.concatenate(parts, axis=0)
        # pandas categoricals encode against the TRAIN-time category
        # lists so reordered/unseen predict-time categories map right
        data = _to_matrix(data, getattr(self, "pandas_categorical", None))
        if data.ndim == 1:
            data = data[None, :]
        n = data.shape[0]
        k = max(self.num_tree_per_iteration, 1)

        if not pred_leaf and not pred_contrib and not pred_early_stop:
            if self._can_device_predict(n, num_iteration, device):
                # in-session single-class fast path: binned device scan
                raw = self._device_predict_raw(data, num_iteration)[:, None]
                if not raw_score and not self.average_output:
                    raw = self._convert_output(raw)
                return raw[:, 0]
            if self._can_device_predict_loaded(n, num_iteration, device):
                # every OTHER model kind (file-loaded, multiclass, DART
                # -renormalized, init_model-merged, RF): raw-feature
                # stacked walk (reference c_api.cpp:177-211 batch
                # predict covers all models; so does this)
                raw, used = self._device_predict_loaded(data,
                                                        num_iteration)
                return self._finish_device_scores(raw, used,
                                                  raw_score=raw_score)

        models = self._used_models(num_iteration)

        if pred_leaf:
            out = np.zeros((n, len(models)), dtype=np.int32)
            for i, t in enumerate(models):
                out[:, i] = t.predict_leaf(data)
            return out
        if pred_contrib:
            from .shap import predict_contrib
            return predict_contrib(self, data, models)

        raw = np.zeros((n, k), dtype=np.float64)
        if pred_early_stop and not self.average_output:
            # rows whose margin already exceeds the threshold skip the
            # remaining trees, checked every pred_early_stop_freq trees
            # (reference prediction_early_stop.cpp: binary |score|,
            # multiclass top-2 gap)
            active = np.ones(n, dtype=bool)
            for i, t in enumerate(models):
                if not active.any():
                    break
                raw[active, i % k] += t.predict(data[active])
                if (i + 1) % (pred_early_stop_freq * k) == 0:
                    if k == 1:
                        margin = np.abs(raw[:, 0])
                    else:
                        part = np.partition(raw, k - 2, axis=1)
                        margin = part[:, -1] - part[:, -2]
                    active &= margin < pred_early_stop_margin
        else:
            for i, t in enumerate(models):
                raw[:, i % k] += t.predict(data)
        raw = self._add_init_and_average(raw, len(models))
        if not raw_score and not self.average_output:
            # RF leaf outputs are already in converted space
            raw = self._convert_output(raw)
        return raw[:, 0] if k == 1 else raw

    def _compact_for_sparse(self, num_iteration: int, width: int):
        """Used-feature compaction for wide-sparse prediction: a
        shallow booster clone whose trees index a dense matrix of ONLY
        the split-on features.  Returns (clone, used_column_ids) or
        None when compaction wouldn't pay (narrow input, empty model,
        or most columns used)."""
        import copy
        self._sync_models()
        models = self._used_models(num_iteration)
        feats = [t.split_feature for t in models if t.num_leaves > 1]
        if not feats:
            return None
        used = np.unique(np.concatenate(feats)).astype(np.int64)
        if used.size == 0 or used.size * 2 >= width:
            return None
        remap = np.zeros(width, dtype=np.int32)
        remap[used] = np.arange(used.size, dtype=np.int32)
        bst = copy.copy(self)
        bst.gbdt = None          # raw-feature walk only (host / stacked)
        bst.best_iteration = 0   # models below are already sliced
        bst.models = []
        for t in models:
            ct = copy.copy(t)
            if t.num_leaves > 1:
                ct.split_feature = remap[t.split_feature]
            bst.models.append(ct)
        bst.max_feature_idx = int(used.size) - 1
        bst._raw_stack_cache = None
        bst._predictor_cache = None
        bst._device_stale = False
        return bst, used

    def _resolve_tree_count(self, total: int, num_iteration: int) -> int:
        """Shared num_iteration/best_iteration -> tree-count resolution
        (used by both the host and device predict paths so they can
        never slice different counts)."""
        k = max(self.num_tree_per_iteration, 1)
        if num_iteration is None or num_iteration <= 0:
            if self.best_iteration > 0:
                num_iteration = self.best_iteration
            else:
                return total
        return min(total, num_iteration * k)

    def _n_used_trees(self, num_iteration: int) -> int:
        total = (len(self.gbdt.device_trees) if self.gbdt is not None
                 else len(self.models))
        return self._resolve_tree_count(total, num_iteration)

    def _can_device_predict(self, n: int, num_iteration: int,
                            device: Optional[bool]) -> bool:
        """Batch device predict is valid for single-class in-session
        models with uniform tree scaling (no DART renorm, no foreign
        init_model trees, not RF averaging)."""
        if device is False or self.gbdt is None or self._device_stale:
            return False
        g = self.gbdt
        ok = (self.num_tree_per_iteration == 1
              and not self.average_output
              and g._scale_offset == 0
              and len(g.device_trees) > 0
              and all(s == 1.0 for s in g._tree_scale))
        if not ok:
            return False
        if device is True:
            return True
        from .backend import on_tpu
        n_trees = self._n_used_trees(num_iteration)
        return on_tpu() and n * n_trees >= 2_000_000

    def _device_predict_raw(self, data: np.ndarray,
                            num_iteration: int) -> np.ndarray:
        """Raw scores via the accelerator: bin the input against the
        training mappers, then accumulate a lax.scan of predict_binned
        over the device-resident tree stacks."""
        import jax
        import jax.numpy as jnp

        g = self.gbdt
        gr = g.grower
        cfg = g.config
        vcore = Dataset.from_matrix(np.asarray(data, dtype=np.float64),
                                    config=cfg, reference=g.train_set)
        vbins = jnp.asarray(vcore.group_bins)
        n_trees = self._n_used_trees(num_iteration)
        shrinks = g._tree_shrink[:n_trees]

        acc = _acc_fn()

        def acc_jit(total, part, sh):
            return acc(total, part, sh, vbins, gr.f_group, gr.g2f_lut,
                       gr.f_missing, gr.f_default_bin, gr.f_num_bin,
                       max_steps=cfg.num_leaves,
                       packed_groups=gr.pack_P)
        # iter-0 trained in session => the boost_from_average bias is
        # NOT folded into the device trees (flush folds it host-side)
        total = jnp.full(vbins.shape[0], np.float32(g.init_score))
        i = 0
        entries = g.device_trees[:n_trees]
        while i < len(entries):
            e = entries[i]
            if isinstance(e, tuple) and e and e[0] in ("stackref",
                                                       "recref"):
                stack = e[1]
                j0 = e[2]
                j1 = j0
                while (i + (j1 - j0) + 1 < len(entries)
                       and isinstance(entries[i + (j1 - j0) + 1], tuple)
                       and entries[i + (j1 - j0) + 1][0] == e[0]
                       and entries[i + (j1 - j0) + 1][1] is stack
                       and entries[i + (j1 - j0) + 1][2] == j1 + 1
                       and entries[i + (j1 - j0) + 1][3:] == e[3:]):
                    j1 += 1
                count = j1 - j0 + 1
                if e[0] == "recref":
                    # packed-carry chunk: unpack the record rows on
                    # device (static slices + bitcasts, no gathers)
                    from .ops.predict import unpack_tree_records_device
                    part = unpack_tree_records_device(
                        stack[j0:j0 + count, e[3]], cfg.num_leaves,
                        gr.max_feature_bin)
                else:
                    part = jax.tree_util.tree_map(
                        lambda x: x[j0:j0 + count], stack)
                sh = jnp.asarray(np.asarray(
                    shrinks[i:i + count], np.float32))
                total = acc_jit(total, part, sh)
                i += count
            else:
                part = jax.tree_util.tree_map(lambda x: x[None], e)
                sh = jnp.asarray(np.asarray(shrinks[i:i + 1], np.float32))
                total = acc_jit(total, part, sh)
                i += 1
        return np.asarray(total)

    def _can_device_predict_loaded(self, n: int, num_iteration: int,
                                   device: Optional[bool]) -> bool:
        """Raw-feature stacked device predict: valid for any model with
        host trees (loaded, multiclass, DART, init_model, RF)."""
        if device is False:
            return False
        total = len(self.models) or (
            len(self.gbdt.device_trees) if self.gbdt is not None else 0)
        if total == 0:
            return False
        if device is True:
            return True
        from .backend import on_tpu
        if not on_tpu():
            return False
        if self._predict_impl() != "scan" \
                and str(getattr(self.config, "predict_bucket", "auto")
                        ).lower() not in ("off", "false", "0"):
            # bucketed serving predictor: small batches reuse the
            # bucket's compiled program, so serving-shaped traffic
            # routes to the accelerator at ANY batch size (the old
            # n*trees floor existed to amortize per-shape compiles)
            return True
        n_trees = self._resolve_tree_count(total, num_iteration)
        return n * n_trees >= 2_000_000

    def _predict_impl(self) -> str:
        k = str(getattr(self.config, "predict_kernel", "auto")).lower()
        return "level" if k in ("auto", "") else k

    @staticmethod
    def _predict_device():
        """The CURRENT default device (thread-local: the serving lane
        pool pins each lane's worker via ``jax.default_device``), or
        None outside any pinning context.  Part of the serving
        predictor cache key so each lane device gets its own resident
        ensemble stack."""
        import jax
        return jax.config.jax_default_device

    def _serving_predictor(self, count: int) -> _ServingPredictor:
        """Per-(model revision, tree count, pinned device) serving
        predictor cache — the ensemble stack uploads once per lane
        device; compiled programs are shared process-wide by the
        module-level jit underneath."""
        cache = getattr(self, "_predictor_cache", None)
        if cache is None or cache[0] != len(self.models):
            cache = (len(self.models), {})
            self._predictor_cache = cache
        by_key = cache[1]
        key = (count, self._predict_device())
        if key not in by_key:
            by_key[key] = _ServingPredictor(
                self.models[:count],
                max(self.num_tree_per_iteration, 1), self.config)
        return by_key[key]

    def warm_predictor(self, batch_sizes=(1,),
                       num_iteration: int = -1,
                       log: bool = False,
                       devices=None) -> "Booster":
        """Serving warm-up: compile the bucketed device predictor for
        the given batch sizes at deploy time instead of on the first
        request (with compile_cache_dir wired this is a disk hit in
        later processes).  Drives the serving predictor DIRECTLY —
        predict() routing would send an in-session booster's call
        through the binned scan instead, warming the wrong programs.
        Wired to `predict_warm_buckets` in engine.train(); the CLI
        predict/serve tasks pass ``log=True`` so deploy scripts see
        the per-bucket warm compile wall before taking traffic.

        ``devices`` (an iterable of jax devices, or None entries for
        the unpinned default) warms every listed device's buckets —
        the lane-pool fix: warming only the default device would
        leave lanes 2..N eating a cold compile on their first
        request.  None keeps the single default-device warm."""
        import contextlib
        import time
        self._sync_models()
        if not self.models:
            return self
        count = self._resolve_tree_count(len(self.models), num_iteration)
        if count == 0 or self._predict_impl() == "scan":
            return self
        f = self.max_feature_idx + 1
        devs = tuple(devices) if devices else (None,)
        for dev in devs:
            if dev is not None:
                import jax
                ctx = jax.default_device(dev)
            else:
                ctx = contextlib.nullcontext()
            with ctx:
                # fetched INSIDE the device context: the per-device
                # cache key pins this lane's resident stack
                pred = self._serving_predictor(count)
                for b in batch_sizes:
                    m = max(int(b), 1)
                    t0 = time.perf_counter()
                    pred(np.zeros((m, f)))
                    if log:
                        bucket = pred._bucket(m, pred._chunk_cap(2 * f))
                        Log.info(
                            f"warm_predictor: batch {m} -> bucket "
                            f"{bucket}"
                            + (f" on {dev}" if dev is not None else "")
                            + " warmed in "
                            f"{(time.perf_counter() - t0) * 1e3:.1f} ms")
        return self

    def _device_predict_loaded(self, data: np.ndarray,
                               num_iteration: int):
        """Raw scores via the ensemble-vectorized level descent (or the
        legacy per-tree stacked walk when predict_kernel=scan).
        Returns ((n, k) float64 raw scores, used tree count).
        Accumulation is float32 (documented device-predict precision);
        decisions match the host walk exactly via the two-float
        threshold compare.  num_iteration resolves through the SAME
        _resolve_tree_count as the host path, so both paths always
        slice identical tree counts."""
        self._sync_models()
        count = self._resolve_tree_count(len(self.models), num_iteration)
        k = max(self.num_tree_per_iteration, 1)
        if count == 0:
            return np.zeros((data.shape[0], k)), 0
        if self._predict_impl() == "scan":
            return self._device_predict_scan(data, count, k), count
        return self._serving_predictor(count)(data), count

    def _device_predict_scan(self, data: np.ndarray, count: int,
                             k: int) -> np.ndarray:
        """Legacy per-tree lax.scan walk (predict_kernel=scan A/B)."""
        import jax
        import jax.numpy as jnp

        from .ops.predict import (predict_raw_ensemble, split_hi_lo,
                                  stack_host_trees)

        cache = getattr(self, "_raw_stack_cache", None)
        if cache is None or cache[0] != len(self.models):
            cache = (len(self.models), stack_host_trees(self.models))
            self._raw_stack_cache = cache
        stack = cache[1]
        if count < len(self.models):
            stack = jax.tree_util.tree_map(lambda x: x[:count], stack)
        cls = jnp.arange(count, dtype=jnp.int32) % k
        Xhi, Xlo = split_hi_lo(data)
        out = predict_raw_ensemble(
            stack, jnp.asarray(Xhi), jnp.asarray(Xlo), cls,
            jnp.zeros((k, data.shape[0]), jnp.float32))
        return np.asarray(out).T.astype(np.float64)

    def _used_models(self, num_iteration: int) -> List[Tree]:
        self._sync_models()
        return self.models[:self._resolve_tree_count(len(self.models),
                                                     num_iteration)]

    def _finish_device_scores(self, raw: np.ndarray, used: int,
                              raw_score: bool = False) -> np.ndarray:
        """Host-side finish of a device raw-score block: RF
        averaging, objective conversion, single-class squeeze — the
        ONE post-dispatch pipeline shared by ``predict()``'s
        level-descent route and the serving co-batcher's per-model
        segment finish, so a fused dispatch's slice goes through
        byte-identical postprocessing to a direct predict."""
        k = max(self.num_tree_per_iteration, 1)
        raw = self._add_init_and_average(raw, used)
        if not raw_score and not self.average_output:
            raw = self._convert_output(raw)
        return raw[:, 0] if k == 1 else raw

    def _add_init_and_average(self, raw, num_models):
        if self.average_output and num_models:
            raw = raw / (num_models // max(self.num_tree_per_iteration, 1))
        return raw

    def _convert_output(self, raw: np.ndarray) -> np.ndarray:
        obj = self.objective_str.split()[0] if self.objective_str else ""
        obj = canonical_objective(obj)
        if obj == "binary":
            m = re.search(r"sigmoid:([0-9.eE+-]+)", self.objective_str)
            sig = float(m.group(1)) if m else 1.0
            return 1.0 / (1.0 + np.exp(-sig * raw))
        if obj == "multiclass":
            e = np.exp(raw - raw.max(axis=1, keepdims=True))
            return e / e.sum(axis=1, keepdims=True)
        if obj == "multiclassova":
            m = re.search(r"sigmoid:([0-9.eE+-]+)", self.objective_str)
            sig = float(m.group(1)) if m else 1.0
            return 1.0 / (1.0 + np.exp(-sig * raw))
        if obj in ("poisson", "gamma", "tweedie"):
            return np.exp(raw)
        if obj == "regression" and "sqrt" in self.objective_str:
            return np.sign(raw) * raw * raw
        if obj == "cross_entropy":
            return 1.0 / (1.0 + np.exp(-raw))
        if obj == "cross_entropy_lambda":
            return np.log1p(np.exp(raw))
        return raw

    # ------------------------------------------------------------------
    def _n_train_eval_rows(self) -> int:
        """gbdt emits training metric rows FIRST; datasets are told
        apart by position, never by name (a valid set may be literally
        named 'training')."""
        if self.gbdt is None:
            return 0
        return sum(len(m.names()) for m in self.gbdt.train_metrics)

    def eval(self) -> List:
        out = self.gbdt.eval_metrics() if self.gbdt else []
        if self._train_data_name != "training":
            k = self._n_train_eval_rows()
            out = [(self._train_data_name, m, v, b) if i < k
                   else (d, m, v, b)
                   for i, (d, m, v, b) in enumerate(out)]
        return out

    def eval_train(self) -> List:
        """reference basic.py Booster.eval_train: training-set metric
        rows only (valid-set metrics are not computed)."""
        if self.gbdt is None:
            if self._datasets_freed:
                Log.fatal("Booster datasets were freed (free_dataset) "
                          "— cannot evaluate training metrics")
            return []
        if not self.gbdt.train_metrics:
            self.gbdt.add_train_metrics()
        out = self.gbdt.eval_metrics("train")
        return [(self._train_data_name, m, v, b)
                for (_d, m, v, b) in out]

    def eval_valid(self) -> List:
        """reference basic.py Booster.eval_valid: validation rows only
        (training metrics are not computed)."""
        return self.gbdt.eval_metrics("valid") if self.gbdt else []

    def add_valid(self, data, name: str) -> "Booster":
        """reference basic.py Booster.add_valid.  Unconstructed lazy
        datasets are bin-aligned to the training mappers automatically
        (the reference package calls set_reference in train(); a valid
        set binned with its OWN mappers would evaluate trees whose
        thresholds live in train bin space — silently wrong)."""
        if self.gbdt is None:
            Log.fatal("Cannot add validation data to a booster without "
                      "a training session (file-loaded model)")
        if hasattr(data, "construct_aligned"):
            core = data.construct_aligned(self.gbdt.train_set,
                                          self.config)
        elif hasattr(data, "construct"):
            core = data.construct(self.config)
        else:
            core = data
        self.gbdt.add_valid(core, name)
        return self

    def set_train_data_name(self, name: str) -> "Booster":
        """reference basic.py Booster.set_train_data_name: the label
        eval() reports for the training rows."""
        self._train_data_name = name
        return self

    def reset_parameter(self, params: Dict[str, Any]) -> "Booster":
        """reference basic.py Booster.reset_parameter — learning_rate
        plus plain config scalars (the surface
        LGBM_BoosterResetParameter forwards here)."""
        if "learning_rate" in params and self.gbdt is not None:
            self.gbdt.shrinkage_rate = float(params["learning_rate"])
        for k, v in params.items():
            if k != "learning_rate" and hasattr(self.config, k):
                cur = getattr(self.config, k)
                try:
                    if isinstance(cur, bool):
                        # bool('false') is True — parse string forms
                        setattr(self.config, k, str(v).lower()
                                in ("1", "true", "yes", "on"))
                    else:
                        setattr(self.config, k, type(cur)(v))
                except (TypeError, ValueError):
                    pass
        return self

    def get_leaf_output(self, tree_id: int, leaf_id: int) -> float:
        """reference basic.py Booster.get_leaf_output."""
        self._sync_models()
        return float(self.models[int(tree_id)].leaf_value[int(leaf_id)])

    def attr(self, key: str) -> Optional[str]:
        """reference basic.py Booster.attr: free-form string
        attributes (python-side, like the reference)."""
        return self._attrs.get(key)

    def set_attr(self, **kwargs) -> "Booster":
        """reference basic.py Booster.set_attr: value None deletes."""
        for k, v in kwargs.items():
            if v is None:
                self._attrs.pop(k, None)
            else:
                self._attrs[k] = str(v)
        return self

    def free_dataset(self) -> "Booster":
        """reference basic.py Booster.free_dataset: ACTUALLY release
        the training/validation state — the grower holds the binned
        device matrix and padded score arrays (GBs at HIGGS scale), so
        dropping only the dataset handle would free almost nothing.
        Models are flushed to host first; prediction still works
        (host walk / raw-feature stacked device path); further
        update() calls error."""
        if self.gbdt is not None:
            self._sync_models()
            self.best_iteration = max(self.best_iteration,
                                      self.gbdt.best_iteration)
            self.gbdt = None
            self._device_stale = True
            self._datasets_freed = True
        return self

    def free_network(self) -> "Booster":
        """reference basic.py Booster.free_network (socket rendezvous
        has no TPU analog — see LGBM_NetworkFree)."""
        return self

    def set_network(self, machines, local_listen_port: int = 12400,
                    listen_time_out: int = 120,
                    num_machines: int = 1) -> "Booster":
        """reference basic.py Booster.set_network: accepted for call
        compatibility; multi-host setup goes through
        jax.distributed.initialize + mesh_shape (warns like
        LGBM_NetworkInit)."""
        from .capi import LGBM_NetworkInit
        LGBM_NetworkInit(machines if isinstance(machines, str)
                         else ",".join(machines), local_listen_port,
                         listen_time_out, num_machines)
        return self

    # ------------------------------------------------------------------
    def save_model(self, filename: str, num_iteration: int = -1) -> None:
        text = self.model_to_string(num_iteration)
        with open(filename, "w") as f:
            f.write(text)
        prof = getattr(self, "quality_profile", None)
        if prof is not None:
            from .quality import model_fingerprint, profile_path
            if model_fingerprint(text) == prof.fingerprint:
                # the profile is bound to the FULL model it was built
                # from — persist it beside the file so a later
                # task=serve can arm drift monitors from disk
                path = prof.save(profile_path(filename))
                Log.info(f"quality profile saved to {path}")
            else:
                # e.g. a num_iteration-sliced save: the written text
                # is not the profiled model — writing the sidecar
                # would trip the fingerprint refusal at serve time
                Log.debug("quality profile not saved beside "
                          f"{filename}: the written model text does "
                          "not match the profiled model (sliced "
                          "save?)")

    def model_to_string(self, num_iteration: int = -1) -> str:
        """reference gbdt_model_text.cpp:235-315 SaveModelToString."""
        models = self._used_models(num_iteration)
        out = ["tree", f"version={MODEL_VERSION}",
               f"num_class={self.num_class}",
               f"num_tree_per_iteration={self.num_tree_per_iteration}",
               "label_index=0",
               f"max_feature_idx={self.max_feature_idx}",
               f"objective={self.objective_str}"]
        if self.average_output:
            out.append("average_output")
        out.append("feature_names=" + " ".join(self.feature_names))
        out.append("feature_infos=" + " ".join(self.feature_infos))
        tree_strs = []
        for i, t in enumerate(models):
            tree_strs.append(f"Tree={i}\n{t.to_string()}\n")
        out.append("tree_sizes=" + " ".join(str(len(s)) for s in tree_strs))
        out.append("")
        text = "\n".join(out) + "\n" + "".join(tree_strs)
        # feature importances footer
        imp = self.feature_importance("split", num_iteration)
        pairs = [(int(v), self.feature_names[i]) for i, v in enumerate(imp)
                 if v > 0]
        pairs.sort(key=lambda p: -p[0])
        text += "\nfeature importances:\n"
        for v, name in pairs:
            text += f"{name}={v}\n"
        if getattr(self, "pandas_categorical", None):
            # trailing mapping line, like the reference python package
            import json as _json
            text += "\npandas_categorical:%s\n" % _json.dumps(
                self.pandas_categorical, default=str)
        return text

    # ------------------------------------------------------------------
    def _load_from_string(self, text: str) -> None:
        """reference gbdt_model_text.cpp:317+ LoadModelFromString."""
        self.pandas_categorical = None
        for line in reversed(text.rstrip().splitlines()[-3:]):
            if line.startswith("pandas_categorical:"):
                import json as _json
                try:
                    self.pandas_categorical = _json.loads(
                        line[len("pandas_categorical:"):])
                except ValueError:
                    pass
                text = text[:text.rfind("pandas_categorical:")]
                break
        header, _, rest = text.partition("Tree=0")
        kv = {}
        for line in header.splitlines():
            if "=" in line:
                k, v = line.split("=", 1)
                kv[k.strip()] = v.strip()
        self.num_class = int(kv.get("num_class", "1"))
        self.num_tree_per_iteration = int(
            kv.get("num_tree_per_iteration", "1"))
        self.max_feature_idx = int(kv.get("max_feature_idx", "0"))
        self.objective_str = kv.get("objective", "regression")
        self.feature_names = kv.get("feature_names", "").split()
        self.feature_infos = kv.get("feature_infos", "").split()
        self.average_output = "average_output" in header.splitlines()
        self.models = []
        if not rest:
            return
        blocks = re.split(r"Tree=\d+\n", "Tree=0" + rest)
        for block in blocks:
            block = block.strip()
            if not block or block.startswith("feature importances"):
                continue
            block = block.split("\nfeature importances")[0]
            if "num_leaves" not in block:
                continue
            self.models.append(Tree.from_string(block))

    # ------------------------------------------------------------------
    def dump_model(self, num_iteration: int = -1) -> Dict[str, Any]:
        """JSON model dump (reference gbdt_model_text.cpp:20-180
        DumpModel / Tree::ToJSON)."""
        models = self._used_models(num_iteration)

        def node_json(tree: Tree, node: int):
            if node < 0:
                leaf = -node - 1
                return {"leaf_index": leaf,
                        "leaf_value": float(tree.leaf_value[leaf]),
                        "leaf_count": int(tree.leaf_count[leaf])}
            dt = int(tree.decision_type[node])
            is_cat = bool(dt & 1)
            mtype = {0: "None", 1: "Zero", 2: "NaN"}[(dt >> 2) & 3]
            out = {
                "split_index": int(node),
                "split_feature": int(tree.split_feature[node]),
                "split_gain": float(tree.split_gain[node]),
                "threshold": float(tree.threshold[node]),
                "decision_type": "==" if is_cat else "<=",
                "default_left": bool(dt & 2),
                "missing_type": mtype,
                "internal_value": float(tree.internal_value[node]),
                "internal_count": int(tree.internal_count[node]),
                "left_child": node_json(tree, int(tree.left_child[node])),
                "right_child": node_json(tree, int(tree.right_child[node])),
            }
            if is_cat:
                ci = int(tree.threshold[node])
                lo, hi = tree.cat_boundaries[ci], tree.cat_boundaries[ci + 1]
                out["cat_threshold"] = list(tree.cat_threshold[lo:hi])
            return out

        return {
            "name": "tree",
            "version": MODEL_VERSION,
            "num_class": self.num_class,
            "num_tree_per_iteration": self.num_tree_per_iteration,
            "label_index": 0,
            "max_feature_idx": self.max_feature_idx,
            "objective": self.objective_str,
            "average_output": self.average_output,
            "feature_names": list(self.feature_names),
            "tree_info": [
                {"tree_index": i, "num_leaves": t.num_leaves,
                 "num_cat": t.num_cat, "shrinkage": t.shrinkage,
                 "tree_structure": node_json(
                     t, 0 if t.num_leaves > 1 else -1)}
                for i, t in enumerate(models)],
        }

    # ------------------------------------------------------------------
    def refit(self, data: np.ndarray, label: np.ndarray,
              params: Optional[Dict[str, Any]] = None) -> "Booster":
        """Refit leaf values on new data keeping the tree structures
        (reference gbdt.cpp:338-360 RefitTree + c_api refit task).
        Telemetry: wrapped in a ``refit`` span, with every leaf whose
        value was recomputed counted in ``refit_leaves_updated`` —
        the continuous lane's refit cycles are sized by it."""
        from .telemetry import TELEMETRY
        span = TELEMETRY.start_span("refit",
                                    rows=int(np.shape(data)[0]))
        try:
            return self._refit_impl(data, label, params)
        finally:
            TELEMETRY.end_span(span)

    def _refit_impl(self, data, label, params) -> "Booster":
        from .config import Config
        from .dataset import Metadata
        from .objectives import create_objective
        from .ops.split import calculate_leaf_output
        from .telemetry import TELEMETRY

        import jax.numpy as jnp  # noqa: F401  (objectives use jnp)

        params = dict(params or {})
        params.setdefault("objective", self.objective_str.split()[0])
        if self.num_tree_per_iteration > 1:
            params.setdefault("num_class", self.num_tree_per_iteration)
        config = Config.from_params(params)
        from .basic import _is_sparse
        if not _is_sparse(data):
            # sparse stays sparse — refit only reads the data through
            # predict(pred_leaf=True), which densifies in bounded chunks
            data = np.ascontiguousarray(np.asarray(data, dtype=np.float64))
        n = data.shape[0]
        objective = create_objective(config)
        meta = Metadata(n)
        meta.set_label(label)
        objective.init(meta, n)

        self._sync_models()
        k = max(self.num_tree_per_iteration, 1)
        leaf_preds = self.predict(data, pred_leaf=True)  # (n, ntrees)
        scores = np.zeros((n, k), dtype=np.float64)
        leaves_updated = 0
        for i, tree in enumerate(self.models):
            cls = i % k
            s = scores if k > 1 else scores[:, 0]
            g, h = objective.get_gradients(np.asarray(s, dtype=np.float32))
            g = np.asarray(g)
            h = np.asarray(h)
            if k > 1:
                g, h = g[:, cls], h[:, cls]
            lp = leaf_preds[:, i]
            shrink = tree.shrinkage if tree.shrinkage != 0 else 1.0
            for leaf in range(tree.num_leaves):
                mask = lp == leaf
                if not mask.any():
                    continue
                sg, sh = float(g[mask].sum()), float(h[mask].sum())
                out = float(calculate_leaf_output(
                    np.float64(sg), np.float64(sh), config.lambda_l1,
                    config.lambda_l2, config.max_delta_step))
                tree.leaf_value[leaf] = out * shrink
                tree.leaf_count[leaf] = int(mask.sum())
                leaves_updated += 1
            scores[:, cls] += tree.leaf_value[lp]
        if TELEMETRY.on:
            TELEMETRY.add("refit_leaves_updated", leaves_updated)
        # host trees diverged from the in-session device stacks;
        # invalidate every device path's cache (the serving/raw-stack
        # predictors rebuild from the refitted host trees on next use
        # — refit mutates leaf values IN PLACE, so the length-keyed
        # caches would otherwise serve stale ensembles)
        self._device_stale = True
        self._raw_stack_cache = None
        self._predictor_cache = None
        return self

    # ------------------------------------------------------------------
    def feature_importance(self, importance_type: str = "split",
                           num_iteration: int = -1) -> np.ndarray:
        """reference gbdt.h FeatureImportance."""
        models = self._used_models(num_iteration)
        n = self.max_feature_idx + 1
        imp = np.zeros(n, dtype=np.float64)
        for t in models:
            m = t.num_leaves - 1
            for i in range(m):
                f = t.split_feature[i]
                if importance_type == "split":
                    imp[f] += 1
                else:
                    imp[f] += max(t.split_gain[i], 0.0)
        return imp

    # ------------------------------------------------------------------
    def __getstate__(self):
        state = {"model_str": self.model_to_string(),
                 "best_iteration": self.best_iteration}
        return state

    def __setstate__(self, state):
        self.__init__(model_str=state["model_str"])
        self.best_iteration = state.get("best_iteration", -1)
