"""GBDT: the boosting orchestrator.

TPU-native re-design of the reference GBDT
(reference: src/boosting/gbdt.{h,cpp}; TrainOneIter hot path
gbdt.cpp:386-481, bagging :234-316, boost_from_average :362-384,
early stopping :582-639, score updating :528-580).  Scores, gradients
and the binned matrix live on device for the whole run; one boosting
iteration is ONE jitted call (gradients -> bagging mask -> tree growth
-> score update -> validation-score update) with no host sync.  Host
work per iteration is O(1) dispatch only; finished trees stay on device
and are pulled to host models in a single batched transfer when the
model is actually needed (flush_models) — a host pull waits for the
device queue to drain, so the loop never blocks on one.
"""
from __future__ import annotations

import time
from contextlib import contextmanager, nullcontext
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..backend import on_tpu
from ..config import Config
from ..dataset import Dataset
from ..learner.grower import TreeGrower, TreeArrays
from ..metrics import Metric, create_metrics
from ..objectives import Objective, create_objective
from ..ops.histogram import ROUTE_ROWS, leaf_value_broadcast
from ..ops.predict import predict_binned
from ..reliability.checkpoint import CheckpointError
from ..reliability.faults import FAULTS
from ..reliability.retry import RetryPolicy, retry_call
from ..telemetry import TELEMETRY
from ..tree import Tree
from ..utils.log import Log


def fit_chunk_slope(times: Dict[int, float]) -> Tuple[float, float]:
    """Least-squares fit of the per-iteration chunk cost model
    ``per_tree(c) = base + slope * c`` from {chunk_len: per_tree_s}
    probe timings (the ROOFLINE round-6 fit: 25.75 + 0.075·c ms on
    v5e with the legacy 18-buffer carry).  Returns (base_s, slope_s)."""
    cs = np.asarray(sorted(times), dtype=np.float64)
    ts = np.asarray([times[int(c)] for c in cs], dtype=np.float64)
    slope, base = np.polyfit(cs, ts, 1)
    return float(base), float(slope)


def pick_dispatch_chunk(base_s: float, slope_s: float, dispatch_s: float,
                        cmin: int = 10, cmax: int = 90) -> int:
    """Amortization point of ``per_tree(c) = base + slope·c +
    dispatch/c``: c* = sqrt(dispatch / slope), clamped to [cmin, cmax].
    A non-positive slope (the packed carry's target state) means longer
    chunks are free — take cmax and amortize the dispatch cost fully."""
    del base_s                     # the additive base doesn't move c*
    if slope_s <= 0.0:
        return cmax
    c = (max(dispatch_s, 0.0) / slope_s) ** 0.5
    return int(min(max(round(c), cmin), cmax))


class _ValidSet:
    """Per-validation-set device state (the ScoreUpdater analog,
    reference score_updater.hpp:17-120)."""

    def __init__(self, dataset: Dataset, num_class: int, init_score: float,
                 metrics: List[Metric]):
        self.dataset = dataset
        self.num_data = dataset.num_data
        self.bins = jax.device_put(dataset.group_bins)
        self.scores = jnp.full((num_class, dataset.num_data), 0.0,
                               dtype=jnp.float32)
        if dataset.metadata.init_score is not None:
            init = dataset.metadata.init_score.astype(np.float32)
            self.scores = jnp.asarray(
                init.reshape(num_class, dataset.num_data))
        if init_score != 0.0:
            self.scores = self.scores + init_score
        self.metrics = metrics


class GBDT:
    """Gradient Boosting Decision Tree trainer."""

    def __init__(self, config: Config, train_set: Dataset,
                 objective: Optional[Objective] = None,
                 custom_objective: bool = False):
        self.config = config
        self.train_set = train_set
        self.num_data = train_set.num_data
        self.objective = (None if custom_objective else
                          (objective if objective is not None
                           else create_objective(config)))
        self.num_class = config.num_tree_per_iteration
        self.shrinkage_rate = config.learning_rate

        # per-row metadata goes to the device in the "upload" set-up
        # stage, with the grower's bin matrix (docs/OBSERVABILITY.md)
        if self.objective is not None:
            with TELEMETRY.stage("upload"):
                self.objective.init(train_set.metadata, self.num_data)

        self.grower = TreeGrower(train_set, config)
        self.grower.quantized_root_totals = bool(getattr(
            self.objective, "quantized_root_totals", False))
        # multi-host (finalize_global): device metadata arrays must
        # follow the assembled per-host-padded row layout, sharded
        self._mh = self.grower._mh_local is not None
        if self._mh and self.objective is not None:
            if self.objective.is_renew_tree_output:
                Log.fatal(
                    "multi-host training does not support "
                    "RenewTreeOutput objectives (l1/huber/quantile/"
                    f"mape) yet — got {self.objective.name}; the "
                    "percentile refit needs a global sort across hosts")
            self.objective.repad_device_arrays(
                lambda a: self.grower.policy.place_rows(
                    self.grower.pad_rows(a)))
        elif self.objective is not None \
                and self.grower.plan.mesh_kernels:
            # one host's mesh on the kernel path: the objective's
            # per-row arrays live on the mesh, beside the rows they
            # belong to (an array left on one device is sent to every
            # other at each dispatch; at 2^26 rows that is 256 MB a
            # chunk)
            pol = self.grower.policy
            self.objective.repad_device_arrays(
                pol.place_rows if self.num_data % pol.num_shards == 0
                else pol.replicate)
        self.models: List[Tree] = []
        self.device_trees: List[TreeArrays] = []   # kept for DART drops
        self.iter_ = 0
        self.train_metrics: List[Metric] = []
        self.valid_sets: List[_ValidSet] = []
        self.valid_names: List[str] = []

        # boost_from_average (reference gbdt.cpp:362-384)
        self.init_score = 0.0
        has_init = train_set.metadata.init_score is not None
        if (self.objective is not None and config.boost_from_average
                and not has_init and self.num_class == 1):
            self.init_score = float(self.objective.boost_from_score())
            if abs(self.init_score) > 1e-15:
                Log.info(f"Start training from score {self.init_score:f}")

        base = np.zeros((self.num_class, self.num_data), dtype=np.float32)
        if has_init:
            base += train_set.metadata.init_score.reshape(
                self.num_class, self.num_data).astype(np.float32)
        base += self.init_score
        with TELEMETRY.stage("upload"):
            padded = np.stack([self.grower.pad_rows(base[c])
                               for c in range(self.num_class)])
            self.scores = self.grower.policy.place_score_rows(padded)

        self._rng = np.random.RandomState(config.seed)
        self._bag_rng = jax.random.PRNGKey(config.bagging_seed)
        self._iter_key_rng = np.random.RandomState(config.bagging_seed)
        self._feat_rng = np.random.RandomState(config.feature_fraction_seed)
        self._grad_fn = jax.jit(self._compute_gradients)
        self._update_train_fn = jax.jit(self._update_train_scores)
        self._predict_valid_fn = jax.jit(self._predict_valid)
        self._eval_cache: Dict[Tuple[int, int], List[float]] = {}
        # lazily-materialized host models: finished device trees queue in
        # _pending as (TreeArrays, shrinkage, bias) and are pulled in one
        # batched transfer by flush_models()
        self._pending: List[Tuple[TreeArrays, float, float]] = []
        self._scale_offset = 0   # foreign (init_model) trees precede ours
        self._tree_scale: List[float] = []    # DART renorm per model idx
        self._tree_shrink: List[float] = []   # shrinkage at train time
        # (feeds the batched device predict; reset_parameter may vary it)
        self._applied_scale: List[float] = []  # scale baked into models[i]
        self._nl_window: List[jax.Array] = []  # deferred 1-leaf stop checks
        # (entries are () or (n,) device arrays — kept stacked so a
        # chunk never pays per-iteration slice dispatches)
        self._nl_count = 0
        # deferred no-split stop detection: each check is a device->host
        # pull that drains the dispatch queue (its cost: not measured
        # on the chip) — amortize it far beyond the reference's every-
        # iteration check; 1-leaf trees contribute exactly zero score,
        # so the late rollback is exact (see _check_stop_window)
        self._stop_check_every = 64
        # threefry PRNGKey(seed) layout is [hi, lo] uint32 — verified
        # once so chunk key batches can be built host-side in numpy
        # (instead of n PRNGKey dispatches per chunk)
        self._np_keys_ok = bool(np.array_equal(
            np.asarray(jax.random.PRNGKey(7)),
            np.array([0, 7], np.uint32)))
        self._fused_step = None
        self._fused_chunk = None
        self._fused_chunk_n = 0
        # the per-leaf histogram cache the chunk program grows its trees
        # in, kept from chunk to chunk (grower.new_hist_pool)
        self._hist_pool = None
        # packed tree carry (round 7): the fused chunk stacks each
        # tree as ONE byte-packed record (tree.TreeRecordLayout) so
        # the scan carries 2 output buffers instead of 18 — the
        # round-6 diagnosis traced the per-iteration chunk penalty to
        # the backend's handling of the 18 O(chunk) stacked outputs.
        # "off" restores the legacy per-field carry (parity-pinned).
        self._packed_carry = str(getattr(config, "packed_tree_carry",
                                         "auto")).lower() \
            not in ("off", "false", "0")
        self._bag_state: Optional[jax.Array] = None
        # early stopping state per (dataset, metric-output)
        self._best_score: Dict[Tuple[int, int], float] = {}
        self._best_iter: Dict[Tuple[int, int], int] = {}
        self.best_iteration = -1

        # row weights as count channel (bagging multiplies into this)
        w = train_set.metadata.weight
        with TELEMETRY.stage("upload"):
            self._full_counts = self.grower.policy.place_rows(
                self.grower.pad_rows(np.ones(self.num_data,
                                             dtype=np.float32)))
            self._weights_dev = (None if w is None else
                                 self.grower.policy.place_rows(
                                     self.grower.pad_rows(
                                         w.astype(np.float32))))
            TELEMETRY.stage_fence((self.scores, self._full_counts))
        self._bag_mask: Optional[jax.Array] = None

        # EVERY O(N) device array must cross the jit boundary as an
        # ARGUMENT, never as a closure: closures are inlined as MLIR
        # constants, which (a) makes XLA compile time linear in rows
        # (~80 s per million measured — a HIGGS-scale compile took
        # 25+ min) and (b) is impossible for multi-host sharded arrays
        # (tracing fetches values spanning non-addressable devices).
        # The captives pytree is built per call and bound to the usual
        # attributes for the dynamic extent of the trace (the grower's
        # _ohb_arg pattern).

    def _build_captives(self):
        obj_caps = {}
        if self.objective is not None:
            obj_caps = {k: v for k, v in self.objective.__dict__.items()
                        if k.endswith("_dev")
                        and isinstance(v, jax.Array)}
        return {
            "bins": self.grower.bins,
            "binsT": self.grower.binsT,
            "rv": self.grower._row_valid,
            "fc": self._full_counts,
            "w": self._weights_dev,
            "obj": obj_caps,
            "vbins": tuple(vs.bins for vs in self.valid_sets),
        }

    @contextmanager
    def _bound_captives(self, cap):
        if cap is None:
            yield
            return
        g, obj = self.grower, self.objective
        saved = (g.bins, g.binsT, g._row_valid, self._full_counts,
                 self._weights_dev,
                 {k: obj.__dict__[k] for k in cap["obj"]}
                 if obj is not None else {})
        g.bins, g.binsT = cap["bins"], cap["binsT"]
        g._row_valid = cap["rv"]
        self._full_counts, self._weights_dev = cap["fc"], cap["w"]
        if obj is not None:
            obj.__dict__.update(cap["obj"])
        try:
            yield
        finally:
            (g.bins, g.binsT, g._row_valid, self._full_counts,
             self._weights_dev) = saved[:5]
            if obj is not None:
                obj.__dict__.update(saved[5])

    # ------------------------------------------------------------------
    def add_valid(self, valid_set: Dataset, name: str) -> None:
        if self._mh:
            Log.fatal("multi-host training does not support validation "
                      "sets yet (metric scores live sharded across "
                      "hosts) — evaluate after training instead")
        # bin-alignment gate: validation trees are walked in TRAIN bin
        # space, so the valid set's mappers must be the training
        # mappers (feature_infos encodes the bin bounds — equal infos
        # means numerically identical binning).  The reference's
        # c_api/python package reject unaligned validation data too.
        if self.train_set is not None and \
                valid_set is not self.train_set and \
                valid_set.feature_infos() != self.train_set.feature_infos():
            Log.fatal(f"validation set {name!r} is not bin-aligned to "
                      "the training data — create it with "
                      "reference=<train dataset> (its own bin mappers "
                      "differ from the training mappers)")
        if self.train_set is not None and valid_set is not self.train_set:
            # storage-layout gate: equal feature_infos no longer imply
            # an equal matrix layout — the same data constructed under
            # a different bin_packing packs (and group-reorders)
            # differently, and _predict_valid walks the valid matrix
            # with the TRAINING set's packed_groups
            def _lay(ds):
                lay = getattr(ds, "bin_layout", None)
                return lay.to_state() if lay is not None else None
            if _lay(valid_set) != _lay(self.train_set):
                Log.fatal(
                    f"validation set {name!r} has a different bin-"
                    f"matrix storage layout ({_lay(valid_set)}) than "
                    f"the training data ({_lay(self.train_set)}) — "
                    "construct it with reference=<train dataset> or "
                    "the same bin_packing setting")
        metrics = create_metrics(self.config)
        for m in metrics:
            m.init(valid_set.metadata, valid_set.num_data)
        self.valid_sets.append(
            _ValidSet(valid_set, self.num_class, self.init_score, metrics))
        self.valid_names.append(name)

    def add_train_metrics(self) -> None:
        self.train_metrics = create_metrics(self.config)
        for m in self.train_metrics:
            m.init(self.train_set.metadata, self.num_data)

    # ------------------------------------------------------------------
    def _compute_gradients(self, scores):
        """scores: (K, n_padded) -> (K, n_padded) grad/hess, zero-padded."""
        if self._mh:
            # multi-host layout: per-host padding blocks are interleaved
            # — the objective's device arrays were re-padded to match,
            # so gradients run full-width (padded rows produce values
            # that never count: their leaf_id is -1)
            s = scores
        else:
            s = scores[:, :self.num_data]
        if self.num_class == 1:
            g, h = self.objective.get_gradients(s[0])
            g, h = g[None, :], h[None, :]
        else:
            g, h = self.objective.get_gradients(s.T)
            g, h = g.T, h.T
        pad = scores.shape[1] - s.shape[1]
        if pad:
            g = jnp.pad(g, ((0, 0), (0, pad)))
            h = jnp.pad(h, ((0, 0), (0, pad)))
        return g, h

    # ------------------------------------------------------------------
    def _bagging_counts(self, iteration: int):
        """Per-iteration bagging mask (reference gbdt.cpp:234-316 with
        mask-based rows instead of index subsets)."""
        cfg = self.config
        if cfg.bagging_freq <= 0 or cfg.bagging_fraction >= 1.0:
            return self._full_counts, None
        if iteration % cfg.bagging_freq == 0 or self._bag_mask is None:
            self._bag_rng, sub = jax.random.split(self._bag_rng)
            u = jax.random.uniform(sub, (self.grower.n_padded,))
            self._bag_mask = (u < cfg.bagging_fraction) & \
                (self._full_counts > 0)
        counts = jnp.where(self._bag_mask, 1.0, 0.0)
        return counts, self._bag_mask

    # ------------------------------------------------------------------
    def _feature_mask_np(self) -> np.ndarray:
        """Per-tree feature sampling (reference
        serial_tree_learner.cpp:252-345 BeforeTrain); host-side."""
        f = self.config.feature_fraction
        F = self.grower.num_features
        if f >= 1.0:
            return np.ones(F, dtype=bool)
        used = max(1, int(round(F * f)))
        idx = self._feat_rng.choice(F, size=used, replace=False)
        mask = np.zeros(F, dtype=bool)
        mask[idx] = True
        return mask

    def _feature_mask(self) -> jax.Array:
        return jnp.asarray(self._feature_mask_np())

    # ------------------------------------------------------------------
    def _update_train_scores(self, scores, leaf_id, leaf_value, class_idx,
                             shrinkage):
        with TELEMETRY.phase("score_update"):
            delta = leaf_value_broadcast(leaf_id, leaf_value) * shrinkage
            return scores.at[class_idx].add(delta)

    def _predict_valid(self, tree: TreeArrays, bins):
        # train and reference-aligned validation matrices share ONE
        # storage layout (dataset alignment copies bin_layout), so the
        # grower's packed_groups applies to both
        g = self.grower
        return predict_binned(tree, bins, g.f_group, g.g2f_lut, g.f_missing,
                              g.f_default_bin, g.f_num_bin,
                              max_steps=self.config.num_leaves,
                              packed_groups=g.pack_P)

    # ------------------------------------------------------------------
    # hooks for DART/GOSS/RF subclasses --------------------------------
    def _before_boosting(self) -> None:
        """Called before gradient computation (DART drops trees here)."""

    def _after_iteration(self) -> None:
        """Called after the iteration's trees are in (DART normalizes)."""

    def _sample_rows(self, g, h, counts):
        """Row-sampling hook for the custom-gradient path; GOSS
        reweights gradients here."""
        return g, h, counts

    def _sample_rows_fused(self, g, h, counts, key):
        """Jit-traceable row-sampling hook (GOSS overrides)."""
        return g, h, counts

    def _sample_active(self) -> bool:
        """Whether _sample_rows_fused does anything this iteration
        (static per compile — GOSS flips it once)."""
        return False

    # ------------------------------------------------------------------
    def _use_bagging_fused(self) -> bool:
        """Whether the fused step draws a bagging mask (GOSS replaces
        bagging entirely — reference goss.hpp Bagging override)."""
        cfg = self.config
        return cfg.bagging_freq > 0 and cfg.bagging_fraction < 1.0

    # ------------------------------------------------------------------
    def _feature_masks(self) -> jax.Array:
        """(K, F) per-tree feature sampling masks for one iteration."""
        if self.config.feature_fraction >= 1.0:
            if not hasattr(self, "_full_feature_masks"):
                self._full_feature_masks = jnp.ones(
                    (self.num_class, self.grower.num_features), bool)
            return self._full_feature_masks
        return jnp.asarray(np.stack(
            [self._feature_mask_np() for _ in range(self.num_class)]))

    # ------------------------------------------------------------------
    def _build_fused(self):
        """One boosting iteration as a single jitted program: gradients,
        bagging draw, K tree growths, train-score and valid-score
        updates.  The only per-iteration host traffic left is the async
        dispatch itself."""
        vbins = tuple(vs.bins for vs in self.valid_sets)

        def step(scores, vscores, bag_mask, key, fmask, shrinkage,
                 ohb=None, cap=None, fresh_bag=False,
                 sample_active=False):
            # sample_active is a static cache key mirroring
            # self._sample_active(), which _boost_one reads at trace time
            del sample_active
            # trace-time only (retrace sentinel + compile counter):
            # runs once per compilation, never on the dispatch path
            TELEMETRY.note_trace("gbdt.fused_step",
                                 (scores.shape, len(vscores)))
            vb = vbins if cap is None else cap["vbins"]
            with self._bound_captives(cap):
                return self._boost_one(scores, vscores, bag_mask, key,
                                       fmask, shrinkage, fresh_bag,
                                       vb, ohb)[:5]

        # no donation here either: the same heap corruption bisected on
        # the fused chunk (see _build_fused_chunk) reproduces on this
        # per-iteration program once several booster shapes jit it in
        # one process — the C-API suite's flaky SIGABRT/SIGSEGV inside
        # jax eager dispatch traced to exactly this path (r7)
        self._fused_step = jax.jit(
            step, static_argnames=("fresh_bag", "sample_active"))

    # ------------------------------------------------------------------
    def _host_qkey(self, class_idx: int):
        """Per-(iteration, class) stochastic-rounding key for the
        HOST-DRIVEN tree paths (RF, custom gradients) — the fused
        chunk derives its own inside _boost_one."""
        if not self._quant_stochastic():
            return None
        import jax as _jax
        seed = int(self._iter_key_rng.randint(0, 2**31 - 1))
        return _jax.random.fold_in(_jax.random.PRNGKey(seed), class_idx)

    def _quant_stochastic(self) -> bool:
        """Whether the int8 quantization rounds stochastically (the v4
        recipe; REQUIRED by skewed-gradient objectives like lambdarank
        — see ops/histogram.py quantize_gradients).  Auto mode defers
        to the objective's need_stochastic_quant."""
        if not self.grower.plan.quantized:
            return False
        mode = int(getattr(self.config, "quant_stochastic_rounding",
                           -1))
        if mode >= 0:
            return bool(mode)
        return (self.objective is not None
                and getattr(self.objective, "need_stochastic_quant",
                            False))

    def can_chunk(self) -> bool:
        """Whether multi-iteration fused chunks are valid: plain GBDT
        gradients only.  DART/RF mutate state between iterations on the
        host; GOSS flips its sampling activation mid-run, which a
        compiled chunk would freeze at build time."""
        return type(self).__name__ == "GBDT"

    def _boost_one(self, scores, vscores, bag_mask, key, fmask,
                   shrinkage, fresh_bag, vbins, ohb=None, hist_pool=None):
        """One boosting iteration's device body — shared by the
        per-iteration fused step and the multi-iteration chunk
        (``fresh_bag`` may be a python bool or a traced scalar).
        ``hist_pool`` (grower.new_hist_pool) is the histogram cache the
        iteration's trees grow in, one after the other; it is returned
        last, as the last tree left it.
        Every op here lies under a ``tel.<phase>`` scope (the grower
        scopes its own), so a device trace splits by phase
        (docs/OBSERVABILITY.md, device phases)."""
        cfg = self.config
        use_bag = self._use_bagging_fused()
        n_pad = self.grower.n_padded
        with TELEMETRY.phase("gradients"):
            g, h = self._compute_gradients(scores)
        with TELEMETRY.phase("sampling"):
            kb, ks = jax.random.split(key)
            if use_bag:
                u = jax.random.uniform(kb, (n_pad,))
                new_mask = (u < cfg.bagging_fraction) \
                    & (self._full_counts > 0)
                bag_mask = jnp.where(fresh_bag, new_mask, bag_mask)
                counts = jnp.where(bag_mask, 1.0, 0.0)
            else:
                counts = self._full_counts
            if self._sample_active():
                g, h, counts = self._sample_rows_fused(g, h, counts, ks)
            g, h = self._mask_gradients(g, h, counts)
        trees = []
        nl = jnp.int32(1)
        new_vscores = list(vscores)
        # stochastic-rounding key for the int8 quantization (folded off
        # the iteration key so the bagging/GOSS streams are untouched)
        with TELEMETRY.phase("quantize"):
            kq = (jax.random.fold_in(key, 0x51AB)
                  if self._quant_stochastic() else None)
        for k in range(self.num_class):
            with TELEMETRY.phase("gradients"):
                g_k, h_k, fmask_k = g[k], h[k], fmask[k]
            with TELEMETRY.phase("quantize"):
                qkey = None if kq is None else jax.random.fold_in(kq, k)
            tree, leaf_id, row_val, hist_pool = \
                self.grower._train_tree_impl(
                    g_k, h_k, counts, fmask_k, ohb, qkey=qkey,
                    hist_pool=hist_pool)
            with TELEMETRY.phase("finalize_tree"):
                tree = self._finalize_tree(tree, leaf_id, k, scores,
                                           counts)
                # a no-split tree must contribute nothing (the
                # reference skips UpdateScore when num_leaves==1,
                # gbdt.cpp:427-460)
                ok = (tree.num_leaves > 1).astype(jnp.float32)
                tree = tree._replace(leaf_value=tree.leaf_value * ok)
            renew = (self.objective is not None
                     and self.objective.is_renew_tree_output)
            with TELEMETRY.phase("score_update"):
                if row_val is not None and not renew:
                    # fused path: the exit-route already carried each
                    # row's leaf value — skip the separate (N, L)
                    # broadcast
                    delta = row_val * ok * shrinkage
                else:
                    delta = leaf_value_broadcast(
                        leaf_id, tree.leaf_value) * shrinkage
                scores = scores.at[k].add(delta)
                for i, vb in enumerate(vbins):
                    pv = self._predict_valid(tree, vb)
                    new_vscores[i] = new_vscores[i].at[k].add(
                        pv * shrinkage)
                nl = jnp.maximum(nl, tree.num_leaves)
            trees.append(tree)
        return (scores, tuple(new_vscores), bag_mask, tuple(trees), nl,
                hist_pool)

    def _build_fused_chunk(self, n_iters: int):
        """n_iters boosting iterations as ONE jitted lax.scan — one
        dispatch per chunk instead of one per iteration, so headless
        stretches of training run chunked (the host dispatch share on
        a directly attached chip: not measured on the chip).  The
        reference has no analog: its Train loop is host-driven per
        iteration (gbdt.cpp:318-336).

        Packed carry (default): each iteration's K trees leave the
        scan as ONE (K, record_size) uint8 stack (grower.emit_tree_
        record), so the while-loop carry holds two O(chunk) output
        buffers — the packed records and the num_leaves series — and
        the per-iteration chunk penalty the 18-buffer carry paid
        disappears (tests/test_carry_hlo.py pins this in compiled
        HLO)."""
        vbins = tuple(vs.bins for vs in self.valid_sets)
        shrinkage = self.shrinkage_rate
        packed = self._packed_carry

        def chunk(scores, vscores, bag_mask, keys, fmasks, fresh_flags,
                  ohb=None, cap=None, hist_pool=None):
            TELEMETRY.note_trace("gbdt.fused_chunk",
                                 (keys.shape[0], scores.shape))
            vb = vbins if cap is None else cap["vbins"]

            def one_iter(carry, xs):
                scores, vscores, bag_mask, pool = carry
                key, fmask, fresh_bag = xs
                scores, vscores, bag_mask, trees, nl, pool = \
                    self._boost_one(scores, vscores, bag_mask, key, fmask,
                                    shrinkage, fresh_bag, vb, ohb, pool)
                if packed:
                    with TELEMETRY.phase("tree_record"):
                        trees = jnp.stack([self.grower.emit_tree_record(t)
                                           for t in trees])
                return (scores, vscores, bag_mask, pool), (trees, nl)

            with self._bound_captives(cap):
                (scores, vscores, bag_mask, hist_pool), (trees, nls) = \
                    jax.lax.scan(one_iter,
                                 (scores, vscores, bag_mask, hist_pool),
                                 (keys, fmasks, fresh_flags))
            return scores, vscores, bag_mask, trees, nls, hist_pool

        # score donation is DISABLED on the fused chunk: donating the
        # scores buffer into the chunk program intermittently corrupted
        # the host heap on the CPU backend of the jaxlib in use at r7
        # (August 2026, before the move to jaxlib 0.9.0; glibc
        # "corrupted double-linked list" / SIGSEGV mid-run, ~50% of
        # 90-iteration runs once more than one chunk shape is compiled
        # — bisected across {packed, legacy} x {donate, no-donate}:
        # every crashing combination donated, every non-donating one
        # was stable over 20+ runs).  The cost is one scores-sized
        # device copy per CHUNK.  Not re-tested on jaxlib 0.9.0 or on
        # the TPU backend; ROADMAP Speed 6 owns restoring it.  The
        # per-iteration _fused_step donation fell to the same bisect:
        # the C-API suite's long-flaky mid-suite SIGABRT/SIGSEGV (many
        # booster shapes jitted per process) stopped reproducing (0/8)
        # once its donation was dropped too.
        # The histogram pool IS donated, on the chip: the job's one
        # per-leaf cache (1.5 GB at 2,000 groups) goes in and comes
        # back in the same buffer.  On the CPU backend it is copied,
        # for the reason above.
        return jax.jit(chunk, donate_argnames=(
            ("hist_pool",) if on_tpu() else ()))

    def _take_hist_pool(self):
        """The histogram pool for the next chunk, out of this object's
        hands: the chunk program donates it, so a dispatch that fails
        leaves none behind and the next one starts a new pool."""
        pool, self._hist_pool = self._hist_pool, None
        if pool is None:
            # stage: the job's one per-leaf cache, zeros on the device
            # (1.5 GB at 2,000 groups), made by the first dispatch
            with TELEMETRY.stage("hist_pool"):
                pool = self.grower.new_hist_pool()
        return pool

    def train_chunk(self, n_iters: int) -> bool:
        """Run n_iters boosting iterations in one device program.
        Returns True when the deferred no-split check stopped training."""
        tm = TELEMETRY
        # host cost is timed from METHOD ENTRY: the per-chunk python
        # prep (key/fmask/flag assembly, pending bookkeeping) is host
        # wall too, and the pre-r9 bench timed the whole call — the
        # counter must cover the same window for series continuity
        t0 = time.perf_counter() if tm.on else 0.0
        span = tm.start_span("train_chunk", first_iter=self.iter_,
                             iters=n_iters)
        built = False
        with tm.span("chunk_prep"):
            cfg = self.config
            chunk_key = (n_iters, len(self.valid_sets), self.shrinkage_rate,
                         self._sample_active())
            if self._fused_chunk_n != chunk_key:
                self._fused_chunk = self._build_fused_chunk(n_iters)
                self._fused_chunk_n = chunk_key
                built = True
            use_bag = self._use_bagging_fused()
            if self._bag_state is None:
                self._bag_state = self._full_counts > 0
            # the per-iteration seed and feature-mask draws below consume
            # host RNG state BEFORE the dispatch can fail — snapshot the
            # streams so a failed dispatch restores them and a retry or
            # engine-level chunk downshift re-draws the IDENTICAL
            # sequence (the byte-identity guarantee under failure,
            # docs/RELIABILITY.md)
            _rng_snap = (self._iter_key_rng.get_state(),
                         self._feat_rng.get_state())
            seeds = np.asarray([self._iter_key_rng.randint(0, 2**31 - 1)
                                for _ in range(n_iters)], np.uint32)
            if self._np_keys_ok and not use_bag \
                    and not self._sample_active() \
                    and not self._quant_stochastic():
                # keys unused by the chunk body (no bagging draw, no GOSS
                # sampling, no stochastic quantization rounding): reuse a
                # cached device array and skip the per-chunk host->device
                # transfer entirely
                cache = getattr(self, "_chunk_keys", None)
                if cache is None or cache.shape[0] != n_iters:
                    cache = jnp.zeros((n_iters, 2), jnp.uint32)
                    self._chunk_keys = cache
                keys = cache
            elif self._np_keys_ok:
                # handed to the jitted chunk as numpy: its call path
                # transfers it with no Python of its own, where
                # ``jnp.asarray`` makes ~150 interpreter calls a chunk —
                # 60% of a dispatch's, and a profiler trace's reduction
                # scans every host event for every device gap (PERF.md
                # §6 PR 38: the traced four-chip run)
                keys = np.stack(
                    [np.zeros(n_iters, np.uint32), seeds], axis=1)
            else:  # pragma: no cover - unexpected key layout
                keys = jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])
            if self.config.feature_fraction >= 1.0:
                cache = getattr(self, "_chunk_fmasks", None)
                if cache is None or cache.shape[0] != n_iters:
                    cache = jnp.ones(
                        (n_iters, self.num_class, self.grower.num_features),
                        bool)
                    self._chunk_fmasks = cache
                fmasks = cache
            else:
                fmasks = jnp.asarray(np.stack(
                    [np.stack([self._feature_mask_np()
                               for _ in range(self.num_class)])
                     for _ in range(n_iters)]))
            if use_bag:
                fresh = np.zeros(n_iters, bool)
                for j in range(n_iters):
                    fresh[j] = (self.iter_ + j) % cfg.bagging_freq == 0
            else:
                # all-False flags never change: cache the device constant
                cache = getattr(self, "_chunk_fresh", None)
                if cache is None or cache.shape[0] != n_iters:
                    cache = jnp.zeros(n_iters, bool)
                    self._chunk_fresh = cache
                fresh = cache

        def _enqueue():
            # fault seam BEFORE the dispatch: an injected failure (or
            # SIGKILL) leaves training state as if the chunk was never
            # dispatched, so a retry — or a checkpoint resume — is
            # exact.  Transient-classified errors (connection/timeout/
            # UNAVAILABLE RPC statuses) retry under the config policy;
            # anything else (OOM included) propagates to the caller's
            # degradation ladder.
            FAULTS.fault_point("gbdt.train_chunk")
            return self._fused_chunk(
                self.scores, tuple(vs.scores for vs in self.valid_sets),
                self._bag_state, keys, fmasks,
                fresh if isinstance(fresh, jax.Array)
                else jnp.asarray(fresh),
                self.grower.ohb, self._build_captives(),
                self._take_hist_pool())

        try:
            # a dispatch that builds its chunk program is a set-up
            # stage, counted like chunk_program_build_ms from method
            # entry: jax's trace / lower / compile of the program are
            # the stages chunk_trace / chunk_lower / chunk_compile, its
            # own time the prep above and the enqueue.  Every other
            # dispatch opens nothing here.
            with tm.stage("chunk_build", compiles="chunk", since=t0) \
                    if built else nullcontext(), tm.span("host_dispatch"):
                scores, vscores, bag, trees, nls, self._hist_pool = \
                    retry_call(
                        self._dispatch_guard(_enqueue, "gbdt.train_chunk"),
                        policy=self._retry_policy(),
                        seam="gbdt.train_chunk")
            if tm.on:
                # the r7 bench split, now first-class counters: time-
                # to-return is the host/dispatch cost (the async
                # enqueue); the optional fence attributes the
                # remainder to device execution
                host_ms = (time.perf_counter() - t0) * 1e3
                tm.add("host_dispatch_ms", host_ms)
                if built:
                    # this dispatch traced, lowered and compiled (or
                    # loaded from the cache) a chunk program: set-up
                    # on the first chunk, a stall if it is a later one
                    tm.add("chunk_program_build_ms", host_ms)
                tm.fence_ready(scores)
                tm.add("trees_dispatched", n_iters * self.num_class)
                tm.add("iterations", n_iters)
                tm.add("chunks_dispatched", 1)
                tm.gauge("dispatch_chunk_size", n_iters)
                tm.sample_memory(device=tm.spans_on)
        except BaseException:
            # one guard covers the enqueue AND the telemetry fence
            # (an async device OOM materializes at the fence, still
            # before any state commits): restore the RNG streams so a
            # retry or downshifted re-dispatch draws the IDENTICAL
            # seed/feature-mask sequence
            self._iter_key_rng.set_state(_rng_snap[0])
            self._feat_rng.set_state(_rng_snap[1])
            tm.end_span(span)
            raise
        if tm.on and self.grower.policy.nproc > 1:
            # per-host step wall -> fleet max/min/mean + straggler
            # ratio via a tiny allgather (all hosts run this SPMD
            # loop in lockstep, so the collective is safe here)
            from ..parallel.monitor import record_step_wall
            record_step_wall(time.perf_counter() - t0)
        commit = tm.start_span("chunk_commit")
        self.scores = scores
        for vs, s in zip(self.valid_sets, vscores):
            vs.scores = s
        self._bag_state = bag
        bias0 = self.init_score if (self.iter_ == 0 and
                                    self.init_score != 0.0) else 0.0
        # trees stay STACKED on device until flush_models — slicing per
        # tree here would cost hundreds of tiny dispatches, defeating
        # the point of chunking.  Packed carry: ONE (n_iters, K,
        # record_size) uint8 stack; legacy: one TreeArrays stack per
        # class.
        if self._packed_carry:
            self._pending.append(("rstack", trees, n_iters,
                                  self.shrinkage_rate, bias0))
            for j in range(n_iters):
                for k in range(self.num_class):
                    self.device_trees.append(("recref", trees, j, k))
                    self._tree_scale.append(1.0)
                    self._tree_shrink.append(self.shrinkage_rate)
        else:
            stacks = list(trees)                  # one stack per class
            self._pending.append(("stack", stacks, n_iters,
                                  self.shrinkage_rate, bias0))
            for j in range(n_iters):
                for stack in stacks:
                    self.device_trees.append(("stackref", stack, j))
                    self._tree_scale.append(1.0)
                    self._tree_shrink.append(self.shrinkage_rate)
        self._nl_window.append(nls)          # stays stacked on device
        self._nl_count += n_iters
        self.iter_ += n_iters
        self._transport_epoch_tick()
        tm.end_span(commit)
        tm.end_span(span)
        if self._nl_count >= self._stop_check_every:
            return self._check_stop_window()
        return False

    def tune_dispatch_chunk(self, probes: Tuple[int, int] = (4, 16),
                            cmin: int = 10, cmax: int = 90):
        """``dispatch_chunk=auto``: re-fit the per-iteration chunk
        slope from two timed probe chunks and pick the amortization
        point.  Each probe size runs TWICE — the first call compiles
        (discarded), the second is timed; probe chunks are real
        training iterations, not throwaway work.  The host dispatch
        cost is the time train_chunk takes to RETURN (the async
        enqueue); the slope is fitted on the REMAINDER (return-to-drain,
        the device execution) — folding the dispatch into the fitted
        times would subtract dispatch/(c1·c2) from the slope and bias
        the pick toward cmax exactly where dispatch is large.

        Returns (chunk, info) where info records the fit
        (base_s/slope_s/dispatch_s/per-probe timings), the training
        iterations consumed, and whether the deferred no-split check
        stopped training mid-probe."""
        import time as _time

        times: Dict[int, float] = {}
        disp = []
        iters_used = 0
        stopped = False
        # the probe measures the RAW async enqueue (time-to-return) —
        # a telemetry device fence inside train_chunk would fold the
        # device wall into it and poison the slope fit
        span = TELEMETRY.start_span("tune_dispatch_chunk")
        with TELEMETRY.suspend_fence():
            for c in probes:
                for timed in (False, True):
                    t0 = _time.perf_counter()
                    stop = self.train_chunk(c)
                    t_return = _time.perf_counter() - t0
                    jax.block_until_ready(self.scores)
                    t_total = _time.perf_counter() - t0
                    iters_used += c
                    if timed:
                        times[c] = (t_total - t_return) / c
                        disp.append(t_return)
                    if stop:
                        stopped = True
                        break
                if stopped:
                    break
        TELEMETRY.end_span(span)
        if stopped or len(times) < 2:
            return cmin, {"iters_used": iters_used, "stopped": stopped,
                          "probe_per_tree_s": times}
        base_s, slope_s = fit_chunk_slope(times)
        dispatch_s = float(np.median(disp))
        chunk = pick_dispatch_chunk(base_s, slope_s, dispatch_s,
                                    cmin=cmin, cmax=cmax)
        info = {"iters_used": iters_used, "stopped": False,
                "probe_per_tree_s": times, "base_s": base_s,
                "slope_s_per_iter": slope_s, "dispatch_s": dispatch_s,
                "chunk": chunk}
        for c, per_tree_s in times.items():
            TELEMETRY.gauge(f"dispatch_probe_ms_per_tree_{c}",
                            per_tree_s * 1e3)
        TELEMETRY.gauge("dispatch_probe_return_ms", dispatch_s * 1e3)
        TELEMETRY.gauge("dispatch_chunk_slope_ms", slope_s * 1e3)
        TELEMETRY.gauge("dispatch_chunk_base_ms", base_s * 1e3)
        Log.debug(f"dispatch_chunk=auto fit: base {base_s * 1e3:.2f} ms "
                  f"+ {slope_s * 1e3:.4f} ms/iter·chunk, dispatch "
                  f"{dispatch_s * 1e3:.1f} ms -> chunk {chunk}")
        return chunk, info

    def train_one_iter(self, grad: Optional[np.ndarray] = None,
                       hess: Optional[np.ndarray] = None) -> bool:
        """One boosting iteration (reference gbdt.cpp:386-481).
        Custom grad/hess (shape (N,) or (N, K)) bypass the objective —
        the LGBM_BoosterUpdateOneIterCustom path."""
        if grad is not None and hess is not None:
            return self._train_one_iter_custom(grad, hess)
        if self.objective is None:
            Log.fatal("No objective and no custom gradients")
        tm = TELEMETRY
        t0 = time.perf_counter() if tm.on else 0.0  # host wall from
        # method entry (same window discipline as train_chunk)
        self._before_boosting()
        if self._fused_step is None:
            self._build_fused()
        cfg = self.config
        use_bag = self._use_bagging_fused()
        fresh_bag = bool(use_bag and (self._bag_state is None or
                                      self.iter_ % cfg.bagging_freq == 0))
        if self._bag_state is None:
            self._bag_state = self._full_counts > 0
        # RNG snapshot: the key/feature-mask draws precede the
        # dispatch; a failed dispatch restores the streams so a retry
        # trains the identical iteration (the masks are drawn ONCE,
        # outside the retried closure, for the same reason)
        _rng_snap = (self._iter_key_rng.get_state(),
                     self._feat_rng.get_state())
        key = jax.random.PRNGKey(
            int(self._iter_key_rng.randint(0, 2**31 - 1)))
        fmasks = self._feature_masks()
        span = tm.start_span("boost_iter", iteration=self.iter_)

        def _enqueue():
            FAULTS.fault_point("gbdt.train_one_iter")
            return self._fused_step(
                self.scores, tuple(vs.scores for vs in self.valid_sets),
                self._bag_state, key, fmasks,
                jnp.asarray(self.shrinkage_rate, jnp.float32),
                self.grower.ohb, self._build_captives(),
                fresh_bag=fresh_bag, sample_active=self._sample_active())

        try:
            with tm.span("host_dispatch"):
                scores, vscores, bag, trees, nl = retry_call(
                    self._dispatch_guard(_enqueue,
                                         "gbdt.train_one_iter"),
                    policy=self._retry_policy(),
                    seam="gbdt.train_one_iter")
            if tm.on:
                tm.add("host_dispatch_ms",
                       (time.perf_counter() - t0) * 1e3)
                tm.fence_ready(scores)
                tm.add("trees_dispatched", self.num_class)
                tm.add("iterations", 1)
        except BaseException:
            # covers the enqueue and the fence (async OOM surfaces at
            # the fence): restore RNG streams for an exact retry
            self._iter_key_rng.set_state(_rng_snap[0])
            self._feat_rng.set_state(_rng_snap[1])
            tm.end_span(span)
            raise
        tm.end_span(span)
        if tm.on and self.grower.policy.nproc > 1:
            from ..parallel.monitor import record_step_wall
            record_step_wall(time.perf_counter() - t0)
        self.scores = scores
        for vs, s in zip(self.valid_sets, vscores):
            vs.scores = s
        self._bag_state = bag
        bias = self.init_score if (self.iter_ == 0 and
                                   self.init_score != 0.0) else 0.0
        for tree in trees:
            self.device_trees.append(tree)
            self._pending.append(("tree", tree, self.shrinkage_rate, bias))
            self._tree_scale.append(1.0)
            self._tree_shrink.append(self.shrinkage_rate)
        self._nl_window.append(nl)
        self._nl_count += 1
        self._after_iteration()
        self.iter_ += 1
        self._transport_epoch_tick()
        if self._nl_count >= self._stop_check_every:
            return self._check_stop_window()
        return False

    # ------------------------------------------------------------------
    def _transport_epoch_tick(self) -> None:
        """Elastic-membership epoch boundary (the WorldLedger protocol,
        parallel/transport.py): with a TCP transport active, every
        ``transport_epoch_iters`` completed iterations all participants
        tick the coordinator — dead peers retire (degraded continuation
        per ``sharded_allow_degraded``), and waiting joiners are
        admitted with this model's captured state as handoff (the r12
        byte-identical-resume snapshot: a joiner restoring it trains
        the exact iterations the world trains next).  Strictly BETWEEN
        iterations, so a collective can never race a membership
        change; with an unchanged world the tick is one tiny control
        round."""
        from ..parallel import transport as _transport
        tp = _transport.active()
        if tp is None or tp.world_size < 1:
            return
        if self.iter_ % max(1, tp.epoch_every) != 0:
            return

        def _handoff() -> bytes:
            import pickle as _pickle
            state, _stopped = self.capture_state()
            return _pickle.dumps(state, protocol=4)

        info = tp.epoch_tick(
            handoff=_handoff,
            allow_degraded=bool(getattr(self.config,
                                        "sharded_allow_degraded",
                                        False)))
        if info.get("changed"):
            Log.warning(
                f"transport epoch {info['epoch']}: world is now "
                f"{info['world_size']} (dead={info['dead']}, "
                f"admitted={info['admitted']}) — training continues "
                "on the reformed membership")

    # ------------------------------------------------------------------
    def _train_one_iter_custom(self, grad, hess) -> bool:
        """Custom-gradient iteration (gradients cross the host boundary
        every call, like the reference's UpdateOneIterCustom)."""
        if self._mh:
            Log.fatal("multi-host training does not support custom "
                      "gradient functions yet (host gradients cannot "
                      "follow the sharded row layout)")
        self._before_boosting()
        grad = np.asarray(grad, dtype=np.float32).reshape(
            self.num_class, self.num_data)
        hess = np.asarray(hess, dtype=np.float32).reshape(
            self.num_class, self.num_data)
        pad = self.grower.n_padded - self.num_data
        g = jnp.asarray(np.pad(grad, ((0, 0), (0, pad))))
        h = jnp.asarray(np.pad(hess, ((0, 0), (0, pad))))
        counts, bag_mask = self._bagging_counts(self.iter_)
        g, h, counts = self._sample_rows(g, h, counts)
        g, h = self._mask_gradients(g, h, counts)

        bias = self.init_score if (self.iter_ == 0 and
                                   self.init_score != 0.0) else 0.0
        nl = jnp.int32(1)
        for k in range(self.num_class):
            feature_mask = self._feature_mask()
            tree_arrays, leaf_id, _ = self.grower.train_tree(
                g[k], h[k], counts, feature_mask,
                qkey=self._host_qkey(k))
            tree_arrays = self._finalize_tree(tree_arrays, leaf_id, k,
                                              self.scores, counts)
            ok = (tree_arrays.num_leaves > 1).astype(jnp.float32)
            tree_arrays = tree_arrays._replace(
                leaf_value=tree_arrays.leaf_value * ok)
            self.device_trees.append(tree_arrays)
            self.scores = self._update_train_fn(
                self.scores, leaf_id, tree_arrays.leaf_value, k,
                self.shrinkage_rate)
            for vs in self.valid_sets:
                delta = self._predict_valid_fn(tree_arrays, vs.bins)
                vs.scores = vs.scores.at[k].add(
                    delta * self.shrinkage_rate)
            self._pending.append(("tree", tree_arrays,
                                  self.shrinkage_rate, bias))
            self._tree_scale.append(1.0)
            self._tree_shrink.append(self.shrinkage_rate)
            nl = jnp.maximum(nl, tree_arrays.num_leaves)
        if TELEMETRY.on:
            TELEMETRY.add("trees_dispatched", self.num_class)
            TELEMETRY.add("iterations", 1)
        self._nl_window.append(nl)
        self._after_iteration()
        self.iter_ += 1
        self._transport_epoch_tick()
        if len(self._nl_window) >= self._stop_check_every:
            return self._check_stop_window()
        return False

    # ------------------------------------------------------------------
    def _check_stop_window(self) -> bool:
        """Deferred no-split detection: pull the queued per-iteration
        max-num_leaves scalars in ONE transfer; if some iteration grew
        no tree, roll back everything after it and stop (the reference
        checks every iteration — here 1-leaf trees contribute exactly
        zero score, so late rollback is exact)."""
        if not self._nl_window:
            return False
        vals = np.asarray(jnp.concatenate(
            [jnp.atleast_1d(x) for x in self._nl_window]))
        self._nl_window = []
        self._nl_count = 0
        for j, v in enumerate(vals):
            if int(v) <= 1:
                overrun = len(vals) - j
                for _ in range(overrun):
                    self.rollback_one_iter()
                Log.warning("Stopped training because there are no more "
                            "leaves that meet the split requirements.")
                return True
        return False

    # ------------------------------------------------------------------
    def flush_models(self, final: bool = False) -> None:
        """Materialize queued device trees into host ``self.models`` in
        one batched device->host transfer, and reconcile DART weight
        rescales on already-materialized trees.  Only a ``final`` flush
        consumes the deferred no-split window (popping degenerate tail
        trees) — mid-training flushes must leave the window for
        train_one_iter's own stop detection."""
        if final and self._nl_window:
            self._check_stop_window()
        for i, t in enumerate(self.models):
            if self._applied_scale[i] != self._tree_scale[i]:
                r = self._tree_scale[i] / self._applied_scale[i]
                t.leaf_value *= r
                t.internal_value *= r
                t.shrinkage *= r
                self._applied_scale[i] = self._tree_scale[i]
        if not self._pending:
            return
        pending, self._pending = self._pending, []
        span = TELEMETRY.start_span("model_flush", entries=len(pending))
        # ONE device->host transfer for everything queued: per-tree
        # entries are stacked, chunk entries already are stacks (packed
        # record stacks travel as their single uint8 buffer)
        plain = [p[1] for p in pending if p[0] == "tree"]
        stacked_plain = (jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *plain) if plain else None)
        chunk_stacks = [p[1] for p in pending if p[0] == "stack"]
        rec_stacks = [p[1] for p in pending if p[0] == "rstack"]
        host_plain, host_chunks, host_recs = jax.device_get(
            (stacked_plain, chunk_stacks, rec_stacks))

        compact_rows = [0, 0]      # [in an active slot, streamed]
        split_rows = [0, 0]        # [split rows copied, gathers]

        def append_tree(arrs, shrinkage, bias):
            if TELEMETRY.on:
                active, streamed = self.grower.compact_pass_rows(arrs)
                compact_rows[0] += active
                compact_rows[1] += streamed
                copied, gathers = self.grower.split_row_copies(arrs)
                split_rows[0] += copied
                split_rows[1] += gathers
            t = Tree.from_grower_arrays(arrs, self.train_set)
            t.apply_shrinkage(shrinkage)
            if bias != 0.0:
                # fold the init score into the first tree so saved models
                # and raw predictions carry it (reference gbdt.cpp:452-454)
                t.leaf_value += bias
                t.internal_value += bias
            idx = len(self.models)
            scale = self._tree_scale[idx]
            if scale != 1.0:
                t.leaf_value *= scale
                t.internal_value *= scale
                t.shrinkage *= scale
            self.models.append(t)
            self._applied_scale.append(scale)

        i_plain = 0
        i_chunk = 0
        i_rec = 0
        n_before = len(self.models)
        layout = self.grower.record_layout
        for p in pending:
            if p[0] == "tree":
                _, _tree, shrinkage, bias = p
                arrs = {f: np.asarray(getattr(host_plain, f)[i_plain])
                        for f in host_plain._fields}
                append_tree(arrs, shrinkage, bias)
                i_plain += 1
            elif p[0] == "rstack":
                _, _recs, n_iters, shrinkage, bias0 = p
                recs = host_recs[i_rec]       # (chunk, K, record_size)
                i_rec += 1
                for j in range(n_iters):
                    for k in range(recs.shape[1]):
                        arrs = layout.unpack_tree_record(recs[j, k])
                        append_tree(arrs, shrinkage,
                                    bias0 if j == 0 else 0.0)
            else:
                _, _stacks, n_iters, shrinkage, bias0 = p
                stacks = host_chunks[i_chunk]
                i_chunk += 1
                for j in range(n_iters):
                    for stack in stacks:
                        arrs = {f: np.asarray(getattr(stack, f)[j])
                                for f in stack._fields}
                        append_tree(arrs, shrinkage,
                                    bias0 if j == 0 else 0.0)
        TELEMETRY.add("trees_flushed", len(self.models) - n_before)
        if compact_rows[1]:
            # the compacting rungs' passes of the committed trees: rows
            # put through the dots over rows streamed (the share of the
            # job so far is the gauge)
            TELEMETRY.add("hist_compact_active_rows", compact_rows[0])
            TELEMETRY.add("hist_compact_streamed_rows", compact_rows[1])
            total = TELEMETRY.counters()
            TELEMETRY.gauge("hist_active_row_share",
                            total["hist_compact_active_rows"]
                            / total["hist_compact_streamed_rows"])
        if split_rows[1]:
            # the committed trees' split-row gathers (a chunked table's
            # passes and exit routes): rows copied over ROUTE_ROWS a
            # gather, what copying every route row takes
            TELEMETRY.add("route_split_rows_copied", split_rows[0])
            TELEMETRY.add("route_split_row_gathers", split_rows[1])
            total = TELEMETRY.counters()
            TELEMETRY.gauge("route_split_row_share",
                            total["route_split_rows_copied"]
                            / (total["route_split_row_gathers"]
                               * ROUTE_ROWS))
        TELEMETRY.end_span(span)

    # ------------------------------------------------------------------
    # crash-safe checkpointing (docs/RELIABILITY.md) ------------------
    def _retry_policy(self) -> RetryPolicy:
        p = getattr(self, "_retry_policy_cache", None)
        if p is None:
            p = RetryPolicy.from_config(self.config)
            self._retry_policy_cache = p
        return p

    def _dispatch_guard(self, fn, seam: str):
        """Deadline-bound a dispatch enqueue under
        ``watchdog_dispatch_s`` (docs/RELIABILITY.md, deadline
        watchdog): an enqueue that has not returned within the
        deadline — a wedged backend RPC, a ``hang`` fault — dumps
        all-thread stacks and raises a classified ``StallError``,
        which the surrounding ``retry_call`` treats as transient
        (the enqueue precedes any state mutation, so re-entering is
        exact).  Disarmed (the default 0) this returns ``fn``
        untouched — zero overhead, identical programs."""
        wd = float(getattr(self.config, "watchdog_dispatch_s", 0.0)
                   or 0.0)
        if wd <= 0:
            return fn
        from ..reliability.watchdog import run_with_deadline

        def _bounded():
            return run_with_deadline(fn, wd, phase="dispatch",
                                     seam=seam)
        return _bounded

    def can_checkpoint(self) -> bool:
        """Whether full-state checkpointing covers this booster: plain
        GBDT and GOSS (their entire RNG state lives in the captured
        streams).  DART re-scales finished trees from host-side drop
        state and RF mutates averaged leaf outputs between iterations
        — neither round-trips through capture_state yet."""
        return type(self).__name__ in ("GBDT", "GOSS") and not self._mh

    def capture_state(self) -> Tuple[dict, bool]:
        """Snapshot FULL training state for a crash-safe checkpoint:
        host models, score caches, bagging/key RNG streams, and
        early-stopping bookkeeping — everything a resumed run needs to
        produce byte-identical trees to an uninterrupted one.  The
        deferred no-split window is consumed first (it is the one
        piece of state that references device-resident tree stacks);
        returns (state, stopped) where stopped means the window
        detected end-of-training."""
        stopped = self._check_stop_window() if self._nl_window else False
        self.flush_models()
        state = {
            "iter_": self.iter_,
            "models": list(self.models),
            "tree_scale": list(self._tree_scale),
            "applied_scale": list(self._applied_scale),
            "tree_shrink": list(self._tree_shrink),
            # informational only: restore_state deliberately sets
            # scale_offset to len(models) instead (restored trees are
            # host-only and route like init_model foreign trees)
            "scale_offset": self._scale_offset,
            "shrinkage_rate": self.shrinkage_rate,
            "init_score": self.init_score,
            "scores": np.asarray(self.scores),
            "valid_scores": [np.asarray(vs.scores)
                             for vs in self.valid_sets],
            "bag_state": (None if self._bag_state is None
                          else np.asarray(self._bag_state)),
            "bag_mask": (None if self._bag_mask is None
                         else np.asarray(self._bag_mask)),
            "bag_rng": np.asarray(self._bag_rng),
            "iter_key_rng": self._iter_key_rng.get_state(),
            "feat_rng": self._feat_rng.get_state(),
            "py_rng": self._rng.get_state(),
            "best_score": dict(self._best_score),
            "best_iter": dict(self._best_iter),
            "best_iteration": self.best_iteration,
            "num_class": self.num_class,
            "num_data": self.num_data,
            "n_padded": self.grower.n_padded,
            "num_valid": len(self.valid_sets),
        }
        if hasattr(self, "_goss_key"):          # GOSS host-path stream
            state["goss_key"] = np.asarray(self._goss_key)
        return state, stopped

    def restore_state(self, state: dict) -> None:
        """Adopt a capture_state snapshot: the inverse restore, run on
        a freshly-constructed GBDT over the SAME dataset (the caller
        verified the checkpoint fingerprint).  Raises CheckpointError
        on any shape/identity mismatch rather than training garbage."""
        if state.get("num_class") != self.num_class or \
                state.get("num_data") != self.num_data or \
                state.get("n_padded") != self.grower.n_padded or \
                state.get("num_valid") != len(self.valid_sets):
            raise CheckpointError(
                "checkpoint state does not match this training setup "
                f"(saved num_data={state.get('num_data')}/"
                f"num_class={state.get('num_class')}/padded="
                f"{state.get('n_padded')}/valid={state.get('num_valid')}"
                f" vs {self.num_data}/{self.num_class}/"
                f"{self.grower.n_padded}/{len(self.valid_sets)})")
        import jax.numpy as jnp
        self.iter_ = int(state["iter_"])
        # in-place: Booster.models aliases this list
        self.models[:] = state["models"]
        self._tree_scale[:] = state["tree_scale"]
        self._applied_scale[:] = state["applied_scale"]
        self._tree_shrink[:] = state["tree_shrink"]
        # restored trees live only on host — register them like
        # init_model foreign trees so the in-session binned device
        # predict (which only knows post-resume device stacks) stands
        # down in favor of the host/stacked path
        self._scale_offset = len(self.models)
        self.shrinkage_rate = float(state["shrinkage_rate"])
        self.init_score = float(state["init_score"])
        self.scores = self.grower.policy.place_score_rows(
            np.asarray(state["scores"], np.float32))
        for vs, arr in zip(self.valid_sets, state["valid_scores"]):
            vs.scores = jnp.asarray(np.asarray(arr, np.float32))
        self._bag_state = (None if state["bag_state"] is None
                           else jnp.asarray(state["bag_state"]))
        mask = state.get("bag_mask")
        self._bag_mask = None if mask is None else jnp.asarray(mask)
        self._bag_rng = jnp.asarray(
            np.asarray(state["bag_rng"], np.uint32))
        self._iter_key_rng.set_state(state["iter_key_rng"])
        self._feat_rng.set_state(state["feat_rng"])
        self._rng.set_state(state["py_rng"])
        self._best_score = dict(state["best_score"])
        self._best_iter = dict(state["best_iter"])
        self.best_iteration = int(state["best_iteration"])
        if "goss_key" in state and hasattr(self, "_goss_key"):
            self._goss_key = jnp.asarray(
                np.asarray(state["goss_key"], np.uint32))
        self.device_trees = []
        self._pending = []
        self._nl_window = []
        self._nl_count = 0

    # ------------------------------------------------------------------
    def _mask_gradients(self, g, h, counts):
        """Apply bagging mask and row weights to gradient channels.
        Row weights are already inside the objective's gradients
        (reference semantics); only the bag mask zeroes rows here."""
        mask = counts > 0
        return g * mask[None, :], h * mask[None, :]

    # ------------------------------------------------------------------
    def _finalize_tree(self, tree_arrays: TreeArrays, leaf_id, class_idx,
                       scores, counts) -> TreeArrays:
        """Objective-specific leaf refitting hook (RenewTreeOutput,
        reference serial_tree_learner.cpp:776-806).  Pure/jittable:
        ``scores`` are the pre-update scores, ``counts`` the bag mask."""
        if self.objective is not None and \
                self.objective.is_renew_tree_output:
            tree_arrays = self._renew_tree_output(tree_arrays, leaf_id,
                                                  class_idx, scores, counts)
        return tree_arrays

    def _renew_tree_output(self, tree_arrays, leaf_id, class_idx,
                           scores, counts):
        """Re-fit leaf outputs to the objective's percentile (L1-family
        objectives; reference regression_objective.hpp RenewTreeOutput).
        Device: lexicographic sort by (leaf, residual) then per-leaf
        percentile interpolation."""
        from ..ops.percentile import leaf_percentiles
        n = self.num_data
        obj = self.objective
        pred = scores[class_idx, :n]
        label = obj._label_dev
        residual = label - pred
        alpha = obj.renew_alpha
        if hasattr(obj, "_label_weight_dev"):
            w = obj._label_weight_dev          # mape weighting
        elif obj.weight is not None:
            w = obj._weight_dev
        else:
            w = None
        # restrict to in-bag rows (reference passes bag_data_indices,
        # gbdt.cpp:446-447): out-of-bag rows get leaf -1 and are ignored
        lid = jnp.where(counts[:n] > 0, leaf_id[:n], -1)
        L = self.config.num_leaves
        new_values = leaf_percentiles(residual, lid, L, alpha, w)
        ok = tree_arrays.leaf_count > 0
        return tree_arrays._replace(
            leaf_value=jnp.where(ok, new_values,
                                 tree_arrays.leaf_value))

    # ------------------------------------------------------------------
    def eval_metrics(self, which: str = "all"
                     ) -> List[Tuple[str, str, float, bool]]:
        """Returns (dataset_name, metric_name, value, bigger_better).
        ``which``: 'all', 'train' or 'valid' — scoped so eval_train /
        eval_valid don't pay for metrics they discard."""
        with TELEMETRY.span("eval_metrics"):
            return self._eval_metrics_impl(which)

    def _eval_metrics_impl(self, which="all"):
        out = []
        if self.train_metrics and which in ("all", "train"):
            s = self._scores_for_eval(self.scores[:, :self.num_data])
            for m in self.train_metrics:
                for name, v in zip(m.names(), m.eval(s, self.objective)):
                    out.append(("training", name, v, m.bigger_is_better))
        if which in ("all", "valid"):
            for vs, vname in zip(self.valid_sets, self.valid_names):
                s = self._scores_for_eval(vs.scores)
                for m in vs.metrics:
                    for name, v in zip(m.names(),
                                       m.eval(s, self.objective)):
                        out.append((vname, name, v,
                                    m.bigger_is_better))
        return out

    def _scores_for_eval(self, scores):
        if self.num_class == 1:
            return scores[0]
        return scores.T       # (N, K)

    # ------------------------------------------------------------------
    def check_early_stopping(self, results, iteration: int) -> bool:
        """Reference gbdt.cpp:582-639: stop as soon as ANY validation
        metric has not improved for early_stopping_round iterations;
        best_iteration comes from the triggering metric."""
        rounds = self.config.early_stopping_round
        if rounds <= 0:
            return False
        for i, (dname, mname, value, bigger) in enumerate(results):
            if dname == "training":
                continue
            key = (i, 0)
            score = value if bigger else -value
            if key not in self._best_score or score > self._best_score[key]:
                self._best_score[key] = score
                self._best_iter[key] = iteration
            elif iteration - self._best_iter[key] >= rounds:
                self.best_iteration = self._best_iter[key] + 1
                return True
        return False

    # ------------------------------------------------------------------
    def _materialize_devtree(self, entry):
        """device_trees entry -> TreeArrays (chunk entries are lazy
        slices of a stacked chunk; packed-carry entries unpack their
        byte record on device)."""
        if isinstance(entry, tuple) and entry and entry[0] == "stackref":
            _, stack, j = entry
            return jax.tree_util.tree_map(lambda x: x[j], stack)
        if isinstance(entry, tuple) and entry and entry[0] == "recref":
            from ..ops.predict import unpack_tree_records_device
            _, recs, j, k = entry
            return unpack_tree_records_device(
                recs[j, k], self.config.num_leaves,
                self.grower.max_feature_bin)
        return entry

    def rollback_one_iter(self) -> None:
        """reference gbdt.cpp:483-499."""
        if self.num_trees < self.num_class:
            return
        # pending bookkeeping: one iteration = num_class trees
        shrinkage = self.shrinkage_rate
        if self._pending:
            last = self._pending[-1]
            if last[0] in ("stack", "rstack"):
                kind, stacks, n, shrinkage, bias0 = last
                if n <= 1:
                    self._pending.pop()
                else:
                    self._pending[-1] = (kind, stacks, n - 1,
                                         shrinkage, bias0)
            else:
                for _ in range(self.num_class):
                    _, _t, shrinkage, _b = self._pending.pop()
        else:
            for _ in range(self.num_class):
                self.models.pop()
                self._applied_scale.pop()
        for k in reversed(range(self.num_class)):
            tree_arrays = self._materialize_devtree(self.device_trees.pop())
            self._tree_scale.pop()
            if self._tree_shrink:
                self._tree_shrink.pop()
            self.scores = self.scores.at[k].add(
                -shrinkage * self._predict_valid_fn(
                    tree_arrays, self.grower.bins))
            for vs in self.valid_sets:
                vs.scores = vs.scores.at[k].add(
                    -shrinkage * self._predict_valid_fn(
                        tree_arrays, vs.bins))
        self.iter_ -= 1

    # ------------------------------------------------------------------
    @property
    def num_trees(self) -> int:
        n = len(self.models)
        for p in self._pending:
            if p[0] == "stack":
                n += p[2] * len(p[1])
            elif p[0] == "rstack":
                n += p[2] * p[1].shape[1]
            else:
                n += 1
        return n
