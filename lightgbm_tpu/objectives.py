"""Objective functions: per-row gradient/hessian computation.

TPU-native re-design of the reference's objective layer
(reference: src/objective/*.hpp behind the factory
objective_function.cpp:10-80; interface objective_function.h:13-80).
Every objective is a pure vectorized function score -> (grad, hess)
executed on device inside the jitted boosting step; the per-row OpenMP
loops become elementwise array ops, and lambdarank's per-query sorted
pairwise loop (rank_objective.hpp:83-170) becomes masked pair tiles over
queries grouped in length buckets, each bucket padded to its own width.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import Config
from .dataset import Metadata
from .telemetry import TELEMETRY
from .utils.log import Log

K_EPSILON = 1e-15


def _percentile(values: np.ndarray, alpha: float) -> float:
    """LightGBM's PercentileFun (reference utils/common.h): index
    interpolation at alpha*(n-1) over sorted values."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    n = len(v)
    if n == 0:
        return 0.0
    if n == 1:
        return float(v[0])
    pos = alpha * (n - 1)
    lo = int(math.floor(pos))
    hi = min(lo + 1, n - 1)
    frac = pos - lo
    return float(v[lo] * (1 - frac) + v[hi] * frac)


def _weighted_percentile(values: np.ndarray, weights: np.ndarray,
                         alpha: float) -> float:
    """Weighted percentile matching WeightedPercentileFun
    (reference utils/common.h): threshold at alpha * (sum_w - w_max/2?) —
    the reference walks sorted values accumulating weights until
    alpha * total is reached, interpolating between neighbors."""
    v = np.asarray(values, dtype=np.float64)
    w = np.asarray(weights, dtype=np.float64)
    order = np.argsort(v)
    v, w = v[order], w[order]
    n = len(v)
    if n == 0:
        return 0.0
    if n == 1:
        return float(v[0])
    cum = np.cumsum(w) - w / 2.0
    threshold = alpha * w.sum()
    idx = int(np.searchsorted(cum, threshold, side="left"))
    if idx <= 0:
        return float(v[0])
    if idx >= n:
        return float(v[-1])
    t = (threshold - cum[idx - 1]) / max(cum[idx] - cum[idx - 1], 1e-30)
    return float(v[idx - 1] * (1 - t) + v[idx] * t)


class Objective:
    """Base objective (reference objective_function.h:13-80)."""

    name = "none"
    is_constant_hessian = False
    is_renew_tree_output = False
    need_accurate_prediction = True
    renew_alpha = 0.5  # percentile for renew-tree-output objectives
    # int8 quantized training: whether THIS objective's gradient
    # distribution needs stochastic rounding (skewed, long-tailed —
    # most values far below the per-tree max; see ops/histogram.py
    # quantize_gradients)
    need_stochastic_quant = False
    # int8 quantized training: whether the grower takes the root's totals
    # from the quantized gradients (TreeGrower.quantized_root_totals)
    quantized_root_totals = False

    def __init__(self, config: Config):
        self.config = config
        self.num_class = 1

    def init(self, metadata: Metadata, num_data: int) -> None:
        self.num_data = num_data
        self.label = metadata.label[:num_data].astype(np.float32)
        self.weight = (None if metadata.weight is None
                       else metadata.weight[:num_data].astype(np.float32))
        self._label_dev = jnp.asarray(self.label)
        self._weight_dev = (None if self.weight is None
                            else jnp.asarray(self.weight))

    @property
    def num_model_per_iteration(self) -> int:
        return 1

    def get_gradients(self, score: jax.Array) -> Tuple[jax.Array, jax.Array]:
        """score: (N,) raw scores (or (N, K) multiclass).  Pure / jittable."""
        raise NotImplementedError

    def boost_from_score(self) -> float:
        return 0.0

    def convert_output(self, raw):
        """Raw score -> output space (jnp or np agnostic)."""
        return raw

    def _apply_weight(self, grad, hess):
        if self._weight_dev is None:
            return grad, hess
        return grad * self._weight_dev, hess * self._weight_dev

    def repad_device_arrays(self, pad_place) -> None:
        """Multi-host layout fixup: every (num_data,)-leading device
        array (the ``*_dev`` convention) is re-padded to the assembled
        global row layout (per-host padding blocks) and placed
        row-sharded over the mesh.  Host-side stats (label means,
        percentiles) were already computed from the unpadded global
        metadata in init().  ``pad_place(np_arr) -> placed array``."""
        for name, val in list(self.__dict__.items()):
            if (name.endswith("_dev") and val is not None
                    and getattr(val, "ndim", 0) >= 1
                    and val.shape[0] == self.num_data):
                self.__dict__[name] = pad_place(np.asarray(val))

    def renew_leaf_values(self, residual_fn, leaf_id, num_leaves):
        raise NotImplementedError


class RegressionL2(Objective):
    """reference regression_objective.hpp:64-174"""
    name = "regression"
    is_constant_hessian = True

    def __init__(self, config: Config):
        super().__init__(config)
        self.sqrt = config.reg_sqrt

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if self.sqrt:
            self.label = (np.sign(self.label)
                          * np.sqrt(np.abs(self.label))).astype(np.float32)
            self._label_dev = jnp.asarray(self.label)
        self.is_constant_hessian = self.weight is None

    def get_gradients(self, score):
        grad = score - self._label_dev
        hess = jnp.ones_like(score)
        return self._apply_weight(grad, hess)

    def boost_from_score(self):
        if self.weight is not None:
            return float(np.average(self.label, weights=self.weight))
        return float(np.mean(self.label))

    def convert_output(self, raw):
        if self.sqrt:
            return jnp.sign(raw) * raw * raw if isinstance(raw, jax.Array) \
                else np.sign(raw) * raw * raw
        return raw


class RegressionL1(RegressionL2):
    """reference regression_objective.hpp:175-260; constant hessian with
    median leaf refitting."""
    name = "regression_l1"
    is_renew_tree_output = True
    renew_alpha = 0.5

    def get_gradients(self, score):
        grad = jnp.sign(score - self._label_dev)
        hess = jnp.ones_like(score)
        return self._apply_weight(grad, hess)

    def boost_from_score(self):
        if self.weight is not None:
            return _weighted_percentile(self.label, self.weight, 0.5)
        return _percentile(self.label, 0.5)


class RegressionHuber(RegressionL2):
    """reference regression_objective.hpp:261-315"""
    name = "huber"
    is_constant_hessian = False

    def get_gradients(self, score):
        a = self.config.alpha
        diff = score - self._label_dev
        grad = jnp.where(jnp.abs(diff) <= a, diff, jnp.sign(diff) * a)
        hess = jnp.ones_like(score)
        return self._apply_weight(grad, hess)


class RegressionFair(RegressionL2):
    """reference regression_objective.hpp:316-363"""
    name = "fair"
    is_constant_hessian = False

    def get_gradients(self, score):
        c = self.config.fair_c
        x = score - self._label_dev
        denom = jnp.abs(x) + c
        grad = c * x / denom
        hess = c * c / (denom * denom)
        return self._apply_weight(grad, hess)


class RegressionPoisson(RegressionL2):
    """reference regression_objective.hpp:364-444: log-link,
    loss = exp(f) - label*f."""
    name = "poisson"
    is_constant_hessian = False

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if np.min(self.label) < 0:
            Log.fatal(f"[{self.name}]: at least one target label is negative")
        if np.sum(self.label) == 0:
            Log.fatal(f"[{self.name}]: sum of labels is zero")

    def get_gradients(self, score):
        grad = jnp.exp(score) - self._label_dev
        hess = jnp.exp(score + self.config.poisson_max_delta_step)
        return self._apply_weight(grad, hess)

    def boost_from_score(self):
        return math.log(max(RegressionL2.boost_from_score(self), 1e-30))

    def convert_output(self, raw):
        return jnp.exp(raw) if isinstance(raw, jax.Array) else np.exp(raw)


class RegressionQuantile(RegressionL2):
    """reference regression_objective.hpp:445-543"""
    name = "quantile"
    is_renew_tree_output = True

    def __init__(self, config):
        super().__init__(config)
        if not (0 < config.alpha < 1):
            Log.fatal("alpha must be in (0, 1) for quantile objective")
        self.renew_alpha = config.alpha

    def get_gradients(self, score):
        a = self.config.alpha
        delta = score - self._label_dev
        grad = jnp.where(delta >= 0, 1.0 - a, -a)
        hess = jnp.ones_like(score)
        return self._apply_weight(grad, hess)

    def boost_from_score(self):
        if self.weight is not None:
            return _weighted_percentile(self.label, self.weight,
                                        self.config.alpha)
        return _percentile(self.label, self.config.alpha)


class RegressionMAPE(RegressionL1):
    """reference regression_objective.hpp:544-644: sign gradient scaled
    by 1/max(1,|label|)."""
    name = "mape"
    is_constant_hessian = True
    is_renew_tree_output = True
    renew_alpha = 0.5

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if np.any(np.abs(self.label) < 1):
            Log.warning("Met 'abs(label) < 1', will convert them to '1' in "
                        "Mape objective and metric.")
        lw = 1.0 / np.maximum(1.0, np.abs(self.label))
        if self.weight is not None:
            lw = lw * self.weight
        self.label_weight = lw.astype(np.float32)
        self._label_weight_dev = jnp.asarray(self.label_weight)

    def get_gradients(self, score):
        diff = score - self._label_dev
        grad = jnp.sign(diff) * self._label_weight_dev
        hess = (jnp.ones_like(score) if self._weight_dev is None
                else jnp.broadcast_to(self._weight_dev, score.shape))
        return grad, hess

    def boost_from_score(self):
        return _weighted_percentile(self.label, self.label_weight, 0.5)


class RegressionGamma(RegressionPoisson):
    """reference regression_objective.hpp:645-681"""
    name = "gamma"

    def get_gradients(self, score):
        ratio = self._label_dev / jnp.exp(score)
        if self._weight_dev is not None:
            # reference applies the weight inside the ratio term only
            grad = 1.0 - ratio * self._weight_dev
            hess = ratio * self._weight_dev
        else:
            grad = 1.0 - ratio
            hess = ratio
        return grad, hess


class RegressionTweedie(RegressionPoisson):
    """reference regression_objective.hpp:682+"""
    name = "tweedie"

    def get_gradients(self, score):
        rho = self.config.tweedie_variance_power
        e1 = jnp.exp((1 - rho) * score)
        e2 = jnp.exp((2 - rho) * score)
        grad = -self._label_dev * e1 + e2
        hess = -self._label_dev * (1 - rho) * e1 + (2 - rho) * e2
        return self._apply_weight(grad, hess)


class BinaryLogloss(Objective):
    """reference binary_objective.hpp:13-155: labels mapped to ±1,
    is_unbalance / scale_pos_weight class weighting."""
    name = "binary"
    need_accurate_prediction = False

    def __init__(self, config: Config):
        super().__init__(config)
        self.sigmoid = config.sigmoid
        if self.sigmoid <= 0:
            Log.fatal(f"Sigmoid parameter {self.sigmoid} should be greater "
                      "than zero")
        if config.is_unbalance and abs(config.scale_pos_weight - 1.0) > 1e-6:
            Log.fatal("Cannot set is_unbalance and scale_pos_weight at the "
                      "same time")

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        is_pos = self.label > 0
        cnt_pos = int(is_pos.sum())
        cnt_neg = num_data - cnt_pos
        if cnt_pos == 0 or cnt_neg == 0:
            Log.warning("Only contain one class.")
        Log.info(f"Number of positive: {cnt_pos}, number of negative: "
                 f"{cnt_neg}")
        w_pos, w_neg = 1.0, 1.0
        if self.config.is_unbalance and cnt_pos > 0 and cnt_neg > 0:
            if cnt_pos > cnt_neg:
                w_neg = cnt_pos / cnt_neg
            else:
                w_pos = cnt_neg / cnt_pos
        w_pos *= self.config.scale_pos_weight
        self._sign_dev = jnp.asarray(np.where(is_pos, 1.0, -1.0)
                                     .astype(np.float32))
        self._lw_dev = jnp.asarray(np.where(is_pos, w_pos, w_neg)
                                   .astype(np.float32))

    def get_gradients(self, score):
        s = self.sigmoid
        response = -self._sign_dev * s / (
            1.0 + jnp.exp(self._sign_dev * s * score))
        abs_r = jnp.abs(response)
        grad = response * self._lw_dev
        hess = abs_r * (s - abs_r) * self._lw_dev
        return self._apply_weight(grad, hess)

    def convert_output(self, raw):
        if isinstance(raw, jax.Array):
            return 1.0 / (1.0 + jnp.exp(-self.sigmoid * raw))
        return 1.0 / (1.0 + np.exp(-self.sigmoid * raw))


class MulticlassSoftmax(Objective):
    """reference multiclass_objective.hpp:16-138: K trees/iteration."""
    name = "multiclass"
    need_accurate_prediction = False

    def __init__(self, config: Config):
        super().__init__(config)
        self.num_class = config.num_class

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        li = self.label.astype(np.int32)
        if li.min() < 0 or li.max() >= self.num_class:
            Log.fatal(f"Label must be in [0, {self.num_class})")
        self._onehot_dev = jnp.asarray(
            (li[:, None] == np.arange(self.num_class)[None, :])
            .astype(np.float32))

    @property
    def num_model_per_iteration(self) -> int:
        return self.num_class

    def get_gradients(self, score):
        # score: (N, K)
        p = jax.nn.softmax(score, axis=1)
        grad = p - self._onehot_dev
        hess = 2.0 * p * (1.0 - p)
        if self._weight_dev is not None:
            grad = grad * self._weight_dev[:, None]
            hess = hess * self._weight_dev[:, None]
        return grad, hess

    def convert_output(self, raw):
        if isinstance(raw, jax.Array):
            return jax.nn.softmax(raw, axis=-1)
        e = np.exp(raw - raw.max(axis=-1, keepdims=True))
        return e / e.sum(axis=-1, keepdims=True)


class MulticlassOVA(Objective):
    """reference multiclass_objective.hpp:139+: K independent binary
    losses."""
    name = "multiclassova"
    need_accurate_prediction = False

    def __init__(self, config: Config):
        super().__init__(config)
        self.num_class = config.num_class
        self.sigmoid = config.sigmoid

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        li = self.label.astype(np.int32)
        self._sign_dev = jnp.asarray(
            np.where(li[:, None] == np.arange(self.num_class)[None, :],
                     1.0, -1.0).astype(np.float32))

    @property
    def num_model_per_iteration(self) -> int:
        return self.num_class

    def get_gradients(self, score):
        s = self.sigmoid
        response = -self._sign_dev * s / (
            1.0 + jnp.exp(self._sign_dev * s * score))
        abs_r = jnp.abs(response)
        grad = response
        hess = abs_r * (s - abs_r)
        if self._weight_dev is not None:
            grad = grad * self._weight_dev[:, None]
            hess = hess * self._weight_dev[:, None]
        return grad, hess

    def convert_output(self, raw):
        if isinstance(raw, jax.Array):
            return 1.0 / (1.0 + jnp.exp(-self.sigmoid * raw))
        return 1.0 / (1.0 + np.exp(-self.sigmoid * raw))


class CrossEntropy(Objective):
    """reference xentropy_objective.hpp:39-141: probabilistic labels."""
    name = "cross_entropy"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if self.label.min() < 0 or self.label.max() > 1:
            Log.fatal("[cross_entropy]: labels must be in [0, 1]")

    def get_gradients(self, score):
        z = jax.nn.sigmoid(score)
        grad = z - self._label_dev
        hess = z * (1.0 - z)
        return self._apply_weight(grad, hess)

    def boost_from_score(self):
        if self.weight is not None:
            pavg = float(np.average(self.label, weights=self.weight))
        else:
            pavg = float(np.mean(self.label))
        pavg = min(max(pavg, 1e-15), 1 - 1e-15)
        score = math.log(pavg / (1 - pavg))
        Log.info(f"[{self.name}]: pavg={pavg:f} -> initscore={score:f}")
        return score

    def convert_output(self, raw):
        if isinstance(raw, jax.Array):
            return jax.nn.sigmoid(raw)
        return 1.0 / (1.0 + np.exp(-raw))


class CrossEntropyLambda(Objective):
    """reference xentropy_objective.hpp:142-250: alternative
    parameterization with weight-dependent link."""
    name = "cross_entropy_lambda"

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if self.label.min() < 0 or self.label.max() > 1:
            Log.fatal("[cross_entropy_lambda]: labels must be in [0, 1]")

    def get_gradients(self, score):
        if self._weight_dev is None:
            z = jax.nn.sigmoid(score)
            grad = z - self._label_dev
            hess = z * (1.0 - z)
            return grad, hess
        w = self._weight_dev
        y = self._label_dev
        epf = jnp.exp(score)
        hhat = jnp.log1p(epf)
        z = 1.0 - jnp.exp(-w * hhat)
        enf = 1.0 / epf
        grad = (1.0 - y / jnp.maximum(z, 1e-30)) * w / (1.0 + enf)
        c = 1.0 / (1.0 - jnp.minimum(z, 1 - 1e-30))
        d = 1.0 + epf
        a = w * epf / (d * d)
        hess = c * (a + (w / d) ** 2 * (z - y) * c
                    * jnp.exp(-w * hhat))  # matches reference expansion
        return grad, hess

    def boost_from_score(self):
        if self.weight is not None:
            havg = float(np.average(self.label, weights=self.weight))
        else:
            havg = float(np.mean(self.label))
        score = math.log(max(math.exp(havg) - 1.0, 1e-15))
        Log.info(f"[{self.name}]: havg={havg:f} -> initscore={score:f}")
        return score

    def convert_output(self, raw):
        if isinstance(raw, jax.Array):
            return jnp.log1p(jnp.exp(raw))
        return np.log1p(np.exp(raw))


def _banded_take_plan(positions: np.ndarray, tile: int = 128):
    """Plan an exact take out[i] = x[positions[i]] (-1 = emit 0) as
    per-``tile`` window takes + one-hot matmuls.

    The valid positions of every ``tile``-slot output tile must lie
    within ``tile`` consecutive input rows (each row of lambdarank's
    length buckets holds a contiguous run of the flat rows and is a
    multiple of the tile wide), so the tile reads from a 2-tile
    (2*128-element) window of the input: lo = min valid position,
    hi <= lo + tile - 1, so hi - (lo//tile)*tile <= (lo % tile) +
    tile - 1 < 2*tile.  This
    is what makes the padded<->flat movement MXU work instead of an
    XLA row gather (~80M rows/s on v5e — 28 ms per 2.26M-row pass).

    Returns (wtiles (nt, 2) int32 window tile indices into the
    128-row tiles of x, local (nt, tile) int32 in-window offsets with
    2*tile as the emit-0 sentinel, nt_in_min = 1 + max window tile)."""
    P = tile
    out_len = len(positions)
    assert out_len % P == 0
    pos = positions.reshape(-1, P).astype(np.int64)
    valid = pos >= 0
    any_valid = valid.any(axis=1)
    big = np.iinfo(np.int64).max
    lo = np.where(any_valid,
                  np.where(valid, pos, big).min(axis=1), 0)
    base = lo // P
    local = np.where(valid, pos - base[:, None] * P, 2 * P)
    assert local.max(initial=0) <= 2 * P and local.min(initial=0) >= 0
    wtiles = np.stack([base, base + 1], axis=1)
    return (wtiles.astype(np.int32), local.astype(np.int32),
            int(wtiles.max(initial=0)) + 1)


def _window_onehot(loc):
    """(tc, 128, 256) f32 0/1 select matrix from in-window offsets —
    the single layout contract both banded directions share (the
    scatter must be the exact transpose of the gather); the 256
    sentinel matches no column and so emits/contributes 0."""
    return (loc[:, :, None] ==
            jnp.arange(256, dtype=jnp.int32)[None, None, :]
            ).astype(jnp.float32)


def _banded_gather(xt, wtiles, local, chunk):
    """Exact banded permutation-gather: xt (nt_in, 128) f32 input
    tiles; returns (nt, 128) f32 with out[t, p] = xt window value at
    local[t, p] (0 at the sentinel).  The one-hot select runs as a
    batched (128, 256) @ (256, 1) HIGHEST-precision dot — products
    with an exact 0/1 operand reproduce f32 values."""
    nt = wtiles.shape[0]
    win = xt[wtiles.reshape(-1)].reshape(nt, 256)

    def body(args):
        loc, w = args
        return jax.lax.dot_general(
            _window_onehot(loc), w[:, :, None],
            (((2,), (1,)), ((0,), (0,))),
            precision=jax.lax.Precision.HIGHEST)[..., 0]

    nc = nt // chunk
    out = jax.lax.map(body, (local.reshape(nc, chunk, 128),
                             win.reshape(nc, chunk, 256)))
    return out.reshape(nt, 128)


def _banded_scatter(gh, wtiles, local, nt_in, chunk):
    """Exact transpose of :func:`_banded_gather`: gh (nt, 128, C)
    padded-order values; returns (nt_in, 128, C) flat tiles with each
    value added at its window position (windows of adjacent tiles
    overlap, so the per-tile transposed dots are combined by a
    tile-row scatter-add — 128-row payloads, not scalar rows)."""
    nt, _, C = gh.shape

    def body(args):
        loc, g = args
        # (tc, 256, C) = sum_p oh[t, p, w] * g[t, p, c]
        return jax.lax.dot_general(
            _window_onehot(loc), g, (((1,), (1,)), ((0,), (0,))),
            precision=jax.lax.Precision.HIGHEST)

    nc = nt // chunk
    parts = jax.lax.map(body, (local.reshape(nc, chunk, 128),
                               gh.reshape(nc, chunk, 128, C)))
    parts = parts.reshape(nt * 2, 128, C)
    out = jnp.zeros((nt_in, 128, C), jnp.float32)
    return out.at[wtiles.reshape(-1)].add(parts, mode="drop")


# -- lambdarank's layout: queries in length buckets ----------------------
# Every row of the bucketed layout is RANK_ROW slots or a multiple of it,
# and holds a contiguous run of the flat rows: one query, or (queries of
# at most RANK_PACK rows) the short queries that start in one
# RANK_PACK-row stretch.  A 128-slot tile of it therefore reads a span of
# under 128 flat rows, which is what the banded permutation needs.
RANK_ROW = 128
RANK_PACK = 64
RANK_TILE = 512           # widest side of a pair tile
RANK_PAIR_BUDGET = 1 << 23   # pair slots one step of the pair loop holds


def _rank_grain(m):
    """Width granularity of a bucket whose longest query is ``m``."""
    return RANK_ROW if m <= 2048 else 4 * RANK_ROW


def _rank_ladder(longest):
    """Bucket limits: 128, then up by a quarter, each a multiple of its
    grain, until ``longest`` fits."""
    ladder = [RANK_ROW]
    while ladder[-1] < longest:
        w = ladder[-1] * 5 // 4
        ladder.append(-(-w // _rank_grain(w)) * _rank_grain(w))
    return np.asarray(ladder)


class _RankBucket:
    """One length bucket: ``rows`` rows of ``width`` slots starting at
    slot ``start`` of the bucketed vector; its pairs in tiles of
    ``tile`` x ``tile``, ``chunk`` rows a step."""

    def __init__(self, start, rows, width, packed):
        self.start, self.width, self.packed = start, width, packed
        self.tile = width if width <= RANK_TILE else next(
            t for t in (512, 384, 256, 128) if width % t == 0)
        self.k = width // self.tile
        chunk = min(rows, max(1, RANK_PAIR_BUDGET // self.tile ** 2))
        self.steps = -(-rows // chunk)
        self.chunk = -(-rows // self.steps)
        self.rows = self.steps * self.chunk
        self.size = self.rows * width


def rank_layout(qb: np.ndarray):
    """The length buckets of queries ``qb`` (boundaries, ascending), as
    (buckets, pos, seg): per slot of the bucketed vector the flat row it
    holds (-1: none) and that row's query (Q past the end).  Queries of
    one row make no pair and have no slot."""
    sizes = np.diff(qb).astype(np.int64)
    n, nq = int(qb[-1]), len(sizes)
    starts = qb[:-1].astype(np.int64)
    ranked = sizes >= 2
    groups = []                           # (packed, row bases, width)
    row_of = np.full(nq + 1, -1, np.int64)  # bucket row of each query
    rows = 0
    short = np.flatnonzero(ranked & (sizes <= RANK_PACK))
    if len(short):
        stretch, row = np.unique(starts[short] // RANK_PACK,
                                 return_inverse=True)
        row_of[short] = rows + row
        groups.append((True, stretch * RANK_PACK, RANK_ROW))
        rows += len(stretch)
    long_q = np.flatnonzero(ranked & (sizes > RANK_PACK))
    if len(long_q):
        ladder = _rank_ladder(int(sizes[long_q].max()))
        which = np.searchsorted(ladder, sizes[long_q])
        for j in np.unique(which):
            qs = long_q[which == j]
            m = int(sizes[qs].max())
            width = -(-m // _rank_grain(m)) * _rank_grain(m)
            row_of[qs] = rows + np.arange(len(qs))
            groups.append((False, starts[qs], width))
            rows += len(qs)
    buckets, pos, seg = [], [], []
    start = row0 = 0
    for packed, bases, width in groups:
        b = _RankBucket(start, len(bases), width, packed)
        p = bases[:, None] + np.arange(width)[None, :]
        q = np.searchsorted(qb, p, side="right") - 1
        q = np.where(p >= n, nq, q)
        mine = row_of[q] == (row0 + np.arange(len(bases)))[:, None]
        extra = ((0, b.rows - len(bases)), (0, 0))
        pos.append(np.pad(np.where(mine, p, -1), extra, constant_values=-1))
        seg.append(np.pad(q, extra, constant_values=nq))
        buckets.append(b)
        start += b.size
        row0 += len(bases)
    if not buckets:
        return [], np.zeros(0, np.int64), np.zeros(0, np.int64)
    cat = lambda parts: np.concatenate([x.reshape(-1) for x in parts])
    return buckets, cat(pos), cat(seg)


def inverse_max_dcg(qb, label, label_gain, k):
    """1 / (DCG at ``k`` of each query's labels in their best order), 0
    where that is 0 (reference dcg_calculator.cpp), without a loop over
    queries."""
    sizes = np.diff(qb)
    q = np.repeat(np.arange(len(sizes)), sizes)
    order = np.lexsort((-label, q))
    place = np.arange(len(q)) - qb[q]
    lab = label[order].astype(np.int64)
    top = place < k
    dcg = np.bincount(q[top], label_gain[lab[top]] / np.log2(place[top] + 2.0),
                      minlength=len(sizes))
    return np.where(dcg > 0, 1.0 / np.where(dcg > 0, dcg, 1.0), 0.0)


class LambdarankNDCG(Objective):
    """reference rank_objective.hpp:19-200: per-query pairwise lambdas
    with |ΔNDCG| weighting.

    The queries are grouped by length into buckets (``rank_layout``),
    each padded to its own width, so the pairs evaluated stay within a
    small factor of the sum of squared query lengths (gauge
    ``objective.rank_pair_padding``) whatever the longest query.  The
    scores move flat -> bucketed and the lambdas back by one static
    permutation each way, as banded one-hot products
    (:func:`_banded_take_plan`: exact, and ~6x cheaper than XLA's row
    gather on the chip).  A bucket's pairs are computed in tiles of at
    most ``RANK_TILE`` x ``RANK_TILE`` slots and ``RANK_PAIR_BUDGET``
    slots a step, so no intermediate grows with the longest query
    squared."""
    name = "lambdarank"
    need_accurate_prediction = False
    # pairwise lambdas are long-tailed: deterministic int8 rounding
    # zeroes most of them (measured 0.33 vs 0.64 NDCG@10 at the
    # MS-LTR bench shape) — stochastic rounding restores the signal
    need_stochastic_quant = True
    # a query's lambdas cancel, so the table's gradients sum to 0 and its
    # hessians are long-tailed: the quantization's residue, booked into
    # one leaf when the root's totals are exact, outweighs that leaf's
    # own sums (a leaf value 100-400 times the reference's at 40,000 rows
    # on the CPU seam: PERF.md section 6)
    quantized_root_totals = True

    def __init__(self, config: Config):
        super().__init__(config)
        self.sigmoid = config.sigmoid
        if self.sigmoid <= 0:
            Log.fatal("Sigmoid param should be greater than zero")
        label_gain = config.label_gain
        if not label_gain:
            label_gain = tuple(float(2 ** i - 1) for i in range(31))
        self.label_gain = np.asarray(label_gain, dtype=np.float64)
        self.optimize_pos_at = config.max_position

    def init(self, metadata, num_data):
        super().init(metadata, num_data)
        if metadata.query_boundaries is None:
            Log.fatal("Lambdarank tasks require query information")
        qb = np.asarray(metadata.query_boundaries, np.int64)
        self.num_queries = len(qb) - 1
        sizes = np.diff(qb)
        self.max_query = int(sizes.max(initial=0))
        if np.any(self.label < 0) or \
                int(self.label.max()) >= len(self.label_gain):
            Log.fatal("Label exceeds label_gain range in lambdarank")
        self._buckets, pos, seg = rank_layout(qb)
        inv = inverse_max_dcg(qb, self.label, self.label_gain,
                              self.optimize_pos_at)
        # per slot, static across trees: label (-1 where no row), its
        # gain, the query's inverse max DCG, the row's weight; its
        # query (Q past the end)
        mine = pos >= 0
        row = np.where(mine, pos, 0)
        lab = np.where(mine, self.label[row], -1.0)
        q = np.minimum(seg, self.num_queries - 1)
        cols = [lab, np.where(mine, self.label_gain[
                    np.maximum(lab, 0).astype(np.int64)], 0.0),
                np.where(mine, inv[q], 0.0)]
        if self.weight is not None:
            cols.append(np.where(mine, self.weight[row], 0.0))
        self._slot_f_dev = jnp.asarray(np.stack(cols).astype(np.float32))
        self._slot_seg_dev = jnp.asarray(seg[None].astype(np.int32))
        # banded flat <-> bucketed plan over the slots, chunk-aligned
        nt = max(1, -(-len(pos) // 128))
        self._tile_chunk = min(512, nt)
        nt = -(-nt // self._tile_chunk) * self._tile_chunk
        self._n_slots = len(pos)
        wtiles, local, self._bp_nt_in_min = _banded_take_plan(np.concatenate(
            [pos, np.full(nt * 128 - len(pos), -1, np.int64)]))
        self._bp_wtiles_dev = jnp.asarray(wtiles)
        self._bp_local_dev = jnp.asarray(local)
        pairs = float(np.sum(sizes.astype(np.float64) ** 2))
        evaluated = float(sum(b.rows * b.width ** 2 for b in self._buckets))
        self.pair_padding = evaluated / pairs if pairs else 1.0
        TELEMETRY.gauge("objective.rank_buckets", len(self._buckets))
        TELEMETRY.gauge("objective.rank_max_query", self.max_query)
        TELEMETRY.gauge("objective.rank_pair_padding", self.pair_padding)

    @staticmethod
    def _above(si, sj, i0, j0, ok):
        """Per i-slot of a tile, how many j-slots with ``ok`` rank above
        it: a higher score, or an equal one on an earlier row (the
        rank is stable in the row order).  ``i0``/``j0``: the tiles'
        first slots in the row."""
        t = si.shape[1]
        earlier = (j0 + jnp.arange(t))[None, None, :] \
            < (i0 + jnp.arange(t))[None, :, None]
        before = (sj[:, None, :] > si[:, :, None]) \
            | ((sj[:, None, :] == si[:, :, None]) & earlier)
        return jnp.sum(before & ok, axis=2, dtype=jnp.int32)

    def _pairs(self, si, sj, fi, fj, spread, ok):
        """One tile of pairs (i high, j low) of ``c`` rows: i-side and
        j-side slot values (``s`` (c, T); ``f`` label, gain, discount,
        inverse max DCG, each (c, T)); ``ok`` (c, T, T) where j is a
        slot of i's query.  Returns the lambda and hessian sums over j
        for each i and over i for each j."""
        li, gi, di, inv = fi
        lj, gj, dj, _ = fj
        x = lambda a: a[:, :, None]
        y = lambda a: a[:, None, :]
        ok = ok & (x(li) > y(lj))
        ds = x(si) - y(sj)
        dn = jnp.abs((x(gi) - y(gj)) * (x(di) - y(dj))) * x(inv)
        dn = jnp.where(x(spread), dn / (0.01 + jnp.abs(ds)), dn)
        p = 2.0 / (1.0 + jnp.exp(2.0 * self.sigmoid * ds))
        lam = jnp.where(ok, -p * dn, 0.0)
        hes = jnp.where(ok, 2.0 * p * (2.0 - p) * dn, 0.0)
        return lam.sum(2), lam.sum(1), hes.sum(2), hes.sum(1)

    def _bucket_lambdas(self, b, s, f, seg):
        """Gradients and hessians of bucket ``b``'s slots, (R, W) each,
        from its scores ``s`` and statics ``f`` (label, gain, inverse
        max DCG[, weight]; (C, R, W)) and ``seg`` (R, W).  A slot's rank
        in its query, and so its discount, is counted from the same
        pairs (no sort)."""
        R, W, T, c = b.rows, b.width, b.tile, b.chunk
        lab, gain, inv = f[0], f[1], f[2]
        disc = lambda above: 1.0 / jnp.log2(2.0 + above.astype(jnp.float32))
        if b.k == 1:
            def chunk(a):
                s_, lab_, gain_, inv_, seg_ = a
                ok = lab_[:, None, :] >= 0
                if b.packed:              # several queries a row
                    ok = ok & (seg_[:, :, None] == seg_[:, None, :])
                # whether the query's scores differ (best != worst)
                spread = jnp.any(ok & (s_[:, :, None] != s_[:, None, :]),
                                 axis=2)
                fi = (lab_, gain_, disc(self._above(s_, s_, 0, 0, ok)), inv_)
                gi, gj, hi, hj = self._pairs(s_, s_, fi, fi, spread, ok)
                return gi - gj, hi + hj
            g, h = jax.lax.map(chunk, tuple(
                a.reshape(b.steps, c, W) for a in (s, lab, gain, inv, seg)))
            g, h = g.reshape(R, W), h.reshape(R, W)
        else:
            # one query a row: its spread from the row, its ranks and
            # then its pairs a tile at a time
            k, valid = b.k, lab >= 0
            spread = jnp.broadcast_to(
                jnp.max(jnp.where(valid, s, -jnp.inf), 1, keepdims=True)
                != jnp.min(jnp.where(valid, s, jnp.inf), 1, keepdims=True),
                (R, W))

            def tiles(t):
                r0, ti, tj = t // (k * k) * c, t // k % k * T, t % k * T
                at = lambda a, j: jax.lax.dynamic_slice(a, (r0, j), (c, T))
                put = lambda a, j, v: jax.lax.dynamic_update_slice(
                    a, at(a, j) + v, (r0, j))
                ok = jnp.broadcast_to(at(lab, tj)[:, None, :] >= 0, (c, T, T))
                return at, put, ti, tj, ok

            def count(t, above):
                at, put, ti, tj, ok = tiles(t)
                return put(above, ti, self._above(at(s, ti), at(s, tj),
                                                  ti, tj, ok))
            d = disc(jax.lax.fori_loop(0, b.steps * k * k, count,
                                       jnp.zeros((R, W), jnp.int32)))

            def body(t, acc):
                g, h = acc
                at, put, ti, tj, ok = tiles(t)
                fi = tuple(at(a, ti) for a in (lab, gain, d, inv))
                fj = tuple(at(a, tj) for a in (lab, gain, d, inv))
                gi, gj, hi, hj = self._pairs(at(s, ti), at(s, tj), fi, fj,
                                             at(spread, ti), ok)
                return (put(put(g, ti, gi), tj, -gj),
                        put(put(h, ti, hi), tj, hj))
            zero = jnp.zeros((R, W), jnp.float32)
            g, h = jax.lax.fori_loop(0, b.steps * k * k, body, (zero, zero))
        if f.shape[0] > 3:
            g, h = g * f[3], h * f[3]
        return g, h

    def get_gradients(self, score):
        n = score.shape[0]
        nt = self._bp_local_dev.shape[0]
        nt_in = max(self._bp_nt_in_min, -(-n // 128))
        with TELEMETRY.phase("rank_layout"):
            xt = jnp.pad(score, (0, nt_in * 128 - n)).reshape(-1, 128)
            slots = _banded_gather(xt, self._bp_wtiles_dev,
                                   self._bp_local_dev, self._tile_chunk)
            slots = slots.reshape(-1)
        with TELEMETRY.phase("rank_lambdas"):
            out = [self._bucket_lambdas(b, *(
                a[..., b.start:b.start + b.size].reshape(
                    a.shape[:-1] + (b.rows, b.width))
                for a in (slots, self._slot_f_dev, self._slot_seg_dev[0])))
                for b in self._buckets]
        with TELEMETRY.phase("rank_layout"):
            pad = jnp.zeros(nt * 128 - self._n_slots, jnp.float32)
            gh = jnp.stack([jnp.concatenate([p[c].reshape(-1) for p in out]
                                            + [pad]) for c in (0, 1)], -1)
            flat = _banded_scatter(gh.reshape(nt, 128, 2), self._bp_wtiles_dev,
                                   self._bp_local_dev, nt_in, self._tile_chunk)
            flat = flat.reshape(-1, 2)[:n]
        return flat[:, 0], flat[:, 1]


_OBJECTIVE_REGISTRY = {
    "regression": RegressionL2,
    "regression_l1": RegressionL1,
    "huber": RegressionHuber,
    "fair": RegressionFair,
    "poisson": RegressionPoisson,
    "quantile": RegressionQuantile,
    "mape": RegressionMAPE,
    "gamma": RegressionGamma,
    "tweedie": RegressionTweedie,
    "binary": BinaryLogloss,
    "multiclass": MulticlassSoftmax,
    "multiclassova": MulticlassOVA,
    "cross_entropy": CrossEntropy,
    "cross_entropy_lambda": CrossEntropyLambda,
    "lambdarank": LambdarankNDCG,
}


def create_objective(config: Config) -> Optional[Objective]:
    """Factory (reference objective_function.cpp:10-80)."""
    if config.objective == "none":
        return None
    cls = _OBJECTIVE_REGISTRY.get(config.objective)
    if cls is None:
        Log.fatal(f"Unknown objective type name: {config.objective}")
    return cls(config)
