"""Training-time reference profiles for model-quality observability.

A :class:`QualityProfile` freezes what "healthy" traffic looked like
when the model trained, in three distributions (the LiteMORT
compact-distribution observation, PAPERS.md arXiv 2001.09419: the
per-feature bin-occupancy profile characterizes a dataset):

- **Per-feature bin-occupancy histograms** — one ``np.bincount`` per
  group column of the ALREADY-BUILT (N, G) uint8 bin matrix, unpacked
  to per-feature bin space through the EFB offset layout: zero extra
  binning work at capture time.  Each feature also carries its frozen
  :class:`~lightgbm_tpu.binning.BinMapper` table
  (:meth:`BinMapper.to_state`), so serving-side monitors bin live rows
  into the SAME bin space without the training dataset.
- **Training prediction-score histogram** — the trained model's
  output-space predictions over the training rows (read from the
  boosting score cache, no predict pass), bucketed at equal-count
  quantile edges (the telemetry fixed-bucket machinery with
  profile-derived bounds; equal-count reference buckets are what makes
  score PSI well-conditioned).
- **Per-tree leaf-occupancy counts** — ``pred_leaf`` over a
  deterministic strided sample of the training rows for the first
  ``QUALITY_LEAF_TREES`` trees (falling back to the trees' training
  ``leaf_count`` when no raw rows survive construction, e.g. two-round
  streaming).

The profile is fingerprinted with the sha256 of the model text it was
built from and persisted as ``<model>.quality.json`` beside the model
file; monitors REFUSE a profile whose fingerprint does not match the
model they serve (a stale profile would page operators on phantom
drift).  Format documented in docs/MODEL_MONITORING.md.
"""
from __future__ import annotations

import hashlib
import json
import os
from typing import Dict, List, Optional

import numpy as np

from ..binning import BinMapper
from ..utils.log import Log

PROFILE_SCHEMA = 1
PROFILE_SUFFIX = ".quality.json"
# trees whose leaf occupancy is profiled/monitored (the leading trees
# carry the coarsest, most drift-sensitive structure; monitoring every
# tree of a 1000-tree ensemble would put a full host walk per sampled
# row on the serving box)
QUALITY_LEAF_TREES = 16
# equal-count quantile buckets for the prediction-score histogram
SCORE_BUCKETS = 16
# contiguous groups the fine-grained bin histograms are merged into
# before PSI: scoring PSI over max_bin=255 near-empty buckets has an
# expected value of ~B/N on IDENTICAL distributions (the classic
# small-sample bias — every empty-vs-one-count bucket contributes),
# so drift scores use <=16 equal-reference-mass groups, the standard
# PSI bucketing.  Deterministic from the reference alone and applied
# identically to both sides, so the comparison stays valid for
# categorical features too.
PSI_BUCKETS = 16
# smoothing floor for PSI: empty buckets would make ln(p/q) blow up;
# distributions with no empty bucket are unaffected (exactness pinned
# by tests/test_quality.py)
PSI_EPS = 1e-4


def psi(ref_counts, cur_counts, eps: float = PSI_EPS) -> float:
    """Population stability index between two aligned histograms:
    ``sum((q - p) * ln(q / p))`` over normalized bucket masses, with
    empty buckets floored at ``eps`` before renormalizing.  0 for
    identical distributions; the standard operating thresholds are
    ~0.1 (minor shift) and ~0.2 (action-worthy drift)."""
    r = np.asarray(ref_counts, dtype=np.float64).reshape(-1)
    c = np.asarray(cur_counts, dtype=np.float64).reshape(-1)
    if r.shape != c.shape:
        raise ValueError(f"psi needs aligned histograms, got "
                         f"{r.shape} vs {c.shape}")
    if r.sum() <= 0 or c.sum() <= 0:
        return 0.0
    p = np.clip(r / r.sum(), eps, None)
    p = p / p.sum()
    q = np.clip(c / c.sum(), eps, None)
    q = q / q.sum()
    return float(np.sum((q - p) * np.log(q / p)))


def psi_group_bounds(ref_counts, target: int = PSI_BUCKETS
                     ) -> np.ndarray:
    """Start indices (for ``np.add.reduceat``) splitting a
    fine-grained reference histogram into at most ``target``
    contiguous groups of roughly equal reference mass.  A function of
    the REFERENCE only — the monitor groups its online counts with
    the same bounds, so both sides aggregate identically."""
    r = np.asarray(ref_counts, dtype=np.float64).reshape(-1)
    n = len(r)
    total = r.sum()
    if n <= target or total <= 0:
        return np.arange(n, dtype=np.int64)
    # accumulate-and-cut (not quantile cuts): a bin that crosses the
    # per-group goal CLOSES its group, so a dominant bin (a zero-heavy
    # sparse feature with 95% of mass in its default bin) gets a group
    # of its own instead of swallowing every cut — quantile cuts would
    # collapse such a reference to ONE group and leave the monitor
    # permanently blind (PSI identically 0) on that feature
    goal = total / target
    bounds = [0]
    acc = 0.0
    for i in range(n - 1):
        acc += r[i]
        if acc >= goal and len(bounds) < target:
            bounds.append(i + 1)
            acc = 0.0
    return np.asarray(bounds, dtype=np.int64)


def psi_grouped(ref_counts, cur_counts, target: int = PSI_BUCKETS,
                eps: float = PSI_EPS) -> float:
    """PSI after merging both histograms into the reference's
    equal-mass groups — the drift score every monitor/report
    surface uses for feature and leaf histograms."""
    r = np.asarray(ref_counts, dtype=np.float64).reshape(-1)
    c = np.asarray(cur_counts, dtype=np.float64).reshape(-1)
    if r.shape != c.shape:
        raise ValueError(f"psi_grouped needs aligned histograms, got "
                         f"{r.shape} vs {c.shape}")
    if len(r) == 0:
        return 0.0
    b = psi_group_bounds(r, target)
    return psi(np.add.reduceat(r, b), np.add.reduceat(c, b), eps=eps)


def model_fingerprint(model_text: str) -> str:
    """sha256 of the model text — the identity a profile is bound to."""
    return hashlib.sha256(model_text.encode("utf-8")).hexdigest()


def strided_rows(data: np.ndarray, cap: int) -> np.ndarray:
    """Deterministic strided row sample: every ``ceil(n/cap)``-th row,
    at most ``cap`` rows, no RNG (a replay cuts identical rows)."""
    data = np.asarray(data)
    n = int(data.shape[0])
    if n <= cap:
        return np.array(data, copy=True)
    stride = int(np.ceil(n / cap))
    return np.array(data[::stride], copy=True)


def feature_bin_counts(core) -> Dict[int, np.ndarray]:
    """Per-feature bin-occupancy histograms from the already-built
    packed bin matrix: ONE ``np.bincount`` per group column, unpacked
    to per-feature bin space.

    Single-feature groups read directly (group bin == feature bin).
    Multi-feature EFB bundles follow the reference offset layout
    (feature bin ``b != default`` lives at group slot ``offset + b``,
    minus one when ``default_bin == 0``; the shared slot 0 plus every
    OTHER feature's slots are this feature's default mass).  Exact
    whenever the bundle is conflict-free — the EFB admission criterion
    — and the construction-time truth either way: these are counts of
    what the training kernels actually saw."""
    gb = np.asarray(core.group_bins)
    n = int(gb.shape[0])
    lay = getattr(core, "bin_layout", None)

    def group_col(g: int) -> np.ndarray:
        # nibble-packed storage (packing.py): a group's bin values
        # live in one nibble of its storage byte — extract before the
        # bincount so packed datasets profile identically to 8-bit
        # ones (pinned equal to the per-feature value_to_bin bincount
        # by tests/test_compact_bins.py)
        return lay.unpack_group(gb, g) if lay is not None else gb[:, g]

    group_counts = [
        np.bincount(group_col(g), minlength=int(core.group_num_bin[g]))
        .astype(np.int64)
        for g in range(core.num_groups)]
    out: Dict[int, np.ndarray] = {}
    for f in core.features:
        gc = group_counts[f.group]
        m = core.mappers[f.feature_idx]
        nb = int(m.num_bin)
        if not f.collapsed_default:
            out[f.feature_idx] = gc[:nb].copy()
            continue
        counts = np.zeros(nb, dtype=np.int64)
        if m.default_bin == 0:
            counts[1:] = gc[f.offset:f.offset + nb - 1]
        else:
            counts[:] = gc[f.offset:f.offset + nb]
            counts[m.default_bin] = 0
        counts[m.default_bin] = n - int(counts.sum())
        out[f.feature_idx] = counts
    return out


def score_edges(scores: np.ndarray, buckets: int = SCORE_BUCKETS
                ) -> List[float]:
    """Equal-count quantile edges (interior bounds, ascending,
    deduplicated) for the prediction-score histogram — each reference
    bucket holds ~1/buckets of the training mass, the standard PSI
    bucketing.  Deterministic: pure quantiles, no RNG."""
    s = np.asarray(scores, dtype=np.float64).reshape(-1)
    s = s[np.isfinite(s)]
    if s.size == 0:
        return [0.0]
    qs = np.linspace(0.0, 1.0, buckets + 1)[1:-1]
    edges = np.unique(np.quantile(s, qs))
    if edges.size == 0:
        edges = np.asarray([float(s[0])])
    return [float(e) for e in edges]


def score_counts(scores: np.ndarray, edges) -> np.ndarray:
    """Bucket ``scores`` at ``edges`` with the telemetry histograms'
    ``le`` semantics (``searchsorted side="left"`` == ``bisect_left``):
    bucket i counts values <= edges[i], trailing slot is +Inf."""
    s = np.asarray(scores, dtype=np.float64).reshape(-1)
    idx = np.searchsorted(np.asarray(edges, dtype=np.float64), s,
                          side="left")
    return np.bincount(idx, minlength=len(edges) + 1).astype(np.int64)


def training_scores(booster) -> np.ndarray:
    """The trained model's OUTPUT-SPACE predictions over the training
    rows, read from the boosting score cache (no predict pass; the
    cache already carries init score + every tree).  FALLBACK source:
    the cache accumulates in float32 while serving observes the
    float64 predict path, so ties at quantile edges bucket slightly
    differently — when raw rows survive construction the profile
    prefers a real ``predict`` over the strided sample (same code
    path serving monitors observe, zero systematic skew)."""
    g = booster.gbdt
    if g is None:
        raise ValueError("quality profile needs the training session "
                         "(capture before free_dataset)")
    raw = np.asarray(g.scores[:, :g.num_data], dtype=np.float64).T
    k = max(booster.num_tree_per_iteration, 1)
    if booster.average_output:
        raw = raw / max(1, len(booster.models) // k)
        return raw.reshape(-1)
    return np.asarray(booster._convert_output(raw)).reshape(-1)


class ProfileMismatch(ValueError):
    """The profile's fingerprint does not match the model it was asked
    to monitor — refusing beats paging operators on phantom drift."""


class QualityProfile:
    """The serialized reference: per-feature mapper tables + bin
    counts, the score histogram (edges + counts), per-tree leaf
    occupancy, and the model fingerprint binding it all."""

    def __init__(self, fingerprint: str, num_rows: int,
                 features: Dict[int, dict], score: dict, leaves: dict,
                 feature_names: Optional[List[str]] = None):
        self.schema = PROFILE_SCHEMA
        self.fingerprint = fingerprint
        self.num_rows = int(num_rows)
        # {real feature index: {"name", "mapper" (BinMapper state),
        #  "counts"}}
        self.features = features
        self.score = score      # {"edges", "counts", "space"}
        self.leaves = leaves    # {"trees", "counts", "source",
        #                         "sample_rows"}
        self.feature_names = list(feature_names or [])
        self._mappers: Optional[Dict[int, BinMapper]] = None

    # ------------------------------------------------------------------
    def mappers(self) -> Dict[int, BinMapper]:
        """Frozen BinMapper objects rebuilt from the carried state
        (cached) — what serving monitors bin live rows through."""
        if self._mappers is None:
            self._mappers = {
                j: BinMapper.from_state(rec["mapper"])
                for j, rec in self.features.items()}
        return self._mappers

    def verify(self, model_text: str) -> None:
        """Raise :class:`ProfileMismatch` unless this profile was built
        from exactly ``model_text``."""
        got = model_fingerprint(model_text)
        if got != self.fingerprint:
            raise ProfileMismatch(
                "quality profile fingerprint mismatch: profile was "
                f"built from model {self.fingerprint[:12]}…, asked to "
                f"monitor model {got[:12]}… — regenerate the profile "
                "(train with quality=on) or drop the stale "
                f"{PROFILE_SUFFIX} file")

    # ------------------------------------------------------------------
    def to_dict(self) -> dict:
        return {
            "schema": self.schema,
            "fingerprint": self.fingerprint,
            "num_rows": self.num_rows,
            "feature_names": self.feature_names,
            "features": {
                str(j): {"name": rec.get("name", f"Column_{j}"),
                         "mapper": rec["mapper"],
                         "counts": [int(c) for c in rec["counts"]]}
                for j, rec in self.features.items()},
            "score": {"edges": [float(e).hex()
                                for e in self.score["edges"]],
                      "counts": [int(c) for c in self.score["counts"]],
                      "space": self.score.get("space", "output"),
                      "source": self.score.get("source",
                                               "predict_sample")},
            "leaves": {"trees": int(self.leaves["trees"]),
                       "source": self.leaves.get("source", "pred_leaf"),
                       "sample_rows": int(self.leaves.get(
                           "sample_rows", 0)),
                       "counts": [[int(c) for c in t]
                                  for t in self.leaves["counts"]]},
        }

    def save(self, path: str) -> str:
        """Atomic write of the JSON profile — through the shared
        reliability writer (tmp + fsync + rename + dir-fsync), the
        one place torn-write semantics are maintained."""
        from ..reliability.checkpoint import atomic_write_text
        d = os.path.dirname(os.path.abspath(path))
        if d:
            os.makedirs(d, exist_ok=True)
        atomic_write_text(path, json.dumps(self.to_dict(), indent=1,
                                           sort_keys=True))
        return path

    @classmethod
    def from_dict(cls, d: dict) -> "QualityProfile":
        if d.get("schema") != PROFILE_SCHEMA:
            raise ValueError(
                f"quality profile schema {d.get('schema')!r} not "
                f"readable by this build (expects {PROFILE_SCHEMA})")
        features = {
            int(j): {"name": rec.get("name", f"Column_{j}"),
                     "mapper": rec["mapper"],
                     "counts": np.asarray(rec["counts"], dtype=np.int64)}
            for j, rec in d["features"].items()}
        score = {
            "edges": [float.fromhex(e) if isinstance(e, str)
                      else float(e) for e in d["score"]["edges"]],
            "counts": np.asarray(d["score"]["counts"], dtype=np.int64),
            "space": d["score"].get("space", "output"),
            "source": d["score"].get("source", "predict_sample"),
        }
        leaves = {
            "trees": int(d["leaves"]["trees"]),
            "source": d["leaves"].get("source", "pred_leaf"),
            "sample_rows": int(d["leaves"].get("sample_rows", 0)),
            "counts": [np.asarray(t, dtype=np.int64)
                       for t in d["leaves"]["counts"]],
        }
        return cls(d["fingerprint"], int(d.get("num_rows", 0)),
                   features, score, leaves,
                   feature_names=d.get("feature_names"))

    @classmethod
    def load(cls, path: str) -> "QualityProfile":
        with open(path) as f:
            return cls.from_dict(json.load(f))


def profile_path(model_path: str) -> str:
    return model_path + PROFILE_SUFFIX


def load_profile_for(model_path: str) -> Optional[QualityProfile]:
    """The profile persisted beside ``model_path``, or None.  A
    corrupt/unreadable sidecar warns and is treated as absent."""
    path = profile_path(model_path)
    if not os.path.exists(path):
        return None
    try:
        return QualityProfile.load(path)
    except (ValueError, KeyError, OSError) as e:
        Log.warning(f"quality profile {path} unreadable "
                    f"({type(e).__name__}: {e}); serving without "
                    "drift monitors")
        return None


def _leaf_reference(booster, sample: Optional[np.ndarray]) -> dict:
    """Per-tree leaf-occupancy reference for the first
    ``QUALITY_LEAF_TREES`` trees: ``pred_leaf`` over the strided
    training sample when raw rows are available, else each tree's
    training ``leaf_count`` (exact over ALL training rows — streaming
    constructions never materialize the raw matrix)."""
    models = booster.models[:QUALITY_LEAF_TREES]
    if sample is not None and len(sample):
        counts = [
            np.bincount(np.asarray(t.predict_leaf(sample),
                                   dtype=np.int64),
                        minlength=t.num_leaves).astype(np.int64)
            for t in models]
        return {"trees": len(models), "counts": counts,
                "source": "pred_leaf", "sample_rows": int(len(sample))}
    counts = [np.asarray(t.leaf_count, dtype=np.int64).copy()
              for t in models]
    return {"trees": len(models), "counts": counts,
            "source": "leaf_count", "sample_rows": 0}


def build_profile(booster, core, config=None) -> QualityProfile:
    """Capture the reference :class:`QualityProfile` for ``booster``
    trained on ``core`` (the constructed training dataset).  Called by
    ``engine.train`` under ``quality=on``, before the training state
    is released; wrapped in the ``quality_profile`` telemetry span."""
    from ..telemetry import TELEMETRY
    span = TELEMETRY.start_span("quality_profile",
                                rows=int(core.num_data))
    try:
        return _build_profile_impl(booster, core, config)
    finally:
        TELEMETRY.end_span(span)


def _build_profile_impl(booster, core, config) -> QualityProfile:
    if getattr(core, "group_bins", None) is None:
        # sharded constructions keep group_bins=None (the grower takes
        # the per-participant shard list) — per-shard profile capture
        # is future work; engine.train turns this into a warning
        raise ValueError(
            "quality profile capture needs the packed bin matrix; "
            "this dataset has none (sharded construction?)")
    booster._sync_models()
    text = booster.model_to_string()
    feat_counts = feature_bin_counts(core)
    features: Dict[int, dict] = {}
    names = core.feature_names or []
    for f in core.features:
        j = f.feature_idx
        features[j] = {
            "name": names[j] if j < len(names) else f"Column_{j}",
            "mapper": core.mappers[j].to_state(),
            "counts": feat_counts[j],
        }
    cap = int(getattr(config, "quality_profile_rows", 4096) or 4096) \
        if config is not None else 4096
    raw = getattr(core, "_raw_data", None)
    if raw is None:
        raw = getattr(core, "_quality_row_sample", None)
    sample = None
    if raw is not None and not (hasattr(raw, "tocsc")
                                and hasattr(raw, "nnz")):
        # the rows first, then float64: never a copy of the raw table
        sample = strided_rows(raw, cap).astype(np.float64, copy=False)
    if sample is not None and len(sample):
        # same predict path the serving monitors observe — no
        # f32-cache-vs-f64-walk tie skew at the quantile edges
        scores = np.asarray(booster.predict(sample)).reshape(-1)
        score_source = "predict_sample"
    else:
        scores = training_scores(booster)
        score_source = "score_cache"
    edges = score_edges(scores)
    score = {"edges": edges, "counts": score_counts(scores, edges),
             "space": "output", "source": score_source}
    leaves = _leaf_reference(booster, sample)
    return QualityProfile(model_fingerprint(text), core.num_data,
                          features, score, leaves,
                          feature_names=list(core.feature_names or []))
