"""Distributed bin-mapper finding for sharded dataset construction.

The redesign of the reference ``DatasetLoader``'s networked bin-
boundary sync (reference: src/io/dataset_loader.cpp:523-605 local
find_bin + :828-886 serialized-mapper allgather, docs/Parallel-
Learning-Guide.md): before ANY participant bins a row, every
participant collects *boundary candidates* — the per-feature sampled
non-zero/NaN values of its own disjoint row range (the same sampling
contract the single-host fit uses, bin.cpp:207) — the candidates are
ALLGATHERED through the instrumented, fault-injectable host-collective
seam, merged DETERMINISTICALLY (participant-rank order, sample-row
offsets rebased into the merged sample space), and the merged sample
feeds the ONE threaded ``Dataset._fit_mappers`` path.  Every shard
therefore bins against IDENTICAL mappers, and — whenever the per-shard
quotas cover the full shards (small/medium datasets, every test) — the
merged fit is BYTE-EQUAL to a single-host fit on the concatenated
data, EFB bundles included (pinned by ``tests/test_sharded.py``).

The collective is the :class:`HostCollectives` backend for simulated
(in-process) participants — calls and payload bytes land in the
``collective_allgather_*`` telemetry counters exactly like every other
explicit collective — and callers with a real multi-host transport
inject their own gather (the ``LGBM_NetworkInitWithFunctions``
pattern).  The ``sharded.binfind`` fault seam fires once per
participant BEFORE its candidates enter the gather, so an injected
kill leaves no merged mappers behind.
"""
from __future__ import annotations

import hashlib
import pickle
from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..binning import BIN_CATEGORICAL, BinMapper
from ..config import Config
from ..data_loader import split_sample_columns
from ..parallel.collectives import HostCollectives
from ..reliability.faults import FAULTS
from ..telemetry import TELEMETRY
from ..utils.log import Log


class BoundaryCandidates:
    """One participant's contribution to distributed bin finding:
    per-feature sampled values + their row indices WITHIN the
    participant's sample, plus the sample/row counts the merge needs
    to rebase rows into the merged sample space."""

    __slots__ = ("rank", "num_rows", "sample_cnt", "vals", "rows")

    def __init__(self, rank: int, num_rows: int, sample_cnt: int,
                 vals: List[np.ndarray], rows: List[np.ndarray]):
        self.rank = rank
        self.num_rows = num_rows
        self.sample_cnt = sample_cnt
        self.vals = vals
        self.rows = rows


def shard_sample_quota(config: Optional[Config], world: int) -> int:
    """Per-participant sample budget: an explicit
    ``sharded_sample_per_shard``, else the single-host
    ``bin_construct_sample_cnt`` split evenly so the merged sample
    stays within the same budget."""
    cfg = config or Config()
    per = int(getattr(cfg, "sharded_sample_per_shard", 0) or 0)
    if per > 0:
        return per
    return max(1, int(cfg.bin_construct_sample_cnt) // max(1, world))


def collect_candidates(shard: np.ndarray, config: Optional[Config],
                       rank: int, world: int) -> BoundaryCandidates:
    """Sample this participant's row range and split it into
    per-feature boundary candidates (``split_sample_columns`` — the
    shared zeros-implicit sampling contract).  Shards at or under the
    quota contribute EVERY row (no RNG), which is what makes the
    merged fit byte-equal to the single-host fit; larger shards draw a
    sorted random subset under a rank-derived seed (the
    ``distributed.sample_local_rows`` idiom)."""
    FAULTS.fault_point("sharded.binfind")
    cfg = config or Config()
    shard = np.asarray(shard)
    n = shard.shape[0]
    quota = shard_sample_quota(cfg, world)
    with TELEMETRY.stage("sample", rows=int(n)):
        if n > quota:
            rng = np.random.RandomState(cfg.data_random_seed
                                        + 7919 * rank)
            idx = rng.choice(n, size=quota, replace=False)
            idx.sort()
            sample = shard[idx]
        else:
            sample = shard
        # only the sampled rows are widened, never the shard
        sample = np.asarray(sample, dtype=np.float64)
        vals, rows = split_sample_columns(sample)
    return BoundaryCandidates(rank, n, sample.shape[0], vals, rows)


def merge_candidates(cands: Sequence[BoundaryCandidates],
                     collective: Optional[HostCollectives] = None
                     ) -> Tuple[List[np.ndarray], List[np.ndarray], int]:
    """Allgather + deterministic merge: every per-feature candidate
    array crosses the collective seam (bytes counted per call, the
    reference's per-feature boundary sync), candidates concatenate in
    participant-RANK order, and sample-row indices rebase by the
    cumulative sample counts — so the merged (vals, rows, total) is
    exactly what a single host would have sampled from the
    concatenated row ranges.  Returns the merged per-feature values,
    rows and total sample count for ``Dataset._fit_mappers`` + the EFB
    bundler."""
    if not cands:
        raise ValueError("merge_candidates needs at least one "
                         "participant")
    cands = sorted(cands, key=lambda c: c.rank)
    hc = collective or HostCollectives(shards=len(cands))
    counts = hc.simulate_allgather(
        [np.asarray([c.sample_cnt], dtype=np.int64) for c in cands]
    ).ravel()
    offsets = np.concatenate([[0], np.cumsum(counts)])
    n_feat = len(cands[0].vals)
    for c in cands:
        if len(c.vals) != n_feat:
            raise ValueError(
                f"participant {c.rank} contributed {len(c.vals)} "
                f"feature columns, expected {n_feat} — shards must "
                "share one schema")
    vals: List[np.ndarray] = []
    rows: List[np.ndarray] = []
    for f in range(n_feat):
        vals.append(hc.simulate_allgather(
            [np.asarray(c.vals[f], dtype=np.float64) for c in cands]))
        rows.append(hc.simulate_allgather(
            [np.asarray(c.rows[f], dtype=np.int64) + offsets[i]
             for i, c in enumerate(cands)]))
    return vals, rows, int(counts.sum())


def gather_merge_remote(local_cand: BoundaryCandidates, transport
                        ) -> Tuple[List[np.ndarray], List[np.ndarray],
                                   int]:
    """Cross-PROCESS candidate gather: this participant's boundary
    candidates cross the TCP transport (one Bruck allgather of the
    pickled :class:`BoundaryCandidates` — wire bytes land in the
    ``collective_tcp_*`` counters), then the full set merges through
    the same deterministic rank-order :func:`merge_candidates` path
    the in-process participants use — so the merged (vals, rows,
    total) is byte-equal whether the shards live in one process or
    N (the ``LGBM_NetworkInitWithFunctions`` injected-gather pattern,
    finally over a real wire)."""
    cands = transport.allgather_obj(local_cand)
    return merge_candidates(cands)


def mapper_fingerprint(mappers: Sequence[BinMapper],
                       bundles: Optional[Sequence[Sequence[int]]] = None,
                       max_bin: int = 0) -> str:
    """sha256 identity of a fitted mapper set (+ EFB bundle layout):
    the byte-level contract two shards (or a shard cache and its
    loader) must agree on before their bin matrices are comparable.
    Canonicalized field-by-field so lazily-built caches (the
    categorical LUT) never perturb the digest."""
    h = hashlib.sha256()
    h.update(f"max_bin={int(max_bin)};".encode())
    for m in mappers:
        h.update(f"{m.bin_type}|{m.num_bin}|{m.missing_type}|"
                 f"{m.default_bin}|{int(m.is_trivial)}|"
                 f"{m.min_val!r}|{m.max_val!r};".encode())
        bub = getattr(m, "bin_upper_bound", None)
        if bub is not None:
            h.update(np.ascontiguousarray(
                np.asarray(bub, dtype=np.float64)).tobytes())
        cat = getattr(m, "categorical_2_bin", None)
        if cat:
            h.update(pickle.dumps(sorted(cat.items()), protocol=4))
        h.update(b"\x00")
    if bundles is not None:
        h.update(pickle.dumps([list(b) for b in bundles], protocol=4))
    return h.hexdigest()


def warn_if_quota_truncated(cands: Sequence[BoundaryCandidates]) -> bool:
    """True (with one loud warning) when any participant subsampled —
    merged mappers are then still identical on every shard, but no
    longer byte-equal to a whole-data single-host fit (same caveat as
    the reference's sampled GreedyFindBin)."""
    truncated = [c.rank for c in cands if c.sample_cnt < c.num_rows]
    if truncated:
        Log.warning(
            "sharded bin finding subsampled participant(s) "
            f"{truncated}: merged mappers are deterministic and "
            "identical on every shard, but reflect the sample, not "
            "the full rows — byte-equality with a whole-data "
            "single-host fit does not hold at this scale "
            "(bin_construct_sample_cnt / sharded_sample_per_shard)")
    return bool(truncated)
