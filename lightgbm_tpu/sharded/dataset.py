"""ShardedDataset: the mesh-sharded training matrix.

End-to-end sharded data plane (ROADMAP item 1): instead of ONE
host-resident packed ``(N, G)`` uint8 matrix (``dataset.py``), the
training rows are split into disjoint contiguous participant ranges,
bin mappers are fitted DISTRIBUTED (``binfind.py`` — per-range
boundary candidates allgathered and deterministically merged, the
reference ``DatasetLoader`` bin-boundary sync), and each range is
stream-ingested through the r11 two-round push protocol
(``Dataset.from_reference_for_push`` + chunked ``push_rows``) into its
OWN per-shard bin matrix.  The grower places the shards straight onto
their mesh devices (``ShardingPolicy.place_row_shards`` — the host
never materializes the concatenated matrix on the mesh path) and the
data-parallel histogram allreduce rides the same collective seams the
single-matrix route compiles to, so trees are BYTE-IDENTICAL across
the two routes (tests/test_sharded.py, the ``sharded_construct``
MULTICHIP gate).

Host peak memory is samples + one streaming chunk + the per-shard
uint8 matrices (the LiteMORT rows-per-chip argument, PAPERS.md arxiv
2001.09419): sharding buys capacity per participant, not just per
fleet.  The shard-cache v2 (``cache.py``) persists the shards +
manifest for zero-copy reload.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from ..config import Config
from ..dataset import Dataset as CoreDataset
from ..dataset import Metadata, bin_row_blocks
from ..reliability.faults import FAULTS
from ..reliability.watchdog import run_with_deadline
from ..telemetry import TELEMETRY
from ..utils.log import Log
from . import binfind


def shard_row_ranges(num_data: int, num_shards: int
                     ) -> List[Tuple[int, int]]:
    """Disjoint contiguous [start, stop) participant ranges covering
    ``num_data`` rows — ``np.array_split`` semantics (first
    ``num_data % num_shards`` shards one row longer), deterministic."""
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    bounds = np.linspace(0, num_data, num_shards + 1).astype(np.int64)
    return [(int(bounds[i]), int(bounds[i + 1]))
            for i in range(num_shards)]


class ShardedDataset(CoreDataset):
    """A constructed dataset whose packed bin matrix lives as
    per-participant row shards (``shard_bins``) instead of one
    ``group_bins`` array.  All mapper/feature/group metadata is the
    merged-fit result shared by every shard; ``metadata`` is the
    GLOBAL view (labels/weights in original row order)."""

    def __init__(self):
        super().__init__()
        self.shard_bins: List[np.ndarray] = []
        self.shard_ranges: List[Tuple[int, int]] = []
        self.world_size = 0
        self.bin_fingerprint = ""

    # engine.train / Booster accept lazy datasets and call construct()
    # — a ShardedDataset is already constructed
    def construct(self, config: Optional[Config] = None
                  ) -> "ShardedDataset":
        return self

    def construct_aligned(self, ref_core, config) -> "ShardedDataset":
        return self

    def assembled_group_bins(self) -> np.ndarray:
        """The concatenated (N, G) matrix — parity checks and the
        no-mesh fallback only; the mesh training path never calls
        this (shards go to devices individually)."""
        return np.concatenate(self.shard_bins, axis=0)

    # ------------------------------------------------------------------
    @classmethod
    def construct_sharded(cls, data, label=None, weight=None,
                          group=None, init_score=None,
                          config: Optional[Config] = None,
                          num_shards: Optional[int] = None,
                          categorical_features: Optional[Sequence[int]]
                          = None,
                          feature_names: Optional[Sequence[str]] = None,
                          collective=None) -> "ShardedDataset":
        """Build the sharded dataset from an in-memory float matrix
        (or a text file path, parsed through the standard loader).

        1. rows split into ``num_shards`` (default
           ``config.sharded_shards``) disjoint contiguous ranges;
        2. distributed bin finding: per-range boundary candidates ->
           instrumented allgather -> deterministic merge -> the ONE
           threaded ``_fit_mappers`` path (+ EFB bundling) — identical
           mappers on every shard, byte-equal to a single-host fit
           whenever the quotas cover the shards;
        3. per-shard streaming ingest (``from_reference_for_push`` +
           ``streaming_chunk_rows`` chunked pushes) into per-shard bin
           matrices, behind the ``sharded.ingest`` fault seam.
        """
        config = config or Config()
        if isinstance(data, str):
            from ..data_loader import load_file
            data, label_from_file, extras = load_file(data, config)
            if label is None:
                label = label_from_file
            if weight is None:
                weight = extras.get("weight")
            if group is None:
                group = extras.get("group")
            if categorical_features is None \
                    and extras.get("categorical_feature"):
                categorical_features = extras["categorical_feature"]
        if hasattr(data, "tocsc") and hasattr(data, "nnz"):
            Log.fatal("sharded construction does not take sparse "
                      "input yet — densify, or use the single-matrix "
                      "sparse path (sharded_shards=0)")
        if group is not None:
            Log.fatal("sharded construction does not support query "
                      "groups yet — queries must not span shards "
                      "(same bound as multi-host ranking)")
        # in the dtype it arrived in: bin finding widens the rows it
        # samples, push_rows bins (or widens) ROW_BLOCK rows at a time
        X = np.asarray(data)
        if X.ndim != 2:
            raise ValueError("data must be 2-dimensional")
        num_data, num_features = X.shape
        world = int(num_shards if num_shards is not None
                    else getattr(config, "sharded_shards", 0) or 0)
        if world < 1:
            raise ValueError(
                "construct_sharded needs num_shards >= 1 (or "
                "sharded_shards set in the config)")
        if world > max(1, num_data):
            # a hard error, not a silent clamp: a clamped world size
            # would commit a shard cache whose manifest disagrees with
            # the UNCHANGED config on the very next run
            Log.fatal(f"sharded_shards={world} exceeds the {num_data} "
                      "data rows — lower sharded_shards (every "
                      "participant needs at least one row)")
        ranges = shard_row_ranges(num_data, world)

        self = cls()
        self.config = config
        self.num_data = num_data
        self.num_total_features = num_features
        self.max_bin = config.max_bin
        self.world_size = world
        self.shard_ranges = ranges
        self.feature_names = list(feature_names) if feature_names else [
            f"Column_{i}" for i in range(num_features)]
        cat_set = set(categorical_features or [])

        # degraded-mode continuation (docs/RELIABILITY.md): with
        # sharded_allow_degraded on, a participant whose binfind or
        # ingest seam dies — or hangs past watchdog_collective_s —
        # is EXCLUDED and construction restarts on the surviving
        # participants' rows with quota-rebalanced shards (byte-
        # identical to a from-scratch run on the surviving world,
        # because it IS one).  Default off = today's fail-fast.  The
        # per-participant deadline only arms in degraded mode: in
        # fail-fast mode a long ingest must not spuriously stall-error
        # under a deadline sized for collective ops.
        allow_degraded = bool(getattr(config, "sharded_allow_degraded",
                                      False))
        part_deadline = float(getattr(config, "watchdog_collective_s",
                                      0.0) or 0.0) \
            if allow_degraded else 0.0

        # ---- distributed bin finding (binfind.py) ----
        with TELEMETRY.span("shard_binfind", shards=world,
                            rows=num_data):
            cands = []
            dead: List[int] = []
            for i, (a, b) in enumerate(ranges):
                try:
                    cands.append(run_with_deadline(
                        binfind.collect_candidates, part_deadline,
                        "shard_binfind", "sharded.binfind",
                        X[a:b], config, rank=i, world=world))
                except Exception as e:  # noqa: BLE001 - mode decides
                    if not allow_degraded:
                        raise
                    Log.warning(
                        f"sharded participant {i} FAILED during bin "
                        f"finding ({type(e).__name__}: {e}) — "
                        "excluding it (sharded_allow_degraded=true)")
                    dead.append(i)
            if dead:
                return cls._construct_degraded(
                    X, label, weight, init_score, config, ranges,
                    dead, categorical_features, feature_names,
                    collective)
            binfind.warn_if_quota_truncated(cands)
            sample_vals, sample_rows, total_sample = \
                binfind.merge_candidates(cands, collective)
            self.mappers = self._fit_mappers(sample_vals, total_sample,
                                             config, cat_set)
        self.used_features = [i for i, m in enumerate(self.mappers)
                              if not m.is_trivial]
        if not self.used_features:
            Log.warning("There are no meaningful features; "
                        "all features are constant or filtered")
        self._build_groups(reference=None, sample_nonzero=sample_rows,
                           sample_cnt=total_sample)
        self._categorical_features = list(categorical_features or [])
        self._resolve_monotone(config)
        self.bin_fingerprint = binfind.mapper_fingerprint(
            self.mappers, self._bundles, self.max_bin)

        # ---- per-shard streaming ingest ----
        chunk_rows = max(1, int(config.streaming_chunk_rows))
        for i, (a, b) in enumerate(ranges):
            def _ingest(a=a, b=b):
                FAULTS.fault_point("sharded.ingest")
                sd = CoreDataset.from_reference_for_push(self, b - a)
                for start in range(0, b - a, chunk_rows):
                    stop = min(b - a, start + chunk_rows)
                    sd.push_rows(X[a + start:a + stop], start)
                sd.finish_load()
                return sd
            try:
                with TELEMETRY.span("shard_ingest", shard=i,
                                    rows=b - a):
                    sd = run_with_deadline(
                        _ingest, part_deadline, "shard_ingest",
                        "sharded.ingest")
            except Exception as e:  # noqa: BLE001 - mode decides
                if not allow_degraded:
                    raise
                Log.warning(
                    f"sharded participant {i} FAILED during ingest "
                    f"({type(e).__name__}: {e}) — excluding it "
                    "(sharded_allow_degraded=true)")
                return cls._construct_degraded(
                    X, label, weight, init_score, config, ranges,
                    [i], categorical_features, feature_names,
                    collective, seam="sharded.ingest")
            self.shard_bins.append(sd.group_bins)
            if TELEMETRY.on:
                TELEMETRY.add("sharded_rows_ingested", int(b - a))
        if TELEMETRY.on:
            TELEMETRY.gauge("sharded_world_size", world)

        self.metadata = Metadata(num_data)
        if label is not None:
            self.metadata.set_label(label)
        self.metadata.set_weight(weight)
        self.metadata.set_init_score(init_score)
        return self

    # ------------------------------------------------------------------
    @classmethod
    def from_row_shards(cls, shards, label=None, weight=None,
                        init_score=None, config: Optional[Config] = None,
                        categorical_features: Optional[Sequence[int]]
                        = None,
                        feature_names: Optional[Sequence[str]] = None
                        ) -> "ShardedDataset":
        """Build from a table that arrives AS row shards: a list of
        (rows_i, F) arrays, in row order (what ``lgb.Dataset([X0, X1,
        ...], label=y)`` constructs).  For tables a host cannot hold
        twice: neither the concatenated table nor a concatenated bin
        matrix exists on this route — each shard is binned in
        ``ROW_BLOCK``-row blocks (``dataset.bin_row_blocks``, the
        one-matrix route's binner) into its own uint8 matrix, which
        ``ShardingPolicy.place_row_shards`` puts straight on the mesh.

        The bin mappers are fitted ONCE, from the rows the
        single-matrix route would sample out of the concatenation (same
        draw, same order), so mappers, bin matrix and trees are the
        single-matrix route's to the byte."""
        from ..data_loader import split_sample_columns
        config = config or Config()
        shards = [np.asarray(a) for a in shards]
        if not shards or any(a.ndim != 2 for a in shards) \
                or len({a.shape[1] for a in shards}) != 1:
            raise ValueError("row shards must be a non-empty list of "
                             "2-dimensional arrays of one width")
        starts = np.cumsum([0] + [a.shape[0] for a in shards])
        num_data, num_features = int(starts[-1]), shards[0].shape[1]

        self = cls()
        self.config = config
        self.num_data = num_data
        self.num_total_features = num_features
        self.max_bin = config.max_bin
        self.world_size = len(shards)
        self.shard_ranges = [(int(a), int(b))
                             for a, b in zip(starts[:-1], starts[1:])]
        self.feature_names = list(feature_names) if feature_names else [
            f"Column_{i}" for i in range(num_features)]

        # the sample dataset._sample_feature_values draws from one matrix
        with TELEMETRY.stage("sample", rows=num_data):
            sample_cnt = config.bin_construct_sample_cnt
            if num_data > sample_cnt:
                idx = np.random.RandomState(
                    config.data_random_seed).choice(
                        num_data, size=sample_cnt, replace=False)
                idx.sort()
            else:
                idx = np.arange(num_data)
            cuts = np.searchsorted(idx, starts)
            sample = np.concatenate(
                [np.asarray(a[idx[cuts[i]:cuts[i + 1]] - starts[i]],
                            dtype=np.float64)
                 for i, a in enumerate(shards)])
            sample_vals, sample_rows = split_sample_columns(sample)
        self.mappers = self._fit_mappers(sample_vals, sample.shape[0],
                                         config,
                                         set(categorical_features or []))
        self.used_features = [i for i, m in enumerate(self.mappers)
                              if not m.is_trivial]
        if not self.used_features:
            Log.warning("There are no meaningful features; "
                        "all features are constant or filtered")
        self._build_groups(reference=None, sample_nonzero=sample_rows,
                           sample_cnt=sample.shape[0])
        self._categorical_features = list(categorical_features or [])
        self._resolve_monotone(config)
        self.bin_fingerprint = binfind.mapper_fingerprint(
            self.mappers, self._bundles, self.max_bin)

        sds = [CoreDataset.from_reference_for_push(self, a.shape[0])
               for a in shards]
        with TELEMETRY.stage("bin", rows=num_data):
            bin_row_blocks([(sd, a, sd.group_bins)
                            for sd, a in zip(sds, shards)], config)
        self.shard_bins = [sd.group_bins for sd in sds]
        if TELEMETRY.on:
            TELEMETRY.add("sharded_rows_ingested", num_data)
            TELEMETRY.gauge("sharded_world_size", len(shards))

        self.metadata = Metadata(num_data)
        if label is not None:
            self.metadata.set_label(label)
        self.metadata.set_weight(weight)
        self.metadata.set_init_score(init_score)
        return self

    # ------------------------------------------------------------------
    @classmethod
    def _construct_degraded(cls, X, label, weight, init_score, config,
                            ranges, dead: List[int],
                            categorical_features, feature_names,
                            collective,
                            seam: str = "sharded.binfind"
                            ) -> "ShardedDataset":
        """Degraded-mode restart (``sharded_allow_degraded``): drop
        the dead participants' row ranges and rebuild FROM SCRATCH on
        the surviving rows with a quota-rebalanced world — the
        degraded dataset is literally a from-scratch construction on
        the surviving world, which is what makes its trees
        byte-identical to one (pinned by ``tests/test_chaos.py``).
        The excluded rows are LOST — logged loudly per participant
        and counted (``sharded_degraded_exclusions``) so the loss is
        never silent."""
        dead_set = set(dead)
        survivors = [i for i in range(len(ranges))
                     if i not in dead_set]
        if not survivors:
            Log.fatal(
                "sharded degraded mode: every participant failed — "
                "nothing left to continue on (replay the fault plan "
                "seed to reproduce)")
        lost_rows = sum(b - a for i, (a, b) in enumerate(ranges)
                        if i in dead_set)
        keep = np.concatenate([np.arange(a, b, dtype=np.int64)
                               for i, (a, b) in enumerate(ranges)
                               if i not in dead_set])

        def _slice(arr, what: str):
            if arr is None:
                return None
            arr = np.asarray(arr)
            if arr.ndim >= 1 and arr.shape[0] == X.shape[0]:
                return arr[keep]
            Log.fatal(
                f"sharded degraded mode cannot re-slice {what} of "
                f"shape {arr.shape} to the surviving "
                f"{len(keep)}-row world — disable "
                "sharded_allow_degraded or drop the metadata")

        if TELEMETRY.on:
            TELEMETRY.add("sharded_degraded_exclusions", len(dead))
            TELEMETRY.gauge("sharded_degraded_world", len(survivors))
        TELEMETRY.flight.dump(
            "sharded_degraded", seam=seam,
            excluded=sorted(dead_set), surviving=len(survivors),
            lost_rows=int(lost_rows))
        Log.warning(
            f"sharded DEGRADED continuation: excluded participant(s) "
            f"{sorted(dead_set)} ({lost_rows} rows lost), continuing "
            f"on the surviving {len(survivors)}-participant world "
            "with rebalanced sample quotas "
            "(sharded_allow_degraded=true; trees are byte-identical "
            "to a from-scratch run on the survivors)")
        return cls.construct_sharded(
            X[keep], label=_slice(label, "label"),
            weight=_slice(weight, "weight"),
            init_score=_slice(init_score, "init_score"),
            config=config, num_shards=len(survivors),
            categorical_features=categorical_features,
            feature_names=feature_names, collective=collective)
