"""User-facing Dataset (lazy) and Booster re-export.

Mirrors the reference python package's basic.py: ``Dataset`` wraps raw
data and constructs the binned core dataset lazily when training starts
(reference: python-package/lightgbm/basic.py:572-1263 _lazy_init,
reference alignment for validation data), so bin mappers are fitted with
the final parameter set exactly once.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Union

import numpy as np

from .booster import Booster  # noqa: F401  (re-export)
from .config import Config
from .dataset import Dataset as CoreDataset
from .utils.log import Log


class Dataset:
    """Lazy dataset handle (the lgb.Dataset analog)."""

    def __init__(self, data, label=None, reference: Optional["Dataset"] = None,
                 weight=None, group=None, init_score=None,
                 feature_name: Union[str, Sequence[str]] = "auto",
                 categorical_feature: Union[str, Sequence] = "auto",
                 params: Optional[Dict[str, Any]] = None,
                 free_raw_data: bool = True):
        # free_raw_data defaults True like the reference python package
        # (the raw matrix is dead weight next to the binned copy once
        # construct has read it; construct keeps no copy of its own:
        # with free_raw_data=False ``_raw_data`` is the caller's array,
        # in the dtype it has).  Continued
        # training (init_model) needs the raw matrix to seed scores —
        # pass free_raw_data=False there, as in the reference.
        self.data = data
        self.label = label
        self.reference = reference
        self.weight = weight
        self.group = group
        self.init_score = init_score
        self.feature_name = feature_name
        self.categorical_feature = categorical_feature
        self.params = dict(params or {})
        self.free_raw_data = free_raw_data
        self._core: Optional[CoreDataset] = None

    # ------------------------------------------------------------------
    def construct(self, config: Optional[Config] = None) -> CoreDataset:
        if self._core is not None:
            return self._core
        if config is None:
            config = Config.from_params(self.params)
        from .telemetry import TELEMETRY
        # the entry-point stage of set-up's first half: every route
        # below runs in it, and its own time is what no stage under it
        # took (docs/OBSERVABILITY.md)
        with TELEMETRY.stage("construct") as stage:
            core = self._construct(config)
        if stage is not None and stage.wall_ms > 0:
            TELEMETRY.gauge("construct_rows_per_s",
                            round(core.num_data / stage.wall_ms * 1e3))
        return core

    def _construct(self, config: Config) -> CoreDataset:
        data = self.data
        label = self.label
        if isinstance(data, str):
            # the reference's DatasetLoader sniffs the binary token on
            # EVERY file load (dataset_loader.cpp LoadFromBinFile /
            # CheckCanLoadFromBin) — a saved binary cache must load
            # wherever a text file would
            from .dataset_io import is_binary_file, load_binary
            if is_binary_file(data):
                # the run's bin_packing intent is checked against the
                # cache's recorded storage layout (loud mismatch
                # refusal — see dataset_io._check_packing)
                self._core = load_binary(data, config=config)
                if self.label is not None:
                    self._core.metadata.set_label(self.label)
                if self.weight is not None:
                    self._core.metadata.set_weight(self.weight)
                if self.group is not None:
                    self._core.metadata.set_group(self.group)
                if self.init_score is not None:
                    self._core.metadata.set_init_score(self.init_score)
                if isinstance(self.feature_name, (list, tuple)):
                    self._core.feature_names = list(self.feature_name)
                return self._core
        if config.sharded_shards > 1 and self.reference is None \
                and config.sharded_cache_dir:
            # shard-cache v2 reload (the sharded analog of the binary-
            # token sniff above): a committed manifest short-circuits
            # parsing AND binning; world-size/fingerprint mismatches
            # refuse loudly inside the loader
            from .sharded import has_shard_cache, load_shard_cache
            if has_shard_cache(config.sharded_cache_dir):
                if self.group is not None:
                    # the fresh-construct route refuses query groups
                    # loudly — the cache-reload route must not let
                    # them vanish silently instead
                    Log.fatal("sharded construction does not support "
                              "query groups yet — drop group= or "
                              "sharded_shards")
                self._core = load_shard_cache(
                    config.sharded_cache_dir,
                    expect_world_size=config.sharded_shards,
                    config=config)
                if self.label is not None:
                    self._core.metadata.set_label(self.label)
                if self.weight is not None:
                    self._core.metadata.set_weight(self.weight)
                if self.init_score is not None:
                    self._core.metadata.set_init_score(self.init_score)
                if isinstance(self.feature_name, (list, tuple)):
                    self._core.feature_names = list(self.feature_name)
                self._core.pandas_categorical = None
                return self._core
        sharded_on = config.sharded_shards > 1 and self.reference is None
        streaming_ok = (isinstance(data, str)
                        and config.use_two_round_loading
                        and self.reference is None
                        and not sharded_on
                        and not isinstance(self.categorical_feature,
                                           (list, tuple)))
        if sharded_on and isinstance(data, str) \
                and config.use_two_round_loading:
            Log.warning("two_round loading is bypassed by sharded "
                        "construction: the file parses into one "
                        "in-RAM matrix before row-range splitting "
                        "(per-shard ingest still streams in "
                        "streaming_chunk_rows chunks)")
        if (isinstance(data, str) and config.use_two_round_loading
                and not streaming_ok and not sharded_on):
            Log.warning("two_round loading does not support reference-"
                        "aligned or explicitly-categorical datasets yet; "
                        "falling back to in-RAM loading")
        if streaming_ok:
            # two-round streaming: the float matrix never exists
            from .data_loader import load_file_streaming
            from .telemetry import TELEMETRY
            with TELEMETRY.stage("binning"):
                self._core = load_file_streaming(data, config)
            if isinstance(self.feature_name, (list, tuple)):
                self._core.feature_names = list(self.feature_name)
            if self.label is not None:
                self._core.metadata.set_label(self.label)
            if self.weight is not None:
                self._core.metadata.set_weight(self.weight)
            if self.group is not None:
                self._core.metadata.set_group(self.group)
            if self.init_score is not None:
                self._core.metadata.set_init_score(self.init_score)
            self._core.pandas_categorical = None
            return self._core
        if isinstance(data, str):
            from .data_loader import load_file
            data, label_from_file, extras = load_file(data, config)
            if label is None:
                label = label_from_file
            if self.weight is None and extras.get("weight") is not None:
                self.weight = extras["weight"]
            if self.group is None and extras.get("group") is not None:
                self.group = extras["group"]
            if self.categorical_feature == "auto" \
                    and extras.get("categorical_feature"):
                # CLI categorical_column= spec, resolved by the loader
                # into post-drop feature indices (reference
                # dataset_loader.cpp categorical_feature handling)
                self.categorical_feature = extras["categorical_feature"]
        ref_core = None
        if self.reference is not None:
            # the reference may be a lazy handle or an already
            # constructed core (Booster.add_valid aligns to the core)
            ref_core = self.reference.construct(config) \
                if hasattr(self.reference, "construct") \
                else self.reference
        if _is_row_shards(data):
            _note_input({a.dtype for a in data})
            return self._construct_row_shards(data, label, config, ref_core)
        # validation frames must encode pandas categoricals against the
        # TRAIN-time category lists (the reference aligns valid frames
        # to the train categories and errors on mismatch)
        train_cats = getattr(ref_core, "pandas_categorical", None)
        pandas_cats = (train_cats if train_cats is not None
                       else _pandas_categories(data))
        if isinstance(data, np.ndarray):
            # the caller's buffer, in the dtype it has: the binner reads
            # float32 and float64 as they are, so no copy of the table
            # is made here (from_matrix widens what it samples, and
            # ROW_BLOCK rows at a time of what the binner cannot read)
            _note_input({data.dtype})
        else:
            data = _to_matrix(data, train_cats)
            if _is_sparse(data) and not config.is_enable_sparse:
                # reference is_enable_sparse=false: bypass the
                # sparse-aware construction and bin the dense matrix
                data = np.ascontiguousarray(
                    np.asarray(data.todense(), dtype=np.float64))
            _note_input((), 0 if _is_sparse(data) else data.nbytes)
        feature_names, cat_indices = self._resolve_columns(data)

        from .telemetry import TELEMETRY
        if sharded_on and ref_core is None:
            # mesh-sharded construction (lightgbm_tpu/sharded/,
            # docs/Parallel-Learning-Guide.md "Sharded construction"):
            # distributed bin finding + per-shard streaming ingest;
            # reference-aligned (validation) datasets never shard —
            # they bin whole against the training mappers
            if _is_sparse(data):
                Log.warning("sharded_shards ignored for sparse input; "
                            "using the single-matrix sparse path")
            else:
                from .sharded import ShardedDataset, save_shard_cache
                with TELEMETRY.stage("binning", rows=int(data.shape[0])):
                    self._core = ShardedDataset.construct_sharded(
                        data, label=label, weight=self.weight,
                        group=self.group, init_score=self.init_score,
                        config=config,
                        categorical_features=cat_indices,
                        feature_names=feature_names)
                if config.sharded_cache_dir:
                    save_shard_cache(self._core,
                                     config.sharded_cache_dir)
                self._core._raw_data = None if self.free_raw_data \
                    else data
                self._core.pandas_categorical = pandas_cats
                if self.free_raw_data:
                    self.data = None
                return self._core
        with TELEMETRY.stage("binning", rows=int(data.shape[0])):
            # host-side bin-mapper fit + matrix binning — the one
            # pre-device phase of training, decomposed into the
            # fit_mappers/bin/pack sub-spans (docs/OBSERVABILITY.md)
            self._core = CoreDataset.from_matrix(
                data, label=label, weight=self.weight, group=self.group,
                init_score=self.init_score, config=config,
                categorical_features=cat_indices,
                feature_names=feature_names, reference=ref_core)
        self._core._raw_data = None if self.free_raw_data else data
        if self.free_raw_data and not _is_sparse(data) \
                and str(getattr(config, "quality", "off")).lower() \
                == "on":
            # quality=on + free_raw_data: the profile's leaf-occupancy
            # pass (pred_leaf) needs raw feature rows AFTER training,
            # but the float matrix dies right here — retain a
            # deterministic strided sample (quality_profile_rows cap)
            # instead of the whole matrix (docs/MODEL_MONITORING.md)
            from .quality.profile import strided_rows
            self._core._quality_row_sample = strided_rows(
                data, int(config.quality_profile_rows))
        self._core._categorical_features = cat_indices
        self._core.pandas_categorical = pandas_cats
        if self.free_raw_data:
            # drop the lazy handle's copy too (reference sets
            # Dataset.data = None after construction) — the binned
            # matrix is the training representation from here on
            self.data = None
        return self._core

    # ------------------------------------------------------------------
    def _construct_row_shards(self, shards, label, config, ref_core):
        """``data`` is a list of 2-D float arrays, the table's row
        shards in order: each is binned block-wise into its own uint8
        matrix (sharded.ShardedDataset.from_row_shards), so a table the
        host cannot hold twice (nor once as float64) still constructs.
        Trees are those of the concatenated matrix."""
        from .sharded import ShardedDataset
        from .telemetry import TELEMETRY
        if ref_core is not None or self.group is not None:
            Log.fatal("a Dataset given as row shards takes no reference "
                      "and no query groups yet — pass one matrix")
        feature_names, cat_indices = self._resolve_columns(shards[0])
        rows = sum(a.shape[0] for a in shards)
        with TELEMETRY.stage("binning", rows=rows):
            self._core = ShardedDataset.from_row_shards(
                shards, label=label, weight=self.weight,
                init_score=self.init_score, config=config,
                categorical_features=cat_indices,
                feature_names=feature_names)
        self._core._raw_data = None if self.free_raw_data \
            else np.concatenate(shards)
        self._core.pandas_categorical = None
        if self.free_raw_data:
            self.data = None
        return self._core

    # ------------------------------------------------------------------
    def _resolve_columns(self, data: np.ndarray):
        n_cols = data.shape[1]
        feature_names = None
        if isinstance(self.feature_name, (list, tuple)):
            feature_names = list(self.feature_name)
        elif _is_pandas(self.data):
            feature_names = [str(c) for c in self.data.columns]
        cat_indices = []
        cf = self.categorical_feature
        if isinstance(cf, (list, tuple)):
            for c in cf:
                if isinstance(c, str):
                    if feature_names and c in feature_names:
                        cat_indices.append(feature_names.index(c))
                    else:
                        Log.warning(f"Unknown categorical column {c}")
                else:
                    cat_indices.append(int(c))
        elif cf == "auto" and _is_pandas(self.data):
            for i, dtype in enumerate(self.data.dtypes):
                if str(dtype) == "category":
                    cat_indices.append(i)
        return feature_names, cat_indices

    # ------------------------------------------------------------------
    def set_label(self, label):
        self.label = label
        if self._core is not None:
            self._core.metadata.set_label(label)
        return self

    def set_weight(self, weight):
        self.weight = weight
        if self._core is not None:
            self._core.metadata.set_weight(weight)
        return self

    def set_group(self, group):
        self.group = group
        if self._core is not None:
            self._core.metadata.set_group(group)
        return self

    def set_init_score(self, init_score):
        self.init_score = init_score
        if self._core is not None:
            self._core.metadata.set_init_score(init_score)
        return self

    def set_field(self, name, data):
        if name == "label":
            return self.set_label(data)
        if name == "weight":
            return self.set_weight(data)
        if name in ("group", "query"):
            return self.set_group(data)
        if name == "init_score":
            return self.set_init_score(data)
        Log.fatal(f"Unknown field {name}")

    def get_field(self, name):
        if self._core is not None:
            return self._core.metadata.get_field(name)
        return {"label": self.label, "weight": self.weight,
                "group": self.group, "init_score": self.init_score}.get(name)

    def get_label(self):
        return self.get_field("label")

    def get_weight(self):
        return self.get_field("weight")

    def get_group(self):
        return self.get_field("group")

    def get_init_score(self):
        return self.get_field("init_score")

    def num_data(self) -> int:
        if self._core is not None:
            return self._core.num_data
        d = self.data
        if isinstance(d, str):
            Log.fatal("Cannot get num_data before construction of a "
                      "file-backed Dataset")
        if _is_sparse(d):
            return d.shape[0]
        if _is_row_shards(d):
            return sum(a.shape[0] for a in d)
        return _shape_of(d)[0]

    def num_feature(self) -> int:
        if self._core is not None:
            return self._core.num_total_features
        if _is_sparse(self.data):
            return self.data.shape[1]
        if _is_row_shards(self.data):
            return self.data[0].shape[1]
        return _shape_of(self.data)[1]

    def set_reference(self, reference: "Dataset") -> "Dataset":
        """reference basic.py Dataset.set_reference: align this
        dataset's bin mappers to another's.  Must precede construct."""
        if self._core is not None and reference is not self.reference:
            Log.fatal("Cannot set reference after the Dataset has "
                      "been constructed; create a new Dataset")
        self.reference = reference
        return self

    def set_feature_name(self, feature_name) -> "Dataset":
        """reference basic.py Dataset.set_feature_name."""
        if isinstance(feature_name, (list, tuple)):
            nf = None
            if self._core is not None:
                nf = self._core.num_total_features
            elif getattr(self.data, "ndim", 0) == 2:
                nf = self.data.shape[1]
            if nf is not None and len(feature_name) != nf:
                Log.fatal(f"Length of feature_name "
                          f"({len(feature_name)}) does not match the "
                          f"number of features ({nf})")
            if self._core is not None:
                self._core.feature_names = list(feature_name)
        self.feature_name = feature_name
        return self

    def set_categorical_feature(self, categorical_feature) -> "Dataset":
        """reference basic.py Dataset.set_categorical_feature — the
        categorical set shapes the bin mappers, so it cannot change
        after construction."""
        if self._core is not None and \
                categorical_feature != self.categorical_feature:
            Log.fatal("Cannot set categorical feature after the "
                      "Dataset has been constructed; create a new "
                      "Dataset")
        self.categorical_feature = categorical_feature
        return self

    def construct_aligned(self, ref_core, config) -> CoreDataset:
        """Construct with bins aligned to ``ref_core`` when nothing
        pinned the mappers yet — the reference package's
        train()/add_valid set_reference behavior.  Already-constructed
        or explicitly-referenced datasets are left alone (the
        bin-alignment gate in gbdt.add_valid rejects mismatches)."""
        if self._core is None and self.reference is None:
            self.reference = ref_core
        return self.construct(config)

    def get_ref_chain(self, ref_limit: int = 100) -> set:
        """reference basic.py Dataset.get_ref_chain: the set of
        datasets reachable through .reference links."""
        head = self
        chain = set()
        count = 0
        while count < ref_limit:
            chain.add(head)
            if head.reference is not None and head.reference not in chain:
                head = head.reference
                count += 1
            else:
                break
        return chain

    def subset(self, used_indices, params=None) -> "Dataset":
        if self.data is None:
            Log.fatal("Cannot subset: raw data was freed — construct "
                      "the Dataset with free_raw_data=False")
        if _is_sparse(self.data):
            data = self.data.tocsr()[used_indices]
        elif isinstance(self.data, np.ndarray):
            data = self.data[used_indices]      # in the dtype it has
        else:
            data = _to_matrix(self.data)[used_indices]
        label = (None if self.label is None
                 else np.asarray(self.label)[used_indices])
        weight = (None if self.weight is None
                  else np.asarray(self.weight)[used_indices])
        return Dataset(data, label=label, weight=weight,
                       feature_name=self.feature_name,
                       categorical_feature=self.categorical_feature,
                       params=params or self.params, reference=self)

    def create_valid(self, data, label=None, weight=None, group=None,
                     init_score=None, params=None) -> "Dataset":
        return Dataset(data, label=label, reference=self, weight=weight,
                       group=group, init_score=init_score,
                       feature_name=self.feature_name,
                       categorical_feature=self.categorical_feature,
                       params=params or self.params)

    def save_binary(self, filename: str) -> "Dataset":
        from .dataset_io import save_binary
        save_binary(self.construct(), filename)
        return self


def _is_pandas(obj) -> bool:
    return type(obj).__module__.startswith("pandas") and \
        hasattr(obj, "dtypes")


def _to_matrix(data, pandas_categorical=None) -> np.ndarray:
    """Raw input -> float64 matrix.  Pandas category-dtype columns
    encode as their category codes; when ``pandas_categorical`` (the
    train-time category lists, in categorical-column order) is given,
    codes are computed AGAINST THOSE categories so a predict-time frame
    with reordered or fewer observed categories maps identically
    (reference basic.py pandas_categorical handling); unseen categories
    become NaN."""
    if isinstance(data, np.ndarray):
        return np.ascontiguousarray(data.astype(np.float64, copy=False))
    if _is_pandas(data) and not hasattr(data, "columns"):
        # a Series: single row of raw features
        return np.ascontiguousarray(np.asarray(data, dtype=np.float64))
    if _is_pandas(data):
        import pandas as pd
        n_cat = sum(1 for c in data.columns
                    if str(data[c].dtype) == "category")
        if pandas_categorical is not None \
                and n_cat != len(pandas_categorical):
            raise ValueError(
                "train and valid/predict dataset categorical_feature do "
                f"not match: trained with {len(pandas_categorical)} "
                f"categorical columns, got {n_cat}")
        cols = []
        i_cat = 0
        for c in data.columns:
            col = data[c]
            if str(col.dtype) == "category":
                if pandas_categorical is not None:
                    cats = pandas_categorical[i_cat]
                    codes = pd.Categorical(
                        col, categories=cats).codes.astype(np.float64)
                    codes[codes < 0] = np.nan
                else:
                    codes = col.cat.codes.to_numpy().astype(np.float64)
                cols.append(codes)
                i_cat += 1
            else:
                cols.append(col.to_numpy().astype(np.float64))
        return np.ascontiguousarray(np.stack(cols, axis=1))
    if _is_sparse(data):
        # sparse stays sparse: Dataset construction bins CSC columns
        # directly and prediction densifies in bounded row chunks —
        # the whole-matrix float64 densify of a 100k x 10k 99%-sparse
        # input would be 8 GB for 80 MB of payload
        return data.tocsc()
    return np.ascontiguousarray(np.asarray(data, dtype=np.float64))


def _is_sparse(obj) -> bool:
    return hasattr(obj, "tocsc") and hasattr(obj, "nnz")


def _shape_of(data):
    """(rows, columns) of raw in-memory input, read off the object where
    it says (arrays, frames) — only what has no 2-D shape of its own (a
    list of rows, a Series) is converted to find out."""
    shape = getattr(data, "shape", None)
    if shape is not None and len(shape) == 2:
        return tuple(shape)
    return _to_matrix(data).shape


def _note_input(dtypes, converted_bytes: int = 0) -> None:
    """What ``construct`` was handed, and what it cost to read
    (docs/OBSERVABILITY.md): gauge ``construct_input_dtype`` is
    ``float32`` / ``float64`` for an array (or row shards) of that
    dtype, ``other`` for everything else; counter
    ``construct_widened_mb`` starts at the float64 matrix that input
    which is no array (a frame, a list of rows) was converted to, and
    grows by every block the binner has to widen."""
    from .telemetry import TELEMETRY
    names = {str(d) for d in dtypes}
    TELEMETRY.gauge("construct_input_dtype",
                    names.pop() if names in ({"float32"}, {"float64"})
                    else "other")
    TELEMETRY.add("construct_widened_mb", converted_bytes / 1e6)


def _is_row_shards(obj) -> bool:
    """A table handed over as its row shards: a list of 2-D arrays.  (A
    list of rows, which numpy reads as one matrix, is not.)"""
    return (isinstance(obj, (list, tuple)) and len(obj) > 0
            and all(isinstance(a, np.ndarray) and a.ndim == 2
                    for a in obj))


def _pandas_categories(data):
    """Category lists of category-dtype columns, in column order (the
    reference's pandas_categorical model attribute)."""
    if not _is_pandas(data):
        return None
    cats = [list(data[c].cat.categories) for c in data.columns
            if str(data[c].dtype) == "category"]
    return cats or None
