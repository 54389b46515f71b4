"""Round-11 construction-pipeline parity gate.

The parallel dataset-construction pipeline (threaded bin-mapper fit,
native categorical/EFB binning, overlapped two-round streaming, binary
cache v2) carries a byte-identity guarantee against the serial Python
path: ``group_bins`` must be EXACTLY equal — and therefore trained
trees byte-identical — for every construction route and every
``construct_threads`` setting, across dense/CSC/categorical/EFB
shapes including the ``collapsed_default`` bundle and NaN /
zero-as-missing corners.  ``construct_threads=1`` +
``native_binning=false`` reproduces the pre-r11 serial behavior by
construction; everything else is checked against it here.
"""
import os
import struct

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.dataset_io import (BINARY_TOKEN, MAGIC_V2, load_binary,
                                     save_binary)
from lightgbm_tpu.utils.log import LightGBMError


def _mixed_matrix(n=2500, seed=3):
    """Dense matrix exercising every feature class at once: numerical
    with NaN + zeros, two categorical columns (incl. an all-small one),
    and eight mutually-exclusive sparse columns that EFB packs into
    multi-feature bundles with collapsed defaults."""
    rng = np.random.RandomState(seed)
    X = np.zeros((n, 14))
    for j in range(8):                      # EFB bundle candidates
        rows = np.arange(j, n, 8)
        X[rows, j] = rng.randn(len(rows))
    X[np.arange(3, n, 16), 2] = np.nan      # NaN inside a bundled col
    X[:, 8] = rng.randn(n)                  # dense numerical
    X[:, 9] = rng.randn(n)
    X[rng.rand(n) < 0.05, 9] = np.nan       # MISSING_NAN numerical
    X[:, 10] = rng.randn(n)
    X[rng.rand(n) < 0.4, 10] = 0.0          # heavy zero bin
    cat = rng.randint(0, 9, n).astype(float)
    cat[rng.rand(n) < 0.03] = np.nan        # NaN categorical
    cat[rng.rand(n) < 0.02] = -2.0          # negative -> NaN bin
    X[:, 11] = cat
    X[:, 12] = rng.randint(0, 3, n).astype(float)   # small cardinality
    X[:, 13] = np.where(rng.rand(n) < 0.1,
                        rng.randint(1, 5, n), 0.0)  # sparse categorical
    y = (rng.rand(n) > 0.5).astype(float)
    return X, y, [11, 12, 13]


BASE = {"verbose": -1, "max_bin": 63, "min_data_in_bin": 1}
SERIAL = {"construct_threads": 1, "native_binning": False}


def _construct(X, y, cats, **overrides):
    params = dict(BASE, **overrides)
    return lgb.Dataset(X.copy(), label=y,
                       categorical_feature=list(cats)).construct(
        Config.from_params(params))


@pytest.fixture(scope="module")
def mixed():
    return _mixed_matrix()


@pytest.fixture(scope="module")
def serial_core(mixed):
    X, y, cats = mixed
    return _construct(X, y, cats, **SERIAL)


@pytest.fixture(scope="module")
def parallel_core(mixed):
    X, y, cats = mixed
    return _construct(X, y, cats)          # defaults: native + auto


def _bins(core):
    return np.asarray(core.group_bins)


def test_mixed_shape_covers_every_feature_class(parallel_core):
    """The fixture must actually exercise bundles (incl. collapsed
    defaults), categoricals and NaN corners, or the parity tests below
    prove nothing."""
    assert any(parallel_core.group_is_multi)
    assert any(f.collapsed_default for f in parallel_core.features)
    assert any(f.is_categorical for f in parallel_core.features)
    from lightgbm_tpu.binning import MISSING_NAN
    assert any(m.missing_type == MISSING_NAN
               for m in parallel_core.mappers if not m.is_trivial)


def test_parallel_native_byte_identical_to_serial(serial_core,
                                                  parallel_core):
    np.testing.assert_array_equal(_bins(serial_core),
                                  _bins(parallel_core))
    assert serial_core.feature_infos() == parallel_core.feature_infos()


@pytest.mark.parametrize("threads", [2, 3])
def test_thread_count_never_changes_bins(mixed, serial_core, threads):
    X, y, cats = mixed
    core = _construct(X, y, cats, construct_threads=threads)
    np.testing.assert_array_equal(_bins(serial_core), _bins(core))


def test_native_only_and_threads_only_match(mixed, serial_core):
    X, y, cats = mixed
    native_only = _construct(X, y, cats, construct_threads=1)
    threads_only = _construct(X, y, cats, construct_threads=4,
                              native_binning=False)
    np.testing.assert_array_equal(_bins(serial_core), _bins(native_only))
    np.testing.assert_array_equal(_bins(serial_core),
                                  _bins(threads_only))


def test_zero_as_missing_parity(mixed):
    X, y, cats = mixed
    a = _construct(X, y, cats, zero_as_missing=True, **SERIAL)
    b = _construct(X, y, cats, zero_as_missing=True)
    np.testing.assert_array_equal(_bins(a), _bins(b))


def test_small_chunk_native_path_parity():
    """The 4096-row native cutoff is gone: tiny matrices (and therefore
    small streaming chunks) must take the native path and still match
    the Python mapper byte for byte."""
    rng = np.random.RandomState(11)
    X = rng.randn(257, 5)
    X[rng.rand(257, 5) < 0.1] = np.nan
    y = rng.rand(257)
    a = lgb.Dataset(X, label=y).construct(Config.from_params(BASE))
    b = lgb.Dataset(X, label=y).construct(
        Config.from_params(dict(BASE, **SERIAL)))
    np.testing.assert_array_equal(_bins(a), _bins(b))


def test_sparse_csc_threaded_parity(mixed):
    sp = pytest.importorskip("scipy.sparse")
    X, y, cats = mixed
    Xs = sp.csr_matrix(np.nan_to_num(X, nan=0.0))
    a = lgb.Dataset(Xs, label=y, categorical_feature=cats).construct(
        Config.from_params(dict(BASE, construct_threads=4)))
    b = lgb.Dataset(Xs.copy(), label=y,
                    categorical_feature=cats).construct(
        Config.from_params(dict(BASE, construct_threads=1)))
    np.testing.assert_array_equal(_bins(a), _bins(b))


# ---------------------------------------------------------------------------
# streaming (overlapped parse/bin) parity
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def stream_csv(tmp_path_factory):
    rng = np.random.RandomState(5)
    X = rng.randn(3000, 8)
    X[rng.rand(3000, 8) < 0.3] = 0.0
    y = (X[:, 0] - X[:, 1] > 0).astype(float)
    p = tmp_path_factory.mktemp("cstream") / "train.csv"
    np.savetxt(p, np.column_stack([y, X]), delimiter=",", fmt="%.8g")
    return str(p)


def test_overlapped_streaming_matches_in_ram(stream_csv):
    params = {"verbose": -1, "max_bin": 63,
              "bin_construct_sample_cnt": 5000}
    ram = lgb.Dataset(stream_csv).construct(Config.from_params(params))
    stream = lgb.Dataset(stream_csv).construct(Config.from_params(
        dict(params, two_round=True, streaming_chunk_rows=256)))
    np.testing.assert_array_equal(_bins(ram), _bins(stream))
    np.testing.assert_array_equal(ram.metadata.label,
                                  stream.metadata.label)


def test_streaming_chunk_size_invariant(stream_csv):
    params = {"verbose": -1, "max_bin": 63, "two_round": True,
              "bin_construct_sample_cnt": 5000}
    a = lgb.Dataset(stream_csv).construct(Config.from_params(
        dict(params, streaming_chunk_rows=173)))
    b = lgb.Dataset(stream_csv).construct(Config.from_params(
        dict(params, streaming_chunk_rows=2048)))
    np.testing.assert_array_equal(_bins(a), _bins(b))


# ---------------------------------------------------------------------------
# binary cache v2 / v1
# ---------------------------------------------------------------------------
def test_cache_v2_roundtrip_byte_identical(parallel_core, tmp_path):
    bp = str(tmp_path / "mixed.bin")
    save_binary(parallel_core, bp)
    re = load_binary(bp)
    assert isinstance(re.group_bins, np.memmap), \
        "v2 reload must memmap the bin section (near-zero-copy)"
    np.testing.assert_array_equal(_bins(parallel_core), _bins(re))
    np.testing.assert_array_equal(parallel_core.metadata.label,
                                  re.metadata.label)
    assert parallel_core.feature_infos() == re.feature_infos()
    assert parallel_core.group_num_bin == re.group_num_bin
    assert [f.offset for f in parallel_core.features] == \
        [f.offset for f in re.features]


def test_cache_v1_backward_load(parallel_core, tmp_path):
    bp = str(tmp_path / "mixed_v1.bin")
    save_binary(parallel_core, bp, version=1)
    re = load_binary(bp)            # deprecation warning, not an error
    np.testing.assert_array_equal(_bins(parallel_core), _bins(re))
    assert parallel_core.feature_infos() == re.feature_infos()


def test_cache_v1_knob(parallel_core, mixed, tmp_path):
    """binary_cache_v2=false writes the legacy pickle payload."""
    X, y, cats = mixed
    core = _construct(X, y, cats, binary_cache_v2=False)
    bp = str(tmp_path / "knob_v1.bin")
    save_binary(core, bp)
    with open(bp, "rb") as f:
        f.read(len(BINARY_TOKEN))
        assert f.read(len(MAGIC_V2)) != MAGIC_V2
    np.testing.assert_array_equal(_bins(parallel_core),
                                  _bins(load_binary(bp)))


def test_corrupted_header_rejected(tmp_path):
    bad_len = tmp_path / "bad_len.bin"
    bad_len.write_bytes(BINARY_TOKEN + MAGIC_V2
                        + struct.pack("<Q", 1 << 40) + b"x" * 64)
    with pytest.raises(LightGBMError):
        load_binary(str(bad_len))
    bad_blob = tmp_path / "bad_blob.bin"
    bad_blob.write_bytes(BINARY_TOKEN + MAGIC_V2
                         + struct.pack("<Q", 16) + b"not a pickle!!!!")
    with pytest.raises(LightGBMError):
        load_binary(str(bad_blob))


def test_truncated_bin_section_rejected(parallel_core, tmp_path):
    bp = tmp_path / "trunc.bin"
    save_binary(parallel_core, str(bp))
    whole = bp.read_bytes()
    bp.write_bytes(whole[:-1024])
    with pytest.raises(LightGBMError):
        load_binary(str(bp))


def test_not_a_binary_file_rejected(tmp_path):
    p = tmp_path / "noise.bin"
    p.write_bytes(b"definitely not a dataset")
    with pytest.raises(LightGBMError):
        load_binary(str(p))


# ---------------------------------------------------------------------------
# trained-tree byte identity across construction routes
# ---------------------------------------------------------------------------
TRAIN_PARAMS = {"objective": "binary", "verbose": -1, "num_leaves": 7,
                "max_bin": 63, "min_data_in_bin": 1,
                "min_data_in_leaf": 5}


def _train_model(core):
    booster = lgb.Booster(config=Config.from_params(TRAIN_PARAMS),
                          train_set=core)
    for _ in range(5):
        booster.update()
    return booster.model_to_string()


def test_trained_trees_byte_identical_across_routes(
        serial_core, parallel_core, tmp_path):
    bp = str(tmp_path / "route.bin")
    save_binary(parallel_core, bp)
    reloaded = load_binary(bp)      # memmap-backed bins -> device path
    m_serial = _train_model(serial_core)
    m_parallel = _train_model(parallel_core)
    m_reload = _train_model(reloaded)
    assert m_serial == m_parallel, \
        "parallel construction changed the trained trees"
    assert m_serial == m_reload, \
        "binary-cache v2 reload changed the trained trees"


# ---------------------------------------------------------------------------
# knobs + mapper cache
# ---------------------------------------------------------------------------
def test_construct_threads_validation():
    with pytest.raises(ValueError):
        Config.from_params({"construct_threads": "many"})
    with pytest.raises(ValueError):
        Config.from_params({"construct_threads": "2.5"})
    assert Config.from_params({"construct_threads": "auto"})
    assert Config.from_params({"construct_threads": 3})
    from lightgbm_tpu.binning import resolve_construct_threads
    assert resolve_construct_threads(
        Config.from_params({"construct_threads": 3})) == 3
    assert resolve_construct_threads(None) >= 1
    assert resolve_construct_threads(
        Config.from_params({"construct_threads": 0})) >= 1


def test_categorical_lut_cached_at_fit_time(parallel_core):
    """value_to_bin must not re-materialize the dict arrays per call:
    the LUT is built once at fit time, and a mapper arriving WITHOUT
    the cache (older pickle) rebuilds it lazily with identical
    results."""
    from lightgbm_tpu.binning import BIN_CATEGORICAL
    m = next(mm for mm in parallel_core.mappers
             if mm.bin_type == BIN_CATEGORICAL and not mm.is_trivial)
    assert m._cat_lut is not None
    probe = np.array([-3.0, 0.0, 1.0, 2.0, 7.0, 99.0, np.nan])
    cached = m.value_to_bin(probe)
    m._cat_lut = None               # simulate an old-pickle mapper
    lazy = m.value_to_bin(probe)
    assert m._cat_lut is not None   # rebuilt
    np.testing.assert_array_equal(cached, lazy)


# ---------------------------------------------------------------------------
# PR 32: the table is binned from the buffer it arrived in.  A float32
# table (and whatever else is no C-contiguous float64 matrix) must give
# the bins, mappers and trees of its float64 copy — without that copy.
# ---------------------------------------------------------------------------
def _f32_dense(n=2500, f=9, seed=21):
    return np.random.RandomState(seed).lognormal(size=(n, f)).astype(
        np.float32)


def _f32_specials():
    """NaN, both infinities, both zeros and float32 denormals, each in
    every column, among ordinary values."""
    X = _f32_dense(seed=22) - np.float32(1.5)
    rng = np.random.RandomState(23)
    tiny = np.finfo(np.float32).tiny
    for v in (np.nan, np.inf, -np.inf, -0.0, 0.0, tiny / 4, -tiny / 8,
              np.float32(1e-36), np.float32(-1e-36)):
        X[rng.rand(*X.shape) < 0.03] = v
    return X


def _f32_mixed():
    """Categoricals and EFB bundles with collapsed defaults: the
    features the dense kernel does not take."""
    return _mixed_matrix()[0].astype(np.float32)


INPUT_CASES = {
    # name: (input table, categorical columns, parameters)
    "dense_f32": (_f32_dense, [], {}),
    "specials_f32": (_f32_specials, [], {}),
    "specials_f32_zero_as_missing": (_f32_specials, [],
                                     {"zero_as_missing": True}),
    "specials_f32_no_missing": (_f32_specials, [], {"use_missing": False}),
    "mixed_cat_efb_f32": (_f32_mixed, [11, 12, 13], {}),
    "mixed_cat_efb_f32_python_mapper": (_f32_mixed, [11, 12, 13],
                                        {"native_binning": False}),
    "mixed_cat_efb_f32_nibbles": (_f32_mixed, [11, 12, 13],
                                  {"max_bin": 15, "bin_packing": "4bit"}),
    "fortran_f32": (lambda: np.asfortranarray(_f32_specials()), [], {}),
    "strided_f32": (lambda: np.hstack([_f32_specials()] * 2)[::2, 1::2],
                    [], {}),
    "fortran_f64": (lambda: np.asfortranarray(
        _f32_specials().astype(np.float64)), [], {}),
    "int32": (lambda: np.random.RandomState(24).randint(
        -40, 40, (2500, 6)).astype(np.int32), [1], {}),
    "float16": (lambda: _f32_specials().astype(np.float16), [], {}),
    "dense_f32_python_mapper": (_f32_specials, [],
                                {"native_binning": False}),
    "dense_f32_one_thread": (_f32_specials, [], {"construct_threads": 1}),
}


def _core_as_given(X, y, cats, **overrides):
    """No ``X.copy()`` (it would make a view contiguous), a sample
    smaller than the table, and blocks small enough that the table is
    several, a few of them in flight."""
    params = dict(dict(BASE, bin_construct_sample_cnt=1500,
                       construct_threads=8), **overrides)
    return lgb.Dataset(X, label=y, categorical_feature=list(cats),
                       free_raw_data=False).construct(
        Config.from_params(params))


def _same_dataset(a, b):
    np.testing.assert_array_equal(_bins(a), _bins(b))
    assert a.group_bins.shape == b.group_bins.shape
    assert [m.to_state() for m in a.mappers] == \
        [m.to_state() for m in b.mappers]
    assert a._bundles == b._bundles


@pytest.fixture
def small_blocks(monkeypatch):
    from lightgbm_tpu.dataset import Dataset as CoreDataset
    monkeypatch.setattr(CoreDataset, "ROW_BLOCK", 512)


@pytest.mark.parametrize("case", sorted(INPUT_CASES))
def test_input_as_given_bins_like_its_float64_copy(case, small_blocks):
    make, cats, overrides = INPUT_CASES[case]
    X = make()
    y = (np.arange(X.shape[0]) % 3 == 0).astype(float)
    wide = np.ascontiguousarray(X, dtype=np.float64)
    given = _core_as_given(X, y, cats, **overrides)
    copied = _core_as_given(wide, y, cats, **overrides)
    _same_dataset(given, copied)
    assert _train_model(given) == _train_model(copied)
    # and the float64 copy through the pre-r11 serial Python path
    _same_dataset(given, _core_as_given(wide, y, cats, **dict(
        overrides, **SERIAL)))
    assert given._raw_data is X        # the caller's own array


def test_value_on_a_bin_upper_bound_float32(small_blocks):
    """Validation tables whose values sit exactly on the training set's
    bin bounds (rounded to float32 here, so that a float32 can: a fitted
    bound is the double just above a midpoint): on the bound falls left
    of it, the next float32 falls right, for the float32 table as for
    its float64 copy."""
    rng = np.random.RandomState(25)
    Xt = rng.randint(0, 30, (2500, 4)).astype(np.float64)
    cfg = Config.from_params(dict(BASE, max_bin=255))
    train = lgb.Dataset(Xt, label=rng.rand(2500))
    for m in train.construct(cfg).mappers:
        m.bin_upper_bound = np.asarray(m.bin_upper_bound).astype(
            np.float32).astype(np.float64)
    bounds = train.construct(cfg).mappers[0].bin_upper_bound[1:-1]
    assert len(bounds) > 20
    V = np.tile(np.concatenate([bounds, np.nextafter(
        bounds.astype(np.float32), np.float32(np.inf))]).astype(
            np.float32)[:, None], (20, 4))
    v32 = lgb.Dataset(V, reference=train).construct(cfg)
    v64 = lgb.Dataset(V.astype(np.float64), reference=train).construct(cfg)
    vpy = lgb.Dataset(V, reference=train).construct(
        Config.from_params(dict(BASE, max_bin=255, **SERIAL)))
    np.testing.assert_array_equal(_bins(v32), _bins(v64))
    np.testing.assert_array_equal(_bins(v32), _bins(vpy))
    col = _bins(v32)[:2 * len(bounds), 0].astype(int)
    np.testing.assert_array_equal(col[:len(bounds)] + 1,
                                  col[len(bounds):])   # on / just above


def test_one_float32_matrix_against_its_row_shards(small_blocks):
    X = _f32_specials()
    y = (np.arange(X.shape[0]) % 3 == 0).astype(float)
    one = _core_as_given(X, y, [])
    cuts = [0, 700, 1211, 1212, X.shape[0]]
    shards = [X[a:b] for a, b in zip(cuts[:-1], cuts[1:])]
    sharded = lgb.Dataset(shards, label=y).construct(Config.from_params(
        dict(BASE, bin_construct_sample_cnt=1500, construct_threads=8)))
    np.testing.assert_array_equal(_bins(one),
                                  sharded.assembled_group_bins())
    assert [m.to_state() for m in one.mappers] == \
        [m.to_state() for m in sharded.mappers]
    wide = [a.astype(np.float64) for a in shards]
    sharded64 = lgb.Dataset(wide, label=y).construct(Config.from_params(
        dict(BASE, bin_construct_sample_cnt=1500, construct_threads=8)))
    np.testing.assert_array_equal(sharded.assembled_group_bins(),
                                  sharded64.assembled_group_bins())


@pytest.mark.parametrize("threads", [1, 3, 8])
def test_float32_kernel_equals_float64_kernel(threads):
    """``ltpu_bin_dense_f32_mt`` on a float32 table against
    ``ltpu_bin_dense_mt`` on its widening: the same bytes at every
    thread count (and ``ltpu_bin_cat_f32`` against ``ltpu_bin_cat``)."""
    import ctypes

    from lightgbm_tpu.native import TABLE_DTYPES, get_lib
    lib = get_lib()
    if lib is None:
        pytest.skip("native library unavailable")
    X = np.ascontiguousarray(np.vstack([_f32_specials()] * 3))
    n, f = X.shape
    rng = np.random.RandomState(26)
    parts = [np.unique(np.concatenate([
        rng.choice(X[:, j][np.isfinite(X[:, j])], 40).astype(np.float64),
        rng.randn(30)])) for j in range(f)]
    off = np.concatenate([[0], np.cumsum([len(b) for b in parts])]).astype(
        np.int64)
    flat = np.concatenate(parts)
    fidx = np.arange(f, dtype=np.int64)
    use_nan = (np.arange(f) % 2).astype(np.uint8)
    nan_bin = np.full(f, 200, np.int64)

    def p(a, t):
        return a.ctypes.data_as(ctypes.POINTER(t))

    outs = {}
    for table in (X, X.astype(np.float64)):
        sfx, c_t = TABLE_DTYPES[table.dtype]
        res = np.empty((f, n), np.uint8)
        getattr(lib, f"ltpu_bin_dense{sfx}_mt")(
            p(table, c_t), n, f, p(fidx, ctypes.c_long), f,
            p(flat, ctypes.c_double), p(off, ctypes.c_long),
            p(use_nan, ctypes.c_ubyte), p(nan_bin, ctypes.c_long),
            p(res, ctypes.c_ubyte), threads)
        lut = (np.arange(12, dtype=np.int32) * 7) % 11
        cat = np.empty(n, np.uint8)
        getattr(lib, f"ltpu_bin_cat{sfx}")(
            p(table * table.dtype.type(4), c_t), n, f, 2,
            p(lut, ctypes.c_int32), len(lut), 11, p(cat, ctypes.c_ubyte), 1)
        outs[sfx] = (res, cat)
    np.testing.assert_array_equal(outs["_f32"][0], outs[""][0])
    np.testing.assert_array_equal(outs["_f32"][1], outs[""][1])
    # and both are numpy's 'left' search on the float64 values
    j = 3
    v = X[:, j].astype(np.float64)
    want = np.searchsorted(parts[j], np.where(np.isnan(v), 0.0, v), "left")
    want = np.where(np.isnan(v) & (use_nan[j] == 1), 200, want)
    np.testing.assert_array_equal(outs["_f32"][0][j], want)
    assert len(set(outs["_f32"][1])) > 3


# -- the invariant: no float64 array of the table's row count -------------
WIDE_ROWS, WIDE_COLS = 100000, 20


def _construct_traced(X, cats=(), **overrides):
    """(core, peak bytes allocated while constructing, gauges, counters)
    at ``telemetry=counters``; numpy's buffers are traced by
    tracemalloc."""
    import gc
    import tracemalloc

    from lightgbm_tpu.telemetry import TELEMETRY
    y = (np.arange(WIDE_ROWS) % 2).astype(np.float32)
    cfg = Config.from_params(dict(
        BASE, bin_construct_sample_cnt=2000, telemetry="counters",
        **overrides))
    ds = lgb.Dataset(X, label=y, categorical_feature=list(cats))
    before = TELEMETRY.counters().get("construct_widened_mb", 0.0)
    gc.collect()
    tracemalloc.start()
    try:
        base = tracemalloc.get_traced_memory()[0]
        tracemalloc.reset_peak()
        core = ds.construct(cfg)
        peak = tracemalloc.get_traced_memory()[1] - base
    finally:
        tracemalloc.stop()
    widened = TELEMETRY.counters()["construct_widened_mb"] - before
    return core, peak, TELEMETRY.gauges()["construct_input_dtype"], widened


@pytest.fixture
def blocks_of_4096(monkeypatch):
    from lightgbm_tpu.dataset import Dataset as CoreDataset
    monkeypatch.setattr(CoreDataset, "ROW_BLOCK", 4096)


def _wide_table(dtype):
    X = np.random.RandomState(27).lognormal(
        size=(WIDE_ROWS, WIDE_COLS)).astype(np.float32)
    X[:, 5] = np.floor(X[:, 5] * 3) % 7         # a categorical column
    return X.astype(dtype)


F64_TABLE_BYTES = WIDE_ROWS * WIDE_COLS * 8


@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_native_route_widens_nothing(dtype, blocks_of_4096):
    """A float32 (or float64) table, categorical column and all, is read
    where it lies: counter 0, and what construct allocates at its peak
    (the uint8 matrix, the sampled rows, block scratch) stays far under
    one float64 copy of the table."""
    X = _wide_table(dtype)
    core, peak, seen, widened = _construct_traced(X, cats=[5])
    assert seen == dtype and widened == 0
    assert core.num_data == WIDE_ROWS and any(
        f.is_categorical for f in core.features)
    assert peak < F64_TABLE_BYTES / 2, peak


@pytest.mark.parametrize("arm", ["python_mapper", "int32", "strided_f32",
                                 "row_shards_int32"])
def test_fallback_arms_widen_a_block_at_a_time(arm, blocks_of_4096):
    """What the float kernels cannot read is widened ROW_BLOCK rows at a
    time: the counter sums to the whole table once, the peak stays under
    a fraction of it."""
    overrides, cats = {"construct_threads": 8}, [5]
    if arm == "python_mapper":
        X, want = _wide_table("float32"), "float32"
        overrides["native_binning"] = False
    elif arm == "int32":
        X, want = _wide_table("float32").astype(np.int32), "other"
    elif arm == "strided_f32":
        X = np.hstack([_wide_table("float32")] * 2)[:, ::2]
        want = "float32"
    else:
        T = _wide_table("float32").astype(np.int32)
        X, want = [T[:35000], T[35000:]], "other"
    core, peak, seen, widened = _construct_traced(X, cats=cats, **overrides)
    assert seen == want
    np.testing.assert_allclose(widened, F64_TABLE_BYTES / 1e6, rtol=1e-9)
    assert core.num_data == WIDE_ROWS
    assert peak < F64_TABLE_BYTES / 2, peak


def test_shape_is_read_without_converting_the_table(monkeypatch):
    import lightgbm_tpu.basic as basic
    X = _f32_dense()

    def refuse(*a, **k):
        raise AssertionError("the table was converted to read its shape")
    monkeypatch.setattr(basic, "_to_matrix", refuse)
    ds = lgb.Dataset(X)
    assert (ds.num_data(), ds.num_feature()) == X.shape
    assert ds.data is X
    monkeypatch.undo()
    assert lgb.Dataset([[1.0, 2.0, 3.0], [4.0, 5.0, 6.0]]).num_feature() == 3


def test_float32_raw_data_serves_its_readers():
    """``free_raw_data=False`` keeps the caller's float32 array; continued
    training and cv read it (through predict and from_matrix, which
    widen what they need) with the float64 table's results."""
    X = _f32_dense(n=1200, f=6)
    y = (X[:, 0] + X[:, 1] > 2.5).astype(float)
    texts, cvs = [], []
    for table in (X, X.astype(np.float64)):
        first = lgb.train(TRAIN_PARAMS, lgb.Dataset(table, label=y), 2)
        ds = lgb.Dataset(table, label=y, free_raw_data=False)
        more = lgb.train(TRAIN_PARAMS, ds, 2, init_model=first)
        assert ds.construct()._raw_data is table
        texts.append(more.model_to_string())
        cvs.append(lgb.cv(TRAIN_PARAMS, lgb.Dataset(
            table, label=y, free_raw_data=False), 2, nfold=2, seed=1))
    assert texts[0] == texts[1]
    assert cvs[0] == cvs[1]


if __name__ == "__main__":
    import sys

    import pytest as _pytest
    sys.exit(_pytest.main([__file__, "-v"]))
