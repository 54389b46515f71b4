"""Test harness: force a CPU-only 8-device virtual mesh.

Distributed learners are exercised on host-simulated devices (the
reference has no multi-node CI at all — SURVEY §4; this is the
deterministic multi-host substitute).  The platform is set both in the
environment and, after import, in jax's config: a chip belongs to one
process at a time, and a test run must never take it from (or wait
on) a process that holds it.
"""
import os

# LGBM_TPU_ONCHIP=1 runs the suite against the real chip (for
# tests/test_tpu_onchip.py's Mosaic-numerics parity checks)
_ONCHIP = os.environ.get("LGBM_TPU_ONCHIP") == "1"

if not _ONCHIP:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

if not _ONCHIP:
    jax.config.update("jax_platforms", "cpu")
# persistent compile cache: every TreeGrower instance re-jits its tree
# function, so without this the suite recompiles identical shapes
# dozens of times (round-1 suite exceeded 25 min; compiles dominated).
# Same placement rule as the package (config.resolve_compile_cache_dir):
# a cache placed from outside through JAX_COMPILATION_CACHE_DIR is the
# only one used; otherwise a fixed directory in the checkout.
# (The string is spelled exactly as it always was, "tests/../…": the
# path is part of jax's cache key, so normalising it would orphan every
# entry already on disk.)
SUITE_CACHE_DIR = os.path.join(os.path.dirname(__file__), "..",
                               ".jax_cache_cpu")
if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    jax.config.update("jax_compilation_cache_dir", SUITE_CACHE_DIR)
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

import subprocess  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NATIVE = os.path.join(_REPO, "lightgbm_tpu", "native")
_CAPI_SRC = os.path.join(_NATIVE, "src", "capi", "c_api_embed.cpp")
_CAPI_LIB = os.path.join(_NATIVE, "liblgbm_tpu.so")


def _python_config(*flags):
    exe = f"python{sys.version_info.major}.{sys.version_info.minor}-config"
    for cand in (exe, "python3-config"):
        try:
            out = subprocess.run([cand, *flags], capture_output=True,
                                 text=True, check=True)
            return out.stdout.split()
        except (OSError, subprocess.CalledProcessError):
            continue
    return None


@pytest.fixture(scope="session")
def native_lib():
    """Session-shared liblgbm_tpu.so: built once per suite (three
    binding test files used to rebuild it independently, ~40 s of g++
    each) and reused only when its key — source and header bytes,
    flags, python-config output — still matches
    (lightgbm_tpu.native.build_shared, the rule libltpu.so follows)."""
    from lightgbm_tpu.native import build_shared
    inc = _python_config("--includes")
    ld = _python_config("--ldflags", "--embed")
    if inc is None or ld is None:
        pytest.skip("python-config not available")
    inc_dir = os.path.join(_NATIVE, "include")
    try:
        return build_shared(
            _CAPI_LIB, [_CAPI_SRC],
            ["-O2", "-std=c++17", "-shared", "-fPIC", *inc],
            deps=[os.path.join(inc_dir, f) for f in os.listdir(inc_dir)],
            link=ld)
    except subprocess.CalledProcessError as e:
        pytest.fail(f"native capi build failed: {e.stderr[-2000:]}")


@pytest.fixture
def rng():
    return np.random.RandomState(42)


@pytest.fixture(scope="session")
def analysis_programs():
    """One ProgramSet per suite: the static-analysis probe builds
    (carry-probe GBDT, predict-probe booster, lowered entry points)
    are shared by tests/test_analysis.py and tests/test_carry_hlo.py
    instead of each file re-training its own."""
    from lightgbm_tpu.analysis.programs import ProgramSet
    return ProgramSet()


# ---------------------------------------------------------------------------
# `fast` smoke tier: one representative test per subsystem (marker
# applied here so the test files stay uncluttered).  pytest -m fast -q
# is the inner development loop; "not slow" is the thorough tier.
_FAST_TESTS = {
    "test_binary",                      # engine end-to-end
    "test_regression",
    "test_missing_value_nan",           # missing-value semantics
    "test_categorical_handling",        # categorical splits
    "test_save_load_pickle_roundtrip",  # model text IO
    "test_simple_numerical",            # binning
    "test_zero_gets_own_bin",
    "test_bundles_exclusive_features",  # EFB
    "test_apply_splits_matches_reference_over_256_groups",  # partition
    "test_pallas_kernel_matches_einsum_interpret",          # hist
    "test_subbyte_streamed_kernels_match_pack1_interpret",
    "test_fused_grower_wiring_interpret_matches_xla_path",
    "test_data_parallel_matches_serial",                    # mesh
    "test_dataset_booster_lifecycle",   # C API
    "test_round4_symbol_tail",
    "test_classifier_binary",           # sklearn surface
    "test_cv",                          # cv + callbacks
    "test_early_stopping",
    "test_shap_contribs_sum",           # SHAP
    "test_virtual_file_scheme_hook",    # IO seams
}


def pytest_collection_modifyitems(config, items):
    matched = set()
    for item in items:
        base = item.name.split("[")[0]
        if base in _FAST_TESTS and "slow" not in item.keywords:
            item.add_marker(pytest.mark.fast)
            matched.add(base)
    missing = _FAST_TESTS - matched
    # renames must not silently shrink the smoke tier.  Only checkable
    # when the whole suite was collected, so key off the invocation
    # (bare `pytest` / `pytest tests/`), not an item-count heuristic —
    # --ignore/-k subsets and file runs must not trip it.
    whole_suite = not config.getoption("ignore", None) \
        and not config.getoption("ignore_glob", None) \
        and not config.getoption("deselect", None) \
        and not config.getoption("keyword", "") \
        and all(os.path.isdir(a.split("::")[0]) for a in config.args)
    if missing and whole_suite:
        raise pytest.UsageError(
            f"fast-tier tests not collected: {missing}")
