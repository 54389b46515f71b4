"""chip_smoke.py plumbing on the CPU.

The smoke itself only passes on a TPU (the driver runs it there); what
tier-1 can pin is that its legs still run end to end — the same
functions at a tiny shape, with the Pallas kernels on the interpret
seam — and that a chipless run refuses instead of reporting a CPU run
under a TPU's name.
"""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
import lightgbm_tpu as lgb  # noqa: E402
from bench import make_data  # noqa: E402
from lightgbm_tpu.telemetry import TELEMETRY  # noqa: E402
from lightgbm_tpu.utils.log import Log  # noqa: E402

TINY = {"num_leaves": 7, "max_bin": 15, "min_sum_hessian_in_leaf": 1.0}
ROUNDS = 3


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """The legs train with telemetry=counters and verbose=-1, both
    process-global: hand the next test file what this one was given."""
    level = Log.level
    TELEMETRY.configure("off")
    TELEMETRY.reset()
    yield
    TELEMETRY.configure("off")
    TELEMETRY.reset()
    Log.set_level(level)


@pytest.mark.fast
def test_legs_run_on_the_interpret_seam():
    X, y, w = make_data(2048, 6)
    Xv, yv, _ = make_data(1024, 6, seed=8, w=w)
    seam = {**TINY, "force_pallas_interpret": True}
    bst_a, a = chip_smoke.leg_fast(lgb, X, y, Xv, yv, ROUNDS, extra=seam,
                                   interpret=True)
    assert a["plan"]["tier"] == "ladder" and a["plan"]["interpret"]
    assert a["hist_path"] == "fused_tiled"
    assert a["dispatch_chunk_auto"] is None     # the TPU-only branch
    bst_b, b = chip_smoke.leg_default(lgb, X, y, Xv, yv, ROUNDS, extra=TINY)
    assert b["hist_path"] == "xla"
    assert b["logloss_last"] < b["logloss_first"]
    # same trees-for-trees comparison main() gates at 1e-3 on the chip;
    # 2048 rows of int8 gradients are noisier than that
    assert abs(a["auc"] - b["auc"]) < 0.05
    c = chip_smoke.leg_predict(lgb, bst_b, Xv)
    assert c["kernel"] == "level"
    # 1 and 3 share the 16-row minimum bucket; 40 rounds up to 64
    assert {16, 64} <= set(c["buckets"])
    assert c["max_abs_dev_vs_host"] < 1e-6


@pytest.mark.fast
def test_leg_d_runs_the_ladder_on_every_shard_of_the_seam():
    """Leg D on four virtual devices: the kernel plan under the mesh,
    the fused kernels in the chunk program, and the model text of one
    device — the gates the chip run makes."""
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    X, y, w = make_data(4096, 6)
    Xv, _, _ = make_data(1024, 6, seed=8, w=w)
    d = chip_smoke.leg_multichip(
        lgb, X, y, Xv, ROUNDS, n_chips=4,
        extra={**TINY, "force_pallas_interpret": True}, interpret=True)
    assert d["model_text_equal_one_chip"] and d["max_abs_vs_one_chip"] == 0
    assert d["plan"]["tier"] == "ladder" and d["plan"]["row_shards"] == 4
    assert "compute_group_histograms_fused_tiled" in d["kernels"]
    assert "route_apply_tiled" in d["kernels"]
    assert len(d["devices"]) == 4


@pytest.mark.fast
def test_main_refuses_without_a_tpu(capsys):
    assert chip_smoke.main() != 0
    captured = capsys.readouterr()
    assert captured.out == "", "a refused run must print no result"
    assert "no TPU" in captured.err


@pytest.mark.fast
def test_verdict_line_has_the_contract_keys_and_no_others():
    # the driver refuses the PR on any extra key in the last stdout line
    import json
    line = chip_smoke.verdict(True, {"platform": "tpu",
                                     "kind": "TPU v5 lite", "count": 1,
                                     "extra": "dropped"})
    assert "\n" not in line
    assert json.loads(line) == {"ok": True, "device": {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
