"""``epsilon_train`` at a size a test run can hold on the CPU: the
configuration's own file with rows, features and leaves shrunk, the
factored kernel's group chunk forced down to 32 of its 40 groups (by the
plan's module constant, not by an option), the limits of
small_limits_wide.json.  Shared by tests/test_epsilon_cell.py (tier-1)
and test_correct_wide.py."""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]
import run

SEED = 2147484003          # above 2**31 - 1, as the driver's can be
ROWS, FEATURES, LEAVES = 6000, 40, 15


def small_cell():
    loaded = run.load_cell("epsilon_train")
    loaded["config"].update(rows=ROWS, features=FEATURES)
    loaded["config"]["params"].update(num_leaves=LEAVES,
                                      min_sum_hessian_in_leaf=5)
    loaded["traffic"]["dispatch_chunk"] = 2
    with open(os.path.join(HERE, "small_limits_wide.json")) as f:
        loaded["limits"] = json.load(f)
    return loaded


def drive(monkeypatch, chunked=True, seed=SEED):
    """One run of the small cell: (line, info, model text).  ``chunked``
    leaves the plan no room for the table's 40 groups in one chunk."""
    import jax
    import modeltext
    from lightgbm_tpu.ops import hist_plan
    texts = []
    real_parse = modeltext.parse
    monkeypatch.setattr(modeltext, "parse",
                        lambda text: texts.append(text) or real_parse(text))
    if chunked:
        monkeypatch.setattr(hist_plan, "CHUNK_VMEM_BUDGET", 1 << 20)
    line, info = run.run_cell(small_cell(), seed, 0.5, False,
                              jax.devices()[:1], interpret=True)
    return line, info, texts[-1]
