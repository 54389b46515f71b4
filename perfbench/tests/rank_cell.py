"""``istella_rank_train`` at a size a test run can hold on the CPU: the
configuration's own file with rows and features shrunk and its table
drawn from SEED (so small_limits_rank.json's readings are this size's),
through the harness's ``run_cell`` and the program's interpret seam.
The query sizes keep the configuration's shape: ragged, lognormal, with
queries longer than a pair tile.  Shared by tests/test_rank_cell.py
(tier-1)."""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]
import run

SEED = 2147484013          # above 2**31 - 1, as a run's --seed can be
ROWS, FEATURES = 40000, 16


def small_cell():
    loaded = run.load_cell("istella_rank_train")
    loaded["config"].update(rows=ROWS, features=FEATURES, table_seed=SEED)
    kind = loaded["limits"]["compare"]["kind"]       # the cell's own
    with open(os.path.join(HERE, "small_limits_rank.json")) as f:
        loaded["limits"] = json.load(f)
    loaded["limits"]["compare"]["kind"] = kind
    return loaded


def drive(seed=SEED, loaded=None):
    """One run of the small cell: (line, info)."""
    import jax
    return run.run_cell(loaded or small_cell(), seed, 0.5, False,
                        jax.devices()[:1], interpret=True)
