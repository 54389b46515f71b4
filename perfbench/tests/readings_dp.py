"""``readings.py`` for a cell over several chips: the same runs and the
same flags, with ``faults_dp.py``'s fault beside the three of
``faults.py``, and the table of a seed made once and kept for that
seed's runs (sound and faults bin the same rows; at 2^26 rows the table
costs 8 s of four chips a run).

    chiprun --chips 4 --timeout 3000 -- python3 perfbench/tests/readings_dp.py \
        --workload criteo_dp4_train --seeds 11,22,33 --fault-seeds 1 --seconds 2
"""
import importlib
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]
import faults
import faults_dp
import readings
import run

faults.FAULTS.update(faults_dp.FAULTS)


def keep_table(generator):
    """``module:function`` wrapped so that the last seed's table is kept."""
    module, _, function = generator.partition(":")
    mod = importlib.import_module(module)
    real, kept = getattr(mod, function), {}

    def memo(seed, rows, features):
        if kept.get("key") != (seed, rows, features):
            kept.clear()
            kept.update(key=(seed, rows, features),
                        data=real(seed, rows, features))
        return kept["data"]
    setattr(mod, function, memo)


def load_cell(name, _load=run.load_cell):
    loaded = _load(name)
    if ":" in loaded["config"]["generator"]:
        keep_table(loaded["config"]["generator"])
    return loaded


if __name__ == "__main__":
    run.load_cell = load_cell
    readings.main()
