"""``readings.py`` for a query-grouped cell: the same runs and the same
flags, with ``faults_rank.py``'s faults beside the three of
``faults.py``.  Each seed draws its own table and so its own query
sizes, so the configuration's expectation of the bucket count, a
property of its one table, is left out here.

    python3 perfbench/tests/readings_rank.py --workload istella_rank_train \
        --seeds 11,22,33 --fault-seeds 1 --seconds 10      (on a TPU host)
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]
import faults
import faults_rank
import readings
import run

faults.FAULTS.update(faults_rank.FAULTS)


def load_cell(name, _load=run.load_cell):
    loaded = _load(name)
    loaded["config"].get("expect_gauges", {}).pop(
        "objective.rank_buckets", None)
    return loaded


if __name__ == "__main__":
    run.load_cell = load_cell
    readings.main()
