"""Readings for a cell's limits, at the cell's own size on the chip, all
in one process: per seed one sound run (its numbers and the control's,
which the reference's pass computes beside them) and, on the first
--fault-seeds seeds, one run under each fault of faults.py.  One JSON
line a run, also appended to chiprun_out/readings_<workload>.jsonl.

    chiprun --timeout 3000 -- python3 perfbench/tests/readings.py \
        --workload criteo_train --seeds 11,22,33,44 --fault-seeds 3 --seconds 10

A run of the benchmark trains on its configuration's one table
(``table_seed``).  A limit has to hold on more than that table, so each
seed here draws the table too (``table_seed`` = the seed) as well as the
comparison's samples.
"""
import argparse
import contextlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]
import faults
import run


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault-seeds", type=int, default=0,
                    help="also run each fault on the first N seeds")
    args = ap.parse_args(argv)
    import jax
    loaded = run.load_cell(args.workload)
    devices = run.require_chips(jax, loaded["cell"]["chips"])
    os.makedirs("chiprun_out", exist_ok=True)
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        loaded["config"]["table_seed"] = seed
        for name in ["sound"] + (sorted(faults.FAULTS) if i < args.fault_seeds else []):
            with faults.FAULTS[name]() if name != "sound" \
                    else contextlib.nullcontext():
                line, info = run.run_cell(loaded, seed, args.seconds, False, devices)
            row = {"seed": seed, "table_seed": info["table_seed"],
                   "run": name, "correct": line["correct"],
                   "control_correct": info["control_correct"],
                   "numbers": info["numbers"], "clocks": info["clocks"],
                   "metrics": line["metrics"]}
            print(json.dumps(row, default=str), flush=True)
            with open(f"chiprun_out/readings_{args.workload}.jsonl", "a") as f:
                f.write(json.dumps(row, default=str) + "\n")


if __name__ == "__main__":
    main()
