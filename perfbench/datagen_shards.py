"""``datagen.binary_dense``'s table handed over as row shards, for a
table the host should not hold twice.

``binary_dense_shards(seed, rows, features)`` returns ``([X0, ..., X3],
y)``: the same rows, block for block and bit for bit, that
``datagen.binary_dense(seed, rows, features)`` returns as one matrix
(each 2^18-row block is drawn from its own stream keyed by (seed,
block)), cut into four equal runs of whole blocks.  ``lgb.Dataset``
takes the list as it stands and bins it run by run, so that neither one
18 GB matrix nor its float64 copy is ever made (2^26 x 67: 4.5 GB a
run); the comparison gets the same list.  Four is the deployment's
number of chips, but nothing downstream depends on it: the program cuts
its bin matrix by its own mesh and the reference by its own devices.
"""
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import datagen

RUNS = 4


def _fill(seed, w, xb, yb, block):
    """One block, as ``datagen._fill_block`` fills it."""
    rng = np.random.default_rng([int(seed), 0, block])
    rng.standard_normal(out=xb, dtype=np.float32)
    logit = xb @ w + 0.5 * np.sin(3.0 * xb[:, 0]) * xb[:, 1]
    noise = rng.logistic(size=xb.shape[0]).astype(np.float32)
    yb[:] = (logit + noise > 0).astype(np.float32)
    np.exp(xb, out=xb)


def binary_dense_shards(seed, rows, features, threads=None):
    """([X_i float32 (rows_i, features)], y float32 (rows,)): RUNS runs,
    fewer where the table has fewer blocks."""
    step = datagen.BLOCK_ROWS
    w = datagen._concept(seed, features)
    blocks = -(-rows // step)
    per = -(-blocks // RUNS) * step                 # rows a run, whole blocks
    cuts = [min(i * per, rows) for i in range(RUNS + 1)]
    runs = [np.empty((hi - lo, features), np.float32)
            for lo, hi in zip(cuts, cuts[1:])]
    y = np.empty(rows, np.float32)
    jobs = []
    for run, lo_run in zip(runs, cuts):
        for lo in range(0, run.shape[0], step):
            hi = min(lo + step, run.shape[0])
            jobs.append((run[lo:hi], y[lo_run + lo:lo_run + hi],
                         (lo_run + lo) // step))
    with ThreadPoolExecutor(max_workers=threads or os.cpu_count()) as pool:
        for f in [pool.submit(_fill, seed, w, xb, yb, block)
                  for xb, yb, block in jobs]:
            f.result()
    return [run for run in runs if run.shape[0]], y
