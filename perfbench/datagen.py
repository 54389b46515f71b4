"""Synthetic tables made from ``--seed``, one general generator per kind.

``binary_dense`` follows ``bench.make_data`` (repo root): standard normal
draws z, a sparse linear concept in z plus one interaction, logistic label
noise.  Unlike the original it draws float32 in row blocks, each block
from a stream of its own keyed by (seed, block), so that the table is the
same whatever the number of threads and never exists as float64; and the
features it hands over are exp(z): positive and heavy-tailed, as the count
and rate columns of a click log are.  A tree sees only the order of a
column's values, so the concept is as learnable as on z; but zero now lies
below every column in every seed (the program compiles each column's bin
of zero into its training program, PERF.md section 7).
"""
from concurrent.futures import ThreadPoolExecutor

import numpy as np

BLOCK_ROWS = 1 << 18


def _concept(seed, features):
    rng = np.random.default_rng([int(seed), 0xC0CE])
    return (rng.standard_normal(features)
            * (rng.random(features) > 0.3)).astype(np.float32)


def _fill_block(seed, w, X, y, lo, hi):
    rng = np.random.default_rng([int(seed), 0, lo // BLOCK_ROWS])
    xb = X[lo:hi]
    rng.standard_normal(out=xb, dtype=np.float32)
    logit = xb @ w + 0.5 * np.sin(3.0 * xb[:, 0]) * xb[:, 1]
    noise = rng.logistic(size=hi - lo).astype(np.float32)
    y[lo:hi] = (logit + noise > 0).astype(np.float32)
    np.exp(xb, out=xb)


def binary_dense(seed, rows, features, threads=12):
    """(X float32 (rows, features), y float32 (rows,))."""
    w = _concept(seed, features)
    X = np.empty((rows, features), np.float32)
    y = np.empty(rows, np.float32)
    spans = [(lo, min(lo + BLOCK_ROWS, rows))
             for lo in range(0, rows, BLOCK_ROWS)]
    with ThreadPoolExecutor(max_workers=threads) as pool:
        for f in [pool.submit(_fill_block, seed, w, X, y, lo, hi)
                  for lo, hi in spans]:
            f.result()
    return X, y


GENERATORS = {"binary_dense": binary_dense}
