"""A query-grouped ranking table made from a seed, shaped as a LETOR
training split: ``istella_like(seed, rows, features)`` returns ``(X, y,
sizes)``, the table, its graded labels and the query sizes, in that
order.  The harness hands ``X`` and ``y`` to ``lgb.Dataset``; the traffic
kind of ``traffic_rank.py`` attaches the sizes, which it takes from
``groups_for`` (the last sizes this module made for that many rows), and
the comparison of ``reference_rank.py`` gets all three.

- Query sizes: lognormal, median ``MEDIAN_QUERY`` and sigma
  ``SIGMA_QUERY``, at least 2, scaled so that they sum to ``rows``
  exactly; ``round(rows / MEAN_QUERY)`` queries (the Istella LETOR
  training split: 7,325,625 documents in 23,219 queries).
- Features: ``datagen.binary_dense``'s, positive and heavy-tailed, so that
  zero lies under every column.
- Labels: 0-4, graded from the same kind of concept as ``binary_dense``'s
  (a sparse linear term, one interaction, logistic noise) cut at fixed
  quantiles, so that the shares are ``LABEL_SHARES``: most documents are
  not relevant, as in graded web judgements.

Everything is drawn from ``(seed, ...)`` streams, so one seed gives one
table whatever the number of threads.
"""
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import datagen

MEAN_QUERY = 7325625 / 23219
MEDIAN_QUERY = 229.0
SIGMA_QUERY = 0.8
LABEL_SHARES = (0.70, 0.15, 0.09, 0.04, 0.02)
_SIZES = {}


def query_sizes(seed, rows):
    """Sizes of ``round(rows / MEAN_QUERY)`` queries summing to ``rows``."""
    nq = max(1, int(round(rows / MEAN_QUERY)))
    rng = np.random.default_rng([int(seed), 0x9E7])
    m = np.exp(rng.normal(np.log(MEDIAN_QUERY), SIGMA_QUERY, nq))
    m = np.maximum(2, np.floor(m * rows / m.sum()).astype(np.int64))
    short = rows - int(m.sum())
    while short:                    # hand the remainder out a row a query
        step = np.sign(short)
        can = np.flatnonzero(m + step >= 2)
        pick = rng.choice(can, size=min(abs(short), len(can)), replace=False)
        m[pick] += step
        short -= step * len(pick)
    return m


def _cuts(seed, w):
    """The concept's values at the label shares' quantiles, from a sample
    of its own stream."""
    rng = np.random.default_rng([int(seed), 0xC07])
    z = rng.standard_normal((1 << 16, len(w)), dtype=np.float32)
    v = _concept(z, w) + rng.logistic(size=len(z)).astype(np.float32)
    return np.quantile(v, np.cumsum(LABEL_SHARES)[:-1]).astype(np.float32)


def _concept(z, w):
    return z @ w + 0.5 * np.sin(3.0 * z[:, 0]) * z[:, 1]


def _fill_block(seed, w, cuts, X, y, lo, hi):
    rng = np.random.default_rng([int(seed), 1, lo // datagen.BLOCK_ROWS])
    xb = X[lo:hi]
    rng.standard_normal(out=xb, dtype=np.float32)
    v = _concept(xb, w) + rng.logistic(size=hi - lo).astype(np.float32)
    y[lo:hi] = np.searchsorted(cuts, v).astype(np.float32)
    np.exp(xb, out=xb)


def istella_like(seed, rows, features, threads=12):
    """(X float32 (rows, features), y float32 (rows,) in 0..4, sizes
    int64 (queries,))."""
    w = datagen._concept(seed, features)
    cuts = _cuts(seed, w)
    X = np.empty((rows, features), np.float32)
    y = np.empty(rows, np.float32)
    spans = [(lo, min(lo + datagen.BLOCK_ROWS, rows))
             for lo in range(0, rows, datagen.BLOCK_ROWS)]
    with ThreadPoolExecutor(max_workers=threads) as pool:
        for f in [pool.submit(_fill_block, seed, w, cuts, X, y, lo, hi)
                  for lo, hi in spans]:
            f.result()
    sizes = query_sizes(seed, rows)
    _SIZES[rows] = sizes
    return X, y, sizes


def groups_for(rows):
    """The query sizes ``istella_like`` last made for ``rows`` rows."""
    if rows not in _SIZES:
        raise SystemExit(f"no query sizes were made for {rows} rows: the "
                         "configuration's generator is not datagen_rank's")
    return _SIZES[rows]
