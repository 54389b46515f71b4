"""The plain reference of a ranking objective, and the comparison of a
query-grouped cell.

``lambdarank(sizes, ...)`` makes the objective ``(scores, labels) ->
(gradient, hessian, loss)`` of LightGBM's lambdarank (v2.1.1
src/objective/rank_objective.hpp, ``LambdarankNDCG``) over the queries
whose sizes are ``sizes``, their rows consecutive and in order; the
scores may carry a leading axis (the comparison's update hands three
sides at once).  It is plain ``jax.numpy`` in float32 under the highest
matmul precision and imports nothing of the program.  It works in
blocks of queries: each query is padded to the odd power of two at or
above its size, and the queries of one width are taken a block at a
time, so that one block's pairs fit.  Per query, as the source's
``GetGradientsForOneQuery`` does:

- the inverse of the best DCG at ``max_position`` of its labels
  (``DCGCalculator::CalMaxDCGAtK``, gain ``label_gain[label]``, discount
  ``1 / log2(2 + position)``);
- its documents ranked by descending score (a document's rank is the
  number of the query's documents ahead of it);
- for every pair ``(high, low)`` with ``label[high] > label[low]``:
  ``delta = score[high] - score[low]``, ``|dNDCG| = |(gain_high -
  gain_low) (discount_high - discount_low)| * inverse_max_dcg``, divided
  by ``0.01 + |delta|`` unless every score of the query is equal;
  ``p = 2 / (1 + exp(2 sigmoid delta))``; ``high`` gets ``-p |dNDCG|``
  and ``low`` the opposite, both get ``2 p (2 - p) |dNDCG|`` of hessian;
- the document's weight, if any, times both (not used here: the
  configurations have none).

Departures from v2.1.1, each deliberate:

- Ties in the ranking go by row index (a stable sort), as the program
  ranks them; v2.1.1's ``std::sort`` leaves their order unspecified.  At
  the first tree every score is equal, so this decides the first tree.
- ``p`` is computed exactly; v2.1.1 reads it from a table of 1,048,576
  bins (``sigmoid_table_``).
- The sums over a document's pairs are float32; v2.1.1 sums the high
  side in double.
- The loss, which v2.1.1 does not have (lambdarank is judged by NDCG),
  is a row's own: the logistic loss of its score against whether it is
  relevant (label > 0).  The comparison reads it only to compare one
  side's scores with another's, and a loss of one row at a time keeps
  its update of three sides at once as light as a per-row objective's
  (a loss over each query's rows makes the TPU compiler hold the three
  sides' scores in a second layout: 3.75 GB more at 7.3M rows).
"""
import numpy as np

import reference

BLOCK_PAIRS = 1 << 22


def _widths(sizes):
    """{width: the queries of at least 2 rows padded to it}: the odd
    powers of two, 2, 8, 32, ..., so that few widths are compiled."""
    w = 2 * 4 ** np.ceil(np.log(np.maximum(sizes, 2) / 2) / np.log(4))
    w = w.astype(np.int64)
    return {int(m): np.flatnonzero((w == m) & (sizes >= 2))
            for m in np.unique(w[sizes >= 2])}


def lambdarank(sizes, sigmoid=1.0, label_gain=None, max_position=20):
    import jax
    import jax.numpy as jnp

    sizes = np.asarray(sizes, np.int64)
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    if not label_gain:
        label_gain = [2.0 ** i - 1.0 for i in range(31)]
    gain_of = jnp.asarray(np.asarray(label_gain, np.float32))
    blocks = []
    for m, qs in _widths(sizes).items():
        per = max(1, BLOCK_PAIRS // (m * m))
        n_blocks = -(-len(qs) // per)
        qs = np.concatenate([qs, np.full(n_blocks * per - len(qs), -1)])
        blocks.append((m, n_blocks, per,
                       np.where(qs >= 0, starts[np.maximum(qs, 0)], 0),
                       np.where(qs >= 0, sizes[np.maximum(qs, 0)], 0)))

    def queries(s, y, m, start, size):
        """One block of queries, (B, m) each: g, h, flattened, and the
        row of each slot (past the end where there is none)."""
        pos = jnp.arange(m)
        valid = pos[None, :] < size[:, None]
        idx = jnp.where(valid, start[:, None] + pos[None, :], 0)
        sq = jnp.where(valid, s[idx], 0.0)
        lab = jnp.where(valid, y[idx], -1.0)
        gain = jnp.where(valid, gain_of[jnp.maximum(lab, 0).astype(jnp.int32)], 0.0)
        i, j = (lambda a: a[:, :, None]), (lambda a: a[:, None, :])
        earlier = pos[None, None, :] < pos[None, :, None]
        other = j(valid)

        def place(v):
            """Each document's position when the query is ordered by
            ``v``, descending, ties by row."""
            ahead = (j(v) > i(v)) | ((j(v) == i(v)) & earlier)
            return jnp.sum(ahead & other, axis=2).astype(jnp.float32)
        # inverse max DCG at max_position: the labels in descending order
        best = place(lab)
        dcg = jnp.sum(jnp.where(valid & (best < max_position),
                                gain / jnp.log2(2.0 + best), 0.0), axis=1)
        inv = jnp.where(dcg > 0, 1.0 / jnp.where(dcg > 0, dcg, 1.0), 0.0)
        # ranks by descending score, ties by row
        disc = 1.0 / jnp.log2(2.0 + place(sq))
        hi = jnp.max(jnp.where(valid, sq, -jnp.inf), axis=1)
        lo = jnp.min(jnp.where(valid, sq, jnp.inf), axis=1)
        spread = (hi != lo)[:, None, None]
        pair = (i(lab) > j(lab)) & other
        delta = i(sq) - j(sq)
        dndcg = jnp.abs((i(gain) - j(gain)) * (i(disc) - j(disc))) \
            * inv[:, None, None]
        dndcg = jnp.where(spread, dndcg / (0.01 + jnp.abs(delta)), dndcg)
        p = 2.0 / (1.0 + jnp.exp(2.0 * sigmoid * delta))
        lam = jnp.where(pair, -p * dndcg, 0.0)
        hes = jnp.where(pair, 2.0 * p * (2.0 - p) * dndcg, 0.0)
        g = lam.sum(2) - lam.sum(1)
        h = hes.sum(2) + hes.sum(1)
        flat = lambda a: a.reshape(-1)
        return flat(g), flat(h), flat(jnp.where(valid, idx, s.shape[0]))

    def one(s, y):
        out = [jnp.zeros(s.shape[0], jnp.float32) for _ in range(2)]
        for m, n_blocks, per, start, size in blocks:
            *parts, idx = jax.lax.map(
                lambda a: queries(s, y, m, a[0], a[1]),
                (jnp.asarray(start.reshape(n_blocks, per), jnp.int32),
                 jnp.asarray(size.reshape(n_blocks, per), jnp.int32)))
            idx = idx.reshape(-1)
            out = [o.at[idx].add(v.reshape(-1), mode="drop")
                   for o, v in zip(out, parts)]
        return tuple(out)

    def objective(s, y):
        with jax.default_matmul_precision("highest"):
            g, h = one(s, y) if s.ndim == 1 else jax.lax.map(
                lambda x: one(x, y), s)
            return g, h, jnp.logaddexp(0.0, s) - (y > 0) * s
    return objective


def gbdt_teacher_forced_rank(answer, data, cfg, seed, objective, **compare):
    """``reference.gbdt_teacher_forced`` with the ranking objective
    ``objective`` (a factory such as ``lambdarank``) made over the
    generator's query sizes, ``data[2]``.  Its control rounds the
    reference's gradients and hessians onto ``control_levels`` steps of
    one scale: a lambdarank hessian is long-tailed, so most of them round
    to 0."""
    X, y, sizes = data
    p = cfg["params"]
    per_query = objective(sizes, sigmoid=p.get("sigmoid", 1.0),
                          label_gain=p.get("label_gain"),
                          max_position=p.get("max_position", 20))
    out = reference.gbdt_teacher_forced(answer, (X, y), cfg, seed,
                                        per_query, **compare)
    # a leaf whose hessians the control rounds to 0 has no value (0 / 0):
    # its gaps are infinite, not NaN, so that they order with the rest
    out["control"] = {k: float("inf") if v != v else v
                      for k, v in out["control"].items()}
    return out
