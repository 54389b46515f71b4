"""The plain reference of ``reference.py`` for a table of more rows than
float32 counts: the same numbers, with a tree's pass made a super-block
of rows at a time and the super-blocks' sums added on the host in float64.

``reference.build_pass`` adds every row block's per-leaf, per-node and
sampled-node sums into float32 accumulators.  The count channel is whole
numbers, exact to 2^24: at 2^25 rows a node that holds more than that
reads a rounded count (5 of 5 followed trees had one, my chip run, PR 35:
``leaf_count_mismatch`` 5 on a sound run).  ``build_pass`` here is that
pass over ``SUPER_ROWS`` rows at a time, each super-block into float32
sums of its own (at most 2^24 rows: its counts are exact), which the host
adds in float64; the control's two scales are taken over every row first,
as there.  ``reference.build_update`` adds ``values[:, leaf_idx]`` in one
gather whose ``f32[rows, 3]`` result the TPU compiler pads to 512 bytes a
row: 16 GB at 2^25 rows (``RESOURCE_EXHAUSTED`` where ``update`` is
compiled, my chip run, PR 35; a ``lax.map`` of it over row blocks gets the
same layout).  ``build_update`` here gathers one side at a time, a vector
by a vector.  Every other line of both, and every line of the comparison,
is ``reference.py``'s, which is not edited: ``gbdt_teacher_forced_rows``
runs ``reference.gbdt_teacher_forced`` with these two in the place of its
own.  The raw float32 table lies whole on the device as (features, rows),
9.7 GB at 67 x 2^25, beside a pass's 0.7 GB of temporaries.  Plain
``jax.numpy`` float32, products exact through ``reference._dot01`` over
``reference._bf16_parts``; nothing of the program's.
"""
from unittest import mock

import numpy as np

import reference
from reference import BLOCK, HIGHEST, _bf16_parts, _dot01

#: rows a super-block: what float32 counts exactly
SUPER_ROWS = 1 << 24


def build_pass(features, n_blocks, max_nodes, max_leaves, n_sample, bins,
               control_levels, objective):
    """``reference.build_pass``: the same arguments, the same results (the
    three sums as float64 numpy arrays)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def scales(y, valid, s):
        g_all, h_all, _ = objective(s, y)
        return (jnp.max(jnp.abs(g_all * valid)) / control_levels,
                jnp.max(jnp.abs(h_all * valid)) / control_levels)

    def super_pass(count, XT, y, valid, s, feat, thr, paths, edges, sampled,
                   g_scale, h_scale, first, leaf_idx):
        """``count`` row blocks from block ``first`` on."""
        leaf_l, leaf_r, leaf_depth, node_l, node_r, node_depth = paths
        g_all, h_all, _ = objective(s, y)
        g_all, h_all = g_all * valid, h_all * valid
        gq_all, hq_all = jnp.round(g_all / g_scale), jnp.round(h_all / h_scale)

        def body(i, carry):
            leaf_sum, node_sum, hist, leaf_idx = carry
            lo = (first + i) * BLOCK
            xt = jax.lax.dynamic_slice(XT, (0, lo), (features, BLOCK))
            sl = lambda v: jax.lax.dynamic_slice(v, (lo,), (BLOCK,))
            g, h, ok, gq, hq = (sl(v) for v in (g_all, h_all, valid,
                                                gq_all, hq_all))
            left = (xt[feat] <= thr[:, None]).astype(jnp.float32)  # (M, R)
            right = 1.0 - left
            in_leaf = (_dot01(leaf_l, left) + _dot01(leaf_r, right)
                       == leaf_depth[:, None]).astype(jnp.float32)  # (L, R)
            in_node = (_dot01(node_l, left) + _dot01(node_r, right)
                       == node_depth[:, None]).astype(jnp.float32)  # (M, R)
            ch = jnp.stack([g, h, ok, gq * g_scale, hq * h_scale], axis=1)
            leaf_sum = leaf_sum + jnp.dot(in_leaf, ch, precision=HIGHEST)
            node_sum = node_sum + jnp.dot(in_node, ch, precision=HIGHEST)
            leaf_idx = jax.lax.dynamic_update_slice(
                leaf_idx, jnp.argmax(in_leaf, axis=0).astype(jnp.int32), (lo,))
            if not n_sample:
                return leaf_sum, node_sum, hist, leaf_idx
            # histograms of the sampled nodes on the reference's own grid:
            # a row is in bin b where it is above edge b-1 and not above b
            above = xt[:, None, :] > edges[:, :, None]          # (G, B+1, R)
            onehot = above[:, :-1] & ~above[:, 1:]              # (G, B, R)
            cols = jnp.stack(_bf16_parts(g) + _bf16_parts(h)
                             + [ok, gq, hq], axis=1)            # (R, 9)
            w = (in_node[sampled].astype(jnp.bfloat16)[:, :, None]
                 * cols.astype(jnp.bfloat16)[None, :, :]        # (K, R, 9)
                 ).transpose(1, 0, 2).reshape(BLOCK, n_sample * 9)
            hist = hist + jnp.einsum("gbr,rk->gbk",
                                     onehot.astype(jnp.bfloat16), w,
                                     preferred_element_type=jnp.float32)
            return leaf_sum, node_sum, hist, leaf_idx

        init = (jnp.zeros((max_leaves, 5), jnp.float32),
                jnp.zeros((max_nodes, 5), jnp.float32),
                jnp.zeros((features, bins, n_sample * 9), jnp.float32),
                leaf_idx)
        return jax.lax.fori_loop(0, count, body, init)

    super_pass = jax.jit(super_pass, static_argnums=0, donate_argnums=13)

    def tree_pass(XT, y, valid, s, feat, thr, paths, edges, sampled):
        g_scale, h_scale = scales(y, valid, s)
        sums = [0.0, 0.0, 0.0]
        leaf_idx = jnp.zeros(XT.shape[1], jnp.int32)
        step = max(1, SUPER_ROWS // BLOCK)
        for first in range(0, n_blocks, step):
            *part, leaf_idx = super_pass(
                min(step, n_blocks - first), XT, y, valid, s, feat, thr,
                paths, edges, sampled, g_scale, h_scale, first, leaf_idx)
            sums = [a + np.asarray(p, np.float64) for a, p in zip(sums, part)]
        return (*sums, leaf_idx, g_scale, h_scale)

    return tree_pass


def build_update(objective):
    """``reference.build_update``: the same arguments, the same results."""
    import jax
    import jax.numpy as jnp

    def update(scores, leaf_idx, values, y, valid, start):
        scores = scores + jnp.stack([side[leaf_idx] for side in values])
        loss = jnp.sum(valid * objective(scores, y)[2], axis=1) / jnp.sum(valid)
        moved = jnp.sqrt(jnp.sum(valid * (scores - start) ** 2, axis=1))
        return scores, loss, moved

    return jax.jit(update)


def gbdt_teacher_forced_rows(answer, data, cfg, seed, objective, **compare):
    """``reference.gbdt_teacher_forced`` with the pass and the update
    above: the numbers it returns, under the names it gives them."""
    with mock.patch.object(reference, "build_pass", build_pass), \
            mock.patch.object(reference, "build_update", build_update):
        return reference.gbdt_teacher_forced(answer, data, cfg, seed,
                                             objective, **compare)
