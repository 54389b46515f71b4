"""The plain reference of ``reference.py`` for a table too wide for its
histogram pass: the same numbers, with a sampled node's histograms made a
block of features at a time.

``reference.build_pass`` builds the (feature, bin, row) one-hot of a whole
row block at once: 280 MB in bfloat16 at 67 features, 8.4 GB at 2,000.
``build_pass`` here is that pass with the one-hot and its product made for
``feature_block`` features at a time and written into their rows of the
same (feature, bin, sampled node x 9) sums; every other line of it, and
every line of the comparison, is ``reference.py``'s, which is not edited:
``gbdt_teacher_forced_wide`` runs ``reference.gbdt_teacher_forced`` with
this pass in the place of its own.  Plain ``jax.numpy`` float32 over the
raw table, products exact through ``reference._dot01`` over
``reference._bf16_parts``; nothing of the program's.
"""
from unittest import mock

import reference
from reference import BLOCK, HIGHEST, _bf16_parts, _dot01

#: bytes of the bfloat16 (features, bins, rows) one-hot a block may take
ONEHOT_BYTES = 256 << 20


def feature_block(features, bins):
    """The most features, dividing ``features``, whose one-hot of a row
    block stays under ``ONEHOT_BYTES``."""
    most = max(1, ONEHOT_BYTES // (bins * BLOCK * 2))
    return max(d for d in range(1, min(most, features) + 1)
               if features % d == 0)


def build_pass(features, n_blocks, max_nodes, max_leaves, n_sample, bins,
               control_levels, objective):
    """``reference.build_pass``: the same arguments, the same results."""
    import jax
    import jax.numpy as jnp

    fb = feature_block(features, bins)

    def tree_pass(XT, y, valid, s, feat, thr, paths, edges, sampled):
        leaf_l, leaf_r, leaf_depth, node_l, node_r, node_depth = paths
        g_all, h_all, _ = objective(s, y)
        g_all, h_all = g_all * valid, h_all * valid
        g_scale = jnp.max(jnp.abs(g_all)) / control_levels
        h_scale = jnp.max(jnp.abs(h_all)) / control_levels
        gq_all, hq_all = jnp.round(g_all / g_scale), jnp.round(h_all / h_scale)

        def body(i, carry):
            leaf_sum, node_sum, hist, leaf_idx = carry
            lo = i * BLOCK
            sl = lambda v: jax.lax.dynamic_slice(v, (lo,), (BLOCK,))
            g, h, ok, gq, hq = (sl(v) for v in (g_all, h_all, valid,
                                                gq_all, hq_all))
            xt = jax.lax.dynamic_slice(XT, (0, lo), (features, BLOCK))
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
            cols = jnp.stack(_bf16_parts(g) + _bf16_parts(h)
                             + [ok, gq, hq], axis=1)            # (R, 9)
            w = (in_node[sampled].astype(jnp.bfloat16)[:, :, None]
                 * cols.astype(jnp.bfloat16)[None, :, :]        # (K, R, 9)
                 ).transpose(1, 0, 2).reshape(BLOCK, n_sample * 9)

            def some_features(j, hist):
                f0 = j * fb
                xb = jax.lax.dynamic_slice(xt, (f0, 0), (fb, BLOCK))
                ed = jax.lax.dynamic_slice(edges, (f0, 0), (fb, bins + 1))
                # a row is in bin b where it is above edge b-1 and not above b
                above = xb[:, None, :] > ed[:, :, None]         # (fb, B+1, R)
                onehot = above[:, :-1] & ~above[:, 1:]          # (fb, B, R)
                part = jnp.einsum("gbr,rk->gbk",
                                  onehot.astype(jnp.bfloat16), w,
                                  preferred_element_type=jnp.float32)
                at = (f0, 0, 0)
                return jax.lax.dynamic_update_slice(
                    hist, jax.lax.dynamic_slice(hist, at, part.shape) + part,
                    at)
            hist = jax.lax.fori_loop(0, features // fb, some_features, hist)
            return leaf_sum, node_sum, hist, leaf_idx

        init = (jnp.zeros((max_leaves, 5), jnp.float32),
                jnp.zeros((max_nodes, 5), jnp.float32),
                jnp.zeros((features, bins, n_sample * 9), jnp.float32),
                jnp.zeros(XT.shape[1], jnp.int32))
        return jax.lax.fori_loop(0, n_blocks, body, init) + (g_scale, h_scale)

    return jax.jit(tree_pass)


def gbdt_teacher_forced_wide(answer, data, cfg, seed, objective, **compare):
    """``reference.gbdt_teacher_forced`` with the pass above: the numbers
    it returns, under the names it gives them."""
    with mock.patch.object(reference, "build_pass", build_pass):
        return reference.gbdt_teacher_forced(answer, data, cfg, seed,
                                             objective, **compare)
