"""The plain reference of one boosting step, and the comparison that
decides ``correct``.

It imports nothing of the program and takes nothing the program has made
except its answer: the trees as the public model text states them and
the training scores it ended with.  It is plain ``jax.numpy`` in float32
with every sum exact to float32; no kernel, no bin matrix of the
program's, no quantized gradient.

Teacher-forced, as a served model is checked against its reference with
the served tokens: a tree's splits are the program's decisions.  Given
them, everything else of a boosting step is determined by the data and
the configuration, and the reference works it out from the raw float32
table: the gradients at its own scores, which rows reach which node
(``x <= threshold`` on raw values), each node's and leaf's sums, each
leaf's value ``-lr * G / (H + lambda_l2)`` (compared as the gradient sum
G it implies, against that leaf's G or the median leaf's, whichever is
larger), the exact gain of every split the program chose, and, for a sample of nodes drawn from the seed, the
best gain any split on the reference's own candidate grid (its own
quantile bins of the raw data) would have had there.

It follows ``steps`` trees from each of the answer's ``phase_starts``:
the first trees of the run, which set-up's dispatch grew, from the
initial score with its own scores; and the first trees of the measured
window from the scores that its own replay of every earlier tree (the
program's leaf values, routed by the reference) leaves.  Every tree of
the run is checked once more through the scores: on rows drawn from the
seed a second, independent walk (numpy on the host) replays them all and
must land where the program's own training scores are.

The control is the reference in the program's place one precision down:
the same sums with gradients and hessians rounded onto ``control_levels``
steps a side (int4 under the configuration's int8).  What does not depend
on the sums (the partition's counts, the replay of the scores) it has
from the program's run, so that it is judged by the same limits.
"""
import numpy as np

BLOCK = 8192
HIGHEST = "highest"


# -- the tree as arrays the device pass can use -------------------------
def _floor_f32(thr):
    """Largest float32 <= thr: ``x <= thr`` on float32 data is then the
    same predicate in float32 as in the model's float64."""
    t = np.asarray(thr, np.float64).astype(np.float32)
    up = t.astype(np.float64) > thr
    return np.where(up, np.nextafter(t, np.float32(-np.inf)), t)


def tree_paths(tree, max_nodes, max_leaves):
    """Ancestor matrices: row j of ``*_left``/``*_right`` marks the nodes
    at which a row must go left/right to reach leaf (or node) j."""
    m = len(tree["left_child"])
    leaf_l = np.zeros((max_leaves, max_nodes), np.float32)
    leaf_r = np.zeros((max_leaves, max_nodes), np.float32)
    node_l = np.zeros((max_nodes, max_nodes), np.float32)
    node_r = np.zeros((max_nodes, max_nodes), np.float32)
    leaf_depth = np.full(max_leaves, -1.0, np.float32)   # -1: no such leaf
    node_depth = np.full(max_nodes, -1.0, np.float32)
    if m == 0:
        leaf_depth[0] = 0
        return leaf_l, leaf_r, leaf_depth, node_l, node_r, node_depth
    stack = [(0, [], [])]
    while stack:
        node, lefts, rights = stack.pop()
        node_l[node, lefts] = 1
        node_r[node, rights] = 1
        node_depth[node] = len(lefts) + len(rights)
        for child, ls, rs in ((tree["left_child"][node], lefts + [node], rights),
                              (tree["right_child"][node], lefts, rights + [node])):
            if child >= 0:
                stack.append((int(child), ls, rs))
            else:
                leaf_l[~child, ls] = 1
                leaf_r[~child, rs] = 1
                leaf_depth[~child] = len(ls) + len(rs)
    return leaf_l, leaf_r, leaf_depth, node_l, node_r, node_depth


def quantile_edges(X, seed, bins, sample=200_000):
    """The reference's own candidate thresholds: per feature the
    ``bins - 1`` interior quantiles of rows drawn from the seed."""
    rng = np.random.default_rng([int(seed), 0xED6E])
    rows = rng.choice(X.shape[0], size=min(sample, X.shape[0]), replace=False)
    q = np.linspace(0, 1, bins + 1)[1:-1]
    return np.quantile(X[np.sort(rows)], q, axis=0).T.astype(np.float32)


# -- objectives -----------------------------------------------------------
def binary_logloss(s, y):
    """Per row: gradient, hessian and loss of the log loss at raw score
    ``s`` (sigmoid 1), as LightGBM's ``binary`` objective states them."""
    import jax
    import jax.numpy as jnp
    p = jax.nn.sigmoid(s)
    return p - y, p * (1.0 - p), jnp.logaddexp(0.0, s) - y * s


OBJECTIVES = {"binary_logloss": binary_logloss}


# -- the device pass ------------------------------------------------------
def _bf16_parts(v):
    """A float32 vector as three bfloat16 ones whose sum is ``v`` to
    float32's last bit, so that a product with a 0/1 operand in bfloat16
    with float32 sums is as exact as a float32 one at three passes."""
    import jax.numpy as jnp
    parts = []
    for _ in range(3):
        p = v.astype(jnp.bfloat16)
        parts.append(p)
        v = v - p.astype(jnp.float32)
    return parts


def _dot01(a, b):
    """``a @ b`` for operands that bfloat16 holds exactly (0/1 masks,
    small integers, ``_bf16_parts``), summed in float32."""
    import jax.numpy as jnp
    return jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                   preferred_element_type=jnp.float32)


def build_pass(features, n_blocks, max_nodes, max_leaves, n_sample, bins,
               control_levels, objective):
    """One jitted pass over all rows for one tree.  Returns per-row leaf
    index, the sums over leaves and nodes with last axis (G, H, count,
    Gq, Hq), and the (feature, bin, sampled node, 9) histograms on the
    reference's own grid with last axis (G in three parts, H in three
    parts, count, Gq, Hq in units of their scales), and the two scales.
    With ``n_sample`` 0 it makes no histograms.
    The q columns are the control's: gradients rounded onto
    ``control_levels`` steps a side, one scale for the whole vector."""
    import jax
    import jax.numpy as jnp

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
                jnp.zeros(XT.shape[1], jnp.int32))
        return jax.lax.fori_loop(0, n_blocks, body, init) + (g_scale, h_scale)

    return jax.jit(tree_pass)


def build_route(features, n_blocks):
    """The leaf each row reaches, for a tree that is only replayed."""
    import jax
    import jax.numpy as jnp

    def route(XT, feat, thr, leaf_l, leaf_r, leaf_depth):
        def body(i, leaf_idx):
            lo = i * BLOCK
            xt = jax.lax.dynamic_slice(XT, (0, lo), (features, BLOCK))
            left = (xt[feat] <= thr[:, None]).astype(jnp.float32)
            in_leaf = (_dot01(leaf_l, left) + _dot01(leaf_r, 1.0 - left)
                       == leaf_depth[:, None])
            return jax.lax.dynamic_update_slice(
                leaf_idx, jnp.argmax(in_leaf, axis=0).astype(jnp.int32), (lo,))
        return jax.lax.fori_loop(0, n_blocks, body,
                                 jnp.zeros(XT.shape[1], jnp.int32))

    return jax.jit(route)


def build_update(objective):
    """Scores after the tree by the reference's leaf values, by the
    program's and by the control's."""
    import jax
    import jax.numpy as jnp

    def update(scores, leaf_idx, values, y, valid, start):
        """``scores`` and ``values`` are (3, ...): the reference's, the
        program's and the control's.  Returns the new scores, each
        side's mean loss and the norm of its change since ``start``."""
        scores = scores + values[:, leaf_idx]
        loss = jnp.sum(valid * objective(scores, y)[2], axis=1) / jnp.sum(valid)
        moved = jnp.sqrt(jnp.sum(valid * (scores - start) ** 2, axis=1))
        return scores, loss, moved

    return jax.jit(update)


def upload_transposed(X, rows_padded):
    """The raw table on the device as (features, rows): float32 rows are
    then lanes, and 67 features pad to 72 sublanes, not to 128 lanes."""
    import jax
    import jax.numpy as jnp
    n, g = X.shape
    put = jax.jit(lambda buf, blk, lo: jax.lax.dynamic_update_slice(
        buf, blk.T, (0, lo)), donate_argnums=0)
    buf = jnp.zeros((g, rows_padded), jnp.float32)
    step = 1 << 20
    for lo in range(0, n, step):
        blk = X[lo:lo + step]
        if blk.shape[0] < step and lo + step <= rows_padded:
            blk = np.concatenate(
                [blk, np.zeros((step - blk.shape[0], g), np.float32)])
        buf = put(buf, jnp.asarray(blk), lo)
    return buf


# -- host side ------------------------------------------------------------
def _gain(G, H, l2):
    return G * G / (H + l2)


def grid_gains(hist, l2, min_hess, min_data, cg=0, chh=1):
    """``gain(left) + gain(right)`` of every (feature, edge) of one node's
    (G, B, 5) histogram, by the sums in columns ``cg``, ``chh``; -inf where
    a child would be left under its minimum hessian or rows."""
    np.seterr(divide="ignore", invalid="ignore")   # empty bins: masked below
    cum = np.cumsum(hist.astype(np.float64), axis=1)[:, :-1, :]   # x <= edge
    rest = hist.astype(np.float64).sum(axis=1, keepdims=True) - cum
    ok = ((cum[..., chh] >= min_hess) & (rest[..., chh] >= min_hess)
          & (cum[..., 2] >= min_data) & (rest[..., 2] >= min_data))
    return np.where(ok, _gain(cum[..., cg], cum[..., chh], l2)
                    + _gain(rest[..., cg], rest[..., chh], l2), -np.inf)


def host_walk(trees, X):
    """Sum of every tree's leaf value per row of raw ``X``: a plain
    level-by-level walk, independent of the device pass above."""
    out = np.zeros(X.shape[0], np.float64)
    rows = np.arange(X.shape[0])
    for t in trees:
        if len(t["left_child"]) == 0:
            out += t["leaf_value"][0]
            continue
        node = np.zeros(X.shape[0], np.int64)
        live = rows
        while live.size:
            nd = node[live]
            go_left = X[live, t["split_feature"][nd]].astype(np.float64) \
                <= t["threshold"][nd]
            nxt = np.where(go_left, t["left_child"][nd], t["right_child"][nd])
            node[live] = nxt
            live = live[nxt >= 0]
        out += t["leaf_value"][~node]
    return out


def gbdt_teacher_forced(answer, data, cfg, seed, objective, steps=3,
                        n_sample=14, replay_rows=131072, control_levels=7):
    """Every number the comparison reads, as {name: value}, and the same
    numbers for the control under ``control``."""
    import jax.numpy as jnp

    X, y = data
    trees, final_scores = answer["trees"], answer["scores"]
    n, g = X.shape
    p, ref = cfg["params"], cfg["reference"]
    lr, l2, init_score = p["learning_rate"], ref["lambda_l2"], ref["init_score"]
    min_hess, min_data = p["min_sum_hessian_in_leaf"], p["min_data_in_leaf"]
    bins, max_leaves = p["max_bin"], p["num_leaves"]
    max_nodes = max_leaves - 1
    n_blocks = -(-n // BLOCK)
    n_pad = n_blocks * BLOCK
    # a phase's followed trees: ``steps`` from its start, cut where the
    # next phase starts or the run ends
    starts = list(answer["phase_starts"])
    phases = [range(s, min(s + steps, nxt, len(trees)))
              for s, nxt in zip(starts, starts[1:] + [len(trees)])]
    followed = {k for ph in phases for k in ph}
    phase_ends = {ph[-1] for ph in phases if len(ph)}

    XT = upload_transposed(X, n_pad)
    pad = lambda v: jnp.asarray(np.concatenate(
        [v.astype(np.float32), np.zeros(n_pad - n, np.float32)]))
    yd, valid = pad(y), pad(np.ones(n, np.float32))
    inf = np.full((g, 1), np.inf, np.float32)
    edges = jnp.asarray(np.concatenate(
        [-inf, quantile_edges(X, seed, bins), inf], axis=1))
    start = jnp.full(n_pad, init_score, jnp.float32)
    scores = jnp.tile(start, (3, 1))                  # ref, program, control
    sums_pass = build_pass(g, n_blocks, max_nodes, max_leaves, 0,
                           bins, control_levels, objective)
    hist_pass = build_pass(g, n_blocks, max_nodes, max_leaves, n_sample,
                           bins, control_levels, objective)
    route = build_route(g, n_blocks)
    update = build_update(objective)

    out = {"leaf_count_mismatch": 0, "loss_gap": 0.0, "update_norm_gap": 0.0}
    ctl = {"loss_gap": 0.0, "update_norm_gap": 0.0}
    leaf_gaps, gain_gaps, split_gaps = [], [], []
    ctl_leaf, ctl_gain, ctl_split = [], [], []
    detail = []
    for k in range(max(followed) + 1):
        t = trees[k]
        m = len(t["left_child"])
        if m == 0:
            raise ValueError(f"tree {k} has no split: nothing to follow")
        if k in starts and k > 0:
            # a later phase starts from the replay of every earlier tree
            start = scores[1]
            scores = jnp.tile(start, (3, 1))
        feat = np.zeros(max_nodes, np.int32)
        thr = np.full(max_nodes, np.inf, np.float32)
        feat[:m] = t["split_feature"]
        thr[:m] = _floor_f32(t["threshold"])
        paths = tuple(jnp.asarray(a) for a in tree_paths(t, max_nodes, max_leaves))
        v_prog = t["leaf_value"] - (init_score if k == 0 else 0.0)
        values = np.zeros((3, max_leaves), np.float32)
        if k not in followed:
            leaf_idx = route(XT, jnp.asarray(feat), jnp.asarray(thr), *paths[:3])
            values[:, :len(v_prog)] = v_prog
            scores, _, _ = update(scores, leaf_idx, jnp.asarray(values),
                                  yd, valid, start)
            continue
        # a phase's last followed tree also gets the histograms of its
        # first splits: under best-first growth, those of the highest gain
        n_real = min(n_sample, m) if k in phase_ends else 0
        sampled = np.arange(n_sample, dtype=np.int32) % m
        leaf_sum, node_sum, hist, leaf_idx, g_scale, h_scale = (
            hist_pass if n_real else sums_pass)(
            XT, yd, valid, scores[0], jnp.asarray(feat), jnp.asarray(thr), paths,
            edges, jnp.asarray(sampled))
        leaf_sum = np.asarray(leaf_sum, np.float64)[:t["num_leaves"]]
        node_sum = np.asarray(node_sum, np.float64)[:m]
        h9 = np.asarray(hist, np.float64).reshape(g, bins, -1, 9)
        hist = np.stack([h9[..., 0:3].sum(-1), h9[..., 3:6].sum(-1), h9[..., 6],
                         h9[..., 7] * float(g_scale), h9[..., 8] * float(h_scale)],
                        axis=-1)                              # (G, B, K, 5)

        # leaf values and counts
        v_ref = -lr * leaf_sum[:, 0] / (leaf_sum[:, 1] + l2)
        v_ctl = -lr * leaf_sum[:, 3] / (leaf_sum[:, 4] + l2)
        # the gap of a leaf's value is the gap of the gradient sum it
        # implies on the reference's hessian, measured against that
        # leaf's gradient sum or the median leaf's, whichever is larger
        floor = np.maximum(np.abs(v_ref), lr * np.median(np.abs(leaf_sum[:, 0]))
                           / (leaf_sum[:, 1] + l2))
        lv = np.abs(v_prog - v_ref) / floor
        leaf_gaps.append(lv)
        ctl_leaf.append(np.abs(v_ctl - v_ref) / floor)
        out["leaf_count_mismatch"] += int(
            (np.rint(leaf_sum[:, 2]).astype(np.int64) != t["leaf_count"]).sum()
            + (np.rint(node_sum[:, 2]).astype(np.int64) != t["internal_count"]).sum())

        # exact gain of every split the program chose, against the gain
        # it recorded (which came out of its own histograms)
        def child(c, col):
            return np.where(c >= 0, node_sum[np.maximum(c, 0), col],
                            leaf_sum[np.where(c >= 0, 0, ~c), col])

        def split_gain(cg, chh):
            L, R = t["left_child"], t["right_child"]
            return (_gain(child(L, cg), child(L, chh), l2)
                    + _gain(child(R, cg), child(R, chh), l2)
                    - _gain(node_sum[:, cg], node_sum[:, chh], l2))
        exact = split_gain(0, 1)
        gfloor = np.maximum(exact, np.median(exact))
        gain_gaps.append(np.abs(t["split_gain"] - exact) / gfloor)
        ctl_gain.append(np.abs(split_gain(3, 4) - exact) / gfloor)

        # the sampled nodes: the best split on the reference's own grid,
        # and the split the control's sums would have put first
        for j in range(n_real):
            nd = sampled[j]
            parent = _gain(node_sum[nd, 0], node_sum[nd, 1], l2)
            gains = grid_gains(hist[:, :, j, :], l2, min_hess, min_data)
            best = float(gains.max()) - parent
            norm = max(best, float(np.median(exact)))
            split_gaps.append(max(0.0, best - exact[nd]) / norm)
            pick = np.argmax(grid_gains(hist[:, :, j, :], l2, min_hess,
                                        min_data, 3, 4))
            ctl_split.append(max(0.0, best - (gains.flat[pick] - parent)) / norm)
        for row, v in enumerate((v_ref, v_prog, v_ctl)):
            values[row, :len(v)] = v
        scores, loss, moved = update(scores, leaf_idx, jnp.asarray(values),
                                     yd, valid, start)
        loss, moved = np.asarray(loss, np.float64), np.asarray(moved, np.float64)
        for into, row in ((out, 1), (ctl, 2)):
            into["loss_gap"] = max(into["loss_gap"],
                                   abs(loss[row] - loss[0]) / loss[0])
        if k in phase_ends:
            # the change of the per-row state over the phase, by its norm
            for into, row in ((out, 1), (ctl, 2)):
                into["update_norm_gap"] = max(
                    into["update_norm_gap"], abs(moved[row] - moved[0]) / moved[0])
        worst = int(np.argmax(lv))
        detail.append({"tree": k, "leaves": int(t["num_leaves"]),
                       "loss_ref": loss[0], "loss_prog": loss[1],
                       "moved_ref": moved[0], "moved_prog": moved[1],
                       "worst_leaf": {"leaf": worst, "gap": float(lv[worst]),
                                      "v_prog": float(v_prog[worst]),
                                      "v_ref": float(v_ref[worst]),
                                      "rows": leaf_sum[worst, 2]}})
    del XT, scores, start

    def spread(parts, into, stem):
        v = np.concatenate([np.atleast_1d(p) for p in parts])
        into[stem + "_median"] = float(np.median(v))
        into[stem + "_p90"] = float(np.quantile(v, 0.9))
        into[stem + "_worst"] = float(v.max())
    for parts, cparts, stem in ((leaf_gaps, ctl_leaf, "leaf_value_gap"),
                                (gain_gaps, ctl_gain, "recorded_gain_gap"),
                                (split_gaps, ctl_split, "split_choice_gap")):
        spread(parts, out, stem)
        spread(cparts, ctl, stem)

    # every tree of the run, through the scores it left
    rows = np.sort(np.random.default_rng([int(seed), 0x4E91]).choice(
        n, size=min(replay_rows, n), replace=False))
    # (the model text folds the initial score into the first tree)
    replay = host_walk(trees, X[rows])
    got = np.asarray(final_scores, np.float64)[rows]
    out["score_gap"] = float(np.abs(got - replay).max()
                             / np.sqrt(np.mean(replay ** 2)))
    for name in ("leaf_count_mismatch", "score_gap"):
        ctl[name] = out[name]          # not the sums': the program's run's
    out["control"] = ctl
    out["detail"] = detail
    return out


COMPARISONS = {"gbdt_teacher_forced": gbdt_teacher_forced}
