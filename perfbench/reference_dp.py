"""The plain reference of ``reference.gbdt_teacher_forced`` for a table
that one chip cannot hold: the same float32 teacher-forced mathematics,
with the raw table's rows spread in equal runs over every device of the
process and the devices' sums added on the host in float64.

It imports nothing of the program and knows nothing of the program's
shards: the table may arrive whole or as a list of row runs of any
lengths (what the generator handed to ``lgb.Dataset``), and is cut anew
here by the number of devices alone.  Every device runs
``reference.build_pass`` / ``build_route`` / ``build_update`` unchanged
on its run of rows; a sum over all rows is the sum of the devices' sums,
a mean loss the row-weighted mean of theirs, a norm the root of the sum
of their squares.  The control's int4 grid has one scale a device (its
own largest gradient) where the one-chip reference has one for the
table: each device's control columns are put back into float units with
its own scale before they are added.

The comparison and every number it returns are ``gbdt_teacher_forced``'s
(reference.py's docstring says what each covers).
"""
import numpy as np

import reference as R


class RowRuns:
    """The table as its row runs: ``shape`` and ``runs[rows]`` for a
    sorted row index, which is all ``quantile_edges`` and ``host_walk``
    ask of a table."""

    def __init__(self, runs):
        self.runs = [np.asarray(a) for a in runs]
        self.starts = np.cumsum([0] + [a.shape[0] for a in self.runs])
        self.shape = (int(self.starts[-1]), self.runs[0].shape[1])

    def __getitem__(self, rows):
        rows = np.asarray(rows)
        cuts = np.searchsorted(rows, self.starts)
        return np.concatenate([a[rows[cuts[i]:cuts[i + 1]] - self.starts[i]]
                               for i, a in enumerate(self.runs)])

    def pieces(self, lo, hi):
        """The rows [lo, hi) as views into the runs, in order."""
        for i, a in enumerate(self.runs):
            s, e = max(lo, self.starts[i]), min(hi, self.starts[i + 1])
            if s < e:
                yield a[s - self.starts[i]:e - self.starts[i]]


def upload_run(table, lo, hi, rows_padded, device, step=1 << 20):
    """Rows [lo, hi) of the table on ``device`` as (features, rows_padded)
    float32, zero beyond ``hi``: ``reference.upload_transposed`` for one
    device, fed from the runs in ``step``-row pieces."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    g = table.shape[1]
    here = SingleDeviceSharding(device)
    buf = jax.jit(lambda: jnp.zeros((g, rows_padded), jnp.float32),
                  out_shardings=here)()
    put = jax.jit(lambda buf, blk, at: jax.lax.dynamic_update_slice(
        buf, blk.T, (0, at)), donate_argnums=0)
    at = 0
    for piece in table.pieces(lo, hi):
        for s in range(0, piece.shape[0], step):
            blk = np.ascontiguousarray(piece[s:s + step], np.float32)
            buf = put(buf, jax.device_put(blk, device), at)
            at += blk.shape[0]
    return buf


def gbdt_teacher_forced_dp(answer, data, cfg, seed, objective, steps=3,
                           n_sample=14, replay_rows=131072,
                           control_levels=7):
    """``reference.gbdt_teacher_forced`` over every device of the
    process: {name: value}, the control's numbers under ``control``."""
    import jax
    import jax.numpy as jnp

    X, y = data
    table = RowRuns(X if isinstance(X, (list, tuple)) else [X])
    y = np.asarray(y, np.float32)
    trees, final_scores = answer["trees"], answer["scores"]
    n, g = table.shape
    p, ref = cfg["params"], cfg["reference"]
    lr, l2, init_score = p["learning_rate"], ref["lambda_l2"], ref["init_score"]
    min_hess, min_data = p["min_sum_hessian_in_leaf"], p["min_data_in_leaf"]
    bins, max_leaves = p["max_bin"], p["num_leaves"]
    max_nodes = max_leaves - 1
    devices = jax.devices()
    per = -(-n // len(devices))                     # rows a device
    n_blocks = -(-per // R.BLOCK)
    per_pad = n_blocks * R.BLOCK
    spans = [(min(d * per, n), min((d + 1) * per, n))
             for d in range(len(devices))]
    counts = np.array([hi - lo for lo, hi in spans], np.float64)

    starts = list(answer["phase_starts"])
    phases = [range(s, min(s + steps, nxt, len(trees)))
              for s, nxt in zip(starts, starts[1:] + [len(trees)])]
    followed = {k for ph in phases for k in ph}
    phase_ends = {ph[-1] for ph in phases if len(ph)}

    def on(dev, a):
        return jax.device_put(a, dev)

    def padded(v, lo, hi):
        out = np.zeros(per_pad, np.float32)
        out[:hi - lo] = v[lo:hi]
        return out

    inf = np.full((g, 1), np.inf, np.float32)
    edges_h = np.concatenate([-inf, R.quantile_edges(table, seed, bins), inf],
                             axis=1)
    ones = np.ones(n, np.float32)
    XT, yd, valid, edges, start, scores = [], [], [], [], [], []
    for dev, (lo, hi) in zip(devices, spans):
        XT.append(upload_run(table, lo, hi, per_pad, dev))
        yd.append(on(dev, padded(y, lo, hi)))
        valid.append(on(dev, padded(ones, lo, hi)))
        edges.append(on(dev, edges_h))
        start.append(on(dev, np.full(per_pad, init_score, np.float32)))
        scores.append(jnp.tile(start[-1], (3, 1)))   # ref, program, control
    sums_pass = R.build_pass(g, n_blocks, max_nodes, max_leaves, 0,
                             bins, control_levels, objective)
    hist_pass = R.build_pass(g, n_blocks, max_nodes, max_leaves, n_sample,
                             bins, control_levels, objective)
    route = R.build_route(g, n_blocks)
    update = R.build_update(objective)
    every = range(len(devices))

    def updated(scores, leaf_idx, values):
        """The devices' scores after a tree, the table's mean loss and
        the norm of its change since the phase began, (3,) each."""
        outs = [update(scores[d], leaf_idx[d], on(devices[d], values),
                       yd[d], valid[d], start[d]) for d in every]
        loss = sum(np.asarray(o[1], np.float64) * counts[d]
                   for d, o in enumerate(outs)) / n
        moved = np.sqrt(sum(np.asarray(o[2], np.float64) ** 2 for o in outs))
        return [o[0] for o in outs], loss, moved

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
            start = [s[1] for s in scores]
            scores = [jnp.tile(s, (3, 1)) for s in start]
        feat = np.zeros(max_nodes, np.int32)
        thr = np.full(max_nodes, np.inf, np.float32)
        feat[:m] = t["split_feature"]
        thr[:m] = R._floor_f32(t["threshold"])
        paths = R.tree_paths(t, max_nodes, max_leaves)
        v_prog = t["leaf_value"] - (init_score if k == 0 else 0.0)
        values = np.zeros((3, max_leaves), np.float32)
        if k not in followed:
            leaf_idx = [route(XT[d], on(devices[d], feat), on(devices[d], thr),
                              *(on(devices[d], a) for a in paths[:3]))
                        for d in every]
            values[:, :len(v_prog)] = v_prog
            scores, _, _ = updated(scores, leaf_idx, values)
            continue
        # a phase's last followed tree also gets the histograms of its
        # first splits: under best-first growth, those of the highest gain
        n_real = min(n_sample, m) if k in phase_ends else 0
        sampled = np.arange(n_sample, dtype=np.int32) % m
        tree_pass = hist_pass if n_real else sums_pass
        outs = [tree_pass(XT[d], yd[d], valid[d], scores[d][0],
                          on(devices[d], feat), on(devices[d], thr),
                          tuple(on(devices[d], a) for a in paths),
                          edges[d], on(devices[d], sampled)) for d in every]
        leaf_idx = [o[3] for o in outs]
        leaf_sum = sum(np.asarray(o[0], np.float64)
                       for o in outs)[:t["num_leaves"]]
        node_sum = sum(np.asarray(o[1], np.float64) for o in outs)[:m]
        hist = 0.0
        for o in outs:                                   # (G, B, K, 5) each
            h9 = np.asarray(o[2], np.float64).reshape(g, bins, -1, 9)
            hist = hist + np.stack(
                [h9[..., 0:3].sum(-1), h9[..., 3:6].sum(-1), h9[..., 6],
                 h9[..., 7] * float(o[4]), h9[..., 8] * float(o[5])], axis=-1)

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
            L, Rt = t["left_child"], t["right_child"]
            return (R._gain(child(L, cg), child(L, chh), l2)
                    + R._gain(child(Rt, cg), child(Rt, chh), l2)
                    - R._gain(node_sum[:, cg], node_sum[:, chh], l2))
        exact = split_gain(0, 1)
        gfloor = np.maximum(exact, np.median(exact))
        gain_gaps.append(np.abs(t["split_gain"] - exact) / gfloor)
        ctl_gain.append(np.abs(split_gain(3, 4) - exact) / gfloor)

        # the sampled nodes: the best split on the reference's own grid,
        # and the split the control's sums would have put first
        for j in range(n_real):
            nd = sampled[j]
            parent = R._gain(node_sum[nd, 0], node_sum[nd, 1], l2)
            gains = R.grid_gains(hist[:, :, j, :], l2, min_hess, min_data)
            best = float(gains.max()) - parent
            norm = max(best, float(np.median(exact)))
            split_gaps.append(max(0.0, best - exact[nd]) / norm)
            pick = np.argmax(R.grid_gains(hist[:, :, j, :], l2, min_hess,
                                          min_data, 3, 4))
            ctl_split.append(max(0.0, best - (gains.flat[pick] - parent)) / norm)
        for row, v in enumerate((v_ref, v_prog, v_ctl)):
            values[row, :len(v)] = v
        scores, loss, moved = updated(scores, leaf_idx, values)
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
    replay = R.host_walk(trees, table[rows])
    got = np.asarray(final_scores, np.float64)[rows]
    out["score_gap"] = float(np.abs(got - replay).max()
                             / np.sqrt(np.mean(replay ** 2)))
    for name in ("leaf_count_mismatch", "score_gap"):
        ctl[name] = out[name]          # not the sums': the program's run's
    out["control"] = ctl
    out["detail"] = detail
    return out
