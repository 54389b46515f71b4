"""Traffic kinds.  A cell's traffic is ``perfbench/traffic/<name>.json``:
``{"kind": <one of KINDS>, ...parameters}``; a new cell of a kind that is
here is a new data file and nothing else.

A kind gets ``(lgb, jax, traffic, params, ds, seconds, tracer)``, calls
``tracer.start()`` where set-up ends and ``tracer.stop()`` where the window
closes, and returns

    t_setup_end, t_window   host clock: end of set-up, (start, end) of the window
    attempted, failed       units of work offered in the window, and lost
    end_to_end              {metric: value} of every end-to-end metric but setup_s
    answer                  what the timed path produced, for the comparison
    work                    what the window did, for the per-layer readers
    model_sha256            optional: a digest of the model, printed under
                            ``info`` (one table gives one model on every seed)

``train_steady``: steady-state training.  Set-up calls the public entry
once, ``lgb.train(params, ds, num_boost_round=c,
keep_training_booster=True)`` with no callback, so that the headless
chunked loop runs, compiles the chunk program and grows the first ``c``
trees.  The window continues THAT booster through the call
``engine.train``'s own loop makes, ``booster.gbdt.train_chunk(c)``, chunk
after chunk, waiting for the scores after each, until ``--seconds`` have
passed; it ends on a chunk boundary.
"""
import hashlib
import time

import numpy as np

import modeltext


def train_steady(lgb, jax, traffic, params, ds, seconds, tracer):
    c = int(traffic["dispatch_chunk"])
    params = dict(params, dispatch_chunk=c)
    bst = lgb.train(params, ds, num_boost_round=c, verbose_eval=False,
                    keep_training_booster=True)
    jax.block_until_ready(bst.gbdt.scores)
    mark = tracer.start()                 # set-up ends here
    t0 = time.perf_counter()
    chunks = 0
    failed = 0
    while True:
        try:
            stop = bst.gbdt.train_chunk(c)
            jax.block_until_ready(bst.gbdt.scores)
        except Exception as e:            # a failed dispatch is counted
            print(f"chunk {chunks} failed: {e!r}", flush=True)
            failed += c
            stop = True
        chunks += 1
        t1 = time.perf_counter()
        if stop or t1 - t0 >= seconds:
            break
    tracer.stop()                         # the memory peak is read here
    n_window = chunks * c
    text = bst.model_to_string()
    trees = modeltext.parse(text)
    scores = np.asarray(bst.gbdt.scores)[0, :ds.num_data()].astype(np.float64)
    window_trees = trees[c:c + n_window]
    failed += sum(1 for t in window_trees if t["num_leaves"] <= 1) \
        + n_window - len(window_trees)    # a tree that did not grow is lost
    return {"t_setup_end": mark, "t_window": (t0, t1),
            "attempted": n_window, "failed": min(failed, n_window),
            "end_to_end": {"train_ms_per_tree": 1e3 * (t1 - t0) / n_window},
            "answer": {"trees": trees, "scores": scores,
                       "phase_starts": [0, c]},
            "work": {"trees": window_trees, "n_trees": n_window},
            "model_sha256": hashlib.sha256(text.encode()).hexdigest()}


KINDS = {"train_steady": train_steady}
