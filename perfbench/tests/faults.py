"""The faults a training cell can have, planted underneath the harness:
in the program's own classes, for the length of a ``with``.  Used by
test_correct.py at a small size on the CPU and by readings.py at
the cell's own size on the chip."""
import contextlib

import numpy as np


@contextlib.contextmanager
def state_unchanged(setup_calls=1):
    """A step that returns its state unchanged: after set-up's calls,
    ``train_chunk`` records its trees and puts the old scores back."""
    from lightgbm_tpu.boosting.gbdt import GBDT
    real = GBDT.train_chunk
    calls = {"n": 0}

    def broken(self, n_iters):
        calls["n"] += 1
        before = self.scores
        stop = real(self, n_iters)
        if calls["n"] > setup_calls:
            self.scores = before
        return stop
    GBDT.train_chunk = broken
    try:
        yield
    finally:
        GBDT.train_chunk = real


@contextlib.contextmanager
def half_batch():
    """Half of the batch left out, the mean taken over the rest: the second
    half of the rows gets weight 0, so no histogram, sum or leaf value sees it."""
    import lightgbm_tpu as lgb
    real = lgb.Dataset

    def halved(data, label=None, **kw):
        w = np.ones(len(label), np.float32)
        w[len(w) // 2:] = 0.0
        return real(data, label=label, weight=w, **kw)
    lgb.Dataset = halved
    try:
        yield
    finally:
        lgb.Dataset = real


@contextlib.contextmanager
def altered_answer(factor=2.0):
    """An answer altered where it is produced: when the trees are brought to
    the host, the last tree's fullest leaf gets ``factor`` times its value."""
    from lightgbm_tpu.boosting.gbdt import GBDT
    real = GBDT.flush_models
    done = {"n": 0}

    def broken(self, final=False):
        real(self, final)
        if self.models and not done["n"]:
            t = self.models[-1]
            t.leaf_value[int(np.argmax(t.leaf_count))] *= factor
            done["n"] = 1
    GBDT.flush_models = broken
    try:
        yield
    finally:
        GBDT.flush_models = real


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "altered_answer": altered_answer}
