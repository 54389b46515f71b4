"""The faults only a query-grouped cell can have, planted like
``faults.py``'s underneath the harness: in the program's own class, for
the length of a ``with``.  Each hands the program query sizes other than
the table's; the reference keeps the generator's."""
import contextlib

import numpy as np


@contextlib.contextmanager
def _groups(change):
    from lightgbm_tpu.dataset import Metadata
    real = Metadata.set_group

    def broken(self, group):
        if group is not None:
            group = change(np.asarray(group, np.int64).copy())
        return real(self, group)
    Metadata.set_group = broken
    try:
        yield
    finally:
        Metadata.set_group = real


def query_shifted():
    """Every query boundary one row later: the first query one row
    longer, the last one shorter, and every other query has lost its
    first row to the query before it."""
    def change(g):
        g[0] += 1
        g[-1] -= 1
        return g
    return _groups(change)


def queries_merged():
    """Each two neighbouring queries as one: pairs across the two, one
    ranking and one best DCG for both."""
    def change(g):
        odd = g[len(g) // 2 * 2:]
        return np.concatenate([g[0:len(g) // 2 * 2:2] + g[1:len(g) // 2 * 2:2],
                               odd])
    return _groups(change)


FAULTS = {"query_shifted": query_shifted, "queries_merged": queries_merged}
