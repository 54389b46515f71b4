"""The fault only a cell over several chips can have, planted like
``faults.py``'s underneath the harness, in the program's own module, for
the length of a ``with``."""
import contextlib


@contextlib.contextmanager
def shard_dropped(shard=1):
    """One chip's histogram left out of every cross-chip sum: the exact
    exchange adds the accumulators of all shards but ``shard``, whose
    rows then count for nothing in any split or leaf value (they are
    still routed, and still scored)."""
    import jax

    from lightgbm_tpu.parallel import collectives
    real = collectives.exchange_int_histograms

    def broken(acc, axis_name, **kw):
        keep = jax.lax.axis_index(axis_name) != shard
        return real(acc * keep.astype(acc.dtype), axis_name, **kw)
    collectives.exchange_int_histograms = broken
    try:
        yield
    finally:
        collectives.exchange_int_histograms = real


FAULTS = {"shard_dropped": shard_dropped}
