"""The traffic kind of a query-grouped training cell:
``traffic_rank:train_rank_steady``.

It is ``traffic.train_steady`` on a Dataset that has its query groups:
the harness constructs ``lgb.Dataset(X, label=y)``, and this kind attaches
the sizes the configuration's generator made (``datagen_rank.groups_for``)
with ``Dataset.set_group`` before the first ``lgb.train``.

Set-up grows its first trees as ``engine.train``'s headless loop does, in
two calls: ``lgb.train(..., num_boost_round=0, keep_training_booster=True)``
builds the booster and the objective's layout, and the booster's own
``gbdt.train_chunk`` then grows them.  Between the two the kind reads the
gauge ``objective.rank_buckets``: a program whose ranking objective does
not group its queries into length buckets pads every query to the
longest, and on a LETOR split that is minutes a tree, so the run stops
there, before any tree, with an error.
"""
import types

import datagen_rank
import traffic


def train_rank_steady(lgb, jax, traffic_mix, params, ds, seconds, tracer):
    from lightgbm_tpu.telemetry import TELEMETRY
    ds.set_group(datagen_rank.groups_for(ds.num_data()))

    def train(params, ds, num_boost_round, **kw):
        bst = lgb.train(params, ds, num_boost_round=0, **kw)
        if TELEMETRY.gauges().get("objective.rank_buckets") is None:
            raise SystemExit(
                "the program's lambdarank objective sets no gauge "
                "objective.rank_buckets: it pads every query to the "
                "longest, which this cell's table cannot afford")
        bst.gbdt.train_chunk(num_boost_round)
        return bst
    return traffic.train_steady(types.SimpleNamespace(train=train), jax,
                                traffic_mix, params, ds, seconds, tracer)
