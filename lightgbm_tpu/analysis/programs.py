"""Registered hot entry points, lowered at small probe shapes.

The invariant checker does not scan arbitrary code — it lowers the
exact programs whose compiled form carries the repo's perf/correctness
guarantees, at the same probe shapes the HLO regression tests always
used:

- the fused train chunk (``GBDT._build_fused_chunk``) at chunk 4 and
  16 — the dispatch-auto probe sizes (r6/r7 carry + donation story),
- the per-iteration fused step (the other r7 donation-crash program),
- ``predict_level_ensemble`` at two tree counts (the r8 gather
  T-invariance claim) plus its serving-bucket shape,
- ``predict_level_ensemble_pallas`` (interpret seam) and the legacy
  ``predict_raw_ensemble`` scan kept for A/B,
- ``unpack_tree_records_device`` (the packed-carry consumer).

Building a :class:`ProgramSet` trains two tiny probe models on the CPU
seam (512x6 and 220x9 — the shapes ``tests/test_carry_hlo.py`` and
``tests/test_predict_cache.py`` pin), so one build serves every rule
and both test files.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional

# distinct-traced-signature budget per telemetry entry point for ONE
# full probe build (HLO008).  The counts are small and exact on a fresh
# process: a builder that starts retracing per call (unhashable static
# arg, shape-dependent closure) blows straight through them.
RETRACE_BOUNDS: Dict[str, int] = {
    # 2 carry probes (chunk 4, 16) + the predict-probe training run's
    # dispatch-auto ladder (probe chunks 4/16 at its own score shape,
    # the fitted chunk, and one odd-length tail chunk)
    "gbdt.fused_chunk": 6,
    # engine may fall back to per-iteration steps around chunk edges
    "gbdt.fused_step": 4,
    # T=4 / T=12 gather probes + the serving bucket (shape-shared with
    # the T=12 probe) + one slack
    "predict.level_ensemble": 4,
    "predict.level_ensemble_pallas": 2,
    "predict.binned_scan": 4,
}


class Program:
    """One lowered entry point: jaxpr + (lazy) StableHLO + (lazy)
    compiled-module text + donation flags + rule metadata."""

    def __init__(self, name: str, source: str,
                 jaxpr=None, lowered=None, stablehlo_text: str = None,
                 compiled_text: str = None,
                 meta: Optional[Dict] = None):
        self.name = name
        self.source = source            # repo-relative defining file
        self.jaxpr = jaxpr              # jax.core.Jaxpr (unclosed)
        self._lowered = lowered
        self._stablehlo = stablehlo_text
        self._compiled = compiled_text
        self.meta = dict(meta or {})

    @property
    def stablehlo(self) -> Optional[str]:
        if self._stablehlo is None and self._lowered is not None:
            self._stablehlo = self._lowered.as_text()
        return self._stablehlo

    @property
    def compiled_text(self) -> Optional[str]:
        if self._compiled is None and self._lowered is not None:
            self._compiled = self._lowered.compile().as_text()
        return self._compiled

    @property
    def donated_args(self) -> List[bool]:
        if self._lowered is None:
            return []
        import jax
        return [bool(getattr(a, "donated", False))
                for a in jax.tree_util.tree_leaves(self._lowered.args_info)]

    def __repr__(self):
        return f"<Program {self.name} ({self.source})>"


# -- probe model builders (shared with the HLO regression tests) ------------

def build_probe_gbdt(**params):
    """The carry-probe GBDT: 512x6 binary, 7 leaves — the shape
    tests/test_carry_hlo.py has pinned since round 7."""
    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config

    rng = np.random.RandomState(7)
    X = rng.randn(512, 6)
    y = (X[:, 0] + 0.4 * X[:, 1] > 0).astype(float)
    cfg = Config.from_params({"objective": "binary", "num_leaves": 7,
                              "verbose": -1, "min_data_in_leaf": 5,
                              **params})
    core = lgb.Dataset(X, label=y).construct(cfg)
    return GBDT(cfg, core)


def chunk_args(g, chunk: int):
    """Probe arguments for the fused chunk at a given chunk length."""
    import jax.numpy as jnp
    keys = jnp.zeros((chunk, 2), jnp.uint32)
    fmasks = jnp.ones((chunk, g.num_class, g.grower.num_features), bool)
    fresh = jnp.zeros(chunk, bool)
    return (g.scores, tuple(), g._full_counts > 0, keys, fmasks, fresh)


def step_args(g):
    """Probe arguments for the per-iteration fused step."""
    import jax.numpy as jnp
    key = jnp.zeros((2,), jnp.uint32)
    fmask = jnp.ones((g.num_class, g.grower.num_features), bool)
    shrink = jnp.asarray(g.shrinkage_rate, jnp.float32)
    return (g.scores, tuple(), g._full_counts > 0, key, fmask, shrink)


def train_probe_booster(f: int = 9, leaves: int = 13, iters: int = 12,
                        n: int = 220, seed: int = 0, **params):
    """The predict-probe booster: 220x9 regression, 13 leaves — the
    shape tests/test_predict_cache.py has pinned since round 8 (unique
    on purpose, so another test's jit cache entries can't mask a
    retrace count)."""
    import numpy as np

    import lightgbm_tpu as lgb

    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = X[:, 0] - 0.4 * X[:, 1]
    p = {"objective": "regression", "verbose": -1,
         "num_leaves": leaves, "min_data_in_leaf": 5, **params}
    bst = lgb.train(p, lgb.Dataset(X, label=y), iters,
                    verbose_eval=False)
    return bst, X


def level_stack(bst, t_count: int):
    """(LevelEnsemble, depth) over the first ``t_count`` trees."""
    import jax.numpy as jnp

    from lightgbm_tpu.ops.predict import LevelEnsemble
    from lightgbm_tpu.tree import flatten_ensemble

    bst._sync_models()
    flat = flatten_ensemble(bst.models[:t_count], 1)
    depth = int(flat.pop("depth"))
    return LevelEnsemble(**{k: jnp.asarray(v)
                            for k, v in flat.items()}), depth


class ProgramSet:
    """Lazy registry of the hot entry-point programs.  One instance
    builds each program (and each probe model) at most once; the
    retrace delta across all builds feeds HLO008."""

    GBDT_SRC = "lightgbm_tpu/boosting/gbdt.py"
    PREDICT_SRC = "lightgbm_tpu/ops/predict.py"

    def __init__(self):
        from lightgbm_tpu.telemetry import TELEMETRY
        self._telemetry = TELEMETRY
        self._baseline = dict(TELEMETRY.retraces())
        self._cache: Dict[str, Program] = {}
        self._gbdt = None
        self._booster = None

    # -- shared probe models ------------------------------------------
    @property
    def gbdt(self):
        if self._gbdt is None:
            self._gbdt = build_probe_gbdt()
        return self._gbdt

    @property
    def booster(self):
        if self._booster is None:
            self._booster = train_probe_booster()
        return self._booster

    # -- programs -----------------------------------------------------
    def _memo(self, name: str, build: Callable[[], Program]) -> Program:
        if name not in self._cache:
            self._cache[name] = build()
        return self._cache[name]

    def fused_chunk(self, chunk: int) -> Program:
        def build():
            import jax
            g = self.gbdt
            fn = g._build_fused_chunk(chunk)
            args = chunk_args(g, chunk)
            jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
            lowered = fn.lower(*args)
            from lightgbm_tpu.tree import TREE_RECORD_SPEC
            return Program(
                f"fused_chunk@{chunk}", self.GBDT_SRC,
                jaxpr=jaxpr, lowered=lowered,
                meta={"boost_chunk_len": chunk,
                      "multi_shape": True,
                      "record_spec_len": len(TREE_RECORD_SPEC),
                      "record_size":
                          g.grower.record_layout.record_size,
                      "packed_carry": g._packed_carry})
        return self._memo(f"fused_chunk@{chunk}", build)

    def fused_step(self) -> Program:
        def build():
            import jax
            g = self.gbdt
            if g._fused_step is None:
                g._build_fused()
            args = step_args(g)
            jaxpr = jax.make_jaxpr(
                lambda *a: g._fused_step(*a))(*args).jaxpr
            lowered = g._fused_step.lower(*args)
            return Program("fused_step", self.GBDT_SRC,
                           jaxpr=jaxpr, lowered=lowered,
                           meta={"multi_shape": True})
        return self._memo("fused_step", build)

    def predict_level(self, t_count: int) -> Program:
        def build():
            import jax
            import jax.numpy as jnp

            from lightgbm_tpu.ops.predict import predict_level_ensemble
            bst, X = self.booster
            stack, depth = level_stack(bst, t_count)
            x2 = jnp.zeros((16, 2 * X.shape[1]), jnp.float32)
            jaxpr = jax.make_jaxpr(
                lambda s, x: predict_level_ensemble(s, x, depth=depth)
            )(stack, x2).jaxpr
            lowered = predict_level_ensemble.lower(stack, x2,
                                                   depth=depth)
            return Program(
                f"predict_level@T{t_count}", self.PREDICT_SRC,
                jaxpr=jaxpr, lowered=lowered,
                meta={"gather_probe_t": t_count, "depth": depth,
                      "multi_shape": True})
        return self._memo(f"predict_level@T{t_count}", build)

    def serving_bucket(self, bucket: int = 16) -> Program:
        """The serving predictor's compiled unit: the level program at
        one power-of-two row bucket over the full probe ensemble —
        what `booster._ServingPredictor` dispatches per request."""
        def build():
            import jax
            import jax.numpy as jnp

            from lightgbm_tpu.ops.predict import predict_level_ensemble
            bst, X = self.booster
            stack, depth = level_stack(bst, 12)
            x2 = jnp.zeros((bucket, 2 * X.shape[1]), jnp.float32)
            jaxpr = jax.make_jaxpr(
                lambda s, x: predict_level_ensemble(s, x, depth=depth)
            )(stack, x2).jaxpr
            lowered = predict_level_ensemble.lower(stack, x2,
                                                   depth=depth)
            return Program(
                f"serving_bucket@{bucket}", self.PREDICT_SRC,
                jaxpr=jaxpr, lowered=lowered,
                meta={"bucket": bucket, "multi_shape": True})
        return self._memo(f"serving_bucket@{bucket}", build)

    def predict_pallas(self) -> Program:
        def build():
            import jax
            import jax.numpy as jnp

            from lightgbm_tpu.ops.predict import (
                predict_level_ensemble_pallas)
            bst, X = self.booster
            stack, depth = level_stack(bst, 12)
            x2 = jnp.zeros((16, 2 * X.shape[1]), jnp.float32)

            def fn(s, x):
                return predict_level_ensemble_pallas(
                    s, x, depth=depth, tile=16, interpret=True)
            jaxpr = jax.make_jaxpr(fn)(stack, x2).jaxpr
            lowered = predict_level_ensemble_pallas.lower(
                stack, x2, depth=depth, tile=16, interpret=True)
            return Program("predict_pallas", self.PREDICT_SRC,
                           jaxpr=jaxpr, lowered=lowered,
                           meta={"multi_shape": True})
        return self._memo("predict_pallas", build)

    def predict_scan(self) -> Program:
        def build():
            import jax
            import jax.numpy as jnp
            import numpy as np

            from lightgbm_tpu.ops.predict import (predict_raw_ensemble,
                                                  split_hi_lo,
                                                  stack_host_trees)
            bst, X = self.booster
            bst._sync_models()
            stack = stack_host_trees(bst.models)
            hi, lo = split_hi_lo(np.asarray(X[:16], np.float64))
            cls = jnp.zeros((len(bst.models),), jnp.int32)
            k_total = jnp.zeros((1, 16), jnp.float32)
            args = (stack, jnp.asarray(hi), jnp.asarray(lo), cls,
                    k_total)
            jaxpr = jax.make_jaxpr(
                lambda *a: predict_raw_ensemble(*a))(*args).jaxpr
            lowered = predict_raw_ensemble.lower(*args)
            return Program("predict_scan", self.PREDICT_SRC,
                           jaxpr=jaxpr, lowered=lowered,
                           meta={"multi_shape": True})
        return self._memo("predict_scan", build)

    def hist_tiered(self) -> Program:
        """The precision-tiered histogram tree step (round 21): a
        probe grower planned with ``hist_precision=tiered`` — the
        int32 quantized-weight accumulation plus its f32 fix-up.
        HLO009's no-f64 / no-callback surface; NOT in
        ``all_programs`` so the HLO003-008 scope is unchanged."""
        def build():
            import jax
            import numpy as np

            g = build_probe_gbdt(hist_precision="tiered",
                                 hist_kernel="pallas",
                                 force_pallas_interpret=True,
                                 max_bin=15).grower
            assert g.plan.quantized, (
                "tiered probe did not plan onto the quantized "
                "kernels — HLO009 would be checking the wrong program")
            zeros = np.zeros(g.n_padded, np.float32)
            fmask = np.ones(g.num_features, bool)
            args = (zeros, zeros, zeros, fmask, g.ohb, g.bins,
                    g.binsT, g._row_valid)
            jaxpr = jax.make_jaxpr(g._train_tree_impl)(*args).jaxpr
            lowered = jax.jit(g._train_tree_impl).lower(*args)
            return Program("hist_tiered_step",
                           "lightgbm_tpu/learner/grower.py",
                           jaxpr=jaxpr, lowered=lowered,
                           meta={"multi_shape": False})
        return self._memo("hist_tiered_step", build)

    def hist_exchange(self, mode: str = "q16") -> Program:
        """The compressed histogram exchange codec (round 21) lowered
        under a shard_map mesh — delta coding, pmax'd scale payload,
        narrow-int psum, cumsum reconstruction.  HLO009 asserts the
        codec stays device-resident (no host callback) and f32-clean;
        NOT in ``all_programs`` (same scoping as hist_tiered)."""
        def build():
            import functools

            import jax
            import jax.numpy as jnp
            import numpy as np
            from jax.sharding import Mesh
            from jax.sharding import PartitionSpec as P

            from lightgbm_tpu.learner.grower import _get_shard_map
            from lightgbm_tpu.parallel.collectives import \
                exchange_histograms

            devs = jax.devices()
            world = 2 if len(devs) >= 2 else 1
            mesh = Mesh(np.array(devs[:world]), ("data",))

            @functools.partial(_get_shard_map(), mesh=mesh,
                               in_specs=(P(),), out_specs=P())
            def fn(h):
                return exchange_histograms(h, "data", mode=mode,
                                           world=world)

            h = jnp.zeros((6, 4, 16, 3), jnp.float32)
            jaxpr = jax.make_jaxpr(fn)(h).jaxpr
            lowered = jax.jit(fn).lower(h)
            return Program(f"hist_exchange@{mode}",
                           "lightgbm_tpu/parallel/collectives.py",
                           jaxpr=jaxpr, lowered=lowered,
                           meta={"multi_shape": False,
                                 "world": world})
        return self._memo(f"hist_exchange@{mode}", build)

    def unpack_records(self) -> Program:
        def build():
            import jax
            import jax.numpy as jnp

            from lightgbm_tpu.ops.predict import (
                unpack_tree_records_device)
            g = self.gbdt
            layout = g.grower.record_layout

            def fn(rec):
                return unpack_tree_records_device(
                    rec, layout.num_leaves, layout.max_feature_bin)
            rec = jnp.zeros((4, 1, layout.record_size), jnp.uint8)
            jaxpr = jax.make_jaxpr(fn)(rec).jaxpr
            lowered = jax.jit(fn).lower(rec)
            return Program("unpack_records", self.PREDICT_SRC,
                           jaxpr=jaxpr, lowered=lowered,
                           meta={"multi_shape": False})
        return self._memo("unpack_records", build)

    # -- iteration ----------------------------------------------------
    def all_programs(self) -> List[Program]:
        return [
            self.fused_chunk(4),
            self.fused_chunk(16),
            self.fused_step(),
            self.predict_level(4),
            self.predict_level(12),
            self.serving_bucket(16),
            self.predict_pallas(),
            self.predict_scan(),
            self.unpack_records(),
        ]

    def retrace_delta(self) -> Dict[str, int]:
        """Distinct traced signatures ADDED per telemetry entry point
        since this ProgramSet was created (HLO008's measurement)."""
        now = self._telemetry.retraces()
        return {fn: n - self._baseline.get(fn, 0)
                for fn, n in now.items()
                if n - self._baseline.get(fn, 0) > 0}
