#!/usr/bin/env python3
"""The quickest proof that lightgbm_tpu still starts on the chip.

One process drives the main path once, through the entry points a user
calls (``lgb.Dataset``, ``lgb.train``, ``Booster.predict``,
``save_model`` / ``Booster(model_file=)``), at the full width of the
model the repo has always measured — binary, 1,000,000 x 28,
``num_leaves=255``, ``max_bin=63`` (BASELINE.json config 1) — with the
depth cut to 64 rounds and data drawn from a seed.  It fails unless JAX
reports a TPU.  It prints two JSON lines on stdout.  The LAST is the
verdict and nothing else — exactly ``{"ok": true, "device": {"platform":
"tpu", "kind": ..., "count": N}}``, the device as JAX reports it (the
driver refuses any other key there).  The line BEFORE it is the report:
``{"report": {...}}`` with the installed versions, each leg's wall /
compile seconds / compile-cache hits and misses, and the kernel plan
each leg actually ran.  Wall times are observations, not metrics: the
benchmark is bench.py.

Legs (each a function of its sizes; tests/test_chip_smoke.py runs A-C
at a tiny shape on the CPU interpret seam):

  A  fast path: bf16 + int8-quantized fused tiled Pallas kernels,
     >= 64 rounds with no valid set, so ``dispatch_chunk=auto`` takes
     its TPU-only tuning branch, then fused ``lax.scan`` chunks.
  B  default path, the way users call it: default dtype (the XLA f32
     histogram on a TPU — reported, not hidden), a valid set, device
     metrics per iteration.  Logloss must fall monotonically; A's
     held-out AUC must sit within 1e-3 of B's (bench.py's gate: the
     reference's own GPU-vs-CPU tolerance).
  C  predict: device vs host walk, bulk and bucketed small batches,
     then save -> reload -> predict bit-identical.
  D  tree_learner=data over four chips, when the host has them: the
     fast path of A on every chip, the chips' histograms added as
     integers; the model text must equal one chip's.

Any leg that raises fails the run: no leg sits inside a handler.
"""
import json
import os
import sys
import tempfile
import time

import numpy as np

ROWS, FEATURES, VALID_ROWS = 1_000_000, 28, 200_000
ROUNDS = 64          # >= 60 remaining rounds opens the auto-chunk branch
MULTICHIP_ROUNDS = 5
SMALL_BATCHES = (1, 3, 16, 40)   # below/at/above the 16-row min bucket
AUC_GATE = 1e-3
# the repo's device-vs-host predict gate (bench.py run_predict_scale,
# tests/test_predict_parity.py): f32 device accumulation vs the f64 walk
PREDICT_RTOL, PREDICT_ATOL = 2e-5, 2e-7

# BASELINE.json config 1 as bench.py runs it
BASE_PARAMS = {
    "objective": "binary", "num_leaves": 255, "max_bin": 63,
    "learning_rate": 0.1, "verbose": -1, "min_data_in_leaf": 1,
    "min_sum_hessian_in_leaf": 100.0,
    # counters only: without them oom_downshifts == 0 would be vacuous
    # and the cache hits/misses invisible; pinned to change no program
    "telemetry": "counters",
}
FAST_PARAMS = {"hist_compute_dtype": "bfloat16", "quantized_grad": True}


class CompileWatch:
    """Sums jax's own compile-duration and persistent-cache events so
    every leg can report what it compiled and what the cache served."""

    def __init__(self):
        import jax.monitoring
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def measure(self, leg, *args):
        """``leg(*args)`` -> (its result, what it cost)."""
        t0, c0, h0, m0 = time.time(), self.compile_s, self.hits, self.misses
        result = leg(*args)
        return result, {"wall_s": round(time.time() - t0, 2),
                        "compile_s": round(self.compile_s - c0, 2),
                        "cache_hits": self.hits - h0,
                        "cache_misses": self.misses - m0}


def check(ok, msg):
    """A gate, not an ``assert``: it must survive ``python -O``."""
    if not ok:
        raise AssertionError(msg)


def grower_plan(grower):
    """The kernel plan a built grower resolved to."""
    plan = grower.plan
    return {"tier": plan.tier, "quantized": plan.quantized,
            "fused": plan.fused, "interpret": plan.interpret,
            "row_shards": plan.row_shards, "block": plan.block_tiled,
            "rows_padded": int(grower.n_padded)}


def leg_fast(lgb, X, y, Xv, yv, rounds, extra=None, interpret=False):
    """Leg A.  ``interpret`` is what the plan's ``interpret`` must equal:
    False everywhere except the CPU plumbing test."""
    from bench import auc_score
    from lightgbm_tpu.backend import on_tpu
    from lightgbm_tpu.telemetry import TELEMETRY
    params = {**BASE_PARAMS, **FAST_PARAMS, **(extra or {})}
    bst = lgb.train(params, lgb.Dataset(X, label=y), rounds,
                    verbose_eval=False, keep_training_booster=True)
    g = bst.gbdt.grower
    plan = grower_plan(g)
    check(g.plan.tier == "ladder",
          f"leg A ran a downgraded kernel plan: {plan}")
    check(g.plan.interpret is interpret,
          f"interpret seam is {g.plan.interpret}")
    check(bst.num_trees() == rounds, f"{bst.num_trees()} trees")
    tel = TELEMETRY.counters()
    check(tel.get("oom_downshifts", 0) == 0, f"oom downshifts: {tel}")
    auto_chunk = TELEMETRY.gauges().get("dispatch_chunk_auto")
    if rounds >= 60:
        # the TPU-only branch of engine.train: taken exactly there
        check((auto_chunk is not None) == on_tpu(),
              f"dispatch_chunk=auto tuned to {auto_chunk}")
    auc = auc_score(yv, bst.predict(Xv, raw_score=True))
    check(np.isfinite(auc) and auc > 0.5, f"held-out AUC {auc}")
    return bst, {"plan": plan,
                 "hist_path": TELEMETRY.gauges().get("grower.hist_kernel"),
                 "dispatch_chunk_auto": auto_chunk,
                 "trees": bst.num_trees(), "auc": round(auc, 6)}


def leg_default(lgb, X, y, Xv, yv, rounds, extra=None):
    """Leg B.  Called the way users call it: default precision, a valid
    set with metrics, the returned booster released from training."""
    from bench import auc_score
    from lightgbm_tpu.telemetry import TELEMETRY
    params = {**BASE_PARAMS, "metric": ["binary_logloss", "auc"],
              **(extra or {})}
    dtrain = lgb.Dataset(X, label=y)
    evals = {}
    bst = lgb.train(params, dtrain, rounds,
                    valid_sets=[lgb.Dataset(Xv, label=yv, reference=dtrain)],
                    evals_result=evals, verbose_eval=False)
    (curves,) = evals.values()
    ll = np.asarray(curves["binary_logloss"])
    check(len(ll) == rounds and np.all(np.isfinite(ll)), f"logloss {ll}")
    check(np.all(np.diff(ll) < 0), f"logloss not monotone: {ll}")
    check(bst.num_trees() == rounds, f"{bst.num_trees()} trees")
    auc = auc_score(yv, bst.predict(Xv, raw_score=True))
    # the device metric and the host recomputation agree
    check(abs(curves["auc"][-1] - auc) < 1e-4,
          f"device AUC {curves['auc'][-1]} vs host {auc}")
    return bst, {"hist_path": TELEMETRY.gauges().get("grower.hist_kernel"),
                 "trees": bst.num_trees(), "auc": round(auc, 6),
                 "logloss_first": round(float(ll[0]), 6),
                 "logloss_last": round(float(ll[-1]), 6)}


def leg_predict(lgb, bst, Xv, small_batches=SMALL_BATCHES, interpret=False):
    """Leg C.  ``device=None`` is the user's call: on a TPU it must
    reach the device at ANY batch size (the bucketed level kernel); the
    host walk (``device=False``) is the reference."""
    from lightgbm_tpu.backend import on_tpu
    from lightgbm_tpu.ops.predict import (PREDICT_TELEMETRY,
                                          reset_predict_telemetry)
    reset_predict_telemetry()
    host = bst.predict(Xv, device=False)
    check(host.shape == (len(Xv),) and np.all(np.isfinite(host)),
          "host predictions malformed")
    auto = bst.predict(Xv)
    routed = PREDICT_TELEMETRY["dispatches"] > 0
    check(routed == on_tpu(),
          f"device=None reached the device: {routed}, on_tpu {on_tpu()}")
    # and the device path explicitly, so the CPU test covers it too
    dev = bst.predict(Xv, device=True)
    for name, got in (("auto", auto), ("device", dev)):
        np.testing.assert_allclose(got, host, rtol=PREDICT_RTOL,
                                   atol=PREDICT_ATOL, err_msg=name)
    for m in small_batches:
        np.testing.assert_allclose(
            bst.predict(Xv[:m], device=True), host[:m],
            rtol=PREDICT_RTOL, atol=PREDICT_ATOL, err_msg=f"batch {m}")
    pred = bst._serving_predictor(bst.num_trees())
    check(pred.interpret is interpret,
          f"serving predictor interpret seam is {pred.interpret}")
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "model.txt")
        bst.save_model(path)
        reloaded = lgb.Booster(model_file=path)
    again = reloaded.predict(Xv, device=False)
    check(np.array_equal(again, host),
          f"reloaded model differs: {np.max(np.abs(again - host))}")
    np.testing.assert_allclose(reloaded.predict(Xv, device=True), host,
                               rtol=PREDICT_RTOL, atol=PREDICT_ATOL,
                               err_msg="reloaded device")
    return {"kernel": pred.kernel, "rows": len(Xv),
            "small_batches": list(small_batches),
            "buckets": sorted(PREDICT_TELEMETRY["buckets"]),
            "max_abs_dev_vs_host": float(np.max(np.abs(dev - host)))}


def leg_multichip(lgb, X, y, Xv, rounds, n_chips=4, extra=None,
                  interpret=False):
    """Leg D: ``tree_learner=data`` over ``n_chips`` devices on the
    fast path — every row shard runs the quantized fused ladder inside
    ``shard_map`` and the shards' int32 accumulators are added exactly
    (``hist_kernel=pallas`` makes a program that cannot do so raise
    instead of running the XLA formulation under this leg's name).

    The gate is identity: the integer sum does not depend on how many
    chips hold the rows, so the model text equals one chip's on the
    same rows and parameters, character for character.  Stochastic
    rounding is on, so the per-row draws are under the gate too."""
    from lightgbm_tpu.telemetry import TELEMETRY
    fast = {**BASE_PARAMS, **FAST_PARAMS, "quant_stochastic_rounding": 1,
            **(extra or {})}
    params = {**fast, "tree_learner": "data", "hist_kernel": "pallas",
              "mesh_shape": (n_chips,), "mesh_axes": ("data",)}
    # SPMD partitioner warnings are C++ logging on fd 2
    with tempfile.TemporaryFile(mode="w+") as cap:
        saved = os.dup(2)
        os.dup2(cap.fileno(), 2)
        try:
            bst = lgb.train(params, lgb.Dataset(X, label=y), rounds,
                            verbose_eval=False, keep_training_booster=True)
        finally:
            os.dup2(saved, 2)
            os.close(saved)
        cap.seek(0)
        stderr = cap.read()
    sys.stderr.write(stderr)
    check("Involuntary full rematerialization" not in stderr,
          "SPMD involuntary full rematerialization")
    g = bst.gbdt.grower
    check(g.policy.mesh is not None and g.policy.mesh.size == n_chips,
          f"mesh is {g.policy.mesh}")
    plan = grower_plan(g)
    gauges = TELEMETRY.gauges()
    check(g.plan.tier == "ladder" and g.plan.row_shards == n_chips
          and g.plan.interpret is interpret,
          f"leg D ran a downgraded kernel plan under the mesh: {plan}")
    check(gauges.get("grower.quantized") == 1
          and gauges.get("grower.hist_kernel") == "fused_tiled",
          f"leg D's gauges: {gauges.get('grower.quantized')}, "
          f"{gauges.get('grower.hist_kernel')}")
    kernels = chunk_kernel_names(bst.gbdt)
    check(any(k.startswith("compute_group_histograms_fused_")
              for k in kernels),
          f"no fused histogram kernel in the mesh's chunk program: "
          f"{kernels}")
    shards = g.bins.addressable_shards
    devices = {s.device for s in shards}
    check(len(devices) == n_chips, f"bins sit on {devices}")
    check(len({np.asarray(s.data).tobytes() for s in shards}) > 1,
          "row shards are identical: replicated, not sharded")
    check(sum(s.data.shape[0] for s in shards) == g.n_padded,
          "row shards do not add up to the padded row count")
    serial = lgb.train({**fast, "tree_learner": "serial"},
                       lgb.Dataset(X, label=y), rounds, verbose_eval=False)
    check(bst.model_to_string() == serial.model_to_string(),
          f"{n_chips}-chip model text differs from one chip's on the "
          "same rows")
    delta = np.abs(bst.predict(Xv, device=False)
                   - serial.predict(Xv, device=False))
    return {"mesh": n_chips, "devices": sorted(str(d) for d in devices),
            "plan": plan, "kernels": kernels,
            "rows_padded": int(g.n_padded), "trees": bst.num_trees(),
            "model_text_equal_one_chip": True,
            "max_abs_vs_one_chip": float(delta.max())}


def chunk_kernel_names(gbdt):
    """Names of the ``pallas_call`` sites in the booster's chunk program
    (its jaxpr, sub-jaxprs included), at the chunk length it last
    dispatched, or 1 where it dispatched single iterations."""
    import jax
    import jax.numpy as jnp
    from jax._src import core as jax_core
    names = set()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.add(str(eqn.params["name"]))
            for sub in jax_core.jaxprs_in_params(eqn.params):
                walk(sub)
    n = gbdt._fused_chunk_n[0] if gbdt._fused_chunk_n else 1
    fmasks = jnp.ones((n, gbdt.num_class, gbdt.grower.num_features), bool)
    walk(jax.make_jaxpr(gbdt._build_fused_chunk(n))(
        gbdt.scores, tuple(), gbdt._full_counts > 0,
        jnp.zeros((n, 2), jnp.uint32), fmasks, jnp.zeros(n, bool),
        gbdt.grower.ohb, gbdt._build_captives()).jaxpr)
    return sorted(names)


def verdict(ok, device):
    """The last stdout line, to the driver's contract: exactly the keys
    ``ok`` and ``device``, and in ``device`` exactly ``platform``,
    ``kind``, ``count``.  Everything else belongs in the report line."""
    return json.dumps({"ok": bool(ok), "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"])}})


def main():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (jax reports platform "
              f"{dev.platform!r}); nothing was run", file=sys.stderr)
        return 1
    from importlib.metadata import version

    import lightgbm_tpu as lgb
    from bench import make_data
    from lightgbm_tpu import native

    watch = CompileWatch()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    out = {"device": device,
           "versions": {p: version(p) for p in ("jax", "jaxlib", "libtpu")},
           "shape": {"rows": ROWS, "features": FEATURES,
                     "valid_rows": VALID_ROWS, "rounds": ROUNDS,
                     "num_leaves": BASE_PARAMS["num_leaves"],
                     "max_bin": BASE_PARAMS["max_bin"]},
           "legs": {}}
    t_all = time.time()
    X, y, w = make_data(ROWS, FEATURES)
    Xv, yv, _ = make_data(VALID_ROWS, FEATURES, seed=8, w=w)

    legs = out["legs"]
    (_, rec_a), cost = watch.measure(leg_fast, lgb, X, y, Xv, yv, ROUNDS)
    legs["A_fast"] = {**rec_a, **cost}

    (bst_b, rec_b), cost = watch.measure(leg_default, lgb, X, y, Xv, yv,
                                         ROUNDS)
    legs["B_default"] = {**rec_b, **cost}
    delta = abs(rec_a["auc"] - rec_b["auc"])
    check(delta <= AUC_GATE,
          f"quantized AUC drifted {delta} from the default path")
    out["auc_delta"] = round(delta, 6)

    rec, cost = watch.measure(leg_predict, lgb, bst_b, Xv)
    legs["C_predict"] = {**rec, **cost}

    n_dev = jax.device_count()
    if n_dev >= 4:
        rec, cost = watch.measure(leg_multichip, lgb, X, y, Xv,
                                  MULTICHIP_ROUNDS)
        legs["D_multichip"] = {**rec, **cost}
    else:
        out["multichip"] = f"not run: {n_dev} device(s)"

    # a failed g++ build bins in Python: visible here, not hidden
    out["binner"] = "python: " + native.build_error \
        if native.build_error else "native"
    out["compile_cache_dir"] = jax.config.jax_compilation_cache_dir
    out["compile_s"] = round(watch.compile_s, 2)
    out["cache_hits"], out["cache_misses"] = watch.hits, watch.misses
    out["wall_s"] = round(time.time() - t_all, 2)
    print(json.dumps({"report": out}))
    print(verdict(True, device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
