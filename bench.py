"""Benchmark: Higgs-like binary training throughput on one chip.

Prints ONE JSON line.  Top-level fields describe the primary (1M-row)
point; ``scales`` carries BOTH measured scales — the 1M iteration
point and the HIGGS-true-scale 10.5M point (the round-2 verdict:
the headline regime must be proven at the baseline's actual scale,
where the resident one-hot only fits HBM because of the sub-byte
packing; docs/ROOFLINE.md).

Speed without an accuracy gate is not evidence: the quantized path's
held-out AUC is measured against the f32 path at the primary scale and
must stay within 1e-3 (the reference's own GPU-vs-CPU tolerance,
docs/GPU-Performance.rst:136-161).

Baseline derivation (BASELINE.md): the reference trains HIGGS
(10.5M rows x 28 features, 500 iters, 255 leaves) in 238.51 s on a
2x E5-2670v3 — 4.543e-8 s per (tree x row).  Each scale trains a
synthetic 28-feature binary task with the GPU-table config (63 bins,
255 leaves — docs/GPU-Performance.rst:108); vs_baseline =
scaled_reference_time / ours (>1 means faster than the reference CPU).

Honest economics: ``value`` is the warm per-tree extrapolation;
``prep_s``/``compile_s``/``cold_total_s`` are what a cold run pays.

Env knobs: BENCH_ROWS/BENCH_ITERS (primary), BENCH_ROWS_BIG/
BENCH_ITERS_BIG (big scale; BENCH_BIG=0 disables), BENCH_SKIP_F32=1
skips the f32 accuracy rerun, BENCH_PARAMS='{...}' overrides params,
BENCH_LEAVES/BENCH_MAX_BIN shrink the tree shape (smoke runs).
Serving bench knobs (BENCH_PREDICT=0 disables the predict scale):
BENCH_PREDICT_TRAIN_ROWS/BENCH_PREDICT_ITERS shape the served model,
BENCH_PREDICT_ROWS the bulk-throughput batch,
BENCH_PREDICT_SMALL_BATCH/BENCH_PREDICT_CALLS the p50 micro-batch
loop, BENCH_PREDICT_ANCHOR_ROWS the reference task=predict anchor.
Construction bench knobs (round 11; BENCH_CONSTRUCT=0 disables):
BENCH_CONSTRUCT_ROWS sizes the cold-construct point (default
min(BENCH_ROWS, 1M)); BENCH_LOCAL_REF_CONSTRUCT=0 skips just the
reference CSV-load anchor.
Local-reference knobs: BENCH_LOCAL_REF=0 disables all same-machine
reference runs; BENCH_LOCAL_REF_BIG=0 / BENCH_LOCAL_REF_LTR=0 /
BENCH_LOCAL_REF_PREDICT=0 disable just the 10.5M / lambdarank /
task=predict anchors (each costs minutes of 1-core CSV write +
reference wall-clock); BENCH_REF_ITERS / BENCH_REF_ITERS_BIG /
BENCH_REF_ITERS_LTR set the differenced iteration counts (defaults
30/10/10).

Budget discipline (round-5 verdict weak #1/#3: the r5 bench blew the
driver's wall-clock limit re-measuring fixed-binary anchors and died
with rc=124 before its own NDCG gate ran): BENCH_BUDGET_S (default
900) is a TOTAL wall-clock budget.  Local-reference anchors are
measured ONCE per (task, scale, params, data-seed, threads) and
persisted to the checked-in LOCAL_REF.json; later invocations reuse
the record instead of re-running the single-threaded reference binary.
An anchor that must run fresh is time-boxed to the remaining budget
minus a finishing reserve and skipped WITH A NOTE in the JSON on
overrun.  BENCH_LOCAL_REF_REFRESH=1 forces re-measurement.

Round-8 extension: the budget now bounds EVERY phase, not just the
anchors (the r5 driver record — `rc 124, parsed null` — came
from the 10.5M lightgbm_tpu MEASUREMENT run itself blowing the outer
driver timeout after the anchors were budgeted).  Each optional scale
is admitted against the measured primary-scale wall: the big scale is
scaled DOWN to rows that fit the remaining budget (with a
`scaled_down_from` note) or skipped with a note; the lambdarank and
predict scales skip with a note when their estimate doesn't fit.  A
budget skip is a note; a phase that FAILS (an exception, a quality
gate — AUC drift, NDCG floor, predict parity) exits non-zero.

Device discipline (PR 21): every scale runs in THIS process — a chip
belongs to one process at a time, so a parent that has trained on it
cannot hand a scale to a child.  The JSON names the device it ran on
(``device``: platform / device_kind / count) and a run that finds no
TPU exits non-zero unless the caller asked for the CPU plumbing run
explicitly with JAX_PLATFORMS=cpu (scripts/bench_smoke.sh does).
"""
import gc
import json
import os
import sys
import time

import numpy as np

BENCH_ROWS = int(os.environ.get("BENCH_ROWS", 1_000_000))
BENCH_FEATURES = 28
BENCH_ITERS = int(os.environ.get("BENCH_ITERS", 100))
BENCH_ROWS_BIG = int(os.environ.get("BENCH_ROWS_BIG", 10_500_000))
BENCH_ITERS_BIG = int(os.environ.get("BENCH_ITERS_BIG", 100))
VALID_ROWS = int(os.environ.get("BENCH_VALID_ROWS", 200_000))
NUM_LEAVES = int(os.environ.get("BENCH_LEAVES", 255))
MAX_BIN = int(os.environ.get("BENCH_MAX_BIN", 63))
REF_SEC_PER_TREE_ROW = 238.51 / (500 * 10_500_000)

BENCH_BUDGET_S = float(os.environ.get("BENCH_BUDGET_S", 900))
# wall-clock reserved for the bench's own remaining work after any
# fresh anchor run (the finishing reserve a time-boxed anchor must
# not eat into)
ANCHOR_RESERVE_S = float(os.environ.get("BENCH_ANCHOR_RESERVE_S", 120))
# wall-clock reserved for emitting the JSON + diagnostics after the
# last admitted phase (round 8; hoisted to module scope in round 13
# so the primary admission can read it too)
FINISH_RESERVE_S = float(os.environ.get("BENCH_FINISH_RESERVE_S", 60))
_T0 = time.time()

LOCAL_REF_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "LOCAL_REF.json")


def device_record() -> dict:
    """The device this process measures on, as JAX reports it.  A run
    that finds no TPU fails unless the caller set JAX_PLATFORMS=cpu
    itself: timing XLA-CPU under TPU metric names is the failure this
    refuses (the CPU run is a plumbing check, scripts/bench_smoke.sh)."""
    import jax
    dev = jax.devices()[0]
    rec = {"platform": dev.platform, "device_kind": dev.device_kind,
           "count": len(jax.devices())}
    if dev.platform != "tpu" \
            and os.environ.get("JAX_PLATFORMS", "").lower() != "cpu":
        raise SystemExit(
            f"bench.py found no TPU (jax reports {rec}); set "
            "JAX_PLATFORMS=cpu to run the CPU plumbing check on purpose")
    return rec


def budget_left() -> float:
    return BENCH_BUDGET_S - (time.time() - _T0)


def _host_tag() -> str:
    """Coarse host-hardware identity for anchor keys: the anchor is a
    SAME-MACHINE measurement, so a record must not be served to a
    different CPU (same-model hosts — e.g. the same chip-host across
    container restarts — correctly share)."""
    model = ""
    try:
        with open("/proc/cpuinfo") as f:
            for ln in f:
                if ln.lower().startswith("model name"):
                    model = ln.split(":", 1)[1].strip()
                    break
    except OSError:
        import platform
        model = platform.processor() or platform.machine()
    return "".join(c if c.isalnum() else "_" for c in model)[:48] or "cpu"


def _local_ref_key(task, rows, iters, seed, params, threads) -> str:
    """Anchor cache key: the reference binary is fixed, so a record is
    valid as long as (task shape, generated data, training params,
    thread count, host CPU model) match."""
    return (f"{task}:rows={rows}:iters={iters}:seed={seed}"
            f":nl={params['num_leaves']}:mb={params['max_bin']}"
            f":lr={params['learning_rate']}"
            f":mdl={params['min_data_in_leaf']}"
            f":msh={params['min_sum_hessian_in_leaf']}"
            f":threads={threads}:host={_host_tag()}")


def _local_ref_load() -> dict:
    try:
        with open(LOCAL_REF_PATH) as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


_EXPECTED_KEY_FIELDS = frozenset(
    ("rows", "iters", "seed", "nl", "mb", "lr", "mdl", "msh",
     "threads", "host"))
_REQUIRED_RECORD_FIELDS = ("per_tree_ms", "threads", "iters")
# task=predict anchors time the reference's batch scorer, not
# training: rows/s replaces per-tree time and no quality metric rides
# along (the parity gate lives in the lightgbm_tpu predict scale)
_REQUIRED_PREDICT_FIELDS = ("rows_per_s", "threads", "iters")
# task=construct anchors time the reference binary's load+bin of the
# same CSV (a num_iterations=1 run — dataset construction dominates);
# no quality metric rides along, parity is gated on the lightgbm_tpu
# side by byte-equality between its own construction paths
_REQUIRED_CONSTRUCT_FIELDS = ("construct_s", "threads", "iters")
_LOCAL_REF_NOTES: list = []
_LOCAL_REF_BAD: set = set()


def validate_local_ref():
    """Anchor-cache validation at bench startup (round 7): every
    LOCAL_REF.json record's key must parse into exactly the CURRENT
    key field set (_local_ref_key) and its payload must carry the
    schema the ratios read — a record written by an older/newer key
    format, or measured on a different host CPU, emits a skip-note
    instead of silently anchoring this run.  Returns
    (notes, bad_keys); bad keys are never served."""
    data = _local_ref_load()
    notes, bad = [], set()
    host = _host_tag()
    for key, rec in data.items():
        if key == "_schema":          # documentation entry, not a record
            continue
        parts = str(key).split(":")
        if parts[0] == "bench_wall":
            # round-13 primary-admission record (this bench's OWN
            # measured wall on this host, not a reference anchor):
            # its key is bench_wall:host=<tag> and its payload the
            # per-(row*iter) unit — own schema, own validation
            fields = dict(p.split("=", 1) for p in parts[1:]
                          if "=" in p)
            if set(fields) != {"host", "nl", "mb"} \
                    or not isinstance(rec, dict) \
                    or "unit_s_per_row_iter" not in rec:
                notes.append(f"bench_wall record {key!r}: schema "
                             "drift — record ignored")
                bad.add(key)
            continue
        fields = {}
        ok_parse = len(parts) >= 2
        for p in parts[1:]:
            if "=" not in p:
                ok_parse = False
                break
            k, v = p.split("=", 1)
            fields[k] = v
        if not ok_parse or set(fields) != _EXPECTED_KEY_FIELDS:
            missing = sorted(_EXPECTED_KEY_FIELDS - set(fields))
            extra = sorted(set(fields) - _EXPECTED_KEY_FIELDS)
            notes.append(
                f"anchor key {key!r}: key-set drift (missing fields "
                f"{missing}, unexpected {extra}) — record ignored; "
                "re-measure with BENCH_LOCAL_REF_REFRESH=1")
            bad.add(key)
            continue
        if parts[0] == "predict":
            schema_ok = (isinstance(rec, dict)
                         and ("skipped" in rec
                              or all(f in rec
                                     for f in _REQUIRED_PREDICT_FIELDS)))
        elif parts[0] == "construct":
            schema_ok = (isinstance(rec, dict)
                         and ("skipped" in rec
                              or all(f in rec
                                     for f in
                                     _REQUIRED_CONSTRUCT_FIELDS)))
        else:
            schema_ok = (isinstance(rec, dict)
                         and ("skipped" in rec
                              or (all(f in rec
                                      for f in _REQUIRED_RECORD_FIELDS)
                                  and ("auc" in rec
                                       or "ndcg10" in rec))))
        if not schema_ok:
            notes.append(
                f"anchor {key!r}: record schema drift (expected "
                f"{list(_REQUIRED_RECORD_FIELDS)} + auc|ndcg10) — "
                "record ignored")
            bad.add(key)
            continue
        if fields["host"] != host:
            notes.append(
                f"anchor {key!r}: measured on host CPU "
                f"{fields['host']!r}, this host is {host!r} — kept "
                "for that host, cannot anchor this run")
    return notes, bad


def _local_ref_store(key: str, record: dict) -> None:
    data = _local_ref_load()
    data[key] = record
    try:
        with open(LOCAL_REF_PATH, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
            f.write("\n")
    except OSError as e:  # read-only checkout: reuse still works
        print(f"could not persist local-ref anchor ({e})",
              file=sys.stderr)


def make_data(n, f, seed=7, w=None):
    """Synthetic binary task.  ``w`` (the concept) defaults to a draw
    from the same stream — pass the training run's w for a held-out
    sample of the SAME concept."""
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f).astype(np.float32)
    if w is None:
        w = rng.randn(f) * (rng.rand(f) > 0.3)
    logit = X[:, :f] @ w + 0.5 * np.sin(3 * X[:, 0]) * X[:, 1]
    y = (logit + rng.logistic(size=n) > 0).astype(np.float32)
    return X.astype(np.float64), y, w


def auc_score(y, s):
    """Tie-aware AUC (numpy; rank-sum formulation)."""
    order = np.argsort(s, kind="mergesort")
    ranks = np.empty_like(order, dtype=np.float64)
    sorted_s = s[order]
    n = len(s)
    i = 0
    while i < n:
        j = i
        while j + 1 < n and sorted_s[j + 1] == sorted_s[i]:
            j += 1
        ranks[order[i:j + 1]] = 0.5 * (i + j) + 1.0
        i = j + 1
    pos = y > 0
    np_ = pos.sum()
    nn = n - np_
    if np_ == 0 or nn == 0:
        return float("nan")
    return float((ranks[pos].sum() - np_ * (np_ + 1) / 2) / (np_ * nn))


def _bench_telemetry():
    """The bench consumes the RUNTIME telemetry counters instead of
    private timers (round-9 tentpole): ``train_chunk`` itself records
    host_dispatch_ms (time-to-return of the async enqueue) and — with
    the fence enabled — device_wait_ms, so the numbers printed here
    and the numbers a production run exports via ``telemetry=spans``
    come from ONE code path (docs/OBSERVABILITY.md, bench-vs-runtime
    equivalence).  Mode is only ever raised, never lowered, so a
    BENCH_PARAMS telemetry override survives."""
    from lightgbm_tpu.telemetry import TELEMETRY
    if not TELEMETRY.on:
        TELEMETRY.configure("counters")
    TELEMETRY.set_fence(True)
    return TELEMETRY


def timed_chunks(gbdt, iters, chunk):
    """Run the warm training loop in ``chunk``-sized fused dispatches
    with the wall clock SPLIT into host/dispatch time (how long each
    train_chunk call takes to RETURN — the async enqueue) and device wait
    (the per-chunk fence up to the drain), both read from the
    telemetry counters train_chunk maintains.  The split is what
    tracks ROOFLINE headroom #3 (the ≈1-2 ms/tree host gap) as a
    series.  Returns the timing dict shared by every bench scale."""
    tm = _bench_telemetry()

    def counters():
        c = tm.counters()
        # iteration (not tree) count: per_tree/trees_total keep the
        # pre-r9 per-ITERATION denominator — trees_dispatched scales
        # by num_class and would shift the series on a multiclass scale
        return (c.get("host_dispatch_ms", 0.0),
                c.get("device_wait_ms", 0.0),
                c.get("iterations", 0))

    def drain():
        np.asarray(gbdt.scores[:, :8])

    t0 = time.time()
    gbdt.train_chunk(chunk)
    drain()
    compile_s = time.time() - t0
    n_chunks = max(1, (iters - chunk) // chunk)
    h0, d0, n0 = counters()
    t0 = time.time()
    for _ in range(n_chunks):
        gbdt.train_chunk(chunk)
    drain()
    steady_s = time.time() - t0
    h1, d1, n1 = counters()
    host_s = (h1 - h0) / 1e3
    device_s = (d1 - d0) / 1e3
    trees = (n1 - n0) or n_chunks * chunk
    return {
        "compile_s": compile_s,
        "steady_s": steady_s,
        "per_tree": steady_s / trees,
        "trees_total": trees + chunk,
        "host_dispatch_s": host_s,
        "device_wait_s": device_s,
        "host_ms_per_tree": host_s / trees * 1e3,
        "device_ms_per_tree": device_s / trees * 1e3,
    }


def chunk_slope_probe(gbdt, probes=(4, 16)):
    """Fit the per-iteration chunk-slope series the r6 diagnosis
    tracks, with this host's measured dispatch cost.  Delegates to
    GBDT.tune_dispatch_chunk — the
    dispatch_chunk=auto implementation — so the bench reports exactly
    what auto would fit, including its compile-discard double pass,
    return-vs-drain split and early-stop handling.  Consumes 2·Σprobes
    real training iterations."""
    chunk, info = gbdt.tune_dispatch_chunk(probes=probes)
    probe_ms = {str(c): round(t * 1e3, 3)
                for c, t in info.get("probe_per_tree_s", {}).items()}
    if info.get("stopped") or "slope_s_per_iter" not in info:
        return {"stopped": True, "probe_per_tree_ms": probe_ms}
    base, slope = info["base_s"], info["slope_s_per_iter"]
    return {
        "probe_per_tree_ms": probe_ms,
        "base_ms": round(base * 1e3, 3),
        "slope_ms_per_iter": round(slope * 1e3, 4),
        "host_dispatch_ms": round(info["dispatch_s"] * 1e3, 2),
        "auto_pick_local": chunk,
    }


def train_timed(cfg_params, X, y, iters):
    """Train ``iters`` trees; returns (gbdt, cfg, dtrain, prep_s,
    timing dict — see timed_chunks)."""
    import lightgbm_tpu as lgb
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config

    cfg = Config.from_params(cfg_params)
    t0 = time.time()
    dtrain = lgb.Dataset(X, label=y)
    core = dtrain.construct(cfg)
    prep_s = time.time() - t0
    gbdt = GBDT(cfg, core)

    chunk = max(1, min(int(os.environ.get("BENCH_CHUNK", 10)),
                       iters // 2))
    timing = timed_chunks(gbdt, iters, chunk)
    # the economics a first-time user actually pays: dataset prep +
    # first (compiling) chunk + the remaining chunks, as measured —
    # NOT the warm per-tree extrapolation the headline `value` reports
    timing["cold_total_s"] = prep_s + timing["compile_s"] \
        + timing["steady_s"]
    return gbdt, cfg, dtrain, prep_s, timing


def attach_timing(out: dict, timing: dict) -> dict:
    """Copy the host/device wall split (and the chunk-slope fit when
    the probe ran) from a timed_chunks dict into a scale record — the
    series ROOFLINE headroom #3 tracks.

    ``timing_source`` marks the round-9 semantics change for series
    continuity: the split now comes from the telemetry counters with a
    per-chunk device fence, so the steady wall is host + device with
    NO chunk overlap (the pre-r9 loop enqueued all chunks back-to-back
    and drained once, hiding host dispatch under device execution on a
    pipelined backend) — compare r9+ per_tree against r8 anchors with
    that in mind."""
    out["host_dispatch_ms_per_tree"] = round(
        timing["host_ms_per_tree"], 3)
    out["device_wait_ms_per_tree"] = round(
        timing["device_ms_per_tree"], 3)
    out["timing_source"] = "telemetry_fenced"
    if "chunk_slope" in timing:
        out["chunk_slope"] = timing["chunk_slope"]
    return out


def heldout_scores(gbdt, cfg, vbins_np):
    """Raw scores of the trained ensemble on a held-out binned matrix,
    computed on device AFTER timing (one scan per pending tree stack;
    packed-carry stacks unpack their byte records inside the scan)."""
    import jax
    import jax.numpy as jnp
    from lightgbm_tpu.ops.predict import (predict_binned,
                                          unpack_tree_records_device)

    g = gbdt.grower
    vbins = jnp.asarray(vbins_np)
    shrink = gbdt.shrinkage_rate

    def acc(total, tr):
        pv = predict_binned(tr, vbins, g.f_group, g.g2f_lut,
                            g.f_missing, g.f_default_bin, g.f_num_bin,
                            max_steps=cfg.num_leaves)
        return total + shrink * pv

    @jax.jit
    def acc_stack(total, stack):
        out, _ = jax.lax.scan(lambda c, tr: (acc(c, tr), None),
                              total, stack)
        return out

    @jax.jit
    def acc_recs(total, recs):
        def body(carry, rec):
            tr = unpack_tree_records_device(rec, cfg.num_leaves,
                                            g.max_feature_bin)
            return acc(carry, tr), None
        out, _ = jax.lax.scan(body, total, recs)
        return out

    total = jnp.full(vbins.shape[0], gbdt.init_score, jnp.float32)
    for p in gbdt._pending:
        assert p[0] in ("stack", "rstack"), "bench expects chunked training"
        if p[0] == "rstack":
            for k in range(p[1].shape[1]):
                total = acc_recs(total, p[1][:, k])
        else:
            for stack in p[1]:
                total = acc_stack(total, stack)
    return np.asarray(total)


REF_LTR_SEC_PER_TREE_ROW = 215.32 / (500 * 2_270_296)  # MS-LTR row,
# docs/Experiments.rst:108-145 (2,270,296 rows, 500 trees, 215.32 s)


def attach_local_ref(out, ref, per_tree):
    """Fold a run_local_reference record + measured ratio into a scale
    dict (shared by the flat scales and the lambdarank scale).  A
    skip record lands as ``local_ref_skipped`` so the JSON documents
    WHY the anchor is absent (budget box, missing binary, ...)."""
    if ref is None:
        return out
    if "skipped" in ref:
        out["local_ref_skipped"] = ref["skipped"]
        return out
    out["local_ref"] = ref
    out["vs_local_reference"] = round(
        (ref["per_tree_ms"] / 1e3) / per_tree, 3)
    return out


def make_ltr_data(n_queries, f=136, seed=11, docs_lo=60, docs_hi=180,
                  w=None):
    """Synthetic MS-LTR-shaped ranking task: variable-size queries,
    graded 0-4 relevance from a noisy latent score with a per-query
    offset (so ranking within queries is learnable but absolute scores
    are not)."""
    rng = np.random.RandomState(seed)
    sizes = rng.randint(docs_lo, docs_hi + 1, size=n_queries)
    n = int(sizes.sum())
    X = rng.randn(n, f).astype(np.float32)
    if w is None:
        w = (rng.randn(f) * (rng.rand(f) > 0.5)).astype(np.float32)
    latent = X @ w + np.repeat(rng.randn(n_queries) * 2.0, sizes) \
        + rng.randn(n).astype(np.float32) * 2.0
    # graded labels by global quantiles (MS-LTR-like skew toward 0)
    qs = np.quantile(latent, [0.55, 0.78, 0.90, 0.97])
    y = np.digitize(latent, qs).astype(np.float32)
    return X.astype(np.float64), y, sizes, w


def ndcg_at_k(y, s, sizes, k=10):
    """Mean NDCG@k over queries (gain 2^label - 1, log2 discounts)."""
    out = []
    start = 0
    for sz in sizes:
        yl = y[start:start + sz]
        sl = s[start:start + sz]
        start += sz
        kk = min(k, sz)
        order = np.argsort(-sl, kind="stable")[:kk]
        gains = 2.0 ** yl[order] - 1
        disc = 1.0 / np.log2(np.arange(2, kk + 2))
        dcg = float(np.sum(gains * disc))
        best = np.sort(yl)[::-1][:kk]
        idcg = float(np.sum((2.0 ** best - 1) * disc))
        out.append(dcg / idcg if idcg > 0 else 0.0)
    return float(np.mean(out))


def run_ltr_scale():
    """Lambdarank perf point at MS-LTR shape (round-3 verdict #8): the
    per-query pairwise kernels get a wall-clock number, gated on
    held-out NDCG@10 actually learning the synthetic concept."""
    import lightgbm_tpu as lgb

    n_queries = int(os.environ.get("BENCH_LTR_QUERIES", 18_900))
    iters = int(os.environ.get("BENCH_LTR_ITERS", 30))
    X, y, sizes, w = make_ltr_data(n_queries)
    Xv, yv, sizes_v, _ = make_ltr_data(2000, seed=12, w=w)
    rows = X.shape[0]

    params = {
        "objective": "lambdarank", "num_leaves": NUM_LEAVES,
        "max_bin": MAX_BIN, "learning_rate": 0.1, "verbose": -1,
        "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 100.0,
        "hist_compute_dtype": "bfloat16",
        "quantized_grad": os.environ.get("BENCH_QUANTIZED", "1") != "0",
    }
    extra = os.environ.get("BENCH_PARAMS")
    if extra:
        params.update(json.loads(extra))
    from lightgbm_tpu.boosting.gbdt import GBDT
    from lightgbm_tpu.config import Config
    cfg = Config.from_params(params)
    t0 = time.time()
    dtrain = lgb.Dataset(X, label=y, group=sizes)
    core = dtrain.construct(cfg)
    prep_s = time.time() - t0
    gbdt = GBDT(cfg, core)

    chunk = max(1, min(int(os.environ.get("BENCH_CHUNK", 10)),
                       iters // 2))
    timing = timed_chunks(gbdt, iters, chunk)
    compile_s = timing["compile_s"]
    per_tree = timing["per_tree"]
    iters = timing["trees_total"]       # trees actually trained

    vcore = lgb.Dataset(Xv, label=yv, group=sizes_v,
                        reference=dtrain).construct(cfg)
    scores = heldout_scores(gbdt, cfg, vcore.group_bins)
    ndcg = ndcg_at_k(yv, scores, sizes_v, k=10)
    ndcg0 = ndcg_at_k(yv, np.zeros_like(scores), sizes_v, k=10)
    if not (ndcg >= ndcg0 + 0.03):
        raise SystemExit(
            f"lambdarank NDCG@10 ({ndcg:.4f}) did not clear the "
            f"untrained baseline ({ndcg0:.4f}) — ranking gate failed")
    ref_scaled = REF_LTR_SEC_PER_TREE_ROW * rows * iters
    out = {
        "rows": rows, "iters": iters, "task": "lambdarank",
        "queries": n_queries,
        "value": round(per_tree * iters, 3),
        "vs_baseline": round(ref_scaled / (per_tree * iters), 3),
        "ndcg10": round(ndcg, 6), "ndcg10_untrained": round(ndcg0, 6),
        "prep_s": round(prep_s, 3), "compile_s": round(compile_s, 3),
        "per_tree_ms": round(per_tree * 1e3, 2),
    }
    attach_timing(out, timing)
    # measured same-machine anchor for the ranking point too (round-4
    # verdict #2: 1.49x rested entirely on the scaled denominator and
    # the NDCG gate was only vs-untrained — this runs the reference
    # binary with .query side files and records its NDCG@10 on the
    # same held-out draw)
    if os.environ.get("BENCH_LOCAL_REF_LTR", "1") != "0":
        # free the TPU training state before the minutes-long host-side
        # reference run (write_csv makes another full float64 copy)
        del gbdt, dtrain, vcore
        gc.collect()
        ref = run_local_reference(
            X, y, Xv, yv, params,
            int(os.environ.get("BENCH_REF_ITERS_LTR", 10)),
            group=sizes, group_valid=sizes_v, task="lambdarank",
            seed=11)
        attach_local_ref(out, ref, per_tree)
        # ranking-quality gate vs the SAME-DATA reference (round 5:
        # the weaker vs-untrained gate let deterministic int8 rounding
        # sit at 0.33 NDCG@10 while the reference scored 0.54 — this
        # gate would have caught it; ours trains 3x the iterations, so
        # matching the reference's 10-iter score is a floor, not a
        # bar).  The LOCAL_REF.json cache is what lets this gate
        # actually EXECUTE under the driver budget (r5 weak #3: the
        # gate was dead code because the anchor path always timed out)
        if ref is not None and "ndcg10" in ref:
            out["ndcg_gate"] = "pass" if ndcg >= ref["ndcg10"] else "fail"
            if ndcg < ref["ndcg10"]:
                raise SystemExit(
                    f"lambdarank NDCG@10 ({ndcg:.4f}) fell below the "
                    f"same-machine reference's ({ref['ndcg10']:.4f}) "
                    "on the identical draw — ranking quality gate "
                    "failed")
        else:
            out["ndcg_gate"] = "skipped (no local reference anchor)"
    else:
        out["ndcg_gate"] = "skipped (BENCH_LOCAL_REF_LTR=0)"
    return out


def run_local_reference(X, y, Xv, yv, params, iters,
                        group=None, group_valid=None, task="binary",
                        seed=7):
    """Train the ACTUAL reference CPU binary (.refbuild/lightgbm) on the
    SAME generated data on THIS machine (round-3 verdict #2: the scaled
    2013 Xeon number is an extrapolation; this is a measurement).

    The reference binary is FIXED, so each anchor is measured once and
    persisted to LOCAL_REF.json keyed by (task, scale, params,
    data-seed, threads); later invocations reuse the record (r5
    verdict weak #1: re-running the single-threaded binary every
    invocation blew the driver budget).  A fresh measurement is
    time-boxed to the remaining BENCH_BUDGET_S minus the finishing
    reserve; on overrun a ``{"skipped": reason}`` record documents the
    absence instead of killing the bench.

    Methodology: data goes through save_binary once (so CSV parsing is
    paid once), then per-tree time = (t(iters) - t(small)) /
    (iters - small) — the two-run differencing cancels binary-load +
    setup time.  ``group``/``group_valid`` (per-query doc counts) switch
    the held-out metric to NDCG@10 and emit the reference's ``.query``
    side files (src/io/metadata.cpp query loading).  Returns a dict with
    per_tree_ms, auc or ndcg10 (held-out), threads; a skip dict; or
    None when disabled (BENCH_LOCAL_REF=0) or iters is too small to
    difference."""
    import shutil
    import subprocess
    import tempfile

    ref_bin = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           ".refbuild", "lightgbm")
    small = max(2, iters // 10)
    if os.environ.get("BENCH_LOCAL_REF", "1") == "0" or iters <= small:
        return None
    threads = os.cpu_count() or 1
    key = _local_ref_key(task, X.shape[0], iters, seed, params, threads)
    if os.environ.get("BENCH_LOCAL_REF_REFRESH") != "1":
        cached = (None if key in _LOCAL_REF_BAD
                  else _local_ref_load().get(key))
        if cached is not None:
            print(f"local reference anchor reused from LOCAL_REF.json "
                  f"[{key}]", file=sys.stderr)
            return dict(cached, cached=True)
    if not os.path.exists(ref_bin):
        return {"skipped": "reference binary absent "
                           "(.refbuild/lightgbm)"}
    box = budget_left() - ANCHOR_RESERVE_S
    # the CSV serialization itself is unboxable once started (host-side
    # numpy/pandas write, ~2M cells/s single-core) — price it into the
    # admission check so a near-empty budget can't start a multi-minute
    # write that overshoots BENCH_BUDGET_S before the first time-boxed
    # subprocess even launches (the r5 rc=124 failure mode)
    est_csv_s = (X.size + X.shape[0] + Xv.size + Xv.shape[0]) / 2e6
    if box < 30 + est_csv_s:
        return {"skipped": f"insufficient budget for a fresh anchor "
                           f"({box:.0f}s left after reserve, CSV write "
                           f"alone est. {est_csv_s:.0f}s); set "
                           "BENCH_BUDGET_S higher or pre-seed "
                           "LOCAL_REF.json"}
    tmp = tempfile.mkdtemp(prefix="bench_ref_")

    def write_csv(path, label, feats):
        arr = np.column_stack([label, feats])
        try:
            import pandas as pd
            pd.DataFrame(arr).to_csv(path, header=False, index=False,
                                     float_format="%.8g")
        except ImportError:
            np.savetxt(path, arr, fmt="%.8g", delimiter=",")

    try:
        train_csv = os.path.join(tmp, "train.csv")
        valid_csv = os.path.join(tmp, "valid.csv")
        write_csv(train_csv, y, X)
        write_csv(valid_csv, yv, Xv)
        if group is not None:
            np.savetxt(train_csv + ".query", np.asarray(group, np.int64),
                       fmt="%d")
            np.savetxt(valid_csv + ".query",
                       np.asarray(group_valid, np.int64), fmt="%d")

        base = (f"task=train data={train_csv} objective={params['objective']}"
                f" num_leaves={params['num_leaves']}"
                f" max_bin={params['max_bin']}"
                f" learning_rate={params['learning_rate']}"
                f" min_data_in_leaf={params['min_data_in_leaf']}"
                f" min_sum_hessian_in_leaf={params['min_sum_hessian_in_leaf']}"
                f" num_threads={threads} verbose=-1").split()

        def run(extra):
            t0 = time.time()
            subprocess.run([ref_bin] + base + extra, check=True,
                           stdout=subprocess.DEVNULL,
                           stderr=subprocess.DEVNULL, cwd=tmp,
                           timeout=max(10.0,
                                       budget_left() - ANCHOR_RESERVE_S))
            return time.time() - t0

        # one-time binning + binary cache (excluded from timing)
        run(["num_iterations=1", "save_binary=true",
             f"output_model={tmp}/warm.txt"])
        base[1] = f"data={train_csv}.bin"
        t_small = run([f"num_iterations={small}",
                       f"output_model={tmp}/m_small.txt"])
        t_full = run([f"num_iterations={iters}",
                      f"output_model={tmp}/model.txt"])
        per_tree = (t_full - t_small) / (iters - small)

        # held-out metric of the reference model on the same valid draw
        pred_file = os.path.join(tmp, "preds.txt")
        subprocess.run(
            [ref_bin, "task=predict", f"data={valid_csv}",
             f"input_model={tmp}/model.txt",
             f"output_result={pred_file}", "verbose=-1"],
            check=True, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL, cwd=tmp,
            timeout=max(10.0, budget_left() - ANCHOR_RESERVE_S))
        preds = np.loadtxt(pred_file)
        out = {"per_tree_ms": round(per_tree * 1e3, 2),
               "threads": threads,
               "train_s_measured": round(t_full, 3), "iters": iters}
        if group is not None:
            out["ndcg10"] = round(ndcg_at_k(yv, preds, group_valid, 10), 6)
        else:
            out["auc"] = round(auc_score(yv, preds), 6)
        _local_ref_store(key, out)
        return out
    except subprocess.TimeoutExpired:
        return {"skipped": "anchor run hit the BENCH_BUDGET_S time box;"
                           " re-run with a larger budget to seed "
                           "LOCAL_REF.json"}
    except Exception as e:  # a broken reference run must not discard
        # the completed TPU measurements
        print(f"local reference run failed ({type(e).__name__}: {e}); "
              "reporting scaled baseline only", file=sys.stderr)
        return {"skipped": f"{type(e).__name__}: {e}"}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run_local_reference_predict(model_str, X, y, params, n_trees,
                                seed=21):
    """Measure the reference CPU binary's ``task=predict`` on the SAME
    model text and data on THIS machine — the serving roofline's
    anchor.  Methodology: the model is our saved text (interchangeable
    format), predict wall is differenced between the full matrix and a
    1/8 prefix so binary-load + model-parse cancel; the per-row CSV
    parse does NOT cancel and is part of the reference CLI's serving
    cost (noted in the record).  Cached in LOCAL_REF.json under a
    ``predict:...`` key (same key fields; ``iters`` = model trees)."""
    import shutil
    import subprocess
    import tempfile

    ref_bin = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           ".refbuild", "lightgbm")
    if os.environ.get("BENCH_LOCAL_REF", "1") == "0" \
            or os.environ.get("BENCH_LOCAL_REF_PREDICT", "1") == "0":
        return None
    threads = os.cpu_count() or 1
    key = _local_ref_key("predict", X.shape[0], n_trees, seed, params,
                         threads)
    if os.environ.get("BENCH_LOCAL_REF_REFRESH") != "1":
        cached = (None if key in _LOCAL_REF_BAD
                  else _local_ref_load().get(key))
        if cached is not None:
            print(f"local predict anchor reused from LOCAL_REF.json "
                  f"[{key}]", file=sys.stderr)
            return dict(cached, cached=True)
    if not os.path.exists(ref_bin):
        return {"skipped": "reference binary absent "
                           "(.refbuild/lightgbm)"}
    box = budget_left() - ANCHOR_RESERVE_S
    est_csv_s = (X.size + X.shape[0]) / 2e6
    if box < 30 + est_csv_s:
        return {"skipped": f"insufficient budget for a fresh predict "
                           f"anchor ({box:.0f}s left after reserve, "
                           f"CSV write alone est. {est_csv_s:.0f}s)"}
    tmp = tempfile.mkdtemp(prefix="bench_refp_")
    try:
        n = X.shape[0]
        n_small = max(1, n // 8)
        full_csv = os.path.join(tmp, "full.csv")
        small_csv = os.path.join(tmp, "small.csv")
        arr = np.column_stack([y, X])
        try:
            import pandas as pd
            pd.DataFrame(arr).to_csv(full_csv, header=False, index=False,
                                     float_format="%.8g")
            pd.DataFrame(arr[:n_small]).to_csv(
                small_csv, header=False, index=False, float_format="%.8g")
        except ImportError:
            np.savetxt(full_csv, arr, fmt="%.8g", delimiter=",")
            np.savetxt(small_csv, arr[:n_small], fmt="%.8g",
                       delimiter=",")
        model_txt = os.path.join(tmp, "model.txt")
        with open(model_txt, "w") as f:
            f.write(model_str)

        def run_predict(data_csv):
            t0 = time.time()
            subprocess.run(
                [ref_bin, "task=predict", f"data={data_csv}",
                 f"input_model={model_txt}",
                 f"output_result={tmp}/preds.txt",
                 f"num_threads={threads}", "verbose=-1"],
                check=True, stdout=subprocess.DEVNULL,
                stderr=subprocess.DEVNULL, cwd=tmp,
                timeout=max(10.0, budget_left() - ANCHOR_RESERVE_S))
            return time.time() - t0

        t_small = run_predict(small_csv)
        t_full = run_predict(full_csv)
        if t_full <= t_small:
            return {"skipped": "predict differencing degenerate "
                               f"(t_full {t_full:.3f}s <= t_small "
                               f"{t_small:.3f}s at n={n})"}
        out = {"rows_per_s": round((n - n_small) / (t_full - t_small)),
               "threads": threads, "iters": n_trees, "rows": n,
               "note": "differenced wall includes the reference CLI's "
                       "per-row CSV parse"}
        _local_ref_store(key, out)
        return out
    except subprocess.TimeoutExpired:
        return {"skipped": "predict anchor hit the BENCH_BUDGET_S time "
                           "box"}
    except Exception as e:
        print(f"local predict reference failed ({type(e).__name__}: "
              f"{e})", file=sys.stderr)
        return {"skipped": f"{type(e).__name__}: {e}"}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run_local_reference_construct(X, y, params, seed=31):
    """Time the reference CPU binary's dataset construction (text parse
    + bin-mapper fit + binning + binary-cache save) of the SAME CSV on
    THIS machine — the anchor for the round-11 ``construct`` block.  A
    ``num_iterations=1`` training run is construction-dominated (one
    31-leaf tree on an already-binned matrix is milliseconds); the one
    tree rides along in the record's note.  Cached in LOCAL_REF.json
    under a ``construct:...`` key (``iters`` = 1)."""
    import shutil
    import subprocess
    import tempfile

    ref_bin = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           ".refbuild", "lightgbm")
    if os.environ.get("BENCH_LOCAL_REF", "1") == "0" \
            or os.environ.get("BENCH_LOCAL_REF_CONSTRUCT", "1") == "0":
        return None
    threads = os.cpu_count() or 1
    key = _local_ref_key("construct", X.shape[0], 1, seed, params,
                         threads)
    if os.environ.get("BENCH_LOCAL_REF_REFRESH") != "1":
        cached = (None if key in _LOCAL_REF_BAD
                  else _local_ref_load().get(key))
        if cached is not None:
            print(f"local construct anchor reused from LOCAL_REF.json "
                  f"[{key}]", file=sys.stderr)
            return dict(cached, cached=True)
    if not os.path.exists(ref_bin):
        return {"skipped": "reference binary absent "
                           "(.refbuild/lightgbm)"}
    box = budget_left() - ANCHOR_RESERVE_S
    est_csv_s = (X.size + X.shape[0]) / 2e6
    if box < 30 + est_csv_s:
        return {"skipped": f"insufficient budget for a fresh construct "
                           f"anchor ({box:.0f}s left after reserve, "
                           f"CSV write alone est. {est_csv_s:.0f}s)"}
    tmp = tempfile.mkdtemp(prefix="bench_refc_")
    try:
        train_csv = os.path.join(tmp, "train.csv")
        arr = np.column_stack([y, X])
        try:
            import pandas as pd
            pd.DataFrame(arr).to_csv(train_csv, header=False,
                                     index=False, float_format="%.8g")
        except ImportError:
            np.savetxt(train_csv, arr, fmt="%.8g", delimiter=",")
        t0 = time.time()
        subprocess.run(
            [ref_bin, "task=train", f"data={train_csv}",
             f"objective={params['objective']}",
             f"num_leaves={params['num_leaves']}",
             f"max_bin={params['max_bin']}",
             "num_iterations=1", "save_binary=true",
             f"num_threads={threads}",
             f"output_model={tmp}/warm.txt", "verbose=-1"],
            check=True, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL, cwd=tmp,
            timeout=max(10.0, budget_left() - ANCHOR_RESERVE_S))
        out = {"construct_s": round(time.time() - t0, 3),
               "threads": threads, "iters": 1, "rows": int(X.shape[0]),
               "note": "reference task=train num_iterations=1 "
                       "save_binary=true wall — CSV parse + bin fit + "
                       "binning + cache write (+ one tree)"}
        _local_ref_store(key, out)
        return out
    except subprocess.TimeoutExpired:
        return {"skipped": "construct anchor hit the BENCH_BUDGET_S "
                           "time box"}
    except Exception as e:
        print(f"local construct reference failed ({type(e).__name__}: "
              f"{e})", file=sys.stderr)
        return {"skipped": f"{type(e).__name__}: {e}"}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def run_construct_scale(params):
    """Dataset-construction roofline point (round 11): cold-construct
    rows/s of the parallel pipeline (threaded mapper fit + native
    numerical/categorical/EFB binning) against the serial pure-Python
    baseline measured IN THE SAME RUN, thread scaling 1 vs auto, and
    the binary-cache v2 save/reload — gated on the packed matrix being
    byte-identical across every path.  On a 1-core host the thread
    scaling row reads ~1.0x by construction; the headline speedup is
    the compiled pipeline vs the Python loop either way."""
    import shutil
    import tempfile

    import lightgbm_tpu as lgb
    from lightgbm_tpu.binning import resolve_construct_threads
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.dataset_io import load_binary, save_binary

    rows = int(os.environ.get("BENCH_CONSTRUCT_ROWS",
                              min(BENCH_ROWS, 1_000_000)))
    X, y, _ = make_data(rows, BENCH_FEATURES, seed=31)
    base = {"objective": "binary", "num_leaves": params["num_leaves"],
            "max_bin": params["max_bin"], "learning_rate": 0.1,
            "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 100.0,
            "verbose": -1}

    def construct(**overrides):
        cfg = Config.from_params(dict(base, **overrides))
        t0 = time.time()
        core = lgb.Dataset(X, label=y).construct(cfg)
        return core, time.time() - t0

    # serial baseline FIRST (same run, same data): pure-Python mapper
    # fit + searchsorted binning, one thread — the pre-r6 pipeline
    core_serial, serial_s = construct(construct_threads=1,
                                      native_binning=False)
    core_cold, cold_s = construct()
    if not np.array_equal(np.asarray(core_serial.group_bins),
                          np.asarray(core_cold.group_bins)):
        raise SystemExit(
            "construct parity gate failed: the parallel/native "
            "pipeline's group_bins differ from the serial Python "
            "path's on the bench draw")
    del core_serial
    gc.collect()
    _, t1_s = construct(construct_threads=1)

    tmp = tempfile.mkdtemp(prefix="bench_construct_")
    try:
        bp = os.path.join(tmp, "train.bin")
        t0 = time.time()
        save_binary(core_cold, bp)
        save_s = time.time() - t0
        t0 = time.time()
        core_re = load_binary(bp)
        # touch the matrix so lazily-paged memmap IO is inside the
        # measurement, not deferred to the consumer
        checksum = int(np.asarray(core_re.group_bins[::
                                  max(1, rows // 4096)]).sum())
        reload_s = time.time() - t0
        if not np.array_equal(np.asarray(core_re.group_bins),
                              np.asarray(core_cold.group_bins)):
            raise SystemExit("binary-cache v2 reload parity gate "
                             "failed: reloaded group_bins differ")
        del core_re
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    del checksum

    out = {
        "task": "construct", "rows": rows, "features": BENCH_FEATURES,
        "cold_construct_s": round(cold_s, 3),
        "cold_rows_per_s": round(rows / max(cold_s, 1e-9)),
        "serial_construct_s": round(serial_s, 3),
        "serial_rows_per_s": round(rows / max(serial_s, 1e-9)),
        "speedup_vs_serial": round(serial_s / max(cold_s, 1e-9), 2),
        "threads_auto": resolve_construct_threads(None),
        "thread_scaling": {"1": round(t1_s, 3),
                           "auto": round(cold_s, 3),
                           "x": round(t1_s / max(cold_s, 1e-9), 2)},
        "cache_save_s": round(save_s, 3),
        "cache_reload_s": round(reload_s, 3),
        "reload_x_cold": round(cold_s / max(reload_s, 1e-9), 1),
        "parity": "pass",
    }
    ref = run_local_reference_construct(X, y, base)
    if ref is None:
        out["local_ref_skipped"] = "BENCH_LOCAL_REF[_CONSTRUCT]=0"
    elif "skipped" in ref:
        out["local_ref_skipped"] = ref["skipped"]
    else:
        out["local_ref"] = ref
        out["vs_local_reference"] = round(
            ref["construct_s"] / max(cold_s, 1e-9), 3)
    return out


def _rss_mb() -> float:
    """Current VmRSS in MB (/proc; 0.0 where unavailable) — the
    shard_construct block reports the resident-set DELTA of each
    construction route, the rows-per-chip signal sharding exists for."""
    try:
        with open("/proc/self/status") as f:
            for ln in f:
                if ln.startswith("VmRSS:"):
                    return float(ln.split()[1]) / 1024.0
    except OSError:
        pass
    return 0.0


def run_shard_construct(params):
    """Sharded-construct roofline point (round 16, ROADMAP item 1):
    the mesh-sharded data plane measured against the single-matrix
    route on the same draw — per-shard construct rows/s, the
    distributed bin-find merge wall, resident-set delta per route —
    gated on the packed shards being byte-identical to the
    single-matrix construction and on a shard-cache v2 round trip
    (manifest world-size refusal included).  2 simulated participants
    by default (BENCH_SHARD_PARTICIPANTS); the
    order-of-magnitude-past-10.5M-rows series tracks the same keys in
    MULTICHIP_r*.json runs."""
    import shutil
    import tempfile

    import lightgbm_tpu as lgb
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.sharded import (ShardCacheError, ShardedDataset,
                                      binfind, load_shard_cache,
                                      save_shard_cache)

    rows = int(os.environ.get("BENCH_SHARD_ROWS",
                              min(BENCH_ROWS, 500_000)))
    shards = int(os.environ.get("BENCH_SHARD_PARTICIPANTS", 2))
    X, y, _ = make_data(rows, BENCH_FEATURES, seed=41)
    base = {"objective": "binary", "num_leaves": params["num_leaves"],
            "max_bin": params["max_bin"], "verbose": -1}
    cfg = Config.from_params(base)

    gc.collect()
    rss0 = _rss_mb()
    t0 = time.time()
    single = lgb.Dataset(X, label=y).construct(cfg)
    single_s = time.time() - t0
    rss_single = max(0.0, _rss_mb() - rss0)

    # the merge wall on its own: candidates + instrumented allgather +
    # deterministic merge (the network-facing slice of construction)
    from lightgbm_tpu.sharded.dataset import shard_row_ranges
    ranges = shard_row_ranges(rows, shards)
    t0 = time.time()
    cands = [binfind.collect_candidates(X[a:b], cfg, rank=i,
                                        world=shards)
             for i, (a, b) in enumerate(ranges)]
    _vals, _rows_m, _tot = binfind.merge_candidates(cands)
    merge_wall_ms = (time.time() - t0) * 1e3
    del cands, _vals, _rows_m

    gc.collect()
    rss1 = _rss_mb()
    t0 = time.time()
    sds = ShardedDataset.construct_sharded(X, label=y, config=cfg,
                                           num_shards=shards)
    shard_s = time.time() - t0
    rss_sharded = max(0.0, _rss_mb() - rss1)

    if not np.array_equal(sds.assembled_group_bins(),
                          np.asarray(single.group_bins)):
        raise SystemExit(
            "shard_construct parity gate failed: sharded-route bins "
            "differ from the single-matrix construction")
    if binfind.mapper_fingerprint(sds.mappers, sds._bundles,
                                  sds.max_bin) \
            != binfind.mapper_fingerprint(single.mappers,
                                          single._bundles,
                                          single.max_bin):
        raise SystemExit("shard_construct mapper gate failed: merged "
                         "mappers differ from the single-host fit")

    tmp = tempfile.mkdtemp(prefix="bench_shard_")
    try:
        save_shard_cache(sds, tmp)
        t0 = time.time()
        re = load_shard_cache(tmp, expect_world_size=shards)
        reload_s = time.time() - t0
        if not np.array_equal(re.assembled_group_bins(),
                              sds.assembled_group_bins()):
            raise SystemExit("shard-cache v2 reload parity gate "
                             "failed")
        try:
            load_shard_cache(tmp, expect_world_size=shards + 1)
            raise SystemExit("shard-cache manifest accepted a wrong "
                             "world size")
        except ShardCacheError:
            manifest_reject = "pass"
        del re
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    per_shard_rows = rows / shards
    return {
        "task": "shard_construct", "rows": rows, "shards": shards,
        "features": BENCH_FEATURES,
        "shard_construct_s": round(shard_s, 3),
        "shard_rows_per_s": round(rows / max(shard_s, 1e-9)),
        "per_shard_rows_per_s": round(
            per_shard_rows / max(shard_s, 1e-9)),
        "single_construct_s": round(single_s, 3),
        "vs_single_matrix": round(single_s / max(shard_s, 1e-9), 2),
        "merge_wall_ms": round(merge_wall_ms, 2),
        "rss_single_mb": round(rss_single, 1),
        "rss_sharded_mb": round(rss_sharded, 1),
        "cache_reload_s": round(reload_s, 3),
        "parity": "pass",
        "manifest_reject": manifest_reject,
    }


_DIST_EXCHANGE_WORKER = r"""
import json, os, sys, time
import numpy as np
os.environ.setdefault("JAX_PLATFORMS", "cpu")
coord, nproc, pid = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
leaves, groups, bins, reps = (int(a) for a in sys.argv[4:8])
from lightgbm_tpu.config import Config
from lightgbm_tpu.parallel import transport as T
from lightgbm_tpu.parallel.collectives import host_exchange_histograms
from lightgbm_tpu.telemetry import TELEMETRY
cfg = Config.from_params({"verbose": -1, "collective_transport": "tcp"})
tp = T.TcpTransport.create(coord, nproc, pid, config=cfg)
T.install(tp)
rng = np.random.RandomState(7 + pid)
hist = np.round(rng.randn(leaves, groups, bins, 3)
                .astype(np.float32) * 100, 3)
# every rank holds ALL shards too, purely to pin the TCP result
# bit-exact against the host codec on the same inputs
shards = np.stack(tp.allgather_obj(hist), axis=0)
TELEMETRY.configure("counters")
out = {}
for mode in ("f32", "q16", "q8"):
    TELEMETRY.reset()
    t0 = time.time()
    for _ in range(reps):
        res = tp.exchange_histograms(hist, mode)
    wall = (time.time() - t0) / reps
    ref = host_exchange_histograms(shards, mode)
    if not np.array_equal(res, ref):
        raise SystemExit(f"hist_exchange {mode} over TCP is not "
                         "bit-exact vs the host codec")
    c = TELEMETRY.counters()
    out[mode] = {
        "payload_wire_bytes":
            int(c.get("collective_tcp_hist_exchange_bytes", 0)) // reps,
        "scale_wire_bytes":
            int(c.get("collective_tcp_hist_scale_bytes", 0)) // reps,
        "total_wire_bytes":
            int(c.get("collective_tcp_bytes", 0)) // reps,
        "rounds": int(c.get("collective_tcp_rounds", 0)) // reps,
        "wall_ms": round(wall * 1e3, 2),
    }
# frame-CRC cost on the q16 wire path, measured two ways: the
# ANALYTIC fraction (the actual payload digest timed over exactly the
# q16 wire volume at the real per-frame granularity, divided by the
# q16 round wall — robust to 1-core scheduler jitter) is the <2%
# gate; the on/off wall delta is informational only
wire = int(out["q16"]["payload_wire_bytes"]) \
    + int(out["q16"]["scale_wire_bytes"])
nframes = max(2 * int(out["q16"]["rounds"]), 1)
frame = bytes(max(wire // nframes, 1))
crc_reps = max(reps, 5)
t0 = time.time()
for _ in range(crc_reps):
    for _ in range(nframes):
        T._payload_crc(frame)
crc_s = (time.time() - t0) / crc_reps
T._FRAME_CRC = False
t0 = time.time()
for _ in range(reps):
    tp.exchange_histograms(hist, "q16")
nocrc_wall = (time.time() - t0) / reps
T._FRAME_CRC = True
out["crc"] = {
    "q16_wire_bytes": wire,
    "crc_ms": round(crc_s * 1e3, 3),
    "crc_frac_of_q16_wall": round(
        crc_s / max(out["q16"]["wall_ms"] / 1e3, 1e-9), 4),
    "q16_wall_ms_nocrc": round(nocrc_wall * 1e3, 2),
}
tp.close()
if pid == 0:
    print(json.dumps(out))
"""


def run_distributed_exchange(params):
    """Distributed-exchange roofline point (this round): the r21
    hist_exchange codec over the REAL host-side TCP transport — two
    processes, real sockets — reporting per-mode wire bytes from the
    ``collective_tcp_*`` per-primitive counters and gating the q16
    payload at >=2x (q8 >=4x) the f32 wire frames, every mode pinned
    bit-exact against ``host_exchange_histograms`` inside the workers.

    Two honest byte views: ``payload`` counts the frames that carry
    histogram data (f32 allgather vs the int16/int8 ring); ``total``
    adds the q-modes' one pmax scale-sync round.  At world=2 the ring
    and the allgather both move the whole array once, so the total
    ratio reads just under the dtype ratio — it grows toward
    world_size at larger worlds, where the f32 allgather pays
    (P-1) full copies and the integer ring stays ~2 copies."""
    import socket
    import subprocess

    leaves = int(os.environ.get("BENCH_DIST_LEAVES", 31))
    groups = int(os.environ.get("BENCH_DIST_GROUPS", 28))
    bins = int(os.environ.get("BENCH_DIST_BINS", 64))
    reps = int(os.environ.get("BENCH_DIST_REPS", 3))
    s = socket.socket()
    s.bind(("localhost", 0))
    coord = f"localhost:{s.getsockname()[1]}"
    s.close()
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen(
        [sys.executable, "-c", _DIST_EXCHANGE_WORKER, coord, "2",
         str(i), str(leaves), str(groups), str(bins), str(reps)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env) for i in range(2)]
    outs = []
    for p in procs:
        try:
            o, e = p.communicate(timeout=300)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise SystemExit("distributed_exchange bench hung")
        if p.returncode != 0:
            raise SystemExit(
                f"distributed_exchange worker failed: {e[-1500:]}")
        outs.append(o)
    modes = json.loads(outs[0].strip().splitlines()[-1])
    crc = modes.pop("crc")
    ratio16 = modes["f32"]["payload_wire_bytes"] \
        / max(modes["q16"]["payload_wire_bytes"], 1)
    ratio8 = modes["f32"]["payload_wire_bytes"] \
        / max(modes["q8"]["payload_wire_bytes"], 1)
    if ratio16 < 2.0 or ratio8 < 4.0:
        raise SystemExit(
            f"distributed_exchange wire gate failed: q16 {ratio16:.2f}x"
            f" (need >=2.0), q8 {ratio8:.2f}x (need >=4.0) vs f32")
    if crc["crc_frac_of_q16_wall"] >= 0.02:
        raise SystemExit(
            "distributed_exchange crc gate failed: frame-CRC costs "
            f"{crc['crc_frac_of_q16_wall'] * 100:.2f}% of the q16 "
            "wire path (budget <2%)")
    return {
        "task": "distributed_exchange", "world": 2,
        "hist_shape": [leaves, groups, bins, 3],
        "modes": modes,
        "wire_ratio_q16": round(ratio16, 2),
        "wire_ratio_q8": round(ratio8, 2),
        "total_wire_ratio_q16": round(
            modes["f32"]["total_wire_bytes"]
            / max(modes["q16"]["total_wire_bytes"], 1), 2),
        "parity": "pass",
        "wire_gate": "pass",
        "crc": crc,
        "crc_overhead_frac": crc["crc_frac_of_q16_wall"],
        "crc_gate": "pass",
    }


def run_compact_bins(params, rows=None):
    """Sub-byte packed bin matrix roofline point (round 18, ROADMAP
    item 4): the nibble-packed (bin_packing=4bit) pipeline measured
    against the 8-bit one on the same max_bin=15 draw.

    Reports construct rows/s per mode (the pack adds one fused
    byte-combine pass over each chunk — gate: within ~0.9x), the
    HOST matrix bytes and the GAUGE-measured device bin-matrix bytes
    (``bin_matrix_bytes``, rows_padded x storage cols), and an
    analytic histogram bytes-read-per-row model (the packed stream the
    tiled kernels actually read).  Hard gates: >= 2x packing ratio at
    max_bin=15 (28 dense feature groups -> exactly 2x) and
    byte-identical trees across modes."""
    import re as _re

    import lightgbm_tpu as lgb
    from lightgbm_tpu.telemetry import TELEMETRY

    if rows is None:        # standalone use; main() passes the
        rows = int(os.environ.get("BENCH_COMPACT_ROWS",  # admitted count
                                  min(BENCH_ROWS, 500_000)))
    X, y, _ = make_data(rows, BENCH_FEATURES, seed=43)
    base = {"objective": "binary", "num_leaves": params["num_leaves"],
            "max_bin": 15, "num_iterations": 2, "min_data_in_leaf": 5,
            "telemetry": "counters", "verbose": -1}

    out = {"task": "compact_bins", "rows": rows,
           "features": BENCH_FEATURES, "max_bin": 15}
    host_bytes = {}
    device_bytes = {}
    trees = {}
    for mode in ("8bit", "4bit"):
        p = dict(base, bin_packing=mode)
        gc.collect()
        rss0 = _rss_mb()
        t0 = time.time()
        dset = lgb.Dataset(X, label=y).construct(
            lgb.config.Config.from_params(p))
        construct_s = time.time() - t0
        host_bytes[mode] = int(np.asarray(dset.group_bins).nbytes)
        out[f"construct_s_{mode}"] = round(construct_s, 3)
        out[f"construct_rows_per_s_{mode}"] = round(
            rows / max(construct_s, 1e-9))
        out[f"rss_delta_mb_{mode}"] = round(
            max(0.0, _rss_mb() - rss0), 1)
        wrapped = lgb.Dataset(X, label=y, params=p)
        wrapped._core = dset
        booster = lgb.train(p, wrapped)
        g = TELEMETRY.snapshot().get("gauges", {})
        device_bytes[mode] = int(g.get("bin_matrix_bytes", 0))
        trees[mode] = _re.sub(r"\[bin_packing: \w+\]", "",
                              booster.model_to_string())
        del dset, wrapped, booster
        gc.collect()

    out["host_matrix_bytes_8bit"] = host_bytes["8bit"]
    out["host_matrix_bytes_4bit"] = host_bytes["4bit"]
    out["bin_matrix_bytes_8bit"] = device_bytes["8bit"]
    out["bin_matrix_bytes_4bit"] = device_bytes["4bit"]
    out["packing_ratio"] = round(
        host_bytes["8bit"] / max(host_bytes["4bit"], 1), 3)
    out["device_packing_ratio"] = round(
        device_bytes["8bit"] / max(device_bytes["4bit"], 1), 3)
    out["construct_ratio_4bit_vs_8bit"] = round(
        out["construct_rows_per_s_4bit"]
        / max(out["construct_rows_per_s_8bit"], 1), 3)
    # analytic histogram bytes-read model: the tiled/fused kernels
    # stream the (transposed) bin matrix + 16 weight/leaf bytes per
    # row per pass — packing halves the bins term, the whole
    # bandwidth story at max_bin <= 16
    g8, g4 = BENCH_FEATURES, (BENCH_FEATURES + 1) // 2
    out["hist_bytes_per_row_8bit"] = g8 + 16
    out["hist_bytes_per_row_4bit"] = g4 + 16
    out["hist_stream_ratio"] = round((g8 + 16) / (g4 + 16), 3)

    if out["packing_ratio"] < 2.0 - 1e-9:
        raise SystemExit(
            f"compact_bins packing gate failed: host ratio "
            f"{out['packing_ratio']} < 2.0 at max_bin=15 "
            f"({BENCH_FEATURES} dense groups must pack two per byte)")
    if device_bytes["8bit"] and device_bytes["4bit"] \
            and out["device_packing_ratio"] < 1.8:
        # padded rows are identical across modes, so the device ratio
        # only dips below 2.0 through an odd group count
        raise SystemExit(
            "compact_bins device gate failed: bin_matrix_bytes ratio "
            f"{out['device_packing_ratio']} < 1.8")
    if trees["8bit"] != trees["4bit"]:
        raise SystemExit("compact_bins parity gate failed: trees "
                         "differ between bin_packing=8bit and 4bit")

    # --- crumb tier (round 21): the same pipeline on a max_bin=4
    # sub-draw, where bin_packing=2bit stores FOUR groups per byte.
    # Gate: the measured host ratio must meet the layout-predicted
    # read-stream reduction G / ceil(G/4) exactly (same rows, the
    # packed matrix IS the kernels' read stream at max_bin <= 4).
    base4 = dict(base, max_bin=4)
    host4 = {}
    dev4 = {}
    trees4 = {}
    for mode in ("8bit", "2bit"):
        p = dict(base4, bin_packing=mode)
        gc.collect()
        t0 = time.time()
        dset = lgb.Dataset(X, label=y).construct(
            lgb.config.Config.from_params(p))
        construct_s = time.time() - t0
        host4[mode] = int(np.asarray(dset.group_bins).nbytes)
        out[f"construct_rows_per_s_{mode}_mb4"] = round(
            rows / max(construct_s, 1e-9))
        wrapped = lgb.Dataset(X, label=y, params=p)
        wrapped._core = dset
        booster = lgb.train(p, wrapped)
        g = TELEMETRY.snapshot().get("gauges", {})
        dev4[mode] = int(g.get("bin_matrix_bytes", 0))
        trees4[mode] = _re.sub(r"\[bin_packing: \w+\]", "",
                               booster.model_to_string())
        del dset, wrapped, booster
        gc.collect()
    g2 = (BENCH_FEATURES + 3) // 4
    out["host_matrix_bytes_8bit_mb4"] = host4["8bit"]
    out["host_matrix_bytes_2bit"] = host4["2bit"]
    out["bin_matrix_bytes_2bit"] = dev4["2bit"]
    out["crumb_packing_ratio"] = round(
        host4["8bit"] / max(host4["2bit"], 1), 3)
    out["crumb_predicted_ratio"] = round(BENCH_FEATURES / g2, 3)
    out["crumb_device_ratio"] = round(
        dev4["8bit"] / max(dev4["2bit"], 1), 3)
    out["hist_bytes_per_row_2bit"] = g2 + 16
    out["crumb_stream_ratio"] = round(
        (BENCH_FEATURES + 16) / (g2 + 16), 3)
    if out["crumb_packing_ratio"] < out["crumb_predicted_ratio"] - 1e-9:
        raise SystemExit(
            "compact_bins crumb gate failed: host ratio "
            f"{out['crumb_packing_ratio']} below the layout-predicted "
            f"{out['crumb_predicted_ratio']} at max_bin=4")
    if trees4["8bit"] != trees4["2bit"]:
        raise SystemExit("compact_bins parity gate failed: trees "
                         "differ between bin_packing=8bit and 2bit")

    # --- compressed histogram exchange (round 21): the q16/q8 codec's
    # measured wire bytes through the SAME host collective path the
    # sharded windows ride, via its telemetry counters.  Gate: q16
    # halves and q8 quarters the f32 payload.
    from lightgbm_tpu.parallel.collectives import host_exchange_histograms
    TELEMETRY.configure("counters")
    rng_h = np.random.RandomState(47)
    shard_hists = [
        np.cumsum(rng_h.randint(-15, 16,
                                size=(params["num_leaves"],
                                      BENCH_FEATURES, 16, 3)),
                  axis=-2).astype(np.float32)
        for _ in range(2)]
    for mode in ("f32", "q16", "q8"):
        TELEMETRY.reset()
        host_exchange_histograms(shard_hists, mode=mode)
        c = TELEMETRY.snapshot().get("counters", {})
        out[f"hist_exchange_bytes_{mode}"] = int(
            c.get("collective_hist_exchange_bytes", 0))
    out["hist_exchange_ratio_q16"] = round(
        out["hist_exchange_bytes_f32"]
        / max(out["hist_exchange_bytes_q16"], 1), 3)
    out["hist_exchange_ratio_q8"] = round(
        out["hist_exchange_bytes_f32"]
        / max(out["hist_exchange_bytes_q8"], 1), 3)
    if out["hist_exchange_ratio_q16"] < 2.0 - 1e-9 \
            or out["hist_exchange_ratio_q8"] < 4.0 - 1e-9:
        raise SystemExit(
            "compact_bins hist_exchange gate failed: byte reduction "
            f"q16 {out['hist_exchange_ratio_q16']}x / q8 "
            f"{out['hist_exchange_ratio_q8']}x (need 2x / 4x)")
    out["parity"] = "pass"
    return out


def run_predict_scale(params):
    """Serving roofline point: bulk scoring throughput, micro-batch
    p50 latency and the compile count of the shape-bucketed device
    predictor, gated on exact parity with the host tree walk and
    anchored against the reference CPU ``task=predict``.

    Runs with ``device=True`` so the measurement exercises the device
    predictor on whatever backend JAX selected (``backend`` is
    recorded; on the CPU seam the numbers are the XLA-CPU analog of
    the on-chip run, same as the training scales)."""
    import jax

    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops.predict import (PREDICT_TELEMETRY,
                                          reset_predict_telemetry)

    train_rows = int(os.environ.get("BENCH_PREDICT_TRAIN_ROWS", 200_000))
    iters = int(os.environ.get("BENCH_PREDICT_ITERS", 50))
    bulk_rows = int(os.environ.get("BENCH_PREDICT_ROWS", 2_000_000))
    small = int(os.environ.get("BENCH_PREDICT_SMALL_BATCH", 32))
    calls = int(os.environ.get("BENCH_PREDICT_CALLS", 50))

    X, y, w = make_data(train_rows, BENCH_FEATURES, seed=21)
    bst = lgb.train(dict(params), lgb.Dataset(X, label=y), iters,
                    verbose_eval=False)
    n_trees = bst.num_trees()
    Xb, yb, _ = make_data(bulk_rows, BENCH_FEATURES, seed=22, w=w)
    del X, y
    gc.collect()

    reset_predict_telemetry()
    # warm pass compiles every bucket the measurement will touch
    t0 = time.time()
    bst.predict(Xb[:small], device=True)
    pred = bst.predict(Xb, device=True)
    warm_s = time.time() - t0
    t0 = time.time()
    pred = bst.predict(Xb, device=True)
    bulk_s = time.time() - t0

    # parity gate: the serving numbers are only evidence if the device
    # predictor routes every row exactly like the host walk
    n_check = min(4096, bulk_rows)
    host = bst.predict(Xb[:n_check], device=False)
    if not np.allclose(pred[:n_check], host, rtol=2e-5, atol=2e-7):
        raise SystemExit(
            "device predict diverged from the host tree walk on the "
            f"bench draw (max |delta| "
            f"{np.max(np.abs(pred[:n_check] - host)):g}) — serving "
            "parity gate failed")

    lat = []
    off = 0
    for _ in range(calls):
        t0 = time.time()
        bst.predict(Xb[off:off + small], device=True)
        lat.append(time.time() - t0)
        off = (off + small) % max(bulk_rows - small, 1)
    p50_ms = float(np.percentile(np.asarray(lat) * 1e3, 50))

    buckets = sorted(PREDICT_TELEMETRY["buckets"])
    out = {
        "task": "predict", "backend": jax.default_backend(),
        "model_trees": n_trees, "model_leaves": params["num_leaves"],
        "rows": bulk_rows,
        "bulk_rows_per_s": round(bulk_rows / bulk_s),
        "bulk_s": round(bulk_s, 3),
        "warm_s": round(warm_s, 3),
        "small_batch": small,
        "p50_ms": round(p50_ms, 3),
        "compile_count": PREDICT_TELEMETRY["traces"],
        "buckets_used": buckets,
        "dispatches": PREDICT_TELEMETRY["dispatches"],
        "parity": "pass",
    }
    anchor_rows = min(bulk_rows,
                      int(os.environ.get("BENCH_PREDICT_ANCHOR_ROWS",
                                         200_000)))
    ref = run_local_reference_predict(
        bst.model_to_string(), Xb[:anchor_rows], yb[:anchor_rows],
        params, n_trees)
    if ref is None:
        out["local_ref_skipped"] = "BENCH_LOCAL_REF[_PREDICT]=0"
    elif "skipped" in ref:
        out["local_ref_skipped"] = ref["skipped"]
    else:
        out["local_ref"] = ref
        out["vs_local_reference"] = round(
            out["bulk_rows_per_s"] / ref["rows_per_s"], 3)
    return out


def run_higgs_real(params):
    """Real-HIGGS anchor (round-4 verdict #6): when the UCI HIGGS
    dataset is available — BENCH_HIGGS_PATH pointing at HIGGS.csv[.gz],
    or BENCH_HIGGS=1 to attempt the UCI download — train the bench
    config on the true data and report held-out AUC against the
    reference's published 0.845 (docs/Experiments.rst:125-129, last
    500k rows held out per the experiment's convention).  Returns the
    scale dict, or None with a stderr note when the data cannot be
    obtained (this image has zero egress, so the download attempt
    documents the impossibility rather than working around it)."""
    import gzip

    path = os.environ.get("BENCH_HIGGS_PATH")
    if not path and os.environ.get("BENCH_HIGGS") == "1":
        path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                            ".data", "HIGGS.csv.gz")
        if not os.path.exists(path):
            url = ("https://archive.ics.uci.edu/ml/machine-learning-"
                   "databases/00280/HIGGS.csv.gz")
            try:
                import urllib.request
                os.makedirs(os.path.dirname(path), exist_ok=True)
                urllib.request.urlretrieve(url, path + ".part")
                os.replace(path + ".part", path)
            except Exception as e:
                print(f"real-HIGGS download failed ({type(e).__name__}:"
                      f" {e}) — this environment has no egress; "
                      "synthetic-only caveat stands (BASELINE.md)",
                      file=sys.stderr)
                return None
    if not path or not os.path.exists(path):
        return None

    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        arr = np.loadtxt(f, delimiter=",", dtype=np.float32)
    y, X = arr[:, 0], arr[:, 1:]
    Xt, yt = X[-500_000:], y[-500_000:]
    X, y = X[:-500_000], y[:-500_000]
    import lightgbm_tpu as lgb
    gbdt, cfg, dtrain, prep_s, timing = train_timed(
        params, X, y, int(os.environ.get("BENCH_HIGGS_ITERS", 100)))
    vcore = lgb.Dataset(Xt, label=yt, reference=dtrain).construct(cfg)
    auc = auc_score(yt, heldout_scores(gbdt, cfg, vcore.group_bins))
    return attach_timing(
        {"rows": int(X.shape[0]), "task": "higgs_real",
         "auc": round(auc, 6), "auc_published_ref": 0.845154,
         "per_tree_ms": round(timing["per_tree"] * 1e3, 2),
         "prep_s": round(prep_s, 3)}, timing)


def run_scale(rows, iters, params, check_f32, local_ref=False,
              ref_iters=None, slope_probe=False):
    """Train + evaluate one scale point; returns its metrics dict."""
    import lightgbm_tpu as lgb

    X, y, w = make_data(rows, BENCH_FEATURES)
    Xv, yv, _ = make_data(VALID_ROWS, BENCH_FEATURES, seed=8, w=w)
    gbdt, cfg, dtrain, prep_s, timing = train_timed(
        params, X, y, iters)
    compile_s = timing["compile_s"]
    per_tree = timing["per_tree"]
    cold_total_s = timing["cold_total_s"]
    total_equiv = per_tree * iters
    vcore = lgb.Dataset(Xv, label=yv, reference=dtrain).construct(cfg)
    auc = auc_score(yv, heldout_scores(gbdt, cfg, vcore.group_bins))
    if slope_probe:
        # AFTER the headline timing and the held-out AUC: the probe
        # appends 2·Σprobes real trees to THIS model only, and the f32
        # comparison below trains exactly `iters` — probing earlier
        # would put an ensemble-size mismatch inside the 1e-3 gate
        timing["chunk_slope"] = chunk_slope_probe(gbdt)

    auc_f32 = auc
    if check_f32 and params.get("quantized_grad"):
        # free the timed run's device state (streamed one-hot etc.)
        # before the second training run — two runs' buffers don't
        # co-reside in HBM
        del gbdt, dtrain, vcore
        gc.collect()
        p32 = dict(params, quantized_grad=False)
        g32, c32, d32, _, _ = train_timed(p32, X, y, iters)
        v32 = lgb.Dataset(Xv, label=yv, reference=d32).construct(c32)
        auc_f32 = auc_score(yv, heldout_scores(g32, c32, v32.group_bins))
        del g32, d32, v32
    else:
        del gbdt, dtrain, vcore
    gc.collect()

    delta = abs(auc - auc_f32)
    if not (delta <= 1e-3):  # catches NaN too; survives python -O
        raise SystemExit(
            f"quantized AUC ({auc}) drifted {delta!r} from the f32 path "
            f"({auc_f32}) — over the 1e-3 reference GPU-vs-CPU tolerance")

    ref_scaled = REF_SEC_PER_TREE_ROW * rows * iters
    out = {
        "rows": rows,
        "iters": iters,
        "value": round(total_equiv, 3),
        "vs_baseline": round(ref_scaled / total_equiv, 3),
        "auc": round(auc, 6),
        "auc_f32": round(auc_f32, 6),
        "auc_delta": round(delta, 6),
        "prep_s": round(prep_s, 3),
        "compile_s": round(compile_s, 3),
        "cold_total_s": round(cold_total_s, 3),
        "per_tree_ms": round(per_tree * 1e3, 2),
    }
    attach_timing(out, timing)
    if local_ref:
        if ref_iters is None:
            ref_iters = int(os.environ.get("BENCH_REF_ITERS",
                                           min(iters, 30)))
        ref = run_local_reference(X, y, Xv, yv, params, ref_iters,
                                  task="binary", seed=7)
        attach_local_ref(out, ref, per_tree)
    return out


def _bench_wall_key() -> str:
    # keyed by workload shape like every other anchor: a unit measured
    # at leaves=15/max_bin=31 (CI config) is off by the per-tree cost
    # ratio for a 255/63 perf run — admission would then re-admit the
    # exact overrun it exists to prevent
    return (f"bench_wall:host={_host_tag()}:nl={NUM_LEAVES}"
            f":mb={MAX_BIN}")


def admit_primary(rows, iters):
    """Round-13: the PRIMARY scale itself is budget-admitted (the r5
    driver record — ``rc 124, parsed null`` — was a
    measurement run escaping admission and blowing the outer driver
    timeout; r8 budgeted every phase EXCEPT the first one).  The
    estimate comes from this bench's own measured wall on this host,
    persisted under the ``bench_wall:`` key in LOCAL_REF.json — the
    first run on a host has no estimate and runs as configured, every
    later run scales the primary rows DOWN to what the budget fits
    (with a ``scaled_down_from`` note) instead of starting a run that
    cannot finish.  Returns (admitted_rows, note-or-None)."""
    rec = _local_ref_load().get(_bench_wall_key())
    if _bench_wall_key() in _LOCAL_REF_BAD or not isinstance(rec, dict):
        return rows, None
    try:
        unit = float(rec.get("unit_s_per_row_iter", 0) or 0)
        fixed = float(rec.get("fixed_s", 0) or 0)
    except (TypeError, ValueError):
        return rows, None
    if unit <= 0:
        return rows, None
    left = budget_left() - FINISH_RESERVE_S
    est = fixed + 1.3 * unit * rows * iters
    if est <= left:
        return rows, None
    rows_fit = int(max(0.0, left - fixed) / (1.3 * unit * max(iters, 1)))
    # floor INSIDE the configured rows: max-then-min would scale a
    # 2048-row primary UP to 4096 and mislabel it scaled_down_from
    rows_fit = min(rows, max(4096, rows_fit))
    note = (f"BENCH_BUDGET_S primary admission: est {est:.0f}s > "
            f"{left:.0f}s left (unit {unit:.3g} s/(row*iter) measured "
            f"on this host last run); rows {rows} -> {rows_fit}")
    return rows_fit, note


def _store_bench_wall(rows, iters, wall_s, compile_s) -> None:
    """Persist the measured primary wall as the next run's admission
    estimate (same-host only — the key carries the CPU model)."""
    fixed = max(0.0, float(compile_s))
    unit = max(wall_s - fixed, 1e-9) / max(rows * iters, 1)
    _local_ref_store(_bench_wall_key(), {
        "unit_s_per_row_iter": unit, "fixed_s": round(fixed, 3),
        "rows": int(rows), "iters": int(iters),
        "wall_s": round(wall_s, 3)})


def main():
    # refuse a chipless run before any phase spends the budget
    device = device_record()
    # the persistent compilation cache is placed by the library's one
    # rule (config.resolve_compile_cache_dir: JAX_COMPILATION_CACHE_DIR
    # if set, else <checkout>/.jax_cache) — the bench sets none
    params = {
        "objective": "binary", "num_leaves": NUM_LEAVES,
        "max_bin": MAX_BIN, "learning_rate": 0.1, "verbose": -1,
        "min_data_in_leaf": 1, "min_sum_hessian_in_leaf": 100.0,
        "hist_compute_dtype": os.environ.get("BENCH_HIST_DTYPE",
                                             "bfloat16"),
        # int8-MXU quantized histograms — the TPU analog of the
        # reference benchmarking its single-precision 63-bin GPU path
        # (docs/GPU-Performance.rst:134-161); the JSON line reports the
        # held-out AUC of this path AND the f32 path at the primary
        # scale, asserting the delta stays within the reference's own
        # GPU-vs-CPU tolerance of 1e-3.  Disable with BENCH_QUANTIZED=0.
        "quantized_grad": os.environ.get("BENCH_QUANTIZED", "1") != "0",
    }
    # ad-hoc experiment overrides, e.g. BENCH_PARAMS='{"frontier_width":64}'
    extra = os.environ.get("BENCH_PARAMS")
    if extra:
        params.update(json.loads(extra))

    # anchor-cache validation BEFORE any scale consults LOCAL_REF.json:
    # drifted keys/records become stderr skip-notes and are never
    # served (round-7 satellite; silently anchoring against a stale
    # key set was the failure mode)
    notes, bad = validate_local_ref()
    _LOCAL_REF_NOTES.extend(notes)
    _LOCAL_REF_BAD.update(bad)
    for n in notes:
        print(f"LOCAL_REF validation: {n}", file=sys.stderr)

    check_f32 = os.environ.get("BENCH_SKIP_F32") != "1"
    # round 13: the primary scale is budget-admitted too — scaled down
    # against the bench_wall unit measured on this host last run
    rows_primary, primary_note = admit_primary(BENCH_ROWS, BENCH_ITERS)
    if primary_note:
        print(f"primary admission: {primary_note}", file=sys.stderr)
    t_primary = time.time()
    primary = run_scale(
        rows_primary, BENCH_ITERS, params, check_f32, local_ref=True,
        slope_probe=os.environ.get("BENCH_SLOPE_PROBE", "1") != "0")
    primary_wall = max(time.time() - t_primary, 1e-3)
    if primary_note:
        primary["scaled_down_from"] = BENCH_ROWS
        primary["budget_note"] = primary_note
    if os.environ.get("BENCH_LOCAL_REF", "1") != "0":
        # persist the measured wall as the next run's admission
        # estimate — but never from the tiny-N smoke driver
        # (BENCH_LOCAL_REF=0): its compile-dominated unit would make
        # the next perf run scale down a primary that actually fits
        _store_bench_wall(rows_primary, BENCH_ITERS, primary_wall,
                          primary.get("compile_s", 0.0))
    scales = [primary]

    # ---- per-phase budget admission (round 8): every REMAINING phase
    # is admitted against an estimate scaled from the measured primary
    # wall, so a lightgbm_tpu measurement run can no longer blow the
    # outer driver timeout the way the r5 10.5M run did (rc=124,
    # parsed null).  Estimates are deliberately
    # conservative (1.5x) — a phase that would overrun is scaled down
    # (big scale) or skipped WITH A NOTE, never started and killed.
    # Every admitted scale runs in THIS process: the parent has already
    # trained on the chip, and a chip belongs to one process at a time.

    def admit(task, est_s):
        """Remaining-budget admission for one phase; returns the skip
        note (None = run it)."""
        left = budget_left() - FINISH_RESERVE_S
        if est_s <= left:
            return None
        return (f"BENCH_BUDGET_S phase bound: est {est_s:.0f}s > "
                f"{left:.0f}s left")

    if os.environ.get("BENCH_BIG", "1") != "0" \
            and BENCH_ROWS_BIG > rows_primary:
        # HIGGS true scale: the f32 accuracy gate already ran at the
        # primary scale (same kernels, same quantization); rerunning
        # two 10.5M trainings would double the bench wall for no new
        # information.
        # local_ref at true scale too (round-4 verdict #5: the 34.1x
        # 10.5M ratio was prose-only — capture it in the JSON record).
        # Unit is per (row * iter) — the r8 estimate silently assumed
        # BENCH_ITERS_BIG == BENCH_ITERS
        big_wall_unit = primary_wall * 1.5 \
            / (rows_primary * max(BENCH_ITERS, 1))
        rows_big = BENCH_ROWS_BIG
        est = big_wall_unit * rows_big * max(BENCH_ITERS_BIG, 1)
        note = admit("big", est)
        if note is not None:
            # scale the row count down to what the budget fits (floor
            # 2x primary — below that the point adds nothing)
            rows_fit = int((budget_left() - FINISH_RESERVE_S)
                           / (big_wall_unit * max(BENCH_ITERS_BIG, 1)))
            rows_big = rows_fit if rows_fit >= 2 * rows_primary else 0
        if rows_big:
            s = run_scale(
                rows_big, BENCH_ITERS_BIG, params, check_f32=False,
                local_ref=os.environ.get("BENCH_LOCAL_REF_BIG",
                                         "1") != "0",
                ref_iters=int(os.environ.get("BENCH_REF_ITERS_BIG",
                                             10)))
            if rows_big != BENCH_ROWS_BIG:
                s["scaled_down_from"] = BENCH_ROWS_BIG
                s["budget_note"] = note
            scales.append(s)
        else:
            scales.append({"task": "binary_big", "rows": BENCH_ROWS_BIG,
                           "skipped": note})
    if os.environ.get("BENCH_LTR", "1") != "0":
        ltr_rows = int(os.environ.get("BENCH_LTR_QUERIES", 18_900)) * 120
        ltr_iters = int(os.environ.get("BENCH_LTR_ITERS", 30))
        # width factor: MS-LTR is 136 features vs the 28-feature
        # primary; anchors self-box against the remaining budget
        est = (primary_wall * 1.5 * (136 / 28)
               * (ltr_rows * ltr_iters) / (rows_primary * BENCH_ITERS))
        note = admit("lambdarank", est)
        if note is None:
            scales.append(run_ltr_scale())
        else:
            scales.append({"task": "lambdarank", "skipped": note})
    predict_block = None
    if os.environ.get("BENCH_PREDICT", "1") != "0":
        p_rows = int(os.environ.get("BENCH_PREDICT_TRAIN_ROWS", 200_000))
        p_iters = int(os.environ.get("BENCH_PREDICT_ITERS", 50))
        est = (primary_wall * 1.5
               * (p_rows * p_iters) / (rows_primary * BENCH_ITERS)) + 30
        note = admit("predict", est)
        if note is None:
            predict_block = run_predict_scale(params)
        else:
            predict_block = {"task": "predict", "skipped": note}
    construct_block = None
    if os.environ.get("BENCH_CONSTRUCT", "1") != "0":
        c_rows = int(os.environ.get("BENCH_CONSTRUCT_ROWS",
                                    min(BENCH_ROWS, 1_000_000)))
        # three constructions (serial python, parallel, threads=1) + a
        # cache round trip; the serial Python pass dominates at
        # ~3-5 s/M rows on one core — 20 s/M is a safe ceiling
        est = max(10.0, 20.0 * c_rows / 1e6)
        note = admit("construct", est)
        if note is None:
            construct_block = run_construct_scale(params)
        else:
            construct_block = {"task": "construct", "rows": c_rows,
                               "skipped": note}
    shard_block = None
    if os.environ.get("BENCH_SHARD", "1") != "0":
        s_rows = int(os.environ.get("BENCH_SHARD_ROWS",
                                    min(BENCH_ROWS, 500_000)))
        # two constructions (single-matrix + sharded) + a standalone
        # merge pass + a cache round trip; same per-row ceiling as the
        # construct block, doubled
        est = max(10.0, 40.0 * s_rows / 1e6)
        note = admit("shard_construct", est)
        if note is None:
            shard_block = run_shard_construct(params)
        else:
            shard_block = {"task": "shard_construct", "rows": s_rows,
                           "skipped": note}
    dist_block = None
    if os.environ.get("BENCH_DIST", "1") != "0":
        # two CPU-pinned worker interpreters + three tiny exchanges:
        # the wall is import-dominated (~20 s on one core), not
        # data-dependent
        note = admit("distributed_exchange", 60.0)
        if note is None:
            dist_block = run_distributed_exchange(params)
        else:
            dist_block = {"task": "distributed_exchange",
                          "skipped": note}
    compact_block = None
    if os.environ.get("BENCH_COMPACT", "1") != "0":
        cb_rows = int(os.environ.get("BENCH_COMPACT_ROWS",
                                     min(BENCH_ROWS, 500_000)))
        # two constructions + two tiny (2-iteration) trainings; same
        # per-row ceiling as the construct block, doubled for the two
        # modes
        est = max(10.0, 40.0 * cb_rows / 1e6)
        note = admit("compact_bins", est)
        if note is None:
            # the admitted cb_rows feeds the run too, so admission and
            # workload can never diverge
            compact_block = run_compact_bins(params, rows=cb_rows)
        else:
            compact_block = {"task": "compact_bins", "rows": cb_rows,
                             "skipped": note}
    if budget_left() > 60 + FINISH_RESERVE_S:
        higgs = run_higgs_real(params)
        if higgs is not None:
            scales.append(higgs)
    elif os.environ.get("BENCH_HIGGS_PATH") \
            or os.environ.get("BENCH_HIGGS") == "1":
        # the real-HIGGS scale was REQUESTED but the budget is spent —
        # document the hole instead of silently dropping the point
        scales.append({"task": "higgs_real",
                       "skipped": "BENCH_BUDGET_S exhausted"})

    result = {
        "metric": f"higgs_synth_{rows_primary//1000}k_{BENCH_ITERS}trees_s",
        "device": device,
        "value": primary["value"],
        "unit": "s",
        "vs_baseline": primary["vs_baseline"],
        "auc": primary["auc"],
        "auc_f32": primary["auc_f32"],
        "auc_delta": primary["auc_delta"],
        "prep_s": primary["prep_s"],
        "compile_s": primary["compile_s"],
        "cold_total_s": primary["cold_total_s"],
        # ROOFLINE headroom #3 series: device wait vs host/dispatch
        # wall, per tree, at the primary scale
        "host_dispatch_ms_per_tree": primary["host_dispatch_ms_per_tree"],
        "device_wait_ms_per_tree": primary["device_wait_ms_per_tree"],
        "scales": scales,
        "budget": {"budget_s": BENCH_BUDGET_S,
                   "elapsed_s": round(time.time() - _T0, 1)},
    }
    if predict_block is not None:
        # the serving roofline block: bulk rows/s, micro-batch p50,
        # compile count (one per shape bucket) and the task=predict
        # anchor status (docs/ROOFLINE.md "Serving roofline")
        result["predict"] = predict_block
    if construct_block is not None:
        # the construction roofline block (round 11): cold-construct
        # rows/s parallel vs serial (same run), thread scaling, binary-
        # cache v2 reload ratio and the reference-CSV-load anchor
        # (docs/ROOFLINE.md round-11 delta)
        result["construct"] = construct_block
    if shard_block is not None:
        # the sharded-construct block (round 16): per-shard construct
        # rows/s, distributed bin-find merge wall, RSS per route,
        # shard-cache round trip — parity-gated against the
        # single-matrix construction inside the block
        result["shard_construct"] = shard_block
    if dist_block is not None:
        # the TCP distributed-exchange block (this round): per-mode
        # wire bytes over real sockets, q16/q8 payload-reduction gates
        # and host-codec bit-exactness — all enforced inside the block
        result["distributed_exchange"] = dist_block
    if compact_block is not None:
        # the sub-byte packed-bin block (round 18): construct rows/s
        # per bin width, host + gauge-measured device matrix bytes,
        # the histogram bytes-read model — packing-ratio- and
        # tree-parity-gated inside the block
        result["compact_bins"] = compact_block
    if "chunk_slope" in primary:
        # the round-6/7 per-iteration chunk-slope fit and what
        # dispatch_chunk=auto would pick on this host
        result["chunk_slope"] = primary["chunk_slope"]
    if _LOCAL_REF_NOTES:
        result["local_ref_validation"] = _LOCAL_REF_NOTES
    if "vs_local_reference" in primary:
        # the MEASURED same-machine ratio (round-3 verdict #2): the
        # actual reference CPU binary on the same data on this host —
        # quote this one, the scaled 2013 number is only for continuity
        result["vs_local_reference"] = primary["vs_local_reference"]
        result["local_ref"] = primary["local_ref"]
    print(json.dumps(result))
    # diagnostics on stderr so the stdout contract stays one line
    # (defensive .get throughout: skip records and the higgs scale
    # don't carry the full field set, and a diagnostics KeyError must
    # never turn a completed bench into rc != 0)
    for s in scales:
        if "skipped" in s:
            print(f"{s.get('task', 'scale')} skipped: {s['skipped']}",
                  file=sys.stderr)
            continue
        if s.get("task") == "lambdarank":
            extra = ""
            if "vs_local_reference" in s:
                extra = (f" vs_local_ref={s['vs_local_reference']} "
                         f"(ref {s['local_ref']['per_tree_ms']}ms/tree @"
                         f"{s['local_ref']['threads']}thr ndcg10 "
                         f"{s['local_ref']['ndcg10']})")
            print(f"ltr rows={s['rows']} per_tree={s['per_tree_ms']}ms "
                  f"vs_baseline={s['vs_baseline']} "
                  f"ndcg10={s['ndcg10']} (untrained "
                  f"{s['ndcg10_untrained']}) prep={s['prep_s']}s{extra}",
                  file=sys.stderr)
            continue
        extra = ""
        if "vs_local_reference" in s:
            extra = (f" vs_local_ref={s['vs_local_reference']} "
                     f"(ref {s['local_ref']['per_tree_ms']}ms/tree @"
                     f"{s['local_ref']['threads']}thr auc "
                     f"{s['local_ref']['auc']})")
        print(f"rows={s.get('rows')} per_tree={s.get('per_tree_ms')}ms "
              f"vs_baseline={s.get('vs_baseline')} prep={s.get('prep_s')}s "
              f"compile={s.get('compile_s')}s{extra}", file=sys.stderr)
    if construct_block is not None:
        if "skipped" in construct_block:
            print(f"construct skipped: {construct_block['skipped']}",
                  file=sys.stderr)
        else:
            extra = ""
            if "vs_local_reference" in construct_block:
                extra = (f" vs_local_ref="
                         f"{construct_block['vs_local_reference']} (ref "
                         f"{construct_block['local_ref']['construct_s']}"
                         "s)")
            c = construct_block
            print(f"construct rows={c['rows']} "
                  f"cold={c['cold_construct_s']}s "
                  f"({c['cold_rows_per_s']} rows/s) "
                  f"serial={c['serial_construct_s']}s "
                  f"speedup={c['speedup_vs_serial']}x "
                  f"reload={c['cache_reload_s']}s "
                  f"({c['reload_x_cold']}x cold){extra}",
                  file=sys.stderr)
    if shard_block is not None:
        if "skipped" in shard_block:
            print(f"shard_construct skipped: {shard_block['skipped']}",
                  file=sys.stderr)
        else:
            sb = shard_block
            print(f"shard_construct rows={sb['rows']} "
                  f"shards={sb['shards']} "
                  f"wall={sb['shard_construct_s']}s "
                  f"({sb['per_shard_rows_per_s']} rows/s/shard) "
                  f"merge={sb['merge_wall_ms']}ms "
                  f"vs_single={sb['vs_single_matrix']}x "
                  f"rss={sb['rss_sharded_mb']}MB "
                  f"(single {sb['rss_single_mb']}MB)", file=sys.stderr)
    if compact_block is not None:
        if "skipped" in compact_block:
            print(f"compact_bins skipped: {compact_block['skipped']}",
                  file=sys.stderr)
        else:
            cb = compact_block
            print(f"compact_bins rows={cb['rows']} "
                  f"ratio={cb['packing_ratio']}x "
                  f"(device {cb['device_packing_ratio']}x) "
                  f"construct 4bit/8bit="
                  f"{cb['construct_ratio_4bit_vs_8bit']}x "
                  f"hist_stream={cb['hist_stream_ratio']}x "
                  f"parity={cb['parity']}", file=sys.stderr)
    if predict_block is not None:
        if "skipped" in predict_block:
            print(f"predict skipped: {predict_block['skipped']}",
                  file=sys.stderr)
        else:
            extra = ""
            if "vs_local_reference" in predict_block:
                extra = (f" vs_local_ref="
                         f"{predict_block['vs_local_reference']} (ref "
                         f"{predict_block['local_ref']['rows_per_s']} "
                         "rows/s)")
            print(f"predict bulk={predict_block['bulk_rows_per_s']} "
                  f"rows/s p50[{predict_block['small_batch']}]="
                  f"{predict_block['p50_ms']}ms "
                  f"compiles={predict_block['compile_count']} "
                  f"buckets={predict_block['buckets_used']}{extra}",
                  file=sys.stderr)


if __name__ == "__main__":
    main()
