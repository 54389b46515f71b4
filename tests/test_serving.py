"""Online serving subsystem (round-14 tentpole): micro-batching
scheduler, model registry with hot swap, load-shedding HTTP frontend.

Pins the tentpole's contracts:

- coalesced results are BYTE-identical to direct ``Booster.predict``
  of the same rows (JSON and CSV transport included), across
  concurrent clients and mixed batch sizes;
- deadline/coalescing semantics against an injectable clock (no
  sleeps, no timing races);
- N concurrent single-row requests cost strictly fewer than N
  dispatches, and ZERO new jit traces occur after registry warmup
  (the ``test_predict_cache`` compile-count lint extended to the
  serving path);
- hot swap under live load never fails a request and never serves a
  mixed-version response; rollback is a pointer flip;
- admission control sheds with 503 + Retry-After instead of queueing
  into a timeout; the ``serving.request`` fault seam exercises the
  500 + flight-dump path without tearing down the listener.
"""
import json
import os
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.ops.predict import PREDICT_TELEMETRY
from lightgbm_tpu.reliability.faults import FAULTS
from lightgbm_tpu.serving import (MicroBatcher, ModelRegistry,
                                  ServingFrontend, ShedLoad)
from lightgbm_tpu.telemetry import TELEMETRY


def _train(f=6, leaves=15, iters=5, n=300, seed=0, label_col=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = X[:, label_col] - 0.4 * X[:, (label_col + 1) % f]
    p = {"objective": "regression", "verbose": -1,
         "num_leaves": leaves, "min_data_in_leaf": 5}
    return lgb.train(p, lgb.Dataset(X, label=y), iters,
                     verbose_eval=False), X


def _cfg(**over):
    base = {"verbose": -1}
    base.update(over)
    return Config.from_params(base)


@pytest.fixture(autouse=True)
def _telemetry():
    TELEMETRY.configure("counters")
    TELEMETRY.reset()
    yield
    FAULTS.reset()
    TELEMETRY.stop_metrics_server()


def _post(port, model, body, ctype="application/json", timeout=60):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/predict/{model}", data=body,
        headers={"Content-Type": ctype})
    resp = urllib.request.urlopen(req, timeout=timeout)
    return resp.status, json.loads(resp.read())


# ---------------------------------------------------------------------------
# micro-batcher
# ---------------------------------------------------------------------------
def test_batcher_concurrent_mixed_sizes_byte_identical():
    """N threads x mixed batch sizes through one batcher == direct
    Booster.predict of the same rows, byte for byte."""
    bst, X = _train()
    batcher = MicroBatcher(bst.predict, _cfg(serve_batch_deadline_ms=5))
    sizes = (1, 3, 7, 16, 2, 11)
    results = {}
    errors = []

    def worker(i):
        n = sizes[i % len(sizes)]
        rows = X[i * 7:i * 7 + n]
        try:
            results[i] = (rows, batcher.submit(rows, timeout_s=60))
        except Exception as e:  # pragma: no cover - failure reporting
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(12)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    batcher.close()
    assert not errors, errors
    assert len(results) == 12
    for rows, got in results.values():
        np.testing.assert_array_equal(got, bst.predict(rows))


def test_deadline_and_coalescing_semantics_injectable_clock():
    """The dispatch decision against a fake clock: no dispatch before
    the oldest request's deadline, dispatch at deadline, immediate
    dispatch on a full batch, and the row cap splits batches on
    request boundaries."""
    now = [100.0]
    calls = []

    def predict(rows):
        calls.append(rows.shape[0])
        return np.zeros(rows.shape[0])

    b = MicroBatcher(
        predict, _cfg(serve_batch_deadline_ms=10, serve_max_batch_rows=8),
        clock=lambda: now[0], start=False)

    def enqueue(n):
        t = threading.Thread(
            target=lambda: b.submit(np.zeros((n, 4)), timeout_s=30))
        t.start()
        # wait until the request is actually queued
        for _ in range(1000):
            if b._pending and b._pending[-1].n == n:
                break
            threading.Event().wait(0.001)
        return t

    t1 = enqueue(1)
    assert not b._ready(now[0]), "dispatched before any deadline"
    now[0] += 0.009
    assert not b._ready(now[0]), "dispatched before the 10 ms deadline"
    now[0] += 0.002
    assert b._ready(now[0]), "deadline passed but not ready"
    # a second request arriving later must NOT reset the window
    t2 = enqueue(2)
    assert b._ready(now[0])
    with b._lock:
        batch = b._take_batch()
    assert [r.n for r in batch] == [1, 2], "window requests coalesced"
    b._run_batch(batch)
    t1.join(30), t2.join(30)
    assert calls == [3]

    # full batch dispatches immediately, and the cap splits on
    # request boundaries (5 + 4 > 8 -> second batch)
    threads = [enqueue(5), enqueue(4)]
    assert b._ready(now[0]), "full batch must not wait for deadline"
    with b._lock:
        first = b._take_batch()
    assert [r.n for r in first] == [5]
    b._run_batch(first)
    now[0] += 0.011
    b.drain_pending()
    for t in threads:
        t.join(30)
    assert calls == [3, 5, 4]
    b.close()


def test_eight_single_row_clients_coalesce_to_fewer_dispatches():
    """Acceptance: under >= 8 concurrent single-row clients the
    serving dispatch count is strictly less than the request count,
    proven via telemetry counters — deterministically, by queueing
    all 8 before the (not-yet-started) dispatcher runs."""
    bst, X = _train(seed=1)
    batcher = MicroBatcher(bst.predict, _cfg(), start=False)
    results = {}

    def worker(i):
        results[i] = batcher.submit(X[i], timeout_s=60)

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for _ in range(2000):
        if batcher.depth() == 8:
            break
        threading.Event().wait(0.001)
    assert batcher.depth() == 8
    dispatches = batcher.drain_pending()
    for t in threads:
        t.join(30)
    assert dispatches == 1, "8 queued single-row requests must "\
        "coalesce into one dispatch"
    c = TELEMETRY.counters()
    assert c["serve_requests"] == 8
    assert c["serve_dispatches"] == 1
    assert c["serve_dispatches"] < c["serve_requests"]
    assert c["serve_coalesced_requests"] == 8
    direct = bst.predict(X[:8])
    for i in range(8):
        np.testing.assert_array_equal(results[i],
                                      direct[i:i + 1])
    hists = TELEMETRY.histograms()
    assert hists["serve_batch_rows"]["count"] == 1
    assert hists["serve_queue_wait_ms"]["count"] == 8
    batcher.close()


def test_zero_new_compiles_after_registry_warmup():
    """The predict_cache trace-count lint extended to the serving
    path: after publish() warms the declared buckets, serving traffic
    inside those buckets triggers ZERO new jit traces."""
    bst, X = _train(f=7, leaves=11, iters=4, seed=2)
    cfg = _cfg(serve_max_batch_rows=64)
    registry = ModelRegistry(cfg)
    # warm the single-row bucket and the coalesced cap; device=True
    # pins the bucketed device predictor on the CPU test backend
    registry.publish("m", bst, warm=(1, 64),
                     predict_kwargs={"device": True})
    traces0 = PREDICT_TELEMETRY["traces"]
    batcher = registry.get("m").batcher
    threads = [threading.Thread(
        target=lambda i=i: registry.predict("m", X[i]))
        for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    registry.predict("m", X[:40])     # chunk inside the warmed cap
    assert PREDICT_TELEMETRY["traces"] == traces0, (
        "serving traffic inside warmed buckets must not compile")
    assert batcher.depth() == 0
    registry.close()


def test_shed_projected_wait_and_queue_full():
    """Admission control: queue-full and projected-wait rejections
    raise ShedLoad without queueing (deterministic — no dispatcher)."""
    b = MicroBatcher(lambda rows: np.zeros(rows.shape[0]),
                     _cfg(serve_queue_depth=2,
                          serve_shed_deadline_ms=50,
                          serve_max_batch_rows=4),
                     start=False)
    # enqueue two requests without waiting on them
    waiters = [threading.Thread(
        target=lambda: b.submit(np.zeros((1, 3)), timeout_s=30))
        for _ in range(2)]
    for t in waiters:
        t.start()
    for _ in range(2000):
        if b.depth() == 2:
            break
        threading.Event().wait(0.001)
    assert b.depth() == 2
    with pytest.raises(ShedLoad):
        b.submit(np.zeros((1, 3)))
    assert TELEMETRY.counters()["serve_shed_requests"] == 1
    # projected-wait path: a measured 100 ms dispatch EWMA with a
    # 50 ms shed deadline sheds even though the queue has space
    b.queue_depth = 10
    b._dispatch_ewma_ms = 100.0
    with pytest.raises(ShedLoad) as ei:
        b.submit(np.zeros((1, 3)))
    assert "projected queue wait" in str(ei.value)
    assert ei.value.retry_after_s > 0
    b.close(drain=True)
    for t in waiters:
        t.join(30)


def test_http_shed_returns_503_with_retry_after():
    """The HTTP shed path: a stalled dispatcher + full queue answer
    503 with a Retry-After header, and recover once unstalled."""
    bst, X = _train(seed=3)
    gate = threading.Event()
    in_dispatch = threading.Event()

    cfg = _cfg(serve_queue_depth=1, serve_batch_deadline_ms=0)
    registry = ModelRegistry(cfg)
    entry = registry.publish("m", bst, warm=())

    def gated(rows):
        in_dispatch.set()
        gate.wait(60)
        return bst.predict(rows)

    # stall the running dispatcher on its first dispatch
    entry.batcher.predict = gated
    frontend = ServingFrontend(registry, cfg)
    port = frontend.start(0).server_address[1]
    body = json.dumps({"rows": [X[0].tolist()]}).encode()

    oks, sheds = [], []

    def client():
        try:
            oks.append(_post(port, "m", body))
        except urllib.error.HTTPError as e:
            sheds.append((e.code, e.headers.get("Retry-After")))

    # request 0 occupies the dispatcher (gated); request 1 fills the
    # depth-1 queue; request 2 must shed with 503 + Retry-After
    threads = [threading.Thread(target=client) for _ in range(3)]
    threads[0].start()
    assert in_dispatch.wait(30), "dispatcher never picked up request 0"
    threads[1].start()
    for _ in range(2000):
        if entry.batcher.depth() >= 1:
            break
        threading.Event().wait(0.001)
    assert entry.batcher.depth() == 1
    threads[2].start()
    threads[2].join(30)
    assert sheds, "overflow request was not shed"
    code, retry_after = sheds[0]
    assert code == 503
    assert retry_after is not None and int(retry_after) >= 1
    assert TELEMETRY.counters()["serve_shed_requests"] == 1
    gate.set()
    for t in threads[:2]:
        t.join(60)
    assert len(oks) == 2, "admitted requests must still complete"
    frontend.stop()


# ---------------------------------------------------------------------------
# registry: hot swap + rollback
# ---------------------------------------------------------------------------
def test_hot_swap_atomic_no_failed_or_mixed_responses():
    """Acceptance: hot swap during live load — every response is
    byte-identical to exactly ONE version's direct predict (never a
    mix), none fail, and the new version's first request comes from
    an already-warm bucket (zero new traces at swap)."""
    bst1, X = _train(seed=4)
    bst2, _ = _train(seed=5, label_col=2)
    rows = X[:4]
    v1 = bst1.predict(rows, device=True)
    v2 = bst2.predict(rows, device=True)
    assert not np.array_equal(v1, v2)

    cfg = _cfg(serve_batch_deadline_ms=1)
    registry = ModelRegistry(cfg)
    registry.publish("m", bst1, warm=(4,),
                     predict_kwargs={"device": True})
    stop = threading.Event()
    errors, mixed = [], []
    seen_versions = set()

    def loadgen():
        while not stop.is_set():
            try:
                entry, out = registry.predict("m", rows)
            except Exception as e:
                errors.append(e)
                return
            want = v1 if entry.version == 1 else v2
            if not np.array_equal(out, want):
                mixed.append((entry.version, out))
            seen_versions.add(entry.version)

    threads = [threading.Thread(target=loadgen) for _ in range(4)]
    for t in threads:
        t.start()
    # let v1 serve, then swap under load: warm-before-cutover means
    # the publish itself compiles nothing new at these shapes either
    for _ in range(2000):
        if 1 in seen_versions:
            break
        threading.Event().wait(0.001)
    traces0 = PREDICT_TELEMETRY["traces"]
    registry.publish("m", bst2, warm=(4,),
                     predict_kwargs={"device": True})
    assert PREDICT_TELEMETRY["traces"] == traces0, (
        "same-shape hot swap must reuse the process-wide programs")
    for _ in range(4000):
        if 2 in seen_versions:
            break
        threading.Event().wait(0.001)
    stop.set()
    for t in threads:
        t.join(60)
    assert not errors, errors
    assert not mixed, mixed[:2]
    assert seen_versions == {1, 2}
    assert TELEMETRY.counters()["serve_model_swaps"] == 1
    # the replaced version drained and released
    assert registry._versions["m"][0].batcher.closed
    registry.close()


def test_registry_rollback_pointer_flip():
    bst1, X = _train(seed=6)
    bst2, _ = _train(seed=7, label_col=1)
    registry = ModelRegistry(_cfg())
    registry.publish("m", bst1, warm=())
    registry.publish("m", bst2, warm=())
    assert registry.get("m").version == 2
    entry = registry.rollback("m")
    assert entry.version == 1
    assert registry.get("m").version == 1
    # the restored version serves (fresh batcher on the old booster)
    _, out = registry.predict("m", X[:3])
    np.testing.assert_array_equal(out, bst1.predict(X[:3]))
    assert TELEMETRY.counters()["serve_rollbacks"] == 1
    with pytest.raises(ValueError):
        registry.rollback("m")          # no earlier SERVING version
    with pytest.raises(KeyError):
        registry.rollback("nope")
    # publishing after rollback picks the next free version number
    e3 = registry.publish("m", bst2, warm=())
    assert e3.version == 3
    # rollback follows SERVING history, not publish order: v1 was
    # serving before v3 (v2 was already rolled back as bad), so a
    # second rollback must restore v1, never re-serve v2
    assert registry.rollback("m").version == 1
    registry.close()


def test_registry_duplicate_version_and_missing_model():
    bst, _X = _train(seed=8)
    registry = ModelRegistry(_cfg())
    registry.publish("m", bst, version=7, warm=())
    with pytest.raises(ValueError):
        registry.publish("m", bst, version=7, warm=())
    with pytest.raises(KeyError):
        registry.get("other")
    assert registry.names() == ["m"]
    registry.close()


# ---------------------------------------------------------------------------
# HTTP frontend
# ---------------------------------------------------------------------------
def test_http_json_and_csv_parity_across_threads():
    """Acceptance: server round-trip byte-identical to
    Booster.predict for JSON and CSV bodies across >= 4 concurrent
    client threads (float repr JSON round-trips doubles exactly)."""
    bst, X = _train(seed=9)
    cfg = _cfg(serve_batch_deadline_ms=2)
    registry = ModelRegistry(cfg)
    registry.publish("m", bst, warm=())
    frontend = ServingFrontend(registry, cfg)
    port = frontend.start(0).server_address[1]
    failures = []

    def client(i):
        rows = X[i * 5:i * 5 + 3]
        want = bst.predict(rows).tolist()
        try:
            if i % 2 == 0:
                body = json.dumps({"rows": rows.tolist()}).encode()
                status, out = _post(port, "m", body)
            else:
                body = "\n".join(
                    ",".join(repr(float(v)) for v in row)
                    for row in rows).encode()
                status, out = _post(port, "m", body, ctype="text/csv")
            if status != 200 or out["predictions"] != want:
                failures.append((i, status, out))
            if out["model"] != "m" or out["version"] != 1:
                failures.append((i, "bad identity", out))
        except Exception as e:
            failures.append((i, e))

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(6)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert not failures, failures[:3]
    # the shared listener still scrapes
    prom = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=10).read()
    assert b"ltpu_serve_http_requests_total" in prom
    assert b"ltpu_serve_request_ms_bucket" in prom
    health = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/healthz", timeout=10).read())
    assert health["status"] == "ok"
    models = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/models", timeout=10).read())
    assert models["m"]["version"] == 1
    frontend.stop()


def test_http_error_statuses():
    bst, X = _train(seed=10)
    cfg = _cfg()
    registry = ModelRegistry(cfg)
    registry.publish("m", bst, warm=())
    frontend = ServingFrontend(registry, cfg)
    port = frontend.start(0).server_address[1]
    ok_body = json.dumps({"rows": [X[0].tolist()]}).encode()

    def expect(code, model="m", body=ok_body, method="POST"):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/predict/{model}",
            data=body if method == "POST" else None, method=method)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == code, (ei.value.code, code)
        return ei.value

    expect(404, model="unknown")
    expect(400, body=b"{not json")
    expect(400, body=b"")
    expect(400, body=b'{"nothing": 1}')
    # wrong feature width rejected at admission (a mismatched matrix
    # inside a coalesced batch would fail every sharing request)
    expect(400, body=json.dumps({"rows": [[1.0, 2.0]]}).encode())
    expect(405, method="GET")
    frontend.stop()


def test_serving_fault_seam_flight_dump_listener_survives(tmp_path):
    """The serving.request reliability seam: an injected fault makes
    the handler answer 500 and dump the flight recorder naming the
    seam — and the NEXT request succeeds (the listener survives)."""
    bst, X = _train(seed=11)
    cfg = _cfg()
    registry = ModelRegistry(cfg)
    registry.publish("m", bst, warm=())
    frontend = ServingFrontend(registry, cfg)
    port = frontend.start(0).server_address[1]
    TELEMETRY.flight.arm(str(tmp_path / "flight"))
    FAULTS.configure("serving.request:1:RuntimeError")
    body = json.dumps({"rows": [X[0].tolist()]}).encode()
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(port, "m", body)
    assert ei.value.code == 500
    assert TELEMETRY.flight.dumps, "handler crash left no flight dump"
    dump = json.load(open(TELEMETRY.flight.dumps[-1]))
    assert dump["seam"] == "serving.request"
    assert dump["reason"] == "serving_handler_crash"
    assert TELEMETRY.counters()["serve_errors"] >= 1
    # fault plan exhausted: the listener still serves
    status, out = _post(port, "m", body)
    assert status == 200
    assert out["predictions"] == bst.predict(X[:1]).tolist()
    TELEMETRY.flight.disarm()
    frontend.stop()


# ---------------------------------------------------------------------------
# compile-cache telemetry (satellite)
# ---------------------------------------------------------------------------
def test_compile_cache_hit_miss_counters():
    """compile_cache_dir activity is a telemetry counter now, not a
    log line: the jax monitoring listener maps persistent-cache
    events to compile_cache_hits/compile_cache_misses."""
    from lightgbm_tpu import telemetry as T
    T.watch_compile_cache()
    assert T._CACHE_WATCH["armed"], "cache watch failed to arm"
    from jax._src import monitoring
    assert T._compile_cache_event in monitoring.get_event_listeners()
    before = TELEMETRY.counters()
    T._compile_cache_event("/jax/compilation_cache/cache_hits")
    T._compile_cache_event("/jax/compilation_cache/cache_misses")
    T._compile_cache_event("/jax/compilation_cache/unrelated")
    c = TELEMETRY.counters()
    assert c["compile_cache_hits"] == \
        before.get("compile_cache_hits", 0) + 1
    assert c["compile_cache_misses"] == \
        before.get("compile_cache_misses", 0) + 1
    # and a REAL fresh compilation reports through the same counters
    # (the suite's persistent cache is enabled by conftest)
    import jax
    import jax.numpy as jnp
    miss0 = TELEMETRY.counters().get("compile_cache_misses", 0)
    hit0 = TELEMETRY.counters().get("compile_cache_hits", 0)

    @jax.jit
    def probe(x):
        return x * 2.0 + 3.0

    probe(jnp.arange(23.0)).block_until_ready()
    c = TELEMETRY.counters()
    assert (c.get("compile_cache_misses", 0) > miss0
            or c.get("compile_cache_hits", 0) > hit0), (
        "a fresh jit compilation produced no cache counter")


def test_compile_cache_dir_resolution_rule(monkeypatch):
    """ONE rule places the compile cache (config.resolve_compile_cache_dir):
    JAX_COMPILATION_CACHE_DIR set -> the program sets NO directory in
    code, whatever compile_cache_dir says; unset -> <checkout>/.jax_cache
    for the default, the given path otherwise, "" disables.  Never a
    temp name, pid or timestamp: the path is part of jax's cache key."""
    import jax

    import conftest
    from lightgbm_tpu import config as C
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert Config().compile_cache_dir == "auto"
    assert C.DEFAULT_COMPILE_CACHE_DIR == os.path.join(repo, ".jax_cache")
    suite_dir = jax.config.jax_compilation_cache_dir
    try:
        # nothing placed from outside: the code picks the directory
        jax.config.update("jax_compilation_cache_dir", None)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert C.resolve_compile_cache_dir("auto") == \
            C.DEFAULT_COMPILE_CACHE_DIR
        assert C.resolve_compile_cache_dir("/some/where") == "/some/where"
        assert C.resolve_compile_cache_dir("") is None
        # placed from outside: the code sets nothing
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/outside")
        assert C.resolve_compile_cache_dir("auto") is None
        assert C.resolve_compile_cache_dir("/some/where") is None
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        # an embedding application that configured jax itself counts too
        jax.config.update("jax_compilation_cache_dir", "/embedder")
        assert C.resolve_compile_cache_dir("auto") is None
    finally:
        jax.config.update("jax_compilation_cache_dir", suite_dir)
    # the harness obeys the same rule: with the variable unset it chose
    # its fixed in-checkout directory, and that is what is live now
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        assert os.path.samefile(os.path.dirname(conftest.SUITE_CACHE_DIR),
                                repo)
        assert suite_dir == conftest.SUITE_CACHE_DIR


def test_conftest_leaves_an_outside_cache_alone(tmp_path):
    """tests/conftest.py under JAX_COMPILATION_CACHE_DIR: the variable's
    directory is the live one, not .jax_cache_cpu."""
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu")
    run = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, 'tests'); import conftest, jax; "
         "from lightgbm_tpu.config import Config; Config(); "
         "print(jax.config.jax_compilation_cache_dir)"],
        capture_output=True, text=True, env=env, cwd=repo, timeout=120)
    assert run.returncode == 0, run.stderr[-2000:]
    assert run.stdout.strip().splitlines()[-1] == str(tmp_path)


def test_prometheus_exposes_serving_families():
    """The serving counters/histograms land in the same Prometheus
    surface as the r8/r13 families."""
    bst, X = _train(seed=12)
    batcher = MicroBatcher(bst.predict, _cfg())
    batcher.submit(X[:3])
    batcher.close()
    prom = TELEMETRY.to_prometheus()
    assert "ltpu_serve_requests_total" in prom
    assert "ltpu_serve_dispatches_total" in prom
    assert 'ltpu_serve_batch_fill_bucket{le="1"}' in prom
    assert "ltpu_serve_queue_wait_ms_bucket" in prom


# ---------------------------------------------------------------------------
# CLI task=serve
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_cli_task_serve_end_to_end(tmp_path):
    """task=serve publishes input_model warm and serves HTTP until
    SIGINT: spawn the CLI, parse the logged port, verify parity and
    the shared /metrics listener, then shut down cleanly."""
    import os
    import re
    import signal
    import subprocess
    import sys
    import time as _time

    bst, X = _train(seed=13)
    model = tmp_path / "served.txt"
    bst.save_model(str(model))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-u", "-m", "lightgbm_tpu", "task=serve",
         f"input_model={model}", "serve_port=0",
         "predict_warm_buckets=1,16", "telemetry=counters"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env)
    port = None
    deadline = _time.time() + 120
    lines = []
    try:
        while _time.time() < deadline:
            line = proc.stdout.readline()
            if not line:
                break
            lines.append(line)
            m = re.search(r"http://127\.0\.0\.1:(\d+)/predict/served",
                          line)
            if m:
                port = int(m.group(1))
                break
        assert port, "serve task never logged its endpoint:\n" \
            + "".join(lines)
        # warm log lines appeared before traffic
        assert any("warm_predictor" in ln for ln in lines), lines
        body = json.dumps({"rows": X[:3].tolist()}).encode()
        status, out = _post(port, "served", body)
        assert status == 200
        # parity vs the same model file the server loaded
        ref = lgb.Booster(model_file=str(model)).predict(X[:3])
        assert out["predictions"] == ref.tolist()
        prom = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read()
        assert b"ltpu_serve_http_requests_total" in prom
    finally:
        proc.send_signal(signal.SIGINT)
        try:
            rc = proc.wait(60)
        except subprocess.TimeoutExpired:
            proc.kill()
            raise
    assert rc == 0, "".join(lines) + (proc.stdout.read() or "")
