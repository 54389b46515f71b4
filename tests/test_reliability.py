"""Fault-tolerance subsystem tests (docs/RELIABILITY.md).

Every recovery path here is driven through the DETERMINISTIC fault
harness (``lightgbm_tpu.reliability.faults``) — the Nth call at a
registered seam fails, every time; no sleeps, no signal races, no
flaky timing.  The headline invariant is kill-resume equivalence: a
training run SIGKILLed mid-train (a real ``os.kill`` injected by the
fault plan in a subprocess) and resumed from the newest valid
checkpoint produces a byte-identical model to an uninterrupted run.
"""
import glob
import os
import subprocess
import sys

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.reliability import checkpoint as ck
from lightgbm_tpu.reliability.faults import FAULTS, FaultInjected, \
    parse_plan
from lightgbm_tpu.reliability.retry import RetryPolicy, is_oom, \
    is_transient, retry_call
from lightgbm_tpu.telemetry import TELEMETRY

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_harness():
    """Every test starts and ends with no armed plan and a clean
    telemetry registry (both are process globals)."""
    FAULTS.reset()
    TELEMETRY.configure("counters")
    TELEMETRY.reset()
    yield
    FAULTS.reset()
    TELEMETRY.configure("off")
    TELEMETRY.reset()


def _data(n=300, f=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, f)
    y = (X[:, 0] + 0.25 * rng.randn(n) > 0).astype(float)
    return X, y


BASE = dict(objective="binary", num_leaves=7, max_bin=31, verbose=-1,
            dispatch_chunk=4, retry_backoff_s=0.0)


def _train(params, n_iters=12, seed=0, **kw):
    X, y = _data(seed=seed)
    return lgb.train(dict(BASE, **params), lgb.Dataset(X, label=y),
                     n_iters, verbose_eval=False, **kw)


# ---------------------------------------------------------------------------
# fault-plan grammar + seams
# ---------------------------------------------------------------------------
def test_fault_plan_grammar():
    entries = parse_plan(
        "gbdt.train_chunk:3:kill; predict.dispatch:1:oom;"
        "dataset.cache_io:2:OSError:x4")
    assert [(e.seam, e.nth, e.action, e.count) for e in entries] == [
        ("gbdt.train_chunk", 3, "kill", 1),
        ("predict.dispatch", 1, "oom", 1),
        ("dataset.cache_io", 2, "OSError", 4)]
    assert entries[2].matches(2) and entries[2].matches(5)
    assert not entries[2].matches(1) and not entries[2].matches(6)
    for bad in ("seam-only",
                "gbdt.train_chunk:0:OSError",
                "gbdt.train_chunk:1:NotAnException",
                "gbdt.train_chunk:1:OSError:y3",
                # unknown seam is a HARD error: a typo'd seam never
                # fires and the recovery test passes vacuously
                "gbdt.trainchunk:1:kill"):
        with pytest.raises(ValueError):
            parse_plan(bad)


def test_fault_injection_counts_calls_deterministically():
    FAULTS.configure("dataset.cache_io:2:OSError")
    from lightgbm_tpu.dataset_io import _open
    with _open(os.devnull, "rb"):       # call 1: clean
        pass
    with pytest.raises(OSError):        # call 2: injected
        _open(os.devnull, "rb")
    with _open(os.devnull, "rb"):       # call 3: clean again
        pass
    assert FAULTS.call_count("dataset.cache_io") == 3
    assert FAULTS.fired == [{"seam": "dataset.cache_io", "call": 2,
                             "action": "OSError"}]
    assert TELEMETRY.counters().get("faults_injected") == 1


def test_config_rearm_same_plan_keeps_counters():
    """The library builds several Configs from one params dict (train
    + lazy dataset construction); an unchanged fault_plan must NOT
    re-arm and zero the per-seam call counters mid-run."""
    from lightgbm_tpu.config import Config
    Config.from_params({"fault_plan": "dataset.cache_io:3:OSError",
                        "verbose": -1})
    from lightgbm_tpu.dataset_io import _open
    with _open(os.devnull, "rb"):
        pass
    assert FAULTS.call_count("dataset.cache_io") == 1
    # same plan again (a second Config from the same params): no reset
    Config.from_params({"fault_plan": "dataset.cache_io:3:OSError",
                        "verbose": -1})
    assert FAULTS.call_count("dataset.cache_io") == 1
    with _open(os.devnull, "rb"):
        pass
    with pytest.raises(OSError):        # still the 3rd call overall
        _open(os.devnull, "rb")
    # a DIFFERENT plan re-arms freshly
    Config.from_params({"fault_plan": "dataset.cache_io:1:OSError",
                        "verbose": -1})
    assert FAULTS.call_count("dataset.cache_io") == 0


def test_native_entry_seam():
    from lightgbm_tpu import native
    FAULTS.configure("native.entry:1:RuntimeError")
    with pytest.raises(RuntimeError, match="injected at seam"):
        native.get_lib()


def test_collectives_seam_fails_fast():
    """Collectives are lockstep across hosts: a per-host retry would
    desynchronize the schedule (hang, or pair with a peer's NEXT
    gather) — a failed collective must propagate loudly instead, and
    recovery is job restart + checkpoint resume."""
    from lightgbm_tpu.parallel.distributed import _allgather
    FAULTS.configure("collectives.allgather:1:ConnectionError")
    with pytest.raises(ConnectionError, match="injected at seam"):
        _allgather(np.arange(4.0))
    assert not TELEMETRY.counters().get("retries")
    FAULTS.reset()
    out = _allgather(np.arange(4.0))    # clean call still works
    assert out.reshape(-1).shape[0] >= 4


# ---------------------------------------------------------------------------
# retry policy + classification
# ---------------------------------------------------------------------------
def test_error_classification():
    assert is_transient(ConnectionError("x"))
    assert is_transient(RuntimeError("UNAVAILABLE: socket closed"))
    assert not is_transient(ValueError("shape mismatch"))
    assert is_oom(FaultInjected("RESOURCE_EXHAUSTED: out of memory"))
    assert is_oom(RuntimeError("Out of memory allocating 1 bytes"))
    # OOM is never transient: re-dispatching the same allocation
    # cannot succeed — the degradation ladder owns it
    assert not is_transient(RuntimeError("RESOURCE_EXHAUSTED: oops"))
    # the two messages a v5e gave in PR 21 (libtpu 0.0.34).  Run-time
    # HBM exhaustion shrinks with the chunk/bucket: the ladder's case
    assert is_oom(ValueError(
        "RESOURCE_EXHAUSTED: Error allocating device buffer: Attempting "
        "to allocate 4.00G. That was not possible. There are 3.62G "
        "free.; (0x0x0_HBM0)"))
    # a kernel refused for VMEM at COMPILE time carries the same
    # status, but a smaller chunk compiles the same kernel: it must
    # surface at once, not after a fresh compile per ladder rung
    assert not is_oom(RuntimeError(
        "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. Ran out "
        "of memory in memory space vmem. Used 256.00M of 128.00M vmem. "
        "Exceeded vmem capacity by 128.00M."))


def test_retry_backoff_bounded_and_exhausts():
    calls = []
    sleeps = []

    def flaky():
        calls.append(1)
        raise TimeoutError("deadline exceeded")

    with pytest.raises(TimeoutError):
        retry_call(flaky, policy=RetryPolicy(max_retries=3,
                                             base_delay_s=1.0,
                                             jitter=0.0),
                   sleep=sleeps.append)
    assert len(calls) == 4              # 1 try + 3 retries
    assert sleeps == [1.0, 2.0, 4.0]    # bounded exponential backoff
    # non-transient errors never retry
    calls.clear()
    with pytest.raises(ValueError):
        retry_call(lambda: (_ for _ in ()).throw(ValueError("no")),
                   policy=RetryPolicy(max_retries=3))
    # time-budget mode (the rendezvous seam): the budget governs, not
    # max_retries — a coordinator needing minutes is waited out
    calls.clear()
    sleeps.clear()
    with pytest.raises(TimeoutError):
        retry_call(flaky, policy=RetryPolicy(max_retries=0,
                                             base_delay_s=1.0,
                                             jitter=0.0, budget_s=7.5),
                   sleep=sleeps.append)
    assert sleeps == [1.0, 2.0, 4.0]    # next (8.0) would bust 7.5
    assert len(calls) == 4


def test_dispatch_retry_trains_identical_model():
    ref = _train({}).model_to_string()
    TELEMETRY.reset()
    FAULTS.configure("gbdt.train_chunk:2:ConnectionError")
    got = _train({}).model_to_string()
    # the fault fires BEFORE the dispatch mutates state, so the retry
    # re-enqueues the identical chunk: byte-identical trees
    assert got == ref
    c = TELEMETRY.counters()
    assert c.get("retries") == 1 and c.get("faults_injected") == 1


def test_dispatch_retry_exhaustion_propagates():
    FAULTS.configure("gbdt.train_chunk:1:ConnectionError:x9")
    with pytest.raises(ConnectionError, match="injected at seam"):
        _train({"dispatch_retries": 2})
    # 1 original + 2 retries, all injected
    assert FAULTS.call_count("gbdt.train_chunk") == 3


# ---------------------------------------------------------------------------
# checkpoint container
# ---------------------------------------------------------------------------
def test_checkpoint_roundtrip_and_fingerprint(tmp_path):
    path = str(tmp_path / "state.ckpt")
    state = {"iteration": 7, "blob": np.arange(5.0)}
    ck.save_checkpoint(path, state, "fp-abc")
    fp, loaded = ck.read_checkpoint(path)
    assert fp == "fp-abc" and loaded["iteration"] == 7
    assert np.array_equal(loaded["blob"], state["blob"])
    with pytest.raises(ck.CheckpointError, match="fingerprint"):
        ck.read_checkpoint(path, "fp-OTHER")
    assert not glob.glob(str(tmp_path / "*.tmp-*"))  # atomic: no tmp


def test_checkpoint_corruption_rejected(tmp_path):
    path = str(tmp_path / "state.ckpt")
    ck.save_checkpoint(path, {"iteration": 1}, "fp")
    blob = open(path, "rb").read()
    # bit-flip in the payload -> checksum mismatch
    bad = bytearray(blob)
    bad[len(bad) // 2] ^= 0xFF
    open(path, "wb").write(bytes(bad))
    with pytest.raises(ck.CheckpointError, match="checksum"):
        ck.read_checkpoint(path)
    # truncation -> rejected
    open(path, "wb").write(blob[: len(blob) // 2])
    with pytest.raises(ck.CheckpointError):
        ck.read_checkpoint(path)
    # not a checkpoint at all -> bad magic
    open(path, "wb").write(b"tree\nversion=v2\n" * 10)
    with pytest.raises(ck.CheckpointError, match="magic"):
        ck.read_checkpoint(path)


def test_rolling_retention_and_fallback_scan(tmp_path):
    prefix = str(tmp_path / "run.ckpt")
    for it in (2, 4, 6, 8):
        ck.save_rolling(prefix, it, {"iteration": it}, "fp", keep=3)
    assert [it for it, _ in ck.list_checkpoints(prefix)] == [8, 6, 4]
    # corrupt the newest: the scan falls back to the next valid one
    newest = ck.checkpoint_file(prefix, 8)
    blob = bytearray(open(newest, "rb").read())
    blob[-1] ^= 0x01
    open(newest, "wb").write(bytes(blob))
    it, state, path = ck.find_resume(prefix, "fp")
    assert it == 6 and state["iteration"] == 6
    # wrong fingerprint everywhere -> nothing valid -> cold start
    assert ck.find_resume(prefix, "other-fp") is None


# ---------------------------------------------------------------------------
# engine resume
# ---------------------------------------------------------------------------
def test_resume_midtrain_byte_identical(tmp_path):
    out = str(tmp_path / "m.txt")
    params = {"checkpoint_freq": 4, "output_model": out,
              "bagging_fraction": 0.8, "bagging_freq": 2,
              "feature_fraction": 0.9}
    full = _train(params, 12).model_to_string()
    # a FRESH train resuming from the mid-train (iter 8) checkpoint
    # must reproduce the exact bytes: scores, bagging RNG stream and
    # feature-sampling stream all restore
    got = _train(params, 12,
                 resume=out + ".ckpt_iter_8").model_to_string()
    assert got == full
    # resume=off ignores existing checkpoints and starts cold (same
    # bytes here because training is deterministic end-to-end)
    cold = _train(params, 12, resume=False).model_to_string()
    assert cold == full
    # checkpoints PAST a smaller target are skipped: a 10-iter run
    # auto-resumes from iter 8 (not the retained iter-12 file) and
    # matches a cold 10-iter run exactly
    cold10 = _train(params, 10, resume=False).model_to_string()
    got10 = _train(params, 10).model_to_string()
    assert got10 == cold10
    assert len(lgb.Booster(model_str=got10).models) == 10


def test_resume_rejects_mismatched_config(tmp_path):
    out = str(tmp_path / "m.txt")
    params = {"checkpoint_freq": 4, "output_model": out}
    _train(params, 8)
    assert ck.list_checkpoints(out + ".ckpt")
    # keep a copy of a num_leaves=7 checkpoint aside (the retrain
    # below rolls the prefix over with num_leaves=5 checkpoints)
    import shutil
    stale = str(tmp_path / "stale.ckpt")
    shutil.copy(ck.list_checkpoints(out + ".ckpt")[0][1], stale)
    # different num_leaves -> fingerprint mismatch -> auto-resume
    # refuses the stale checkpoints and trains cold
    cold_ref = _train({"num_leaves": 5}, 8).model_to_string()
    got = _train(dict(params, num_leaves=5), 8).model_to_string()
    assert got == cold_ref
    # explicit path with mismatched config errors LOUDLY
    with pytest.raises(ck.CheckpointError, match="fingerprint"):
        _train(dict(params, num_leaves=5), 8, resume=stale)


def test_resume_skips_corrupt_falls_back_to_previous(tmp_path):
    out = str(tmp_path / "m.txt")
    params = {"checkpoint_freq": 4, "output_model": out}
    full = _train(params, 12).model_to_string()
    # corrupt the NEWEST checkpoint (iter 12); auto-resume must fall
    # back to iter 8 and still finish byte-identical
    newest = ck.checkpoint_file(out + ".ckpt", 12)
    blob = bytearray(open(newest, "rb").read())
    blob[len(blob) // 2] ^= 0xFF
    open(newest, "wb").write(bytes(blob))
    got = _train(params, 12).model_to_string()
    assert got == full


def test_fingerprint_separates_init_model(tmp_path):
    """A continued-training run (init_model) and a fresh run must
    never adopt each other's checkpoints: engine passes the init-model
    identity into the fingerprint."""
    from lightgbm_tpu.config import Config
    X, y = _data()
    core = lgb.Dataset(X, label=y).construct(
        Config.from_params(dict(BASE)))
    cfg = Config.from_params(dict(BASE))
    fresh = ck.training_fingerprint(cfg, core, 0, "")
    seeded = ck.training_fingerprint(cfg, core, 0, "old_model.txt")
    assert fresh != seeded
    # end-to-end: checkpoints from a fresh run are refused by a
    # continued-training rerun (auto-resume scans come back empty and
    # it trains cold from the init model)
    out = str(tmp_path / "m.txt")
    base_model = str(tmp_path / "base.txt")
    _train({}, 4).save_model(base_model)

    def run(**kw):
        # continued training reads the raw matrix to seed scores
        ds = lgb.Dataset(X, label=y, free_raw_data=False)
        return lgb.train(dict(BASE, checkpoint_freq=4,
                              output_model=out), ds, 8,
                         verbose_eval=False, **kw)

    run()                              # fresh run writes checkpoints
    # auto-resume FIRST, while only fresh-run checkpoints exist: they
    # must be rejected (fingerprint) and the run trains from the init
    # model instead of adopting the fresh run's state
    cont = run(init_model=base_model)
    cold = run(init_model=base_model, resume=False)
    assert cont.model_to_string() == cold.model_to_string()
    assert len(cont.models) == 12      # 4 seeded + 8 trained


def test_early_stopping_state_round_trips(tmp_path):
    out = str(tmp_path / "m.txt")
    X, y = _data(400, 8, seed=3)
    Xv, yv = _data(120, 8, seed=4)
    params = dict(BASE, metric="binary_logloss",
                  early_stopping_round=3, checkpoint_freq=5,
                  output_model=out)

    def run(resume):
        er = {}
        ds = lgb.Dataset(X, label=y)
        bst = lgb.train(params, ds, 40,
                        valid_sets=[lgb.Dataset(Xv, label=yv,
                                                reference=ds)],
                        evals_result=er, verbose_eval=False,
                        resume=resume)
        return bst, er

    full, er_full = run(resume=False)
    ckpts = ck.list_checkpoints(out + ".ckpt")
    assert ckpts, "early-stopped run saved no checkpoint"
    resumed, er_res = run(resume=ckpts[-1][1])   # oldest kept
    assert resumed.best_iteration == full.best_iteration
    assert resumed.model_to_string() == full.model_to_string()
    # eval history restored + continued, not restarted
    assert er_res["valid_0"]["binary_logloss"] == \
        er_full["valid_0"]["binary_logloss"]


# ---------------------------------------------------------------------------
# snapshots (satellite: atomic writer + retention + chunk alignment)
# ---------------------------------------------------------------------------
def test_snapshots_atomic_rolling_and_chunk_aligned(tmp_path):
    out = str(tmp_path / "m.txt")
    _train({"snapshot_freq": 3, "snapshot_keep": 2,
            "output_model": out, "dispatch_chunk": 10}, 12)
    snaps = sorted(glob.glob(out + ".snapshot_iter_*"))
    # rolling retention: keep-last-2 of {3, 6, 9, 12}
    assert [os.path.basename(p) for p in snaps] == \
        ["m.txt.snapshot_iter_12", "m.txt.snapshot_iter_9"]
    assert not glob.glob(str(tmp_path / "*.tmp-*"))
    # snapshots are valid, loadable models
    snap = lgb.Booster(model_file=snaps[1])
    assert len(snap.models) == 9
    # the fix for the r12 satellite: snapshotting runs keep FUSED
    # chunk dispatch (boundary-cut to the snapshot schedule) instead
    # of silently degrading to per-iteration dispatch
    c = TELEMETRY.counters()
    assert c.get("chunks_dispatched", 0) == 4     # 3+3+3+3
    assert c.get("iterations") == 12


# ---------------------------------------------------------------------------
# OOM graceful degradation
# ---------------------------------------------------------------------------
def test_training_oom_downshifts_chunk():
    # bagging + feature sampling ON: the failed chunk consumed host
    # RNG draws before the fault, and train_chunk must restore the
    # streams so the downshifted re-dispatch draws the IDENTICAL
    # sequence — without that the downshift silently trains a
    # different model
    params = {"bagging_fraction": 0.8, "bagging_freq": 2,
              "feature_fraction": 0.8}
    ref = _train(params).model_to_string()
    TELEMETRY.reset()
    FAULTS.configure("gbdt.train_chunk:2:oom")
    got = _train(params).model_to_string()
    # chunk length is byte-parity pinned, so the downshift changes
    # dispatch amortization only — the model is identical
    assert got == ref
    assert TELEMETRY.counters().get("oom_downshifts") == 1


def test_serving_oom_downshifts_bucket():
    bst = _train({})
    X, _ = _data()
    host = bst.predict(X, device=False)
    FAULTS.configure("predict.dispatch:1:oom")
    dev = bst.predict(X, device=True)
    assert np.allclose(host, dev, rtol=1e-5, atol=1e-6)
    c = TELEMETRY.counters()
    assert c.get("oom_downshifts") == 1
    assert c.get("predict_requests") == 1
    # the degraded cap persists: the next request starts at the
    # smaller bucket without re-failing
    FAULTS.reset()
    dev2 = bst.predict(X, device=True)
    assert np.allclose(host, dev2, rtol=1e-5, atol=1e-6)
    assert TELEMETRY.counters().get("oom_downshifts") == 1


def test_serving_oom_at_min_bucket_reraises():
    bst = _train({})
    X, _ = _data(8)
    # every dispatch OOMs: the ladder runs out at bucket 1 and the
    # original error propagates (degradation must not mask a real
    # capacity problem forever)
    FAULTS.configure("predict.dispatch:1:oom:x64")
    with pytest.raises(FaultInjected, match="RESOURCE_EXHAUSTED"):
        bst.predict(X, device=True)


# ---------------------------------------------------------------------------
# kill-resume equivalence (the headline invariant)
# ---------------------------------------------------------------------------
_CHILD = """
import os, sys
import numpy as np
import lightgbm_tpu as lgb

out = sys.argv[1]
rng = np.random.RandomState(7)
X = rng.randn(400, 8)
y = (X[:, 0] + 0.25 * rng.randn(400) > 0).astype(float)
params = dict(objective="binary", num_leaves=15, max_bin=63, verbose=1,
              dispatch_chunk=4, checkpoint_freq=4, output_model=out,
              bagging_fraction=0.8, bagging_freq=2,
              feature_fraction=0.9, retry_backoff_s=0.0)
bst = lgb.train(params, lgb.Dataset(X, label=y), 20,
                verbose_eval=False)
bst.save_model(out)
print("TRAINED_OK", bst.num_trees())
"""


def _run_child(tmp_path, out, fault_plan=""):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("LTPU_FAULT_PLAN", None)
    if fault_plan:
        env["LTPU_FAULT_PLAN"] = fault_plan
    script = tmp_path / "child.py"
    script.write_text(_CHILD)
    return subprocess.run(
        [sys.executable, str(script), out], env=env, cwd=REPO,
        capture_output=True, text=True, timeout=240)


def test_kill_resume_byte_identical(tmp_path):
    """A run SIGKILLed mid-train (injected by the fault plan at the
    4th fused-chunk dispatch — a REAL kill -9, no cleanup, no atexit)
    and then re-launched auto-resumes from the newest valid checkpoint
    and produces a byte-identical model to an uninterrupted run."""
    out_cold = str(tmp_path / "cold.txt")
    out_kill = str(tmp_path / "kill.txt")
    # uninterrupted reference
    cold = _run_child(tmp_path, out_cold)
    assert cold.returncode == 0, cold.stdout + cold.stderr
    # SIGKILL at the 4th chunk dispatch: iterations 12..16 never run;
    # checkpoints at 4, 8, 12 were written (rolling keep-2 -> 8, 12)
    killed = _run_child(tmp_path, out_kill,
                        fault_plan="gbdt.train_chunk:4:kill")
    assert killed.returncode == -9, (killed.returncode, killed.stdout)
    assert "TRAINED_OK" not in killed.stdout
    assert not os.path.exists(out_kill), "killed run saved no model"
    ckpts = ck.list_checkpoints(out_kill + ".ckpt")
    assert [it for it, _ in ckpts] == [12, 8]
    # relaunch the SAME command: auto-resume from iteration 12
    resumed = _run_child(tmp_path, out_kill)
    assert resumed.returncode == 0, resumed.stdout + resumed.stderr
    log = resumed.stdout + resumed.stderr
    assert "Resumed training from checkpoint" in log
    assert "ckpt_iter_12" in log
    with open(out_cold) as f_cold, open(out_kill) as f_res:
        assert f_res.read() == f_cold.read()


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-v"]))
