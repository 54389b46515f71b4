"""Checkpoint save/resume overhead probe + smoke fault-plan recovery.

Run by ``scripts/bench_smoke.sh`` and asserted by
``tests/test_bench_smoke.py``.  Three child runs of one tiny training
job (same deterministic data, ``checkpoint_freq=2``):

1. **cold**    — uninterrupted; yields the cold wall and the
   checkpoint-save telemetry (ms per snapshot).
2. **kill**    — ``LTPU_FAULT_PLAN=gbdt.train_chunk:3:kill`` SIGKILLs
   the process at the third fused-chunk dispatch (a real ``kill -9``
   through the fault harness, docs/RELIABILITY.md).
3. **resume**  — the same command again; auto-resumes from the newest
   valid checkpoint and must produce a byte-identical model.

Writes ``/tmp/lgbtpu_smoke/reliability.json``:
``save_ms_per_snapshot`` (the per-snapshot overhead series),
``resume_vs_cold_delta_s`` (wall saved by resuming instead of
retraining), ``kill_recovery`` ("pass"/"fail") and the raw runs.

Usage: python scripts/reliability_probe.py [out_json]
       python scripts/reliability_probe.py --child <model_out>
"""
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

ITERS = 8
CHUNK = 2


def child(out_model: str) -> None:
    import numpy as np

    import lightgbm_tpu as lgb
    from lightgbm_tpu.telemetry import TELEMETRY
    TELEMETRY.configure("counters")
    rng = np.random.RandomState(11)
    X = rng.randn(600, 8)
    y = (X[:, 0] + 0.3 * rng.randn(600) > 0).astype(float)
    # verbose=1: the "Resumed training from checkpoint" info line (on
    # stderr) is how the parent PROVES the third run resumed rather
    # than deterministically retraining from scratch
    params = dict(objective="binary", num_leaves=15, max_bin=63,
                  verbose=1, dispatch_chunk=CHUNK, checkpoint_freq=2,
                  output_model=out_model, retry_backoff_s=0.0)
    t0 = time.perf_counter()
    bst = lgb.train(params, lgb.Dataset(X, label=y), ITERS,
                    verbose_eval=False)
    wall = time.perf_counter() - t0
    bst.save_model(out_model)
    c = TELEMETRY.counters()
    print(json.dumps({
        "wall_s": round(wall, 3),
        "trees": bst.num_trees(),
        "checkpoint_saves": c.get("checkpoint_saves", 0),
        "checkpoint_save_ms": round(c.get("checkpoint_save_ms", 0.0),
                                    3),
    }))


def run_child(out_model: str, fault_plan: str = ""):
    # CPU-pinned like the other probes' children: one is SIGKILLed
    # mid-run and the next starts at once — on a chip host it would
    # find the chip still held
    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    env.pop("LTPU_FAULT_PLAN", None)
    if fault_plan:
        env["LTPU_FAULT_PLAN"] = fault_plan
    t0 = time.perf_counter()
    run = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child",
         out_model],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300)
    wall = time.perf_counter() - t0
    info = {}
    for line in (run.stdout or "").splitlines():
        line = line.strip()
        if line.startswith("{"):
            info = json.loads(line)
    return run.returncode, wall, info, run


def main() -> int:
    out_json = sys.argv[1] if len(sys.argv) > 1 \
        else "/tmp/lgbtpu_smoke/reliability.json"
    os.makedirs(os.path.dirname(out_json), exist_ok=True)
    work = os.path.join(os.path.dirname(out_json), "reliability_work")
    os.makedirs(work, exist_ok=True)
    cold_model = os.path.join(work, "cold.txt")
    kill_model = os.path.join(work, "kill.txt")
    for stale in (cold_model, kill_model):
        if os.path.exists(stale):
            os.unlink(stale)
        for ck in os.listdir(work):
            if ck.startswith(os.path.basename(stale) + ".ckpt"):
                os.unlink(os.path.join(work, ck))

    rc, cold_wall, cold_info, cold_run = run_child(cold_model)
    if rc != 0:
        sys.stderr.write(cold_run.stdout + cold_run.stderr)
        return 1
    saves = max(1, int(cold_info.get("checkpoint_saves", 0)))
    save_ms = cold_info.get("checkpoint_save_ms", 0.0) / saves

    # SIGKILL at the third fused-chunk dispatch: iterations 4..6 never
    # run; the newest valid checkpoint is iteration 4
    rc_kill, _, _, _ = run_child(kill_model,
                                 fault_plan="gbdt.train_chunk:3:kill")
    rc_res, resume_wall, res_info, res_run = run_child(kill_model)
    equal = False
    if rc_res == 0 and os.path.exists(kill_model):
        with open(cold_model) as a, open(kill_model) as b:
            equal = a.read() == b.read()
    resumed = "Resumed training from checkpoint" in (
        res_run.stdout + res_run.stderr)
    ok = rc_kill == -9 and rc_res == 0 and equal and resumed

    out = {
        "iters": ITERS,
        "dispatch_chunk": CHUNK,
        "checkpoint_saves": saves,
        "save_ms_per_snapshot": round(save_ms, 3),
        "cold_wall_s": round(cold_info.get("wall_s", cold_wall), 3),
        "resume_wall_s": round(res_info.get("wall_s", resume_wall), 3),
        # resuming retrains only the lost tail, so the in-train wall
        # should come in under the cold run's (noisy at smoke scale —
        # reported, not gated)
        "resume_vs_cold_delta_s": round(
            cold_info.get("wall_s", 0.0) - res_info.get("wall_s", 0.0),
            3),
        "kill_returncode": rc_kill,
        "byte_identical": equal,
        "kill_recovery": "pass" if ok else "fail",
    }
    with open(out_json, "w") as f:
        json.dump(out, f, indent=1)
    sys.stderr.write("reliability probe: " + json.dumps(out) + "\n")
    return 0 if ok else 1


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--child":
        child(sys.argv[2])
        sys.exit(0)
    sys.exit(main())
