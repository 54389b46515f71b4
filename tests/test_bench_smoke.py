"""bench.py plumbing regression gate.

The r5 perf artifact was an rc=124 timeout — a bench-only code path
(unbudgeted local-reference anchors) that nothing in the suite
exercised.  This runs the tiny-N smoke driver (scripts/bench_smoke.sh:
BENCH_ITERS=2, BENCH_LOCAL_REF=0) as a subprocess and pins the bench's
stdout contract: exactly one parseable JSON line carrying every field
the perf driver reads.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_bench_smoke_json_contract():
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_CHUNK="1")
    run = subprocess.run(
        ["sh", os.path.join(REPO, "scripts", "bench_smoke.sh")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=640)
    assert run.returncode == 0, (run.stdout or "")[-2000:] + \
        (run.stderr or "")[-2000:]
    lines = [ln for ln in run.stdout.strip().splitlines() if ln.strip()]
    assert len(lines) == 1, f"stdout must be ONE JSON line, got {lines!r}"
    out = json.loads(lines[0])
    for field in ("metric", "value", "unit", "vs_baseline", "auc",
                  "auc_delta", "scales", "budget"):
        assert field in out, f"missing {field}"
    assert out["budget"]["elapsed_s"] <= out["budget"]["budget_s"]
    tasks = {s.get("task", "binary") for s in out["scales"]}
    assert "lambdarank" in tasks, "LTR scale must run in the smoke"
    ltr = next(s for s in out["scales"] if s.get("task") == "lambdarank")
    # the same-data NDCG gate must EXECUTE or say why it didn't
    assert "ndcg_gate" in ltr
    # serving roofline block (round 8): bulk throughput, micro-batch
    # p50, compile telemetry and the parity gate result
    assert "predict" in out, "predict scale must run in the smoke"
    p = out["predict"]
    for field in ("bulk_rows_per_s", "p50_ms", "small_batch",
                  "compile_count", "buckets_used", "parity"):
        assert field in p, f"predict block missing {field}"
    assert p["parity"] == "pass"
    # compile-count lint: ONE compilation per shape bucket — every
    # batch size inside a bucket reuses the bucket's program
    assert p["compile_count"] == len(p["buckets_used"]), (
        f"{p['compile_count']} compiles for buckets "
        f"{p['buckets_used']} — bucketed predict must compile once "
        "per bucket")
    assert p["dispatches"] > p["compile_count"], \
        "smoke issued no cache-hit dispatches"
    # construction roofline block (round 11): cold vs serial rows/s,
    # thread scaling, cache-v2 reload — parity gated inside the bench
    assert "construct" in out, "construct scale must run in the smoke"
    c = out["construct"]
    for field in ("rows", "features", "cold_construct_s",
                  "cold_rows_per_s", "serial_construct_s",
                  "serial_rows_per_s", "speedup_vs_serial",
                  "threads_auto", "thread_scaling", "cache_save_s",
                  "cache_reload_s", "reload_x_cold", "parity"):
        assert field in c, f"construct block missing {field}"
    assert c["parity"] == "pass"
    assert set(c["thread_scaling"]) == {"1", "auto", "x"}
    # the anchor must be present or carry an explicit skip reason
    assert "local_ref" in c or "local_ref_skipped" in c
    # sharded-construct probe (round 16): 2 simulated participants,
    # merged-mapper + bin parity vs the single-matrix route, merge
    # wall, RSS per route, shard-cache v2 manifest round trip with
    # the wrong-world-size refusal exercised
    assert "shard_construct" in out, \
        "shard_construct probe must run in the smoke"
    sc = out["shard_construct"]
    for field in ("rows", "shards", "shard_construct_s",
                  "shard_rows_per_s", "per_shard_rows_per_s",
                  "single_construct_s", "merge_wall_ms",
                  "rss_single_mb", "rss_sharded_mb", "cache_reload_s",
                  "parity", "manifest_reject"):
        assert field in sc, f"shard_construct block missing {field}"
    assert sc["shards"] == 2, "smoke runs 2 simulated participants"
    assert sc["parity"] == "pass"
    assert sc["manifest_reject"] == "pass"
    # compact-bins probe (round 18): nibble-packed (bin_packing=4bit)
    # pipeline vs 8-bit on the same max_bin=15 draw — >=2x packing
    # ratio (host AND gauge-measured device matrix), construct rows/s
    # per mode, the histogram bytes-read model, byte-identical trees
    assert "compact_bins" in out, \
        "compact_bins probe must run in the smoke"
    cb = out["compact_bins"]
    for field in ("rows", "max_bin", "construct_rows_per_s_8bit",
                  "construct_rows_per_s_4bit",
                  "construct_ratio_4bit_vs_8bit",
                  "host_matrix_bytes_8bit", "host_matrix_bytes_4bit",
                  "bin_matrix_bytes_8bit", "bin_matrix_bytes_4bit",
                  "packing_ratio", "device_packing_ratio",
                  "hist_bytes_per_row_8bit", "hist_bytes_per_row_4bit",
                  "hist_stream_ratio", "parity",
                  # round-21 crumb tier + compressed exchange fields
                  "construct_rows_per_s_2bit_mb4",
                  "host_matrix_bytes_2bit", "bin_matrix_bytes_2bit",
                  "crumb_packing_ratio", "crumb_predicted_ratio",
                  "crumb_device_ratio", "hist_bytes_per_row_2bit",
                  "crumb_stream_ratio", "hist_exchange_bytes_f32",
                  "hist_exchange_bytes_q16", "hist_exchange_bytes_q8",
                  "hist_exchange_ratio_q16", "hist_exchange_ratio_q8"):
        assert field in cb, f"compact_bins block missing {field}"
    assert cb["max_bin"] == 15
    assert cb["packing_ratio"] >= 2.0, \
        "4-bit matrix must halve the 8-bit bytes at max_bin=15"
    # acceptance: device matrix <= 0.55x the 8-bit bytes, gauge-measured
    # (a zero gauge would make the ratio assert pass vacuously)
    assert cb["bin_matrix_bytes_8bit"] > 0, \
        "bin_matrix_bytes gauge must be measured, not defaulted"
    assert cb["bin_matrix_bytes_4bit"] <= \
        0.55 * cb["bin_matrix_bytes_8bit"]
    # crumb tier: the measured host ratio meets the layout-predicted
    # G / ceil(G/4) read-stream reduction on the max_bin=4 sub-draw
    assert cb["crumb_packing_ratio"] >= cb["crumb_predicted_ratio"]
    assert cb["bin_matrix_bytes_2bit"] > 0
    # compressed exchange: the wire payload genuinely shrinks 2x / 4x
    assert cb["hist_exchange_bytes_f32"] > 0
    assert cb["hist_exchange_ratio_q16"] >= 2.0
    assert cb["hist_exchange_ratio_q8"] >= 4.0
    assert cb["parity"] == "pass"
    # distributed-exchange probe (this round): the r21 hist_exchange
    # codec over the REAL 2-process TCP transport — per-mode wire
    # bytes from the collective_tcp_* per-primitive counters, q16/q8
    # payload-reduction gates, every mode bit-exact vs the host codec
    # inside the workers
    assert "distributed_exchange" in out, \
        "distributed_exchange probe must run in the smoke"
    dx = out["distributed_exchange"]
    for field in ("world", "hist_shape", "modes", "wire_ratio_q16",
                  "wire_ratio_q8", "total_wire_ratio_q16", "parity",
                  "wire_gate", "crc", "crc_overhead_frac", "crc_gate"):
        assert field in dx, f"distributed_exchange block missing {field}"
    assert dx["world"] == 2
    assert dx["parity"] == "pass" and dx["wire_gate"] == "pass"
    # frame-integrity budget (ISSUE 20): the tiered payload digest
    # must cost < 2% of the q16 wire-path wall
    assert dx["crc_gate"] == "pass"
    assert 0.0 <= dx["crc_overhead_frac"] < 0.02
    assert dx["crc"]["q16_wire_bytes"] > 0
    assert dx["wire_ratio_q16"] >= 2.0, \
        "q16 must halve the f32 wire payload over real TCP"
    assert dx["wire_ratio_q8"] >= 4.0
    for mode in ("f32", "q16", "q8"):
        assert dx["modes"][mode]["payload_wire_bytes"] > 0, \
            f"{mode} wire bytes must be measured, not defaulted"
    # the scale sync must actually cross the wire in the q modes
    assert dx["modes"]["q16"]["scale_wire_bytes"] > 0
    assert dx["modes"]["f32"]["scale_wire_bytes"] == 0
    # reliability probe (round 12): checkpoint save overhead measured
    # and the smoke fault-plan recovery (SIGKILL mid-train -> resume)
    # byte-identical — scripts/reliability_probe.py, run in-line by
    # bench_smoke.sh
    with open("/tmp/lgbtpu_smoke/reliability.json") as f:
        r = json.load(f)
    for field in ("save_ms_per_snapshot", "checkpoint_saves",
                  "cold_wall_s", "resume_wall_s",
                  "resume_vs_cold_delta_s", "kill_returncode",
                  "byte_identical", "kill_recovery"):
        assert field in r, f"reliability probe missing {field}"
    assert r["kill_recovery"] == "pass"
    assert r["kill_returncode"] == -9, "harness must really SIGKILL"
    assert r["byte_identical"] is True
    assert r["checkpoint_saves"] >= 2
    assert r["save_ms_per_snapshot"] > 0
    # chaos probe (round 19): seeded randomized multi-fault plans
    # across train/serve/continuous, gated by the invariant registry
    # — scripts/chaos_probe.py, run in-line by bench_smoke.sh
    with open("/tmp/lgbtpu_smoke/chaos.json") as f:
        ch = json.load(f)
    for field in ("plans_run", "plans_green", "plans", "invariants",
                  "faults_injected", "status"):
        assert field in ch, f"chaos probe missing {field}"
    assert ch["status"] == "pass"
    assert ch["plans_green"] == ch["plans_run"]
    if ch["budget_exceeded"]:
        # CHAOS_BUDGET_S tripped on a slow machine: the sweep stops
        # with a note INSTEAD of blowing the smoke wall — whatever ran
        # must still be green, but the floor below is waived
        assert ch["plans_run"] >= 1
    else:
        # the acceptance floor: >= 12 seeded plans across all three
        # workloads, every one green, every plan carrying its seed +
        # expanded spec for replay
        assert ch["plans_run"] >= 20, \
            f"chaos sweep ran only {ch['plans_run']} plans"
        # in-process workloads (serve/continuous) count into the
        # probe's own faults_injected; train faults fire in
        # subprocesses.  A zero here would mean the draws never hit a
        # live seam — vacuous plans
        assert ch["faults_injected"] >= 4
        workloads = {p["workload"] for p in ch["plans"]}
        assert workloads == {"train", "serve", "continuous",
                             "transport"}
    for p in ch["plans"]:
        assert p["green"] and not p["violations"], p
        assert isinstance(p["seed"], int) and p["plan"], \
            "a chaos plan must be replayable from its seed"
    assert set(ch["invariants"]) >= {
        "resume_byte_identical", "no_partial_artifacts",
        "ledger_converges", "serving_parity", "loud_failure",
        "transport_no_silent_misdata", "partition_heals",
        "coordinator_failover"}
    # distributed-observability probe (round 13): the Prometheus
    # textfile was written and scrape-parsed (bucket monotonicity is
    # asserted inside bench_smoke.sh), and the flight-recorder smoke
    # left a dump naming the injected seam
    import glob
    with open("/tmp/lgbtpu_smoke/metrics.prom") as f:
        prom = f.read()
    assert "ltpu_predict_latency_ms_bucket{le=" in prom
    assert 'le="+Inf"' in prom
    dumps = glob.glob("/tmp/lgbtpu_smoke/flight*.flight.json")
    assert dumps, "flight-recorder smoke left no dump"
    d = json.load(open(dumps[-1]))
    assert d["seam"] == "predict.dispatch"
    assert d["events"]
    # continuous-training probe (round 15): the closed
    # train->evaluate->publish loop — scripts/continuous_probe.py,
    # run in-line by bench_smoke.sh
    with open("/tmp/lgbtpu_smoke/continuous.json") as f:
        ct = json.load(f)
    for field in ("cycles", "rows_ingested", "publishes", "rollbacks",
                  "parity", "rollback_fired", "rollback_parity",
                  "kill_returncode", "byte_identical",
                  "kill_recovery"):
        assert field in ct, f"continuous probe missing {field}"
    assert ct["cycles"] >= 2 and ct["publishes"] >= 2
    # served predictions byte-identical to a direct Booster.predict
    # of the published model, before AND after the auto-rollback
    assert ct["parity"] == "pass"
    assert ct["rollback_fired"] and ct["rollbacks"] >= 1
    assert ct["rollback_parity"] == "pass"
    # the SIGKILL smoke really killed (-9), the cycle resumed from
    # its ledger, and the resumed publish is byte-identical
    assert ct["kill_returncode"] == -9
    assert ct["cycle_resumed_from_ledger"] is True
    assert ct["byte_identical"] is True
    assert ct["kill_recovery"] == "pass"
    # model-quality probe (round 17): profile captured at train,
    # monitors armed from the sidecar at publish, zero drift on
    # in-distribution rows, a shifted stream past threshold with the
    # warn fired, gauges on the Prometheus surface, report CLI
    # agreeing — scripts/quality_probe.py, run in-line by
    # bench_smoke.sh
    with open("/tmp/lgbtpu_smoke/quality.json") as f:
        q = json.load(f)
    for field in ("parity", "profile_features", "in_dist_worst_psi",
                  "shifted_worst_feature", "shifted_worst_psi",
                  "warn_fired", "prom_gauges", "report_cli",
                  "models_quality_block", "sampled_rows"):
        assert field in q, f"quality probe missing {field}"
    assert q["parity"] == "pass"
    # zero drift on in-distribution rows, loud drift on the shift
    assert q["in_dist_worst_psi"] < 0.05
    assert q["shifted_worst_psi"] > 0.2
    assert q["shifted_worst_feature"] == 2
    assert q["warn_fired"] is True
    assert any("worst_feature_psi" in g for g in q["prom_gauges"])
    assert q["report_cli"] == "pass"
    assert q["models_quality_block"] == "pass"
    # serving probe (round 14): concurrent single-row clients through
    # the micro-batching HTTP frontend — scripts/serve_bench.py, run
    # in-line by bench_smoke.sh
    with open("/tmp/lgbtpu_smoke/serve.json") as f:
        s = json.load(f)
    for field in ("requests", "requests_ok", "dispatches",
                  "amortization", "p50_ms", "p99_ms", "shed",
                  "coalesced_requests", "parity", "drain"):
        assert field in s, f"serve probe missing {field}"
    assert s["parity"] == "pass"
    assert not s["failures"]
    # every offered request was either answered or explicitly shed
    # (bounds derived from the run's own totals — SERVE_CLIENTS /
    # SERVE_REQUESTS overrides must not break the assertion)
    assert s["requests_ok"] + s["shed"] >= s["requests"]
    assert s["requests_ok"] >= s["clients"]
    # the tentpole claim: N concurrent single-row requests cost
    # strictly fewer than N dispatches
    assert s["dispatches"] < s["requests"], (
        f"{s['dispatches']} dispatches for {s['requests']} requests "
        "— the micro-batcher coalesced nothing")
    assert s["coalesced_requests"] > 0
    # generous tail bound: the smoke runs on CPU with cold jit
    assert s["p99_ms"] < 30000
    assert s["drain"] == "clean", "serving queues not drained at stop"
    # lane fleet probe (round 20): the same closed-loop load through
    # 1 then 2 simulated lanes over a per-row simulated device wall —
    # the scale-out tentpole gate is 2-lane rows/s >= 1.5x single
    ls = s["lane_scaling"]
    assert ls["parity"] == "pass" and ls["drain"] == "clean"
    assert ls["gate"] == "pass", (
        f"2-lane scaling {ls['scaling_x']}x below the 1.5x gate "
        f"({ls['single_lane_rows_per_s']} -> "
        f"{ls['multi_lane_rows_per_s']} rows/s)")
    assert ls["scaling_x"] >= 1.5
    # co-batching probe (round 20): mixed-model open-loop traffic
    # over one fused program — fused dispatches must be strictly
    # fewer than the per-model dispatches they replaced, at full
    # per-member parity
    mm = s["mixed_model"]
    assert mm["parity"] == "pass" and not mm["failures"]
    assert mm["fused_group"] == ["m0", "m1", "m2"]
    assert mm["cobatch_dispatches"] > 0
    assert mm["cobatch_dispatches"] < mm["cobatch_fused_models"], (
        f"{mm['cobatch_dispatches']} fused dispatches for "
        f"{mm['cobatch_fused_models']} model-dispatches — "
        "co-batching amortized nothing")
    assert mm["cobatch_amortized"] is True
    # trace-overhead probe (round 23): the same load with tracing off
    # vs spans+headers — the p50 delta is the whole per-request cost
    # of context propagation; the in-bench gate bounds it at 25%
    # (generous: CPU smoke jitter dwarfs the microseconds under test;
    # the design target documented in docs/OBSERVABILITY.md is <5%)
    to = s["trace_overhead"]
    assert to["parity"] == "pass"
    assert isinstance(to["overhead_pct"], (int, float))
    assert to["gate"] == "pass", (
        f"tracing p50 overhead {to['overhead_pct']}% "
        f"({to['p50_ms_tracing_off']} -> "
        f"{to['p50_ms_tracing_on']} ms)")
    # distributed-tracing probe (round 23): header round trip over
    # real HTTP, the merged timeline's request->dispatch flow arrow,
    # and the injected stall journaled with seam + trace id —
    # scripts/trace_probe.py, run in-line by bench_smoke.sh
    with open("/tmp/lgbtpu_smoke/trace.json") as f:
        tr = json.load(f)
    for field in ("header_echo", "flow_link", "flow_links",
                  "stall_journal", "journal_instants",
                  "status_overall"):
        assert field in tr, f"trace probe missing {field}"
    assert tr["header_echo"] == "pass"
    assert tr["flow_link"] == "pass" and tr["flow_links"] >= 1
    assert tr["stall_journal"] == "pass"
    assert tr["journal_instants"] >= 1
    assert tr["status_overall"] == "pass"


@pytest.mark.slow
def test_bench_refuses_chipless_run_unless_cpu_was_asked_for():
    """A run that finds no TPU must fail, not time XLA-CPU under TPU
    metric names — unless the caller set JAX_PLATFORMS=cpu explicitly
    (the plumbing run above).  JAX_PLATFORMS="" lets jax pick its
    default backend, which on a chipless machine is still the CPU."""
    env = dict(os.environ, JAX_PLATFORMS="")
    run = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=120)
    assert run.returncode != 0
    assert "found no TPU" in run.stderr
    assert not run.stdout.strip(), "a refused run must print no result"


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-v"]))
