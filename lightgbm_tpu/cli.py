"""Command-line application: train / predict / convert_model / refit
/ serve.

The analog of the reference CLI driver (reference: src/main.cpp,
src/application/application.cpp:30-268 — param parsing with config
file + k=v args, task dispatch, data loading, prediction output file)
plus the online-serving entry point the reference never had:
``task=serve`` publishes ``input_model`` into a model registry
(buckets warmed before traffic) and serves ``POST /predict/<model>``
from the shared telemetry listener (docs/SERVING.md).

Usage:  python -m lightgbm_tpu config=train.conf [key=value ...]
"""
from __future__ import annotations

import sys
from typing import Dict, List

import numpy as np

from .basic import Dataset
from .booster import Booster
from .config import Config
from .engine import train as _train
from .utils.log import Log


def parse_args(argv: List[str]) -> Dict[str, str]:
    """CLI `k=v` pairs + config file contents, CLI wins
    (reference application.cpp:48-81)."""
    cli: Dict[str, str] = {}
    for tok in argv:
        if "=" in tok:
            k, v = tok.split("=", 1)
            cli[k.strip()] = v.strip()
    params: Dict[str, str] = {}
    cfg_file = cli.get("config", cli.get("config_file"))
    if cfg_file:
        with open(cfg_file) as f:
            for line in f:
                line = line.split("#", 1)[0].strip()
                if "=" in line:
                    k, v = line.split("=", 1)
                    params[k.strip()] = v.strip()
    params.update(cli)
    return params


def run(argv: List[str]) -> int:
    params = parse_args(argv)
    config = Config.from_params(params)
    Log.set_level(config.verbose)
    task = config.task
    if task == "train":
        _task_train(params, config)
    elif task in ("predict", "prediction", "test"):
        _task_predict(params, config)
    elif task == "convert_model":
        _task_convert(params, config)
    elif task == "refit":
        _task_refit(params, config)
    elif task == "serve":
        _task_serve(params, config)
    else:
        Log.fatal(f"Unknown task {task}")
    from .telemetry import TELEMETRY
    if TELEMETRY.on and config.telemetry_out:
        # explicit export at task end (the atexit hook is only the
        # safety net): telemetry=spans telemetry_out=/tmp/run writes
        # /tmp/run.jsonl + /tmp/run.perfetto.json (ui.perfetto.dev);
        # multi-host runs write per-host .host<i> shards — merge with
        # `python -m lightgbm_tpu.telemetry merge`
        paths = TELEMETRY.export(config.telemetry_out)
        Log.info("telemetry written: " + ", ".join(paths))
    if TELEMETRY.on and config.telemetry_prom_out:
        # Prometheus textfile (node-exporter textfile-collector
        # pattern): serving latency histograms + counters/gauges in
        # scrape format (docs/OBSERVABILITY.md, Prometheus export)
        Log.info("prometheus metrics written: "
                 + TELEMETRY.write_prom(config.telemetry_prom_out))
    return 0


def _task_train(params, config: Config) -> None:
    if not config.data:
        Log.fatal("No training data: set data=<file>")
    if config.num_machines > 1:
        # socket rendezvous config (reference application.cpp:87-105):
        # machines= inline list wins, machine_list_file= is the file
        # form; forwarded to the call-compat network surface
        machines = config.machines
        if not machines and config.machine_list_file:
            import os
            if not os.path.exists(config.machine_list_file):
                Log.fatal("machine_list_file not found: "
                          f"{config.machine_list_file}")
            with open(config.machine_list_file) as f:
                machines = ",".join(ln.strip() for ln in f
                                    if ln.strip())
        if machines:
            from .capi import LGBM_NetworkInit
            from .reliability.faults import FAULTS
            from .reliability.retry import RetryPolicy, retry_call

            def _net_init():
                FAULTS.fault_point("distributed.init")
                return LGBM_NetworkInit(machines,
                                        config.local_listen_port,
                                        config.time_out,
                                        config.num_machines)
            # transient rendezvous failures (peers still starting,
            # port in TIME_WAIT) retry with growing backoff for the
            # reference's time_out budget (minutes, the reference's
            # socket-timeout semantic) — the TIME budget governs the
            # rendezvous patience, not the dispatch retry count
            policy = RetryPolicy.from_config(config)
            policy.budget_s = config.time_out * 60.0
            retry_call(_net_init, seam="distributed.init",
                       policy=policy)
    if config.sharded_shards > 1:
        # mesh-sharded construction (docs/Parallel-Learning-Guide.md,
        # "Sharded construction"): Dataset.construct routes through
        # lightgbm_tpu/sharded/ — distributed bin finding, per-shard
        # streaming ingest, per-device placement over the mesh row
        # axis, optional shard-cache v2 under sharded_cache_dir
        Log.info(f"sharded construction armed: "
                 f"{config.sharded_shards} participant shard(s)"
                 + (f", cache {config.sharded_cache_dir}"
                    if config.sharded_cache_dir else ""))
    # input_model (continued training) seeds scores from raw data —
    # retain it in that case (reference CLI keeps data in memory too)
    train_set = Dataset(config.data, params=params,
                        free_raw_data=not config.input_model)
    if config.is_save_binary_file:
        # reference DatasetLoader::SaveBinaryFile writes the cache at
        # LOAD time, not after training: constructing once here reuses
        # the core for the training run below AND persists the
        # (memmap-able v2) cache even if a long run is interrupted —
        # the next invocation short-circuits straight to load_binary
        train_set.save_binary(config.data + ".bin")
        Log.info(f"Saved binned dataset to {config.data}.bin")
    valid_sets = []
    valid_names = []
    for i, vf in enumerate(config.valid_data):
        valid_sets.append(Dataset(vf, reference=train_set, params=params))
        valid_names.append(f"valid_{i}" if len(config.valid_data) > 1
                           else "valid_1")
    booster = _train(params, train_set, config.num_iterations,
                     valid_sets=valid_sets, valid_names=valid_names,
                     init_model=config.input_model or None)
    booster.save_model(config.output_model)
    Log.info(f"Finished training; model saved to {config.output_model}")


def _task_predict(params, config: Config) -> None:
    if not config.input_model:
        Log.fatal("No model file: set input_model=<file>")
    # the parsed config rides along so CLI predict knobs
    # (predict_kernel, predict_bucket, predict_chunk_rows, ...) reach
    # the serving predictor
    booster = Booster(config=config, model_file=config.input_model)
    if config.predict_warm_buckets:
        # deploy-script warm-up without the Python API: pre-compile
        # the declared serving buckets (and log each bucket's warm
        # compile wall) before the first real prediction
        booster.warm_predictor(config.predict_warm_buckets, log=True)
    from .data_loader import load_file
    X, _, _ = load_file(config.data, config)
    pred = booster.predict(
        X,
        num_iteration=config.num_iteration_predict,
        raw_score=config.is_predict_raw_score,
        pred_leaf=config.is_predict_leaf_index,
        pred_contrib=config.is_predict_contrib,
        pred_early_stop=config.pred_early_stop,
        pred_early_stop_freq=config.pred_early_stop_freq,
        pred_early_stop_margin=config.pred_early_stop_margin)
    out = np.atleast_2d(np.asarray(pred))
    if out.shape[0] == 1 and X.shape[0] != 1:
        out = out.T
    with open(config.output_result, "w") as f:
        for row in (out if out.ndim > 1 else out[:, None]):
            f.write("\t".join(f"{v:g}" for v in np.atleast_1d(row)) + "\n")
    Log.info(f"Finished prediction; results saved to "
             f"{config.output_result}")


def _task_convert(params, config: Config) -> None:
    if not config.input_model:
        Log.fatal("No model file: set input_model=<file>")
    if config.convert_model_language not in ("", "cpp"):
        Log.fatal("Only cpp is supported for convert_model_language")
    booster = Booster(model_file=config.input_model)
    from .codegen import model_to_ifelse_cpp
    code = model_to_ifelse_cpp(booster)
    with open(config.convert_model, "w") as f:
        f.write(code)
    Log.info(f"Finished converting model to if-else code at "
             f"{config.convert_model}")


def _task_serve(params, config: Config) -> None:
    """Online serving (docs/SERVING.md): publish input_model into a
    registry (warming its buckets first — predict_warm_buckets, or
    the 1-row + serve_max_batch_rows defaults), then serve
    POST /predict/<model> with micro-batching and load shedding from
    the shared /metrics + /healthz listener until interrupted.

    With ``continuous_ingest_dir`` set, the continuous-training lane
    (docs/CONTINUOUS_TRAINING.md) runs BESIDE the frontend: new data
    slices dropped into the directory are append-constructed against
    the base dataset (``data=``), trained from the last good model
    (``continuous_mode=continue|refit``), eval-gated and hot-published
    into the SAME registry this frontend serves from — control it via
    GET/POST /continuous on the shared listener."""
    if not config.input_model:
        Log.fatal("No model file: set input_model=<file>")
    import os
    import signal
    import threading

    from .serving import ModelRegistry, ServingFrontend
    # graceful SIGTERM drain (docs/RELIABILITY.md): the orchestrator's
    # polite shutdown (kubectl delete, systemd stop) must not look
    # like a crash — on SIGTERM the process stops admission (routes
    # unmounted), drains every in-flight coalesced batch, lets the
    # continuous lane finish its phase (the ledger commit is the
    # phase boundary), and exits 0.  Only SIGKILL is a crash, and the
    # r12 checkpoint/ledger machinery owns that path.  Installed
    # BEFORE the first publish so a shutdown during warm-up is
    # graceful too.
    stop = threading.Event()

    def _on_sigterm(signum, frame):
        Log.info("SIGTERM: stopping admission and draining in-flight "
                 "work (serving queues + continuous lane)")
        stop.set()

    prev_term = signal.signal(signal.SIGTERM, _on_sigterm)
    name = os.path.splitext(
        os.path.basename(config.input_model))[0] or "model"
    registry = ModelRegistry(config)
    entry = registry.publish(name, config.input_model, log_warm=True)
    frontend = ServingFrontend(registry, config)
    srv = frontend.start()
    port = srv.server_address[1]
    Log.info(f"serving model {name!r} at "
             f"http://127.0.0.1:{port}/predict/{name} "
             '(POST JSON {"rows": [[...]]} or CSV rows, or binary '
             "application/x-ltpu-f32; GET /models /metrics /healthz)")
    if registry.pool is not None:
        Log.info(f"lane fleet: {registry.pool.n_lanes} dispatch "
                 f"lanes (serve_lanes={config.serve_lanes}); per-lane "
                 "state on GET /models under '_fleet'")
    if entry.monitor is not None:
        # model-quality drift monitors (docs/MODEL_MONITORING.md):
        # armed from the <input_model>.quality.json sidecar a
        # quality=on training run saved beside the model
        Log.info(f"quality monitors armed for {name!r}: sample "
                 f"stride {entry.monitor.stride}, drift report at "
                 f"http://127.0.0.1:{port}/quality/{name} "
                 f"(ltpu_quality_* gauges on /metrics)")
    lane = None
    if config.continuous_ingest_dir:
        if not config.data:
            Log.fatal("continuous_ingest_dir is set but data= is not: "
                      "the lane needs the base dataset whose bin "
                      "mappers ingested slices bind to")
        from .continuous import ContinuousLane
        lane = ContinuousLane(config, registry, name=name,
                              train_params=dict(params)).start()
        Log.info(f"continuous-training lane armed: watching "
                 f"{config.continuous_ingest_dir} "
                 f"(mode={config.continuous_mode}, poll "
                 f"{config.continuous_poll_s:g}s; GET/POST "
                 f"http://127.0.0.1:{port}/continuous)")
    try:
        stop.wait()                   # serve until SIGTERM or SIGINT
    except KeyboardInterrupt:
        Log.info("interrupt: draining serving queues")
    finally:
        if prev_term is not None:
            # None = the previous disposition was installed outside
            # Python (embedding host); signal.signal(None) would raise
            signal.signal(signal.SIGTERM, prev_term)
        if lane is not None:
            lane.stop()
        frontend.stop(drain=True)
        Log.info("serving drained cleanly; exiting 0")


def _task_refit(params, config: Config) -> None:
    if not config.input_model:
        Log.fatal("No model file: set input_model=<file>")
    # the parsed config rides along (like task=predict) so predict
    # knobs reach the pred_leaf pass and the telemetry/export knobs
    # configured on the command line govern the refit run too
    booster = Booster(config=config, model_file=config.input_model)
    from .data_loader import load_file
    X, label, _ = load_file(config.data, config)
    booster.refit(X, label, params)
    booster.save_model(config.output_model)
    Log.info(f"Finished refitting; model saved to {config.output_model}")


def main() -> int:
    return run(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
