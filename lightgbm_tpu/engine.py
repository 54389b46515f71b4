"""Training entry points: train() and cv().

The lgb.train / lgb.cv analogs (reference: python-package/lightgbm/
engine.py:18-230 train, :312 cv) driving the device GBDT loop with the
reference's callback/early-stopping protocol.
"""
from __future__ import annotations

import collections
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Union

import numpy as np

from .backend import on_tpu
from .booster import Booster
from .config import Config
from .dataset import Dataset
from .reliability import checkpoint as _ckpt
from .reliability.retry import is_oom
from .telemetry import TELEMETRY
from .utils.log import Log


def train(params: Dict[str, Any], train_set: Dataset,
          num_boost_round: int = 100,
          valid_sets: Optional[Sequence[Dataset]] = None,
          valid_names: Optional[Sequence[str]] = None,
          fobj: Optional[Callable] = None,
          feval: Optional[Callable] = None,
          init_model: Optional[Union[str, "Booster"]] = None,
          feature_name: Union[str, Sequence[str]] = "auto",
          categorical_feature: Union[str, Sequence] = "auto",
          early_stopping_rounds: Optional[int] = None,
          evals_result: Optional[dict] = None,
          verbose_eval: Union[bool, int] = True,
          learning_rates: Optional[Union[Sequence[float],
                                         Callable]] = None,
          keep_training_booster: bool = False,
          callbacks: Optional[Sequence[Callable]] = None,
          resume: Optional[Union[bool, str]] = None) -> Booster:
    """Train a gradient-boosted model (reference engine.py:18-229;
    parameter order follows the reference signature engine.py:18-24).

    ``feature_name``/``categorical_feature`` apply to a still-lazy
    train_set before construction (engine.py:122-123);
    ``learning_rates`` (list or callable of the iteration index) is
    sugar for a reset_parameter callback (engine.py:167-168);
    ``keep_training_booster=False`` (the reference default,
    engine.py:224-226) releases the training state after the final
    flush — the returned booster predicts and serves as ``init_model``
    for continued training, but update() on it errors.

    ``resume`` (docs/RELIABILITY.md): ``None`` defers to
    ``config.resume`` (default "auto" — scan for the newest valid
    checkpoint when ``checkpoint_freq`` is active); ``False``/"off"
    always starts cold; a string path resumes from exactly that
    checkpoint file.  A resumed run continues FULL training state
    (model, score cache, RNG streams, early-stopping bookkeeping) and
    produces byte-identical trees to an uninterrupted run."""
    t_entry = time.perf_counter()
    params = dict(params or {})
    if feature_name != "auto" and hasattr(train_set, "set_feature_name"):
        train_set.set_feature_name(feature_name)
    if categorical_feature != "auto" \
            and hasattr(train_set, "set_categorical_feature"):
        train_set.set_categorical_feature(categorical_feature)
    if learning_rates is not None:
        from .callback import reset_parameter
        callbacks = list(callbacks or []) + [
            reset_parameter(learning_rate=learning_rates)]
    if early_stopping_rounds is not None and not any(
            k in params for k in ("early_stopping_round",
                                  "early_stopping_rounds", "early_stopping")):
        params["early_stopping_round"] = early_stopping_rounds
    # params aliases override the argument (reference engine.py:85-91)
    from .config import PARAM_ALIASES
    has_num_iter = "num_iterations" in params or any(
        PARAM_ALIASES.get(str(k).lower()) == "num_iterations" for k in params)
    if not has_num_iter:
        params["num_iterations"] = num_boost_round
    config = Config.from_params(params)
    # the entry-point stage of set-up's second half, counted from the
    # call's entry (the Config above is what may turn telemetry on):
    # Booster construction, the chunk program's build and the wait for
    # the first trees are stages under it, and its own time is what
    # none of them took
    with TELEMETRY.stage("train", since=t_entry,
                         num_boost_round=config.num_iterations):
        return _train(config, params, train_set, valid_sets, valid_names,
                      fobj, feval, init_model, evals_result, verbose_eval,
                      callbacks, keep_training_booster, resume)


def _train(config, params, train_set, valid_sets, valid_names, fobj, feval,
           init_model, evals_result, verbose_eval, callbacks,
           keep_training_booster, resume):
    """``train`` from its resolved Config on."""
    num_boost_round = config.num_iterations
    # config.verbosity routes to the process-global Log level on the
    # python API too, not only in CLI runs (the reference's Config
    # verbosity is global the same way); Log.fatal ignores the level
    Log.set_level(config.verbose)

    resume_arg = config.resume if resume is None else resume
    if isinstance(resume_arg, bool):
        resume_arg = "auto" if resume_arg else "off"
    resume_arg = str(resume_arg or "off")
    if resume_arg.lower() not in ("off", "false", "0", "none", "auto") \
            and init_model is not None:
        # an explicit checkpoint path + init_model is a contradiction,
        # not a precedence question: the checkpoint carries the FULL
        # training state (model included), so whichever fingerprint
        # happens to match would silently discard the other input.
        # (resume="auto" composes fine — the fingerprint carries the
        # init_model identity, so auto only ever adopts checkpoints
        # from an identically-seeded run.)  Checked BEFORE any dataset
        # construction: the conflict must fail fast.
        raise ValueError(
            "engine.train: both init_model= and an explicit resume= "
            f"checkpoint path ({resume_arg!r}) are set — the "
            "checkpoint already contains the full training state, so "
            "one of them would be silently ignored. Pass resume='off' "
            "to continue from init_model, or drop init_model to "
            "resume from the checkpoint.")

    if hasattr(train_set, "construct"):
        core_train = train_set.construct(config)
    else:
        core_train = train_set
    aligned = []
    for vs in (valid_sets or []):
        if not hasattr(vs, "construct"):
            aligned.append(vs)
        elif vs is train_set:
            aligned.append(core_train)
        else:
            # bin-align lazy valid sets to the training mappers (the
            # reference package calls set_reference in train(); a
            # valid set binned with its own mappers would evaluate
            # trees whose thresholds live in train bin space)
            aligned.append(vs.construct_aligned(core_train, config)
                           if hasattr(vs, "construct_aligned")
                           else vs.construct(config))
    valid_sets = aligned
    train_set = core_train

    valid_sets = list(valid_sets or [])
    names = list(valid_names or [])
    while len(names) < len(valid_sets):
        names.append(f"valid_{len(names)}")
    # stage: the training state, less the upload / grower_init / binsT
    # stages inside it (objective, initial scores, RNG streams, the
    # validation sets and metrics)
    with TELEMETRY.stage("booster_init"):
        booster = Booster(config=config, train_set=train_set,
                          init_model=init_model,
                          custom_objective=fobj is not None)
        for vs, name in zip(valid_sets, names):
            if vs is train_set:
                booster.gbdt.add_train_metrics()
            else:
                booster.gbdt.add_valid(vs, name)
        if config.is_training_metric and not booster.gbdt.train_metrics:
            booster.gbdt.add_train_metrics()

    eval_freq = (verbose_eval if isinstance(verbose_eval, int)
                 and not isinstance(verbose_eval, bool)
                 else config.output_freq)
    show_eval = bool(verbose_eval)

    if evals_result is not None:
        evals_result.clear()

    # --- reliability wiring (docs/RELIABILITY.md) --------------------
    # Periodic model snapshots (reference gbdt.cpp:330-334 writes
    # <output_model>.snapshot_iter_N every snapshot_freq iterations)
    # are handled INLINE in the loop, not as a callback: the callback
    # form silently forced every snapshotting run to per-iteration
    # dispatch (chunkable checks `not callbacks`), and wrote through a
    # bare save_model a kill mid-write would tear.  Snapshots now go
    # through the atomic writer with rolling retention, and fused
    # chunks are CUT at snapshot/checkpoint boundaries instead of
    # being disabled.
    snap_on = config.snapshot_freq > 0 and bool(config.output_model)
    ckpt_on = config.checkpoint_freq > 0
    ckpt_prefix = config.checkpoint_path or \
        (config.output_model or "LightGBM_model.txt") + ".ckpt"
    if ckpt_on and not booster.gbdt.can_checkpoint():
        Log.warning(
            f"checkpoint_freq is set but boosting_type="
            f"{config.boosting_type} training state does not "
            "round-trip through checkpoints yet (gbdt/goss only); "
            "continuing without checkpoints")
        ckpt_on = False
    if ckpt_on and (fobj is not None or feval is not None):
        # a python callable has no stable identity to fingerprint: a
        # rerun with an EDITED fobj/feval would silently adopt the old
        # run's checkpoint and train a hybrid of two objectives
        Log.warning(
            "checkpoint_freq is set but custom fobj/feval callables "
            "cannot be fingerprinted for safe resume; continuing "
            "without checkpoints")
        ckpt_on = False
    # init_model identity rides the fingerprint: a continued-training
    # run (seeded scores + foreign trees) and a fresh run must never
    # adopt each other's checkpoints
    init_key = (init_model if isinstance(init_model, str)
                else "<booster>" if init_model is not None else "")
    fingerprint = (_ckpt.training_fingerprint(config, train_set,
                                              len(valid_sets), init_key)
                   if ckpt_on else None)

    def _save_checkpoint(it: int) -> bool:
        """Full-state checkpoint at iteration ``it``; True when the
        consumed no-split window says training is over."""
        t0 = time.perf_counter()
        span = TELEMETRY.start_span("checkpoint_save", iteration=it)
        state, stopped = booster.gbdt.capture_state()
        payload = {"iteration": it, "gbdt": state, "stopped": stopped,
                   "evals_result": evals_result or {}}
        path = _ckpt.save_rolling(ckpt_prefix, it, payload, fingerprint,
                                  keep=config.checkpoint_keep)
        TELEMETRY.end_span(span)
        TELEMETRY.add("checkpoint_saves", 1)
        TELEMETRY.add("checkpoint_save_ms",
                      (time.perf_counter() - t0) * 1e3)
        Log.debug(f"checkpoint saved: {path}")
        return stopped

    def _after_iterations(it: int, force: bool = False) -> bool:
        """Snapshot/checkpoint work due once iteration count ``it`` is
        reached (``force`` fires both regardless of the schedule —
        the catch-up after an unaligned stretch); True when training
        must stop."""
        if snap_on and (force or it % config.snapshot_freq == 0):
            booster.gbdt.flush_models()
            _ckpt.atomic_write_text(
                f"{config.output_model}.snapshot_iter_{it}",
                booster.model_to_string())
            _ckpt.prune_snapshots(config.output_model,
                                  config.snapshot_keep)
        if ckpt_on and (force or it % config.checkpoint_freq == 0):
            return _save_checkpoint(it)
        return False

    def _boundary(it: int) -> Optional[int]:
        """Iterations until the next periodic snapshot/checkpoint —
        fused chunks are cut here so their boundaries LAND on the
        snapshot/checkpoint schedule."""
        nxt = None
        for freq, on in ((config.snapshot_freq, snap_on),
                         (config.checkpoint_freq, ckpt_on)):
            if on:
                b = freq - (it % freq)
                nxt = b if nxt is None else min(nxt, b)
        return nxt

    # headless stretches (no per-iteration callbacks/eval/early-stop
    # consumers) run as multi-iteration fused chunks, one dispatch per
    # chunk instead of one per iteration (the per-dispatch host cost
    # on a directly attached chip: not measured on the chip)
    # (show_eval is irrelevant: with no valid sets and no train metrics
    # there is nothing to print between iterations)
    chunkable = (fobj is None and feval is None and not callbacks
                 and evals_result is None
                 and config.early_stopping_round <= 0
                 and not booster.gbdt.valid_sets
                 and not booster.gbdt.train_metrics
                 and booster.gbdt.can_chunk())
    # dispatch_chunk: iterations fused per device program.  An integer
    # pins it; "auto" re-fits the per-iteration chunk slope from two
    # probe chunks and picks the amortization point against the
    # measured dispatch cost (GBDT.tune_dispatch_chunk).  The probe
    # pass only runs where it can pay off — on a TPU (on the CPU
    # simulation the dispatch is sub-ms and auto degenerates to the
    # default 10) and in a run long enough to absorb the probe
    # iterations.  chip_smoke.py leg A is what executes this branch.
    chunk_cfg = str(config.dispatch_chunk).lower()
    chunk_size = 10 if chunk_cfg in ("auto", "") \
        else max(1, int(float(chunk_cfg)))

    stopped_early = False
    iteration = 0

    # --- resume (docs/RELIABILITY.md): continue from the newest valid
    # checkpoint (auto) or an explicit checkpoint file ---------------
    loaded = None
    if resume_arg.lower() not in ("off", "false", "0", "none", ""):
        if resume_arg.lower() == "auto":
            if ckpt_on:
                loaded = _ckpt.find_resume(ckpt_prefix, fingerprint,
                                           max_iteration=num_boost_round)
        else:
            # explicit checkpoint path: invalid files error LOUDLY —
            # the user named this exact file, silence would train a
            # different model than they asked for
            fp = fingerprint if fingerprint is not None else \
                _ckpt.training_fingerprint(config, train_set,
                                           len(valid_sets), init_key)
            _fp, payload = _ckpt.read_checkpoint(resume_arg, fp)
            loaded = (int(payload["iteration"]), payload, resume_arg)
    if loaded is not None:
        it0, payload, ck_path = loaded
        span = TELEMETRY.start_span("checkpoint_resume", iteration=it0)
        try:
            booster.gbdt.restore_state(payload["gbdt"])
        except _ckpt.CheckpointError as e:
            TELEMETRY.end_span(span)
            if resume_arg.lower() != "auto":
                raise
            Log.warning(f"cannot resume from {ck_path}: {e}; "
                        "starting cold")
        else:
            TELEMETRY.end_span(span)
            iteration = it0
            if evals_result is not None:
                evals_result.update(payload.get("evals_result") or {})
            Log.info(f"Resumed training from checkpoint {ck_path} at "
                     f"iteration {it0}")
            if payload.get("stopped"):
                # the checkpointed run had already detected end of
                # training (no-split stop window): training further
                # would grow no-gain trees past the detected end
                Log.warning(
                    "checkpoint marks the end of training (no leaves "
                    "met the split requirements); not training "
                    "further")
                num_boost_round = min(num_boost_round, it0)

    oom_warned = False

    def _train_chunk_guarded(c: int):
        """Dispatch one fused chunk with the OOM degradation ladder:
        RESOURCE_EXHAUSTED halves the chunk length (down to 1) and
        re-dispatches — trained trees are byte-identical at every
        chunk length (test_packed_carry), so the downshift degrades
        only dispatch amortization, never the model.  Returns
        (stop, iterations_actually_dispatched)."""
        nonlocal chunk_size, oom_warned
        while True:
            it0 = booster.gbdt.iter_
            try:
                return booster.gbdt.train_chunk(c), c
            except Exception as e:
                if not (config.oom_downshift and is_oom(e)) or c <= 1:
                    raise
                if booster.gbdt.iter_ != it0:
                    # the OOM surfaced AFTER the chunk committed state
                    # (async backend, late materialization at a fence
                    # or the stop-window pull): scores/iter_ already
                    # absorbed the poisoned chunk, so re-dispatching
                    # would train on garbage — fail cleanly instead;
                    # checkpoint resume is the recovery path for this
                    raise
                c = max(1, c // 2)
                chunk_size = max(1, min(chunk_size, c))
                TELEMETRY.add("oom_downshifts", 1)
                TELEMETRY.journal.emit("oom_downshift",
                                       seam="gbdt.train_chunk",
                                       new_chunk=chunk_size)
                TELEMETRY.flight.dump("oom_downshift",
                                      seam="gbdt.train_chunk",
                                      new_chunk=chunk_size)
                if not oom_warned:
                    oom_warned = True
                    Log.warning(
                        "RESOURCE_EXHAUSTED during fused-chunk "
                        f"dispatch ({e}); downshifting dispatch_chunk "
                        f"to {chunk_size} and continuing")

    # tuner gate counts REMAINING iterations: a resumed run near its
    # target must not spend (or overshoot with) probe chunks
    if chunkable and chunk_cfg in ("auto", "") \
            and num_boost_round - iteration >= 60:
        if on_tpu():
            chunk_size, info = booster.gbdt.tune_dispatch_chunk()
            iteration += info["iters_used"]
            if info.get("stopped"):
                num_boost_round = iteration
            else:
                TELEMETRY.gauge("dispatch_chunk_auto", chunk_size)
                Log.info(
                    f"dispatch_chunk=auto: fitted slope "
                    f"{info['slope_s_per_iter'] * 1e3:.4f} ms/iter·chunk,"
                    f" dispatch {info['dispatch_s'] * 1e3:.1f} ms -> "
                    f"chunk {chunk_size}")
            if iteration > 0 and (snap_on or ckpt_on):
                # the probe chunks trained real iterations without
                # boundary alignment: write a catch-up snapshot/
                # checkpoint so a preemption right after the probe
                # window has something to resume from
                _after_iterations(iteration, force=True)
    while iteration < num_boost_round:
        remaining = num_boost_round - iteration
        if chunkable:
            # chunk length: the configured size, capped by what's left
            # and CUT at snapshot/checkpoint boundaries (a cut chunk
            # repeats the same length every period, so it costs one
            # extra compile total, not one per snapshot).  Tails of
            # 10+ run as one odd-length chunk — a single extra compile
            # instead of per-iteration dispatches, each paying the
            # dispatch cost the chunking exists to amortize.
            c = min(chunk_size, remaining)
            bound = _boundary(iteration)
            cut = bound is not None and bound <= c
            if cut:
                c = bound
            if c == chunk_size or cut or c >= 10:
                stop, done = _train_chunk_guarded(c)
                iteration += done
                if stop or _after_iterations(iteration):
                    break
                continue
        if callbacks:
            for cb in callbacks:
                if getattr(cb, "before_iteration", False):
                    cb(_CallbackEnv(booster, params, iteration,
                                    num_boost_round, None))
        if fobj is not None:
            grad, hess = fobj(booster._current_train_scores(), train_set)
            stop = booster.gbdt.train_one_iter(grad, hess)
        else:
            stop = booster.gbdt.train_one_iter()
        if stop:
            break

        results = booster.gbdt.eval_metrics()
        if feval is not None:
            fr = feval(booster._current_train_scores(), train_set)
            if fr is not None:
                if not isinstance(fr, list):
                    fr = [fr]
                for name, val, bigger in fr:
                    results.append(("feval", name, val, bigger))
        if evals_result is not None:
            for dname, mname, value, _ in results:
                evals_result.setdefault(dname, collections.OrderedDict()) \
                    .setdefault(mname, []).append(value)
        if show_eval and results and eval_freq > 0 \
                and (iteration + 1) % eval_freq == 0:
            msg = "\t".join(f"{d}'s {m}: {v:g}"
                            for d, m, v, _ in results)
            Log.info(f"[{iteration + 1}]\t{msg}")
        if callbacks:
            env = _CallbackEnv(booster, params, iteration, num_boost_round,
                               [(d, m, v, b) for d, m, v, b in results])
            for cb in callbacks:
                if not getattr(cb, "before_iteration", False):
                    try:
                        cb(env)
                    except EarlyStopException as e:
                        booster.best_iteration = e.best_iteration + 1
                        stopped_early = True
            if stopped_early:
                break
        if booster.gbdt.check_early_stopping(results, iteration):
            booster.best_iteration = booster.gbdt.best_iteration
            Log.info(f"Early stopping at iteration {iteration + 1}, best "
                     f"iteration is {booster.best_iteration}")
            stopped_early = True
            break
        iteration += 1
        if _after_iterations(iteration):
            break
    if not stopped_early:
        booster.best_iteration = -1
    if booster.gbdt is not None:
        # where a headless lgb.train first blocks on the device: the
        # pull of the trees still queued (all of a job's first chunk,
        # when the call trains one).  A wait that happens anyway.
        with TELEMETRY.stage("first_chunk_wait"):
            booster.gbdt.flush_models(final=True)
    if str(config.quality).lower() == "on" \
            and booster.gbdt is not None and booster.models:
        # model-quality reference profile (docs/MODEL_MONITORING.md):
        # captured while the training state is still resident — the
        # feature histograms read the already-built bin matrix, the
        # score histogram reads the boosting score cache, so capture
        # costs one bincount pass + a pred_leaf over a strided sample.
        # save_model persists it as <model>.quality.json; serving
        # monitors bin live traffic against it.
        from .quality import build_profile
        try:
            booster.quality_profile = build_profile(booster, train_set,
                                                    config)
        except Exception as e:  # capture must never fail the training
            Log.warning(
                f"quality profile capture failed "
                f"({type(e).__name__}: {e}); model trains/saves "
                "without a profile")
    if not keep_training_booster:
        # reference engine.py:224-226: the default return is a
        # predictor — training state (binned device matrix, padded
        # score arrays) is released; prediction and use as init_model
        # keep working
        booster.free_dataset()
    if config.predict_warm_buckets and booster.num_trees() > 0:
        # serving warm-up: pre-compile the bucketed device predictor
        # for the declared batch shapes, so the first request after
        # deploy pays a cache hit instead of a compile
        booster.warm_predictor(config.predict_warm_buckets)
    return booster


class EarlyStopException(Exception):
    def __init__(self, best_iteration: int, best_score=None):
        self.best_iteration = best_iteration
        self.best_score = best_score


_CallbackEnv = collections.namedtuple(
    "LightGBMCallbackEnv",
    ["model", "params", "iteration", "end_iteration", "evaluation_result_list"])


class CVBooster:
    """Ensemble of per-fold boosters (reference engine.py:230-260).
    Attribute access fans out to every fold's booster and returns the
    list of results."""

    def __init__(self, boosters=None):
        self.boosters = list(boosters or [])
        self.best_iteration = -1

    def _append(self, booster):
        self.boosters.append(booster)

    def __getattr__(self, name):
        def handler(*args, **kwargs):
            return [getattr(b, name)(*args, **kwargs)
                    for b in self.boosters]
        return handler


def cv(params: Dict[str, Any], train_set: Dataset, num_boost_round: int = 100,
       folds=None, nfold: int = 5, stratified: bool = True, shuffle: bool = True,
       metrics=None, fobj=None, feval=None, init_model=None,
       early_stopping_rounds=None, seed: int = 0,
       callbacks=None, verbose_eval=None,
       return_cvbooster: bool = False) -> Dict[str, List[float]]:
    """K-fold cross-validation (reference engine.py:312-425)."""
    params = dict(params or {})
    if metrics is not None:
        params["metric"] = metrics
    config = Config.from_params(params)
    # keep the pre-construct raw data in hand: with the reference's
    # free_raw_data=True default the constructed core drops it, but cv
    # re-bins each fold from raw (the reference's cv instead subsets
    # the constructed dataset; per-fold re-binning is this framework's
    # equivalent, and fold mappers are refit per fold like `lgb.cv`
    # semantics require)
    lazy_data = getattr(train_set, "data", None)
    if hasattr(train_set, "construct"):
        train_set = train_set.construct(config)
    label = train_set.metadata.label
    n = train_set.num_data
    rng = np.random.RandomState(seed)

    if folds is None:
        idx = np.arange(n)
        if stratified and config.objective in ("binary", "multiclass",
                                               "multiclassova"):
            folds = _stratified_folds(label, nfold, rng, shuffle)
        else:
            if shuffle:
                rng.shuffle(idx)
            folds = [(np.setdiff1d(idx, idx[i::nfold], assume_unique=False),
                      idx[i::nfold]) for i in range(nfold)]

    raw = train_set._raw_data
    if raw is None and lazy_data is not None \
            and not isinstance(lazy_data, str):
        # free_raw_data=True (the default) dropped the converted matrix
        # at construct; re-convert the caller's in-memory data once for
        # the per-fold re-binning (costs one extra materialization —
        # pass free_raw_data=False to avoid it)
        from .basic import _is_sparse, _to_matrix
        raw = (lazy_data.tocsr() if _is_sparse(lazy_data)
               else _to_matrix(lazy_data, None))
    if raw is None:
        Log.fatal("cv requires the Dataset's raw data: pass an "
                  "in-memory matrix, or a non-streaming file dataset "
                  "with free_raw_data=False (two_round streaming never "
                  "materializes the matrix)")

    results: Dict[str, List[float]] = collections.defaultdict(list)
    boosters = []
    fold_evals = []
    for train_idx, test_idx in folds:
        dtrain = Dataset.from_matrix(
            raw[train_idx], label=label[train_idx],
            weight=None if train_set.metadata.weight is None
            else train_set.metadata.weight[train_idx],
            config=config,
            categorical_features=train_set._categorical_features)
        dtest = Dataset.from_matrix(
            raw[test_idx], label=label[test_idx],
            weight=None if train_set.metadata.weight is None
            else train_set.metadata.weight[test_idx],
            config=config, reference=dtrain)
        er: dict = {}
        bst = train(params, dtrain, num_boost_round, valid_sets=[dtest],
                    valid_names=["valid"], fobj=fobj, feval=feval,
                    early_stopping_rounds=early_stopping_rounds,
                    evals_result=er, verbose_eval=False,
                    # the reference's cv never frees fold boosters —
                    # a returned CVBooster stays trainable/evaluable
                    keep_training_booster=True)
        boosters.append(bst)
        fold_evals.append(er.get("valid", {}))

    if fold_evals and fold_evals[0]:
        num_iters = min(len(next(iter(fe.values()))) for fe in fold_evals)
        for mname in fold_evals[0]:
            for i in range(num_iters):
                vals = [fe[mname][i] for fe in fold_evals]
                results[f"{mname}-mean"].append(float(np.mean(vals)))
                results[f"{mname}-stdv"].append(float(np.std(vals)))
    out = dict(results)
    if return_cvbooster:
        cvb = CVBooster(boosters)
        cvb.best_iteration = max((b.best_iteration for b in boosters),
                                 default=-1)
        out["cvbooster"] = cvb
    return out


def _stratified_folds(label, nfold, rng, shuffle):
    classes = np.unique(label)
    fold_test = [[] for _ in range(nfold)]
    for c in classes:
        idx = np.nonzero(label == c)[0]
        if shuffle:
            rng.shuffle(idx)
        for i in range(nfold):
            fold_test[i].append(idx[i::nfold])
    folds = []
    all_idx = np.arange(len(label))
    for i in range(nfold):
        test = np.concatenate(fold_test[i])
        train_idx = np.setdiff1d(all_idx, test)
        folds.append((train_idx, test))
    return folds
