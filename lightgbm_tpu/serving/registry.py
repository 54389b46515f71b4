"""Model registry: named, versioned Boosters with atomic hot swap.

Deploys must never serve a cold compile: ``publish`` warms the new
version's serving-predictor buckets (``Booster.warm_predictor`` —
with ``compile_cache_dir`` wired this is a disk hit in repeat
processes, visible as ``compile_cache_hits``) BEFORE the cutover, so
the new version's first request dispatches an already-compiled
bucket.  The cutover itself is one pointer flip under the registry
lock; entries are immutable (booster + version + batcher fixed at
publish), so a request that grabbed an entry can never observe a
half-swapped ensemble.  The old version's micro-batcher then drains
its in-flight queue and closes — a submit that raced the swap gets
:class:`~lightgbm_tpu.serving.batcher.BatcherClosed` and the
registry transparently retries against the new current entry, so
hot swap produces zero failed and zero mixed-version responses
(pinned by ``tests/test_serving.py``).

Rollback is the same pointer flip back to the previous version
(kept resident: its booster — and the process-wide compiled
programs underneath — stay warm), with a fresh batcher replacing
the drained one.
"""
from __future__ import annotations

import threading
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..telemetry import TELEMETRY
from ..utils.log import Log
from .batcher import BatcherClosed, MicroBatcher
from .cobatch import CoBatchGroup, cobatch_key
from .lanes import LanePool, resolve_lanes


class FeatureWidthMismatch(ValueError):
    """Request rows don't match the served model's feature count.
    Raised per attempt inside :meth:`ModelRegistry.predict` (so a
    width check can never race a hot swap to a different-width
    model); the HTTP frontend maps it to 400."""

    def __init__(self, expected: int, got: int):
        super().__init__(f"expected {expected} features per row, "
                         f"got {got}")
        self.expected = expected
        self.got = got


class ModelEntry:
    """One immutable (name, version) serving unit: the Booster, its
    predict closure, the micro-batcher that owns its in-flight queue,
    and the publish-time audit metadata (``meta``: who published it,
    when, and at what eval metric — what a rollback decision reads)."""

    __slots__ = ("name", "version", "booster", "batcher", "_predict_fn",
                 "meta", "monitor", "cobatch", "cobatch_k")

    def __init__(self, name: str, version: int, booster, predict_fn,
                 batcher: MicroBatcher, meta=None, monitor=None,
                 cobatch_k=None):
        self.name = name
        self.version = int(version)
        self.booster = booster
        self._predict_fn = predict_fn
        self.batcher = batcher
        self.meta: dict = dict(meta or {})
        # per-version serving quality monitor (lightgbm_tpu/quality/),
        # or None when quality=off / no profile — the off-mode cost is
        # this one attribute staying None
        self.monitor = monitor
        # co-batching (lightgbm_tpu/serving/cobatch.py): the fusion
        # key this entry is eligible under (None = never fuses), and
        # the live group pointer the registry flips when membership
        # changes — requests route to the group's fused batcher while
        # set, to this entry's solo batcher otherwise
        self.cobatch_k = cobatch_k
        self.cobatch = None

    def predict(self, rows: np.ndarray) -> np.ndarray:
        group = self.cobatch
        if group is not None:
            return group.submit(self.name, rows)
        return self.batcher.submit(rows)


class ModelRegistry:
    """Process-local registry of served models (one per frontend)."""

    def __init__(self, config=None):
        self.config = config
        self._lock = threading.Lock()
        # drift→refit hook (quality monitors read it at FIRE time,
        # late-bound): ContinuousLane.start() installs its
        # report_serving_drift here so serving-side drift past
        # quality_drift_refit_threshold lands in the lane's
        # ledger-committed drift tally (docs/MODEL_MONITORING.md)
        self.on_quality_drift = None
        self._current: Dict[str, ModelEntry] = {}
        self._versions: Dict[str, List[ModelEntry]] = {}
        # serving history per name: what _current pointed at before
        # each swap, in order — rollback restores from HERE, not from
        # publish order (after rollback-then-republish, the previous
        # SERVING version is not the previously PUBLISHED one)
        self._history: Dict[str, List[ModelEntry]] = {}
        # lane fleet (lightgbm_tpu/serving/lanes.py): built lazily at
        # first publish from serve_lanes; None when the config
        # resolves to a single lane (today's inline dispatch)
        self._pool: Optional[LanePool] = None
        self._pool_init = False
        # co-batch groups (serving/cobatch.py) by fusion key; control
        # -plane swaps (publish/rollback) serialize on _swap_lock so
        # group membership never races a concurrent publish
        self._groups: Dict[tuple, CoBatchGroup] = {}
        self._swap_lock = threading.Lock()

    # -- lane fleet ----------------------------------------------------
    def _ensure_pool(self) -> Optional[LanePool]:
        """Build the lane pool on first use (``serve_lanes=auto|N``).
        None when the config resolves to one lane — requests then run
        on each batcher's own dispatcher thread exactly as before the
        fleet existed."""
        with self._lock:
            if not self._pool_init:
                self._pool_init = True
                n, devices = resolve_lanes(self.config)
                if n >= 2:
                    self._pool = LanePool(devices, name="serve")
                    Log.info(
                        f"serving lane pool: {n} lanes"
                        + (" (simulated on one device)"
                           if all(d is None for d in devices)
                           else f" on {len(set(map(str, devices)))} "
                                "device(s)"))
            return self._pool

    @property
    def pool(self) -> Optional[LanePool]:
        return self._pool

    # -- publish / swap ------------------------------------------------
    @staticmethod
    def _routes_to_device(predict_kwargs: dict) -> bool:
        """Whether this entry's predict calls will reach the bucketed
        device predictor (what ``warm_predictor`` compiles).  Pinned
        routing wins; auto routing follows the backend."""
        device = predict_kwargs.get("device")
        if device is not None:
            return bool(device)
        from ..backend import on_tpu
        return on_tpu()

    def _default_warm(self, predict_kwargs: dict) -> Tuple[int, ...]:
        cfg = self.config
        declared = tuple(getattr(cfg, "predict_warm_buckets", ()) or ())
        if declared:
            # explicitly declared shapes always warm — the operator
            # said so (e.g. ahead of forcing device routing later)
            return declared
        if not self._routes_to_device(predict_kwargs):
            # auto routing on a host backend takes the float64 tree
            # walk: compiling the device bucket ladder would burn
            # publish time on programs no request ever dispatches
            Log.debug("serving registry: implicit warm skipped — "
                      "predict routes to the host walk on this "
                      "backend")
            return ()
        # no declared shapes: warm the WHOLE power-of-two ladder from
        # the single-row bucket up to the coalesced-dispatch cap — a
        # mid-size coalesced batch lands on an intermediate bucket,
        # and warming only the endpoints would leave it a cold
        # compile mid-traffic (with compile_cache_dir wired, repeat
        # deploys disk-hit every rung anyway)
        lo = max(1, int(getattr(cfg, "predict_min_bucket_rows", 16)))
        hi = max(lo, int(getattr(cfg, "serve_max_batch_rows", 1024)))
        ladder = []
        b = lo
        while b < hi:
            ladder.append(b)
            b <<= 1
        ladder.append(hi)
        return tuple(ladder)

    def publish(self, name: str, model, version: Optional[int] = None,
                warm: Optional[Tuple[int, ...]] = None,
                predict_kwargs: Optional[dict] = None,
                log_warm: bool = False,
                published_unix: Optional[float] = None,
                eval_metric: Optional[float] = None,
                source: str = "manual") -> ModelEntry:
        """Register ``model`` (a Booster or a model-file path) as the
        new current version of ``name``.  Buckets are warmed BEFORE
        the pointer flip; the replaced version drains its in-flight
        work and releases its dispatcher.

        Audit metadata (surfaced per version by ``GET /models`` so a
        rollback decision can be traced): ``published_unix`` is the
        publish wall clock PASSED IN BY THE CALLER (the registry never
        stamps it itself — the continuous lane records the clock its
        ledger committed, so a crash-replayed publish carries the same
        timestamp), ``eval_metric`` the gate metric the candidate
        scored at publish, and ``source`` who published it
        (``manual`` | ``continuous``)."""
        with self._swap_lock:
            return self._publish_locked(
                name, model, version=version, warm=warm,
                predict_kwargs=predict_kwargs, log_warm=log_warm,
                published_unix=published_unix,
                eval_metric=eval_metric, source=source)

    def _publish_locked(self, name, model, version=None, warm=None,
                        predict_kwargs=None, log_warm=False,
                        published_unix=None, eval_metric=None,
                        source="manual") -> ModelEntry:
        from ..booster import Booster
        if source not in ("manual", "continuous"):
            raise ValueError(
                f"publish source must be manual/continuous, got "
                f"{source!r}")
        cfg = self.config
        if isinstance(model, str):
            booster = Booster(config=cfg, model_file=model)
        else:
            booster = model
        meta = {"source": source}
        if published_unix is not None:
            meta["published_unix"] = round(float(published_unix), 6)
        if eval_metric is not None:
            meta["eval_metric"] = float(eval_metric)
        kw = dict(predict_kwargs or {})
        pool = self._ensure_pool()

        def predict_fn(rows, _b=booster, _kw=kw):
            return _b.predict(rows, **_kw)

        warm = self._default_warm(kw) if warm is None else tuple(warm)
        if warm:
            # warm-before-cutover: compile (or disk-hit) every
            # declared bucket while the OLD version still serves —
            # on EVERY lane's device, so no lane takes a cold compile
            # after the pointer flip
            booster.warm_predictor(
                warm, log=log_warm,
                devices=pool.warm_devices if pool is not None
                else None)
        # serving quality monitor (lightgbm_tpu/quality/): armed when
        # the knobs allow it AND a fingerprint-matching profile rides
        # the model (sidecar file for a path publish, the in-memory
        # engine.train attachment for a Booster publish); observes
        # every coalesced dispatch read-only through the batcher hook
        from ..quality import maybe_monitor
        monitor = maybe_monitor(model, booster, cfg, name,
                                registry=self)
        with self._lock:
            versions = self._versions.setdefault(name, [])
            if version is None:
                version = max((e.version for e in versions),
                              default=0) + 1
            version = int(version)
            if any(e.version == version for e in versions):
                raise ValueError(
                    f"model {name!r} already has a version {version}")
            entry = ModelEntry(
                name, version, booster, predict_fn,
                MicroBatcher(predict_fn, cfg,
                             name=f"{name}@v{version}",
                             observer=monitor.observe
                             if monitor is not None else None,
                             pool=pool),
                meta=meta, monitor=monitor,
                cobatch_k=cobatch_key(booster, kw, cfg,
                                      self._routes_to_device(kw)))
            versions.append(entry)
            old = self._current.get(name)
            if old is not None:
                self._history.setdefault(name, []).append(old)
            self._current[name] = entry      # THE atomic cutover
        tm = TELEMETRY
        if tm.on:
            tm.add("serve_model_swaps" if old is not None
                   else "serve_model_publishes", 1)
            tm.gauge(f"serve_version.{name}", version)
        tm.journal.emit(
            "publish", seam="serving.request", model=name,
            version=version,
            **({"replaced": old.version} if old is not None else {}))
        if old is not None:
            # new version already serves; finish the old one's queue
            old.batcher.close(drain=True)
        self._refresh_cobatch()
        Log.info(f"serving registry: {name!r} -> v{version}"
                 + (f" (replaced v{old.version})" if old else "")
                 + (f", warmed buckets {list(warm)}" if warm else ""))
        return entry

    def _refresh_cobatch(self) -> None:
        """Recompute fused groups from the current pointers (runs
        under ``_swap_lock`` after every publish/rollback flip).  Each
        fusion key with >= 2 eligible current entries gets one
        :class:`CoBatchGroup`; a new group is built and warmed OFF the
        registry lock, installed by pointer flip on every member
        entry, and only then is the replaced group drained — the same
        warm-before-cutover / drain-after discipline as a version
        swap, so membership changes lose zero requests."""
        with self._lock:
            current = dict(self._current)
        desired: Dict[tuple, list] = {}
        for entry in current.values():
            if entry.cobatch_k is not None:
                desired.setdefault(entry.cobatch_k, []).append(entry)
        desired = {k: es for k, es in desired.items() if len(es) >= 2}
        retired = []
        for key, entries in desired.items():
            old = self._groups.get(key)
            versions = {e.name: e.version for e in entries}
            if old is not None and old.versions == versions:
                continue                 # membership unchanged
            group = CoBatchGroup(entries, self.config,
                                 pool=self._pool)
            devs = (self._pool.warm_devices
                    if self._pool is not None else (None,))
            group.warm(self._default_warm({}) or (1,), devices=devs)
            with self._lock:
                self._groups[key] = group
                for e in entries:
                    e.cobatch = group
            if old is not None:
                retired.append(old)
            Log.info("serving registry: co-batch group "
                     + "+".join(group.names) + " live "
                     + f"({len(group.names)} models, one fused "
                     "program)")
        for key in [k for k in self._groups if k not in desired]:
            retired.append(self._groups.pop(key))
        with self._lock:
            live = set(map(id, self._groups.values()))
            for entry in current.values():
                g = entry.cobatch
                if g is not None and (id(g) not in live
                                      or entry.name not in g.names):
                    entry.cobatch = None
        for g in retired:
            g.close(drain=True)

    def rollback(self, name: str) -> ModelEntry:
        """Pointer-flip ``name`` back to the version that was SERVING
        before the current one took over (the serving history, not
        publish order — after a rollback-then-republish, the previous
        publish may be the very version ops already rolled back as
        bad).  The restored version's compiled programs are still
        resident, so rollback serves warm immediately."""
        with self._swap_lock:
            return self._rollback_locked(name)

    def _rollback_locked(self, name: str) -> ModelEntry:
        with self._lock:
            if name not in self._current:
                raise KeyError(f"no model named {name!r}")
            cur = self._current[name]
            hist = self._history.get(name) or []
            if not hist:
                raise ValueError(
                    f"model {name!r} has no earlier serving version "
                    f"to roll back to (current v{cur.version})")
            prev = hist.pop()
            if prev.batcher.closed:
                prev.batcher = MicroBatcher(
                    prev._predict_fn, self.config,
                    name=f"{name}@v{prev.version}",
                    observer=prev.monitor.observe
                    if prev.monitor is not None else None,
                    pool=self._pool)
            self._current[name] = prev
        tm = TELEMETRY
        if tm.on:
            tm.add("serve_rollbacks", 1)
            tm.gauge(f"serve_version.{name}", prev.version)
        tm.journal.emit(
            "rollback", seam="serving.request", model=name,
            from_version=cur.version, to_version=prev.version)
        cur.batcher.close(drain=True)
        self._refresh_cobatch()
        Log.warning(f"serving registry: rolled {name!r} back "
                    f"v{cur.version} -> v{prev.version}")
        return prev

    # -- lookup / serve ------------------------------------------------
    def get(self, name: str) -> ModelEntry:
        with self._lock:
            entry = self._current.get(name)
        if entry is None:
            raise KeyError(f"no model named {name!r}")
        return entry

    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._current)

    def predict(self, name: str,
                rows: np.ndarray) -> Tuple[ModelEntry, np.ndarray]:
        """Serve one request against the current version of ``name``.
        A submit that lands on a version mid-drain (hot-swap race)
        retries against the new current pointer — the caller never
        sees the swap.  Feature width is validated against the SAME
        entry the request is submitted to (per attempt, so a swap to
        a different-width model between check and submit is
        impossible); a mismatch raises
        :class:`FeatureWidthMismatch`, which one bad client gets as
        a 400 instead of failing every batchmate's concatenate."""
        rows = np.asarray(rows, dtype=np.float64)
        if rows.ndim == 1:
            rows = rows[None, :]
        from ..reliability.watchdog import StallError
        for _ in range(64):
            entry = self.get(name)
            nf = entry.booster.num_feature()
            if rows.shape[1] != nf:
                raise FeatureWidthMismatch(nf, rows.shape[1])
            try:
                # entry.predict routes to the fused co-batch group
                # when one is live, the solo batcher otherwise; a
                # group drained by a membership change raises
                # BatcherClosed like any swap race and retries against
                # the refreshed pointers
                return entry, entry.predict(rows)
            except BatcherClosed:
                continue
            except StallError as e:
                # stall classification (docs/RELIABILITY.md): the
                # version's dispatch blew its watchdog_serve_s
                # deadline.  NOT retried here — the same wedged
                # program would stall again and multiply the damage;
                # the error names the model so ops can correlate the
                # flight dump, and the frontend answers 503
                TELEMETRY.flight.note(
                    "stall", f"serve:{name}", version=entry.version)
                raise StallError(
                    f"serving {name!r} v{entry.version}", e.seam,
                    e.deadline_s, e.elapsed_s) from e
        raise RuntimeError(
            f"model {name!r}: current version kept closing underneath "
            "the request (registry shutting down?)")

    def describe(self) -> Dict[str, dict]:
        """The ``/models`` endpoint body.  ``versions`` carries one
        record per published version with its audit metadata
        (``published_unix`` / ``eval_metric`` / ``source`` as passed to
        :meth:`publish`) and whether that version is the one currently
        serving — the trail a rollback decision is audited against.
        Versions with an armed quality monitor additionally carry a
        live ``quality`` block (worst-feature PSI, score drift,
        sampled-row count; full detail on ``GET /quality/<model>``) —
        the registry is the one pane of glass."""
        with self._lock:
            # snapshot ONLY under the registry lock; the monitor
            # summaries (which take each monitor's own lock, possibly
            # held through a whole observation pass) are built after
            # release — a /models poll must never park /predict
            # requests behind a monitoring refresh
            snap = {name: (entry, list(self._versions.get(name, [])),
                           entry.cobatch)
                    for name, entry in self._current.items()}
            pool = self._pool
        body: Dict[str, dict] = {
            name: {
                "version": entry.version,
                "versions": [
                    {"version": e.version,
                     "serving": e is entry, **e.meta,
                     **({"quality": e.monitor.summary()}
                        if e.monitor is not None else {})}
                    for e in versions],
                # group-aware: a fused entry's in-flight work lives in
                # the GROUP's queue, not the (idle) solo batcher's
                "queue_depth": (group.batcher.depth()
                                if group is not None
                                else entry.batcher.depth()),
                **({"cobatch": group.describe()}
                   if group is not None else {}),
                "quality": (entry.monitor.summary()
                            if entry.monitor is not None else None),
            }
            for name, (entry, versions, group) in snap.items()
        }
        if pool is not None:
            # per-lane state (snapshot-and-release inside the pool:
            # a /models poll never parks dispatch routing)
            body["_fleet"] = {
                "n_lanes": pool.n_lanes,
                "healthy_lanes": pool.healthy_count(),
                "lanes": pool.snapshot(),
            }
        return body

    def close(self) -> None:
        """Drain and release every entry (process shutdown): fused
        groups first (they feed the lanes), then solo batchers, then
        the lane pool itself."""
        with self._swap_lock:
            with self._lock:
                entries = [e for vs in self._versions.values()
                           for e in vs]
                groups = list(self._groups.values())
                self._current.clear()
                self._versions.clear()
                self._history.clear()
                self._groups.clear()
                for e in entries:
                    e.cobatch = None
            for g in groups:
                g.close(drain=True)
            for e in entries:
                e.batcher.close(drain=True)
            pool, self._pool, self._pool_init = self._pool, None, False
            if pool is not None:
                pool.close()
