"""Device lane pool: N parallel dispatch streams under one registry.

The r14 micro-batcher ran every coalesced dispatch inline on its own
dispatcher thread — ONE stream between the listener and the hardware.
This module is the fleet layer (ROADMAP open item: replicate the
predictor across local devices): a :class:`LanePool` owns N worker
threads ("lanes"), each optionally pinned to a local accelerator
device via ``jax.default_device``, and every micro-batcher in the
registry hands its coalesced batches to the pool instead of running
them itself.  The per-device serving-predictor cache
(``Booster._serving_predictor`` keyed on the pinned device) gives
each lane its own resident ensemble stack, so lanes dispatch
concurrently instead of serializing on one device stream.

Routing is round-robin with work stealing: the candidate lane
advances per dispatch, but when the candidate's in-flight queue is
deeper than the shallowest healthy neighbor the batch is stolen to
that neighbor instead (``serve_steals`` counts them; per-lane
``serve_lane_depth.<i>`` gauges are what the steal decision reads).
Admission stays bounded: ``submit`` blocks while every healthy lane
already holds ``max_inflight`` batches, which backs the batcher
queue up and lets the r14 shed logic engage — the pool never grows
an unbounded second queue behind the first.

Reliability (docs/RELIABILITY.md): a dispatch that blows
``watchdog_serve_s`` stall-classifies its LANE, not the fleet — the
wedged lane is marked stalled (``serve_lane_stalls``), its queued
batches are failed loudly with the stall error (503 for exactly the
in-flight work on the wedged lane), and the router excludes it from
then on; survivors keep serving.  Only when EVERY lane is stalled
does ``submit`` itself raise, browning the whole service out loudly.
The stall is sticky by design — a wedged device stream does not
silently un-wedge, and ops sees the brownout on ``GET /models``.

On a single-device host (the CPU test seam) lanes are "simulated":
``serve_lanes=N`` builds N unpinned workers sharing the one device —
scheduling, stealing, stall isolation and parity behave identically,
which is what the lane-parity suite and the serve_bench scaling gate
run against.
"""
from __future__ import annotations

import collections
import threading
import time
from typing import Callable, Deque, List, Optional, Tuple

from ..reliability.watchdog import StallError
from ..telemetry import TELEMETRY
from ..utils.log import Log


def resolve_lanes(config) -> Tuple[int, list]:
    """``serve_lanes=auto|N`` -> (lane count, per-lane device list).
    "auto" is one lane per local device on accelerator backends and 1
    on host backends; an explicit N forces N lanes, sharing devices
    round-robin when N exceeds the device count.  With only one
    distinct device the list is all-None (unpinned): pinning every
    lane to the same device would split the jit cache key for no
    parallelism, so simulated lanes share the default stream's
    compiled programs."""
    spec = str(getattr(config, "serve_lanes", "auto") or "auto")
    spec = spec.strip().lower()
    import jax

    from ..backend import on_tpu
    accel = on_tpu()
    local = list(jax.local_devices()) if accel else []
    if spec in ("auto", ""):
        n = max(1, len(local)) if accel else 1
    else:
        n = max(1, int(spec))
    if len(local) > 1:
        devices = [local[i % len(local)] for i in range(n)]
    else:
        devices = [None] * n
    return n, devices


class Lane:
    """One dispatch stream: a worker thread, its bounded in-flight
    queue, and its health/telemetry counters.  All mutable state is
    guarded by the owning pool's single lock."""

    __slots__ = ("index", "device", "jobs", "inflight", "dispatches",
                 "stalls", "stalled", "thread")

    def __init__(self, index: int, device):
        self.index = int(index)
        self.device = device
        # (job, abort) pairs: job(lane) runs on the worker under the
        # lane's device context; abort(error) fails the batch without
        # running it (stall drain)
        self.jobs: Deque[Tuple[Callable, Callable]] = collections.deque()
        self.inflight = False
        self.dispatches = 0
        self.stalls = 0
        self.stalled = False
        self.thread: Optional[threading.Thread] = None

    def depth(self) -> int:
        """Queued + running batches (pool lock held)."""
        return len(self.jobs) + (1 if self.inflight else 0)


class LanePool:
    """N lanes behind one submit door (one pool per registry, shared
    by every served model's batcher)."""

    def __init__(self, devices: list, name: str = "serve",
                 max_inflight: int = 2):
        if not devices:
            raise ValueError("LanePool needs at least one device slot")
        self.name = name
        # per-lane in-flight bound (queued + running): 2 mirrors the
        # predictor's double buffer — one batch computing, one staged.
        # Beyond that, submit blocks and the batcher queue (where the
        # r14 shed logic lives) absorbs the backlog
        self.max_inflight = max(1, int(max_inflight))
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._closed = False
        self._rr = -1
        self._lanes: List[Lane] = [Lane(i, d)
                                   for i, d in enumerate(devices)]
        for lane in self._lanes:
            t = threading.Thread(
                target=self._worker, args=(lane,), daemon=True,
                name=f"ltpu-lane-{name}-{lane.index}")
            lane.thread = t
            t.start()

    # -- introspection -------------------------------------------------
    @property
    def n_lanes(self) -> int:
        return len(self._lanes)

    def healthy_count(self) -> int:
        with self._lock:
            return sum(1 for lane in self._lanes if not lane.stalled)

    @property
    def warm_devices(self) -> tuple:
        """Distinct per-lane devices to warm before cutover (a single
        (None,) when lanes are unpinned/simulated — one warm covers
        the shared default stream)."""
        seen: dict = {}
        for lane in self._lanes:
            seen.setdefault(lane.device, None)
        return tuple(seen)

    def snapshot(self) -> List[dict]:
        """Per-lane state for ``GET /models``: copied under the pool
        lock (ints only) and released — a /models poll never parks
        dispatch routing behind response serialization."""
        with self._lock:
            return [{
                "lane": lane.index,
                "device": (str(lane.device)
                           if lane.device is not None else None),
                "queue_depth": lane.depth(),
                "dispatches": lane.dispatches,
                "stalls": lane.stalls,
                "stalled": lane.stalled,
            } for lane in self._lanes]

    # -- routing -------------------------------------------------------
    def submit(self, job: Callable, abort: Callable) -> Lane:
        """Enqueue one coalesced batch: ``job(lane)`` runs on the
        selected lane's worker, ``abort(error)`` is called instead if
        the lane stalls before the batch runs.  Blocks while every
        healthy lane is at ``max_inflight`` (backpressure into the
        batcher queue); raises :class:`StallError` when no healthy
        lane remains."""
        tm = TELEMETRY
        with self._cond:
            while True:
                if self._closed:
                    raise RuntimeError("lane pool closed")
                healthy = [ln for ln in self._lanes if not ln.stalled]
                if not healthy:
                    raise StallError(
                        f"serve_dispatch({self.name})",
                        "predict.dispatch", 0.0, 0.0)
                if any(ln.depth() < self.max_inflight
                       for ln in healthy):
                    break
                self._cond.wait(1.0)
            # round-robin candidate, stolen to the shallowest healthy
            # neighbor when the candidate's queue is deeper (the
            # per-lane depth gauges drive this decision)
            self._rr += 1
            cand = healthy[self._rr % len(healthy)]
            dmin = min(ln.depth() for ln in healthy)
            if cand.depth() > dmin:
                cand = min(healthy,
                           key=lambda ln: (ln.depth(), ln.index))
                if tm.on:
                    tm.add("serve_steals", 1)
            cand.jobs.append((job, abort))
            depth = cand.depth()
            self._cond.notify_all()
        if tm.on:
            tm.gauge(f"serve_lane_depth.{cand.index}", depth)
        return cand

    def note_dispatch(self, lane: Lane, dt_ms: float) -> None:
        """Per-lane success accounting (called by the batcher after a
        dispatch completes on ``lane``)."""
        with self._lock:
            lane.dispatches += 1
        tm = TELEMETRY
        if tm.on:
            tm.add("serve_lane_dispatches", 1)
            tm.observe(f"serve_lane_dispatch_ms.{lane.index}", dt_ms)

    def mark_stalled(self, lane: Lane, error: BaseException) -> int:
        """Brown the lane out: exclude it from routing, fail its
        queued batches with the stall error (they were in-flight on
        the wedged stream — answering them promptly beats burning one
        watchdog deadline each, serially), count it loudly.  Returns
        the number of aborted batches."""
        with self._cond:
            if lane.stalled:
                return 0
            lane.stalled = True
            lane.stalls += 1
            aborted = list(lane.jobs)
            lane.jobs.clear()
            self._cond.notify_all()
        tm = TELEMETRY
        if tm.on:
            tm.add("serve_lane_stalls", 1)
            tm.gauge(f"serve_lane_depth.{lane.index}", 0)
        Log.warning(
            f"serving lane {lane.index}"
            + (f" ({lane.device})" if lane.device is not None else "")
            + f" stalled ({error}); routing around it"
            + (f", failing {len(aborted)} queued batch(es)"
               if aborted else ""))
        for _job, abort in aborted:
            try:
                abort(error)
            except Exception:
                pass
        return len(aborted)

    # -- worker --------------------------------------------------------
    def _worker(self, lane: Lane) -> None:
        while True:
            with self._cond:
                while not lane.jobs:
                    if self._closed:
                        return
                    self._cond.wait()
                job, _abort = lane.jobs.popleft()
                lane.inflight = True
            try:
                if lane.device is not None:
                    import jax
                    with jax.default_device(lane.device):
                        job(lane)
                else:
                    job(lane)
            except Exception as e:
                # jobs own their error propagation (the batcher fails
                # its requests internally); a raise here is a bug in
                # the job wrapper — keep the lane alive, log it
                Log.warning(f"serving lane {lane.index} job crashed "
                            f"outside the batch path: {e!r}")
            finally:
                with self._cond:
                    lane.inflight = False
                    self._cond.notify_all()
                if TELEMETRY.on:
                    with self._lock:
                        depth = lane.depth()
                    TELEMETRY.gauge(f"serve_lane_depth.{lane.index}",
                                    depth)

    # -- lifecycle -----------------------------------------------------
    def drain(self, timeout_s: float = 60.0) -> bool:
        """Wait until every lane is idle with an empty queue."""
        end = time.monotonic() + timeout_s
        with self._cond:
            while any(lane.jobs or lane.inflight
                      for lane in self._lanes):
                left = end - time.monotonic()
                if left <= 0:
                    return False
                self._cond.wait(min(left, 1.0))
        return True

    def close(self, timeout_s: float = 60.0) -> None:
        """Drain queued work, stop the workers.  A worker whose
        dispatch was abandoned by the watchdog is a daemon — it never
        blocks process exit."""
        self.drain(timeout_s)
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        for lane in self._lanes:
            if lane.thread is not None:
                lane.thread.join(min(timeout_s, 5.0))

    @property
    def closed(self) -> bool:
        return self._closed
