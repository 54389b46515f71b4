"""Deadline watchdog: bounded stalls instead of silent hangs.

The r12 fault harness could only make a seam *crash* — every
hang-shaped failure mode (a wedged collective, a dispatch RPC that
never returns, an NFS checkpoint write that blocks forever) was
untested and, in production, unbounded.  The reference LightGBM guards
every socket operation with ``Network`` ``time_out`` semantics; this
module is that guarantee for the jax_graft stack: per-phase deadlines
(``watchdog_*_s`` knobs, default 0 = off so the hot path is untouched)
that, on expiry, dump ALL-thread stacks into the crash flight recorder
(docs/OBSERVABILITY.md) and surface the stall as a classified
:class:`StallError` — a ``TimeoutError`` subclass, so the existing
retry machinery (``reliability/retry.py``) treats it as transient and
re-enters safe seams, while exhaustion fails loudly with the seam
named.

Two mechanisms, one stall path:

- :func:`run_with_deadline` — bound a BLOCKING host call (dispatch
  enqueue, host collective, checkpoint IO, serve dispatch): the call
  runs on a daemon worker; if it has not returned within the deadline
  the caller gets a :class:`StallError` (stacks dumped, ``stalls_total``
  counted) and the wedged worker is abandoned.  This is what turns the
  fault harness's ``hang`` action from a test-killer into tested
  behavior.
- :class:`Watchdog` (singleton ``WATCHDOG``) — a monitor thread for
  phases that cannot be wrapped (a whole continuous-lane cycle phase):
  ``watch(phase, deadline_s, seam)`` arms a one-shot token; expiry
  dumps stacks + counts + warns (it cannot interrupt the stalled
  thread, but it makes the stall observable within the deadline);
  ``cancel(token)`` disarms on phase completion.

Deadline knobs (``Config``): ``watchdog_dispatch_s`` (fused-chunk /
per-iteration dispatch enqueue), ``watchdog_collective_s`` (host
collectives + sharded binfind participants), ``watchdog_checkpoint_s``
(checkpoint/ledger file IO), ``watchdog_serve_s`` (coalesced serving
dispatch), ``watchdog_continuous_s`` (continuous-lane cycle phases).
Callers with a Config in hand read it directly; the config-less seams
(``distributed._allgather``, ``HostCollectives``, ``checkpoint.io``)
read the process-global registry :func:`deadline`, armed by
``apply_config`` from any Config carrying a non-zero knob (a zero
leaves the armed value alone — internally-built default Configs must
not disarm a run's deadlines mid-flight; tests reset via
:func:`set_deadline`).
"""
from __future__ import annotations

import sys
import threading
import time
import traceback
from typing import Callable, Dict, Optional

from ..utils.log import Log

# phases with a process-global deadline slot (watchdog_<phase>_s knob)
PHASES = ("dispatch", "collective", "checkpoint", "serve", "continuous")

_DEADLINES: Dict[str, float] = {p: 0.0 for p in PHASES}
_STACK_FRAMES = 24   # frames kept per thread in a stall dump


class StallError(TimeoutError):
    """A watched operation exceeded its deadline.  Subclasses
    ``TimeoutError`` ON PURPOSE: ``retry.is_transient`` classifies it
    retryable by type, so a stall at a safe re-entry seam (the
    dispatch enqueue) rides the existing bounded-retry machinery, and
    retry exhaustion re-raises it with the seam named."""

    def __init__(self, phase: str = "", seam: str = "",
                 deadline_s: float = 0.0,
                 elapsed_s: Optional[float] = None):
        what = phase or seam or "operation"
        msg = (f"{what} stalled: deadline exceeded after "
               f"{deadline_s:g}s")
        if elapsed_s is not None:
            msg += f" ({elapsed_s:.2f}s elapsed)"
        if seam:
            msg += f" [seam {seam}]"
        super().__init__(msg)
        self.phase = phase
        self.seam = seam
        self.deadline_s = float(deadline_s)
        self.elapsed_s = elapsed_s


def set_deadline(phase: str, seconds: float) -> None:
    """Set one phase deadline directly (0 disarms) — the test seam;
    production code arms via the Config knobs."""
    if phase not in _DEADLINES:
        raise ValueError(f"unknown watchdog phase {phase!r} "
                         f"(registered: {', '.join(PHASES)})")
    _DEADLINES[phase] = max(0.0, float(seconds))


def deadline(phase: str) -> float:
    """The armed deadline for ``phase`` (0 = unbounded)."""
    return _DEADLINES.get(phase, 0.0)


def apply_config(cfg) -> None:
    """Arm the process-global deadlines from a Config's
    ``watchdog_*_s`` knobs.  Non-zero values arm; zero (the default)
    leaves the current value alone, so internally-built default
    Configs cannot disarm a run's deadlines mid-flight (the
    ``faults.apply_config`` contract)."""
    knobs = {
        "dispatch": getattr(cfg, "watchdog_dispatch_s", 0.0),
        "collective": getattr(cfg, "watchdog_collective_s", 0.0),
        "checkpoint": getattr(cfg, "watchdog_checkpoint_s", 0.0),
        "serve": getattr(cfg, "watchdog_serve_s", 0.0),
        "continuous": getattr(cfg, "watchdog_continuous_s", 0.0),
    }
    for phase, raw in knobs.items():
        v = float(raw or 0.0)
        if v > 0:
            _DEADLINES[phase] = v


def all_thread_stacks(limit: int = _STACK_FRAMES) -> Dict[str, list]:
    """{thread name: [formatted frames]} for every live thread — the
    stall dump's payload.  Pure introspection (``sys._current_frames``),
    safe to call from the monitor thread while the stalled thread is
    still blocked."""
    names = {t.ident: t.name for t in threading.enumerate()}
    out: Dict[str, list] = {}
    for tid, frame in sys._current_frames().items():
        key = f"{names.get(tid, 'unknown')}-{tid}"
        out[key] = [ln.rstrip("\n") for ln in
                    traceback.format_stack(frame)[-limit:]]
    return out


def _record_stall(phase: str, seam: str, deadline_s: float,
                  elapsed_s: float) -> None:
    """The one stall path both mechanisms share: count
    ``stalls_total`` (Prometheus ``ltpu_stalls_total``), dump the
    flight recorder with the seam, the blown deadline and ALL-thread
    stacks, and warn loudly."""
    from ..telemetry import TELEMETRY
    TELEMETRY.add("stalls_total", 1)
    # fleet event journal: the stall names its seam and carries the
    # active trace context (a stalled serve dispatch journals with the
    # coalesced request's trace)
    TELEMETRY.journal.emit(
        "stall", seam=seam, phase=phase,
        deadline_s=round(float(deadline_s), 6),
        elapsed_s=round(float(elapsed_s), 6))
    TELEMETRY.flight.dump(
        "stall", seam=seam, phase=phase,
        deadline_s=round(float(deadline_s), 6),
        elapsed_s=round(float(elapsed_s), 6),
        stacks=all_thread_stacks())
    Log.warning(
        f"watchdog: {phase or seam or 'operation'} exceeded its "
        f"{deadline_s:g}s deadline ({elapsed_s:.2f}s elapsed"
        + (f", seam {seam}" if seam else "")
        + ") — all-thread stacks dumped to the flight recorder")


def run_with_deadline(fn: Callable, deadline_s: float,
                      phase: str = "", seam: str = "",
                      *args, **kwargs):
    """Run ``fn(*args, **kwargs)`` bounded by ``deadline_s`` seconds.
    ``deadline_s <= 0`` calls inline (zero overhead when disarmed).
    Otherwise the call runs on a daemon worker thread; a call that
    has not finished within the deadline raises :class:`StallError`
    in the CALLER (stacks dumped, ``stalls_total`` counted) and the
    wedged worker is abandoned — its eventual result or exception is
    discarded, exactly like a socket op timed out by the reference's
    ``Network`` ``time_out``.  A worker exception inside the deadline
    re-raises unchanged in the caller."""
    if deadline_s is None or deadline_s <= 0:
        return fn(*args, **kwargs)
    from ..telemetry import TELEMETRY
    box: dict = {}
    done = threading.Event()
    # the set-up stages the worker runs hand their time to the stage
    # that waits here, so its own time does not count them twice
    waiting = TELEMETRY.current_stage()

    def _work():
        try:
            with TELEMETRY.stage_of(waiting):
                box["result"] = fn(*args, **kwargs)
        except BaseException as e:  # noqa: BLE001 - relayed to caller
            box["error"] = e
        finally:
            done.set()

    t0 = time.perf_counter()
    worker = threading.Thread(
        target=_work, daemon=True,
        name=f"ltpu-deadline-{phase or seam or 'op'}")
    worker.start()
    if not done.wait(deadline_s):
        elapsed = time.perf_counter() - t0
        _record_stall(phase, seam, deadline_s, elapsed)
        raise StallError(phase, seam, deadline_s, elapsed)
    if "error" in box:
        raise box["error"]
    return box.get("result")


class Watchdog:
    """Monitor-thread deadline watching for phases that cannot be
    wrapped in :func:`run_with_deadline` (the work runs on the
    caller's own thread across many calls — a continuous-lane cycle
    phase).  ``watch`` arms a one-shot token; on expiry the monitor
    dumps stacks + counts the stall + warns (it cannot interrupt the
    stalled thread); ``cancel`` disarms when the phase completes."""

    def __init__(self):
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._tokens: Dict[int, tuple] = {}
        self._next = 1
        self._thread: Optional[threading.Thread] = None
        self.fired: int = 0     # tokens that expired (tests)

    def watch(self, phase: str, deadline_s: float,
              seam: str = "") -> Optional[int]:
        """Arm a one-shot deadline on ``phase``; returns the token to
        :meth:`cancel` on completion (None when ``deadline_s <= 0``)."""
        if deadline_s is None or deadline_s <= 0:
            return None
        with self._cond:
            token = self._next
            self._next += 1
            now = time.monotonic()
            self._tokens[token] = (now + deadline_s, phase, seam,
                                   now, deadline_s)
            if self._thread is None or not self._thread.is_alive():
                self._thread = threading.Thread(
                    target=self._run, daemon=True, name="ltpu-watchdog")
                self._thread.start()
            self._cond.notify_all()
        return token

    def cancel(self, token: Optional[int]) -> None:
        if token is None:
            return
        with self._cond:
            self._tokens.pop(token, None)
            self._cond.notify_all()

    def _run(self) -> None:
        while True:
            expired = []
            with self._cond:
                now = time.monotonic()
                for token, rec in list(self._tokens.items()):
                    if rec[0] <= now:
                        expired.append(rec)
                        del self._tokens[token]
                if not expired:
                    nxt = min((rec[0] for rec in
                               self._tokens.values()), default=None)
                    self._cond.wait(None if nxt is None
                                    else max(nxt - now, 0.01))
                    continue
                self.fired += len(expired)
            # fire OUTSIDE the lock: the dump walks every thread's
            # stack and writes a file — new watch()/cancel() calls
            # must not block behind it
            for _abs, phase, seam, t0, dl in expired:
                _record_stall(phase, seam, dl, time.monotonic() - t0)


WATCHDOG = Watchdog()
