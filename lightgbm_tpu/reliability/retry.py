"""Retry with bounded exponential backoff + jitter, and the
transient / OOM error classification the recovery paths share.

Scope discipline: retries wrap only TRANSIENT-classified errors at
host seams that are safe to re-enter (the dispatch enqueue before any
state mutation, the distributed rendezvous, host collective calls).
An error that is not transient — a real bug, a shape mismatch, an OOM
— propagates immediately: OOM is handled by the degradation ladders
(``docs/RELIABILITY.md``), never by blind re-dispatch of the exact
allocation that just failed.
"""
from __future__ import annotations

import dataclasses
import random
import time
from typing import Callable, Optional

from ..telemetry import TELEMETRY
from ..utils.log import Log

# connection-/scheduling-shaped builtin exceptions are transient by
# type; everything else is classified by message marker (jax surfaces
# backend RPC errors as XlaRuntimeError with the grpc status text)
TRANSIENT_TYPES = (ConnectionError, TimeoutError, InterruptedError)
TRANSIENT_MARKERS = (
    "unavailable", "deadline exceeded", "deadline_exceeded",
    "connection reset", "connection refused", "broken pipe",
    "temporarily unavailable", "socket closed", "transient",
    "try again",
)
OOM_MARKERS = (
    "resource_exhausted", "resource exhausted", "out of memory",
    "failed to allocate", "allocation failure", "oom killed",
)
# a kernel the compiler REFUSES for its on-core memory carries the same
# RESOURCE_EXHAUSTED status as a run-time allocation failure ("XLA:TPU
# compile permanent error. Ran out of memory in memory space vmem" —
# libtpu 0.0.34 on a v5e, PR 21).  A kernel's VMEM footprint depends on
# its block shape, not on the chunk length or the row bucket, so a
# smaller chunk or bucket is refused the same way — these must not
# enter a downshift ladder, where each rung would pay a fresh
# multi-second compile before the error finally surfaced.  (HBM
# exhaustion, at compile time or run time, does shrink with the
# chunk/bucket and stays in the ladder.)
VMEM_REFUSAL_MARKER = "memory space vmem"


def is_oom(exc: BaseException) -> bool:
    """Whether ``exc`` is a RUN-TIME device/host memory-exhaustion
    error (the degradation ladders key on this; jax raises
    XlaRuntimeError with a RESOURCE_EXHAUSTED status on device OOM).
    A kernel refused for VMEM at compile time is not: see
    VMEM_REFUSAL_MARKER."""
    msg = str(exc).lower()
    if VMEM_REFUSAL_MARKER in msg:
        return False
    return any(m in msg for m in OOM_MARKERS)


def is_transient(exc: BaseException) -> bool:
    """Whether ``exc`` is worth retrying: connection/timeout shaped,
    or carrying an RPC-unavailability marker — and NOT an OOM (the
    same allocation would fail again; degrade instead)."""
    if is_oom(exc):
        return False
    if isinstance(exc, TRANSIENT_TYPES):
        return True
    msg = str(exc).lower()
    return any(m in msg for m in TRANSIENT_MARKERS)


@dataclasses.dataclass
class RetryPolicy:
    """Bounded exponential backoff: attempt k (0-based) sleeps
    ``min(max_delay_s, base_delay_s * 2**k)`` scaled by a uniform
    jitter in [1, 1+jitter] (decorrelates a fleet of workers retrying
    the same dead endpoint).

    The bound is ``max_retries`` attempts — UNLESS ``budget_s`` is
    set, in which case the TIME budget governs instead: retries
    continue (with the backoff still growing toward ``max_delay_s``)
    until the next sleep would exceed ``budget_s`` cumulative.  That
    is the reference ``time_out`` semantic at the rendezvous seam: a
    coordinator that needs two minutes to come up is waited out for
    the configured minutes, not for three fixed attempts."""

    max_retries: int = 2
    base_delay_s: float = 0.5
    max_delay_s: float = 30.0
    jitter: float = 0.25
    budget_s: Optional[float] = None

    @classmethod
    def from_config(cls, config) -> "RetryPolicy":
        return cls(
            max_retries=max(0, int(getattr(config, "dispatch_retries",
                                           2))),
            base_delay_s=max(0.0, float(getattr(config,
                                                "retry_backoff_s",
                                                0.5))))

    def delay(self, attempt: int, rng: random.Random) -> float:
        d = min(self.max_delay_s, self.base_delay_s * (2 ** attempt))
        return d * (1.0 + self.jitter * rng.random())


def retry_call(fn: Callable, *args, policy: Optional[RetryPolicy] = None,
               seam: str = "", classify: Callable = is_transient,
               sleep: Callable = time.sleep, **kwargs):
    """Call ``fn`` retrying transient-classified failures under
    ``policy``.  Retries count into the ``retries`` telemetry counter
    and warn with the seam name; exhaustion (or a non-transient error)
    re-raises the LAST error unchanged so callers and tests see the
    original failure, not a wrapper."""
    policy = policy or RetryPolicy()
    rng = random.Random()
    spent = 0.0
    attempt = 0
    while True:
        try:
            return fn(*args, **kwargs)
        except Exception as e:  # noqa: BLE001 - classification decides
            if not classify(e):
                raise
            if policy.budget_s is None:
                if attempt >= policy.max_retries:
                    # retry budget exhausted: count it (Prometheus
                    # ltpu_retry_exhausted_total — exhaustion used to
                    # re-raise with no metric trail) and flight-dump
                    # the last-N telemetry events naming the seam
                    # before re-raising the original error (the dump
                    # is a no-op unless the recorder is armed)
                    TELEMETRY.add("retry_exhausted_total", 1)
                    TELEMETRY.flight.dump("retry_exhausted", seam=seam,
                                          attempts=attempt + 1,
                                          budget="max_retries="
                                          f"{policy.max_retries}",
                                          error=repr(e)[:300])
                    raise
                d = policy.delay(attempt, rng)
            else:
                # time-budget mode: the count bound is the budget, not
                # max_retries; floor the delay so a zero base backoff
                # cannot hot-spin the budget away
                d = max(policy.delay(attempt, rng), 0.05)
                if spent + d > policy.budget_s:
                    TELEMETRY.add("retry_exhausted_total", 1)
                    TELEMETRY.flight.dump("retry_exhausted", seam=seam,
                                          attempts=attempt + 1,
                                          budget=f"{policy.budget_s:g}s"
                                          f" (spent {spent:.2f}s)",
                                          error=repr(e)[:300])
                    raise
            TELEMETRY.add("retries", 1)
            bound = (f"{policy.budget_s:.0f}s budget"
                     if policy.budget_s is not None
                     else f"of {policy.max_retries}")
            Log.warning(
                f"transient error at {seam or 'call'} (attempt "
                f"{attempt + 1} {bound}): {e!r}; retrying in {d:.2f}s")
            sleep(d)
            spent += d
            attempt += 1
