"""Leveled logging (reference: include/LightGBM/utils/log.h).

``Log.fatal`` raises instead of aborting, matching the reference's
``Log::Fatal`` -> std::runtime_error contract (log.h:83-95).
"""
from __future__ import annotations

import sys


class LightGBMError(RuntimeError):
    """Raised by Log.fatal (reference: Log::Fatal throws std::runtime_error)."""


_SINK = None


def set_sink(fn) -> None:
    """Install an observer for emitted log lines (``fn(tag, msg)``) —
    the crash flight recorder subscribes here so the last-N warnings
    ride its ring buffer.  One sink; None uninstalls."""
    global _SINK
    _SINK = fn


class Log:
    # verbosity: <0 fatal only, =0 warning+, =1 info+, >1 debug+
    level: int = 1

    @classmethod
    def set_level(cls, level: int) -> None:
        cls.level = level

    @classmethod
    def _emit(cls, tag: str, msg: str) -> None:
        sys.stderr.write(f"[LightGBM-TPU] [{tag}] {msg}\n")
        sys.stderr.flush()
        if _SINK is not None:
            try:
                _SINK(tag, msg)
            except Exception:
                pass

    @classmethod
    def debug(cls, msg: str) -> None:
        if cls.level > 1:
            cls._emit("Debug", msg)

    @classmethod
    def info(cls, msg: str) -> None:
        if cls.level >= 1:
            cls._emit("Info", msg)

    @classmethod
    def warning(cls, msg: str) -> None:
        if cls.level >= 0:
            cls._emit("Warning", msg)

    @classmethod
    def fatal(cls, msg: str) -> None:
        cls._emit("Fatal", msg)
        raise LightGBMError(msg)
