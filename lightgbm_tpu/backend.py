"""The one place that says which JAX platform is the accelerator.

Kernel selection (learner/grower.py), dispatch-chunk tuning
(engine.py), predict routing (booster.py) and serving warm-up/lanes
(serving/) all branch on this predicate; the on-chip test module and
``chip_smoke.py`` gate on it too.  ``chip_smoke.py`` is what proves the
``True`` side of every one of those branches still runs.
"""
from __future__ import annotations


def on_tpu() -> bool:
    """True when JAX's default backend is a TPU."""
    import jax
    return jax.default_backend() == "tpu"
