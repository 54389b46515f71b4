"""Collective-communication seam.

The functional equivalent of the reference's static Network class
(reference: include/LightGBM/network.h:86-296 — Allreduce,
ReduceScatter, Allgather, GlobalSyncUpByMin/Max/Mean, GlobalSum — and
the external-function injection point Network::Init(num_machines, rank,
reduce_scatter_fn, allgather_fn) at network.h:96 / c_api.h:760).

Inside jitted programs the collectives are implicit in shardings (see
parallel/mesh.py); this module exists for code that needs EXPLICIT
collective calls — the voting learner's vote exchange, distributed
objective syncs (RenewTreeOutput's GlobalSum, gbdt.cpp:795-804), and
tests that inject a fake backend the way LGBM_NetworkInitWithFunctions
allowed.  The default backend maps straight onto jax.lax collectives
over a named mesh axis; a host backend (numpy, single process) makes
the distributed code paths unit-testable without any devices.
"""
from __future__ import annotations

import re
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..telemetry import TELEMETRY


def traced_nbytes(x) -> int:
    """Byte size of an array/tracer from its abstract shape+dtype —
    Python arithmetic only, safe to call at trace time inside jitted
    bodies (no host-library calls, no HLO change)."""
    size = 1
    for d in getattr(x, "shape", ()) or ():
        size *= int(d)
    dt = getattr(x, "dtype", None)
    return size * (int(dt.itemsize) if dt is not None else 4)


def _note_collective(kind: str, x) -> None:
    """Trace-time collective accounting (docs/OBSERVABILITY.md,
    distributed observability): counts explicit collective call SITES
    and their payload bytes per kind.  Inside a jitted body this runs
    once per trace (so the counters read "bytes exchanged per
    compiled step", the same unit the MULTICHIP gate asserts);
    on the host backends it counts every call.  Pure host Python —
    the telemetry=off/counters identity guarantee holds because
    nothing here emits an op."""
    if TELEMETRY.on:
        TELEMETRY.add(f"collective_{kind}_calls", 1)
        TELEMETRY.add(f"collective_{kind}_bytes", traced_nbytes(x))


class Collectives:
    """Collective ops over a named mesh axis, usable inside shard_map."""

    def __init__(self, axis_name: Optional[str]):
        self.axis_name = axis_name

    @property
    def is_distributed(self) -> bool:
        return self.axis_name is not None

    # -- core three (the only ones the learners need; SURVEY §2.4) ----
    def allreduce_sum(self, x):
        if self.axis_name is None:
            return x
        _note_collective("allreduce", x)
        return jax.lax.psum(x, self.axis_name)

    def reduce_scatter(self, x, tiled_axis: int = 0):
        if self.axis_name is None:
            return x
        _note_collective("reduce_scatter", x)
        return jax.lax.psum_scatter(x, self.axis_name,
                                    scatter_dimension=tiled_axis,
                                    tiled=True)

    def all_gather(self, x, axis: int = 0):
        if self.axis_name is None:
            return x
        _note_collective("allgather", x)
        return jax.lax.all_gather(x, self.axis_name, axis=axis,
                                  tiled=True)

    # -- scalar sync helpers (network.h:165-257) ----------------------
    def global_sum(self, x):
        return self.allreduce_sum(x)

    def global_min(self, x):
        if self.axis_name is None:
            return x
        _note_collective("allreduce", x)
        return jax.lax.pmin(x, self.axis_name)

    def global_max(self, x):
        if self.axis_name is None:
            return x
        _note_collective("allreduce", x)
        return jax.lax.pmax(x, self.axis_name)

    def global_mean(self, x):
        if self.axis_name is None:
            return x
        _note_collective("allreduce", x)
        return jax.lax.pmean(x, self.axis_name)

    def argmax_sync(self, value, payload):
        """Global argmax with payload broadcast — the
        SyncUpGlobalBestSplit pattern (parallel_tree_learner.h:184-207):
        every shard contributes (gain, split-struct); all shards end up
        with the payload of the globally best gain."""
        if self.axis_name is None:
            return payload
        _note_collective("allgather", value)
        gains = jax.lax.all_gather(value, self.axis_name)
        best = jnp.argmax(gains)
        gathered = jax.tree_util.tree_map(
            lambda p: (_note_collective("allgather", p),
                       jax.lax.all_gather(p, self.axis_name))[1],
            payload)
        return jax.tree_util.tree_map(lambda g: g[best], gathered)

    def rank(self):
        if self.axis_name is None:
            return 0
        return jax.lax.axis_index(self.axis_name)

    def num_machines(self):
        if self.axis_name is None:
            return 1
        return jax.lax.axis_size(self.axis_name)


class HostCollectives(Collectives):
    """Single-process fake backend — the LGBM_NetworkInitWithFunctions
    analog for unit tests: simulates a k-way reduction by applying the
    reduction to caller-provided per-shard arrays."""

    def __init__(self, shards: int = 1):
        super().__init__(None)
        self.shards = shards

    def simulate_allreduce(self, per_shard_arrays):
        for a in per_shard_arrays:
            _note_collective("allreduce", a)
        return np.sum(np.stack(per_shard_arrays), axis=0)

    def simulate_reduce_scatter(self, per_shard_arrays, axis: int = 0):
        total = np.sum(np.stack(per_shard_arrays), axis=0)
        for a in per_shard_arrays:
            _note_collective("reduce_scatter", a)
        return np.array_split(total, self.shards, axis=axis)

    def simulate_allgather(self, per_shard_arrays, axis: int = 0):
        # the simulated gather carries the SAME reliability seam and
        # deadline as the real host collective (distributed._allgather):
        # sharded-construct merges route through here, so a chaos plan
        # naming collectives.allgather — including a hang bounded by
        # watchdog_collective_s — exercises the simulated participants
        # exactly like a pod would see it
        from ..reliability import watchdog as _watchdog
        from ..reliability.faults import FAULTS

        def _gather():
            FAULTS.fault_point("collectives.allgather")
            for a in per_shard_arrays:
                _note_collective("allgather", a)
            return np.concatenate(per_shard_arrays, axis=axis)

        return _watchdog.run_with_deadline(
            _gather, _watchdog.deadline("collective"),
            phase="host_collective", seam="collectives.allgather")


# ---------------------------------------------------------------------------
# Compressed histogram exchange (Config.hist_exchange): the data-
# parallel per-pass histogram psum is the largest recurring ICI
# payload (the MULTICHIP gate's byte window), and histogram bins are
# SMOOTH along the bin axis — neighboring bins hold similar mass — so
# a delta code along bins concentrates values near zero and a shared
# per-(leaf, group, channel) scale quantizes the deltas to int16/int8
# at bounded reconstruction error.  Delta-coding is linear, so it
# COMMUTES with the cross-shard sum: shards quantize against one
# pmax'd scale, psum the narrow integers (with world-size headroom so
# the integer sum can never overflow), and every shard reconstructs
# the identical f32 histogram by cumsum BEFORE the FixHistogram /
# parent-subtraction step.
# ---------------------------------------------------------------------------
HIST_EXCHANGE_MODES = ("f32", "q16", "q8")


def _exchange_qparams(mode: str, world: int):
    """(qmax, int dtype) for a codec mode: the quantization ceiling
    leaves ``world``-way summation headroom inside the wire dtype."""
    bits = 16 if mode == "q16" else 8
    qmax = (2 ** (bits - 1) - 1) // max(int(world), 1)
    if qmax < 1:
        raise ValueError(
            f"hist_exchange={mode}: world size {world} leaves no "
            f"quantization levels inside int{bits}; use "
            + ("hist_exchange=q16 or f32" if mode == "q8" else
               "hist_exchange=f32"))
    return qmax, (jnp.int16 if mode == "q16" else jnp.int8)


def exchange_histograms(hist, axis_name, mode: str = "f32",
                        world: int = 1):
    """Cross-shard histogram sum over ``axis_name`` under the
    ``hist_exchange`` codec.  ``hist`` is the local (L, G, B, 3) f32
    histogram (bin axis -2); returns the reconstructed f32 global sum
    on every shard.

    "f32" is the legacy raw psum — identical lowering, byte-identical
    trees.  "q16"/"q8" ship delta-coded integers plus a tiny f32
    scale payload; wire bytes land in the
    ``collective_hist_exchange_bytes`` counter (the int payload) and
    ``collective_hist_exchange_scale_bytes`` (the scales), so the
    MULTICHIP gate reads the compressed stream directly."""
    if mode not in HIST_EXCHANGE_MODES:
        raise ValueError(f"hist_exchange must be one of "
                         f"{HIST_EXCHANGE_MODES}, got {mode!r}")
    if axis_name is None:
        return hist
    if mode == "f32":
        _note_collective("hist_exchange", hist)
        return jax.lax.psum(hist, axis_name)
    qmax, qdt = _exchange_qparams(mode, world)
    first = hist[..., :1, :]
    delta = jnp.concatenate([first, jnp.diff(hist, axis=-2)], axis=-2)
    # ONE scale per (leaf, group, channel), shared across shards via
    # pmax so every shard quantizes against the same grid and the
    # integer sum dequantizes exactly once.  The non-integrality
    # residual rides the same pmax payload (bin axis, position 1):
    # channels whose deltas are integral on EVERY shard and fit qmax
    # (the count channel always; grad/hess too under the unit-gradient
    # objectives, e.g. regression_l1) ship verbatim on the unit grid —
    # the reconstruction is then bit-exact against the f32 psum
    amax = jnp.max(jnp.abs(delta), axis=-2, keepdims=True)
    frac = jnp.max(jnp.abs(delta - jnp.round(delta)), axis=-2,
                   keepdims=True)
    stat = jax.lax.pmax(jnp.concatenate([amax, frac], axis=-2),
                        axis_name)
    amax, frac = stat[..., :1, :], stat[..., 1:, :]
    _note_collective("hist_exchange_scale", stat)
    exact = (frac == 0) & (amax <= qmax)
    denom = jnp.where(exact, jnp.float32(qmax),
                      jnp.maximum(amax, 1e-30))
    q = jnp.clip(jnp.round(delta / denom * qmax),
                 -qmax, qmax).astype(qdt)
    _note_collective("hist_exchange", q)
    qsum = jax.lax.psum(q, axis_name)
    deq = qsum.astype(jnp.float32) * (denom / qmax)
    return jnp.cumsum(deq, axis=-2)


def int_exchange_fits_int32(global_rows: int) -> bool:
    """True where the cross-shard sum of int32 histogram accumulators
    cannot leave int32: every shard's accumulator is bounded by its rows
    times the int8 weight ceiling, so the sum is by ``global_rows``
    (ops/histogram.py ``quant_rows_ok``, the same bound)."""
    from ..ops.histogram import quant_rows_ok
    return quant_rows_ok(global_rows)


def exchange_int_histograms(acc, axis_name, *, global_rows: int,
                            segments: int = 1):
    """EXACT sum of the quantized path's int32 histogram accumulators
    ``(..., 3)`` — across the shards of ``axis_name``, and first across
    a shard's own row segments where ``segments`` > 1 (``acc`` is then
    ``(segments, ..., 3)``; ``axis_name`` None: one device's segments
    alone) — taken before any dequantize.  Returns on every shard
    ``(total, rows)``: the float32 nearest to each integer total, which
    is a function of the total alone — so the trees do not depend on
    how many shards or segments hold the rows (the data-parallel
    learner's ReduceScatter of histograms,
    data_parallel_tree_learner.cpp:147-162, as integers) — and the
    count channel's total as int32, which float32 would round above
    2^24 rows (``global_rows`` < 2^31, so it fits).

    Where ``global_rows * 127 < 2**31`` every total fits int32 and one
    ``psum`` carries it.  Beyond that (4 shards x 2^24 rows x 127 =
    2^33; any device in segments) each accumulator travels as two
    16-bit limbs, ``lo`` in [0, 65535] and ``hi`` the arithmetic shift:
    each limb's sum is exact in int32 and, under 2^24 as it is for the
    shards of one host and their segments (fewer than 256), in float32;
    ``hi_sum * 65536`` is exact, and the one float32 addition that puts
    them together rounds the exact total once."""
    if str(acc.dtype) != "int32":
        raise TypeError(f"exchange_int_histograms sums int32 "
                        f"accumulators, got {acc.dtype}")
    if axis_name is None and segments == 1:
        return acc.astype(jnp.float32), acc[..., 2]
    fits = segments == 1 and int_exchange_fits_int32(global_rows)
    payload = acc if fits else jnp.stack([acc & 0xFFFF, acc >> 16])
    if segments > 1:
        payload = jnp.sum(payload, axis=1)
    if axis_name is not None:
        _note_collective("allreduce", payload)
        _note_collective("hist_exchange", payload)
        payload = jax.lax.psum(payload, axis_name)
    if fits:
        return payload.astype(jnp.float32), payload[..., 2]
    lo, hi = payload
    return (hi.astype(jnp.float32) * 65536.0 + lo.astype(jnp.float32),
            hi[..., 2] * 65536 + lo[..., 2])


def host_exchange_histograms(per_shard_hists, mode: str = "f32"):
    """Single-process analog of :func:`exchange_histograms` over
    caller-provided per-shard numpy histograms — the
    HostCollectives.simulate_* pattern, so the codec path (and its
    byte counters) is unit-testable and benchable without devices.
    Carries the ``collectives.hist_exchange`` fault seam and the
    collective watchdog deadline exactly like the simulated
    allgather."""
    if mode not in HIST_EXCHANGE_MODES:
        raise ValueError(f"hist_exchange must be one of "
                         f"{HIST_EXCHANGE_MODES}, got {mode!r}")
    from ..reliability import watchdog as _watchdog
    from ..reliability.faults import FAULTS

    def _exchange():
        FAULTS.fault_point("collectives.hist_exchange")
        world = len(per_shard_hists)
        stack = np.stack([np.asarray(a, dtype=np.float32)
                          for a in per_shard_hists])
        if mode == "f32":
            for a in per_shard_hists:
                _note_collective("hist_exchange", a)
            return np.sum(stack, axis=0)
        bits = 16 if mode == "q16" else 8
        qmax = (2 ** (bits - 1) - 1) // world
        if qmax < 1:
            raise ValueError(
                f"hist_exchange={mode}: world size {world} leaves no "
                f"quantization levels inside int{bits}")
        npdt = np.int16 if mode == "q16" else np.int8
        delta = np.concatenate(
            [stack[..., :1, :], np.diff(stack, axis=-2)], axis=-2)
        amax = np.max(np.abs(delta), axis=(0, -2), keepdims=True)[0]
        # exact-integer fast path (see exchange_histograms): integral
        # channels that fit qmax ship verbatim on the unit grid
        frac = np.max(np.abs(delta - np.round(delta)), axis=(0, -2),
                      keepdims=True)[0]
        exact = (frac == 0) & (amax <= qmax)
        denom = np.where(exact, np.float32(qmax),
                         np.maximum(amax, np.float32(1e-30)))
        q = np.clip(np.round(delta / denom * qmax),
                    -qmax, qmax).astype(npdt)
        stat = np.concatenate([amax, frac], axis=-2)
        for s in range(world):
            _note_collective("hist_exchange", q[s])
            _note_collective("hist_exchange_scale", stat)
        qsum = np.sum(q.astype(np.int32), axis=0)
        deq = qsum.astype(np.float32) * (denom / np.float32(qmax))
        return np.cumsum(deq, axis=-2, dtype=np.float32)

    return _watchdog.run_with_deadline(
        _exchange, _watchdog.deadline("collective"),
        phase="host_collective", seam="collectives.hist_exchange")


class ExternalCollectives(HostCollectives):
    """User-injected reduce-scatter/allgather callables — the direct
    analog of LGBM_NetworkInitWithFunctions (reference c_api.h:760-762,
    network.h:96).  Callables receive and return numpy arrays; used by
    embedders that bring their own transport."""

    def __init__(self, num_machines: int, rank: int,
                 reduce_scatter_fn: Optional[Callable] = None,
                 allgather_fn: Optional[Callable] = None):
        super().__init__(shards=num_machines)
        self.external_rank = rank
        self.reduce_scatter_fn = reduce_scatter_fn
        self.allgather_fn = allgather_fn

    def simulate_reduce_scatter(self, per_shard_arrays, axis: int = 0):
        if self.reduce_scatter_fn is None:
            return super().simulate_reduce_scatter(per_shard_arrays, axis)
        return self.reduce_scatter_fn(per_shard_arrays)

    def simulate_allgather(self, per_shard_arrays, axis: int = 0):
        if self.allgather_fn is None:
            return super().simulate_allgather(per_shard_arrays, axis)
        return self.allgather_fn(per_shard_arrays)


# ---------------------------------------------------------------------------
# Compiled-program collective accounting: the sharding-implicit
# collectives (the SPMD partitioner inserts them — nothing in Python
# calls an op) are read back from the compiled module text.  This is
# the per-collective byte signal the MULTICHIP gate asserts
# (__graft_entry__) and a telemetric run exports (the "largest reduce
# 220320 B, 3 collectives/step" numbers as counters, not prose).
# ---------------------------------------------------------------------------
_HLO_COLLECTIVE_RE = re.compile(
    r"= .*?\s(all-reduce|reduce-scatter|all-gather|all-to-all|"
    r"collective-permute)(-start)?\(")
_HLO_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_HLO_ITEMSIZE = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4,
                 "u32": 4, "bf16": 2, "f16": 2, "s16": 2, "u16": 2,
                 "s8": 1, "u8": 1, "pred": 1}
_REDUCE_KINDS = ("all-reduce", "reduce-scatter")


def scan_compiled_collectives(compiled_text: str) -> Dict:
    """Parse a compiled HLO module's collective ops into per-kind
    byte/count totals.  Tuple-shaped ops (XLA's collective combiner
    emits ``(f32[378], f32[8192]) all-reduce(...)``) account every
    member shape.  Returns ``{"kinds": {kind: {"count", "bytes"}},
    "ops": [(kind, total_bytes, worst_dim)], "largest_reduce_bytes",
    "reduce_count"}``."""
    kinds: Dict[str, Dict[str, int]] = {}
    ops: List[Tuple[str, int, int]] = []
    reduce_sizes: List[int] = []
    for ln in compiled_text.splitlines():
        m = _HLO_COLLECTIVE_RE.search(ln)
        if not m:
            continue
        kind = m.group(1)
        total = 0
        worst_dim = 0
        for dt, dims in _HLO_SHAPE_RE.findall(ln[:m.start(1)]):
            dvals = [int(d) for d in dims.split(",") if d]
            n = 1
            for d in dvals:
                n *= d
            total += n * _HLO_ITEMSIZE.get(dt, 4)
            worst_dim = max(worst_dim, max(dvals or [0]))
        k = kinds.setdefault(kind, {"count": 0, "bytes": 0})
        k["count"] += 1
        k["bytes"] += total
        ops.append((kind, total, worst_dim))
        if kind in _REDUCE_KINDS:
            reduce_sizes.append(total)
    return {
        "kinds": kinds,
        "ops": ops,
        "largest_reduce_bytes": max(reduce_sizes, default=0),
        "reduce_count": len(reduce_sizes),
    }


def record_compiled_collectives(compiled_text: str,
                                program: str = "step") -> Dict:
    """Scan a compiled module's collectives AND publish them as
    telemetry counters/gauges (no-op at ``telemetry=off``):
    ``hlo_collective_<kind>_count`` / ``hlo_collective_<kind>_bytes``
    per kind, the ``collective_largest_reduce_bytes`` /
    ``collective_reduce_count`` gauges, and a
    ``collective_profile.<program>`` string gauge naming the program
    scanned.  Returns the scan dict."""
    stats = scan_compiled_collectives(compiled_text)
    if TELEMETRY.on:
        for kind, k in sorted(stats["kinds"].items()):
            name = kind.replace("-", "_")
            TELEMETRY.add(f"hlo_collective_{name}_count", k["count"])
            TELEMETRY.add(f"hlo_collective_{name}_bytes", k["bytes"])
        TELEMETRY.gauge("collective_largest_reduce_bytes",
                        stats["largest_reduce_bytes"])
        TELEMETRY.gauge("collective_reduce_count",
                        stats["reduce_count"])
        TELEMETRY.gauge(f"collective_profile.{program}",
                        "+".join(f"{k}:{v['count']}x"
                                 for k, v in
                                 sorted(stats["kinds"].items()))
                        or "none")
    return stats


_external: Optional[ExternalCollectives] = None


def install_external(num_machines: int, rank: int,
                     reduce_scatter_fn: Optional[Callable] = None,
                     allgather_fn: Optional[Callable] = None) -> None:
    """Install a process-global external backend (the
    LGBM_NetworkInitWithFunctions seam, exposed via capi.py)."""
    global _external
    _external = ExternalCollectives(num_machines, rank,
                                    reduce_scatter_fn, allgather_fn)


def external() -> Optional[ExternalCollectives]:
    return _external
