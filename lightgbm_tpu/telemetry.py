"""Runtime telemetry: structured spans, counters, device-time split,
retrace watch, Perfetto + newline-JSON export.

Every roofline decision so far (the r7 chunk-slope fit, the r8 serving
bucket policy, the r6 leaf-partition rejection) was made from one-off
instrumentation private to ``bench.py`` — invisible to a real training
or serving run.  This module is the one code path both worlds share:
``bench.py`` reads its counters for the host-dispatch / device-wait
split, and a production process gets the same attribution in-process
via ``telemetry=counters|spans``.

Design constraints (pinned by ``tests/test_telemetry.py``):

- **One program at every mode.**  All instrumentation lives at host
  seams (dispatch boundaries, trace-time Python), and every mode
  lowers byte-identical StableHLO (``test_off_mode_hlo_identity``).
  ``phase`` scopes (``jax.named_scope("tel.<phase>")``) are ALWAYS
  entered, ``off`` included: a named scope writes op-location
  METADATA only, which ``as_text()`` does not print and which costs
  nothing at dispatch — so a profiler trace of a run at any mode
  attributes device ops to the program's own phases.
- **Zero dependencies.**  Stdlib only; jax is imported lazily and only
  for the optional device fence / named-scope / live-array features.
- **Thread-safe.**  Span stacks are thread-local; counters, gauges and
  the event log are guarded by one lock.  Serving handlers may call
  ``predict`` from many threads into one global registry.

Modes (``Config.telemetry``):

- ``off``       — nothing recorded (the retrace sentinel still counts:
                  it is a runtime guard, not telemetry — see
                  ``note_trace``).
- ``counters``  — named counters/gauges only; no fencing, so the
                  device pipeline is untouched (``device_wait_ms``
                  stays empty unless a fence is explicitly enabled,
                  as ``bench.py`` does).
                  Spans and stages also enter a
                  ``jax.profiler.TraceAnnotation("ltpu.<name>")``, so
                  an active profiler session shows them in its host
                  plane, on the device trace's clock (a flag test when
                  no session is active).
- ``spans``     — counters + nested timing spans recorded in memory
                  + a per-dispatch ``jax.block_until_ready`` fence
                  attributing wall time to host dispatch vs device
                  wait.  The fence is host-side only (no program
                  change) but serializes chunk overlap — a documented
                  observer effect.
- ``trace``     — accepted alias of ``spans`` (it once gated the
                  ``phase`` scopes, which are now always on).

Export (``Config.telemetry_out`` = path prefix): ``<prefix>.jsonl``
(newline-JSON span events + one final snapshot line) and
``<prefix>.perfetto.json`` (Chrome ``trace_event`` format — load in
``ui.perfetto.dev``).  See docs/OBSERVABILITY.md for the span map and
counter glossary.  Since round 11 the ``binning`` span decomposes into
``parse``/``fit_mappers``/``bin``/``pack`` sub-spans (with
``construct_rows_per_s`` / ``construct_stream_rows_per_s`` gauges) —
in a streaming load the ``parse`` spans live on the producer thread
and visibly overlap the consumer's ``bin`` spans in the Perfetto
view, which is exactly the pipelining the round-11 construct bench
series tracks.

Round-13 distributed/production surface (docs/OBSERVABILITY.md):

- **Histograms** (``observe``): fixed log-spaced-bucket latency/depth
  histograms (Prometheus ``le`` semantics) so any scraper can derive
  p50/p95/p99 without the process keeping raw samples.
- **Prometheus export** (``to_prometheus``/``write_prom``/
  ``serve_metrics``): stdlib-only text-format writer — a node-exporter
  style textfile (``Config.telemetry_prom_out``) and an optional
  ``/metrics`` + ``/healthz`` HTTP endpoint
  (``Config.telemetry_http_port``).
- **Cross-host trace shards** (``export`` tags every file with
  ``(host_id, run_id)`` and a rendezvous clock-sync mark) merged by
  ``python -m lightgbm_tpu.telemetry merge`` into ONE Perfetto
  timeline with one track lane per host.
- **Crash flight recorder** (``flight``): a bounded ring of recent
  span/counter/log events, dumped to a timestamped JSON by the
  reliability layer on injected faults, retry exhaustion, OOM
  downshift or unhandled exception
  (``Config.flight_recorder_out``).
"""
from __future__ import annotations

import atexit
import bisect
import collections
import contextlib
import contextvars
import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

from .utils.log import Log
from .utils import log as _log_mod

MODES = ("off", "counters", "spans", "trace")     # "trace" == "spans"
_OFF, _COUNTERS, _SPANS = range(3)

# hard bound on retained span events: a week-long serving process must
# not grow its heap linearly in requests.  Overflow increments the
# ``events_dropped`` counter instead of silently truncating.
MAX_EVENTS = 500_000

# log-spaced histogram bucket spec (docs/OBSERVABILITY.md): upper
# bounds 0.05ms * 2^i for i in 0..20 (~0.05 ms .. ~52 s) + an implicit
# +Inf overflow bucket.  Fixed power-of-two spacing means every host
# and every process bins identically, so shard histograms are
# mergeable by bucket-wise addition and any scraper can derive
# p50/p95/p99 from the cumulative counts.
LATENCY_BOUNDS_MS = tuple(0.05 * (1 << i) for i in range(21))
# small-integer bound spec for depth/occupancy histograms
DEPTH_BOUNDS = (1.0, 2.0, 4.0, 8.0, 16.0, 32.0)
# fraction bound spec (0..1]: batch fill ratio of the serving
# micro-batcher (real rows / bucket rows of one coalesced dispatch)
RATIO_BOUNDS = (0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0)
# power-of-two row-count bounds for coalesced-batch-size histograms
# (mirrors the serving predictor's bucket ladder)
BATCH_BOUNDS = tuple(float(1 << i) for i in range(13))  # 1 .. 4096

# prometheus metric name prefix (docs/OBSERVABILITY.md name mapping:
# counter `x` -> `ltpu_x_total`, gauge `x` -> `ltpu_x`, histogram `x`
# -> `ltpu_x_bucket{le=...}` / `ltpu_x_sum` / `ltpu_x_count`)
PROM_PREFIX = "ltpu_"

# flight-recorder ring capacity (events, not bytes): the last-N
# span/counter/log events correlated with a fault
FLIGHT_EVENTS = 512

# fleet event journal ring capacity: the last-N state transitions
# (membership epochs, fault firings, stalls, publishes...).  Bounded
# like the flight ring; eviction counts into ``journal.dropped``
JOURNAL_EVENTS = 4096

# HTTP header carrying the trace context across the serving edge:
# value is ``<trace_id>-<span_id>`` (lowercase hex, 32 + 16 chars in
# the W3C traceparent id widths).  Accepted on ``POST /predict`` and
# echoed on every response (docs/OBSERVABILITY.md, Tracing)
TRACE_HEADER = "X-Ltpu-Trace"

# the active causal trace context: ``(trace_id, span_id)`` hex pair or
# None.  A contextvar propagates per-thread and survives the handler's
# call stack without threading arguments through every layer; the
# micro-batcher snapshots it at submit so a coalesced dispatch on the
# dispatcher thread still links back to each member request's span.
_TRACE_CTX: "contextvars.ContextVar" = contextvars.ContextVar(
    "ltpu_trace", default=None)


def new_trace_id() -> str:
    """Fresh 128-bit trace id (32 lowercase hex chars)."""
    return os.urandom(16).hex()


def new_span_id() -> str:
    """Fresh 64-bit span id (16 lowercase hex chars)."""
    return os.urandom(8).hex()


def current_trace():
    """The active ``(trace_id, span_id)`` pair, or None."""
    return _TRACE_CTX.get()


def set_trace(trace_id: str, span_id: Optional[str] = None):
    """Install a trace context on the current thread/context; returns
    the reset token for :func:`clear_trace` (always pair them — a
    leaked context would mis-attribute unrelated later work)."""
    return _TRACE_CTX.set((str(trace_id),
                           str(span_id) if span_id else new_span_id()))


def clear_trace(token) -> None:
    _TRACE_CTX.reset(token)


def parse_trace_header(value) -> Optional[tuple]:
    """Parse an ``X-Ltpu-Trace: <trace>-<span>`` header value into a
    ``(trace_id, span_id)`` pair; None on anything malformed (a bad
    client header must degrade to an untraced request, never a 500).
    Lenient on width — any 8..32 / 4..16 hex pair is accepted."""
    if not value:
        return None
    parts = str(value).strip().lower().split("-")
    if len(parts) != 2:
        return None
    trace, span = parts
    if not (8 <= len(trace) <= 32 and 4 <= len(span) <= 16):
        return None
    try:
        int(trace, 16)
        int(span, 16)
    except ValueError:
        return None
    return trace, span


def format_trace_header(ctx=None) -> str:
    """Render a ``(trace_id, span_id)`` pair (default: the active
    context) as the header value; empty string when untraced."""
    if ctx is None:
        ctx = _TRACE_CTX.get()
    if ctx is None:
        return ""
    return f"{ctx[0]}-{ctx[1]}"


class _Hist:
    """Fixed-bucket histogram, Prometheus ``le`` semantics: bucket i
    counts observations <= bounds[i]; the trailing slot is +Inf."""

    __slots__ = ("bounds", "counts", "total", "count")

    def __init__(self, bounds):
        self.bounds = tuple(float(b) for b in bounds)
        self.counts = [0] * (len(self.bounds) + 1)
        self.total = 0.0
        self.count = 0

    def observe(self, value: float) -> None:
        # bisect_left: a value exactly on a bound lands in that
        # bound's bucket (<= semantics)
        self.counts[bisect.bisect_left(self.bounds, value)] += 1
        self.total += value
        self.count += 1

    def observe_many(self, values) -> None:
        """Vectorized bulk observe (numpy): one searchsorted over the
        batch instead of a Python-level bisect per sample — the
        serving-side quality monitors feed whole sampled batches
        through their per-model score histograms this way.
        ``side="left"`` matches ``bisect_left`` exactly, so a value on
        a bound lands in the same bucket either route."""
        import numpy as np
        v = np.asarray(values, dtype=np.float64).reshape(-1)
        if v.size == 0:
            return
        idx = np.searchsorted(np.asarray(self.bounds), v, side="left")
        for i, c in zip(*np.unique(idx, return_counts=True)):
            self.counts[int(i)] += int(c)
        self.total += float(v.sum())
        self.count += int(v.size)

    def to_dict(self) -> Dict[str, Any]:
        return {"bounds": list(self.bounds), "counts": list(self.counts),
                "sum": round(self.total, 6), "count": self.count}


# public name for the fixed-bucket histogram container: the quality
# monitors (lightgbm_tpu/quality/) build per-model score histograms
# over PROFILE-derived bounds with the same le-semantics machinery the
# latency histograms use, so their counts merge/compare bucket-wise
Hist = _Hist


def hist_quantile(h: Dict[str, Any], q: float) -> float:
    """Quantile from a histogram dict (``snapshot()["histograms"]``
    entry): the upper bound of the bucket where the cumulative count
    first reaches ``q * count`` (conservative — the true quantile is
    <= the returned bound; +Inf for the overflow bucket).  A scraper
    reads the SAME cumulative ``_bucket`` series, so it lands in the
    same bucket; note PromQL's ``histogram_quantile`` additionally
    interpolates linearly WITHIN that bucket, so its estimate can sit
    below this bound by up to one bucket width (a factor-2 spacing
    here)."""
    total = h["count"]
    if total <= 0:
        return 0.0
    target = q * total
    cum = 0
    for i, c in enumerate(h["counts"]):
        cum += c
        if cum >= target:
            bounds = h["bounds"]
            return float(bounds[i]) if i < len(bounds) else float("inf")
    return float("inf")  # pragma: no cover - cum always reaches total


def _prom_name(name: str) -> str:
    out = "".join(c if (c.isalnum() or c == "_") else "_"
                  for c in str(name))
    if out and out[0].isdigit():
        out = "_" + out
    return PROM_PREFIX + out


def _fmt_val(v: float) -> str:
    """Full-precision sample rendering: '%g' would truncate to 6
    significant digits, silently flattening large byte/row counters
    (a 12,345,678-row counter scraping as 1.23457e+07 makes
    scrape-to-scrape rate() read zero then jump)."""
    f = float(v)
    if f.is_integer() and abs(f) < 2 ** 63:
        return str(int(f))
    return repr(f)


def _fmt_le(bound: float) -> str:
    """Prometheus le label: integral bounds print bare, others with
    enough digits to round-trip."""
    if bound == float("inf"):
        return "+Inf"
    if float(bound).is_integer():
        return str(int(bound))
    return repr(float(bound))


class FlightRecorder:
    """Bounded ring of recent telemetry/log events + the dump that
    correlates them with the fault seam that fired (the crash flight
    recorder, docs/OBSERVABILITY.md).  Disarmed (the default) every
    hook is one attribute check; arming (``Config.flight_recorder_out``)
    starts recording and installs an unhandled-exception dump hook."""

    def __init__(self, maxlen: int = FLIGHT_EVENTS):
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(maxlen=maxlen)
        self.out = ""
        self.dumps: List[str] = []
        self._hook_installed = False

    @property
    def armed(self) -> bool:
        return bool(self.out)

    def arm(self, out_prefix: str) -> "FlightRecorder":
        self.out = str(out_prefix)
        if not self._hook_installed:
            self._hook_installed = True
            _log_mod.set_sink(self._log_sink)
            import sys
            prev = sys.excepthook

            def _hook(exc_type, exc, tb):  # pragma: no cover - crash path
                try:
                    self.dump(f"unhandled:{exc_type.__name__}",
                              detail=str(exc)[:500])
                except Exception:
                    pass
                prev(exc_type, exc, tb)
            sys.excepthook = _hook
        return self

    def disarm(self) -> None:
        with self._lock:
            self.out = ""
            self._ring.clear()
            self.dumps = []

    def _log_sink(self, tag: str, msg: str) -> None:
        self.note("log", tag, msg=msg[:300])

    def note(self, kind: str, name: str, **detail) -> None:
        if not self.out:
            return
        with self._lock:
            self._ring.append((time.time(), kind, name,  # lint: disable=TRC001(flight-recorder wall-clock stamp: host observability only, never read by traced code)
                               detail or None))

    def events(self) -> List[dict]:
        with self._lock:
            ring = list(self._ring)
        return [{"ts_unix": round(ts, 6), "kind": kind, "name": name,
                 **({"detail": det} if det else {})}
                for ts, kind, name, det in ring]

    def dump(self, reason: str, seam: str = "", **extra) -> Optional[str]:
        """Write the flight dump (timestamped JSON next to ``out``);
        returns the path, or None when disarmed."""
        if not self.out:
            return None
        tm = TELEMETRY
        ns = time.time_ns()
        payload = {
            "reason": reason,
            "seam": seam,
            "unix_ts": ns / 1e9,
            "run_id": tm.run_id,
            "host_id": tm.host(),
            "pid": os.getpid(),
            "events": self.events(),
            "counters": tm.counters(),
            "gauges": tm.gauges(),
            "retraces": tm.retraces(),
        }
        if extra:
            payload.update(extra)
        path = f"{self.out}-{ns}.flight.json"
        try:
            d = os.path.dirname(os.path.abspath(path))
            if d:
                os.makedirs(d, exist_ok=True)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(payload, f, indent=1)
            os.replace(tmp, path)
        except OSError as e:  # pragma: no cover - fs-dependent
            Log.warning(f"flight recorder dump failed: {e}")
            return None
        self.dumps.append(path)
        Log.warning(f"flight recorder: {reason}"
                    + (f" at seam {seam}" if seam else "")
                    + f" — dumped {path}")
        return path


class EventJournal:
    """Bounded, monotonically-sequenced, host-tagged fleet event
    journal (docs/OBSERVABILITY.md, event journal): the state
    transitions that used to exist only as warn-logs — membership
    epoch changes, degraded exclusions, chaos fault firings, watchdog
    stalls, OOM downshifts, publish/rollback/quarantine, drift→refit
    flips — recorded as structured events each carrying the active
    trace context.  Exported beside the span shards as
    ``<prefix>.events.jsonl`` (same clock-sync alignment), queryable
    via ``python -m lightgbm_tpu.telemetry events``, and rendered by
    the merge tool as Perfetto instant events.

    Off-mode cost is one attribute check in :meth:`emit`; the ring is
    bounded so a week-long process cannot grow its heap in events."""

    def __init__(self, tm: "Telemetry", maxlen: int = JOURNAL_EVENTS):
        self._tm = tm
        self._lock = threading.Lock()
        self._ring: collections.deque = collections.deque(maxlen=maxlen)
        self._seq = 0
        self.dropped = 0

    def emit(self, kind: str, seam: str = "", **fields) -> None:
        """Record one state-transition event.  No-op at ``off``;
        ``seam`` names the subsystem seam (fault-seam grammar where
        one exists); extra keyword fields are kept verbatim.  The
        active trace context is captured so a cross-host cause (the
        request, the round) stays attached to its effect."""
        tm = self._tm
        if tm.mode < _COUNTERS:
            return
        ctx = _TRACE_CTX.get()
        ts = time.perf_counter() - tm._t0
        with self._lock:
            if len(self._ring) == self._ring.maxlen:
                self.dropped += 1
            self._seq += 1
            self._ring.append((self._seq, ts, kind, seam, ctx,
                               fields or None))
        tm.add("journal_events", 1)
        if tm.flight.out:
            detail = dict(fields) if fields else {}
            if seam:
                detail["seam"] = seam
            tm.flight.note("journal", kind, **detail)

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._seq = 0
            self.dropped = 0

    def events(self) -> List[dict]:
        """The retained events as export-ready dicts (``ts_us`` is
        relative to the telemetry clock origin, same timeline as the
        span export)."""
        host = self._tm.host()
        with self._lock:
            ring = list(self._ring)
        out = []
        for seq, ts, kind, seam, ctx, fields in ring:
            ev = {"type": "event", "seq": seq,
                  "ts_us": round(ts * 1e6, 1),
                  "host_id": host, "kind": kind}
            if seam:
                ev["seam"] = seam
            if ctx is not None:
                ev["trace"], ev["span"] = ctx
            if fields:
                ev["fields"] = fields
            out.append(ev)
        return out


class _NullCtx:
    """Shared no-op context for disabled spans/phases."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _NullCtx()


def _annotation(name: str, attrs):
    """The span as a ``jax.profiler.TraceAnnotation("ltpu.<name>")``:
    a host event of an active profiler session, on the device trace's
    clock.  A process that never imported jax has no such session (and
    a pure-host tool must not import it for this), so it gets the
    no-op context."""
    jax = sys.modules.get("jax")
    if jax is None:
        return _NULL
    return jax.profiler.TraceAnnotation("ltpu." + name, **(attrs or {}))


class _Span:
    __slots__ = ("_tm", "name", "attrs", "t0", "_depth", "_ann")

    def __init__(self, tm: "Telemetry", name: str, attrs):
        self._tm = tm
        self.name = name
        self.attrs = attrs

    def __enter__(self):
        stack = self._tm._stack()
        self._depth = len(stack)
        stack.append(self)
        self._ann = _annotation(self.name, self.attrs)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self.t0
        self._ann.__exit__(*exc)
        stack = self._tm._stack()
        # reentrancy guard: pop OUR frame even if an inner span leaked
        while stack and stack[-1] is not self:
            stack.pop()
        if stack:
            stack.pop()
        self._tm._record(self.name, self.t0, dur, self._depth, self.attrs)
        return False


def read_rss_mb() -> Optional[float]:
    """The resident set in MiB, a /proc read (None without /proc)."""
    try:
        with open("/proc/self/status") as f:
            for ln in f:
                if ln.startswith("VmRSS:"):
                    return round(int(ln.split()[1]) / 1024, 1)
    except (OSError, ValueError, IndexError):
        pass
    return None


# the package's import, clocked by lightgbm_tpu/__init__.py whatever the
# mode (telemetry is ``off`` while it runs): the first ``configure``
# that turns counters on publishes it as the stages ``import`` and
# ``import_sklearn`` (``Telemetry._publish_import``)
_IMPORT: Dict[str, float] = {}


def note_import(t0: float, t1: float, rss_before: Optional[float],
                sklearn_s: float) -> None:
    """``lightgbm_tpu/__init__.py``'s first and last line on the
    ``perf_counter`` clock, the resident set at each, and the seconds
    its ``from .sklearn import`` took."""
    _IMPORT.update(ms=(t1 - t0) * 1e3, sklearn_ms=sklearn_s * 1e3,
                   rss_before=rss_before, rss_after=read_rss_mb())


class _Stage:
    """An open stage on its thread's stack (``Telemetry.stage``)."""
    __slots__ = ("parent", "split", "t0", "nested_ms", "wall_ms")

    def __init__(self, parent, compiles, since):
        self.parent = parent
        self.t0 = time.perf_counter() if since is None else since
        self.split = _CompileSplit(compiles, self.t0) if compiles else None
        self.nested_ms = 0.0
        self.wall_ms = 0.0

    @staticmethod
    def pop(stack, frame) -> None:
        """Take ``frame`` off ``stack``, and with it whatever an inner
        stage that never closed left above it."""
        while stack and stack.pop() is not frame:
            pass


class _CompileSplit:
    """What jax.monitoring reports while a ``stage(compiles=prefix)``
    is open, as stages told after the fact.  jax reports the jits
    nested in a program too, each before the one that holds it, and a
    trace can compile (an eager op on a constant): a duration (of
    ``_MIN_STEP_S`` or more: the listener drops the rest) counts less
    what was already booked inside it, whatever its kind, so the parts
    never add up to more than the stage's wall."""
    __slots__ = ("prefix", "t0", "booked")

    def __init__(self, prefix, t0):
        self.prefix = prefix
        self.t0 = t0            # the stage's start: nothing is older
        self.booked = []        # disjoint (start, end), in order of end

    def told(self, kind: str, secs: float) -> None:
        end = time.perf_counter()
        start = max(end - secs, self.t0)
        inside = 0.0
        while self.booked and self.booked[-1][0] >= start:
            s, e = self.booked.pop()
            inside += e - s
        self.booked.append((start, end))
        TELEMETRY.stage_told(f"{self.prefix}_{kind}",
                             max(0.0, end - start - inside) * 1e3)


class Telemetry:
    """Process-global telemetry registry (module singleton
    ``TELEMETRY``).  All methods are cheap no-ops at ``off``."""

    def __init__(self):
        self._lock = threading.RLock()
        self._tls = threading.local()
        self._t0 = time.perf_counter()
        # wall-clock anchor for t0: lets the merge tool (and humans)
        # place the relative timestamps on an absolute timeline
        self._t0_unix = time.time()
        self.mode = _OFF
        self.out = ""
        self.prom_out = ""
        self.retrace_warn = 8
        self._fence = False
        self._fence_suspended = 0
        self._counters: Dict[str, float] = {}
        self._gauges: Dict[str, Any] = {}
        self._hists: Dict[str, _Hist] = {}
        self._events: list = []          # (name, t0, dur, tid, depth, attrs)
        self._traces: Dict[str, set] = {}
        self._retrace_warned: set = set()
        self._atexit_armed = False
        self._import_published = False
        # cross-host identity: host_id resolves lazily (env override
        # LTPU_HOST_ID, else jax.process_index() IF jax is already
        # imported — a pure-host tool must not boot a backend);
        # run_id is stamped at first configure
        self.host_id: Optional[int] = None
        self.run_id = ""
        self._sync: Optional[tuple] = None   # (name, rel_ts_s)
        self.flight = FlightRecorder()
        self.journal = EventJournal(self)
        self._http = None
        # HTTP route table for the shared scrape/serving listener:
        # {path or prefix-ending-in-/: fn(method, path, body, headers)
        # -> (status, content_type, body_bytes, extra_headers|None)}.
        # serve_metrics installs /metrics and /healthz; the serving
        # frontend mounts /predict/ and /models on the SAME server
        self._http_routes: Dict[str, Any] = {}

    # -- configuration -------------------------------------------------
    def configure(self, mode: str = "counters", out: str = "",
                  fence: Optional[bool] = None,
                  retrace_warn: Optional[int] = None) -> "Telemetry":
        """Set the global mode (``trace`` is ``spans``).  ``fence=None``
        resolves to the mode default (on for spans, off for counters).
        ``out`` arms an atexit export to ``<out>.jsonl`` /
        ``<out>.perfetto.json``."""
        if mode not in MODES:
            raise ValueError(f"telemetry mode must be one of {MODES}, "
                             f"got {mode!r}")
        with self._lock:
            self.mode = min(MODES.index(mode), _SPANS)
            if self.mode >= _COUNTERS and _IMPORT \
                    and not self._import_published:
                self._publish_import()
            if not self.run_id:
                import uuid
                self.run_id = uuid.uuid4().hex[:12]
            self._fence = (self.mode >= _SPANS) if fence is None \
                else bool(fence)
            if retrace_warn is not None:
                self.retrace_warn = max(1, int(retrace_warn))
            if out:
                self.out = out
                if not self._atexit_armed:
                    self._atexit_armed = True
                    atexit.register(self._export_atexit)
        return self

    def _publish_import(self) -> None:
        """The package's import as the stages ``import`` (own time) and
        ``import_sklearn`` (nested in it): once per process, since the
        import ran once."""
        self._import_published = True
        imp = _IMPORT
        self.add("setup_import_ms", imp["ms"] - imp["sklearn_ms"])
        self.add("setup_import_sklearn_ms", imp["sklearn_ms"])
        for when in ("before", "after"):
            if imp[f"rss_{when}"] is not None:
                self.gauge(f"rss_mb_{when}_import", imp[f"rss_{when}"])

    def reset(self) -> None:
        """Clear recorded state (events, counters, gauges, retrace
        watch); the configured mode/out/fence survive."""
        with self._lock:
            self._counters.clear()
            self._gauges.clear()
            self._hists.clear()
            self._events = []
            self._traces.clear()
            self._retrace_warned.clear()
            self._sync = None
            self._t0 = time.perf_counter()
            self._t0_unix = time.time()
        self.journal.clear()

    @property
    def on(self) -> bool:
        return self.mode >= _COUNTERS

    @property
    def spans_on(self) -> bool:
        return self.mode >= _SPANS

    @property
    def level(self) -> str:
        return MODES[self.mode]

    # -- cross-host identity -------------------------------------------
    @staticmethod
    def _distributed_state():
        """jax's multi-process rendezvous state WITHOUT booting a
        backend: ``jax.process_index()`` would initialize XLA (fatal
        before ``jax.distributed.initialize``, and a /metrics scrape
        can land in that window), so read the distributed global state
        directly.  Returns (process_id, num_processes, initialized)."""
        import sys
        if "jax" not in sys.modules:
            return 0, 1, False
        # private module: jax has no public way to read the process id
        # without booting a backend.  No handler — if a jax upgrade
        # moves it, this must fail loudly, not tag every shard host 0
        from jax._src import distributed as _dist
        st = _dist.global_state
        return (int(st.process_id or 0), int(st.num_processes or 1),
                st.client is not None)

    def host(self) -> int:
        """This process's host id for trace-shard tagging:
        ``LTPU_HOST_ID`` env override (tests, external launchers), else
        the ``jax.distributed`` process id.  The id is only CACHED once
        it is authoritative (env override, or the rendezvous client
        exists) — a pre-rendezvous call must not latch host 0 onto
        every process of a fleet that has not initialized yet."""
        if self.host_id is not None:
            return self.host_id
        env = os.environ.get("LTPU_HOST_ID")
        if env is not None:
            self.host_id = int(env)
            return self.host_id
        pid, _n, initialized = self._distributed_state()
        if initialized:
            self.host_id = pid
            return self.host_id
        return pid  # uncached: may resolve differently after rendezvous

    def _n_hosts(self) -> int:
        env = os.environ.get("LTPU_NUM_HOSTS")
        if env is not None:
            return max(1, int(env))
        return max(1, self._distributed_state()[1])

    def mark_sync(self, name: str = "rendezvous") -> None:
        """Record the clock-sync marker the cross-host merge aligns
        shards on: the multi-host rendezvous is a barrier every
        process exits near-simultaneously, so shifting each shard's
        clock to make its marker coincide with host 0's puts all
        hosts on one timeline (docs/OBSERVABILITY.md, trace merge).
        Recorded as a zero-duration event whenever telemetry is on
        (counters mode included — the marker is one event, not a
        span stream)."""
        if self.mode < _COUNTERS:
            return
        ts = time.perf_counter()
        with self._lock:
            self._sync = (name, ts - self._t0)
        self._record(name, ts, 0.0, 0, None)

    # -- spans ---------------------------------------------------------
    def _stack(self):
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def span(self, name: str, **attrs):
        """Nested timing span (context manager).  No-op at ``off``; at
        ``counters`` only the profiler annotation (``_annotation``);
        at ``spans`` also recorded in memory — safe on any hot path."""
        if self.mode < _COUNTERS:
            return _NULL
        if self.mode < _SPANS:
            return _annotation(name, attrs)
        return _Span(self, name, attrs or None)

    def start_span(self, name: str, **attrs):
        """Explicit begin/end form for spans that cannot wrap a lexical
        block (pair with ``end_span(token)`` on the same thread).
        Deliberately does NOT touch the thread-local nesting stack, so
        an exception between start and end cannot corrupt later spans'
        depths; the event is recorded at depth 0 (Perfetto nests by
        time overlap anyway)."""
        if self.mode < _COUNTERS:
            return None
        ann = _annotation(name, attrs)
        ann.__enter__()
        t0 = time.perf_counter() if self.mode >= _SPANS else None
        return (name, t0, attrs or None, ann)

    def end_span(self, token) -> None:
        if token is None:
            return
        name, t0, attrs, ann = token
        ann.__exit__(None, None, None)
        if t0 is not None:
            self._record(name, t0, time.perf_counter() - t0, 0, attrs)

    def _stage_stack(self) -> list:
        st = getattr(self._tls, "stages", None)
        if st is None:
            st = self._tls.stages = []
        return st

    @contextlib.contextmanager
    def stage(self, name: str, compiles: str = "",
              since: Optional[float] = None, **attrs):
        """A set-up stage that runs once per job (binning, upload, the
        grower's constructor): the span ``name``, plus — the part a
        benchmark at ``counters`` mode can read — its OWN wall time
        (less the stages nested in it, so the stages of a job add up)
        in counter ``setup_<name>_ms`` and the resident set around it
        in gauges ``rss_mb_before_<name>`` / ``rss_mb_after_<name>``
        (a /proc read each, which also raises ``rss_mb_peak``).
        Yields the open stage (``wall_ms`` is set when it closes);
        nothing at ``off``, where it yields None.

        ``compiles="<prefix>"``: the stage wraps a dispatch that builds
        a program, and what ``jax.monitoring`` reports of it on this
        thread while the stage is open — trace, lowering, backend
        compile or the cache's load — becomes the nested stages
        ``<prefix>_trace`` / ``_lower`` / ``_compile``
        (``watch_compile_cache`` registers the listener), so the
        stage's own time is the rest: the enqueue.

        ``since``: a ``perf_counter`` reading taken earlier on this
        thread, with no stage between it and here; the stage counts
        from there (an entry point whose first lines decide whether
        telemetry is on, a dispatch that learns in its prep that it
        builds a program)."""
        if self.mode < _COUNTERS:
            yield None
            return
        rss = self.sample_memory()
        if rss is not None:
            self.gauge(f"rss_mb_before_{name}", rss)
        stack = self._stage_stack()
        frame = _Stage(stack[-1] if stack else None, compiles, since)
        stack.append(frame)
        try:
            with self.span(name, **attrs):
                yield frame
        finally:
            frame.wall_ms = (time.perf_counter() - frame.t0) * 1e3
            _Stage.pop(stack, frame)
            self._stage_done(frame.parent, name, frame.wall_ms,
                             frame.nested_ms)
            rss = self.sample_memory()
            if rss is not None:
                self.gauge(f"rss_mb_after_{name}", rss)

    def _stage_done(self, parent, name: str, wall_ms: float,
                    nested_ms: float = 0.0) -> None:
        """Book a closed stage: its own time to its counter, its wall
        to the stage it ran in.  Own time is floored at 0: stages that
        worker threads ran side by side under one waiting stage can
        hand it more than its wall."""
        with self._lock:
            if parent is not None:
                parent.nested_ms += wall_ms
        self.add(f"setup_{name}_ms", max(0.0, wall_ms - nested_ms))

    def stage_told(self, name: str, ms: float) -> None:
        """A stage whose duration is known only after the fact (the
        compile listener's): ``ms`` to ``setup_<name>_ms`` and to the
        stage open on this thread.  No span, no gauges."""
        if self.mode < _COUNTERS:
            return
        stack = self._stage_stack()
        self._stage_done(stack[-1] if stack else None, name, ms)

    def current_stage(self):
        """The innermost stage open on this thread (None at ``off`` or
        outside every stage), to hand to ``stage_of`` on a worker."""
        stack = self._stage_stack()
        return stack[-1] if stack else None

    @contextlib.contextmanager
    def stage_of(self, frame):
        """On a worker thread: run under ``frame``, a stage open on the
        thread that waits for this one (``current_stage()`` there), so
        that the stages the worker runs hand their time to it and its
        own time does not count them twice."""
        if frame is None:
            yield
            return
        stack = self._stage_stack()
        stack.append(frame)
        try:
            yield
        finally:
            _Stage.pop(stack, frame)

    def stage_fence(self, x) -> None:
        """The closing fence of a device stage (``upload``, ``binsT``):
        ``block_until_ready`` of what the stage placed, made only with
        telemetry on so that the stage ends when the device has the
        data.  The wait is part of the stage's own time; counter
        ``setup_fence_ms`` says how much of the stages' time it is."""
        if self.mode < _COUNTERS:
            return
        import jax
        t0 = time.perf_counter()
        jax.block_until_ready(x)
        self.add("setup_fence_ms", (time.perf_counter() - t0) * 1e3)

    def _record(self, name, t0, dur, depth, attrs):
        if self.flight.out:
            self.flight.note("span", name, dur_ms=round(dur * 1e3, 3))
        with self._lock:
            if len(self._events) >= MAX_EVENTS:
                self._counters["events_dropped"] = \
                    self._counters.get("events_dropped", 0) + 1
                return
            self._events.append((name, t0 - self._t0, dur,
                                 threading.get_ident(), depth, attrs))

    def instant(self, name: str, **attrs) -> None:
        """Zero-duration marker event (spans mode and above)."""
        if self.mode >= _SPANS:
            self._record(name, time.perf_counter(), 0.0,
                         len(self._stack()), attrs or None)

    # -- counters / gauges ---------------------------------------------
    def add(self, name: str, value: float = 1) -> None:
        if self.mode < _COUNTERS:
            return
        if self.flight.out:
            self.flight.note("counter", name, add=value)
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + value

    def gauge(self, name: str, value) -> None:
        if self.mode < _COUNTERS:
            return
        with self._lock:
            self._gauges[name] = value

    def gauge_max(self, name: str, value: float) -> None:
        if self.mode < _COUNTERS:
            return
        with self._lock:
            cur = self._gauges.get(name)
            if cur is None or value > cur:
                self._gauges[name] = value

    def counters(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._counters)

    def gauges(self) -> Dict[str, Any]:
        with self._lock:
            return dict(self._gauges)

    # -- histograms ----------------------------------------------------
    def observe(self, name: str, value: float, bounds=None) -> None:
        """Record ``value`` into the fixed-bucket histogram ``name``
        (created on first observe; default bounds LATENCY_BOUNDS_MS).
        Active from ``counters`` mode — one lock + one bisect, cheap
        enough for the serving hot path."""
        if self.mode < _COUNTERS:
            return
        with self._lock:
            h = self._hists.get(name)
            if h is None:
                h = self._hists[name] = _Hist(bounds or
                                              LATENCY_BOUNDS_MS)
            h.observe(float(value))

    def histograms(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            return {k: h.to_dict() for k, h in self._hists.items()}

    def set_prom_out(self, path: str) -> None:
        """Arm the Prometheus textfile path (written at CLI task end
        and process exit, like ``out``)."""
        with self._lock:
            self.prom_out = str(path)
            if self.prom_out and not self._atexit_armed:
                self._atexit_armed = True
                atexit.register(self._export_atexit)

    # -- device fence --------------------------------------------------
    @property
    def fence_active(self) -> bool:
        """Whether dispatch sites should block_until_ready to split
        host-dispatch from device-wait wall time."""
        return (self.mode >= _COUNTERS and self._fence
                and self._fence_suspended == 0)

    def set_fence(self, on: bool) -> None:
        self._fence = bool(on)

    def suspend_fence(self):
        """Context manager: temporarily disable the device fence —
        ``tune_dispatch_chunk`` times the raw async enqueue and a
        fenced ``train_chunk`` would fold device wall into it."""
        tm = self

        class _Suspend:
            def __enter__(self):
                with tm._lock:
                    tm._fence_suspended += 1

            def __exit__(self, *exc):
                with tm._lock:
                    tm._fence_suspended -= 1
                return False

        return _Suspend()

    def fence_ready(self, x, counter: str = "device_wait_ms") -> float:
        """``jax.block_until_ready(x)`` inside a ``device_wait`` span,
        accumulating the wait into ``counter``.  Returns seconds
        waited (0.0 when the fence is inactive)."""
        if not self.fence_active:
            return 0.0
        import jax
        t0 = time.perf_counter()
        with self.span("device_wait"):
            jax.block_until_ready(x)
        dt = time.perf_counter() - t0
        self.add(counter, dt * 1e3)
        return dt

    # -- device phase annotation -----------------------------------------
    def phase(self, name: str):
        """``jax.named_scope("tel.<name>")`` for code inside jitted
        bodies, at EVERY mode: the phase lands in the op metadata of the
        HLO (``op_name``), so a profiler trace attributes each device
        event to the innermost phase it ran under.  Trace-time Python
        only — nothing at dispatch; the scope writes op locations, which
        ``lowered.as_text()`` does not print, so programs lower
        byte-identical with and without it."""
        import jax
        return jax.named_scope(f"tel.{name}")

    # -- retrace sentinel ----------------------------------------------
    def note_trace(self, fn: str, shape) -> None:
        """Record one jit trace of entry point ``fn`` with ``shape``
        (any hashable shape key).  ALWAYS counts — trace-time Python
        only, never on the dispatch path — and warns once per fn when
        the distinct-shape count exceeds ``retrace_warn`` (the runtime
        promotion of the ``test_predict_cache`` compile-count lint;
        ``Config.telemetry_retrace_warn``)."""
        key = repr(shape)
        with self._lock:
            shapes = self._traces.setdefault(fn, set())
            shapes.add(key)
            n = len(shapes)
            if self.mode >= _COUNTERS:
                self._counters["compiles_observed"] = \
                    self._counters.get("compiles_observed", 0) + 1
            warn = n > self.retrace_warn and fn not in self._retrace_warned
            if warn:
                self._retrace_warned.add(fn)
        if warn:
            Log.warning(
                f"jitted entry point {fn} has now traced {n} distinct "
                f"shapes (telemetry_retrace_warn={self.retrace_warn}) — "
                "each retrace is an XLA compilation; bucket or pad the "
                "offending shape (docs/OBSERVABILITY.md, retrace "
                "sentinel)")

    def retraces(self) -> Dict[str, int]:
        with self._lock:
            return {fn: len(s) for fn, s in self._traces.items()}

    # -- memory watch ---------------------------------------------------
    def sample_memory(self, device: bool = False) -> Optional[float]:
        """Record RSS (and optionally device-buffer) watermarks and
        return the RSS read, in MiB (None at ``off`` or without
        /proc).  Called at chunk/predict boundaries and after each
        set-up stage — a /proc read per call."""
        if self.mode < _COUNTERS:
            return None
        rss = read_rss_mb()
        if rss is not None:
            self.gauge_max("rss_mb_peak", rss)
        if device:
            try:
                import jax
                nbytes = sum(getattr(a, "nbytes", 0)
                             for a in jax.live_arrays())
                self.gauge_max("device_buffer_mb_peak",
                               round(nbytes / (1 << 20), 1))
            except Exception:  # pragma: no cover - backend-dependent
                pass
        return rss

    # -- snapshot / export ----------------------------------------------
    def snapshot(self) -> Dict[str, Any]:
        """Counters + gauges + retrace map + derived per-tree /
        serving ratios — the dict the ``telemetry_snapshot`` callback
        hands to user code and the JSONL export's final line."""
        with self._lock:
            out: Dict[str, Any] = {
                "mode": MODES[self.mode],
                "host_id": None,
                "run_id": self.run_id,
                "counters": dict(self._counters),
                "gauges": dict(self._gauges),
                "histograms": {k: h.to_dict()
                               for k, h in self._hists.items()},
                "retraces": {fn: len(s) for fn, s in self._traces.items()},
            }
        out["host_id"] = self.host()
        c = out["counters"]
        derived: Dict[str, float] = {}
        trees = c.get("trees_dispatched", 0)
        if trees:
            derived["host_dispatch_ms_per_tree"] = round(
                c.get("host_dispatch_ms", 0.0) / trees, 4)
            derived["device_wait_ms_per_tree"] = round(
                c.get("device_wait_ms", 0.0) / trees, 4)
        scored = c.get("predict_rows", 0) + c.get("predict_pad_rows", 0)
        if scored:
            derived["predict_tail_waste"] = round(
                c.get("predict_pad_rows", 0) / scored, 4)
        lat = out["histograms"].get("predict_latency_ms")
        if lat and lat["count"]:
            # the tail percentiles any scraper would derive from the
            # cumulative buckets, precomputed for in-process readers
            for q, tag in ((0.5, "p50"), (0.95, "p95"), (0.99, "p99")):
                derived[f"predict_latency_{tag}_ms"] = \
                    hist_quantile(lat, q)
        if derived:
            out["derived"] = derived
        if not out["histograms"]:
            del out["histograms"]
        return out

    def events_snapshot(self) -> list:
        with self._lock:
            return list(self._events)

    def export(self, prefix: Optional[str] = None,
               shard: Optional[bool] = None) -> list:
        """Write ``<prefix>.jsonl`` (meta line + events + snapshot)
        and ``<prefix>.perfetto.json`` (Chrome trace_event, loadable
        in ui.perfetto.dev).  Returns the written paths.

        ``shard`` (default auto): in a multi-host run — or when
        ``LTPU_HOST_ID`` tags this process — each host writes its OWN
        ``<prefix>.host<id>.jsonl`` trace shard tagged with
        ``(host_id, run_id)`` and the rendezvous clock-sync mark, so
        N processes never clobber one file; merge the shards into one
        per-host-lane timeline with
        ``python -m lightgbm_tpu.telemetry merge``."""
        prefix = prefix or self.out
        if not prefix:
            raise ValueError("telemetry export needs a path prefix "
                             "(Config.telemetry_out)")
        host = self.host()
        if shard is None:
            shard = self._n_hosts() > 1 \
                or os.environ.get("LTPU_HOST_ID") is not None
        if shard:
            prefix = f"{prefix}.host{host}"
        d = os.path.dirname(os.path.abspath(prefix))
        if d:
            os.makedirs(d, exist_ok=True)
        events = self.events_snapshot()
        snap = self.snapshot()
        with self._lock:
            sync = self._sync
            t0_unix = self._t0_unix
        meta = {"type": "meta", "host_id": host, "run_id": self.run_id,
                "pid": os.getpid(), "t0_unix": round(t0_unix, 6)}
        if sync is not None:
            meta["sync_name"] = sync[0]
            meta["sync_ts_us"] = round(sync[1] * 1e6, 1)
        jsonl = f"{prefix}.jsonl"
        with open(jsonl, "w") as f:
            f.write(json.dumps(meta) + "\n")
            for name, ts, dur, tid, depth, attrs in events:
                ev = {"type": "span", "name": name,
                      "ts_us": round(ts * 1e6, 1),
                      "dur_us": round(dur * 1e6, 1),
                      "tid": tid, "depth": depth}
                if attrs:
                    ev["attrs"] = attrs
                f.write(json.dumps(ev) + "\n")
            f.write(json.dumps({"type": "snapshot", **snap}) + "\n")
        perfetto = f"{prefix}.perfetto.json"
        with open(perfetto, "w") as f:
            json.dump(self._perfetto(events, snap), f)
        paths = [jsonl, perfetto]
        journal = self.journal.events()
        if journal:
            # the fleet event journal, beside the span shard with the
            # SAME meta line (host/run identity + clock-sync mark), so
            # the merge tool aligns it onto the same timeline
            epath = f"{prefix}.events.jsonl"
            with open(epath, "w") as f:
                f.write(json.dumps(meta) + "\n")
                for ev in journal:
                    f.write(json.dumps(ev) + "\n")
            paths.append(epath)
        return paths

    def _perfetto(self, events, snap) -> Dict[str, Any]:
        pid = os.getpid()
        tids = {}
        trace = []
        for name, ts, dur, tid, depth, attrs in events:
            short = tids.setdefault(tid, len(tids) + 1)
            ev = {"name": name, "cat": "host", "ph": "X",
                  "ts": round(ts * 1e6, 1),
                  "dur": round(dur * 1e6, 1),
                  "pid": pid, "tid": short}
            if attrs:
                ev["args"] = {k: (v if isinstance(v, (int, float, str,
                                                      bool))
                                  else repr(v)) for k, v in attrs.items()}
            trace.append(ev)
        now = round((time.perf_counter() - self._t0) * 1e6, 1)
        for k, v in sorted(snap["counters"].items()):
            trace.append({"name": k, "cat": "counter", "ph": "C",
                          "ts": now, "pid": pid,
                          "args": {"value": round(float(v), 3)}})
        for k, v in sorted(snap["gauges"].items()):
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                trace.append({"name": k, "cat": "gauge", "ph": "C",
                              "ts": now, "pid": pid,
                              "args": {"value": v}})
            else:
                trace.append({"name": f"{k}={v}", "cat": "gauge",
                              "ph": "i", "ts": now, "pid": pid,
                              "tid": 0, "s": "g"})
        for tid, short in tids.items():
            trace.append({"name": "thread_name", "ph": "M", "pid": pid,
                          "tid": short,
                          "args": {"name": f"thread-{short}"}})
        return {"traceEvents": trace, "displayTimeUnit": "ms"}

    # -- prometheus export ---------------------------------------------
    def to_prometheus(self) -> str:
        """Render counters/gauges/histograms in the Prometheus text
        exposition format (stdlib only — docs/OBSERVABILITY.md name
        mapping): counter ``x`` -> ``ltpu_x_total``, numeric gauge
        ``x`` -> ``ltpu_x``, histogram ``x`` -> cumulative
        ``ltpu_x_bucket{le="..."}`` + ``ltpu_x_sum`` / ``ltpu_x_count``
        — p50/p95/p99 derivable by any scraper via
        ``histogram_quantile``."""
        with self._lock:
            counters = dict(self._counters)
            gauges = dict(self._gauges)
            hists = {k: h.to_dict() for k, h in self._hists.items()}
        lines: List[str] = []
        info_name = PROM_PREFIX + "info"
        lines.append(f"# TYPE {info_name} gauge")
        lines.append(
            f'{info_name}{{run_id="{self.run_id}",'
            f'host_id="{self.host()}",mode="{MODES[self.mode]}"}} 1')
        for k in sorted(counters):
            name = _prom_name(k) + "_total"
            lines.append(f"# TYPE {name} counter")
            lines.append(f"{name} {_fmt_val(counters[k])}")
        for k in sorted(gauges):
            v = gauges[k]
            if isinstance(v, bool) or not isinstance(v, (int, float)):
                continue  # string gauges have no prometheus form
            name = _prom_name(k)
            lines.append(f"# TYPE {name} gauge")
            lines.append(f"{name} {_fmt_val(v)}")
        for k in sorted(hists):
            h = hists[k]
            name = _prom_name(k)
            lines.append(f"# TYPE {name} histogram")
            cum = 0
            for bound, c in zip(list(h["bounds"]) + [float("inf")],
                                h["counts"]):
                cum += c
                lines.append(
                    f'{name}_bucket{{le="{_fmt_le(bound)}"}} {cum}')
            lines.append(f"{name}_sum {_fmt_val(h['sum'])}")
            lines.append(f"{name}_count {h['count']}")
        return "\n".join(lines) + "\n"

    def write_prom(self, path: Optional[str] = None) -> str:
        """Atomically write the Prometheus textfile (the
        node-exporter textfile-collector pattern;
        ``Config.telemetry_prom_out``).  Returns the path."""
        path = path or self.prom_out
        if not path:
            raise ValueError("prometheus export needs a path "
                             "(Config.telemetry_prom_out)")
        d = os.path.dirname(os.path.abspath(path))
        if d:
            os.makedirs(d, exist_ok=True)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write(self.to_prometheus())
        os.replace(tmp, path)
        return path

    def register_http_route(self, prefix: str, fn) -> None:
        """Mount ``fn(method, path, body, headers) -> (status, ctype,
        body_bytes, extra_headers|None)`` on the shared HTTP listener.
        A ``prefix`` ending in ``/`` matches any path under it (longest
        prefix wins); otherwise the match is exact.  Routes may be
        registered before OR after ``serve_metrics`` starts the
        server — the handler resolves against the live table."""
        with self._lock:
            self._http_routes[str(prefix)] = fn

    def unregister_http_route(self, prefix: str) -> None:
        with self._lock:
            self._http_routes.pop(str(prefix), None)

    def _resolve_route(self, path: str):
        with self._lock:
            routes = dict(self._http_routes)
        best = None
        for prefix, fn in routes.items():
            if prefix.endswith("/"):
                if not path.startswith(prefix):
                    continue
            elif path != prefix:
                continue
            if best is None or len(prefix) > len(best[0]):
                best = (prefix, fn)
        return best[1] if best else None

    def _metrics_route(self, method, path, body, headers):
        return (200, "text/plain; version=0.0.4",
                self.to_prometheus().encode(), None)

    def _healthz_route(self, method, path, body, headers):
        return (200, "application/json", json.dumps(
            {"status": "ok", "run_id": self.run_id,
             "host_id": self.host(),
             "mode": MODES[self.mode]}).encode(), None)

    def serve_metrics(self, port: int, host: str = "127.0.0.1"):
        """Start the stdlib HTTP scrape endpoint
        (``Config.telemetry_http_port``): ``GET /metrics`` returns the
        Prometheus text format, ``GET /healthz`` a JSON liveness body,
        plus any route mounted via ``register_http_route`` (the
        serving frontend's ``/predict/<model>`` shares this one
        listener instead of opening a second port).  Daemon-threaded;
        returns the server (``.server_address`` for an ephemeral port,
        ``.shutdown()`` to stop)."""
        if self._http is not None:
            return self._http
        self.register_http_route("/metrics", self._metrics_route)
        self.register_http_route("/healthz", self._healthz_route)
        from http.server import BaseHTTPRequestHandler, \
            ThreadingHTTPServer
        tm = self

        class _Handler(BaseHTTPRequestHandler):
            def _dispatch(self, method):
                fn = tm._resolve_route(self.path.split("?", 1)[0])
                if fn is None:
                    self.send_error(404)
                    return
                n = int(self.headers.get("Content-Length") or 0)
                req_body = self.rfile.read(n) if n > 0 else b""
                try:
                    status, ctype, body, extra = fn(
                        method, self.path, req_body, self.headers)
                except Exception as e:  # pragma: no cover - route bug
                    # routes are expected to answer errors themselves;
                    # a crash here must not tear down the listener
                    self.send_error(500, explain=str(e)[:200])
                    return
                self.send_response(status)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                for k, v in (extra or {}).items():
                    self.send_header(k, str(v))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                self._dispatch("GET")

            def do_POST(self):
                self._dispatch("POST")

            def log_message(self, *args):  # quiet: scrapes are periodic
                pass

        srv = ThreadingHTTPServer((host, int(port)), _Handler)
        t = threading.Thread(target=srv.serve_forever, daemon=True,
                             name="ltpu-metrics")
        t.start()
        self._http = srv
        Log.info(f"telemetry /metrics endpoint on "
                 f"http://{host}:{srv.server_address[1]} (+ /healthz)")
        return srv

    def stop_metrics_server(self) -> None:
        if self._http is not None:
            self._http.shutdown()
            self._http.server_close()
            self._http = None

    def prom_shard_path(self, path: str) -> str:
        """Multi-host-safe Prometheus textfile path: in a multi-host
        run (or when ``LTPU_HOST_ID`` tags this process) the atexit
        textfile shards per host like the JSONL export —
        ``metrics.prom`` becomes ``metrics.host<i>.prom`` — instead
        of N processes last-writer-winning one file."""
        if not (self._n_hosts() > 1
                or os.environ.get("LTPU_HOST_ID") is not None):
            return path
        root, ext = os.path.splitext(path)
        return f"{root}.host{self.host()}{ext or '.prom'}"

    def _export_atexit(self) -> None:  # pragma: no cover - process exit
        try:
            if self.out and (self._events or self._counters
                             or len(self.journal)):
                self.export(self.out)
            if self.prom_out and (self._counters or self._hists
                                  or self._gauges):
                self.write_prom(self.prom_shard_path(self.prom_out))
        except Exception:
            pass


TELEMETRY = Telemetry()


# ---------------------------------------------------------------------------
# Persistent-compile-cache counters (round 14): jax emits monitoring
# events on every persistent-cache lookup; bridging them into named
# counters makes the cache visible on the Prometheus surface (the
# registry's warm-before-cutover guarantee is monitored there —
# a deploy that compiles instead of disk-hitting shows up as
# compile_cache_misses climbing).
# ---------------------------------------------------------------------------
_CACHE_EVENT_COUNTERS = {
    "/jax/compilation_cache/cache_hits": "compile_cache_hits",
    "/jax/compilation_cache/cache_misses": "compile_cache_misses",
}
# the three steps of building a program, as jax.monitoring times them
# (jax._src.dispatch): the kinds of a ``stage(compiles=...)``'s parts
_COMPILE_STEP_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_MIN_STEP_S = 1e-3      # a shorter step is left to the one that holds it
_CACHE_WATCH = {"armed": False}


def _compile_cache_event(event: str, **kwargs) -> None:
    name = _CACHE_EVENT_COUNTERS.get(event)
    if name is not None:
        TELEMETRY.add(name, 1)


def _compile_duration_event(event: str, secs: float, **kwargs) -> None:
    # jax reports every jit nested in a program, thousands a chunk
    # program and nearly all under a millisecond: those return here, at
    # the cost of a lookup and a compare.  Their time stays where it
    # falls, inside the duration of the step that holds them.
    kind = _COMPILE_STEP_EVENTS.get(event)
    if kind is not None and secs < _MIN_STEP_S:
        return
    tm = TELEMETRY
    if tm.mode < _COUNTERS:
        return
    if kind is None:
        if event == _CACHE_LOAD_EVENT:
            # part of backend_compile_duration on a hit: a counter of
            # its own, not a stage beside ``<prefix>_compile``
            tm.add("compile_cache_load_ms", secs * 1e3)
        return
    for frame in reversed(tm._stage_stack()):
        if frame.split is not None:
            frame.split.told(kind, secs)
            return


def watch_compile_cache() -> None:
    """Register the jax monitoring listeners: persistent-cache hit/miss
    events to the ``compile_cache_hits``/``compile_cache_misses``
    counters, the cache's load time to ``compile_cache_load_ms``, and
    the trace / lower / compile durations to the parts of an open
    ``stage(compiles=...)``.  Idempotent."""
    if _CACHE_WATCH["armed"]:
        return
    import jax.monitoring
    jax.monitoring.register_event_listener(_compile_cache_event)
    jax.monitoring.register_event_duration_secs_listener(
        _compile_duration_event)
    _CACHE_WATCH["armed"] = True


_RETRACE_WARN_DEFAULT = 8


def apply_config(cfg) -> None:
    """Wire a Config's telemetry knobs into the process-global
    registry.  A fully default-valued Config (``telemetry=off``, the
    universal default) leaves the global state COMPLETELY alone — the
    library builds internal Configs (Booster(), dataset construction)
    and those must not stomp a threshold or mode an earlier enabling
    Config set.  Disable explicitly via ``TELEMETRY.configure("off")``."""
    warn = max(1, int(getattr(cfg, "telemetry_retrace_warn",
                              _RETRACE_WARN_DEFAULT)))
    mode = str(getattr(cfg, "telemetry", "off")).lower()
    out = str(getattr(cfg, "telemetry_out", ""))
    if mode != "off" or warn != _RETRACE_WARN_DEFAULT:
        TELEMETRY.retrace_warn = warn
    if mode != "off":
        TELEMETRY.configure(mode, out=out)
    elif out and TELEMETRY.on:
        TELEMETRY.configure(TELEMETRY.level, out=out)
    # production-surface knobs (round 13): each only ever ARMS — a
    # default-valued internal Config must not disarm an earlier one
    prom = str(getattr(cfg, "telemetry_prom_out", ""))
    if prom:
        TELEMETRY.set_prom_out(prom)
    flight = str(getattr(cfg, "flight_recorder_out", ""))
    if flight:
        TELEMETRY.flight.arm(flight)
    port = int(getattr(cfg, "telemetry_http_port", 0))
    if port > 0 and TELEMETRY._http is None:
        try:
            TELEMETRY.serve_metrics(port)
        except OSError as e:  # pragma: no cover - port in use
            Log.warning(f"telemetry_http_port {port} unavailable: {e}")


# ---------------------------------------------------------------------------
# Cross-host trace merge (``python -m lightgbm_tpu.telemetry merge``)
# ---------------------------------------------------------------------------
def _read_shard(path: str) -> Dict[str, Any]:
    meta: Dict[str, Any] = {}
    spans: List[dict] = []
    events: List[dict] = []
    snap: Dict[str, Any] = {}
    with open(path) as f:
        for ln in f:
            ln = ln.strip()
            if not ln:
                continue
            obj = json.loads(ln)
            t = obj.get("type")
            if t == "meta":
                meta = obj
            elif t == "span":
                spans.append(obj)
            elif t == "event":
                events.append(obj)
            elif t == "snapshot":
                snap = obj
    if not meta:
        # pre-r13 shard (no meta line): synthesize identity from the
        # snapshot, clock alignment falls back to zero shift
        meta = {"host_id": snap.get("host_id", 0),
                "run_id": snap.get("run_id", "")}
    meta["path"] = path
    return {"meta": meta, "spans": spans, "events": events,
            "snapshot": snap}


def merge_shards(paths: List[str]) -> Dict[str, Any]:
    """Merge per-host trace shards into ONE Perfetto timeline with one
    track lane (pid) per host.

    Clock alignment: every host records the ``rendezvous`` sync mark
    when it exits the multi-host barrier (near-simultaneous on all
    hosts), so each shard's relative clock is shifted to make its mark
    coincide with the reference host's — collective skew between hosts
    then reads directly as slice offsets between lanes.  Shards
    without a sync mark merge with zero shift and are listed under
    ``metadata.unaligned``.

    Tracing (round 23): spans carrying a ``span`` trace attr are
    indexed across ALL shards; every span carrying a ``links`` attr
    (the coalesced dispatch's fan-in list) gets a Perfetto flow arrow
    (``ph:"s"/"f"``) drawn from each linked member span to it — the
    causal request→dispatch edges read directly across host lanes.
    ``<shard>.events.jsonl`` journal shards (passed explicitly or
    auto-discovered beside a span shard) render as instant events on
    their host's lane, clock-shifted identically."""
    if not paths:
        raise ValueError("merge needs at least one shard path")
    pathset = {os.path.abspath(p) for p in paths}
    shards = []
    for p in paths:
        s = _read_shard(p)
        if p.endswith(".jsonl") and not p.endswith(".events.jsonl"):
            # auto-discover the sibling journal shard so a plain
            # `merge run.host*.jsonl` that predates the journal keeps
            # working and a journal-producing run needs no extra args
            sib = p[:-len(".jsonl")] + ".events.jsonl"
            if os.path.abspath(sib) not in pathset \
                    and os.path.exists(sib):
                s["events"].extend(_read_shard(sib)["events"])
        shards.append(s)
    shards.sort(key=lambda s: int(s["meta"].get("host_id", 0)))
    run_ids = {s["meta"].get("run_id", "") for s in shards}
    ref = next((s for s in shards
                if s["meta"].get("sync_ts_us") is not None),
               shards[0])
    ref_sync = ref["meta"].get("sync_ts_us")
    trace: List[dict] = []
    shifts: Dict[str, float] = {}
    unaligned: List[str] = []
    seen_hosts: List[int] = []
    # cross-shard trace index for flow arrows: span_id -> placed slice
    span_index: Dict[str, tuple] = {}
    link_sources: List[tuple] = []   # (links, pid, tid, ts, dur)
    for s in shards:
        meta = s["meta"]
        host = int(meta.get("host_id", 0))
        if host not in seen_hosts:
            seen_hosts.append(host)
        sync = meta.get("sync_ts_us")
        if ref_sync is not None and sync is not None:
            shift = float(ref_sync) - float(sync)
        else:
            shift = 0.0
            unaligned.append(meta["path"])
        shifts[meta["path"]] = round(shift, 1)
        trace.append({"name": "process_name", "ph": "M", "pid": host,
                      "args": {"name": f"host {host}"}})
        trace.append({"name": "process_sort_index", "ph": "M",
                      "pid": host, "args": {"sort_index": host}})
        tids: Dict[int, int] = {}
        for ev in s["spans"]:
            tid = tids.setdefault(ev.get("tid", 0), len(tids) + 1)
            ts = round(ev["ts_us"] + shift, 1)
            dur = ev.get("dur_us", 0.0)
            out = {"name": ev["name"], "cat": "host", "ph": "X",
                   "ts": ts, "dur": dur, "pid": host, "tid": tid}
            attrs = ev.get("attrs")
            if attrs:
                out["args"] = attrs
                sid = attrs.get("span")
                if sid:
                    span_index[str(sid)] = (host, tid, ts, dur)
                links = attrs.get("links")
                if links:
                    link_sources.append((links, host, tid, ts, dur))
            trace.append(out)
        for tid, short in tids.items():
            trace.append({"name": "thread_name", "ph": "M", "pid": host,
                          "tid": short,
                          "args": {"name": f"host{host}-t{short}"}})
        for ev in s["events"]:
            # journal events: process-scoped instants on the host lane
            name = ev.get("kind", "event")
            if ev.get("seam"):
                name = f"{name}:{ev['seam']}"
            args = {k: v for k, v in ev.items()
                    if k in ("seq", "seam", "trace", "span")}
            if ev.get("fields"):
                args.update(ev["fields"])
            trace.append({"name": name, "cat": "journal", "ph": "i",
                          "ts": round(ev.get("ts_us", 0.0) + shift, 1),
                          "pid": host, "tid": 0, "s": "p",
                          "args": args})
        counters = (s["snapshot"] or {}).get("counters", {})
        last_ts = max((ev["ts_us"] + shift for ev in s["spans"]),
                      default=0.0)
        for k, v in sorted(counters.items()):
            trace.append({"name": k, "cat": "counter", "ph": "C",
                          "ts": round(last_ts, 1), "pid": host,
                          "args": {"value": round(float(v), 3)}})
    flow_id = 0
    flows = 0
    for links, dpid, dtid, dts, ddur in link_sources:
        for lk in links if isinstance(links, (list, tuple)) else []:
            src = span_index.get(str(lk))
            if src is None:
                continue
            spid, stid, sts, sdur = src
            flow_id += 1
            flows += 1
            # flow start bound mid-slice of the member request span,
            # finish bound to the enclosing dispatch slice (bp:"e")
            trace.append({"name": "trace", "cat": "trace", "ph": "s",
                          "id": flow_id, "pid": spid, "tid": stid,
                          "ts": round(sts + sdur / 2, 1)})
            trace.append({"name": "trace", "cat": "trace", "ph": "f",
                          "bp": "e", "id": flow_id, "pid": dpid,
                          "tid": dtid,
                          "ts": round(dts + ddur / 2, 1)})
    merged = {
        "traceEvents": trace,
        "displayTimeUnit": "ms",
        "metadata": {
            "tool": "lightgbm_tpu.telemetry merge",
            "run_ids": sorted(r for r in run_ids if r),
            "hosts": seen_hosts,
            "clock_shifts_us": shifts,
            "flow_links": flows,
        },
    }
    if unaligned:
        merged["metadata"]["unaligned"] = unaligned
    return merged


def _cmd_merge(argv: List[str]) -> int:
    import sys
    out_path = None
    if "-o" in argv:
        i = argv.index("-o")
        try:
            out_path = argv[i + 1]
        except IndexError:
            print("merge: -o needs a path", file=sys.stderr)
            return 2
        del argv[i:i + 2]
    if not argv:
        print("merge: no shard files given", file=sys.stderr)
        return 2
    missing = [p for p in argv if not os.path.exists(p)]
    if missing:
        print(f"merge: shard(s) not found: {missing}", file=sys.stderr)
        return 2
    merged = merge_shards(argv)
    if out_path is None:
        out_path = os.path.join(
            os.path.dirname(os.path.abspath(argv[0])) or ".",
            "merged.perfetto.json")
    with open(out_path, "w") as f:
        json.dump(merged, f)
    run_ids = merged["metadata"]["run_ids"]
    if len(run_ids) > 1:
        print(f"merge: WARNING shards carry {len(run_ids)} distinct "
              f"run_ids {run_ids} — merged anyway", file=sys.stderr)
    print(f"merged {len(argv)} shard(s), "
          f"{len(merged['metadata']['hosts'])} host lane(s) -> "
          f"{out_path}")
    return 0


def _cmd_events(argv: List[str]) -> int:
    """Query exported journal shards: filter by seam/host/kind/time
    range, print matching events one JSON per line (sorted by aligned
    time then per-host sequence)."""
    import sys
    filt = {"seam": None, "host": None, "kind": None,
            "since": None, "until": None}
    paths: List[str] = []
    i = 0
    while i < len(argv):
        a = argv[i]
        if a in ("--seam", "--host", "--kind", "--since", "--until"):
            if i + 1 >= len(argv):
                print(f"events: {a} needs a value", file=sys.stderr)
                return 2
            filt[a[2:]] = argv[i + 1]
            i += 2
        elif a.startswith("--"):
            print(f"events: unknown option {a}", file=sys.stderr)
            return 2
        else:
            paths.append(a)
            i += 1
    if not paths:
        print("events: no journal files given", file=sys.stderr)
        return 2
    missing = [p for p in paths if not os.path.exists(p)]
    if missing:
        print(f"events: file(s) not found: {missing}", file=sys.stderr)
        return 2
    try:
        host = None if filt["host"] is None else int(filt["host"])
        since = None if filt["since"] is None else float(filt["since"])
        until = None if filt["until"] is None else float(filt["until"])
    except ValueError as e:
        print(f"events: bad filter value ({e})", file=sys.stderr)
        return 2
    rows: List[tuple] = []
    for p in paths:
        s = _read_shard(p)
        h = int(s["meta"].get("host_id", 0))
        for ev in s["events"]:
            ts = float(ev.get("ts_us", 0.0))
            if host is not None and ev.get("host_id", h) != host:
                continue
            if filt["seam"] is not None \
                    and ev.get("seam", "") != filt["seam"]:
                continue
            if filt["kind"] is not None \
                    and ev.get("kind", "") != filt["kind"]:
                continue
            if since is not None and ts < since:
                continue
            if until is not None and ts > until:
                continue
            rows.append((ts, ev.get("host_id", h),
                         ev.get("seq", 0), ev))
    rows.sort(key=lambda r: (r[0], r[1], r[2]))
    for _, _, _, ev in rows:
        print(json.dumps(ev))
    print(f"{len(rows)} event(s) from {len(paths)} shard(s)",
          file=sys.stderr)
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    """``python -m lightgbm_tpu.telemetry merge [-o OUT] shard.jsonl...``
    — merge per-host trace shards (``<prefix>.host<i>.jsonl`` +
    journal ``.events.jsonl`` siblings) into one Perfetto file
    (default ``<first shard dir>/merged.perfetto.json``).

    ``python -m lightgbm_tpu.telemetry events [--seam S] [--host H]
    [--kind K] [--since US] [--until US] <events.jsonl> [...]`` —
    query exported journal shards.  rc 0 ok / 2 usage."""
    import sys
    argv = list(sys.argv[1:] if argv is None else argv)
    if not argv or argv[0] not in ("merge", "events"):
        print("usage: python -m lightgbm_tpu.telemetry merge "
              "[-o OUT.perfetto.json] <shard.jsonl> [...]\n"
              "       python -m lightgbm_tpu.telemetry events "
              "[--seam S] [--host H] [--kind K] [--since US] "
              "[--until US] <events.jsonl> [...]",
              file=sys.stderr)
        return 2
    if argv[0] == "merge":
        return _cmd_merge(argv[1:])
    return _cmd_events(argv[1:])


if __name__ == "__main__":  # pragma: no cover - CLI entry
    import sys
    sys.exit(main())
