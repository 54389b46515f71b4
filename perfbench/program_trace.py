"""Device time per phase of the program, and its set-up stages.

The program names its own phases: every op of the fused chunk program is
traced under a ``jax.named_scope("tel.<phase>")`` (lightgbm_tpu/telemetry.py
``phase``; docs/OBSERVABILITY.md lists them), and its set-up stages add
their wall time to counters ``setup_<stage>_ms``.  This file reduces a
profiler trace of a run to device seconds per phase.

Where a device event's scope path lives (one real trace of criteo_train on
a v5e, my chip run, PR 26): NOT in the event's name and in no stat that
``jax.profiler.ProfileData`` shows (an ``XLA Ops`` event has
``device_offset_ps``, ``device_duration_ps`` and nothing else).  The raw
xplane has it twice: as the stat ``tf_op`` of the event's METADATA
(``XEventMetadata.stats``, which ProfileData does not expose), and in the
optimized HLO module that the plane ``/host:metadata`` carries for each
program (stat ``Hlo Proto``), as each instruction's ``metadata.op_name``,
e.g. ``jit(chunk)/while/body/closed_call/while/body/tel.histogram/
tel.split_finder/cond/branch_0_fun/tel.split_finder/gather``.  This file
reads the second, with a protobuf wire-format decoder of its own (no
TensorFlow): the module also has the graph, which the rule below needs.
Events are joined to instructions by name (``%fusion.431 = ...`` is
instruction ``fusion.431``).

How an event is booked.  A fusion carries the metadata of one of its
instructions (its root, as a rule) and is booked whole to that scope.
Half of the device time outside the histogram kernel (18.9 of 36.9
ms/tree) runs in instructions the TPU compiler made itself, with no
metadata at all (the two-level ``reduce-window`` it rewrites a 255-bin
prefix sum into, copies for loop-carried buffers, ``copy-done``).  So, in
this order:

1. the innermost ``tel.<phase>`` of the instruction's own ``op_name``;
2. else that of the instruction which calls its computation (a
   ``conditional``, ``while``, ``call`` or fusion), and so on outwards;
3. else that of the nearest instruction of its computation that has one,
   going through its operands ("what it copies was made by"), and then
   through its users;
4. else none: ``unscoped``.

Readers (``perfbench/metrics/<name>.json``: ``{"reader":
"program_trace:<function>", "params": {...}}``), each returning None where
it finds nothing to read, as on a program without the scopes or counters:

    device_phase    {"phases": [...], "per": "tree", "scale": 1000.0}
                    device seconds of leaf events booked to these phases,
                    the histogram kernel's own events (KERNEL, which
                    hist_ms_per_tree reads) left out; "phases": null
                    reads the unscoped events
    setup_counter   {"counters": [...], "scale": 0.001}
                    the sum of telemetry counters over the whole process
                    (the windowed ctx["counters"] has lost set-up)

The metrics these were written for (ISSUE 26; not in BENCHMARK.json yet,
PERF.md section 7 says what the harness lacks), all ``per: tree, scale:
1000`` but the last three:

    split_ms_per_tree             phases [split_finder]
    route_ms_per_tree             phases [apply_split, partition, route]
    objective_ms_per_tree         phases [gradients, sampling, quantize]
    score_update_ms_per_tree      phases [score_update, finalize_tree,
                                          tree_record, init_state]
    hist_glue_ms_per_tree         phases [histogram]
    unscoped_device_ms_per_tree   phases null
    upload_s                      counters [setup_upload_ms, setup_binsT_ms]
    grower_init_s                 counters [setup_grower_init_ms]
    chunk_program_build_s         counters [chunk_program_build_ms]

The first six partition ``nonhist_device_ms_per_tree``; ``phase_seconds``
asserts that.
"""
import gzip
import json
import os
import re

import xplane

KERNEL = re.compile(r"^%?compute_group_histograms")
PHASE = re.compile(r"tel\.(\w+)")
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
OUT_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), ".perfbench_out")


# -- protobuf wire format: just enough to walk XSpace and HloProto ---------
def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields are
    skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
            yield key >> 3, value
        elif kind == 2:
            size, i = _varint(buf, i)
            yield key >> 3, buf[i:i + size]
            i += size
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
        else:
            raise ValueError(f"wire type {kind} in a profiler trace")


def _ints(values):
    """A repeated int64 field, packed (a blob of varints) or not."""
    out = []
    for v in values:
        if isinstance(v, int):
            out.append(v)
        else:
            i = 0
            while i < len(v):
                x, i = _varint(v, i)
                out.append(x)
    return out


def _text(view):
    return bytes(view).decode("utf-8", "replace")


def _hlo_protos(xspace):
    """The serialized HloProto of every program in the trace."""
    for f, plane in _fields(xspace):
        if f != 1:                                   # XSpace.planes
            continue
        name, stat_names, events = "", {}, []
        for f, v in _fields(plane):
            if f == 2:                               # XPlane.name
                name = _text(v)
            elif f == 5:                             # stat_metadata entry
                meta = dict(_fields(dict(_fields(v))[2]))
                stat_names[meta.get(1, 0)] = _text(meta.get(2, b""))
            elif f == 4:                             # event_metadata entry
                events.append(dict(_fields(v))[2])
        if name != METADATA_PLANE:
            continue
        for event in events:
            for f, stat in _fields(event):
                if f != 5:                           # XEventMetadata.stats
                    continue
                stat = dict(_fields(stat))
                if stat_names.get(stat.get(1)) == HLO_STAT and 6 in stat:
                    yield stat[6]                    # XStat.bytes_value


def hlo_instructions(path):
    """[[id, name, phase or None, computation id, [operand ids], [called
    computation ids]], ...] for every instruction of every program in the
    trace ``path``; ids are made unique across programs."""
    with open(path, "rb") as f:
        xspace = memoryview(f.read())
    rows = []
    for n, proto in enumerate(_hlo_protos(xspace)):
        base = n << 40
        module = next(v for f, v in _fields(proto) if f == 1)
        for f, comp in _fields(module):
            if f != 3:                               # computations
                continue
            comp_id, instrs = 0, []
            for f, v in _fields(comp):
                if f == 5:
                    comp_id = v
                elif f == 2:
                    instrs.append(v)
            for instr in instrs:
                name, op_name, iid, operands, called = "", "", 0, [], []
                for f, v in _fields(instr):
                    if f == 1:
                        name = _text(v)
                    elif f == 7:                     # OpMetadata.op_name
                        op_name = _text(dict(_fields(v)).get(2, b""))
                    elif f == 35:
                        iid = v
                    elif f == 36:
                        operands.append(v)
                    elif f == 38:
                        called.append(v)
                scopes = PHASE.findall(op_name)
                rows.append([base + iid, name, scopes[-1] if scopes else None,
                             base + comp_id,
                             [base + x for x in _ints(operands)],
                             [base + x for x in _ints(called)]])
    return rows


# -- the booking rule ------------------------------------------------------
def book(instructions, reach=8):
    """{instruction name: phase or None} by the four rules of this file's
    docstring; a name that two programs book differently gets None."""
    by_id = {r[0]: r for r in instructions}
    caller, users = {}, {}
    for iid, _, _, _, operands, called in instructions:
        for c in called:
            caller.setdefault(c, iid)
        for o in operands:
            users.setdefault(o, []).append(iid)

    def outwards(row):
        for _ in range(64):
            if row[2]:
                return row[2]
            row = by_id.get(caller.get(row[3]))
            if row is None:
                return None
        return None

    def nearest(row, step):
        seen, frontier = {row[0]}, [row[0]]
        for _ in range(reach):
            nxt = []
            for x in frontier:
                for y in step(x):
                    other = by_id.get(y)
                    if other is None or y in seen or other[3] != row[3]:
                        continue
                    seen.add(y)
                    if other[2]:
                        return other[2]
                    nxt.append(y)
            frontier = nxt
        return None

    out = {}
    for row in instructions:
        phase = (outwards(row)
                 or nearest(row, lambda x: by_id[x][4])
                 or nearest(row, lambda x: users.get(x, ())))
        if out.setdefault(row[1], phase) != phase:
            out[row[1]] = None
    return out


def instruction_name(event_name):
    """``%fusion.431 = (s32[...`` -> ``fusion.431``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _device_events(planes):
    return [lines.get(xplane.OPS_LINE, []) for p, lines in planes.items()
            if xplane.DEVICE_PLANE.match(p)]


def phase_seconds(planes, booked):
    """Device seconds of the leaf events of ``planes`` (as xplane.load
    gives them) by the phase ``booked`` gives their instruction, averaged
    over the device planes: {"phases": {phase: s}, "kernel": s (the
    histogram kernel's own events), "unscoped": s, "busy": s}, or None if
    no event has a phase.  The phases, the kernel and the unscoped events
    are every leaf event once, so they add up to the busy time; that is
    asserted."""
    devices = _device_events(planes)
    if not any(devices):
        return None
    phases, kernel, unscoped, busy = {}, 0.0, 0.0, 0.0
    for events in devices:
        lv = xplane.leaves(events)
        busy += sum(hi - lo for lo, hi in
                    xplane.merged([e[1], e[1] + e[2]] for e in lv))
        for name, _, dur in lv:
            phase = booked.get(instruction_name(name))
            if KERNEL.search(name):
                kernel += dur
            elif phase is None:
                unscoped += dur
            else:
                phases[phase] = phases.get(phase, 0.0) + dur
    if not phases:
        return None
    total = sum(phases.values()) + kernel + unscoped
    if abs(total - busy) > 1e-3 * (busy - kernel):
        raise AssertionError(
            f"leaf events overlap: their durations add up to {total} ns, "
            f"the device was busy {busy} ns")
    n = 1e9 * len(devices)
    return {"phases": {k: v / n for k, v in phases.items()},
            "kernel": kernel / n, "unscoped": unscoped / n, "busy": busy / n}


def idle_by_span(planes, prefix="ltpu."):
    """The device's idle seconds between its first and its last event,
    by the program's own host span (``TELEMETRY.span`` as a profiler
    annotation) that the host was in, the innermost where they nest, and
    ``outside`` for the rest: {span or "outside": seconds}.  (The
    ``breakdown.idle_gaps`` of xplane.reduce names a gap by the one host
    event that overlaps it most, which is always an enclosing frame of
    the Python tracer; and the profiler's host and device clocks differ
    by about a millisecond, so a gap shorter than that is not the
    host's.)"""
    spans = sorted(([dur, start, name]
                    for events in planes.get(xplane.HOST_PLANE, {}).values()
                    for name, start, dur in events
                    if name.startswith(prefix) and dur > 0))
    out = {}
    devices = _device_events(planes)
    for events in devices:
        busy = xplane.merged([e[1], e[1] + e[2]]
                             for e in xplane.leaves(events))
        for (_, lo), (hi, _) in zip(busy, busy[1:]):
            rest = [[lo, hi]]
            for dur, start, name in spans:
                end = start + dur
                if end <= lo or start >= hi:
                    continue
                nxt = []
                for a, b in rest:
                    c, d = max(a, start), min(b, end)
                    if c >= d:
                        nxt.append([a, b])
                        continue
                    out[name] = out.get(name, 0.0) + (d - c)
                    nxt += [x for x in ([a, c], [d, b]) if x[0] < x[1]]
                rest = nxt
            out["outside"] = out.get("outside", 0.0) \
                + sum(b - a for a, b in rest)
    n = 1e9 * max(1, len(devices))
    return {k: v / n for k, v in out.items()}


def load_fixture(path):
    """(planes, instructions) of a recorded fixture: gzipped JSON
    ``{"planes": ..., "hlo": ...}``, as ``cut_fixture`` writes it."""
    with gzip.open(path, "rt") as f:
        d = json.load(f)
    return d["planes"], d["hlo"]


def cut_fixture(trace, out, seconds=0.25):
    """Writes the first ``seconds`` of the device's events in the trace
    ``trace``, with the host's events of that time and the programs'
    instructions, to ``out`` (how perfbench/tests' fixtures were cut)."""
    planes = xplane.load(trace)
    start = min(e[1] for events in _device_events(planes) for e in events)
    end = start + seconds * 1e9
    lead = 5e6                  # the host's dispatch comes before the device
    cut = {p: {line: [e for e in events
                      if (start <= e[1] and e[1] + e[2] <= end
                          if xplane.DEVICE_PLANE.match(p)
                          else start - lead < e[1] + e[2] and e[1] < end)]
               for line, events in lines.items()}
           for p, lines in planes.items()}
    with gzip.open(out, "wt") as f:
        json.dump({"planes": cut, "hlo": hlo_instructions(trace)}, f,
                  separators=(",", ":"))


# -- readers -----------------------------------------------------------------
def _phases_of_run(ctx):
    if ctx["trace_planes"] is None:
        return None
    cache = ctx["trace_cache"]
    if "program_trace" not in cache:
        try:
            booked = book(hlo_instructions(xplane.newest_xplane(OUT_DIR)))
        except xplane.NoDeviceTrace:
            booked = {}
        cache["program_trace"] = phase_seconds(ctx["trace_planes"], booked)
    return cache["program_trace"]


def _scaled(ctx, value, p):
    if p.get("per") == "tree":
        value /= ctx["n_trees"]
    elif p.get("per") is not None:
        raise ValueError(f"unknown 'per': {p['per']!r}")
    return value * p.get("scale", 1.0)


def device_phase(ctx, p):
    """Device seconds of the leaf events booked to ``phases`` (None: to
    no phase), the histogram kernel's own events left out."""
    r = _phases_of_run(ctx)
    if r is None:
        return None
    if p["phases"] is None:
        return _scaled(ctx, r["unscoped"], p)
    return _scaled(ctx, sum(r["phases"].get(k, 0.0) for k in p["phases"]), p)


def setup_counter(ctx, p):
    """The sum of telemetry counters over the whole process; None where
    the program has not one of them."""
    from lightgbm_tpu.telemetry import TELEMETRY
    totals = TELEMETRY.counters()
    if not all(name in totals for name in p["counters"]):
        return None
    return sum(totals[name] for name in p["counters"]) * p.get("scale", 1.0)
