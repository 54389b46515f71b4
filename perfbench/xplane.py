"""From a profiler trace (``*.xplane.pb``) to device busy time, kernel
sums, the top operations and the idle gaps.

What one real trace of this program on a v5e looks like (my chip run,
PR 25): the device is the plane ``/device:TPU:0``; its line ``XLA Ops``
holds one event per executed HLO instruction, named by the instruction's
whole text (``%compute_group_histograms_fused_tiled.5 = (s32[...``), and
NESTED: a ``while`` or ``conditional`` spans its body's events, so only
events with no event inside them ("leaves") are work.  ``Async XLA Ops``
holds DMA copies that overlap the work and is not counted.  Scope paths
(``tel.*``) do not appear in event names.  Host threads are lines of the
plane ``/host:CPU``; the line ``python`` has the interpreter's calls.
"""
import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


class NoDeviceTrace(RuntimeError):
    """The trace has no device plane, or no device event."""


def newest_xplane(trace_dir):
    pbs = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                           recursive=True), key=os.path.getmtime)
    if not pbs:
        raise NoDeviceTrace(f"no *.xplane.pb under {trace_dir}")
    return pbs[-1]


def load(path):
    """{plane: {line: [[name, start_ns, duration_ns], ...]}} for the
    device planes' ops and the host's threads."""
    if path.endswith(".json.gz"):          # a recorded fixture
        with gzip.open(path, "rt") as f:
            return json.load(f)
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            keep = lambda line: line.name == OPS_LINE
        elif plane.name == HOST_PLANE:
            keep = lambda line: True
        else:
            continue
        out[plane.name] = {
            line.name: [[e.name, e.start_ns, e.duration_ns]
                        for e in line.events]
            for line in plane.lines if keep(line)}
    return out


def short_name(hlo_text):
    """``%fusion.431 = (s32[...`` -> ``fusion``: the instruction's name
    without its number, so that a recompile does not rename it."""
    name = hlo_text.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.clone|\.\d+)+$", "", name) or name


def leaves(events):
    """Events with no other event inside them, sorted by start."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    parent = [False] * len(evs)
    stack = []
    for i, (_, start, dur) in enumerate(evs):
        while stack and evs[stack[-1]][1] + evs[stack[-1]][2] <= start:
            stack.pop()
        if stack and dur > 0:
            parent[stack[-1]] = True
        if dur > 0:
            stack.append(i)
    return [e for e, p in zip(evs, parent) if not p and e[2] > 0]


def merged(intervals):
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def _host_labels(host_lines, gaps):
    """For each gap [lo, hi) of ``gaps`` (disjoint, in order of time): what
    the host was in for most of it, its most specific (shortest) span
    among those that overlap the gap the most, the first in line and
    event order on a tie.

    One sweep: host events enter in order of start once one starts
    before a gap's end, and leave once they end at or before a gap's
    start, which no later gap can undo; so a gap looks only at the events
    that overlap it, not at every event of the trace."""
    events = []
    for line, evs in host_lines.items():
        for name, start, dur in evs:
            events.append((start, start + dur, dur, len(events),
                           f"{line}: {name}"))
    events.sort()
    labels, live, i = [], [], 0
    for lo, hi in gaps:
        while i < len(events) and events[i][0] < hi:
            live.append(events[i])
            i += 1
        live = [e for e in live if e[1] > lo]
        best = None
        for start, end, dur, order, label in live:
            ov = min(hi, end) - max(lo, start)
            if ov > 0 and (best is None or (-ov, dur, order) < best[0]):
                best = ((-ov, dur, order), label)
        labels.append(best[1][:120] if best else "no host span in the trace")
    return labels


def reduce(planes, patterns=()):
    """The numbers every trace reader starts from, per device plane and
    averaged over them: busy seconds (union of leaf events), seconds per
    short op name, seconds in leaves matching each pattern group, and the
    idle gaps between the first and the last event, by host activity."""
    devices = {p: lines.get(OPS_LINE, []) for p, lines in planes.items()
               if DEVICE_PLANE.match(p)}
    if not devices:
        raise NoDeviceTrace("the trace has no /device:TPU:n plane")
    if not any(devices.values()):
        raise NoDeviceTrace("the device planes hold no XLA Ops event")
    regs = [re.compile(p) for p in patterns]
    n = len(devices)
    busy = matched = 0.0
    ops, gaps = {}, {}
    for events in devices.values():
        lv = leaves(events)
        spans = merged([e[1], e[1] + e[2]] for e in lv)
        busy += sum(hi - lo for lo, hi in spans) / 1e9 / n
        for name, _, dur in lv:
            key = short_name(name)
            ops[key] = ops.get(key, 0.0) + dur / 1e9 / n
            if any(r.search(name) for r in regs):
                matched += dur / 1e9 / n
        idle = [(hi0, lo1) for (_, hi0), (lo1, _) in zip(spans, spans[1:])]
        for (lo, hi), label in zip(
                idle, _host_labels(planes.get(HOST_PLANE, {}), idle)):
            gaps[label] = gaps.get(label, 0.0) + (hi - lo) / 1e9 / n
    top = lambda d: [[k, v] for k, v in
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"busy_s": busy, "matched_s": matched, "devices": n,
            "device_ops": top(ops), "idle_gaps": top(gaps)}
