"""Shared jaxpr / StableHLO walkers — the ONE implementation of the
compiled-program introspection that `tests/test_carry_hlo.py`,
`tests/test_predict_cache.py` and the `lightgbm_tpu.analysis` rule
engine all used to private-copy.

Every helper takes a plain ``jaxpr`` (a ``jax.core.Jaxpr``; pass
``closed.jaxpr`` for a ClosedJaxpr) and recurses into every sub-jaxpr
reachable through eqn params — scan/while/cond bodies, pjit calls,
custom_* envelopes — so a primitive count is a whole-program count no
matter how deeply XLA's control-flow nesting buries it.
"""
from __future__ import annotations

import re
from typing import Iterator, List, Optional, Set


def _sub_jaxprs(eqn) -> Iterator:
    """Every jaxpr hanging off one equation's params (closed jaxprs are
    unwrapped to their inner jaxpr)."""
    for v in eqn.params.values():
        if hasattr(v, "jaxpr"):            # ClosedJaxpr
            yield v.jaxpr
        elif hasattr(v, "eqns"):           # bare Jaxpr
            yield v
        elif isinstance(v, (list, tuple)):
            for b in v:
                if hasattr(b, "jaxpr"):
                    yield b.jaxpr
                elif hasattr(b, "eqns"):
                    yield b


def iter_eqns(jaxpr) -> Iterator:
    """Depth-first generator over every equation in ``jaxpr`` and all
    nested sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub)


def find_primitives(jaxpr, name: str) -> List:
    """All equations (any nesting depth) whose primitive is ``name``."""
    return [e for e in iter_eqns(jaxpr) if e.primitive.name == name]


def count_primitive(jaxpr, name: str) -> int:
    """Whole-program occurrence count of primitive ``name``."""
    return sum(1 for e in iter_eqns(jaxpr) if e.primitive.name == name)


def find_scans(jaxpr, length: Optional[int] = None) -> List:
    """All ``scan`` equations, optionally filtered to an exact trip
    count (``params["length"]``) — how the carry tests pick the
    boosting scan out of a program whose inner kernels scan too."""
    scans = find_primitives(jaxpr, "scan")
    if length is not None:
        scans = [s for s in scans if s.params.get("length") == length]
    return scans


def scan_output_stacks(scan_eqn) -> int:
    """Number of O(length) output buffers (ys) a scan stacks — the
    loop-carried output stores the round-6 chunk-slope diagnosis traced
    the per-iteration dispatch penalty to."""
    return len(scan_eqn.outvars) - scan_eqn.params["num_carry"]


def jaxpr_dtypes(jaxpr) -> Set[str]:
    """Every aval dtype name appearing anywhere in the program
    (inputs, outputs, and every equation's operands/results)."""
    out: Set[str] = set()

    def _add(v):
        aval = getattr(v, "aval", None)
        dt = getattr(aval, "dtype", None)
        if dt is not None:
            out.add(str(dt))

    def _walk(jx):
        for v in list(jx.invars) + list(jx.outvars) + list(jx.constvars):
            _add(v)
        for eqn in jx.eqns:
            for v in list(eqn.invars) + list(eqn.outvars):
                _add(v)
            for sub in _sub_jaxprs(eqn):
                _walk(sub)

    _walk(jaxpr)
    return out


def primitive_names(jaxpr) -> Set[str]:
    """Set of every primitive name in the program (nested included)."""
    return {e.primitive.name for e in iter_eqns(jaxpr)}


def scatter_eqns_with_dtype(jaxpr, dtype_name: str) -> List:
    """Scatter-family equations touching an operand of ``dtype_name``
    — the jaxpr-level form of the "no uint8 scatter" tree-record
    guarantee (more robust than regexing operand types out of the
    StableHLO text, where the type signature trails the region body)."""
    hits = []
    for eqn in iter_eqns(jaxpr):
        if not eqn.primitive.name.startswith("scatter"):
            continue
        if any(str(getattr(v.aval, "dtype", "")) == dtype_name
               for v in eqn.invars if hasattr(v, "aval")):
            hits.append(eqn)
    return hits


# -- StableHLO text helpers -------------------------------------------------

# ops whose presence means the module's shapes are not fully static
DYNAMIC_SHAPE_OPS = (
    "stablehlo.dynamic_reshape",
    "stablehlo.dynamic_broadcast_in_dim",
    "stablehlo.dynamic_iota",
    "stablehlo.dynamic_pad",
    "stablehlo.dynamic_gather",
    "stablehlo.dynamic_conv",
    "stablehlo.real_dynamic_slice",
)

# host-transfer / callback markers in lowered text
HOST_CALLBACK_MARKERS = (
    "stablehlo.infeed",
    "stablehlo.outfeed",
    "xla_python_cpu_callback",
    "xla_python_gpu_callback",
    "xla_ffi_python",
)

# jaxpr primitives that round-trip through the host per dispatch
HOST_CALLBACK_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "infeed", "outfeed", "host_callback_call",
})


def count_op(text: str, op: str) -> int:
    """Occurrences of a StableHLO op name in lowered module text."""
    return text.count(op)


def compiled_lines_from_file(hlo: str, op: str, filename: str) -> List[str]:
    """Instruction lines of compiled HLO text that run ``op`` and whose
    innermost source frame lies in a file ending with ``filename``.

    The HLO printer (jaxlib 0.9) no longer writes ``source_file=`` on
    each instruction: metadata carries ``stack_frame_id=N`` and the
    module ends with FileNames / FileLocations / StackFrames tables,
    frame -> location -> file."""
    def table(name: str, value: str) -> dict:
        """``{row id: captured value}`` of one trailing table."""
        m = re.search(rf"^{name}\n((?:\d+ .*\n?)+)", hlo, re.M)
        rows = re.findall(rf"^(\d+) .*?{value}", m.group(1), re.M) \
            if m else []
        return {int(k): v for k, v in rows}

    files = table("FileNames", r'"([^"]*)"')
    loc_file = table("FileLocations", r"file_name_id=(\d+)")
    frame_loc = table("StackFrames", r"file_location_id=(\d+)")
    out = []
    for ln in hlo.splitlines():
        m = re.search(r"stack_frame_id=(\d+)", ln)
        if m is None or f" {op}(" not in ln:
            continue
        loc = int(frame_loc.get(int(m.group(1)), 0))
        name = files.get(int(loc_file.get(loc, 0)), "")
        if name.endswith(filename):
            out.append(ln)
    return out


def dynamic_shape_markers(text: str) -> List[str]:
    """Dynamic-shape evidence in a lowered module: any dynamic-shape
    op, or an unranked/dynamic tensor type (``tensor<?``)."""
    found = [op for op in DYNAMIC_SHAPE_OPS if op in text]
    if "tensor<?" in text:
        found.append("tensor<?...> (dynamic dimension)")
    return found
