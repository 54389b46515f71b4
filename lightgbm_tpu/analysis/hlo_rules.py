"""Program-invariant rules HLO001-HLO009.

Each rule encodes one hard-won compiled-program guarantee as a check
over the registered entry points' lowered artifacts (see
``programs.py``).  The per-program check functions are module-level so
``tests/test_analysis.py`` can aim them at seeded fixture programs;
the registered rule just fans a check across ``ctx.programs``.

Incident index (docs/STATIC_ANALYSIS.md carries the full glossary):

- r6: per-field loop-carried output stacks made per-tree cost grow
  with chunk length (HLO003), and scattered record writes were the
  degenerate lowering the fix had to avoid (HLO004).
- r7: buffer donation on multi-shape jitted programs corrupted the
  native heap (HLO006).
- r8: the level descent's gather count must stay T-independent or
  serving regresses to the per-tree walk (HLO005); the serving bucket
  ladder bounds the retrace surface (HLO008).
- standing TPU discipline: f32 accumulation everywhere (HLO001), no
  host round-trips inside hot programs (HLO002), fully static shapes
  (HLO007).
- r21: the histogram compression programs (precision-tiered
  accumulation, compressed histogram exchange) re-assert both
  standing disciplines at their own probe surfaces (HLO009).
"""
from __future__ import annotations

from typing import Dict, List

from . import walker
from .core import Finding, rule

MAX_CARRY_OUTPUT_BUFFERS = 4


# -- per-program checks (fixture-testable) ----------------------------------

def check_no_f64(program) -> List[Finding]:
    """HLO001: no float64 anywhere in the program."""
    out: List[Finding] = []
    if program.jaxpr is not None:
        bad = sorted(d for d in walker.jaxpr_dtypes(program.jaxpr)
                     if d in ("float64", "complex128"))
        for d in bad:
            out.append(Finding(
                rule="HLO001", file=program.source,
                message=f"program {program.name}: {d} value in the "
                        "jaxpr — a silent f64 promotion doubles HBM "
                        "traffic and falls off the MXU fast path"))
    text = program.stablehlo
    if text and not out and "f64" in text:
        out.append(Finding(
            rule="HLO001", file=program.source,
            message=f"program {program.name}: f64 type in lowered "
                    "StableHLO"))
    return out


def check_no_host_callback(program) -> List[Finding]:
    """HLO002: no host callback / infeed / outfeed in a hot program."""
    out: List[Finding] = []
    if program.jaxpr is not None:
        prims = walker.primitive_names(program.jaxpr) \
            & walker.HOST_CALLBACK_PRIMITIVES
        for p in sorted(prims):
            out.append(Finding(
                rule="HLO002", file=program.source,
                message=f"program {program.name}: host-callback "
                        f"primitive `{p}` — every dispatch would "
                        "round-trip through Python"))
    text = program.stablehlo
    if text and not out:
        for marker in walker.HOST_CALLBACK_MARKERS:
            if marker in text:
                out.append(Finding(
                    rule="HLO002", file=program.source,
                    message=f"program {program.name}: `{marker}` in "
                            "lowered StableHLO"))
    return out


def check_carry_bound(program,
                      bound: int = MAX_CARRY_OUTPUT_BUFFERS
                      ) -> List[Finding]:
    """HLO003: the boosting scan stacks at most ``bound`` O(chunk)
    output buffers (packed carry: records + num_leaves = 2)."""
    chunk = program.meta.get("boost_chunk_len")
    if not chunk or program.jaxpr is None:
        return []
    scans = walker.find_scans(program.jaxpr)
    if not scans:
        return [Finding(
            rule="HLO003", file=program.source,
            message=f"program {program.name}: no lax.scan left in the "
                    "fused chunk — the dispatch loop was unrolled or "
                    "restructured; the carry bound cannot be checked")]
    boost = walker.find_scans(program.jaxpr, length=chunk)
    if not boost:
        return [Finding(
            rule="HLO003", file=program.source,
            message=f"program {program.name}: no scan of length "
                    f"{chunk} (the boosting scan) in the fused chunk")]
    ys = walker.scan_output_stacks(boost[0])
    if ys > bound:
        return [Finding(
            rule="HLO003", file=program.source,
            message=f"program {program.name}: boosting scan stacks "
                    f"{ys} loop-carried output buffers (bound "
                    f"{bound}) — the r6 diagnosis: per-field stacks "
                    "are what made per-tree cost grow with chunk "
                    "length")]
    return []


def check_dus_not_scatter(program) -> List[Finding]:
    """HLO004: tree-record writes lower to static-offset
    dynamic-update-slice, never to a uint8 scatter, and the compiled
    module keeps DUS instructions attributed to tree.py."""
    spec_len = program.meta.get("record_spec_len")
    if not spec_len:
        return []
    out: List[Finding] = []
    if program.jaxpr is not None:
        for eqn in walker.scatter_eqns_with_dtype(program.jaxpr,
                                                  "uint8"):
            out.append(Finding(
                rule="HLO004", file=program.source,
                message=f"program {program.name}: a tree-record write "
                        f"lowered to `{eqn.primitive.name}` on a uint8 "
                        "operand — record emission regressed from "
                        "static-offset dynamic-update-slice to "
                        "scatter"))
    text = program.stablehlo
    if text is not None:
        n_dus = walker.count_op(text, "stablehlo.dynamic_update_slice")
        if n_dus < spec_len:
            out.append(Finding(
                rule="HLO004", file=program.source,
                message=f"program {program.name}: only {n_dus} "
                        "dynamic_update_slice ops in the lowered "
                        f"chunk — expected one per record field "
                        f"({spec_len}); record emission regressed"))
    hlo = program.compiled_text
    if hlo is not None and not out:
        if not walker.compiled_lines_from_file(
                hlo, "dynamic-update-slice", "tree.py"):
            out.append(Finding(
                rule="HLO004", file=program.source,
                message=f"program {program.name}: compiled HLO carries "
                        "no dynamic-update-slice attributed to tree.py "
                        "— XLA rewrote the record writes out of "
                        "in-place form"))
    return out


def check_gather_t_invariance(small, large) -> List[Finding]:
    """HLO005: the level descent's gather count is independent of the
    tree count, and within the per-level budget (8/level + leaf
    fetch)."""
    out: List[Finding] = []
    counts = {p.meta["gather_probe_t"]:
              walker.count_primitive(p.jaxpr, "gather")
              for p in (small, large)}
    ts = sorted(counts)
    if counts[ts[0]] != counts[ts[1]]:
        out.append(Finding(
            rule="HLO005", file=large.source,
            message=f"level-descent gather count grew with tree count "
                    f"({{T={ts[0]}: {counts[ts[0]]}, T={ts[1]}: "
                    f"{counts[ts[1]]}}}) — the descent regressed to "
                    "per-tree gathers"))
    depth = large.meta.get("depth", 6)
    budget = depth * 8 + 2
    if counts[ts[1]] > budget:
        out.append(Finding(
            rule="HLO005", file=large.source,
            message=f"{counts[ts[1]]} gathers for depth {depth} — "
                    f"over the level-synchronous budget ({budget}: "
                    "8/level + leaf fetch)"))
    return out


def check_no_donation(program) -> List[Finding]:
    """HLO006: no donated input buffers on a multi-shape jitted
    program (the r7 native-heap-corruption root cause)."""
    if not program.meta.get("multi_shape"):
        return []
    donated = program.donated_args
    n = sum(donated)
    if n:
        return [Finding(
            rule="HLO006", file=program.source,
            message=f"program {program.name}: {n} donated input "
                    "buffer(s) — donation on a multi-shape jitted "
                    "program is the bisected r7 heap-corruption root "
                    "cause (glibc corrupted double-linked list); keep "
                    "donate_argnums off these programs")]
    return []


def check_static_shapes(program) -> List[Finding]:
    """HLO007: no dynamic-shape ops in the lowered module."""
    text = program.stablehlo
    if text is None:
        return []
    return [Finding(
        rule="HLO007", file=program.source,
        message=f"program {program.name}: dynamic-shape lowering "
                f"`{m}` — hot programs must be fully static so one "
                "compilation serves the bucket/chunk ladder")
        for m in walker.dynamic_shape_markers(text)]


def check_retrace_surface(delta: Dict[str, int],
                          bounds: Dict[str, int]) -> List[Finding]:
    """HLO008: distinct traced signatures per entry point stay within
    the declared probe budget."""
    out: List[Finding] = []
    for fn, n in sorted(delta.items()):
        bound = bounds.get(fn)
        if bound is None:
            continue
        if n > bound:
            out.append(Finding(
                rule="HLO008", file="lightgbm_tpu/telemetry.py",
                message=f"entry point `{fn}` traced {n} distinct "
                        f"signatures during the probe build (budget "
                        f"{bound}) — each is an XLA compilation; the "
                        "retrace surface regressed past the declared "
                        "shape ladder"))
    return out


# -- registered rules -------------------------------------------------------

@rule("HLO001", "no float64 anywhere in hot programs",
      incident="standing f32-accumulation discipline",
      needs_programs=True)
def _hlo001(ctx) -> List[Finding]:
    out: List[Finding] = []
    for p in ctx.programs.all_programs():
        out.extend(check_no_f64(p))
    return out


@rule("HLO002", "no host callback / infeed in hot programs",
      incident="standing no-host-round-trip discipline",
      needs_programs=True)
def _hlo002(ctx) -> List[Finding]:
    out: List[Finding] = []
    for p in ctx.programs.all_programs():
        out.extend(check_no_host_callback(p))
    return out


@rule("HLO003", "fused-chunk carried-output-stack bound (packed carry)",
      incident="r6 chunk-slope diagnosis / r7 packed carry",
      needs_programs=True)
def _hlo003(ctx) -> List[Finding]:
    out: List[Finding] = []
    for chunk in (4, 16):
        out.extend(check_carry_bound(ctx.programs.fused_chunk(chunk)))
    return out


@rule("HLO004", "tree-record writes are DUS, not scatter",
      incident="r7 packed-record emission",
      needs_programs=True)
def _hlo004(ctx) -> List[Finding]:
    return check_dus_not_scatter(ctx.programs.fused_chunk(4))


@rule("HLO005", "level-descent gather count is tree-count-invariant",
      incident="r8 ensemble-vectorized predict",
      needs_programs=True)
def _hlo005(ctx) -> List[Finding]:
    return check_gather_t_invariance(ctx.programs.predict_level(4),
                                     ctx.programs.predict_level(12))


@rule("HLO006", "donation banned on multi-shape fused programs",
      incident="r7 native-heap-corruption bisect",
      needs_programs=True)
def _hlo006(ctx) -> List[Finding]:
    out: List[Finding] = []
    for p in ctx.programs.all_programs():
        out.extend(check_no_donation(p))
    return out


@rule("HLO007", "no dynamic-shape ops in hot programs",
      incident="standing static-shape discipline",
      needs_programs=True)
def _hlo007(ctx) -> List[Finding]:
    out: List[Finding] = []
    for p in ctx.programs.all_programs():
        out.extend(check_static_shapes(p))
    return out


@rule("HLO008", "retrace surface bounded per entry point",
      incident="r8 serving bucket ladder / r9 retrace sentinel",
      needs_programs=True)
def _hlo008(ctx) -> List[Finding]:
    from .programs import RETRACE_BOUNDS
    ctx.programs.all_programs()      # force every probe build first
    return check_retrace_surface(ctx.programs.retrace_delta(),
                                 RETRACE_BOUNDS)


@rule("HLO009", "tiered accumulation f32-clean; exchange codec "
                "device-resident",
      incident="r21 histogram compression arc",
      needs_programs=True)
def _hlo009(ctx) -> List[Finding]:
    """The round-21 compression programs uphold the standing
    disciplines at their own probe surfaces: the precision-tiered
    tree step (int32 accumulation + f32 fix-up) must introduce no
    f64 promotion, and the ``hist_exchange`` codec's quantize /
    pmax-scale / psum / reconstruct chain must lower with no host
    callback — a callback inside the exchange would serialize every
    per-pass histogram sum on the host."""
    probes = [ctx.programs.hist_tiered(),
              ctx.programs.hist_exchange("q16"),
              ctx.programs.hist_exchange("q8")]
    out: List[Finding] = []
    for p in probes:
        for f in check_no_f64(p) + check_no_host_callback(p):
            out.append(Finding(rule="HLO009", file=f.file,
                               line=f.line, message=f.message))
    return out
