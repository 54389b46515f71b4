"""TEL001 — telemetry span/stage/phase names <-> docs/OBSERVABILITY.md
span map, both directions (re-homed from
``scripts/check_telemetry_coverage.py``, now a thin wrapper here).

The span map is the contract between the instrumentation and anyone
reading a Perfetto trace — an undocumented span is a mystery slice in
the UI, and a documented-but-deleted span means the doc (and any
dashboard built on it) silently rotted.
"""
from __future__ import annotations

import os
import re
from typing import Dict, List, Set

from .core import Finding, rule

CALL_RE = re.compile(
    r"\.(?:span|start_span|stage|phase)\(\s*(?:f?)([\"'])([^\"']+)\1")
DYNAMIC_RE = re.compile(r"\.(?:span|start_span|stage|phase)\(\s*[^\"')]")
DOC = "docs/OBSERVABILITY.md"

# telemetry.py itself defines the API (its internal span("device_wait")
# helper IS a real span and is scanned too); profile_train.py and
# bench.py sit outside the package but emit real spans
EXTRA_SOURCES = ("scripts/profile_train.py", "bench.py")


def code_spans(sources: Dict[str, str]) -> Dict[str, Set[str]]:
    """{span name: files using it} plus dynamic-name findings are
    handled in the rule body (they cannot be in the glossary)."""
    names: Dict[str, Set[str]] = {}
    for rel, src in sources.items():
        for m in CALL_RE.finditer(src):
            names.setdefault(m.group(2), set()).add(rel)
    return names


def dynamic_span_findings(sources: Dict[str, str]) -> List[Finding]:
    out: List[Finding] = []
    for rel, src in sources.items():
        for m in DYNAMIC_RE.finditer(src):
            frag = src[m.start():m.start() + 60].splitlines()[0]
            # allow the API definition sites in telemetry.py and
            # variable-forwarding helpers that pass a `name` parameter
            if rel.endswith("telemetry.py") or re.match(
                    r"\.(?:span|start_span|stage|phase)\(\s*(?:self|name|f?\")",
                    frag):
                continue
            line = src[:m.start()].count("\n") + 1
            out.append(Finding(
                rule="TEL001", file=rel, line=line,
                message="dynamic span/phase name cannot be linted "
                        f"against the glossary: {frag!r}"))
    return out


def doc_spans(text: str) -> Set[str]:
    names: Set[str] = set()
    in_table = False
    for line in text.splitlines():
        if line.startswith("| Span |") or line.startswith("| Phase |"):
            in_table = True
            continue
        if in_table:
            m = re.match(r"\|\s*`([^`]+)`\s*\|", line)
            if m:
                names.add(m.group(1))
            elif not line.startswith("|"):
                in_table = False
    return names


@rule("TEL001", "span/phase names consistent with the "
                "docs/OBSERVABILITY.md span map, both directions",
      incident="r9 telemetry subsystem")
def _tel001(ctx) -> List[Finding]:
    sources = dict(ctx.sources)
    for rel in EXTRA_SOURCES:
        path = os.path.join(ctx.repo, rel)
        if os.path.exists(path) and rel not in sources:
            with open(path) as fh:
                sources[rel] = fh.read()

    doc_path = os.path.join(ctx.repo, DOC)
    try:
        with open(doc_path) as fh:
            doc = doc_spans(fh.read())
    except FileNotFoundError:
        return [Finding(rule="TEL001", file=DOC,
                        message=f"{DOC} missing — the span map is the "
                                "observability contract")]
    out = dynamic_span_findings(sources)
    code = code_spans(sources)
    if not doc:
        out.append(Finding(
            rule="TEL001", file=DOC,
            message=f"no span map tables parsed from {DOC}"))
    for name, sites in sorted(code.items()):
        if name not in doc:
            out.append(Finding(
                rule="TEL001", file=sorted(sites)[0],
                message=f"span {name!r} (used in "
                        f"{', '.join(sorted(sites))}) is missing from "
                        f"the {DOC} span map"))
    for name in sorted(doc - set(code)):
        out.append(Finding(
            rule="TEL001", file=DOC,
            message=f"{DOC} documents span {name!r} but no span(/"
                    "phase( call with that name exists in the code"))
    return out
