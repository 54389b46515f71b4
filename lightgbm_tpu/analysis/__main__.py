"""CLI for the compiled-program lint framework.

Usage::

    python -m lightgbm_tpu.analysis                # text report
    python -m lightgbm_tpu.analysis --json         # machine output
    python -m lightgbm_tpu.analysis --rules HLO003,HLO004
    python -m lightgbm_tpu.analysis --list         # rule glossary

Exit status: 0 clean, 1 unsuppressed finding(s), 2 usage error.
``scripts/bench_smoke.sh`` runs the ``--json`` form and fails CI on
any unsuppressed finding.
"""
from __future__ import annotations

import argparse
import os
import sys


def main(argv=None) -> int:
    # program rules lower on the CPU seam and must not take the chip
    # (one process holds it at a time).
    # The parent package may have imported jax already (python -m
    # imports it first), so pin the live config too, not just the env.
    if not os.environ.get("JAX_PLATFORMS"):
        os.environ["JAX_PLATFORMS"] = "cpu"
        try:
            import jax
            jax.config.update("jax_platforms", "cpu")
        except Exception:  # pragma: no cover - jax-less source checks
            pass
    ap = argparse.ArgumentParser(
        prog="python -m lightgbm_tpu.analysis",
        description="static analysis over the lowered hot programs "
                    "and the package source (docs/STATIC_ANALYSIS.md)")
    ap.add_argument("--json", action="store_true",
                    help="one JSON document instead of the text report")
    ap.add_argument("--rules", default="",
                    help="comma-separated rule IDs (default: all)")
    ap.add_argument("--list", action="store_true",
                    help="print the rule glossary and exit")
    args = ap.parse_args(argv)

    from .core import RULES, render_json, render_text, run_rules, \
        unsuppressed
    from . import ast_rules, hlo_rules, layout_rule, teldoc_rule  # noqa: F401

    if args.list:
        for rid in sorted(RULES):
            r = RULES[rid]
            inc = f"  [{r.incident}]" if r.incident else ""
            print(f"{rid}  {r.title}{inc}")
        return 0

    rule_ids = [r.strip() for r in args.rules.split(",") if r.strip()] \
        or None
    try:
        findings = run_rules(rule_ids)
    except ValueError as e:
        print(str(e), file=sys.stderr)
        return 2
    ids = rule_ids or sorted(RULES)
    if args.json:
        print(render_json(findings, ids))
    else:
        sys.stdout.write(render_text(findings, ids))
    return 1 if unsuppressed(findings) else 0


if __name__ == "__main__":
    sys.exit(main())
