"""Tests for the `lightgbm_tpu.analysis` compiled-program lint
framework (static-analysis round).

Coverage contract (ISSUE acceptance):
- one minimal fixture program per HLO rule that VIOLATES it (the
  checker must flag it),
- the real registered entry points SATISFY every rule (the checker
  must pass — shared `analysis_programs` session fixture),
- suppression semantics (trailing line / standalone file scope /
  unused-suppression SUP001) and the JSON report schema.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lightgbm_tpu.analysis import (Context, Finding, RULES, run_rules,
                                   unsuppressed, walker)
from lightgbm_tpu.analysis.ast_rules import (JIT_SEEDS, SourceIndex,
                                             config_reads,
                                             documented_params,
                                             scan_host_calls,
                                             scan_python_branching)
from lightgbm_tpu.analysis.core import (Suppression, _apply_suppressions,
                                        parse_suppressions, render_json)
from lightgbm_tpu.analysis.hlo_rules import (check_carry_bound,
                                             check_dus_not_scatter,
                                             check_gather_t_invariance,
                                             check_no_donation,
                                             check_no_f64,
                                             check_no_host_callback,
                                             check_retrace_surface,
                                             check_static_shapes)
from lightgbm_tpu.analysis.programs import RETRACE_BOUNDS, Program

SRC = "lightgbm_tpu/boosting/gbdt.py"   # arbitrary attribution file


def _prog(name="fixture", jaxpr=None, lowered=None, text=None,
          **meta):
    return Program(name, SRC, jaxpr=jaxpr, lowered=lowered,
                   stablehlo_text=text, meta=meta)


# ---------------------------------------------------------------------------
# HLO rules: real entry points pass, seeded fixtures flag
# ---------------------------------------------------------------------------

def test_hlo_rules_pass_on_registered_entry_points(analysis_programs):
    ctx = Context(programs=analysis_programs)
    ids = [f"HLO00{i}" for i in range(1, 10)]
    findings = run_rules(ids, ctx=ctx, check_suppressions=False)
    assert not unsuppressed(findings), "\n".join(
        f"{f.rule} {f.location()}: {f.message}"
        for f in unsuppressed(findings))


def test_hlo001_flags_f64_fixture():
    with jax.enable_x64(True):
        jaxpr = jax.make_jaxpr(lambda x: x * 2)(
            jnp.zeros(3, jnp.float64)).jaxpr
    findings = check_no_f64(_prog(jaxpr=jaxpr))
    assert findings and findings[0].rule == "HLO001"
    assert "float64" in findings[0].message


def test_hlo002_flags_host_callback_fixture():
    def f(x):
        return jax.pure_callback(
            lambda v: np.asarray(v) * 2,
            jax.ShapeDtypeStruct((3,), jnp.float32), x)
    jaxpr = jax.make_jaxpr(f)(jnp.zeros(3, jnp.float32)).jaxpr
    findings = check_no_host_callback(_prog(jaxpr=jaxpr))
    assert findings and findings[0].rule == "HLO002"
    assert "pure_callback" in findings[0].message
    # text-level detection too (lowered custom_call marker)
    findings = check_no_host_callback(
        _prog(text='custom_call @xla_python_cpu_callback'))
    assert findings and findings[0].rule == "HLO002"


def test_hlo003_flags_fat_carry_fixture():
    def fat_scan(x):
        def body(c, _):
            return c + 1, (c, c * 2, c + 3, c * 4, c - 5)
        return jax.lax.scan(body, x, None, length=4)
    jaxpr = jax.make_jaxpr(fat_scan)(jnp.float32(0)).jaxpr
    findings = check_carry_bound(_prog(jaxpr=jaxpr,
                                       boost_chunk_len=4))
    assert findings and findings[0].rule == "HLO003"
    assert "5 loop-carried output buffers" in findings[0].message
    # a chunk program with NO scan at all is also a finding (the
    # dispatch structure itself regressed)
    jaxpr2 = jax.make_jaxpr(lambda x: x + 1)(jnp.float32(0)).jaxpr
    findings2 = check_carry_bound(_prog(jaxpr=jaxpr2,
                                        boost_chunk_len=4))
    assert findings2 and "no lax.scan" in findings2[0].message


def test_hlo004_flags_uint8_scatter_fixture():
    def scatter_u8(buf, idx, val):
        return buf.at[idx].set(val)
    jaxpr = jax.make_jaxpr(scatter_u8)(
        jnp.zeros((8,), jnp.uint8), jnp.zeros((3,), jnp.int32),
        jnp.zeros((3,), jnp.uint8)).jaxpr
    findings = check_dus_not_scatter(_prog(jaxpr=jaxpr,
                                           record_spec_len=17))
    assert any("scatter" in f.message for f in findings)
    # and a lowered module with too few DUS ops trips the count side
    findings = check_dus_not_scatter(_prog(text="module @m {}",
                                           record_spec_len=17))
    assert any("only 0 dynamic_update_slice" in f.message
               for f in findings)


def test_hlo005_flags_per_tree_gathers_fixture():
    def per_tree(x, idx, t_count):
        out = jnp.zeros((), jnp.float32)
        for t in range(t_count):          # gathers grow with T
            out = out + jnp.take(x, idx[t])
        return out
    progs = {}
    for t in (4, 12):
        jaxpr = jax.make_jaxpr(
            lambda x, i: per_tree(x, i, t))(
                jnp.zeros(32, jnp.float32),
                jnp.zeros(12, jnp.int32)).jaxpr
        progs[t] = _prog(f"fixture@T{t}", jaxpr=jaxpr,
                         gather_probe_t=t, depth=1)
    findings = check_gather_t_invariance(progs[4], progs[12])
    assert findings and findings[0].rule == "HLO005"
    assert "grew with tree count" in findings[0].message


def test_hlo006_flags_donated_fixture():
    lowered = jax.jit(lambda x: x * 2, donate_argnums=(0,)).lower(
        jnp.zeros((4,), jnp.float32))
    findings = check_no_donation(_prog(lowered=lowered,
                                       multi_shape=True))
    assert findings and findings[0].rule == "HLO006"
    # single-shape programs are exempt by design
    assert check_no_donation(_prog(lowered=lowered,
                                   multi_shape=False)) == []


def test_hlo007_flags_dynamic_shape_fixture():
    text = ('func.func @main(%arg0: tensor<?xf32>) {\n'
            '  %0 = stablehlo.dynamic_reshape %arg0 ...\n}')
    findings = check_static_shapes(_prog(text=text))
    assert findings and all(f.rule == "HLO007" for f in findings)
    msgs = "\n".join(f.message for f in findings)
    assert "dynamic_reshape" in msgs and "tensor<?" in msgs


def test_hlo008_flags_retrace_churn_fixture():
    findings = check_retrace_surface({"predict.level_ensemble": 9},
                                     {"predict.level_ensemble": 4})
    assert findings and findings[0].rule == "HLO008"
    assert check_retrace_surface({"predict.level_ensemble": 3},
                                 {"predict.level_ensemble": 4}) == []
    # unknown entry points carry no declared budget -> not flagged
    assert check_retrace_surface({"new.entry": 99}, {}) == []


def test_retrace_surface_within_bounds(analysis_programs):
    """HLO008 on the real probe build: the measured delta stays within
    the declared budget AND is non-vacuous (the probes really trace)."""
    analysis_programs.all_programs()
    delta = analysis_programs.retrace_delta()
    assert check_retrace_surface(delta, RETRACE_BOUNDS) == []
    assert delta.get("gbdt.fused_chunk", 0) >= 2


# ---------------------------------------------------------------------------
# trace-safety AST pass
# ---------------------------------------------------------------------------

FIXTURE_BAD = '''\
import math
import random
import time

import numpy as np
import jax.numpy as jnp


def _boost_one(x):
    y = _helper(x)
    if jnp.any(x > 0):
        x = x + 1
    return np.mean(x) + y


def _helper(x):
    t = time.time()
    r = random.random()
    return math.sin(t) + r


def _unreached(x):
    return np.median(x)
'''


def _fixture_index():
    return SourceIndex({"lightgbm_tpu/boosting/gbdt.py": FIXTURE_BAD})


def test_trc001_flags_host_calls_through_call_graph():
    idx = _fixture_index()
    fns = idx.reachable([("boosting/gbdt.py", "_boost_one")])
    assert {f.name for f in fns} == {"_boost_one", "_helper"}
    findings = scan_host_calls(idx, fns)
    flagged = {m for f in findings
               for m in ("np.mean", "time.time", "random.random",
                         "math.sin") if f"`{m}(...)`" in f.message}
    assert flagged == {"np.mean", "time.time", "random.random",
                       "math.sin"}
    # np.median in _unreached must NOT be flagged (not jit-reachable)
    assert not any("np.median" in f.message for f in findings)


def test_trc002_flags_python_branch_on_jnp():
    idx = _fixture_index()
    fns = idx.reachable([("boosting/gbdt.py", "_boost_one")])
    findings = scan_python_branching(idx, fns)
    assert len(findings) == 1
    assert findings[0].rule == "TRC002"
    assert "if" in findings[0].message


def test_jit_seeds_resolve_in_real_package():
    """Every declared seed must resolve against the live AST index —
    a rename of a seeded entry point fails here instead of silently
    shrinking the lint's reachability."""
    idx = SourceIndex(Context().sources)
    for suffix, name in JIT_SEEDS:
        assert any(f.path.endswith(suffix)
                   for f in idx.functions.get(name, [])), \
            f"seed {name} not found in {suffix}"
    # and the expansion covers the device-side modules
    fns = idx.reachable(JIT_SEEDS)
    paths = {f.path for f in fns}
    assert "lightgbm_tpu/ops/histogram.py" in paths
    assert "lightgbm_tpu/ops/split.py" in paths
    assert len(fns) > 50


# ---------------------------------------------------------------------------
# Config consistency
# ---------------------------------------------------------------------------

FAKE_CONFIG = '''\
import dataclasses


@dataclasses.dataclass
class Config:
    num_leaves: int = 31
    dead_knob: int = 0
'''


def test_cfg002_flags_never_read_knob():
    ctx = Context(sources={"lightgbm_tpu/config.py": FAKE_CONFIG})
    findings = run_rules(["CFG002"], ctx=ctx,
                         check_suppressions=False)
    live = unsuppressed(findings)
    assert [f for f in live if "dead_knob" in f.message]
    # num_leaves is read ("num_leaves" appears via attribute loads in
    # nothing here — fixture has no reads at all, so both flag; the
    # discriminating pass side is the real repo below)
    assert all(f.rule == "CFG002" for f in live)


def test_cfg001_flags_undocumented_knob():
    ctx = Context(sources={"lightgbm_tpu/config.py": FAKE_CONFIG})
    findings = run_rules(["CFG001"], ctx=ctx,
                         check_suppressions=False)
    assert any("dead_knob" in f.message for f in
               unsuppressed(findings))
    # num_leaves IS documented in the real docs/Parameters.md
    assert not any("`num_leaves`" in f.message
                   for f in unsuppressed(findings))


def test_config_contract_clean_on_real_repo():
    findings = run_rules(["CFG001", "CFG002", "TRC001", "TRC002"])
    live = unsuppressed(findings)
    assert not live, "\n".join(
        f"{f.rule} {f.location()}: {f.message}" for f in live)
    # the suppressions that waive the intentionally-inert knobs are
    # all USED (none stale) and carry reasons
    sup = [f for f in findings if f.suppressed]
    assert sup and all(f.reason for f in sup)


def test_config_reads_sees_getattr_and_attributes():
    reads = config_reads({
        "m.py": "x = cfg.alpha\ny = getattr(c, 'beta', 1)\n"
                "hasattr(c, 'gamma')\n"})
    assert {"alpha", "beta", "gamma"} <= reads


def test_documented_params_parses_tables():
    doc = "| Parameter | D |\n|---|---|\n| `alpha` | `1` |\n"
    assert documented_params(doc) == {"alpha"}


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

def test_parse_suppressions_trailing_and_standalone():
    text = ("x = 1  # lint: disable=TRC001(host side)\n"
            "# lint: disable=HLO006(legacy program, tracked in r11)\n")
    sups = parse_suppressions("f.py", text)
    assert [(s.rule, s.line, s.file_scope, s.reason) for s in sups] \
        == [("TRC001", 1, False, "host side"),
            ("HLO006", 2, True, "legacy program, tracked in r11")]


def test_apply_suppressions_line_and_file_scope():
    f1 = Finding(rule="TRC001", message="m", file="f.py", line=3)
    f2 = Finding(rule="TRC001", message="m", file="f.py", line=9)
    f3 = Finding(rule="HLO006", message="m", file="f.py", line=0)
    sups = [Suppression("f.py", 3, "TRC001", "why", False),
            Suppression("f.py", 1, "HLO006", "all", True)]
    _apply_suppressions([f1, f2, f3], sups)
    assert f1.suppressed and f1.reason == "why"
    assert not f2.suppressed            # different line, line scope
    assert f3.suppressed                # file scope covers line 0
    assert all(s.used for s in sups)


def test_suppressed_violation_and_unused_suppression_end_to_end():
    bad = ("import numpy as np\n\n\n"
           "def _boost_one(x):\n"
           "    return np.mean(x)  # lint: disable=TRC001(reviewed)\n")
    ctx = Context(sources={"lightgbm_tpu/boosting/gbdt.py": bad})
    findings = run_rules(["TRC001"], ctx=ctx)
    assert findings and all(f.suppressed for f in findings)
    assert not unsuppressed(findings)

    stale = "import numpy as np\n# lint: disable=TRC001(stale)\n"
    ctx = Context(sources={"lightgbm_tpu/boosting/gbdt.py": stale})
    findings = run_rules(["TRC001"], ctx=ctx)
    live = unsuppressed(findings)
    assert len(live) == 1 and live[0].rule == "SUP001"
    assert "unused suppression" in live[0].message


# ---------------------------------------------------------------------------
# JSON report, CLI, registry
# ---------------------------------------------------------------------------

def test_json_report_schema():
    findings = [Finding(rule="TRC001", message="m", file="f.py",
                        line=3),
                Finding(rule="HLO001", message="n", file="g.py",
                        line=0, suppressed=True, reason="why")]
    doc = json.loads(render_json(findings, ["TRC001", "HLO001"]))
    assert doc["version"] == 1
    assert doc["rules_run"] == ["TRC001", "HLO001"]
    assert doc["counts"] == {"total": 2, "suppressed": 1,
                             "unsuppressed": 1}
    assert doc["clean"] is False
    for f in doc["findings"]:
        assert set(f) == {"rule", "message", "file", "line",
                          "suppressed", "reason"}
    assert json.loads(render_json([], ["HLO001"]))["clean"] is True


def test_rule_registry_has_issue_contract():
    run_rules(["CFG001"], Context(sources={}))   # force registration
    ids = set(RULES)
    expected = {f"HLO00{i}" for i in range(1, 10)} \
        | {"TRC001", "TRC002", "CFG001", "CFG002",
           "CARRY001", "TEL001"}
    assert expected <= ids
    for rid in expected:
        assert RULES[rid].title
    # every HLO rule declares the incident it encodes
    assert all(RULES[f"HLO00{i}"].incident for i in range(1, 10))


def test_rehomed_lints_pass_on_real_repo():
    findings = run_rules(["CARRY001", "TEL001"],
                         check_suppressions=False)
    live = unsuppressed(findings)
    assert not live, "\n".join(f.message for f in live)


def test_cli_json_subset_and_unknown_rule(capsys, monkeypatch):
    from lightgbm_tpu.analysis.__main__ import main
    rc = main(["--rules", "CFG001,CFG002,TEL001", "--json"])
    out = capsys.readouterr().out.strip().splitlines()[-1]
    doc = json.loads(out)
    assert rc == 0 and doc["clean"] is True
    assert doc["rules_run"] == ["CFG001", "CFG002", "TEL001"]

    assert main(["--rules", "NOPE999"]) == 2

    rc = main(["--list"])
    out = capsys.readouterr().out
    assert rc == 0 and "HLO004" in out and "CARRY001" in out


def test_cli_exits_nonzero_on_violation(capsys, monkeypatch):
    """The acceptance bit: a seeded violation drives the CLI exit
    status non-zero (fixture Context swapped in under the engine)."""
    import lightgbm_tpu.analysis.core as core
    from lightgbm_tpu.analysis.__main__ import main
    bad = "import numpy as np\n\n\ndef _boost_one(x):\n" \
          "    return np.mean(x)\n"
    fixture = Context(sources={"lightgbm_tpu/boosting/gbdt.py": bad})
    monkeypatch.setattr(core, "Context", lambda: fixture)
    rc = main(["--rules", "TRC001", "--json"])
    doc = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 1
    assert doc["clean"] is False
    assert doc["counts"]["unsuppressed"] == 1
