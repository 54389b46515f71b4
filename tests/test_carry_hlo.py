"""HLO regression gate for the packed tree carry (round 7), asserted
through the shared `lightgbm_tpu.analysis` engine since the
static-analysis round.

ROOFLINE round-6 traced the dispatch-chunk degradation (per-tree ≈
25.75 + 0.075·chunk ms on v5e) to the TPU backend's handling of the
fused chunk's EIGHTEEN O(chunk)-sized loop-carried output stacks — one
per TreeArrays field plus the num_leaves series.  The round-7 fix
carries each tree as ONE byte-packed record (tree.TreeRecordLayout),
so the scan's output side holds two buffers: the uint8 record stack
and the num_leaves series.

These tests pin that structure at the compiler seam, for chunk 4 AND
16 (the auto-policy probe sizes), so a refactor that quietly
reintroduces per-field output stacks — or turns the static-offset
record writes back into scattered updates — fails the suite instead of
silently re-opening the chunk slope.  The jaxpr walking and the
bound itself live in ``lightgbm_tpu/analysis`` (rules HLO003/HLO004 +
``walker``): CI's `python -m lightgbm_tpu.analysis` and this file
assert the SAME guarantee through the SAME code.
"""
import re

import jax
import pytest

from lightgbm_tpu.analysis import walker
from lightgbm_tpu.analysis.hlo_rules import (MAX_CARRY_OUTPUT_BUFFERS,
                                             check_carry_bound,
                                             check_dus_not_scatter,
                                             check_no_donation)
from lightgbm_tpu.analysis.programs import build_probe_gbdt, chunk_args

# the legacy per-field carry this refactor retired: 17 TreeArrays
# fields + the num_leaves series
LEGACY_CARRY_OUTPUT_BUFFERS = 18


def _scan_output_stacks(g, chunk):
    """Number of O(chunk) output buffers (ys) the fused chunk's
    boosting scan stacks — read off the jaxpr's scan primitive through
    the shared walker, the exact quantity the backend turns into
    loop-carried output stores."""
    fn = g._build_fused_chunk(chunk)
    jaxpr = jax.make_jaxpr(fn)(*chunk_args(g, chunk)).jaxpr
    assert walker.find_scans(jaxpr), \
        "fused chunk no longer lowers through lax.scan"
    # the boosting scan is the one of length == chunk (inner kernels
    # may scan too, but over other extents)
    boost = walker.find_scans(jaxpr, length=chunk)
    assert boost, f"no scan of length {chunk} in the fused chunk"
    return walker.scan_output_stacks(boost[0])


@pytest.mark.parametrize("chunk", [4, 16])
def test_packed_carry_bounds_output_buffers(analysis_programs, chunk):
    """Rule HLO003 on the registered fused-chunk programs: the carry
    tuple holds at most MAX_CARRY_OUTPUT_BUFFERS O(chunk) output
    stacks (the packed path uses 2: records + num_leaves)."""
    assert analysis_programs.gbdt._packed_carry, \
        "packed_tree_carry must default on"
    prog = analysis_programs.fused_chunk(chunk)
    findings = check_carry_bound(prog)
    assert not findings, "\n".join(f.message for f in findings)


def test_legacy_carry_counter_discriminates(analysis_programs):
    """The same counter must report the 18-buffer legacy carry — if it
    stopped discriminating, the HLO003 bound would be vacuous."""
    g = build_probe_gbdt(packed_tree_carry="off")
    assert not g._packed_carry
    assert _scan_output_stacks(g, 4) == LEGACY_CARRY_OUTPUT_BUFFERS
    # sanity: the packed default stays within the rule bound (probe
    # model reused from the session fixture — no extra training run)
    assert _scan_output_stacks(analysis_programs.gbdt, 4) \
        <= MAX_CARRY_OUTPUT_BUFFERS


def test_record_writes_lower_to_dynamic_update_slice(analysis_programs):
    """Rule HLO004: every tree-record field write lowers to a
    static-offset dynamic-update-slice (the in-place form), never a
    uint8 scatter, and the compiled module keeps DUS instructions
    attributed to tree.py (XLA's simplifier did not rewrite them into
    copies)."""
    prog = analysis_programs.fused_chunk(4)
    findings = check_dus_not_scatter(prog)
    assert not findings, "\n".join(f.message for f in findings)
    # the positive side the rule asserts must not be vacuous here:
    # the program really does carry one DUS per record field
    assert walker.count_op(prog.stablehlo,
                           "stablehlo.dynamic_update_slice") \
        >= prog.meta["record_spec_len"]


def test_donation_stays_off_fused_programs(analysis_programs):
    """Rule HLO006 on both probe chunks + the per-iteration step: the
    r7 heap-corruption bisect pinned donation OFF these multi-shape
    programs."""
    for prog in (analysis_programs.fused_chunk(4),
                 analysis_programs.fused_chunk(16),
                 analysis_programs.fused_step()):
        findings = check_no_donation(prog)
        assert not findings, "\n".join(f.message for f in findings)
        assert prog.donated_args, \
            f"{prog.name}: no args_info — the donation check went blind"


def test_compiled_while_carries_packed_record_stack(analysis_programs):
    """The compiled chunk's outer while-loop tuple must hold the uint8
    record stack (chunk, K, record_size) — the single packed output
    buffer the dispatch scan carries."""
    prog = analysis_programs.fused_chunk(4)
    rec = prog.meta["record_size"]
    # "%while.N = (carried tuple type) while(%operand), ..." — the
    # printer writes the carried types before the opcode
    pat = re.compile(r"u8\[%d,1,%d\][^)]*\) while\(" % (4, rec))
    assert any(pat.search(ln)
               for ln in prog.compiled_text.splitlines()), (
        f"no while loop carries the packed u8[4,1,{rec}] record "
        "stack in the compiled chunk")
