"""The wide dense table's cell (``epsilon_train``, perfbench) at a size
the CPU holds, through the benchmark's own ``run_cell`` and the
program's interpret seam: the factored kernel over two group chunks
grows the model of the one-chunk kernel, and the plain reference finds
it correct."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "perfbench", "tests"))
import wide_cell


@pytest.fixture(scope="module")
def runs():
    out = {}
    for chunked in (True, False):
        with pytest.MonkeyPatch.context() as mp:
            out[chunked] = wide_cell.drive(mp, chunked=chunked)
    return out


def test_small_epsilon_is_correct_over_group_chunks(runs):
    line, info, _ = runs[True]
    assert info["gauges"]["grower.num_groups"] == wide_cell.FEATURES
    assert info["gauges"]["grower.hist_group_chunk"] == 32
    assert info["gauges"]["grower.hist_group_chunks"] == 2
    assert info["gauges"]["grower.hist_kernel"] == "fused_tiled"
    assert info["gauges"]["grower.hist_cache_mb"] > 0
    assert line["correct"], info["verdict"]
    assert line["failed"] == 0 and line["attempted"] >= 2
    assert info["control_correct"] is False


def test_group_chunks_grow_the_one_chunk_model(runs):
    _, info, one = runs[False]
    assert info["gauges"]["grower.hist_group_chunks"] == 1
    assert info["gauges"]["grower.hist_group_chunk"] == wide_cell.FEATURES
    # the window is timed, so the runs differ in how many trees they
    # grew: set-up's two and the window's first two, in full
    trees, want = ([block.split("\n\n")[0]
                    for block in text.split("Tree=")[1:5]]
                   for text in (runs[True][2], one))
    assert len(want) == 4 and trees == want
