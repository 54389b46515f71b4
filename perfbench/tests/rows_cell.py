"""``criteo_r25_train`` at a size a test run can hold on the CPU: the
configuration's own file with rows and leaves shrunk to test_correct.py's
size and its table drawn from SEED (so small_limits.json's readings are
this size's: row segments grow the one-segment trees), and the row
segment of the int8 histogram forced down from 2^24 rows to 32,768 of
the table's 40,960 padded ones (by the kernels' module constant, not by
an option): two segments, the second
uneven; the reference's pass in three row super-blocks likewise.  Shared
by tests/test_r25_cell.py (tier-1)."""
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]
import run

SEED = 2147484005          # above 2**31 - 1, as the driver's can be
ROWS, LEAVES, SEGMENT = 40000, 31, 32768
SUPER_ROWS = 16384         # three row super-blocks of the reference's pass


def small_cell():
    loaded = run.load_cell("criteo_r25_train")
    loaded["config"].update(rows=ROWS, table_seed=SEED)
    loaded["config"]["params"].update(num_leaves=LEAVES)
    loaded["traffic"]["dispatch_chunk"] = 2
    kind = loaded["limits"]["compare"]["kind"]       # the cell's own
    with open(os.path.join(HERE, "small_limits.json")) as f:
        loaded["limits"] = json.load(f)
    loaded["limits"]["compare"]["kind"] = kind
    return loaded


def drive(monkeypatch, seed=SEED):
    """One run of the small cell: (line, info, the answer the run handed
    to the comparison)."""
    import jax
    import reference_rows
    from lightgbm_tpu.ops import histogram
    monkeypatch.setattr(histogram, "QUANT_SEGMENT_ROWS", SEGMENT)
    monkeypatch.setattr(reference_rows, "SUPER_ROWS", SUPER_ROWS)
    real, answers = reference_rows.gbdt_teacher_forced_rows, []

    def recording(answer, *args, **kw):
        answers.append(answer)
        return real(answer, *args, **kw)
    monkeypatch.setattr(reference_rows, "gbdt_teacher_forced_rows", recording)
    line, info = run.run_cell(small_cell(), seed, 0.5, False,
                              jax.devices()[:1], interpret=True)
    return line, info, answers[-1]
