"""A configuration names its table (``table_seed``): every run of its cells
trains on that table, and ``--seed`` draws only the comparison's samples.
Three cases of one test: two seeds through ``run_cell`` on the CPU seam
give one table and one model, and the comparison's own numbers differ
between them; ``load_cell`` refuses a configuration without
``table_seed``; ``readings.py``, which takes a limit's readings, hands
each seed's run a table of that seed.

    python -m pytest perfbench/tests/test_table_seed.py -q
"""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), HERE]
import datagen
import modeltext
import readings
import reference
import run
import test_correct


def two_seeds_one_table(monkeypatch, tmp_path):
    import jax
    seen = {"table": [], "text": []}

    def recording(module, name, key, keep=lambda args, out: out):
        real = getattr(module, name)

        def wrapped(*args, **kw):
            out = real(*args, **kw)
            seen[key].append(keep(args, out))
            return out
        return wrapped
    monkeypatch.setitem(datagen.GENERATORS, "binary_dense",
                        recording(datagen, "binary_dense", "table"))
    monkeypatch.setattr(modeltext, "parse", recording(
        modeltext, "parse", "text", keep=lambda args, out: args[0]))
    # at this size the quantile sample and the host walk's rows would be the
    # whole table; cut both under it, as they are at the cells' size, so
    # that what --seed draws reaches the numbers compared
    quantile_edges = reference.quantile_edges
    monkeypatch.setattr(reference, "quantile_edges",
                        lambda X, seed, bins: quantile_edges(
                            X, seed, bins, sample=len(X) // 4))
    loaded = test_correct.small_cell()
    loaded["limits"]["compare"]["replay_rows"] = \
        loaded["config"]["rows"] // 4
    runs = []
    for seed in (test_correct.SEED, test_correct.SEED + 7919):
        for v in seen.values():
            v.clear()
        line, info = run.run_cell(loaded, seed, 0.5, False,
                                  jax.devices()[:1], interpret=True)
        assert line["correct"], info["verdict"]
        assert info["seed"] == seed
        assert info["table_seed"] == test_correct.SEED
        runs.append(dict(line=line, info=info, table=seen["table"][-1],
                         text=seen["text"][-1]))
    a, b = runs
    for x, y in zip(a["table"], b["table"]):
        assert x.tobytes() == y.tobytes()
    assert a["line"]["attempted"] == b["line"]["attempted"]
    assert a["text"] == b["text"]
    assert a["info"]["model_sha256"] == b["info"]["model_sha256"]
    for name in ("split_choice_gap_p90", "score_gap"):
        assert (a["line"]["compared"][name]["value"]
                != b["line"]["compared"][name]["value"]), name


def no_table_seed_is_refused(monkeypatch, tmp_path):
    real = run.load_json

    def without(*parts):
        data = real(*parts)
        if parts[-1].startswith("perfbench/configs/"):
            data.pop("table_seed")
        return data
    monkeypatch.setattr(run, "load_json", without)
    with pytest.raises(SystemExit, match="table_seed"):
        run.load_cell("criteo_train")


def readings_draw_a_table_a_seed(monkeypatch, tmp_path):
    handed = []

    def fake_run_cell(loaded, seed, seconds, trace, devices):
        handed.append((seed, loaded["config"]["table_seed"]))
        return ({"correct": True, "metrics": {}},
                {"table_seed": loaded["config"]["table_seed"],
                 "control_correct": False, "numbers": {}, "clocks": {}})
    monkeypatch.setattr(run, "require_chips", lambda jax, chips: ["chip"])
    monkeypatch.setattr(run, "run_cell", fake_run_cell)
    monkeypatch.chdir(tmp_path)
    readings.main(["--workload", "criteo_train", "--seeds", "11,22",
                   "--seconds", "1"])
    assert handed == [(11, 11), (22, 22)]
    assert (tmp_path / "chiprun_out" / "readings_criteo_train.jsonl").exists()


CASES = {f.__name__: f for f in (two_seeds_one_table, no_table_seed_is_refused,
                                 readings_draw_a_table_a_seed)}


@pytest.mark.parametrize("case", sorted(CASES))
def test_a_configuration_names_its_table(case, monkeypatch, tmp_path):
    CASES[case](monkeypatch, tmp_path)
