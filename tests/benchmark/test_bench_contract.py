"""One run of `benchmarks/run.py` as far as a CPU can take it: off a TPU
it refuses to measure; with --rehearse it runs the cell's tiny preset
through every phase, names the platform it ran on and prints no time."""
import json
import os

import pytest

from bench_overlay import (add_predict_cell, add_train_cell,
                           copy_of_the_benchmark)
from benchmarks import run

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
                 "compared"}


def _parsed(stdout):
    out = stdout.strip().splitlines()
    # the program logs to standard output too; the result is the last line
    return json.loads(out[-1]), [json.loads(line[len("[bench] "):])
                                 for line in out[:-1]
                                 if line.startswith("[bench] ")]


def _last_line(capsys):
    return _parsed(capsys.readouterr().out)


def test_without_a_tpu_nothing_is_measured(capsys):
    assert run.main(["--workload", "higgs-int8.train", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) == run.NO_CHIP_EXIT
    captured = capsys.readouterr()
    assert captured.out == ""
    assert "needs 1 TPU chip" in captured.err


def test_an_unknown_cell_is_refused():
    with pytest.raises(SystemExit, match="no workload"):
        run.main(["--workload", "no-such-cell", "--rehearse"])


@pytest.mark.parametrize("cell,trace,named", [
    ("higgs-int8.train", 0, {"train_iter_ms", "setup_s"}),
    ("higgs-int8.train", 1, {"entry.host_ms_per_iter", "setup.data_s",
                             "setup.bin_s", "setup.bin_256k_s",
                             "setup.booster_s", "setup.compile_s",
                             "setup.warmup_s", "setup.check_s"}),
    ("higgs-int8.predict", 0, {"predict_mrows_per_s", "setup_s"}),
    ("higgs-int8.predict", 1, {"predict.host_ms_per_call", "setup.data_s",
                               "setup.model_s", "setup.booster_s",
                               "setup.compile_s", "setup.check_s"}),
])
def test_rehearsal_prints_the_contracts_line_and_no_time(
        tmp_path, capsys, cell, trace, named):
    """A train cell of BENCHMARK.json, and the predict cell PERF.md keeps
    for later, brought as a later PR would bring it."""
    root = copy_of_the_benchmark(tmp_path)
    if cell.endswith(".predict"):
        add_predict_cell(root)
    assert run.main(["--workload", cell, "--seed", "3", "--seconds", "0.5",
                     "--trace", str(trace), "--rehearse"], root=root) == 0
    captured = capsys.readouterr()
    (last, said), err = _parsed(captured.out), captured.err
    assert set(last) == CONTRACT_KEYS          # a CPU trace has no breakdown
    # each number compared beside its limit, last in the line and last on
    # standard error, every one within its limit in a run that is correct
    assert list(last)[-1] == "compared"
    compared = last["compared"]
    assert compared["problems"] == {"value": 0, "limit": 0}
    assert "walker_diff" in compared
    if cell.endswith(".train"):
        assert {"gain_shortfall", "leaf_value_off_of_allowed",
                "f32_quality_gap", "own_quality_gap"} <= set(compared)
    assert all(0 <= pair["value"] <= pair["limit"]
               for pair in compared.values()), compared
    told = [line.split() for line in err.strip().splitlines()
            if line.startswith("compared ")]
    assert [t[1] for t in told] == list(compared)
    assert err.strip().splitlines()[-1].startswith("compared problems 0 ")
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1
    assert last["device"]["platform"] == "cpu"
    assert set(last["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # counts only: every metric the CPU could produce is named, none valued
    assert set(last["metrics"]) == named
    assert all(m["value"] is None and m["unit"]
               for m in last["metrics"].values())
    verdict = [s for s in said if s["what"] == "verdict"][0]
    # what the cell's metrics list and a CPU cannot read: the memory peak,
    # and whatever comes from a device trace — never a part of set-up, or
    # BENCHMARK.json lists a set-up metric for a cell that has no such phase
    left_out = verdict.pop("left_out")
    assert left_out == ["peak_hbm_gib"] if not trace else (
        left_out and not any(n.startswith("setup.") for n in left_out))
    assert verdict == {"what": "verdict", "problems": [],
                       "compiles_in_window": 0}


@pytest.mark.parametrize("cell,path", [
    ("higgs-int8.train-bagged",
     {"engine": "partition", "quantized": True, "spine": "unfused",
      "carried": False}),
    ("mslr-rank.train",
     {"engine": "partition", "quantized": False, "spine": "fused",
      "carried": False}),
])
def test_rehearsal_takes_the_path_the_cells_files_state(tmp_path, capsys,
                                                        cell, path):
    root = copy_of_the_benchmark(tmp_path)
    if cell.endswith("-bagged"):
        add_train_cell(root)
    assert run.main(["--workload", cell, "--seed", "4", "--seconds", "0.5",
                     "--trace", "1", "--rehearse"], root=root) == 0
    last, said = _last_line(capsys)
    assert last["correct"] is True, said
    assert [s for s in said if s["what"] == "quality"][0]["path"] == path
    assert [s for s in said if s["what"] == "reference-check"]
    # the traced slice ran and was looked for; a CPU trace has no chip in it
    trace = [s for s in said if s["what"] == "trace"][0]
    assert trace["xplane"].endswith(".xplane.pb") and not trace["reduced"]
    assert "entry.host_ms_per_iter" in last["metrics"]


def test_a_cell_on_another_path_than_it_states_is_not_correct(tmp_path,
                                                              capsys):
    """`expect` is checked, not assumed: a bagged mix that states the fused
    spine fails the run rather than changing the cell."""
    root = copy_of_the_benchmark(tmp_path)
    with open(os.path.join(root, "benchmarks", "traffic",
                           "train-bagged.json")) as f:
        mix = dict(json.load(f), expect={"spine": "fused"})
    with open(os.path.join(root, "benchmarks", "traffic",
                           "train-misstated.json"), "w") as f:
        json.dump(mix, f)
    add_train_cell(root, "higgs-int8.misstated", traffic="train-misstated")
    assert run.main(["--workload", "higgs-int8.misstated", "--seed", "4",
                     "--seconds", "0.5", "--rehearse"], root=root) == 0
    last, said = _last_line(capsys)
    assert last["correct"] is False
    problems = [s for s in said if s["what"] == "verdict"][0]["problems"]
    assert any("spine is 'unfused'" in p for p in problems), problems
