"""One run of `benchmarks/run.py` as far as a CPU can take it: off a TPU
it refuses to measure; with --rehearse it runs the cell's tiny preset
through every phase, names the platform it ran on and prints no time."""
import json

import pytest

from bench_overlay import (REPO, add_predict_cell, add_probe_mix,
                           add_train_cell, copy_of_the_benchmark)
from benchmarks import run
from benchmarks.harness import manifest

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


# upstream examples/binary_classification/train.conf's sampling block, with
# its bags and column subsets fixed by the mix's own seeds
SAMPLED = {"bagging_fraction": 0.8, "bagging_freq": 5,
           "feature_fraction": 0.8, "bagging_seed": 3,
           "feature_fraction_seed": 2}


def _stated(root, cell):
    """The path a cell's files state, as drivers/train.py compares it."""
    c = manifest.Cell(root, cell, rehearse=True)
    want = dict(c.config["expect"], **c.traffic["expect"])
    if want["spine"] != "fused":
        want["carried"] = False
    return want


@pytest.mark.parametrize("cell,path", [
    ("probe-sampled.train", None),
    ("mslr-rank.train",
     {"engine": "partition", "quantized": False, "spine": "fused",
      "carried": False}),
])
def test_rehearsal_takes_the_path_the_cells_files_state(tmp_path, capsys,
                                                        cell, path):
    """A cell is correct exactly when it took the path its files state.  A
    sampled mix may take whichever spine the program gives a row bag: the
    run is held to what it reports, not to a spine."""
    root = copy_of_the_benchmark(tmp_path)
    if path is None:
        # blocks of one and one traced iteration: the CPU walks the out-of-bag
        # rows of every iteration of the unfused spine
        add_probe_mix(root, "probe-sampled", params=SAMPLED,
                      expect={"spine": "unfused"},
                      rehearse={"warmup_iterations": 2, "block_iterations": 1,
                                "trace_iterations": 1})
        add_train_cell(root, cell, traffic="probe-sampled")
    assert run.main(["--workload", cell, "--seed", "4", "--seconds", "0.5",
                     "--trace", "1", "--rehearse"], root=root) == 0
    last, said = _last_line(capsys)
    took = [s for s in said if s["what"] == "quality"][0]["path"]
    stated = path or _stated(root, cell)
    assert path is None or took == path
    assert last["correct"] is (took == stated), said
    problems = [s for s in said if s["what"] == "verdict"][0]["problems"]
    if took["spine"] != stated["spine"]:
        assert any("spine is %r" % took["spine"] in p for p in problems), \
            problems
    if took["spine"] == "unfused":
        assert took["carried"] is False
    assert [s for s in said if s["what"] == "reference-check"]
    # the traced slice ran and was looked for; a CPU trace has no chip in it
    trace = [s for s in said if s["what"] == "trace"][0]
    assert trace["xplane"].endswith(".xplane.pb") and not trace["reduced"]
    assert "entry.host_ms_per_iter" in last["metrics"]


def test_a_cell_on_another_path_than_it_states_is_not_correct(tmp_path,
                                                              capsys):
    """`expect` is checked, not assumed: the full-bag Higgs mix, whose fused
    spine `higgs-int8.train` checks in every run, stating the unfused one
    fails the run rather than changing the cell."""
    root = copy_of_the_benchmark(tmp_path)
    add_probe_mix(root, "probe-misstated", expect={"spine": "unfused"})
    add_train_cell(root, "probe-misstated.train", traffic="probe-misstated")
    assert run.main(["--workload", "probe-misstated.train", "--seed", "4",
                     "--seconds", "0.5", "--rehearse"], root=root) == 0
    last, said = _last_line(capsys)
    assert last["correct"] is False
    problems = [s for s in said if s["what"] == "verdict"][0]["problems"]
    assert any("spine is 'fused'" in p for p in problems), problems


class _Booster:
    """What drivers/train.py reads off a booster to say which path it took."""

    def __init__(self, engine="partition", quantized=True, spine="fused",
                 carried=True):
        self.path = {"engine": engine, "quantized": quantized,
                     "spine": spine, "carried": carried}
        self._use_partition_engine = engine == "partition"
        self._quantized = quantized
        self._fused_validated = spine == "fused"
        self._carried_active = carried


class _Cell:
    def __init__(self, config_expect, traffic_expect):
        self.config = {"expect": config_expect}
        self.traffic = {"expect": traffic_expect}


HIGGS = {"engine": "partition", "quantized": True, "carried": True}


@pytest.mark.parametrize("booster,traffic,wrong", [
    # the headline as stated
    (_Booster(), {"spine": "fused"}, []),
    # only the fused spine can carry: stating unfused states not carried,
    # whatever the configuration says
    (_Booster(spine="unfused", carried=False), {"spine": "unfused"}, []),
    (_Booster(), {"spine": "unfused"}, ["spine", "carried"]),
    (_Booster(spine="unfused", carried=False), {"spine": "fused"},
     ["spine", "carried"]),
    # every key is compared
    (_Booster(carried=False), {"spine": "fused"}, ["carried"]),
    (_Booster(engine="label"), {"spine": "fused"}, ["engine"]),
    (_Booster(quantized=False), {"spine": "fused"}, ["quantized"]),
], ids=["as-stated", "unfused-not-carried", "fused-stated-unfused",
        "unfused-stated-fused", "not-carried", "label-engine", "float32"])
def test_path_problems_name_each_key_that_differs(booster, traffic, wrong):
    train = manifest.load_module(REPO, "drivers", "train")
    took, problems = train._path_problems(booster, _Cell(HIGGS, traffic))
    assert took == booster.path
    assert [p.split()[1] for p in problems] == wrong
    for key, p in zip(wrong, problems):
        assert "is %r" % took[key] in p
