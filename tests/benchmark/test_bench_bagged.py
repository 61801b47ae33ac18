"""What the row-sampled Higgs cell adds to the benchmark: the configuration
`higgs-binary-int8-bagged` (upstream train.conf's sampling block on the
Higgs problem), the traffic mix `train-rowsampled` with its driver kind
`train_rowsampled`, the check `harness/checks_bagged.py`, two per-layer
metrics over an existing reader and the cell `higgs-bagged.train`."""
import contextlib
import io
import json

import pytest

import manifest_shape as shape
from bench_overlay import REPO, copy_of_the_benchmark
from benchmarks import run
from benchmarks.harness import checks_bagged, manifest

CELL = "higgs-bagged.train"
CONFIG = "higgs-binary-int8-bagged"
NEW_METRICS = ["xla.bag.ms_per_iter", "xla.oob_score.ms_per_iter"]


def _cell(rehearse=False):
    return manifest.Cell(REPO, CELL, rehearse=rehearse)


# ---- the manifest --------------------------------------------------------
def test_the_manifest_lists_the_cell_its_configuration_and_two_metrics():
    m = shape.manifest_of(REPO)
    entry = shape.by_name(m["workloads"], CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        CONFIG, "train-rowsampled", 1)
    assert "higgs-int8.train" in entry["why"] and len(entry["why"]) <= 200
    config = shape.by_name(m["configs"], CONFIG)
    assert config["file"] == "benchmarks/configs/%s.json" % CONFIG
    assert config["reduced"] == ["num_iterations"]
    assert config["source"].endswith(
        "v2.2.4/examples/binary_classification/train.conf")
    assert CELL in shape.by_name(m["end_to_end"],
                                 "train_iter_ms")["workloads"]
    assert shape.stand_together(m["per_layer"], NEW_METRICS)
    for name in NEW_METRICS:
        metric = shape.by_name(m["per_layer"], name)
        assert metric["workloads"] == [CELL]
        assert (metric["layer"], metric["moves"], metric["source"]) == (
            "xla", "train_iter_ms", "device_trace")


def test_the_cell_reads_what_a_train_cell_reads_and_its_own_two():
    names = {p["name"] for p in _cell().per_layer}
    assert set(NEW_METRICS) <= names
    # the row ledger's seven, list-less, read here as in every train cell
    assert set(shape.LEDGER_SEVEN) <= names
    readers = {metric["name"]: reader.__name__ for metric, _, reader
               in _cell().layer_readers()}
    assert all(readers[n].endswith("trace_scope") for n in NEW_METRICS)


def test_the_configuration_is_the_headlines_block_with_the_sampling_block():
    cfg = _cell().config
    headline = manifest.Cell(REPO, "higgs-int8.train").config
    sampling = {"feature_fraction": 0.8, "bagging_freq": 5,
                "bagging_fraction": 0.8}
    assert cfg["params"] == dict(headline["params"], bagging_seed=3,
                                 feature_fraction_seed=2, **sampling)
    for key, value in sampling.items():
        assert cfg["published"][key] == value, key
    # the bags and column draws are the configuration's, --seed draws the
    # label noise and the int8 rounding only
    assert cfg["seed_params"] == ["seed"]
    assert cfg["data"] == headline["data"]
    assert cfg["expect"] == headline["expect"]
    assert {"data", "num_leaves", "bagging_seed",
            "tpu_quantized_grad"} <= set(cfg["assumed"])
    c = cfg["correct"]
    assert c["check"] == "bagged" and c["trees"] > 5
    assert 0 < c["oob_score_atol"] <= 1e-4 and c["oob_score_why"]
    for key in ("gain_rtol", "leaf_value_rtol", "leaf_value_atol_of_largest",
                "f32_band", "own_band", "walker_atol"):
        assert c[key] == headline["correct"][key], key


def test_a_block_and_the_traced_slice_hold_one_bag_each():
    cell = _cell()
    freq = cell.config["params"]["bagging_freq"]
    traffic = cell.traffic
    assert traffic["kind"] == "train_rowsampled"
    assert traffic["block_iterations"] == traffic["trace_iterations"] == freq
    assert traffic["seed_params"] == [] and traffic["params"] == {}


def test_the_driver_counts_a_trees_rows_as_its_bag():
    params = _cell().config["params"]
    assert checks_bagged.expected_bag_rows(params, 10_500_000) == 8_400_000
    assert checks_bagged.expected_bag_rows({}, 10_500_000) == 10_500_000
    assert checks_bagged.expected_columns(params, 28) == 22


# ---- the cell, rehearsed under a control -----------------------------------
def test_a_bag_one_row_short_fails_the_rehearsal(tmp_path, monkeypatch):
    """The program drawing one row fewer than the configuration asks: the
    check reads it off the booster and the run is not correct.  The timed
    booster's own scores, which the fault leaves right, pass their check."""
    from lightgbm_tpu.ops import bagging
    rows = bagging.bag_rows
    monkeypatch.setattr(bagging, "bag_rows",
                        lambda fraction, n: rows(fraction, n) - 1)
    root = copy_of_the_benchmark(tmp_path)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run.main(["--workload", CELL, "--seed", "2147483777",
                         "--seconds", "0.1", "--trace", "0", "--rehearse"],
                        root=root) == 0
    lines = out.getvalue().strip().splitlines()
    last = json.loads(lines[-1])
    said = {s["what"]: s for s in (json.loads(line[len("[bench] "):])
                                   for line in lines
                                   if line.startswith("[bench] "))}
    assert not last["correct"]
    assert last["compared"]["bag_rows_off"] == {"value": 1.0, "limit": 0.0}
    check = said["bag-check"]
    assert check["bag_rows"][0] == check["want_bag_rows"] - 1
    assert any("a bag holds" in p for p in said["verdict"]["problems"])
    timed = last["compared"]["timed_score_diff"]
    assert timed["value"] <= timed["limit"]
    assert said["timed-score-check"]["rows"] >= 256


@pytest.mark.parametrize("name", NEW_METRICS)
def test_the_new_metrics_read_their_scope(name):
    spec = shape.load(REPO, "benchmarks", "layer_metrics", name + ".json")
    assert spec["reader"] == "trace_scope"
    scope = name.split(".")[1]
    assert spec["args"] == {"scopes": r"^lgbm\.%s$" % scope}
