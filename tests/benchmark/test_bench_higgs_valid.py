"""What PR 34 adds to the benchmark: the configuration
`higgs-binary-int8-valid` (Higgs with its published held-out set), the
traffic mix `train-eval` with its driver kind `train_eval`, the plain
float64 AUC of `reference/metrics.py`, the comparison of the
`eval_valid()` series in `harness/checks_valid.py`, three per-layer
metrics over existing readers and the cell `higgs-valid.train`."""
import contextlib
import io
import json
import types

import numpy as np
import pytest

import manifest_shape as shape
from bench_overlay import REPO, copy_of_the_benchmark
from benchmarks import run
from benchmarks.harness import checks_valid, manifest
from benchmarks.reference import metrics, walker

CELL = "higgs-valid.train"
NEW_METRICS = set(shape.VALID_THREE)


def _cell(rehearse=False):
    return manifest.Cell(REPO, CELL, rehearse=rehearse)


# ---- the manifest --------------------------------------------------------
def test_the_manifest_lists_the_cell_as_the_issue_names_it():
    shape.check_higgs_valid_is_listed_as_pr_34_names_it(REPO)


def test_the_cell_reports_the_train_metrics_and_its_own_three():
    shape.check_higgs_valid_reports_the_train_metrics_and_its_own_three(REPO)


def test_the_configuration_states_its_source_its_cut_and_its_guesses():
    cfg = _cell().config
    headline = manifest.Cell(REPO, "higgs-int8.train").config
    assert cfg["architecture"] is None
    pub = cfg["published"]
    assert (pub["train_rows"], pub["test_rows"]) == (10_500_000, 500_000)
    assert pub["metric"] == "auc" and pub["metric_freq"] == 1
    assert pub["test_auc_at_500_iterations"] == 0.845154
    assert cfg["reduced"] == ["num_iterations"]
    assert {"data", "test_set", "tpu_quantized_grad", "num_iterations",
            "metric_freq"} <= set(cfg["assumed"])
    assert "from memory" in cfg["assumed"]["test_set"]
    # the headline's block and data, plus what produces the accuracy table
    assert cfg["params"] == dict(headline["params"], metric="auc")
    assert cfg["data"] == headline["data"]
    assert cfg["expect"] == headline["expect"]
    assert cfg["valid"]["part"] == "test" and cfg["valid"]["rows"] == 500_000
    for key, value in headline["correct"].items():
        assert cfg["correct"][key] == value, key
    series = cfg["correct"]["valid_series"]
    assert 0 < series["auc_atol"] <= 1e-6 and series["auc_atol_why"]
    assert 0 < series["score_atol"] <= 1e-3 and series["score_atol_why"]
    assert cfg["guarantees"]
    # a preset for the CPU
    small = _cell(rehearse=True)
    assert small.config["valid"]["rows"] <= 4096
    assert small.config["data"]["rows"] <= 8192


def test_the_traffic_is_update_then_eval_valid_in_trains_blocks():
    cell = _cell()
    dense = manifest.load_json(REPO, "benchmarks", "traffic",
                               "train-fullbag.json")
    assert cell.traffic["kind"] == "train_eval"
    assert callable(cell.driver().run)
    for key in ("warmup_iterations", "block_iterations", "trace_iterations",
                "params", "seed_params", "rehearse"):
        assert cell.traffic[key] == dense[key], key
    assert cell.traffic["expect"] == {"spine": "fused",
                                      "valid_scoring": "device"}


# ---- the plain reference --------------------------------------------------
def test_the_reference_auc_is_the_pair_count():
    r = np.random.RandomState(0)
    y = r.rand(2000) < 0.35
    scores = np.round(r.randn(2000) + 0.8 * y, 1)       # many ties
    assert len(np.unique(scores)) < 100
    assert metrics.auc(y, scores) == pytest.approx(
        metrics.auc_by_pairs(y, scores), abs=1e-12)
    # and with weights: a row of weight 2 is that row twice
    w = r.randint(1, 4, 2000)
    assert metrics.auc(y, scores, w) == pytest.approx(
        metrics.auc_by_pairs(np.repeat(y, w), np.repeat(scores, w)),
        abs=1e-12)
    assert metrics.auc(np.ones(5), np.arange(5.0)) == 0.5


def _model_text(trees):
    """A LightGBM v2 model text of stumps on column 0: [(threshold, left
    value, right value)]."""
    blocks = []
    for i, (thr, left, right) in enumerate(trees):
        blocks.append("\n".join([
            "Tree=%d" % i, "num_leaves=2", "num_cat=0", "split_feature=0",
            "split_gain=1", "threshold=%r" % thr, "decision_type=2",
            "left_child=-1", "right_child=-2",
            "leaf_value=%r %r" % (left, right), "leaf_count=1 1",
            "internal_value=0", "internal_count=2", "shrinkage=1", ""]))
    return ("tree\nversion=v2\nnum_class=1\nnum_tree_per_iteration=1\n"
            "max_feature_idx=0\nobjective=binary sigmoid:1\n\n"
            + "\n".join(blocks) + "\nend of trees\n")


@pytest.fixture(scope="module")
def stumps():
    r = np.random.RandomState(1)
    X = r.randn(3000, 1)
    y = (X[:, 0] + r.randn(3000) > 0).astype(np.float32)
    trees = [(float(t), -0.1 * (i + 1), 0.07 * (i + 2))
             for i, t in enumerate(np.linspace(-1.5, 1.5, 9))]
    text = _model_text(trees)
    series, raw = checks_valid.reference_series(text, X, y)
    return types.SimpleNamespace(X=X, y=y, trees=trees, text=text,
                                 series=series, raw=raw)


def test_the_reference_series_walks_raw_rows_tree_by_tree(stumps):
    assert len(stumps.series) == len(stumps.trees)
    for i in (0, 4, 8):
        raw = walker.raw_scores(stumps.text, stumps.X, num_trees=i + 1)
        assert stumps.series[i] == metrics.auc(stumps.y, raw)
    np.testing.assert_array_equal(
        stumps.raw, walker.raw_scores(stumps.text, stumps.X))
    # stumps on one column rank alike from the second on, the first splits
    # the rows once
    assert stumps.series[0] < stumps.series[-1]


def test_checks_valid_holds_every_iteration_to_the_tolerance(stumps):
    atol = _cell().config["correct"]["valid_series"]["auc_atol"]
    assert checks_valid.series_problems(list(stumps.series), stumps.series,
                                        atol) == []
    moved = list(stumps.series)
    moved[5] += 1e-4
    problem, = checks_valid.series_problems(moved, stumps.series, atol)
    assert "first at 5, most at 5" in problem
    # one value too few, one not finite
    assert checks_valid.series_problems(moved[:-1], stumps.series, atol)
    moved[2] = float("nan")
    assert "not finite" in checks_valid.series_problems(
        moved, stumps.series, atol)[0]


def test_checks_valid_fails_scores_rounded_to_bfloat16(stumps):
    """Validation scores kept in bfloat16 fail both comparisons: the AUC
    series (rows a rounding apart become ties) and the held scores."""
    import ml_dtypes
    c = _cell().config["correct"]["valid_series"]
    r = np.random.RandomState(2)
    X = r.randn(20000, 1)
    y = (X[:, 0] + r.randn(20000) > 0).astype(np.float32)
    # scores that differ from row to row, as a deep ensemble's do
    raw = X[:, 0] * 0.8 + 0.01 * r.randn(20000)
    rounded = raw.astype(ml_dtypes.bfloat16).astype(np.float64)
    reference = [metrics.auc(y, raw)]
    assert checks_valid.series_problems([metrics.auc(y, rounded)], reference,
                                        c["auc_atol"])
    off, problems = checks_valid.score_problems(rounded, raw, c["score_atol"])
    assert problems and off > 1e-3
    # float32 passes both
    f32 = raw.astype(np.float32).astype(np.float64)
    assert checks_valid.series_problems([metrics.auc(y, f32)], reference,
                                        c["auc_atol"]) == []
    assert checks_valid.score_problems(f32, raw, c["score_atol"])[1] == []
    # a constant missing from every row, or one factor on every value,
    # moves no AUC: the held scores catch them
    assert metrics.auc(y, raw + 0.002) == reference[0]
    assert checks_valid.score_problems(raw + 0.002, raw, c["score_atol"])[1]
    assert checks_valid.score_problems(raw * 10, raw, c["score_atol"])[1]


# ---- the cell, rehearsed --------------------------------------------------
@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    root = copy_of_the_benchmark(tmp_path_factory.mktemp("higgs-valid"))
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run.main(["--workload", CELL, "--seed", "2147483777",
                         "--seconds", "0.3", "--trace", "1", "--rehearse"],
                        root=root) == 0
    lines = out.getvalue().strip().splitlines()
    said = [json.loads(line[len("[bench] "):]) for line in lines
            if line.startswith("[bench] ")]
    return json.loads(lines[-1]), {s["what"]: s for s in said}


def test_the_cell_rehearses_on_the_fused_spine_with_device_scoring(rehearsal):
    last, said = rehearsal
    assert last["correct"] is True and last["failed"] == 0, said["verdict"]
    assert last["device"]["platform"] == "cpu"
    assert all(m["value"] is None for m in last["metrics"].values())
    assert said["quality"]["path"] == {
        "engine": "partition", "quantized": True, "spine": "fused",
        "carried": True, "valid_scoring": "device"}
    window = said["window"]
    assert window["sync_model_calls"] == 0
    assert window["materialize_score_calls"] == 0
    assert window["iterations"] >= 2
    check = said["valid-check"]
    # warm-up, window and traced slice: every iteration was compared
    assert check["iterations"] == 2 + window["iterations"] + 2
    assert check["rows"] == said["setup"]["valid_rows"] == 1024
    assert check["max_abs_auc_diff"] <= 1e-9
    assert check["max_abs_score_diff"] <= 1e-5
    assert said["setup"]["metrics"] == [["test", "auc"]]
    # the slice's own spans: the metric's, and none that waits for the
    # host's copy of the model
    spans = said["slice-spans"]["spans"]
    assert spans["eval_valid"][0] == spans["valid/metric_fetch"][0] == 2
    assert not {"sync_model", "tree_fetch", "materialize_score"} & set(spans)


def test_the_traced_line_names_the_new_metrics(rehearsal):
    """Off the chip a trace holds no device plane, so the readers find
    nothing and the verdict names every trace-read metric under
    `left_out`: the three new ones are among the wanted."""
    last, said = rehearsal
    left_out = set(said["verdict"]["left_out"])
    assert NEW_METRICS <= left_out
    assert not any(name.startswith("setup.") for name in left_out)
    assert {"setup.bin_s", "setup.bin_256k_s", "setup.check_s",
            "setup.warmup_s", "entry.host_ms_per_iter"} <= set(
                last["metrics"])
