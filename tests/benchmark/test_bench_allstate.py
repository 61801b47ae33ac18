"""What PR 32 adds to the benchmark: the Allstate generator (CSR one-hot),
the check for a bundled set, the split scan's byte floor and its reader,
the driver kind `train_sparse` and the cell `allstate-efb.train`."""
import contextlib
import copy
import io
import json
import os
import types

import numpy as np
import pytest
import scipy.sparse as sp

import manifest_shape as shape
from bench_overlay import REPO, copy_of_the_benchmark
from benchmarks import run
from benchmarks.data import allstate
from benchmarks.harness import (binned, checks, checks_bundled, costs_scan,
                                manifest)
from benchmarks.harness.trace_reduce import TraceSummary
from benchmarks.reference import grower, objectives

CELL = "allstate-efb.train"


def _cell(rehearse=True):
    return manifest.Cell(REPO, CELL, rehearse=rehearse)


# ---- the generator ------------------------------------------------------
def test_the_published_structure_is_32_variables_over_4228_columns():
    args = _cell(rehearse=False).config["data"]["args"]
    cards = args["cardinalities"]
    assert len(cards) == 32 and sum(cards) == 4228
    assert sorted(cards)[-3:] == [700, 1000, 1200]
    assert all(8 <= c <= 120 for c in sorted(cards)[:-3])


def test_features_are_csr_with_one_nonzero_per_variable_and_row():
    args = _cell().config["data"]["args"]
    cards = np.asarray(args["cardinalities"])
    X = allstate.features(args, "train", 3000)
    assert sp.isspmatrix_csr(X) and X.shape == (3000, cards.sum())
    assert X.dtype == np.float32 and X.indices.dtype == np.int32
    assert X.nnz == 3000 * len(cards) and (X.data == 1.0).all()
    assert X.has_sorted_indices
    offsets = np.concatenate([[0], np.cumsum(cards)])
    cols = X.indices.reshape(3000, len(cards))
    assert ((cols >= offsets[:-1]) & (cols < offsets[1:])).all()
    # a function of (feature_seed, part) alone, whatever the block size
    again = allstate.features(args, "train", 3000)
    assert (again != X).nnz == 0
    assert (allstate.features(args, "holdout", 3000) != X).nnz > 0


def test_the_seed_draws_another_label_of_the_same_columns():
    args = _cell().config["data"]["args"]
    X = allstate.features(args, "sample", 4000)
    y1, g1 = allstate.labels(args, 1, "sample", X)
    y2, _ = allstate.labels(args, 2, "sample", X)
    assert g1 is None and y1.dtype == np.float32
    assert set(np.unique(y1)) == {0.0, 1.0}
    assert 0.0 < (y1 != y2).mean() < 0.5
    np.testing.assert_array_equal(allstate.labels(args, 1, "sample", X)[0],
                                  y1)
    # the train part's noise is the seed's too, as in the other generators:
    # no key of the configuration takes the seed away from the timed work
    assert "train_noise_seed" not in args
    assert (allstate.labels(args, 1, "train", X)[0]
            != allstate.labels(args, 2, "train", X)[0]).any()
    # the label follows the columns: the fixed effects separate it
    w, _, _ = allstate.effects(args)
    score = X @ w
    assert score[y1 == 1].mean() > score[y1 == 0].mean()


# ---- the check for a bundled set ----------------------------------------
@pytest.fixture(scope="module")
def sample():
    """The rehearse preset's sample, the system's float32 trees on it and
    the plain bins the check makes."""
    import lightgbm_tpu as lgb
    cell = _cell()
    c, args = cell.config["correct"], cell.config["data"]["args"]
    params = dict(cell.config["params"], seed=5)
    Xs = allstate.features(args, "sample", c["sample_rows"])
    ys, _ = allstate.labels(args, 5, "sample", Xs)
    ds = binned.fresh(lgb, Xs, ys, None, params)
    assert ds._binned.bundle is not None
    f32 = checks._train(lgb, dict(params, tpu_quantized_grad=False), ds,
                        c["trees"])
    kept = np.asarray(ds._binned.real_feature_index)
    return types.SimpleNamespace(
        c=c, params=params, ys=ys, trees=f32._gbdt.models,
        bins=checks_bundled.plain_bins(Xs, kept))


def _judge(sample, trees):
    init = objectives.binary_init_score(sample.ys)
    return checks_bundled.judge_trees(
        trees, sample.bins, sample.bins[:64],
        lambda score: objectives.binary_gradients(score, sample.ys),
        grower.SplitRules(sample.params),
        float(sample.params["learning_rate"]), init, sample.c)[0]


def test_plain_bins_are_made_without_the_systems_mappers(sample):
    assert sample.bins.dtype == np.uint8
    assert set(np.unique(sample.bins)) == {0, 1}
    assert (sample.bins.sum(axis=0) > 0).all()     # kept columns have rows


def test_the_check_accepts_the_systems_trees(sample):
    assert all(t.num_leaves == sample.params["num_leaves"]
               for t in sample.trees)
    assert _judge(sample, sample.trees) == []


def test_the_check_refuses_a_split_moved_to_the_second_best_column(sample):
    init = objectives.binary_init_score(sample.ys)
    grad, hess = objectives.binary_gradients(
        np.full(len(sample.ys), init), sample.ys)
    g = grower.LeafwiseGrower(sample.bins, np.full(sample.bins.shape[1], 2),
                              grad, hess, grower.SplitRules(sample.params))
    gains = g.gains[0][:, 0]
    best, second = np.argsort(-gains)[:2]
    tree = copy.deepcopy(sample.trees[0])
    assert tree.split_feature_inner[0] == best
    assert gains[second] < gains[best] * (1 - sample.c["gain_rtol"])
    tree.split_feature_inner[0] = second
    problems = _judge(sample, [tree] + list(sample.trees[1:]))
    assert len(problems) == 1 and "tree 0" in problems[0]
    assert "the reference does not accept" in problems[0]


# ---- the split scan's byte floor and its reader --------------------------
@pytest.mark.parametrize("groups, max_bin, children, expected", [
    # 37 group columns pad to 40; 256 bins x (g, h, count) float32
    (37, 255, 1, 40 * 256 * 3 * 4),
    (37, 255, 2, 2 * 40 * 256 * 3 * 4),
    (40, 255, 2, 2 * 40 * 256 * 3 * 4),
    (41, 255, 1, 48 * 256 * 3 * 4),
    # a dense set's columns are its features, each of max_bin + 1 bins
    (2000, 63, 2, 2 * 2000 * 64 * 3 * 4),
])
def test_scan_bytes_are_the_bundled_histograms(groups, max_bin, children,
                                               expected):
    assert costs_scan.scan_bytes(groups, max_bin, children) == expected


def _traced(ops, **shape):
    return types.SimpleNamespace(
        device_kind="TPU v5 lite",
        trace=TraceSummary(window_s=3.0, busy_s=2.9, ops=ops, programs=30,
                           gaps=[], chips=1),
        shape=dict({"units": 10, "traced_units": 3, "rows": 13_184_290,
                    "features": 37, "columns": 4228, "max_bin": 255},
                   **shape))


def _read(run_):
    spec = manifest.load_json(REPO, "benchmarks", "layer_metrics",
                              "split_scan_roofline.json")
    reader = manifest.load_module(REPO, "readers", spec["reader"])
    return reader.read(run_, spec["args"])


def test_the_reader_counts_one_leaf_for_a_trees_first_call():
    # 3 trees: 3 root calls of one leaf, 3 x 254 calls of two children
    ops = {"_run_scan.5 f32[2,128] mosaic": (0.006, 3 * 254),
           "_run_scan.2 f32[1,128] mosaic": (0.00002, 3),
           "partition_segment.13 bf16[64,79142912] mosaic": (0.9, 762)}
    floor = (3 * costs_scan.scan_bytes(37, 255, 1)
             + 3 * 254 * costs_scan.scan_bytes(37, 255, 2)) / 819e9
    assert _read(_traced(ops)) == pytest.approx(100 * floor / 0.00602)
    assert 0 < _read(_traced(ops)) < 100


def test_the_reader_returns_nothing_when_there_is_nothing_to_read():
    none = _traced({"partition_segment.13 bf16[64,79142912] mosaic":
                    (0.9, 762)})
    assert _read(none) is None
    untraced = _traced({})
    untraced.trace = None
    assert _read(untraced) is None


def test_scan_glue_reads_the_scope_of_the_scan():
    spec = manifest.load_json(REPO, "benchmarks", "layer_metrics",
                              "xla.scan_glue.ms_per_iter.json")
    assert spec["reader"] == "trace_scope"
    import re
    rx = re.compile(spec["args"]["scopes"])
    assert rx.search("lgbm.grow.scan")
    assert not rx.search("lgbm.grow.scanner") and not rx.search("lgbm.grow")
    # a part of xla.grow_glue, as xla.gradient_pairs is of xla.gradient
    glue = manifest.load_json(REPO, "benchmarks", "layer_metrics",
                              "xla.grow_glue.ms_per_iter.json")
    assert re.search(glue["args"]["scopes"], "lgbm.grow.scan")


# ---- the cell --------------------------------------------------------------
def test_the_manifest_lists_the_cell_where_the_issue_says():
    shape.check_allstate_is_listed_where_pr_32_says(REPO)


def test_the_harness_finds_the_new_kind_by_the_traffic_files_name():
    cell = _cell(rehearse=False)
    assert cell.traffic["kind"] == "train_sparse"
    assert callable(cell.driver().run)
    assert cell.generator().__name__.endswith("allstate")
    dense = manifest.load_json(REPO, "benchmarks", "traffic",
                               "train-fullbag.json")
    for key in ("warmup_iterations", "block_iterations", "trace_iterations",
                "expect", "params", "seed_params"):
        assert cell.traffic[key] == dense[key], key
    # the window is a count of whole blocks, stated with its reason
    assert cell.traffic["window_iterations"] % dense["block_iterations"] == 0
    assert cell.traffic["window_why"] and cell.traffic["reference_why"]
    assert 10 < cell.traffic["reference_row_passes"] < 30
    assert ({k: v for k, v in cell.traffic["rehearse"].items()
             if k != "window_iterations"} == dense["rehearse"])


def _rehearse(root_dir, cell, trace):
    """One rehearsal of `cell`: (the last line, the [bench] lines by their
    `what`, what the driver returned to run.py)."""
    root = copy_of_the_benchmark(root_dir)
    module = manifest.load_module(
        root, "drivers", manifest.Cell(root, cell).traffic["kind"])
    returned = {}

    def run_and_keep(bench):
        returned.update(module.run(bench))
        return returned

    out = io.StringIO()
    with pytest.MonkeyPatch.context() as patch, \
            contextlib.redirect_stdout(out):
        patch.setattr(manifest.Cell, "driver",
                      lambda self: types.SimpleNamespace(run=run_and_keep))
        assert run.main(["--workload", cell, "--seed", "2147483747",
                         "--seconds", "0.3", "--trace", str(trace),
                         "--rehearse"], root=root) == 0
    lines = out.getvalue().strip().splitlines()
    said = [json.loads(line[len("[bench] "):]) for line in lines
            if line.startswith("[bench] ")]
    return json.loads(lines[-1]), {s["what"]: s for s in said}, returned


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    return _rehearse(tmp_path_factory.mktemp("allstate"), CELL, 1)


def test_the_cell_rehearses_through_bundling_and_the_group_scan(rehearsal):
    last, said, _ = rehearsal
    assert last["correct"] is True and last["failed"] == 0, said["verdict"]
    # the window is the traffic file's count, whatever --seconds says
    assert last["attempted"] == said["window"]["iterations"] == 4
    assert len(said["window"]["block_ms_per_iter"]) == 2
    trees = said["trees"]
    assert trees["first"] == 2 and trees["count"] == 4
    assert len(trees["block_row_passes_per_iter"]) == 2
    assert 1.0 <= trees["row_passes_per_iter"] <= 6.0     # 7 leaves
    # two blocks are no line: the rehearsal reads the plain mean
    assert trees["ms_per_row_pass"] is None
    assert trees["ms_per_iter_at_reference"] == pytest.approx(
        np.mean(said["window"]["block_ms_per_iter"]))
    assert last["device"]["platform"] == "cpu"
    assert all(m["value"] is None for m in last["metrics"].values())
    assert said["quality"]["path"] == {"engine": "partition",
                                       "quantized": True, "spine": "fused",
                                       "carried": True}
    setup = said["setup"]
    assert setup["columns"] == 437 and setup["columns_kept"] < 437
    assert 12 <= setup["groups"] <= 16 and setup["conflicts"] == 0
    assert setup["scan_space"] == "group"
    assert setup["conflict_rows"] == setup["lost_entries"] == 0
    check = said["reference-check"]
    assert check["groups"] >= 12 and check["columns_kept"] < check["columns"]
    # every part of set-up is reported, bin_256k among them; what a CPU
    # cannot read comes from a device trace
    assert {"setup.bin_s", "setup.bin_256k_s", "setup.check_s",
            "setup.warmup_s"} <= set(last["metrics"])
    left_out = said["verdict"]["left_out"]
    assert not any(name.startswith("setup.") for name in left_out)
    assert {"split_scan_roofline", "xla.scan_glue.ms_per_iter"} \
        <= set(left_out)


def test_train_sparse_reports_trains_keys(rehearsal, tmp_path):
    """The two drivers are one window written twice: what `train` returns
    for the readers, `train_sparse` returns too (and the column count)."""
    sparse = rehearsal[2]
    dense = _rehearse(tmp_path, "higgs-int8.train", 0)[2]
    assert set(sparse) == set(dense)
    assert set(sparse["end_to_end"]) == set(dense["end_to_end"])
    assert set(sparse["shape"]) == set(dense["shape"]) | {"columns"}
    assert sparse["shape"]["features"] < sparse["shape"]["columns"]



def test_train_iter_ms_is_read_at_the_reference_depth():
    """Blocks of other trees (other passes over the rows) on one line give
    one reading; a stalled block does not move it."""
    driver = manifest.load_module(REPO, "drivers", "train_sparse")
    rng = np.random.RandomState(3)
    readings = []
    for _ in range(4):                          # four seeds' worth of trees
        passes = np.linspace(13, 19, 8) * (1 + 0.03 * rng.randn(8))
        ms = 26.5 * passes + 96.0
        at, a, b = driver._at_reference_depth(ms, passes, 16.66)
        assert (a, b) == (pytest.approx(26.5), pytest.approx(96.0))
        readings.append(at)
        assert abs(ms.mean() - at) > 0.5        # the plain mean follows them
        ms[5] += 480.0                          # one block stalls
        assert driver._at_reference_depth(ms, passes, 16.66)[0] \
            == pytest.approx(at, rel=2e-3)
    assert readings == pytest.approx([26.5 * 16.66 + 96.0] * 4)
    # too few blocks for a line: the plain mean, and no line
    assert driver._at_reference_depth([3.0, 5.0], [1.0, 2.0], 9.0) \
        == (4.0, None, None)
    assert driver._at_reference_depth([3.0, 5.0, 4.0], [2.0, 2.0, 2.0], 9.0) \
        == (4.0, None, None)


def test_conflict_rows_counts_every_row_not_the_bin_sample():
    """Two columns of one bundle that never meet on the rows the bundles
    were decided on, and meet later: the count finds those rows."""
    driver = manifest.load_module(REPO, "drivers", "train_sparse")
    rows, width = 600, 6
    dense = np.zeros((rows, width), np.float32)
    dense[np.arange(rows), np.arange(rows) % 3] = 1.0       # one variable
    dense[:, 3] = np.arange(rows) % 2                       # a free column
    X = sp.csr_matrix(dense)
    binned_set = types.SimpleNamespace(
        real_feature_index=[0, 1, 2, 3, 5],                 # column 4 dropped
        bundle=types.SimpleNamespace(
            groups=[[0, 1, 2, 4], [3]], num_groups=2,
            feature_group=np.array([0, 0, 0, 1, 0])))
    assert driver._conflict_rows(X, binned_set) == (0, 0)
    dense[10, 5] = dense[11, 5] = 1.0       # meets column 10 % 3 and 11 % 3
    dense[12, 1] = 1.0                      # a row with three of the group:
    dense[12, 5] = 1.0                      # columns 0, 1 and 5
    dense[13, 4] = 1.0                      # a dropped column conflicts with
    dense[14, 3] = 1.0                      # nothing, nor a group of one
    assert driver._conflict_rows(sp.csr_matrix(dense), binned_set) == (3, 4)
    # stored zeros are no entries
    X = sp.csr_matrix(dense)
    X.data[:] = 0
    assert driver._conflict_rows(X, binned_set) == (0, 0)
    assert driver._conflict_rows(X, types.SimpleNamespace(bundle=None)) \
        == (0, 0)


@pytest.mark.parametrize("change, problem", [
    ({"correct": {"conflict_rows_max_share": -1.0}}, "bundling: on 0 of"),
    ({"expect": {"scan_space": "feature"}}, "path: scan_space is 'group'"),
])
def test_the_cell_is_refused_over_the_conflict_share_or_off_its_space(
        tmp_path, change, problem):
    root = copy_of_the_benchmark(tmp_path)
    path = os.path.join(root, "benchmarks", "configs",
                        "allstate-onehot-int8.json")
    with open(path) as f:
        config = json.load(f)
    config["rehearse"] = manifest.deep_merge(config["rehearse"], change)
    with open(path, "w") as f:
        json.dump(config, f)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        assert run.main(["--workload", CELL, "--seed", "7", "--seconds",
                         "0.3", "--trace", "0", "--rehearse"], root=root) == 0
    lines = out.getvalue().strip().splitlines()
    assert json.loads(lines[-1])["correct"] is False
    verdict = [json.loads(line[len("[bench] "):]) for line in lines
               if '"what": "verdict"' in line][0]
    assert any(p.startswith(problem) for p in verdict["problems"]), verdict
