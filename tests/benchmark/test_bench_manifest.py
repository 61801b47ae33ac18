"""BENCHMARK.json against the benchmark's contract, as far as a file check
can go, and against the files it names: on the repo, and on a copy to
which a later PR's entries were appended (manifest_shape.py holds the
assertions, every cell's test file calls its own)."""
import json
import os
import re

import pytest

import manifest_shape as shape
from bench_overlay import (REPO, add_train_cell, copy_of_the_benchmark,
                           edit_manifest)
from benchmarks import run
from benchmarks.harness import manifest as harness_manifest


def test_top_level_keys_and_limits():
    shape.check_top_level_keys_and_limits(REPO)


def test_names_are_plain_and_used_once():
    shape.check_names_are_plain_and_used_once(REPO)


def test_configs_are_files_of_their_own_and_every_one_is_used():
    shape.check_configs_are_files_of_their_own_and_every_one_is_used(REPO)


def test_cells_name_existing_files_once():
    shape.check_cells_name_existing_files_once(REPO)


def test_metrics_follow_the_contract():
    shape.check_metrics_follow_the_contract(REPO)


def test_every_per_layer_metric_has_its_file_and_reader():
    shape.check_every_per_layer_metric_has_its_file_and_reader(REPO)


def test_full_check_fits_the_drivers_budget():
    shape.check_full_check_fits_the_drivers_budget(REPO)


def test_the_fused_root_metrics_list_only_int8_cells():
    shape.check_the_fused_root_metrics_list_only_int8_cells(REPO)


def test_the_split_scans_two_metrics_list_the_train_cells():
    shape.check_the_split_scans_two_list_the_cells_they_read_in(REPO)


def test_the_manifest_is_the_parents_plus_appended_entries():
    """What PR 38 left of the accepted manifest: five configurations, five
    cells, three end-to-end metrics with their bounds, 40 per-layer
    metrics of which the last seven are the row ledger's."""
    m = shape.manifest_of(REPO)
    assert [c["name"] for c in m["configs"]][:5] == [
        "higgs-binary-int8", "mslr-lambdarank-255", "epsilon-dense-int8",
        "allstate-onehot-int8", "higgs-binary-int8-valid"]
    assert [w["name"] for w in m["workloads"]][:5] == shape.TRAIN_CELLS
    assert [(e["name"], e["bound"]) for e in m["end_to_end"]][:3] == [
        ("train_iter_ms", 0.01), ("peak_hbm_gib", 0.01), ("setup_s", 0.1)]
    assert m["run_seconds"] == 20
    names = [p["name"] for p in m["per_layer"]]
    assert len(names) >= 40 and names[33:40] == shape.LEDGER_SEVEN


# ---- a later PR's entries, appended ---------------------------------------
NEW_CELL = "higgs-bagged.train"
NEW_CONFIG = "higgs-binary-int8-bagged"
NEW_MIX = "train-rowsampled"
NEW_METRIC = "xla.oob_score.ms_per_iter"
NEW_CHECK = '''"""The plain check under another name, for a cell that names its own."""
from benchmarks.harness import checks


def against_reference(bench, lgb, params):
    bench.say("check-by-name", name="bagged")
    return checks.against_reference(bench, lgb, params)
'''


def _write(root, rel, spec):
    path = os.path.join(root, "benchmarks", rel)
    assert not os.path.exists(path), "a later PR only adds files"
    with open(path, "w") as f:
        f.write(spec if isinstance(spec, str) else json.dumps(spec))


@pytest.fixture(scope="module")
def appended(tmp_path_factory):
    """A copy of the benchmark after the next `model_config` PR as ISSUE 38
    sizes it (row sampling on Higgs, the upstream project's train.conf): a
    configuration file, a traffic file, a check found by its name, a
    per-layer metric that lists the one new cell, the cell itself with its
    name added to `train_iter_ms` and to every list the headline cell is
    in.  Every entry appended, no file that was there edited."""
    root = copy_of_the_benchmark(tmp_path_factory.mktemp("appended"))
    config = shape.load(root, "benchmarks", "configs",
                        "higgs-binary-int8.json")
    config["source"] = ("https://github.com/microsoft/LightGBM/blob/v2.2.4/"
                        "examples/binary_classification/train.conf")
    config["correct"]["check"] = "bagged"
    _write(root, "configs/%s.json" % NEW_CONFIG, config)
    _write(root, "traffic/%s.json" % NEW_MIX,
           shape.load(root, "benchmarks", "traffic", "train-bagged.json"))
    _write(root, "harness/checks_bagged.py", NEW_CHECK)
    _write(root, "layer_metrics/%s.json" % NEW_METRIC,
           {"reader": "trace_scope", "args": {"scopes": r"^lgbm\.oob$"}})
    add_train_cell(root, NEW_CELL, NEW_CONFIG, NEW_MIX)

    def edit(manifest):
        manifest["configs"].append({
            "name": NEW_CONFIG, "source": config["source"],
            "file": "benchmarks/configs/%s.json" % NEW_CONFIG,
            "reduced": config["reduced"], "why": "Higgs with row sampling"})
        manifest["per_layer"].append({
            "name": NEW_METRIC, "unit": "ms", "better": "lower",
            "source": "device_trace", "layer": "xla",
            "moves": "train_iter_ms", "workloads": [NEW_CELL]})
    edit_manifest(root, edit)
    return root


def test_the_copy_holds_the_parents_entries_first_and_the_new_ones_last(
        appended):
    before, after = shape.manifest_of(REPO), shape.manifest_of(appended)
    for key in ("configs", "workloads", "per_layer", "end_to_end"):
        assert len(after[key]) >= len(before[key])
        assert [e["name"] for e in after[key]][:len(before[key])] \
            == [e["name"] for e in before[key]]
    assert after["configs"][len(before["configs"])]["name"] == NEW_CONFIG
    assert after["workloads"][len(before["workloads"])]["name"] == NEW_CELL
    assert after["per_layer"][len(before["per_layer"])]["name"] == NEW_METRIC
    assert NEW_CELL in shape.by_name(after["end_to_end"],
                                     "train_iter_ms")["workloads"]


@pytest.mark.parametrize("check", shape.CHECKS,
                         ids=lambda check: check.__name__)
def test_with_entries_appended_every_shape_assertion_holds(appended, check):
    check(appended)


def test_the_new_cell_resolves_by_name(appended):
    cell = harness_manifest.Cell(appended, NEW_CELL)
    assert (cell.config_name, cell.traffic_name) == (NEW_CONFIG, NEW_MIX)
    assert cell.config["correct"]["check"] == "bagged"
    assert cell.traffic["kind"] == "train" and callable(cell.driver().run)
    assert [e["name"] for e in cell.end_to_end] == [
        "train_iter_ms", "peak_hbm_gib", "setup_s"]
    readers = {metric["name"]: reader.__name__
               for metric, _, reader in cell.layer_readers()}
    assert readers[NEW_METRIC].endswith("trace_scope")
    assert set(shape.LEDGER_SEVEN) <= set(readers)
    # and no other cell got the new cell's metric
    for name in shape.TRAIN_CELLS:
        assert NEW_METRIC not in {
            p["name"] for p in harness_manifest.Cell(appended, name).per_layer}


def test_the_new_cell_rehearses_and_its_check_is_found_by_name(appended,
                                                               capsys):
    """drivers/train.py takes the reference check the configuration names
    under `correct.check` from harness/checks_<name>.py: a cell whose
    reference differs only in the check adds that file."""
    assert run.main(["--workload", NEW_CELL, "--seed", "2147483777",
                     "--seconds", "0.3", "--trace", "0", "--rehearse"],
                    root=appended) == 0
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1])["correct"] is True
    said = [json.loads(line[len("[bench] "):]) for line in out
            if line.startswith("[bench] ")]
    assert [s for s in said if s["what"] == "check-by-name"] \
        == [{"what": "check-by-name", "name": "bagged"}]
    assert [s for s in said if s["what"] == "reference-check"]


def test_an_unknown_check_is_no_silent_default(appended, tmp_path):
    root = copy_of_the_benchmark(tmp_path)
    cell = harness_manifest.Cell(root, "higgs-int8.train", rehearse=True)
    train = harness_manifest.load_module(root, "drivers", "train")
    assert train._reference_check(cell).__module__.endswith("checks")
    cell.config["correct"]["check"] = "no_such_check"
    with pytest.raises(FileNotFoundError):
        train._reference_check(cell)


# ---- no test finds an entry by its distance from the end -------------------
FROM_THE_END = re.compile(
    r"""\[\s*["'](workloads|configs|per_layer|end_to_end)["']\s*\]"""
    r"""\s*\[\s*(-\s*\d+\s*\]|-\s*\d+\s*:|:\s*-\s*\d+)""")


def test_the_pattern_finds_an_index_from_the_end():
    # the key is laid in here, so that no sample matches in this file
    for text in ('m[%s][-1]', "m[%s][ -2 ]", 'm[%s][-3:]', 'm[%s][:-1]',
                 'manifest[%s] [-1]'):
        assert FROM_THE_END.search(text % '"configs"'), text
        assert FROM_THE_END.search(text % "'per_layer'"), text
    for text in ('m[%s][0]', 'm[%s][:len(before)]', 'm[%s][i - 1]',
                 'lines[-1]'):
        assert not FROM_THE_END.search(text.replace('%s', '"workloads"'))


def test_no_benchmark_test_indexes_the_manifests_lists_from_the_end():
    folder = os.path.dirname(os.path.abspath(__file__))
    found = []
    for name in sorted(os.listdir(folder)):
        if name.endswith(".py"):
            with open(os.path.join(folder, name)) as f:
                found += ["%s:%d" % (name, number)
                          for number, line in enumerate(f, 1)
                          if FROM_THE_END.search(line)]
    assert found == []
