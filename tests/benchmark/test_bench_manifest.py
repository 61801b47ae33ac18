"""BENCHMARK.json against the benchmark's contract, as far as a file check
can go, and against the files it names: on the repo, and on a copy to
which a later PR's entries were appended (manifest_shape.py holds the
assertions, every cell's test file calls its own)."""
import ast
import json
import os
import re

import pytest

import manifest_shape as shape
from bench_overlay import (REPO, add_probe_mix, add_train_cell,
                           copy_of_the_benchmark, edit_manifest, write_new)
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


def test_no_real_entry_takes_the_reserved_probe_prefix():
    shape.check_the_probe_prefix_is_the_appended_fixtures_alone(REPO)
    assert shape.probes_in(REPO) == set()


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


# ---- a later PR's entries, appended -------------------------------------
# under the reserved prefix (manifest_shape.PROBE), so that no name a later
# PR takes is claimed here first, and over the full-bag mix, whose path
# `higgs-int8.train` checks in every run: nothing here depends on which
# spine the program gives a row bag
NEW_CELL = shape.PROBE_CELL
NEW_CONFIG = shape.PROBE_CONFIG
NEW_MIX = shape.PROBE_MIX
NEW_METRIC = shape.PROBE_METRIC
NEW_CHECK = '''"""The plain check under another name, for a cell that names its own."""
from benchmarks.harness import checks


def against_reference(bench, lgb, params):
    bench.say("check-by-name", name="%s")
    return checks.against_reference(bench, lgb, params)
''' % shape.PROBE_CHECK


@pytest.fixture(scope="module")
def appended(tmp_path_factory):
    """A copy of the benchmark after a later `model_config` PR: a
    configuration file (the headline's, with a check of its own), a traffic
    file (the full-bag mix), a check found by its name, a per-layer metric
    that lists the one new cell, the cell itself with its name added to
    `train_iter_ms` and to every list the headline cell is in.  Every entry
    appended, no file that was there edited."""
    root = copy_of_the_benchmark(tmp_path_factory.mktemp("appended"))
    config = shape.load(root, "benchmarks", "configs",
                        "higgs-binary-int8.json")
    config["correct"]["check"] = shape.PROBE_CHECK
    write_new(root, "configs/%s.json" % NEW_CONFIG, config)
    add_probe_mix(root, NEW_MIX)
    write_new(root, "harness/checks_%s.py" % shape.PROBE_CHECK, NEW_CHECK)
    write_new(root, "layer_metrics/%s.json" % NEW_METRIC,
              {"reader": "trace_scope", "args": {"scopes": r"^lgbm\.score$"}})
    add_train_cell(root, NEW_CELL, NEW_CONFIG, NEW_MIX)

    def edit(manifest):
        manifest["configs"].append({
            "name": NEW_CONFIG, "source": config["source"],
            "file": "benchmarks/configs/%s.json" % NEW_CONFIG,
            "reduced": config["reduced"], "why": "Higgs, checked by name"})
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
    assert cell.config["correct"]["check"] == shape.PROBE_CHECK
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
        == [{"what": "check-by-name", "name": shape.PROBE_CHECK}]
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


# ---- no test pins the spine of a sampled mix --------------------------------
# Which spine the program gives a row bag or a column subset is the
# program's to change.  A test here that states `spine` or `carried` for a
# mix with bagging_fraction or feature_fraction below 1 and requires a fixed
# `correct` of it fails the PR that changes that, and that PR may not edit
# it.  A test may still hold such a run to what it reports.  A test is read
# with what it names, and what that names in turn: its fixtures and the
# module's other top-level names, bench_overlay's helpers, and the traffic
# files it quotes.
SAMPLES = re.compile(
    r"""\b(?:bagging|feature)_fraction["']?\s*[:=]\s*(?:0?\.\d+|0)(?![\d.])""")
STATES_A_PATH = re.compile(r"""["'](?:spine|carried)["']\s*:""")
REQUIRES_CORRECT = re.compile(
    r"""\[\s*["']correct["']\s*\]\s*(?:is|==)\s*(?:True|False)\b""")
QUOTED = re.compile(r"""["']([A-Za-z0-9_.-]+?)(?:\.json)?["']""")
NAMES = re.compile(r"\b[A-Za-z_]\w*\b")


def _sampled_mixes(root):
    """Traffic files whose params sample rows or columns, by name, each
    with whether the file itself states a spine or `carried`."""
    folder = os.path.join(root, "benchmarks", "traffic")
    mixes = {}
    for f in os.listdir(folder):
        spec = shape.load(folder, f) if f.endswith(".json") else {}
        if any(spec.get("params", {}).get(key, 1) < 1
               for key in ("bagging_fraction", "feature_fraction")):
            mixes[f[:-len(".json")]] = bool(
                {"spine", "carried"} & set(spec.get("expect", {})))
    return mixes


def _pins_a_sampled_spine(text, sampled_mixes=None):
    named = [m for m in QUOTED.findall(text) if m in (sampled_mixes or {})]
    sampled = SAMPLES.search(text) or named
    states = STATES_A_PATH.search(text) or any(sampled_mixes[m]
                                               for m in named)
    return bool(sampled and states and REQUIRES_CORRECT.search(text))


def _top_level(path):
    """{name: (first line, text)} of a file's top-level definitions and
    assignments, decorators with their definition."""
    with open(path) as f:
        text = f.read()
    lines = text.splitlines()
    found = {}
    for node in ast.parse(text).body:
        first = min([node.lineno] + [
            d.lineno for d in getattr(node, "decorator_list", ())])
        block = (first, "\n".join(lines[first - 1:node.end_lineno]))
        targets = getattr(node, "targets", [node])
        for target in targets:
            for name in ([target.name] if hasattr(target, "name") else
                         [n.id for n in ast.walk(target)
                          if isinstance(n, ast.Name)]):
                found[name] = block
    return found


def _tests_with_what_they_name(path, helpers):
    """(first line, text) of each test, with the text of every top-level
    name of its file or of `helpers` that it names, and that those name."""
    own = _top_level(path)
    known = dict(helpers, **own)
    for name, (first, text) in own.items():
        if name.startswith("test_"):
            read, texts = {name}, [text]
            for part in texts:
                for n in sorted((set(NAMES.findall(part)) & set(known))
                                - read):
                    read.add(n)
                    texts.append(known[n][1])
            yield first, "\n".join(texts)


def test_the_scan_finds_a_sampled_mix_pinned_to_a_spine(tmp_path):
    # the keys are laid in here, so that no sample matches in this file
    keys = {"bag": "bagging_fraction", "col": "feature_fraction",
            "spine": "spine", "carried": "carried", "ok": "correct"}
    pinned = [
        '@pytest.mark.parametrize("c,p", [("x", {"%(spine)s": "unfused"})])\n'
        'def test(c, p):\n'
        '    mix = dict(base, params={"%(bag)s": 0.8})\n'
        '    assert last["%(ok)s"] is True',
        'add_probe_mix(root, params=dict(%(col)s=.5),\n'
        '              expect={"%(carried)s": False})\n'
        'assert out[ "%(ok)s" ] == False',
    ]
    for text in pinned:
        assert _pins_a_sampled_spine(text % keys), text
    # a mix named by its file, whose params sample and whose expect states
    # a spine: naming it states the spine
    named = 'cell = mix("train-x.json")\nassert last["%(ok)s"] is True' % keys
    assert _pins_a_sampled_spine(named, {"train-x": True})
    assert not _pins_a_sampled_spine(named, {"train-x": False})
    assert not _pins_a_sampled_spine(named, {"train-y": True})
    free = [
        # held to what it reports, not to a stated spine
        'mix = dict(params={"%(bag)s": 0.8}, expect={"%(spine)s": "u"})\n'
        'assert last["%(ok)s"] is (took == stated)',
        # no sampling
        'mix = dict(params={"%(bag)s": 1.0}, expect={"%(spine)s": "u"})\n'
        'assert last["%(ok)s"] is True',
        # no stated path
        'mix = dict(params={"%(col)s": 0.8})\nassert last["%(ok)s"] is True',
    ]
    for text in free:
        assert not _pins_a_sampled_spine(text % keys), text
    # a test is read with the fixture, the constant and the helper it names
    test_file, helper_file = tmp_path / "test_x.py", tmp_path / "helpers.py"
    test_file.write_text((
        'MIX = {"params": {"%(bag)s": 0.8}}\n\n\n'
        '@pytest.fixture\ndef root():\n    return copy(MIX)\n\n\n'
        'def test_a(root):\n    add_cell(root)\n'
        '    assert last["%(ok)s"] is True\n\n\n'
        'def test_b():\n    assert last["%(ok)s"] is True\n') % keys)
    helper_file.write_text(
        'def add_cell(root, expect={"%(spine)s": "fused"}):\n    pass\n'
        % keys)
    found = [(first, _pins_a_sampled_spine(text)) for first, text
             in _tests_with_what_they_name(str(test_file),
                                           _top_level(str(helper_file)))]
    assert found == [(9, True), (14, False)]


def test_no_benchmark_test_pins_the_spine_of_a_sampled_mix():
    folder = os.path.dirname(os.path.abspath(__file__))
    sampled = _sampled_mixes(REPO)
    helpers = _top_level(os.path.join(folder, "bench_overlay.py"))
    found = []
    for name in sorted(os.listdir(folder)):
        if name.startswith("test_") and name.endswith(".py"):
            found += ["%s:%d" % (name, first) for first, text
                      in _tests_with_what_they_name(
                          os.path.join(folder, name), helpers)
                      if _pins_a_sampled_spine(text, sampled)]
    assert found == []
