"""Every assertion the tests make about the shape of BENCHMARK.json, as
functions of the checkout's root: the test files call them on the repo,
and test_bench_manifest.py calls all of them again on a copy to which a
configuration, a traffic mix, a cell and a per-layer metric were appended,
as a later PR appends them.  An assertion that depends on how far an entry
stands from the end of a list fails there.

Entries are found by name, never by position.  What an earlier PR appended
side by side is asserted to stand side by side still, wherever that is.
"""
import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TRAIN_CELLS = ["higgs-int8.train", "mslr-rank.train", "epsilon-int8.train",
               "allstate-efb.train", "higgs-valid.train"]
# PR 34's three per-layer metrics and PR 36's seven, in the order appended
VALID_THREE = ["xla.valid_score.ms_per_iter", "xla.valid_metric.ms_per_iter",
               "entry.eval_host_ms_per_iter"]
LEDGER_SEVEN = ["kernel.partition.row_passes_per_iter",
                "kernel.partition.ms_per_pass", "partition_roofline",
                "kernel.partition.call_us", "kernel.seg_hist.ms_per_pass",
                "seg_hist_roofline", "kernel.seg_hist.call_us"]
# The prefix only tests give what they append to a copy of the benchmark,
# so that no name a later PR takes is claimed here first.  The appended-cell
# fixture's five: a cell, a configuration, a traffic mix, a check
# (harness/checks_<PROBE_CHECK>.py) and a per-layer metric.
PROBE = "probe"
PROBE_CELL = "probe-appended.train"
PROBE_CONFIG = "probe-config"
PROBE_MIX = "probe-mix"
PROBE_CHECK = "probe"
PROBE_METRIC = "probe.appended_ms_per_iter"


def load(root, *parts):
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


def manifest_of(root):
    return load(root, "BENCHMARK.json")


def by_name(entries, name):
    """The one entry of a list of the manifest that carries `name`."""
    (entry,) = [e for e in entries if e["name"] == name]
    return entry


def listing(manifest, cell):
    """Names of the metrics whose `workloads` list names `cell`."""
    return {x["name"] for x in manifest["end_to_end"] + manifest["per_layer"]
            if cell in x.get("workloads", ())}


def stand_together(entries, names):
    """`names` are entries of the list, adjacent and in this order,
    wherever in the list they stand."""
    found = [e["name"] for e in entries]
    at = found.index(names[0])
    return found[at:at + len(names)] == list(names)


# ---- the contract, as far as a file check can go --------------------------
def check_top_level_keys_and_limits(root):
    manifest = manifest_of(root)
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmarks", "tests/benchmark"]
    assert manifest["command"] == ["python3", "benchmarks/run.py"]
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(root, "BENCHMARK.json")) <= 64 << 10
    assert 2 <= len(manifest["workloads"]) <= 24
    assert 1 <= len(manifest["configs"]) <= 24
    assert 1 <= len(manifest["end_to_end"]) <= 16
    assert 1 <= len(manifest["per_layer"]) <= 128


def check_names_are_plain_and_used_once(root):
    manifest = manifest_of(root)
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in manifest[key]]
    assert all(NAME.match(n) for n in names), names
    assert len(names) == len(set(names))
    for key in ("configs", "workloads"):
        assert all(len(e["why"]) <= 200 for e in manifest[key])


def check_configs_are_files_of_their_own_and_every_one_is_used(root):
    manifest = manifest_of(root)
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}
    for c in manifest["configs"]:
        assert c["file"].startswith("benchmarks/configs/")
        spec = load(root, c["file"])
        assert spec["source"] == c["source"]
        assert spec["reduced"] == c["reduced"]


def check_cells_name_existing_files_once(root):
    manifest = manifest_of(root)
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    # four chips cost four times: a quarter of the cells at most, one always
    on_four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert on_four <= max(1, len(pairs) // 4)
    for w in manifest["workloads"]:
        assert w["chips"] in (1, 4)
        assert os.path.exists(os.path.join(
            root, "benchmarks", "traffic", w["traffic"] + ".json"))


def check_metrics_follow_the_contract(root):
    manifest = manifest_of(root)
    cells = {w["name"] for w in manifest["workloads"]}
    end_to_end = {m["name"]: m for m in manifest["end_to_end"]}
    assert end_to_end["setup_s"]["bound"] == 0.1
    for m in manifest["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        # the cells a metric lists report the metric it moves
        assert set(m.get("workloads", ())) <= set(
            end_to_end[m["moves"]].get("workloads", cells))
        assert m["source"] in SOURCES
        assert m["moves"] in end_to_end
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    # every cell reports setup_s, another end-to-end metric, a layer metric
    for cell in cells:
        mine = {n for n, m in end_to_end.items()
                if cell in m.get("workloads", cells)}
        assert "setup_s" in mine and len(mine) >= 2
        assert any(m["moves"] in mine for m in manifest["per_layer"])


def check_every_per_layer_metric_has_its_file_and_reader(root):
    listed = {m["name"] for m in manifest_of(root)["per_layer"]}
    folder = os.path.join(root, "benchmarks", "layer_metrics")
    on_disk = {f[:-len(".json")] for f in os.listdir(folder)}
    # files beyond the list belong to cells PERF.md keeps for later, which
    # a PR brings by appending entries
    assert listed <= on_disk
    for name in listed:
        spec = load(folder, name + ".json")
        assert os.path.exists(os.path.join(
            root, "benchmarks", "readers", spec["reader"] + ".py"))


def check_full_check_fits_the_drivers_budget(root):
    """2 + 14 runs per cell, each run_seconds + 60 s, 2 x 90 s per cell to
    compile, 1200 s spare, all within 43200 s — at the full 24 cells."""
    cells = 24
    total = ((2 + 14 * cells) * (manifest_of(root)["run_seconds"] + 60)
             + cells * 2 * 90 + 1200)
    assert total <= 43200


def probes_in(root):
    """Everything in the checkout that carries the reserved prefix: the
    manifest's names, the checks configurations name, and the files under
    benchmarks/ (a check's file by the name after `checks_`)."""
    manifest = manifest_of(root)
    found = {("name", e["name"]) for key in ("configs", "workloads",
                                              "end_to_end", "per_layer")
             for e in manifest[key] if e["name"].startswith(PROBE)}
    found |= {("traffic", w["traffic"]) for w in manifest["workloads"]
              if w["traffic"].startswith(PROBE)}
    for c in manifest["configs"]:
        check = load(root, c["file"]).get("correct", {}).get("check", "")
        if check.startswith(PROBE):
            found.add(("check", check))
    for folder, dirs, files in os.walk(os.path.join(root, "benchmarks")):
        # what runs leave behind (compiled modules, caches) is no entry
        dirs[:] = [d for d in dirs
                   if not (d.startswith(".") or d == "__pycache__")]
        for f in files:
            if f.startswith(PROBE) or f.startswith("checks_" + PROBE):
                found.add(("file", os.path.relpath(os.path.join(folder, f),
                                                   root)))
    return found


def check_the_probe_prefix_is_the_appended_fixtures_alone(root):
    """Nothing real takes the reserved prefix: a checkout holds none of it,
    or exactly what the appended-cell fixture appends."""
    fixture = {("name", PROBE_CELL), ("name", PROBE_CONFIG),
               ("name", PROBE_METRIC), ("traffic", PROBE_MIX),
               ("check", PROBE_CHECK),
               ("file", "benchmarks/configs/%s.json" % PROBE_CONFIG),
               ("file", "benchmarks/traffic/%s.json" % PROBE_MIX),
               ("file", "benchmarks/harness/checks_%s.py" % PROBE_CHECK),
               ("file", "benchmarks/layer_metrics/%s.json" % PROBE_METRIC)}
    assert probes_in(root) in (set(), fixture), probes_in(root) ^ fixture


def check_the_fused_root_metrics_list_only_int8_cells(root):
    """The driver wants every per-layer metric of a cell on its traced
    line, and float32 runs no fused root pass (it refused PR 22 over
    mslr-rank.train): the two metrics of that pass name their cells."""
    manifest = manifest_of(root)
    quantized = set()
    for w in manifest["workloads"]:
        spec = load(root, "benchmarks", "configs", w["config"] + ".json")
        if spec["params"].get("tpu_quantized_grad"):
            quantized.add(w["name"])
    for m in manifest["per_layer"]:
        if m["name"] in ("kernel.root.ms_per_iter", "fused_root_roofline"):
            assert set(m["workloads"]) <= quantized


# ---- the cells, each as the PR that added it left it ----------------------
def check_epsilon_is_listed_where_pr_27_says(root):
    m = manifest_of(root)
    cell = "epsilon-int8.train"
    entry = by_name(m["workloads"], cell)
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == ("epsilon-dense-int8", "train-fullbag", 1)
    config = by_name(m["configs"], entry["config"])
    assert config["reduced"] == ["num_iterations"]
    # what PR 27 listed is still listed; later PRs list more (PR 38: the
    # split scan's two metrics read here too)
    assert {"train_iter_ms", "setup.bin_s", "setup.bin_256k_s",
            "setup.warmup_s", "kernel.root.ms_per_iter",
            "fused_root_roofline", "xla.quantize.ms_per_iter",
            "partition_root_roofline"} <= listing(m, cell)
    c = load(root, config["file"])
    assert c["data"]["rows"] == c["published"]["rows"] == 400_000
    assert c["data"]["features"] == c["published"]["features"] == 2000
    for key in ("num_leaves", "learning_rate", "max_bin", "min_data_in_leaf",
                "min_sum_hessian_in_leaf"):
        assert c["params"][key] == c["published"][key], key
    assert c["expect"] == {"engine": "partition", "quantized": True,
                           "carried": True}


def check_allstate_is_listed_where_pr_32_says(root):
    m = manifest_of(root)
    cell = "allstate-efb.train"
    entry = by_name(m["workloads"], cell)
    assert (entry["config"], entry["traffic"], entry["chips"]) \
        == ("allstate-onehot-int8", "train-fullbag-sparse", 1)
    config = by_name(m["configs"], entry["config"])
    assert config["reduced"] == ["num_iterations"]
    assert {"train_iter_ms", "setup.bin_s", "setup.bin_256k_s",
            "setup.warmup_s", "kernel.root.ms_per_iter",
            "fused_root_roofline", "xla.quantize.ms_per_iter",
            "partition_root_roofline", "xla.scan_glue.ms_per_iter",
            "split_scan_roofline"} <= listing(m, cell)
    c = load(root, config["file"])
    assert c["source"] == config["source"]
    assert c["data"]["rows"] == c["published"]["rows"] == 13_184_290
    assert c["data"]["features"] == c["published"]["features"] == 4228
    assert sum(c["data"]["args"]["cardinalities"]) == 4228
    for key in ("num_leaves", "learning_rate", "max_bin"):
        assert c["params"][key] == c["published"][key], key
    # the block of the Higgs row of the same table, and bundling at defaults
    higgs = load(root, "benchmarks", "configs", "higgs-binary-int8.json")
    assert {k: c["params"][k] for k in higgs["params"]} == higgs["params"]
    # nothing pins the rounding's seed: it is `seed`, which --seed sets
    assert set(c["params"]) - set(higgs["params"]) == {
        "enable_bundle", "max_conflict_rate"}
    assert c["seed_params"] == ["seed"]
    # the source's block where the run departs from it, and the departure
    assert c["published"]["min_data_in_leaf"] == 0
    assert c["published"]["min_sum_hessian_in_leaf"] == 100
    assert {"min_data_in_leaf", "min_sum_hessian_in_leaf", "why",
            "effect"} <= set(c["departs"])
    assert c["reduced"] == ["num_iterations"]
    assert c["params"]["enable_bundle"] is True
    assert c["params"]["max_conflict_rate"] == 0
    assert c["expect"] == {"engine": "partition", "quantized": True,
                           "carried": True, "scan_space": "group"}
    assert 0 < c["correct"]["conflict_rows_max_share"] < 1e-2
    for key in ("gain_rtol", "leaf_value_rtol", "leaf_value_atol_of_largest",
                "f32_band", "own_band", "walker_atol"):
        assert c["correct"][key] == higgs["correct"][key], key


def check_higgs_valid_is_listed_as_pr_34_names_it(root):
    m = manifest_of(root)
    cell = "higgs-valid.train"
    entry = by_name(m["workloads"], cell)
    assert entry == {
        "name": cell, "config": "higgs-binary-int8-valid",
        "traffic": "train-eval", "chips": 1, "why": entry["why"]}
    assert len(entry["why"]) <= 200
    assert "eval_valid()" in entry["why"] and "500K" in entry["why"]
    config = by_name(m["configs"], "higgs-binary-int8-valid")
    assert config["file"] == "benchmarks/configs/higgs-binary-int8-valid.json"
    assert config["reduced"] == ["num_iterations"]
    # a source of its own, the accuracy table's row
    assert config["source"].endswith("Experiments.rst?plain=1#L127")
    assert config["source"] not in [c["source"] for c in m["configs"]
                                    if c is not config]


def check_higgs_valid_reports_the_train_metrics_and_its_own_three(root):
    from benchmarks.harness.manifest import Cell
    m = manifest_of(root)
    cell = Cell(root, "higgs-valid.train")
    assert [e["name"] for e in cell.end_to_end] == [
        "train_iter_ms", "peak_hbm_gib", "setup_s"]
    names = {p["name"] for p in cell.per_layer}
    assert set(VALID_THREE) <= names
    # what an int8 training cell on the carried spine reports, all of it
    headline = {p["name"] for p in Cell(root, "higgs-int8.train").per_layer}
    assert names - set(VALID_THREE) == headline
    for p in m["per_layer"]:
        if p["name"] in VALID_THREE:
            assert p["workloads"] == ["higgs-valid.train"]
            assert p["moves"] == "train_iter_ms"
            assert p["unit"] == "ms" and p["better"] == "lower"
    # appended side by side, and side by side still: PR 34 moved nothing
    assert stand_together(m["per_layer"], VALID_THREE)
    for metric, reader_args, reader in cell.layer_readers():
        if metric["name"] in VALID_THREE:
            assert reader.__name__.endswith(
                ("trace_scope", "program_span")), reader.__name__


def check_the_row_ledgers_seven_are_listed_as_their_files_say(root):
    """PR 36 built them, PR 38 listed them: each entry equal to the one
    its file carries, the seven side by side in their order, none with a
    `workloads` key, every train cell resolving them to the one reader."""
    from benchmarks.harness.manifest import Cell
    m = manifest_of(root)
    entries = [load(root, "benchmarks", "layer_metrics", name + ".json")
               ["entry"] for name in LEDGER_SEVEN]
    assert [e["name"] for e in entries] == LEDGER_SEVEN
    for e in entries:
        assert set(e) == {"name", "unit", "better", "source", "layer",
                          "moves"}
        assert (e["source"], e["layer"], e["moves"]) \
            == ("program_counter", "kernels", "train_iter_ms")
        assert e["unit"] == "%" if e["name"].endswith("_roofline") \
            else e["unit"] in ("passes", "ms", "us")
        assert by_name(m["per_layer"], e["name"]) == e
    assert stand_together(m["per_layer"], LEDGER_SEVEN)
    for cell in TRAIN_CELLS:
        resolved = {metric["name"]: reader.__name__ for metric, _, reader
                    in Cell(root, cell).layer_readers()}
        assert all(resolved[metric].endswith("row_ledger")
                   for metric in LEDGER_SEVEN), cell


def check_the_split_scans_two_list_the_cells_they_read_in(root):
    """PR 32's two metrics read wherever a tree is grown; PR 38 listed
    the train cells in which the chip read them (PERF.md section 6)."""
    m = manifest_of(root)
    for name in ("xla.scan_glue.ms_per_iter", "split_scan_roofline"):
        assert set(TRAIN_CELLS) <= set(
            by_name(m["per_layer"], name)["workloads"]), name


CHECKS = [value for name, value in sorted(globals().items())
          if name.startswith("check_")]
