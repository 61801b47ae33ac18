"""The harness is driven by data: a new configuration, traffic mix,
per-layer metric and reader are new files plus appended entries, and the
new cell runs without any existing file changing."""
import filecmp
import json
import os

from bench_overlay import (REPO, add_predict_cell, copy_of_the_benchmark,
                           edit_manifest)
from benchmarks import run


def _write(root, rel, text):
    path = os.path.join(root, "benchmarks", rel)
    assert not os.path.exists(path), "the overlay may only add files"
    with open(path, "w") as f:
        f.write(text)


def test_a_new_cell_is_new_files_and_appended_entries(tmp_path, capsys):
    root = copy_of_the_benchmark(tmp_path)
    with open(os.path.join(root, "benchmarks", "configs",
                           "higgs-binary-int8.json")) as f:
        config = json.load(f)
    # a configuration: the f32 twin the README works through
    del config["params"]["tpu_quantized_grad"]
    config["expect"]["quantized"] = False
    _write(root, "configs/higgs-binary-f32.json", json.dumps(config))
    # a traffic mix of an existing kind: data only
    _write(root, "traffic/predict-small.json", json.dumps({
        "kind": "predict",
        "model": {"trees": 64, "leaves": 7, "bins": 63, "leaf_scale": 0.02,
                  "edge_sample_rows": 4096},
        "pool_rows": 131072, "batch_rows": 65536, "trace_calls": 1,
        "walker_rows": 128, "walker_atol": 1e-5,
        "expect": {"server": "DeviceEnsemble"}}))
    # a per-layer metric with a reader of its own
    _write(root, "layer_metrics/predict.calls_in_window.json", json.dumps(
        {"reader": "window_units", "args": {}}))
    _write(root, "readers/window_units.py",
           "def read(run, args):\n    return run.shape['units']\n")

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        before = json.load(f)

    def edit(manifest):
        manifest["configs"].append({
            "name": "higgs-binary-f32", "source": config["source"],
            "file": "benchmarks/configs/higgs-binary-f32.json",
            "reduced": config["reduced"], "why": "the f32 twin"})
        manifest["per_layer"].append({
            "name": "predict.calls_in_window", "unit": "calls",
            "better": "higher", "source": "program_counter",
            "layer": "predict", "moves": "predict_mrows_per_s"})

    add_predict_cell(root, "higgs-f32.predict-small", "higgs-binary-f32",
                     "predict-small")
    manifest = edit_manifest(root, edit)

    assert run.main(["--workload", "higgs-f32.predict-small", "--seed", "9",
                     "--seconds", "0.3", "--trace", "1", "--rehearse"],
                    root=root) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["correct"] is True and last["attempted"] >= 1
    assert "predict.calls_in_window" in last["metrics"]
    assert "predict.host_ms_per_call" in last["metrics"]

    # nothing that was there changed: the files byte for byte, the
    # manifest entry for entry
    diff = filecmp.dircmp(os.path.join(REPO, "benchmarks"),
                          os.path.join(root, "benchmarks"),
                          ignore=[".cache", "__pycache__"])

    def changed(d):
        return d.diff_files + d.left_only + d.funny_files + [
            x for sub in d.subdirs.values() for x in changed(sub)]

    assert changed(diff) == []
    for key in ("command", "paths", "run_seconds"):
        assert manifest[key] == before[key]
    for key in ("configs", "workloads", "per_layer"):
        assert manifest[key][:len(before[key])] == before[key]
    assert manifest["end_to_end"][:len(before["end_to_end"])] \
        == before["end_to_end"]


def test_the_run_writes_only_under_its_own_cache(tmp_path, capsys):
    root = copy_of_the_benchmark(tmp_path)
    add_predict_cell(root)
    before = {os.path.join(d, f) for d, _, fs in os.walk(root) for f in fs}
    assert run.main(["--workload", "higgs-int8.predict", "--seed", "9",
                     "--seconds", "0.3", "--trace", "1", "--rehearse"],
                    root=root) == 0
    capsys.readouterr()
    after = {os.path.join(d, f) for d, _, fs in os.walk(root) for f in fs}
    cache = os.path.join(root, "benchmarks", ".cache") + os.sep
    new = {p for p in after - before if "__pycache__" not in p}
    assert new and all(p.startswith(cache) for p in new), new
