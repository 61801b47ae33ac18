"""A temporary copy of the benchmark that tests may add to: the way a
later PR adds a cell, but without touching the repo."""
import json
import os
import shutil

import manifest_shape as shape

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def copy_of_the_benchmark(tmp_path):
    root = str(tmp_path / "overlay")
    shutil.copytree(os.path.join(REPO, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    return root


def edit_manifest(root, edit):
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        manifest = json.load(f)
    edit(manifest)
    with open(path, "w") as f:
        json.dump(manifest, f)
    return manifest


def write_new(root, rel, spec):
    """benchmarks/<rel>, which must not exist yet: a later PR only adds
    files.  `spec` is the file's text, or an object written as JSON."""
    path = os.path.join(root, "benchmarks", rel)
    assert not os.path.exists(path), "a later PR only adds files"
    with open(path, "w") as f:
        f.write(spec if isinstance(spec, str) else json.dumps(spec))


def add_probe_mix(root, name=shape.PROBE_MIX, **changes):
    """traffic/train-fullbag.json under a name of the reserved prefix, with
    `changes` laid over its top-level keys."""
    assert name.startswith(shape.PROBE), name
    mix = dict(shape.load(root, "benchmarks", "traffic", "train-fullbag.json"),
               **changes)
    write_new(root, "traffic/%s.json" % name, mix)
    return mix


def add_train_cell(root, name=shape.PROBE_CELL, config="higgs-binary-int8",
                   traffic=shape.PROBE_MIX, like="higgs-int8.train"):
    """A further training cell, under the reserved prefix by default, over
    a mix that add_probe_mix wrote (or any mix the copy has).  It joins
    every metric's `workloads` list that the cell `like` it (same kind of
    traffic, same precision) is in."""
    def edit(manifest):
        manifest["workloads"].append({
            "name": name, "config": config, "traffic": traffic, "chips": 1,
            "why": "update() back to back under another traffic mix"})
        for metric in manifest["end_to_end"] + manifest["per_layer"]:
            if like in metric.get("workloads", ()):
                metric["workloads"].append(name)
    return edit_manifest(root, edit)


def add_predict_cell(root, name="higgs-int8.predict",
                     config="higgs-binary-int8", traffic="predict-batch"):
    """The batch-predict cell PERF.md keeps for later (it ran on the chip
    in PR 22 but holds too little device memory to be admitted): appended
    entries only, over files the benchmark already has."""
    def edit(manifest):
        manifest["workloads"].append({
            "name": name, "config": config, "traffic": traffic, "chips": 1,
            "why": "Booster.predict on host batches, closed loop"})
        manifest["end_to_end"].append({
            "name": "predict_mrows_per_s", "unit": "Mrows/s",
            "better": "higher", "bound": 0.03, "source": "host_clock",
            "workloads": [name]})
        for metric, unit, source, layer in (
                ("predict.host_ms_per_call", "ms", "host_clock", "entry"),
                ("predict.programs_per_call", "programs", "device_trace",
                 "predict"),
                ("predict.device_ms_per_mrow", "ms", "device_trace",
                 "predict"),
                ("predict_matmul_roofline", "%", "device_trace", "predict"),
                ("predict.device_idle_share", "%", "device_trace", "device"),
                ("setup.model_s", "s", "host_clock", "setup")):
            # every cell reports setup_s, so a set-up metric that only
            # this cell has must say so
            manifest["per_layer"].append(dict({
                "name": metric, "unit": unit, "better": "lower",
                "source": source, "layer": layer,
                "moves": "setup_s" if layer == "setup"
                else "predict_mrows_per_s"},
                **({"workloads": [name]} if layer == "setup" else {})))
    return edit_manifest(root, edit)
