"""BENCHMARK.json and the files it names, found by name and never from a
table in code:

    workloads[].config   -> benchmarks/configs/<config>.json
        its data.generator   -> benchmarks/data/<generator>.py
    workloads[].traffic  -> benchmarks/traffic/<mix>.json
        its kind             -> benchmarks/drivers/<kind>.py
    per_layer[].name     -> benchmarks/layer_metrics/<name>.json
        its reader           -> benchmarks/readers/<reader>.py

A later PR adds a cell, a mix, a metric, a generator, a driver kind or a
reader by adding files and appending entries to BENCHMARK.json.
"""
import importlib.util
import json
import os

BENCH_DIR = "benchmarks"


def load_json(root, *parts):
    with open(os.path.join(root, *parts)) as f:
        return json.load(f)


def load_module(root, folder, name):
    """benchmarks/<folder>/<name>.py, loaded by its path so that a file
    added beside the existing ones is found without being registered."""
    path = os.path.join(root, BENCH_DIR, folder, name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks_%s_%s" % (folder, name.replace("-", "_")), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def deep_merge(base, override):
    """`base` with `override` laid over it, dictionaries merged key by key."""
    out = dict(base)
    for key, value in override.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = deep_merge(out[key], value)
        else:
            out[key] = value
    return out


def _applies(metric, cell_name):
    return cell_name in metric.get("workloads", [cell_name])


class Cell:
    """One entry of `workloads` with everything it names resolved."""

    def __init__(self, root, name, rehearse=False):
        self.root = root
        manifest = load_json(root, "BENCHMARK.json")
        entries = [w for w in manifest["workloads"] if w["name"] == name]
        if not entries:
            raise SystemExit(
                "no workload %r in BENCHMARK.json (it has: %s)" % (
                    name, ", ".join(w["name"] for w in manifest["workloads"])))
        entry = entries[0]
        self.name = name
        self.run_seconds = manifest["run_seconds"]
        self.chips = int(entry["chips"])
        self.config_name = entry["config"]
        self.traffic_name = entry["traffic"]
        self.config = load_json(root, BENCH_DIR, "configs",
                                entry["config"] + ".json")
        self.traffic = load_json(root, BENCH_DIR, "traffic",
                                 entry["traffic"] + ".json")
        if rehearse:
            # the tiny preset each file carries for the CPU
            for spec in (self.config, self.traffic):
                spec.update(deep_merge(spec, spec.get("rehearse", {})))
        self.end_to_end = [m for m in manifest["end_to_end"]
                           if _applies(m, name)]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in manifest["per_layer"]
                          if _applies(m, name) and m["moves"] in reported]

    def driver(self):
        return load_module(self.root, "drivers", self.traffic["kind"])

    def generator(self):
        return load_module(self.root, "data",
                           self.config["data"]["generator"])

    def layer_readers(self):
        """[(metric entry, its file's arguments, reader module)]."""
        out = []
        for metric in self.per_layer:
            spec = load_json(self.root, BENCH_DIR, "layer_metrics",
                             metric["name"] + ".json")
            out.append((metric, spec.get("args", {}),
                        load_module(self.root, "readers", spec["reader"])))
        return out
