"""BENCHMARK.json against the benchmark's contract, as far as a file check
can go, and against the files it names."""
import json
import os
import re

import pytest

from bench_overlay import REPO

NAME = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_top_level_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmarks", "tests/benchmark"]
    assert manifest["command"] == ["python3", "benchmarks/run.py"]
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 << 10
    assert 2 <= len(manifest["workloads"]) <= 24
    assert 1 <= len(manifest["end_to_end"]) <= 16
    assert 1 <= len(manifest["per_layer"]) <= 128


def test_names_are_plain_and_used_once(manifest):
    names = [e["name"] for key in ("configs", "workloads", "end_to_end",
                                   "per_layer") for e in manifest[key]]
    assert all(NAME.match(n) for n in names), names
    assert len(names) == len(set(names))
    for key in ("configs", "workloads"):
        assert all(len(e["why"]) <= 200 for e in manifest[key])


def test_configs_are_files_of_their_own_and_every_one_is_used(manifest):
    files = [c["file"] for c in manifest["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}
    for c in manifest["configs"]:
        assert c["file"].startswith("benchmarks/configs/")
        with open(os.path.join(REPO, c["file"])) as f:
            spec = json.load(f)
        assert spec["source"] == c["source"]
        assert spec["reduced"] == c["reduced"]


def test_cells_name_existing_files_once(manifest):
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    # four chips cost four times: a quarter of the cells at most, one always
    on_four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert on_four <= max(1, len(pairs) // 4)
    for w in manifest["workloads"]:
        assert w["chips"] in (1, 4)
        assert os.path.exists(os.path.join(
            REPO, "benchmarks", "traffic", w["traffic"] + ".json"))


def test_metrics_follow_the_contract(manifest):
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


def test_every_per_layer_metric_has_its_file_and_reader(manifest):
    listed = {m["name"] for m in manifest["per_layer"]}
    folder = os.path.join(REPO, "benchmarks", "layer_metrics")
    on_disk = {f[:-len(".json")] for f in os.listdir(folder)}
    # files beyond the list belong to cells PERF.md keeps for later, which
    # a PR brings by appending entries
    assert listed <= on_disk
    for name in listed:
        with open(os.path.join(folder, name + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(
            REPO, "benchmarks", "readers", spec["reader"] + ".py"))


def test_full_check_fits_the_drivers_budget(manifest):
    """2 + 14 runs per cell, each run_seconds + 60 s, 2 x 90 s per cell to
    compile, 1200 s spare, all within 43200 s — at the full 24 cells."""
    cells = 24
    total = ((2 + 14 * cells) * (manifest["run_seconds"] + 60)
             + cells * 2 * 90 + 1200)
    assert total <= 43200


def test_the_fused_root_metrics_list_only_int8_cells(manifest):
    """The driver wants every per-layer metric of a cell on its traced
    line, and float32 runs no fused root pass (it refused PR 22 over
    mslr-rank.train): the two metrics of that pass name their cells."""
    quantized = set()
    for w in manifest["workloads"]:
        with open(os.path.join(REPO, "benchmarks", "configs",
                               w["config"] + ".json")) as f:
            if json.load(f)["params"].get("tpu_quantized_grad"):
                quantized.add(w["name"])
    for m in manifest["per_layer"]:
        if m["name"] in ("kernel.root.ms_per_iter", "fused_root_roofline"):
            assert set(m["workloads"]) <= quantized
