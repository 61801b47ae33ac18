"""The harness's own pieces: resolution of a cell by names, the run's
clocks and spans, and the binned-data cache."""
import os
import time

import numpy as np
import pytest

import lightgbm_tpu as lgb
from bench_overlay import REPO
from benchmarks.data import higgs
from benchmarks.harness import binned
from benchmarks.harness.bench import Bench
from benchmarks.harness.manifest import Cell, deep_merge
from benchmarks.harness.steady import interquartile_mean


def test_deep_merge_lays_the_override_over_nested_keys_only():
    base = {"a": 1, "b": {"x": 1, "y": {"p": 1, "q": 2}}, "c": [1, 2]}
    over = {"b": {"y": {"q": 3}, "z": 4}, "c": [9]}
    assert deep_merge(base, over) == {
        "a": 1, "b": {"x": 1, "y": {"p": 1, "q": 3}, "z": 4}, "c": [9]}
    assert base["b"]["y"]["q"] == 2             # the base is left alone


@pytest.mark.parametrize("name", ["higgs-int8.train", "mslr-rank.train"])
def test_a_cell_resolves_to_its_files_by_name(name):
    cell = Cell(REPO, name)
    assert cell.chips == 1
    assert cell.config["params"]["num_leaves"] == 255
    assert cell.config["data"]["rows"] >= 2_000_000   # full size, no preset
    assert callable(cell.driver().run)
    assert callable(cell.generator().features)
    assert {m["name"] for m in cell.end_to_end} \
        == {"train_iter_ms", "peak_hbm_gib", "setup_s"}
    readers = cell.layer_readers()
    assert len(readers) == len(cell.per_layer) >= 10
    assert all(callable(reader.read) for _, _, reader in readers)


def test_rehearsal_lays_each_files_tiny_preset_over_it():
    full, tiny = (Cell(REPO, "higgs-int8.train", rehearse=r)
                  for r in (False, True))
    assert tiny.config["data"]["rows"] < 10_000 < full.config["data"]["rows"]
    assert tiny.config["params"]["num_leaves"] < 255
    # what the preset does not name stays: widths, objective, generator
    assert tiny.config["data"]["features"] == 28
    assert tiny.config["params"]["objective"] == "binary"
    assert tiny.config["correct"]["gain_rtol"] \
        == full.config["correct"]["gain_rtol"]
    assert tiny.traffic["block_iterations"] < full.traffic["block_iterations"]


def _bench(tmp_path, name="higgs-int8.train"):
    return Bench(str(tmp_path), Cell(REPO, name, rehearse=True), seed=1,
                 seconds=1, trace=0, t_start=time.perf_counter())


def test_phases_add_up_and_spans_of_the_window_are_kept(tmp_path):
    bench = _bench(tmp_path)
    for _ in range(2):
        with bench.phase("data"):
            time.sleep(0.01)
    assert 0.02 <= bench.phases["data"] < 0.5
    with bench.span("before-the-window"):
        pass
    bench.open_window()
    with bench.span("update"):
        time.sleep(0.01)
    assert bench.close_window() >= 0.01
    with bench.span("after-the-window"):
        pass
    assert [name for name, _, _ in bench.window_spans] == ["update"]
    (_, start, end), = bench.window_spans
    assert bench.window[0] <= start < end <= bench.window[1]
    assert bench.compiles_in_window == 0
    assert bench.setup_s >= bench.phases["data"]


def test_a_compilation_inside_the_window_is_counted(tmp_path):
    import jax
    import jax.numpy as jnp
    bench = _bench(tmp_path)
    bench.open_window()
    jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()
    bench.close_window()
    assert bench.compiles_in_window >= 1


def test_cache_files_live_under_the_checkouts_benchmark_cache(tmp_path):
    bench = _bench(tmp_path)
    path = bench.cache_path("binned", "x.bin")
    assert path == os.path.join(str(tmp_path), "benchmarks", ".cache",
                                "binned", "x.bin")
    assert os.path.isdir(os.path.dirname(path))


def test_binned_cache_misses_once_then_hits(tmp_path):
    bench = _bench(tmp_path)
    X = higgs.features({"feature_seed": 22}, "train", 2000)
    params = {"max_bin": 255, "verbose": -1}
    y1, _ = higgs.labels({"label_seed": 22}, 1, "train", X)
    first, hit1 = binned.cached(bench, lgb, X, y1, None, params, "k")
    y2, _ = higgs.labels({"label_seed": 22}, 2, "train", X)
    second, hit2 = binned.cached(bench, lgb, X, y2, None, params, "k")
    assert (hit1, hit2) == (False, True)
    folder = os.path.dirname(bench.cache_path("binned", "k.bin"))
    assert os.listdir(folder) == ["k.bin"]       # no temporary file is left
    assert np.array_equal(first._binned.bins, second._binned.bins)
    assert np.array_equal(second.get_label(), y2)    # this run's label


@pytest.mark.parametrize("values,expected", [
    ([5.0], 5.0),
    ([1, 2, 9], 4.0),                          # under four values: the mean
    ([4, 1, 3, 100], 3.5),                     # one of four set aside each end
    ([340, 351, 360, 370, 372, 382, 376, 382, 387, 381, 384],   # 11 blocks
     (360 + 370 + 372 + 376 + 381 + 382 + 382) / 7),
    # a stalled block (404 for 370) moves the result by what replaces it
    ([340, 351, 360, 404, 372, 382, 376, 382, 387, 381, 384],
     (360 + 372 + 376 + 381 + 382 + 382 + 384) / 7),
])
def test_interquartile_mean_sets_a_quarter_aside_at_each_end(values,
                                                             expected):
    assert interquartile_mean(values) == pytest.approx(expected)
