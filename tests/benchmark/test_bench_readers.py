"""Each per-layer reader on a hand-made run: the number it takes, and
nothing when there is nothing to read."""
import types

import pytest

from benchmarks.harness import manifest
from benchmarks.harness.trace_reduce import TraceSummary
from bench_overlay import REPO

OPS = {
    "partition_segment.13 bf16[48,63033344] mosaic": (0.6, 508),
    "segment_histogram.13 f32[768,128] mosaic": (0.1, 508),
    "fused_refresh_histogram.1 bf16[48,63033344] mosaic": (0.04, 2),
    "while.36 f32[254,16]": (0.01, 2),
    "fusion.27 f32[1,10500000]": (0.03, 2),
}


def _run(trace=True, **shape):
    summary = TraceSummary(window_s=0.8, busy_s=0.78, ops=OPS, programs=20,
                           gaps=[("update", 0.02)], chips=1) if trace else None
    return types.SimpleNamespace(
        device_kind="TPU v5 lite", trace=summary,
        phases={"data": 1.5, "compile": 6.0},
        spans=[("update", 0.0, 0.004), ("update", 1.0, 1.008),
               ("sync", 2.0, 2.9)],
        shape=dict({"units": 2, "traced_units": 2, "rows": 10_500_000,
                    "features": 28, "max_bin": 255}, **shape))


def _read(metric, run):
    spec = manifest.load_json(REPO, "benchmarks", "layer_metrics",
                              metric + ".json")
    reader = manifest.load_module(REPO, "readers", spec["reader"])
    return reader.read(run, spec.get("args", {}))


@pytest.mark.parametrize("metric,value", [
    ("setup.data_s", 1.5),
    ("setup.compile_s", 6.0),
    ("entry.host_ms_per_iter", 6.0),             # (4 + 8) ms over 2 iterations
    ("spine.programs_per_iter", 10.0),
    ("spine.gap_ms_per_iter", 10.0),             # 20 ms idle over 2 iterations
    ("device.idle_share", 2.5),
    ("kernel.partition.ms_per_iter", 300.0),
    ("kernel.seg_hist.ms_per_iter", 50.0),
    ("kernel.root.ms_per_iter", 20.0),
    ("xla.other_ms_per_iter", 20.0),             # the while and the fusion
    # 10.5M rows x 100 B = 1.05 GB at 819 GB/s is 1.28 ms a call, of 20 ms
    ("fused_root_roofline", 100 * (10_500_000 * 100 + 28 * 255 * 12)
     / 819e9 / 0.02),
])
def test_reader_takes_its_number(metric, value):
    assert _read(metric, _run()) == pytest.approx(value, rel=1e-9)


@pytest.mark.parametrize("metric", [
    "setup.bin_s",                     # the run had no such phase
    "kernel.split_scan.ms_per_iter",   # no such operation in the trace
    "kernel.compact.ms_per_iter",
])
def test_reader_returns_nothing_when_there_is_nothing_to_read(metric):
    assert _read(metric, _run()) is None


@pytest.mark.parametrize("metric", [
    "spine.programs_per_iter", "device.idle_share", "xla.other_ms_per_iter",
    "kernel.partition.ms_per_iter", "fused_root_roofline",
    "predict.device_ms_per_mrow", "predict_matmul_roofline"])
def test_trace_readers_return_nothing_without_a_reduced_trace(metric):
    assert _read(metric, _run(trace=False)) is None


def test_predict_readers_at_the_500_tree_shape():
    run = _run(rows=262_144, ensemble={"T": 512, "L": 256, "N": 254})
    # 0.78 s busy over 2 calls of 262144 rows
    assert _read("predict.device_ms_per_mrow", run) == pytest.approx(
        780.0 / (2 * 0.262144))
    assert _read("predict_matmul_roofline", run) == pytest.approx(
        100 * 2 * 262_144 * 66_584_576 / 197e12 / 0.78)
    assert _read("predict.host_ms_per_call", run) is None   # no such span
    assert _read("predict_matmul_roofline", _run(ensemble=None)) is None


def test_a_roofline_on_an_unknown_device_is_an_error():
    run = _run()
    run.device_kind = "TPU v9"
    with pytest.raises(KeyError, match="no published peaks"):
        _read("fused_root_roofline", run)
