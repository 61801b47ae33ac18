"""What PR 36 added to the benchmark: the in-loop kernels' calls found one
by one in a trace (harness/loop_calls.py), their byte floors
(harness/costs_inloop.py) and the reader that pairs them with the
program's split ledger (readers/row_ledger.py), on a trace recorded on the
chip with the ledger of its two trees saved beside it
(`tools/row_ledger_fit.py --workload higgs-int8.train --rehearse --seed 7
--save`: 4 096 rows x 28 columns, int8, 7 leaves, so 6 `partition_segment`
and 6 `segment_histogram` calls a tree inside one growth `while`)."""
import gzip
import json
import os
import shutil
import types

import numpy as np
import pytest

from benchmarks.harness import costs_inloop, costs_partition, loop_calls
from benchmarks.harness import manifest
from benchmarks.harness import trace_reduce as tr
import manifest_shape as shape
from bench_overlay import REPO
from lightgbm_tpu.obs import device as obs_device

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SEVEN = ["kernel.partition.row_passes_per_iter",
         "kernel.partition.ms_per_pass", "partition_roofline",
         "kernel.partition.call_us", "kernel.seg_hist.ms_per_pass",
         "seg_hist_roofline", "kernel.seg_hist.call_us"]


def _unpacked(tmp_path_factory, name):
    path = str(tmp_path_factory.mktemp("trace") / (name + ".xplane.pb"))
    with gzip.open(os.path.join(DATA, name + ".xplane.pb.gz"), "rb") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """(trace file, what was saved beside it)."""
    with open(os.path.join(DATA, "tiny_v5e_ledger.json")) as f:
        saved = json.load(f)
    return _unpacked(tmp_path_factory, "tiny_v5e_ledger"), saved


@pytest.fixture
def fed(recorded):
    """The program's ring holding the recorded trees last, as after the
    run that recorded them."""
    for e in recorded[1]["ledgers"]:
        obs_device.record_split_ledger(
            e["iteration"], e["slot"], e["num_data"],
            np.array(e["partition_rows"]), np.array(e["histogram_rows"]))
    return recorded


def _spec(metric):
    return manifest.load_json(REPO, "benchmarks", "layer_metrics",
                              metric + ".json")


def _reader(metric=SEVEN[0]):
    return manifest.load_module(REPO, "readers", _spec(metric)["reader"])


def _run(recorded, trace=True, **shape):
    path, saved = recorded
    return types.SimpleNamespace(
        trace=tr.reduce(path) if trace else None, xplane=path, spans=[],
        phases={}, device_kind=saved["device_kind"],
        shape=dict(saved["shape"], **shape))


def _read(metric, run, **more):
    spec = _spec(metric)
    return _reader(metric).read(run, dict(spec["args"], **more))


def test_floors_are_the_hand_reckoned_bytes():
    # 28 columns: 48 arena channels of 2 bytes, read and written
    assert costs_inloop.partition_bytes(1000, 28) == 2 * 1000 * 48 * 2
    assert costs_inloop.partition_bytes(10_500_000, 28) \
        == costs_partition.partition_root_bytes(10_500_000, 28)
    # 137 columns pad to 144; gradient and hessian are three planes each;
    # the histogram written is 137 x 255 float32 triples
    assert costs_inloop.histogram_bytes(1000, 137, 255) \
        == 1000 * (144 + 6) * 2 + 137 * 255 * 3 * 4
    assert costs_inloop.histogram_bytes(0, 28, 63) == 28 * 63 * 12
    assert costs_inloop.histogram_bytes(7, 2000, 63) \
        == 7 * 2006 * 2 + 2000 * 63 * 12


def test_calls_are_grouped_by_the_loop_that_encloses_them(
        recorded, tmp_path_factory):
    path, _ = recorded
    for metric in (SEVEN[0], SEVEN[4]):
        (chip,) = loop_calls.in_loop_calls(
            path, _spec(metric)["args"]["pattern"])
        assert [len(tree) for tree in chip] == [6, 6]
        assert all(0 < s < 1e-3 for tree in chip for s in tree)
    # PR 24's lambdarank trace: a float32 root histogram runs before each
    # tree's growth loop, under no `while`, and is no in-loop call
    scoped = _unpacked(tmp_path_factory, "tiny_v5e_scoped")
    pattern = _spec(SEVEN[4])["args"]["pattern"]
    (chip,) = loop_calls.in_loop_calls(scoped, pattern)
    assert [len(tree) for tree in chip] == [6, 6]
    assert tr.reduce(scoped).family(pattern)[1] == 14
    assert loop_calls.in_loop_calls(scoped, "^no_such_kernel") == []


# read off the recorded run once (my chip run, PR 36, call 1)
PINNED = {
    "kernel.partition.row_passes_per_iter": 3.763916015625,
    "kernel.partition.ms_per_pass": 0.006393872737886748,
    "partition_roofline": 15.018041046462885,
    "kernel.partition.call_us": 4.260871559633027,
    "kernel.seg_hist.ms_per_pass": 0.023925699545867395,
    "seg_hist_roofline": 5.4926828779297985,
    "kernel.seg_hist.call_us": 2.6797214343665487,
}


def test_the_seven_metrics_read_the_recorded_run(fed):
    run = _run(fed)
    values = {metric: _read(metric, run) for metric in SEVEN}
    assert values == pytest.approx(PINNED, rel=1e-9)
    # passes x ms a pass is the kernel's time per iteration, which the
    # accepted metric reads from the reduced trace
    family = manifest.load_module(REPO, "readers", "trace_family")
    per_iter = family.read(run, _spec("kernel.partition.ms_per_iter")["args"])
    assert values[SEVEN[0]] * values[SEVEN[1]] == pytest.approx(per_iter)
    for metric in ("partition_roofline", "seg_hist_roofline"):
        assert 0 < values[metric] < 100
    # by hand: the ledger's rows over the data set's, per tree
    rows = [sum(e["partition_rows"]) for e in fed[1]["ledgers"]]
    assert values[SEVEN[0]] == pytest.approx(sum(rows) / 4096 / 2)


def test_over_first_calls_the_share_is_the_root_metrics(fed):
    run = _run(fed)
    root = manifest.load_module(REPO, "readers", "roofline_partition_root")
    want = root.read(run, _spec("partition_root_roofline")["args"])
    assert _read("partition_roofline", run, calls="first") \
        == pytest.approx(want, rel=1e-12)
    assert _read("partition_roofline", run) < want


def test_without_a_trace_a_ledger_or_a_match_nothing_is_read(
        fed, monkeypatch):
    metric = "partition_roofline"
    assert _read(metric, _run(fed)) is not None
    assert _read(metric, _run(fed, trace=False)) is None
    # other trees than the slice's: their first partition is not every row
    assert _read(metric, _run(fed, rows=4095)) is None
    # more trees traced than loops found
    assert _read(metric, _run(fed, traced_units=3)) is None
    # a tree whose entry is one step short of its loop's calls
    last = fed[1]["ledgers"][-1]
    obs_device.record_split_ledger(
        last["iteration"], last["slot"], last["num_data"],
        np.array(last["partition_rows"][:-1]),
        np.array(last["histogram_rows"][:-1]))
    assert _read(metric, _run(fed)) is None
    assert _read("seg_hist_roofline", _run(fed)) is None
    # a one-leaf tree (empty arrays) among the slice's
    obs_device.record_split_ledger(0, 0, 4096, np.zeros(0, np.int64),
                                   np.zeros(0, np.int64))
    assert _read(metric, _run(fed)) is None
    # two chips: the ledger's counts are global, a chip's calls are not
    two = _run(fed)
    two.trace.chips = 2
    assert _read(metric, two) is None
    # the program before the ledger
    monkeypatch.delattr(obs_device, "split_ledgers")
    assert all(_read(m, _run(fed)) is None for m in SEVEN)


def test_the_line_through_exact_points_is_theirs():
    reader = _reader()
    rows = np.array([100, 4000, 2500, 900, 100, 7000])
    assert reader.theil_sen(rows, 3e-9 * rows + 5e-6) \
        == pytest.approx((3e-9, 5e-6))
    assert reader.theil_sen([5, 5, 5], [1.0, 2.0, 3.0]) is None


def test_appended_as_their_files_say_every_train_cell_reads_the_seven():
    """BENCHMARK.json lists the seven since PR 38 (PR 36 built them and
    could not: a test it might not edit pinned the list's last three
    entries): each entry equal to the one its metric's file carries,
    appended in order, no `workloads` key (`kernel.partition.ms_per_iter`
    has none either), every train cell resolving them to this reader."""
    assert shape.LEDGER_SEVEN == SEVEN
    shape.check_the_row_ledgers_seven_are_listed_as_their_files_say(REPO)
