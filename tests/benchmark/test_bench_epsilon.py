"""What PR 27 added to the benchmark: the configuration `epsilon-dense-int8`
and its cell (the tiny preset end to end, on a copy), the byte floor of a
tree's root partition (harness/costs_partition.py) and the reader that
finds each tree's first `partition_segment` call in a trace
(readers/roofline_partition_root.py), on the trace recorded on the chip."""
import gzip
import json
import os
import shutil
import types

import pytest

from benchmarks import run
from benchmarks.harness import costs_partition, manifest
from benchmarks.harness import trace_reduce as tr
import manifest_shape as shape
from bench_overlay import REPO, copy_of_the_benchmark

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
METRIC = "partition_root_roofline"


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    """Two iterations of a 7-leaf lambdarank tree over 3840 rows x 137
    columns on a TPU v5e (PR 24's tree): 6 `partition_segment` calls a
    tree, all inside one growth `while`."""
    path = str(tmp_path_factory.mktemp("trace") / "scoped.xplane.pb")
    with gzip.open(os.path.join(DATA, "tiny_v5e_scoped.xplane.pb.gz"),
                   "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


def _spec():
    return manifest.load_json(REPO, "benchmarks", "layer_metrics",
                              METRIC + ".json")


def _reader():
    return manifest.load_module(REPO, "readers", _spec()["reader"])


def _run(path, trace=True, **shape):
    return types.SimpleNamespace(
        trace=tr.reduce(path) if trace else None, xplane=path, spans=[],
        phases={}, device_kind="TPU v5 lite",
        shape=dict({"units": 2, "traced_units": 2, "rows": 3840,
                    "features": 137, "max_bin": 255}, **shape))


def _partition_calls(path):
    """The kernel's events on the chip's operation line, in time order,
    read here without the reader's help."""
    from jax.profiler import ProfileData
    calls = []
    for plane in ProfileData.from_file(path).planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            if line.name == tr.OPS_LINE:
                calls += [(e.start_ns, e.duration_ns) for e in line.events
                          if tr.op_label(e.name).startswith(
                              "partition_segment")]
    return sorted(calls)


def test_root_bytes_are_rows_times_channels_read_and_written():
    # 2 000 columns: 2 000 + 9 payload planes -> 2 016 channels of 2 bytes
    assert costs_partition.arena_channels(2000) == 2016
    assert costs_partition.partition_root_bytes(400_000, 2000) \
        == 2 * 400_000 * 2016 * 2
    assert costs_partition.arena_channels(28) == 48
    assert costs_partition.arena_channels(137) == 160


@pytest.mark.parametrize("features", [1, 7, 8, 28, 137, 520, 700, 968, 2000])
def test_the_benchmarks_channel_count_is_the_programs(features):
    """Two copies on purpose (a kernel PR cannot move its own floor); they
    must agree today."""
    from lightgbm_tpu.ops import partition_pallas as pp
    assert costs_partition.arena_channels(features) \
        == pp.arena_channels(features)


def test_reader_takes_each_trees_first_call(scoped):
    calls = _partition_calls(scoped)
    assert len(calls) == 12                      # 6 splits x 2 trees
    firsts = [calls[0][1] / 1e9, calls[6][1] / 1e9]
    reader = _reader()
    assert reader.first_calls(scoped, _spec()["args"]["pattern"], 2) \
        == pytest.approx(firsts)
    floor_s = 2 * 3840 * 160 * 2 / 819e9         # read + write, bf16
    value = reader.read(_run(scoped), _spec()["args"])
    assert value == pytest.approx(100 * 2 * floor_s / sum(firsts))
    assert 0 < value < 100


def test_reader_cuts_by_count_where_no_loop_encloses_the_calls(
        scoped, monkeypatch):
    """A trace whose operation line shows no `while` around the kernel:
    the calls are cut into `traced_units` equal runs."""
    reader = _reader()
    real = tr.op_label
    monkeypatch.setattr(tr, "op_label", lambda text: (
        "loop" + real(text) if real(text).startswith("while")
        else real(text)))
    calls = _partition_calls(scoped)
    assert reader.first_calls(scoped, _spec()["args"]["pattern"], 2) \
        == pytest.approx([calls[0][1] / 1e9, calls[6][1] / 1e9])
    # a count the trees do not divide tells nothing
    assert reader.first_calls(scoped, _spec()["args"]["pattern"], 5) == []


def test_reader_returns_nothing_when_there_is_nothing_to_read(scoped):
    reader = _reader()
    assert reader.read(_run(scoped, trace=False), _spec()["args"]) is None
    assert reader.read(_run(scoped), {"pattern": "^no_such_kernel"}) is None


def test_the_manifest_lists_the_cell_where_the_issue_says():
    shape.check_epsilon_is_listed_where_pr_27_says(REPO)


def test_the_cell_rehearses_in_channel_blocks(tmp_path, capsys):
    """The tiny preset (4 096 rows x 520 columns, 7 leaves): 544 arena
    channels are two partition blocks and five histogram steps, so the
    rehearsal runs the blocked kernels, the checks against the float64
    grower and the trace plumbing end to end."""
    from lightgbm_tpu.ops import partition_pallas as pp
    plan = pp.engine_plan(520, 63, True)
    assert plan["partition_blocks"] > 1 and plan["hist_steps"] > 1
    root = copy_of_the_benchmark(tmp_path)
    assert run.main(["--workload", "epsilon-int8.train", "--seed",
                     "2147483747", "--seconds", "0.3", "--trace", "1",
                     "--rehearse"], root=root) == 0
    out = capsys.readouterr().out
    last = json.loads(out.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 1
    assert last["device"]["platform"] == "cpu"
    assert all(m["value"] is None for m in last["metrics"].values())
    quality = [json.loads(line[len("[bench] "):])
               for line in out.splitlines()
               if line.startswith('[bench] {"what": "quality"')]
    assert quality[0]["path"] == {"engine": "partition", "quantized": True,
                                  "spine": "fused", "carried": True}


# ---- the hessian floor's control, at a size a test run can hold (PR 38) ----
def _check_lines(capsys):
    lines = capsys.readouterr().out.strip().splitlines()
    said = [json.loads(line[len("[bench] "):]) for line in lines
            if line.startswith("[bench] ")]
    checks_ = [json.loads(line[len("[check] "):]) for line in lines
               if line.startswith("[check] ")]
    return json.loads(lines[-1]), checks_, said


def test_a_system_at_half_the_hessian_floor_is_not_correct(tmp_path, capsys):
    """The control of `bound_rtol` (on the chip at the cell's own size:
    PERF.md section 6, PR 38): the system's boosters trained at half the
    floor the reference holds them to.  The tiny preset with a floor that
    binds: 2 048 rows of hessian 0.25 against a floor of 100 allow five
    leaves of 400 rows; at 50 the system grows leaves of 200."""
    from benchmarks import check_seeds
    root = copy_of_the_benchmark(tmp_path)
    path = os.path.join(root, "benchmarks", "configs",
                        "epsilon-dense-int8.json")
    with open(path) as f:
        config = json.load(f)
    assert config["correct"]["bound_rtol"] == 1e-5
    assert config["correct"]["bound_rtol_why"]
    config["rehearse"]["params"]["min_sum_hessian_in_leaf"] = 100
    with open(path, "w") as f:
        json.dump(config, f)
    argv = ["--workload", "epsilon-int8.train", "--seeds", "2147483693",
            "--rehearse"]
    assert check_seeds.main(argv, root=root) == 0
    last, (sound,), said = _check_lines(capsys)
    assert sound["correct"] is True and sound["problems"] == []
    assert last == {"cell": "epsilon-int8.train", "platform": "cpu",
                    "seeds": 1, "correct": 1, "system_params": {}}
    assert check_seeds.main(
        argv + ["--system-params", '{"min_sum_hessian_in_leaf": 50}'],
        root=root) == 0
    last, (control,), said = _check_lines(capsys)
    assert last["correct"] == 0 and control["correct"] is False
    assert "the reference does not accept" in control["problems"][0]
    # the line names the miss: a child between the two floors
    (line,) = [s for s in said if s["what"] == "reference-check"]
    chosen = line["miss"]["chosen"]
    assert chosen["allowed_gain"] is None or chosen["allowed_gain"] < 0
    assert -0.5 <= min(chosen["hessian_over_floor_rel"]) < -1e-3
    assert min(chosen["rows"]) >= 200
