"""The reduction from a profiler trace to busy time, kernel-family times
and named gaps: on a trace recorded on the chip, and piece by piece."""
import gzip
import os
import shutil

import pytest

from benchmarks.harness import trace_reduce as tr

RECORDED = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "tiny_v5e.xplane.pb.gz")
MOSAIC = r"\b.* mosaic$"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The traced slice of `higgs-int8.train --rehearse --trace 1` on a TPU
    v5e (PR 22's first traced chip run): two iterations of a 7-leaf tree
    over 4096 rows, benchmark spans around update() and the sync."""
    path = str(tmp_path_factory.mktemp("trace") / "tiny.xplane.pb")
    with gzip.open(RECORDED, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return tr.reduce(path)


def test_recorded_trace_busy_share_and_programs(recorded):
    assert recorded.chips == 1
    assert recorded.window_s == pytest.approx(0.023933227, rel=1e-9)
    assert recorded.busy_s == pytest.approx(0.000629053, rel=1e-9)
    assert recorded.programs == 20
    # everything is either inside an operation or inside a gap
    assert sum(s for _, s in recorded.gaps) == pytest.approx(
        recorded.window_s - recorded.busy_s, rel=1e-9)
    in_ops = sum(s for s, _ in recorded.ops.values())
    assert in_ops == pytest.approx(recorded.busy_s, rel=1e-6)


@pytest.mark.parametrize("family,seconds,calls", [
    (r"^partition_segment" + MOSAIC, 9.4215e-05, 12),    # 2 trees x 6 splits
    (r"^segment_histogram" + MOSAIC, 4.4111e-05, 12),
    (r"^_run_scan" + MOSAIC, 5.304e-05, 14),
    (r"^fused_refresh_histogram" + MOSAIC, 1.6694e-05, 2),   # once a tree
    (r"^compact_(carry|segments)" + MOSAIC, 1.8703e-05, 2),
    (r"^while\b", 1.23114e-04, 2),       # self time: its body is not in it
])
def test_recorded_trace_kernel_family_times(recorded, family, seconds, calls):
    got_seconds, got_calls = recorded.family(family)
    assert got_calls == calls
    assert got_seconds == pytest.approx(seconds, rel=1e-6)


def test_recorded_trace_other_is_the_rest(recorded):
    kernels, _ = recorded.family(" mosaic$")
    other, _ = recorded.family(" mosaic$", invert=True)
    assert other == pytest.approx(0.00040229, rel=1e-6)
    assert kernels + other == pytest.approx(recorded.busy_s, rel=1e-6)


def test_recorded_trace_gaps_are_named_by_the_hosts_span(recorded):
    # at this size the chip is done long before update() returns
    assert recorded.gaps[0] == ("update", pytest.approx(0.012306984))
    assert {name for name, _ in recorded.gaps} == {"update"}
    b = recorded.breakdown()
    assert len(b["device_ops"]) == 10 and len(b["idle_gaps"]) == 5
    assert b["device_ops"][1] == [
        "partition_segment.13 bf16[48,57344] mosaic",
        pytest.approx(9.4215e-05)]
    seconds = [s for _, s in b["device_ops"]]
    assert seconds == sorted(seconds, reverse=True)


def test_a_trace_without_a_chip_reduces_to_nothing(tmp_path):
    """A CPU trace has host threads and no /device:TPU plane."""
    import jax
    import jax.numpy as jnp
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench:update"):
        jnp.ones(8).sum().block_until_ready()
    jax.profiler.stop_trace()
    path = tr.find_xplane(str(tmp_path))
    assert path and path.endswith(".xplane.pb")
    assert tr.reduce(path) is None
    assert tr.find_xplane(str(tmp_path / "nothing-here")) is None


def test_op_label_keeps_instruction_result_and_mosaic():
    text = ('%partition_segment.13 = (bf16[48,63033344]{1,0:T(8,128)(2,1)}, '
            's32[2]{0:T(128)S(1)}) custom-call(s32[7]{0} %c), '
            'custom_call_target="tpu_custom_call", operand_layout={}')
    assert tr.op_label(text) == "partition_segment.13 bf16[48,63033344] mosaic"
    assert tr.op_label("%fusion.6 = u8[10500000]{0:T(1024)} fusion(u8[1] %x)"
                       ) == "fusion.6 u8[10500000]"
    assert tr.op_label("%while.3 = (f32[]{:T(128)}, s32[]) while(%t)") \
        == "while.3 f32[]"
    assert tr.op_label("not an instruction") == "not an instruction"


def test_self_time_is_length_less_what_is_enclosed():
    events = [(0, 100, "while"), (10, 30, "a"), (30, 50, "b"),
              (35, 45, "inner"), (200, 210, "after")]
    got = {name: self_ns for _, _, name, self_ns in tr._self_times(events)}
    assert got == {"while": 60, "a": 20, "b": 10, "inner": 10, "after": 10}


def test_union_merges_and_clips():
    events = [(0, 10, "x"), (5, 20, "y"), (30, 40, "z"), (90, 120, "w")]
    assert tr._union(events, 2, 100) == [[2, 20], [30, 40], [90, 100]]
    assert tr._union(events, 41, 89) == []


def test_a_gap_takes_the_innermost_span_or_between_calls():
    spans = [(0, 100, "update"), (20, 40, "bagging"), (110, 150, "sync")]
    assert tr._span_at(spans, 10) == "update"
    assert tr._span_at(spans, 25) == "bagging"
    assert tr._span_at(spans, 105) == tr.BETWEEN == "between-calls"
    assert tr._span_at(spans, 149) == "sync"
