"""The names the program puts into a profiler trace, as the benchmark reads
them: each device operation's `tf_op` path straight off the .xplane.pb
(harness/xplane_names.py), the scope sums of readers/trace_scope.py and
the host-span times of readers/program_span.py — on two traces recorded
on the chip, on hand-made files and on hand-made intervals."""
import gzip
import os
import shutil
import types

import pytest

from benchmarks.harness import manifest
from benchmarks.harness import trace_reduce as tr
from benchmarks.harness import xplane_names as xn
from bench_overlay import REPO

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SIX = ["xla.gradient.ms_per_iter", "xla.quantize.ms_per_iter",
       "xla.hist_cache.ms_per_iter", "xla.grow_glue.ms_per_iter",
       "xla.score_update.ms_per_iter", "xla.unscoped.ms_per_iter"]
NEW = SIX + ["xla.gradient_pairs.ms_per_iter", "entry.host_self_ms_per_iter",
             "spine.dispatch_ms_per_iter"]


def _unpacked(tmp_path_factory, name):
    path = str(tmp_path_factory.mktemp("trace") / (name + ".xplane.pb"))
    with gzip.open(os.path.join(DATA, name + ".xplane.pb.gz"), "rb") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return path


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """PR 22's trace: a program from before the names existed."""
    return _unpacked(tmp_path_factory, "tiny_v5e")


@pytest.fixture(scope="module")
def scoped(tmp_path_factory):
    """The traced slice of `mslr-rank.train --rehearse --trace 1 --seed 7`
    on a TPU v5e, from PR 24's tree: two iterations of a 7-leaf lambdarank
    tree over 3840 rows, with the program's scopes and spans in it."""
    return _unpacked(tmp_path_factory, "tiny_v5e_scoped")


def _run(path, traced_units=2):
    return types.SimpleNamespace(
        trace=tr.reduce(path), xplane=path, spans=[], phases={},
        device_kind="TPU v5 lite",
        shape={"units": traced_units, "traced_units": traced_units})


def _read(metric, run):
    spec = manifest.load_json(REPO, "benchmarks", "layer_metrics",
                              metric + ".json")
    reader = manifest.load_module(REPO, "readers", spec["reader"])
    return reader.read(run, spec.get("args", {}))


def _scope_ms(run, **args):
    return manifest.load_module(REPO, "readers", "trace_scope").read(run, args)


# -- a hand-made .xplane.pb: the wire format, field by field ------------- #
def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n >> 7 else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _entry(key, message):
    return _field(1, key) + _field(2, message)


def _space(plane_name, stat_names, events):
    """XSpace of one plane.  events: [(name, [XStat bytes])]."""
    plane = _field(2, plane_name.encode())
    for ident, (name, stats) in enumerate(events, 1):
        meta = _field(1, ident) + _field(2, name.encode())
        plane += _field(4, _entry(ident, meta + b"".join(
            _field(5, stat) for stat in stats)))
    for ident, text in stat_names.items():
        plane += _field(5, _entry(
            ident, _field(1, ident) + _field(2, text.encode())))
    return _field(1, plane)


def _written(tmp_path, data):
    path = str(tmp_path / "made.xplane.pb")
    with open(path, "wb") as f:
        f.write(data)
    return path


# -- the wire reader ----------------------------------------------------- #
def test_wire_reader_counts_the_names_that_have_a_path(recorded):
    paths = xn.device_paths(recorded)
    assert len(paths) == 212
    assert sum(1 for p in paths.values() if p) == 140
    # keyed like TraceSummary.ops: every reduced operation is found
    labels = xn.label_paths(recorded)
    assert set(tr.reduce(recorded).ops) <= set(labels)
    assert len(labels) == 211 and sum(1 for p in labels.values() if p) == 139


@pytest.mark.parametrize("label,path", [
    ("segment_histogram.13 f32[768,128] mosaic",
     "jit(fused)/while/body/jit(segment_histogram)/pallas_call:"),
    ("reshape.959 f32[8,4,3,8,4,32]",
     "jit(fused)/while/body/jit(segment_histogram)/reshape:"),
    ("while.33 f32[6,16]", ""),            # the loop itself has no op_name
])
def test_wire_reader_gives_the_known_paths(recorded, label, path):
    assert xn.label_paths(recorded)[label] == path


def test_wire_reader_resolves_a_reference_stat(tmp_path):
    """A stat may hold its text (str_value) or point at a stat_metadata
    entry whose name is the text (ref_value); a shared label keeps the
    path that is not empty."""
    stat_names = {1: "tf_op", 2: "hlo_category",
                  3: "jit(f)/lgbm.score/add:"}
    data = _space("/device:TPU:0", stat_names, [
        ("%add.1 = f32[8]{0} add(...)", [_field(1, 2) + _field(5, b"x"),
                                         _field(1, 1) + _field(7, 3)]),
        ("%mul.2 = f32[8]{0} multiply(...)",
         [_field(1, 1) + _field(5, b"jit(f)/lgbm.gradient/mul:")]),
        ("%copy.3 = f32[8]{0} copy(...)", []),
    ]) + _space("/host:CPU", {1: "tf_op"}, [
        ("%add.1 = f32[8]{0} add(...)",
         [_field(1, 1) + _field(5, b"not-a-device-plane")])])
    assert xn.label_paths(_written(tmp_path, data)) == {
        "add.1 f32[8]": "jit(f)/lgbm.score/add:",
        "mul.2 f32[8]": "jit(f)/lgbm.gradient/mul:",
        "copy.3 f32[8]": ""}


def test_a_truncated_file_raises_a_clear_error(recorded, tmp_path):
    with open(recorded, "rb") as f:
        data = f.read()
    with pytest.raises(ValueError, match="cut short"):
        xn.device_paths(_written(tmp_path, data[:len(data) // 2]))


@pytest.mark.parametrize("path,scope", [
    ("jit(fused)/lgbm.gradient/jit(_lambda_bucket)/lgbm.gradient.pairs/"
     "while/body/mul:", "lgbm.gradient.pairs"),       # the last one wins
    ("jit(fused)/while/body/lgbm.grow.cache/select_n:", "lgbm.grow.cache"),
    ("jit(fused)/lgbm.score", "lgbm.score"),
    ("jit(fused)/while/body/jit(segment_histogram)/reshape:", None),
    ("jit(lgbm_like)/add:", None),          # a component starts with it
    ("", None),
])
def test_an_operations_scope_is_its_last_lgbm_component(path, scope):
    assert xn.scope_of(path) == scope


def test_newest_xplane_is_the_last_one_written(tmp_path):
    assert xn.newest_xplane(str(tmp_path)) is None
    for age, cell in ((50, "a.train"), (10, "b.train"), (30, "c.train")):
        folder = tmp_path / cell / "plugins" / "profile" / "2026_01_01"
        folder.mkdir(parents=True)
        path = folder / "vm.xplane.pb"
        path.write_bytes(b"")
        os.utime(path, (1e9 - age, 1e9 - age))
    assert xn.newest_xplane(str(tmp_path)).split(os.sep)[-5] == "b.train"


# -- host spans ---------------------------------------------------------- #
MS = 1_000_000          # the profiler's clock counts nanoseconds


def _spans_run(monkeypatch, threads):
    """A run whose trace file holds these `lgbm:` intervals."""
    monkeypatch.setattr(xn, "program_spans", lambda path: threads)
    return types.SimpleNamespace(trace=object(), xplane="made",
                                 shape={"traced_units": 2})


@pytest.mark.parametrize("args,ms", [
    # two iterations of 10 ms and 12 ms
    ({"span": "train/iteration"}, 11.0),
    # less fused_iter (6 + 7) and the one drain (2); feature_sample lies
    # inside fused_iter and is taken from that, not from the iteration
    ({"span": "train/iteration", "self": True}, 3.5),
    ({"span": "fused_iter"}, 6.5),
    ({"span": "fused_iter", "self": True}, 5.5),
    # a span another thread opened counts under its own name
    ({"span": "drain_inflight"}, 2.5),
    ({"span": "oob_walk"}, 0.0),           # spans were read, none of these
])
def test_program_span_total_and_self_time(monkeypatch, args, ms):
    main = [(0, 10 * MS, "train/iteration"),
            (1 * MS, 7 * MS, "fused_iter"),
            (2 * MS, 3 * MS, "feature_sample"),
            (20 * MS, 32 * MS, "train/iteration"),
            (20 * MS, 22 * MS, "drain_inflight"),
            (23 * MS, 30 * MS, "fused_iter"),
            (24 * MS, 25 * MS, "feature_sample")]
    other = [(5 * MS, 8 * MS, "drain_inflight")]
    reader = manifest.load_module(REPO, "readers", "program_span")
    run = _spans_run(monkeypatch, [main, other])
    assert reader.read(run, args) == pytest.approx(ms, rel=1e-12)


def test_program_span_reads_nothing_from_a_program_without_spans(
        monkeypatch, recorded):
    reader = manifest.load_module(REPO, "readers", "program_span")
    assert reader.read(_spans_run(monkeypatch, []),
                       {"span": "fused_iter"}) is None
    # PR 22's trace: bench: spans only
    assert xn.program_spans(recorded) == []
    assert _read("spine.dispatch_ms_per_iter", _run(recorded)) is None


# -- scope sums ---------------------------------------------------------- #
def test_a_program_without_scopes_reads_all_of_it_as_unscoped(recorded):
    """What the driver's parent-side run of PR 24 sees: paths but no
    `lgbm.` component, so every scope reads 0.0 (not None: the cell is not
    refused) and `unscoped` is the whole of `xla.other`."""
    run = _run(recorded)
    values = {m: _read(m, run) for m in SIX}
    other = _read("xla.other_ms_per_iter", run)
    assert other == pytest.approx(0.201145, rel=1e-5)
    assert values.pop("xla.unscoped.ms_per_iter") == pytest.approx(
        other, rel=1e-12)
    assert set(values.values()) == {0.0}


def test_every_new_reader_reads_nothing_without_a_reduced_trace(scoped):
    """Off the chip `trace_reduce` reduces nothing; the host plane of a
    CPU trace still holds the program's spans, and a rehearsal must not
    name a metric for them (test_bench_contract pins its names)."""
    run = _run(scoped)
    run.trace = None
    assert [_read(m, run) for m in NEW] == [None] * len(NEW)


def test_a_trace_that_names_no_path_reads_nothing(tmp_path):
    data = _space("/device:TPU:0", {1: "tf_op", 2: "hlo_category"}, [
        ("%fusion.1 = f32[8]{0} fusion(...)", [_field(1, 2) + _field(5, b"x")]),
        ("%while.2 = (s32[]) while(...)", [])])
    run = types.SimpleNamespace(
        xplane=_written(tmp_path, data), shape={"traced_units": 1},
        trace=tr.TraceSummary(1.0, 0.5, {"fusion.1 f32[8]": (0.5, 1)},
                              1, [], 1))
    assert _scope_ms(run, none=True) is None
    assert _scope_ms(run, scopes="^lgbm") is None


# -- on the trace recorded from PR 24's tree ----------------------------- #
def test_the_six_scope_sums_are_the_non_kernel_total(scoped):
    run = _run(scoped)
    values = {m: _read(m, run) for m in SIX}
    non_kernel, _ = run.trace.family(" mosaic$", invert=True)
    assert sum(values.values()) == pytest.approx(non_kernel / 2 * 1e3,
                                                 rel=1e-9)
    assert sum(values.values()) == pytest.approx(
        _read("xla.other_ms_per_iter", run), rel=1e-9)
    # every purpose this path runs took some time; float32 does not quantise
    assert values.pop("xla.quantize.ms_per_iter") == 0.0       # not None
    assert all(v > 0 for v in values.values()), values


def test_a_nested_scope_counts_under_its_last_component(scoped):
    """The pairwise chain is traced under lgbm.gradient/.../
    lgbm.gradient.pairs: in `xla.gradient` by prefix, under `pairs` alone
    by its own name, and never under the outer scope's exact name."""
    run = _run(scoped)
    pairs = _read("xla.gradient_pairs.ms_per_iter", run)
    whole = _read("xla.gradient.ms_per_iter", run)
    outer = _scope_ms(run, scopes=r"^lgbm\.gradient$")
    scatter = _scope_ms(run, scopes=r"^lgbm\.gradient\.scatter$")
    assert 0 < pairs < whole
    assert outer + pairs + scatter == pytest.approx(whole, rel=1e-9)
    # the loop's body sits under lgbm.grow.carry and keeps its own scopes
    nested = [p for p in xn.label_paths(scoped).values()
              if "lgbm.grow.carry/while/body/lgbm.grow.cache/" in p]
    assert nested and {xn.scope_of(p) for p in nested} == {"lgbm.grow.cache"}
    assert _scope_ms(run, scopes=r"^lgbm\.nothing$") == 0.0


def test_the_recorded_host_plane_holds_the_programs_spans(scoped):
    (thread,) = xn.program_spans(scoped)
    iterations = [s for s in thread if s[2] == "train/iteration"]
    dispatches = [s for s in thread if s[2] == "fused_iter"]
    assert len(iterations) == len(dispatches) == 2
    for (i0, i1, _), (d0, d1, _) in zip(iterations, dispatches):
        assert i0 <= d0 and d1 <= i1
    run = _run(scoped)
    whole = manifest.load_module(REPO, "readers", "program_span").read(
        run, {"span": "train/iteration"})
    self_ms = _read("entry.host_self_ms_per_iter", run)
    dispatch = _read("spine.dispatch_ms_per_iter", run)
    assert whole == pytest.approx(
        sum(e - s for s, e, _ in iterations) / 2 / 1e6, rel=1e-12)
    assert 0 < self_ms < whole and 0 < dispatch < whole
    # nothing but the dispatch is nested in these iterations
    assert self_ms + dispatch == pytest.approx(whole, rel=1e-9)


@pytest.mark.parametrize("metric,ms", [
    ("xla.gradient.ms_per_iter", 0.3194855),
    ("xla.gradient_pairs.ms_per_iter", 0.2179605),
    ("xla.hist_cache.ms_per_iter", 0.0500265),
    ("xla.grow_glue.ms_per_iter", 0.342879),
    ("xla.score_update.ms_per_iter", 3.7e-05),
    ("xla.unscoped.ms_per_iter", 0.0782215),
    ("entry.host_self_ms_per_iter", 0.0969495),
    ("spine.dispatch_ms_per_iter", 4.69588),
])
def test_the_recorded_trace_reads_these_numbers(scoped, metric, ms):
    """Pinned, so that a change to a reader or to the join shows as a
    changed number on a file that does not change."""
    assert _read(metric, _run(scoped)) == pytest.approx(ms, rel=1e-6)
    paths = xn.device_paths(scoped)
    assert (len(paths), sum(1 for p in paths.values() if p)) == (248, 168)
