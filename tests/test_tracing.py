"""Distributed span tracing (obs/tracing.py + tools/trace_merge.py +
tools/trace_check.py): file format, zero-cost-when-disabled, bitwise
model identity with tracing on/off, cross-rank collective correlation
over the real SocketComm transport, the trace tools against committed
fixtures, and the observability satellites (compile-listener
idempotency, recorder durability, TraceSession double-start guard)."""
import json
import multiprocessing as mp
import os
import socket
import subprocess
import sys
import threading

import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.config import Config
from lightgbm_tpu.obs import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXDIR = os.path.join(REPO, "tests", "fixtures")


def _import_tool(name):
    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    sys.path.insert(0, tools)
    try:
        return __import__(name)
    finally:
        sys.path.remove(tools)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _train_data(n=300, nf=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, nf)
    y = 2.0 * X[:, 0] - X[:, 1] + 0.05 * rng.randn(n)
    return X, y


@pytest.fixture(autouse=True)
def _reset_tracer():
    """The tracer is process-wide; disarm it between tests so one test's
    trace path cannot leak spans into another's."""
    yield
    tr = tracing.get_tracer()
    tr.enabled = False
    tr.path = None
    with tr._lock:
        tr._metadata = {}
        tr._events = []


# ------------------------------------------------------------ span recorder

def test_span_nesting_and_file_format(tmp_path):
    path = str(tmp_path / "t.trace")
    tr = tracing.get_tracer().configure(path, rank=0, world=1)
    with tracing.span("outer", "train", iter=3):
        with tracing.span("inner", "phase"):
            pass
        tracing.instant("marker", "train", note="hi")
    tracing.complete("late", 0.005, cat="xla", event="test")
    assert tr.close() == path

    data = json.load(open(path))
    assert set(data) == {"traceEvents", "displayTimeUnit", "metadata"}
    events = data["traceEvents"]
    by_name = {e["name"]: e for e in events}
    outer, inner = by_name["outer"], by_name["inner"]
    assert outer["ph"] == inner["ph"] == "X"
    # nesting: inner's parent is outer, and inner lies inside outer
    assert inner["args"]["parent_id"] == outer["args"]["span_id"]
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1
    assert by_name["marker"]["ph"] == "i"
    assert by_name["late"]["dur"] == 5000       # 5 ms in us
    # metadata carries everything trace_merge needs
    meta = data["metadata"]
    for key in ("schema", "trace_id", "rank", "world", "wall_epoch_us",
                "clock_offset_us", "dropped_events"):
        assert key in meta, key
    # M-events name the process and thread lanes
    m_names = {e["name"] for e in events if e["ph"] == "M"}
    assert {"process_name", "thread_name", "process_sort_index"} <= m_names


def test_zero_cost_when_disabled(monkeypatch):
    """Nothing of the tracer when it is off: no span object, no event, no
    lock, no clock read.  What a disabled span leaves is the one
    jax.profiler.TraceAnnotation every span enters (``lgbm:<name>`` in a
    profiler trace; a no-op while no profiler session runs)."""
    from jax.profiler import TraceAnnotation
    tr = tracing.get_tracer()
    assert not tr.enabled

    def forbidden(*args, **kwargs):
        raise AssertionError("a disabled span reached into the tracer")

    monkeypatch.setattr(tracing, "_Span", forbidden)        # no allocation
    monkeypatch.setattr(tracing, "time", None)              # no clock read
    monkeypatch.setattr(tr, "_lock", None)                  # no lock
    events = len(tr._events)
    cm = tracing.span("x", "y", iter=3)
    assert type(cm) is TraceAnnotation
    with cm:
        with tracing.span("z"):
            pass
    tracing.instant("nope")
    tracing.complete("nope", 0.1)
    assert len(tr._events) == events
    assert tracing.current_context() == ("", 0)
    assert tracing.flush() is None


def test_span_error_flag_and_buffer_cap(tmp_path):
    path = str(tmp_path / "t.trace")
    tr = tracing.get_tracer().configure(path, max_events=1024)
    with pytest.raises(ValueError):
        with tracing.span("fails", "train"):
            raise ValueError("boom")
    for i in range(1100):               # overflow the (clamped) 1024 cap
        tracing.instant("spam", "test", i=i)
    tr.close()
    data = json.load(open(path))
    failed = next(e for e in data["traceEvents"] if e["name"] == "fails")
    assert failed["args"]["error"] == "ValueError"
    assert data["metadata"]["dropped_events"] > 0
    assert len([e for e in data["traceEvents"] if e["ph"] != "M"]) <= 1024


def test_span_threads_get_distinct_lanes(tmp_path):
    path = str(tmp_path / "t.trace")
    tr = tracing.get_tracer().configure(path)

    def work():
        with tracing.span("threaded", "test"):
            pass

    t = threading.Thread(target=work, name="worker-9")
    with tracing.span("main-side", "test"):
        t.start()
        t.join()
    tr.close()
    data = json.load(open(path))
    spans = {e["name"]: e for e in data["traceEvents"] if e["ph"] == "X"}
    assert spans["threaded"]["tid"] != spans["main-side"]["tid"]
    # thread stacks are per-thread: no cross-thread parent linkage
    assert "parent_id" not in spans["threaded"]["args"]
    names = {e["args"]["name"] for e in data["traceEvents"]
             if e["name"] == "thread_name"}
    assert "worker-9" in names


def test_kind_histograms_reach_registry(tmp_path):
    from lightgbm_tpu.obs import default_registry
    tr = tracing.get_tracer().configure(str(tmp_path / "t.trace"))
    with tracing.span("anything", "testkind"):
        pass
    tr.close()
    text = default_registry().render_prometheus()
    assert 'lgbm_trace_span_ms_bucket{kind="testkind"' in text


# ------------------------------------------------- bitwise model identity

def test_trace_bitwise_identical_gbdt(tmp_path):
    X, y = _train_data(seed=3)
    params = {"objective": "regression", "num_leaves": 15, "verbose": -1,
              "min_data_in_leaf": 5, "bagging_freq": 2,
              "bagging_fraction": 0.7, "bagging_seed": 9}
    b_off = lgb.train(dict(params), lgb.Dataset(X, label=y),
                      num_boost_round=6)
    path = str(tmp_path / "run.trace")
    b_on = lgb.train(dict(params, tpu_trace_path=path),
                     lgb.Dataset(X, label=y), num_boost_round=6)
    assert b_on.model_to_string() == b_off.model_to_string()
    # and the trace itself is a real timeline: data + train + phase spans
    data = json.load(open(path))
    names = {e["name"] for e in data["traceEvents"]}
    assert "data/construct" in names
    assert "data/bin" in names
    assert "train/iteration" in names
    iters = [e for e in data["traceEvents"]
             if e["name"] == "train/iteration"]
    assert sorted(e["args"]["iter"] for e in iters) == list(range(6))
    assert "compile_counts" in data["metadata"]


def test_trace_bitwise_identical_data_parallel(tmp_path):
    # one distributed mode: the data-parallel learner on the 8-device mesh
    X, y = _train_data(n=400, nf=8, seed=5)
    y = (y > np.median(y)).astype(float)
    params = {"objective": "binary", "num_leaves": 7, "verbose": -1,
              "min_data_in_leaf": 5, "tree_learner": "data",
              "num_machines": 8}
    b_off = lgb.train(dict(params), lgb.Dataset(X, label=y),
                      num_boost_round=3)
    path = str(tmp_path / "dp.trace")
    b_on = lgb.train(dict(params, tpu_trace_path=path),
                     lgb.Dataset(X, label=y), num_boost_round=3)
    assert b_on.model_to_string() == b_off.model_to_string()
    # world > 1 resolves to a per-rank file
    assert os.path.exists(path + ".rank0")


# ------------------------------------------- cross-rank correlation (real TCP)

def _traced_rank(rank, machines, base_path, q):
    from lightgbm_tpu.obs import tracing as tr_mod
    from lightgbm_tpu.parallel import distributed as dist
    tr = tr_mod.get_tracer().configure(base_path, rank=rank, world=2)
    comm = dist.SocketComm(rank, 2, machines, timeout_s=60, port_offset=0)
    try:
        for rnd in range(3):
            with tr.span("train/iteration", "train", {"iter": rnd}):
                comm.allgather({"rank": rank, "round": rnd})
    finally:
        comm.close()
        tr.close()
    q.put(rank)


class TestCrossRank:
    def test_two_rank_traces_fuse_into_one_timeline(self, tmp_path):
        """The acceptance path: a 2-rank SocketComm run writes per-rank
        traces whose matching allgather spans share a collective
        trace-id, and trace_merge fuses them into one valid Chrome
        trace."""
        port = _free_port()
        machines = ["127.0.0.1:%d" % port, "127.0.0.1:%d" % port]
        base = str(tmp_path / "dist.trace")
        ctx = mp.get_context("spawn")
        q = ctx.Queue()
        child = ctx.Process(target=_traced_rank,
                            args=(1, machines, base, q))
        child.start()
        try:
            _traced_rank(0, machines, base, q)
            child.join(timeout=60)
            assert child.exitcode == 0
        finally:
            if child.is_alive():
                child.terminate()

        r0, r1 = base + ".rank0", base + ".rank1"
        t0, t1 = json.load(open(r0)), json.load(open(r1))

        def collective_ids(t):
            return sorted(e["args"]["trace_id"] for e in t["traceEvents"]
                          if e.get("name") == "comm/allgather"
                          and e.get("ph") == "X")

        ids0, ids1 = collective_ids(t0), collective_ids(t1)
        assert len(ids0) == 3
        assert ids0 == ids1             # SAME trace-id per collective
        # comm identity propagated into both files' metadata
        assert (t0["metadata"]["comm_session"]
                == t1["metadata"]["comm_session"])
        # the spoke estimated a clock offset against the hub
        assert "clock_offset_us" in t1["metadata"]
        # the receiving side recorded the sender's span via the frame
        # header: a comm/recv instant carrying a peer span id
        recv = [e for e in t0["traceEvents"] + t1["traceEvents"]
                if e.get("name") == "comm/recv"]
        assert recv and all(e["args"]["peer_span"] > 0 for e in recv)

        trace_merge = _import_tool("trace_merge")
        merged_path = str(tmp_path / "merged.json")
        rc = trace_merge.main([r0, r1, "-o", merged_path, "--strict"])
        assert rc == 0
        merged = json.load(open(merged_path))
        assert merged["metadata"]["collectives_total"] == 3
        assert merged["metadata"]["collectives_matched_all_ranks"] == 3
        assert {e["pid"] for e in merged["traceEvents"]} == {0, 1}
        # timestamps monotone after the clock-offset rebase
        ts = [e["ts"] for e in merged["traceEvents"] if e["ph"] != "M"]
        assert ts == sorted(ts) and ts[0] >= 0


# --------------------------------------------- tools against committed fixtures

class TestTraceTools:
    def test_merge_fixture_produces_valid_chrome_trace(self, tmp_path):
        trace_merge = _import_tool("trace_merge")
        out = str(tmp_path / "merged.json")
        rc = trace_merge.main([
            os.path.join(FIXDIR, "trace", "rank0.trace.json"),
            os.path.join(FIXDIR, "trace", "rank1.trace.json"),
            "-o", out, "--strict"])
        assert rc == 0
        data = json.load(open(out))
        # Perfetto-schema assertions: object form, complete events carry
        # numeric ts/dur, instants carry scope, metadata events pass
        # through, pid == source rank
        assert isinstance(data["traceEvents"], list)
        assert data["displayTimeUnit"] == "ms"
        for e in data["traceEvents"]:
            assert {"name", "ph", "pid"} <= set(e)
            if e["ph"] == "X":
                assert isinstance(e["ts"], (int, float))
                assert isinstance(e["dur"], (int, float)) and e["dur"] >= 0
            elif e["ph"] == "i":
                assert e["s"] in ("t", "p", "g")
        assert {e["pid"] for e in data["traceEvents"]} == {0, 1}
        m = data["metadata"]
        assert m["collectives_total"] == 2
        assert m["collectives_matched_all_ranks"] == 2
        # rank1's -4800us offset moved its epoch to hub time
        assert m["clock_offsets_us"]["1"] == -4800.0

    def test_merge_strict_flags_unmatched_collectives(self, tmp_path):
        trace_merge = _import_tool("trace_merge")
        r1 = json.load(open(os.path.join(FIXDIR, "trace",
                                         "rank1.trace.json")))
        r1["traceEvents"] = [e for e in r1["traceEvents"]
                             if (e.get("args") or {}).get("seq") != 2]
        broken = str(tmp_path / "rank1.json")
        json.dump(r1, open(broken, "w"))
        rc = trace_merge.main([
            os.path.join(FIXDIR, "trace", "rank0.trace.json"), broken,
            "-o", str(tmp_path / "m.json"), "--strict"])
        assert rc == 1

    def test_merge_rejects_non_trace_files(self, tmp_path):
        trace_merge = _import_tool("trace_merge")
        bad = str(tmp_path / "bad.json")
        json.dump({"hello": 1}, open(bad, "w"))
        rc = trace_merge.main([bad, "-o", str(tmp_path / "m.json")])
        assert rc == 2

    def test_trace_check_passes_committed_baseline(self, capsys):
        trace_check = _import_tool("trace_check")
        rc = trace_check.main([
            os.path.join(FIXDIR, "trace", "rank0.trace.json"),
            "--baseline", os.path.join(FIXDIR, "trace", "baseline.json")])
        assert rc == 0
        assert "OK" in capsys.readouterr().out

    def test_trace_check_fails_on_breach(self, capsys):
        trace_check = _import_tool("trace_check")
        rc = trace_check.main([
            os.path.join(FIXDIR, "trace", "rank0.trace.json"),
            "--baseline",
            os.path.join(FIXDIR, "trace", "baseline_breach.json")])
        assert rc == 1
        err = capsys.readouterr().err
        # every enforced dimension breaches: phases, compiles, comm share
        assert "p95" in err and "backend_compiles" in err
        assert "comm_wait_share" in err

    def test_trace_check_summary_and_write_baseline(self, tmp_path):
        trace_check = _import_tool("trace_check")
        fixture = os.path.join(FIXDIR, "trace", "rank0.trace.json")
        summary = trace_check.summarize(json.load(open(fixture)))
        assert summary["backend_compiles"] == 2     # from metadata
        assert summary["retraces"] == 3
        assert summary["phases"]["train/iteration"]["count"] == 2
        assert 0.0 < summary["comm_wait_share"] < 1.0
        # a derived baseline must accept the trace it came from
        out = str(tmp_path / "b.json")
        assert trace_check.main([fixture, "--write-baseline", out]) == 0
        assert trace_check.main([fixture, "--baseline", out]) == 0

    def test_trace_check_bad_input_exit_2(self, tmp_path):
        trace_check = _import_tool("trace_check")
        bad = str(tmp_path / "bad.json")
        open(bad, "w").write("not json")
        assert trace_check.main([bad]) == 2

    # the same three verdicts through the CLI's real exit codes
    @pytest.mark.parametrize("trace,baseline,rc,stream,word", [
        ("rank0.trace.json", "baseline.json", 0, "stdout", "OK"),
        ("rank0.trace.json", "baseline_breach.json", 1, "stderr", "BREACH"),
        ("bad.json", None, 2, "stderr", ""),
    ], ids=["passes_committed_baseline", "breach", "unreadable"])
    def test_trace_check_subprocess(self, tmp_path, trace, baseline, rc,
                                    stream, word):
        fix = os.path.join(FIXDIR, "trace")
        (tmp_path / "bad.json").write_text("nope")
        argv = [os.path.join(fix, trace) if baseline
                else str(tmp_path / trace)]
        if baseline:
            argv += ["--baseline", os.path.join(fix, baseline)]
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "trace_check.py")]
            + argv, capture_output=True, text=True, cwd=REPO, timeout=300)
        assert proc.returncode == rc, proc.stderr
        assert word in getattr(proc, stream)

    def test_telemetry_report_fixture(self):
        telemetry_report = _import_tool("telemetry_report")
        events = telemetry_report.load_events(
            os.path.join(FIXDIR, "telemetry", "train.telemetry.jsonl"))
        text = telemetry_report.render(events, show_iterations=True)
        assert "boosting=gbdt objective=binary" in text
        assert "iterations: 2" in text
        assert "tree_grow" in text
        assert "xla: 2 backend compiles, 3 traces" in text
        assert "comm: 2 allgathers" in text
        # deferred round 1's tree shape was backfilled from tree_stats
        assert "leaves avg 6.5" in text


# ------------------------------------------------------ observability satellites

def test_install_compile_listeners_idempotent(monkeypatch):
    """Repeat calls must NOT register more jax.monitoring listeners —
    counters would double-count every compile."""
    import jax
    from lightgbm_tpu.obs import device
    assert device.install_compile_listeners() is True   # hooks live

    def boom(*_a, **_k):
        raise AssertionError("listeners registered twice")

    monkeypatch.setattr(jax.monitoring, "register_event_listener", boom)
    monkeypatch.setattr(jax.monitoring,
                        "register_event_duration_secs_listener", boom)
    before = device.install_count()
    assert device.install_compile_listeners() is True
    assert device.install_compile_listeners() is True
    assert device.install_count() == before + 2


def test_compile_counts_published_as_metrics():
    from lightgbm_tpu.obs import adapters, default_registry, device
    device.install_compile_listeners()
    reg = default_registry()
    adapters.ensure_device_metrics(reg)
    text = reg.render_prometheus()
    for fam in ("lgbm_xla_backend_compiles_total", "lgbm_xla_traces_total",
                "lgbm_xla_cache_hits_total"):
        assert fam in text, fam


def test_trace_session_double_start_and_finally_stop(monkeypatch, tmp_path):
    import jax
    from lightgbm_tpu.utils.profiling import TraceSession
    calls = {"start": 0, "stop": 0}

    def fake_start(_d):
        calls["start"] += 1
        if calls["start"] > 1:
            raise RuntimeError("profiler session already active")

    monkeypatch.setattr(jax.profiler, "start_trace", fake_start)
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.__setitem__("stop",
                                                  calls["stop"] + 1))
    s1 = TraceSession(str(tmp_path / "a"))
    s1.start()
    assert s1._live
    s2 = TraceSession(str(tmp_path / "b"))
    s2.start()                          # double start: warn, don't own
    assert not s2._live
    s2.stop()
    assert calls["stop"] == 0           # s2 never stops a session it
    s1.stop()                           # doesn't own
    s1.stop()                           # idempotent
    assert calls["stop"] == 1
    # a raising stop_trace is swallowed (teardown runs in finally)
    s3 = TraceSession(str(tmp_path / "c"))
    calls["start"] = 0
    s3.start()
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: (_ for _ in ()).throw(RuntimeError("x")))
    s3.stop()                           # must not raise
    assert not s3._live


class _FakeGBDT:
    num_tree_per_iteration = 1
    num_data = 10
    iter = 3
    models = [None, None, None]         # all deferred: no tree decode
    _bag_count = None

    def __init__(self):
        from lightgbm_tpu.utils.profiling import Profiler
        self.profiler = Profiler(enabled=False)


def test_recorder_midwrite_failure_degrades_to_warning(tmp_path, capsys):
    from lightgbm_tpu.obs.recorder import TrainingRecorder
    path = str(tmp_path / "t.jsonl")
    rec = TrainingRecorder(path, Config({"verbose": "0"}))  # warnings on
    g = _FakeGBDT()
    rec.on_iteration(g, 0, 0.01, False)
    rec.on_iteration(g, 1, 0.01, False)     # flushes iter 0 to disk
    rec._file.close()                       # yank the stream mid-run
    rec.on_iteration(g, 2, 0.01, False)     # flush of iter 1 fails
    assert rec._write_failed
    assert "prior events intact" in capsys.readouterr().err
    rec.finalize(g)                         # must not raise
    # prior lines still valid JSONL: header + the one flushed iteration
    lines = [json.loads(l) for l in open(path)]
    assert lines[0]["event"] == "start"
    assert [e["iter"] for e in lines if e["event"] == "iteration"] == [0]


def test_recorder_finalize_fsyncs_and_closes(tmp_path):
    from lightgbm_tpu.obs.recorder import TrainingRecorder
    path = str(tmp_path / "t.jsonl")
    rec = TrainingRecorder(path, Config({"verbose": "-1"}))
    g = _FakeGBDT()
    rec.on_iteration(g, 0, 0.01, False)
    rec.finalize(g)
    assert rec._file is None
    events = [json.loads(l) for l in open(path)]
    assert events[-1]["event"] == "summary"
    rec.finalize(g)                         # idempotent


def test_recorder_emits_per_round_span_summaries(tmp_path):
    X, y = _train_data()
    tele = str(tmp_path / "t.jsonl")
    lgb.train({"objective": "regression", "num_leaves": 7, "verbose": -1,
               "min_data_in_leaf": 5, "tpu_telemetry_path": tele,
               "tpu_trace_path": str(tmp_path / "t.trace")},
              lgb.Dataset(X, label=y), num_boost_round=3)
    iters = [json.loads(l) for l in open(tele)
             if json.loads(l).get("event") == "iteration"]
    assert len(iters) == 3
    for e in iters:
        assert "spans" in e
    # the train-iteration span kind shows up with per-round counts
    assert any("train" in e["spans"] for e in iters)


# ------------------------------------------------------------- serving spans

def test_serving_request_spans(tmp_path):
    from lightgbm_tpu.serving import Server
    X, y = _train_data()
    bst = lgb.Booster(params={"objective": "regression", "num_leaves": 7,
                              "verbose": -1, "min_data_in_leaf": 5},
                      train_set=lgb.Dataset(X, label=y))
    for _ in range(2):
        bst.update()
    path = str(tmp_path / "serve.trace")
    srv = Server(Config({"verbose": "-1", "tpu_trace_path": path}))
    srv.load_model("m1", model_str=bst.model_to_string())
    srv.predict(X[:8], model="m1")
    srv.shutdown()                          # flushes the tracer
    data = json.load(open(path))
    names = {e["name"] for e in data["traceEvents"]}
    assert {"serve/request", "serve/enqueue", "serve/micro_batch"} <= names
    # request wraps enqueue: parent chain intact across the queue handoff
    spans = {e["name"]: e for e in data["traceEvents"] if e["ph"] == "X"}
    assert (spans["serve/enqueue"]["args"]["parent_id"]
            == spans["serve/request"]["args"]["span_id"])


# ---------------------------------------------------------- checkpoint spans

def test_checkpoint_spans_in_trace(tmp_path):
    X, y = _train_data()
    root = str(tmp_path / "ckpts")
    path = str(tmp_path / "ck.trace")
    lgb.train({"objective": "regression", "num_leaves": 7, "verbose": -1,
               "min_data_in_leaf": 5, "tpu_checkpoint_path": root,
               "tpu_checkpoint_interval": 2, "tpu_trace_path": path},
              lgb.Dataset(X, label=y), num_boost_round=4)
    data = json.load(open(path))
    saves = [e for e in data["traceEvents"] if e["name"] == "ckpt/save"]
    assert len(saves) >= 2 and all(e["cat"] == "ckpt" for e in saves)
