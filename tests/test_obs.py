"""lightgbm_tpu.obs: metrics registry (thread-safety, Prometheus text
exposition), training telemetry JSONL (one event per iteration, schema,
bitwise model identity with telemetry on/off), comm/device counters,
telemetry_report tool, and the log satellites — all on the fast tier
(JAX_PLATFORMS=cpu, conftest)."""
import io
import json
import os
import re
import sys
import threading
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import (Counter, Gauge, Histogram, MetricsRegistry,
                              default_registry)
from lightgbm_tpu.utils import log


def _train_data(n=300, nf=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, nf)
    y = 2.0 * X[:, 0] - X[:, 1] + 0.05 * rng.randn(n)
    return X, y


# ---------------------------------------------------------------- registry

def test_registry_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("jobs_total", help="jobs")
    c.inc()
    c.inc(2.5)
    assert reg.counter("jobs_total").value == pytest.approx(3.5)
    g = reg.gauge("depth", help="queue depth")
    g.set(7)
    g.inc(3)
    g.dec(1)
    assert reg.gauge("depth").value == pytest.approx(9)
    # labeled children are distinct
    reg.counter("per_model", model="a").inc(1)
    reg.counter("per_model", model="b").inc(5)
    assert reg.counter("per_model", model="a").value == 1
    assert reg.family_sum("per_model") == 6


def test_registry_thread_safety():
    reg = MetricsRegistry()
    c = reg.counter("hits_total")
    h = reg.histogram("lat_ms", bounds=[1, 10, 100])
    n_threads, n_iter = 8, 500

    def work():
        for i in range(n_iter):
            c.inc()
            h.observe(i % 120)

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == n_threads * n_iter
    snap = h.snapshot()
    assert snap["count"] == n_threads * n_iter
    assert sum(h.cumulative_buckets()[-1:][0][1:]) == n_threads * n_iter


def test_histogram_percentile_edge_cases():
    # empty -> None (not 0.0, not a crash)
    h = Histogram([1, 10])
    assert h.percentile(50) is None
    assert h.snapshot()["count"] == 0
    # single observation: every percentile is clamped into [min, max]
    h.observe(4.0)
    for q in (0, 50, 99, 100):
        assert h.percentile(q) == pytest.approx(4.0)
    # estimates never escape the observed range even at bucket edges
    h2 = Histogram([1, 10, 100])
    h2.observe(2.0)
    h2.observe(3.0)
    p99 = h2.percentile(99)
    assert 2.0 <= p99 <= 3.0


def test_render_prometheus_format():
    reg = MetricsRegistry()
    reg.counter("req_total", help="requests", model="m\\1", path="a\"b").inc(2)
    reg.gauge("temp").set(1.5)
    h = reg.histogram("lat_ms", bounds=[1, 10], help="latency")
    h.observe(0.5)
    h.observe(99)
    text = reg.render_prometheus()
    lines = text.splitlines()
    # every family gets HELP+TYPE; label values are escaped
    assert "# TYPE req_total counter" in lines
    assert 'req_total{model="m\\\\1",path="a\\"b"} 2' in lines
    assert "# TYPE lat_ms histogram" in lines
    assert 'lat_ms_bucket{le="+Inf"} 2' in text
    assert "lat_ms_count 2" in text
    # cumulative buckets are monotone
    counts = [int(l.rsplit(" ", 1)[1]) for l in lines
              if l.startswith("lat_ms_bucket")]
    assert counts == sorted(counts)
    # integral values render without a decimal point
    assert "req_total" in text and "2.0" not in text.split("lat_ms_sum")[0]


def test_registry_remove_and_reset():
    reg = MetricsRegistry()
    reg.counter("x_total", model="a").inc()
    reg.counter("x_total", model="b").inc()
    assert reg.remove(model="a") == 1
    assert reg.family_sum("x_total") == 1
    reg.reset()
    assert reg.family_sum("x_total") is None


# ------------------------------------------------------- training telemetry

REQUIRED_ITER_KEYS = {"event", "iter", "wall_ms", "finished", "deferred",
                      "trees", "metrics", "phases", "sample", "compile"}


def test_training_event_log_schema(tmp_path):
    X, y = _train_data()
    path = str(tmp_path / "tele.jsonl")
    rounds = 5
    evals = {}
    lgb.train({"objective": "regression", "num_leaves": 7, "verbose": -1,
               "min_data_in_leaf": 5, "tpu_telemetry_path": path},
              lgb.Dataset(X, label=y), num_boost_round=rounds,
              valid_sets=[lgb.Dataset(X[:100], label=y[:100])],
              evals_result=evals, verbose_eval=False)
    events = [json.loads(l) for l in open(path)]
    kinds = [e["event"] for e in events]
    assert kinds[0] == "start"
    assert kinds[-1] == "summary"
    iters = [e for e in events if e["event"] == "iteration"]
    # exactly one event per boosting round, in order
    assert [e["iter"] for e in iters] == list(range(rounds))
    start = events[0]
    assert start["schema"] == 1
    assert start["num_leaves"] == 7
    for e in iters:
        assert REQUIRED_ITER_KEYS <= set(e)
        assert e["wall_ms"] >= 0
        # non-deferred rounds carry tree shape inline
        if not e["deferred"]:
            assert e["trees"] and e["trees"][0]["leaves"] >= 1
            assert e["trees"][0]["depth"] >= 0
        # the eval callback's values were merged into the same event
        assert "valid_0" in e["metrics"]
        assert set(e["phases"])  # at least one phase timed
        assert e["sample"]["rows"] == len(X)
        assert e["compile"]["traces"] >= 0
    summary = events[-1]
    assert summary["iterations"] == rounds
    assert summary["num_trees"] == rounds
    assert summary["phases"]  # full profiler snapshot
    # metric values in the log match what record_evaluation saw
    logged = [e["metrics"]["valid_0"]["l2"] for e in iters]
    assert logged == pytest.approx(evals["valid_0"]["l2"])


def test_telemetry_bitwise_identical_model(tmp_path):
    X, y = _train_data(seed=3)
    params = {"objective": "regression", "num_leaves": 15, "verbose": -1,
              "min_data_in_leaf": 5, "bagging_freq": 2,
              "bagging_fraction": 0.7, "bagging_seed": 9}
    path = str(tmp_path / "tele.jsonl")
    b_on = lgb.train(dict(params, tpu_telemetry_path=path),
                     lgb.Dataset(X, label=y), num_boost_round=6)
    b_off = lgb.train(dict(params), lgb.Dataset(X, label=y),
                      num_boost_round=6)
    assert b_on.model_to_string() == b_off.model_to_string()
    # and the log did record bagging sample sizes
    iters = [json.loads(l) for l in open(path)
             if json.loads(l).get("event") == "iteration"]
    assert any(e["sample"]["bagging_rows"] for e in iters)


def test_telemetry_report_tool(tmp_path):
    X, y = _train_data()
    path = str(tmp_path / "tele.jsonl")
    lgb.train({"objective": "regression", "num_leaves": 7, "verbose": -1,
               "min_data_in_leaf": 5, "tpu_telemetry_path": path},
              lgb.Dataset(X, label=y), num_boost_round=3)
    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    sys.path.insert(0, tools)
    try:
        import telemetry_report
        text = telemetry_report.render(telemetry_report.load_events(path),
                                       show_iterations=True)
    finally:
        sys.path.remove(tools)
    assert "iterations: 3" in text
    assert "phases:" in text
    assert "xla:" in text
    assert re.search(r"^\s*2\s", text, re.M)  # per-iteration table row


# ------------------------------------------------------ serving /metrics

def test_serving_metrics_endpoint(tmp_path):
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.serving import Server
    from lightgbm_tpu.parallel.distributed import SocketComm

    X, y = _train_data()
    bst = lgb.Booster(params={"objective": "regression", "num_leaves": 7,
                              "verbose": -1, "min_data_in_leaf": 5},
                      train_set=lgb.Dataset(X, label=y))
    for _ in range(3):
        bst.update()
    # a world=1 comm so the comm families exist on the shared registry
    SocketComm(0, 1, ["localhost:12400"]).allgather({"ping": 1})

    srv = Server(Config({"verbose": "-1"}))
    srv.load_model("m1", model_str=bst.model_to_string())
    srv.predict(X[:8], model="m1")
    httpd = srv.serve_http(port=0, block=False)
    try:
        port = httpd.server_address[1]
        resp = urllib.request.urlopen(
            "http://127.0.0.1:%d/metrics" % port, timeout=30)
        assert "version=0.0.4" in resp.headers.get("Content-Type", "")
        body = resp.read().decode()
    finally:
        httpd.shutdown()
        srv.shutdown()

    # parse: every sample line is NAME{labels} VALUE with numeric value
    families = {}
    for line in body.splitlines():
        if not line or line.startswith("#"):
            assert not line or re.match(r"# (HELP|TYPE) \S+", line)
            continue
        m = re.match(r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{.*\})? (\S+)$", line)
        assert m, "bad exposition line: %r" % line
        float(m.group(3))  # must parse as a number
        families.setdefault(m.group(1), 0)
        families[m.group(1)] += 1
    # request-path, batching, comm and device families are all present
    for fam in ("lgbm_serve_requests_total", "lgbm_serve_rows_total",
                "lgbm_serve_batches_total", "lgbm_serve_latency_ms_bucket",
                "lgbm_serve_batch_size_bucket", "lgbm_serve_wait_ms_bucket",
                "lgbm_comm_allgather_total", "lgbm_comm_bytes_sent_total",
                "lgbm_device_live_buffers", "lgbm_xla_traces_total"):
        assert fam in families, "missing family %s" % fam
    # the predict above went through the queue: requests counted
    req = [l for l in body.splitlines()
           if l.startswith("lgbm_serve_requests_total{")]
    assert any(float(l.rsplit(" ", 1)[1]) >= 1 for l in req)


def test_comm_counters_world1():
    from lightgbm_tpu.parallel.distributed import SocketComm
    from lightgbm_tpu.obs.adapters import comm_totals

    reg = default_registry()
    before = (comm_totals(reg) or {}).get("allgather", 0)
    comm = SocketComm(0, 1, ["localhost:12400"])
    comm.allgather({"a": 1})
    comm.allgather({"a": 2})
    comm.close()
    totals = comm_totals(reg)
    assert totals is not None
    assert totals["allgather"] >= before + 2
    assert totals["bytes_sent"] >= 0 and totals["sync_wait_seconds"] >= 0


# ------------------------------------------------------------ log satellites

def test_log_warning_to_stderr(capsys):
    log.warning("telemetry-test warn")
    log.info("telemetry-test info")
    cap = capsys.readouterr()
    assert "telemetry-test warn" in cap.err
    assert "telemetry-test warn" not in cap.out
    assert "telemetry-test info" in cap.out


def test_log_json_mode_and_context(capsys):
    log.set_json_mode(True)
    log.bind_context(rank=2, world=4)
    try:
        log.info("evt %d", 7)
    finally:
        log.set_json_mode(False)
        log.clear_context()
    rec = json.loads(capsys.readouterr().out.strip())
    assert rec["level"] == "info"
    assert rec["msg"] == "evt 7"
    assert rec["rank"] == 2 and rec["world"] == 4
    assert isinstance(rec["ts"], float)


def test_log_set_level_by_name(capsys):
    log.set_level_by_name("warning")
    try:
        log.info("hidden line")
        log.warning("visible line")
    finally:
        log.set_level_by_name("info")
    cap = capsys.readouterr()
    assert "hidden line" not in cap.out + cap.err
    assert "visible line" in cap.err
    with pytest.raises(log.LightGBMError):
        log.set_level_by_name("chatty")


@pytest.mark.parametrize("verbosity,level", [
    (-1, log.FATAL), (0, log.WARNING), (1, log.INFO), (2, log.DEBUG),
    ("3", log.DEBUG), (-7, log.FATAL)])
def test_verbosity_sets_the_level_and_keep_level_restores_it(
        capsys, verbosity, level):
    """An explicit verbosity moves the process-wide level as the params
    are parsed (the reference's Config::Set); a Config without one leaves
    it; keep_level() hands the level back (the supervisor's quiet refits
    inside a server)."""
    from lightgbm_tpu.config import Config
    with log.keep_level():
        Config({"verbose": verbosity})
        assert log.get_level() == level
        Config({"num_leaves": 7})
        assert log.get_level() == level
        log.warning("inside %s", verbosity)
    assert log.get_level() == log.INFO
    log.warning("outside")
    err = capsys.readouterr().err
    assert ("inside" in err) == (level >= log.WARNING)
    assert "outside" in err


def test_profiler_reset_and_minmax():
    from lightgbm_tpu.utils.profiling import Profiler
    p = Profiler(enabled=True)
    for _ in range(3):
        with p.phase("work"):
            pass
    snap = p.snapshot()["work"]
    assert snap["calls"] == 3
    assert 0 <= snap["min_ms"] <= snap["max_ms"]
    p.reset()
    assert p.snapshot() == {}


# ------------------------------------------------- device / peak-HBM gauges

def test_peak_hbm_gauge_published():
    from lightgbm_tpu.obs import adapters, device
    reg = MetricsRegistry()
    adapters.ensure_device_metrics(reg)
    text = reg.render_prometheus()
    assert "lgbm_xla_peak_hbm_bytes" in text
    assert "lgbm_xla_cost_analyses_total" in text
    f = jax.jit(lambda a: jnp.sum(a * 2.0))
    stats = device.analyze_compiled(f, (jnp.ones((64, 64)),), "64x64")
    hbm = device.hbm_stats()
    if stats is not None:                 # analysis availability varies
        assert hbm["analyses"] >= 1
        assert hbm["peak_hbm_bytes"] >= stats.get("peak_hbm_bytes", 0) or \
            hbm["peak_hbm_bytes"] >= 0
    # the gauge renders the live high-water mark
    val = reg.get("lgbm_xla_peak_hbm_bytes").value
    assert val == hbm["peak_hbm_bytes"]
