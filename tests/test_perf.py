"""Roofline observatory (obs/perf + tools/perf_gate + roofline_report):
cost-model registry, chained measurement harness, iteration byte
budget, recorder roofline section (and its bitwise-identity guarantee),
peak-HBM gauges, and the perf-ledger / trace-check gate exit codes via
real subprocesses — all on the fast tier (JAX_PLATFORMS=cpu, conftest)."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import lightgbm_tpu as lgb
from lightgbm_tpu.obs import MetricsRegistry, perf

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXDIR = os.path.join(REPO, "tests", "fixtures")


def _run_tool(tool, *args):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", tool)] + list(args),
        capture_output=True, text=True, cwd=REPO, timeout=300)


V5E = perf.DEVICE_PEAKS["TPU v5 lite"]


def _bench_fixture(tmp_path, scale=1.0, n=8):
    """A bench.py result in the driver's wrapper, on the `tpu` backend,
    whose tracked numbers are the committed ledger's own baselines times
    `scale` — synthetic: the repo keeps no recorded bench run."""
    with open(os.path.join(REPO, "tools", "perf_baseline.json")) as f:
        base = {k: v["baseline"] for k, v in json.load(f)["metrics"].items()}
    higgs = base["higgs_mrows_iter_s"] * scale
    mslr = base["mslr_mrows_iter_s"] * scale
    bench = {"n": n, "parsed": {
        "metric": "higgs_shape_binary_train_throughput", "value": higgs,
        "detail": {
            "backend": "tpu",
            "higgs": {"throughput_mrows_iter_s": higgs},
            "lambdarank": {"throughput_mrows_iter_s": mslr},
            "quantized": {"throughput_mrows_iter_s":
                          base["higgs_quantized_mrows_iter_s"] * scale}}}}
    path = str(tmp_path / ("bench_%g.json" % scale))
    with open(path, "w") as f:
        json.dump(bench, f)
    return path


def _train_data(n=300, nf=6, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, nf)
    y = 2.0 * X[:, 0] - X[:, 1] + 0.05 * rng.randn(n)
    return X, y


# ------------------------------------------------------- cost models

def test_cost_models_registered_next_to_kernels():
    names = perf.cost_models()
    for expected in ("hist/xla", "hist/pallas", "split/xla",
                     "split/pallas", "partition/segment",
                     "partition/hist", "partition/compact",
                     "tree/iteration", "predict/ensemble"):
        assert expected in names


def test_cost_models_scale_with_shapes():
    small = perf.cost("hist/xla", rows=1000, features=8, max_bin=63)
    big = perf.cost("hist/xla", rows=2000, features=8, max_bin=63)
    assert big.hbm_bytes > small.hbm_bytes
    assert big.flops == 2 * small.flops
    # partition is priced off the bf16 arena row footprint, so bytes
    # must be an even multiple of the row count
    p = perf.cost("partition/segment", rows=4096, features=28)
    assert p.hbm_bytes > 2 * 4096 * 2 * 28
    assert perf.cost("partition/compact", rows=4096, features=28).flops == 0
    pred = perf.cost("predict/ensemble", rows=100, features=8, trees=16,
                     leaves=8, nodes=8, classes=1)
    assert pred.flops >= 2 * 100 * 16 * 8 * 8


def test_achieved_and_roofline_math():
    kc = perf.KernelCost("k", hbm_bytes=819_000_000, flops=0)
    # 819 MB in 1 ms at the v5e's 819 GB/s roof = exactly full utilization
    row = perf.achieved(kc, 1.0, V5E)
    assert row["gbps"] == pytest.approx(819.0)
    assert row["hbm_util"] == pytest.approx(1.0)


def test_unknown_device_has_no_roof():
    """One table keyed by device_kind; a kind that is not in it (every
    CPU run) yields no utilisation share — never a default roof."""
    assert jax.devices()[0].device_kind not in perf.DEVICE_PEAKS
    assert perf.device_roofline() is None
    row = perf.achieved(perf.KernelCost("k", hbm_bytes=10 ** 6, flops=10),
                        1.0, None)
    assert row["gbps"] > 0
    assert "hbm_util" not in row and "flop_util" not in row
    s = perf.budget_summary(perf.iteration_budget(1000, 8, 63, 7), 0.01)
    assert "hbm_util" not in s and s["achieved_gbps"] > 0
    assert V5E.hbm_gbps == 819.0 and V5E.peak_tflops == 197.0 \
        and V5E.int8_tops == 393.0 and "TPU v5e" in V5E.source


# ------------------------------------------------- measurement harness

def test_measure_chained_dispatches():
    x = jnp.ones((512, 64), jnp.float32)
    f = jax.jit(lambda a: a * 2.0 + 1.0)
    ms = perf.measure(f, (x,), chain=4)
    assert ms > 0.0
    row = perf.measure_kernel("hist/xla", f, (x,), roof=V5E, chain=2,
                              rows=512, features=64, max_bin=63)
    assert row["kernel"] == "hist/xla"
    assert row["gbps"] > 0 and row["hbm_util"] > 0


def test_probe_picks_smallest_leaf():
    big = jnp.ones((1024, 128))
    small = jnp.ones((2,))
    # the probe must depend on the OUTPUT, not cost a full re-reduction
    # of the big leaf
    val = float(perf._probe_scalar({"big": big, "small": small}))
    assert val == pytest.approx(2.0)


# ------------------------------------------------- iteration budget

@pytest.mark.parametrize("engine", ["partition", "label"])
def test_iteration_budget_totals(engine):
    b = perf.iteration_budget(10000, 28, 255, 31, engine=engine)
    assert b["total_bytes"] == sum(p["bytes"] for p in b["phases"])
    assert b["total_flops"] == sum(p["flops"] for p in b["phases"])
    assert sum(p["share"] for p in b["phases"]) == pytest.approx(1.0,
                                                                 abs=0.01)
    assert b["engine"] == engine and b["total_bytes"] > 0


def test_budget_summary_and_gauges():
    b = perf.iteration_budget(10000, 28, 255, 31)
    s = perf.budget_summary(b, wall_s=0.010, roof=V5E)
    assert s["achieved_gbps"] == pytest.approx(
        b["total_bytes"] / 1e9 / 0.010, rel=1e-3)
    reg = MetricsRegistry()
    perf.publish_iteration_gauges(reg, s)
    text = reg.render_prometheus()
    assert "lgbm_roofline_achieved_gbps" in text
    assert "lgbm_roofline_hbm_util" in text
    perf.publish_kernel_summaries(reg, [
        dict(kernel="hist/xla", gbps=1.0, gflops=2.0, hbm_util=0.01)])
    text = reg.render_prometheus()
    assert 'lgbm_roofline_kernel_gbps{kernel="hist/xla"}' in text


# ------------------------------------------------- recorder integration

def test_recorder_roofline_section(tmp_path):
    X, y = _train_data()
    path = str(tmp_path / "tele.jsonl")
    lgb.train({"objective": "regression", "num_leaves": 7, "verbose": -1,
               "min_data_in_leaf": 5, "tpu_telemetry_path": path},
              lgb.Dataset(X, label=y), num_boost_round=3)
    iters = [json.loads(l) for l in open(path)
             if json.loads(l).get("event") == "iteration"]
    assert iters and all("roofline" in e for e in iters)
    r = iters[0]["roofline"]
    for key in ("analytic_mb", "achieved_gbps"):
        assert key in r
    # the CPU has no published peaks: achieved rates, no shares
    assert "hbm_util" not in r and "flop_util" not in r
    assert r["analytic_mb"] > 0 and r["achieved_gbps"] > 0


def test_recorder_roofline_disabled(tmp_path):
    X, y = _train_data()
    path = str(tmp_path / "tele.jsonl")
    lgb.train({"objective": "regression", "num_leaves": 7, "verbose": -1,
               "min_data_in_leaf": 5, "tpu_telemetry_path": path,
               "tpu_perf_roofline": False},
              lgb.Dataset(X, label=y), num_boost_round=2)
    iters = [json.loads(l) for l in open(path)
             if json.loads(l).get("event") == "iteration"]
    assert iters and all("roofline" not in e for e in iters)


def test_roofline_bitwise_identical_model(tmp_path):
    X, y = _train_data(seed=5)
    params = {"objective": "regression", "num_leaves": 15, "verbose": -1,
              "min_data_in_leaf": 5}
    b_on = lgb.train(dict(params,
                          tpu_telemetry_path=str(tmp_path / "t.jsonl")),
                     lgb.Dataset(X, label=y), num_boost_round=5)
    b_off = lgb.train(dict(params, tpu_perf_roofline=False),
                      lgb.Dataset(X, label=y), num_boost_round=5)
    assert b_on.model_to_string() == b_off.model_to_string()


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


# ------------------------------------------------- perf_gate subprocess

def test_perf_gate_passes_committed_baseline(tmp_path):
    # a bench at the committed ledger's own numbers passes it
    proc = _run_tool("perf_gate.py", "--bench", _bench_fixture(tmp_path))
    assert proc.returncode == 0, proc.stderr
    assert "OK" in proc.stdout


def test_perf_gate_breach_on_injected_regression(tmp_path):
    proc = _run_tool("perf_gate.py",
                     "--bench", _bench_fixture(tmp_path, scale=0.8))  # -20%
    assert proc.returncode == 1
    assert "BREACH" in proc.stderr
    assert "higgs_mrows_iter_s" in proc.stderr


def test_perf_gate_skips_cpu_backend(tmp_path):
    bench = {"n": 99, "parsed": {"detail": {
        "backend": "cpu",
        "higgs": {"throughput_mrows_iter_s": 0.001}}}}
    path = str(tmp_path / "cpu.json")
    json.dump(bench, open(path, "w"))
    proc = _run_tool("perf_gate.py", "--bench", path)
    assert proc.returncode == 0
    assert "skipped" in proc.stdout


def test_perf_gate_unreadable_input(tmp_path):
    bad = str(tmp_path / "bad.json")
    open(bad, "w").write("{not json")
    proc = _run_tool("perf_gate.py", "--bench", bad)
    assert proc.returncode == 2


def test_perf_gate_roofline_floor(tmp_path):
    baseline = {"schema": 1, "metrics": {},
                "roofline": {"hist/pallas": {"hbm_util_min": 0.5}}}
    bl = str(tmp_path / "bl.json")
    json.dump(baseline, open(bl, "w"))
    summary = {"kernels": [{"kernel": "hist/pallas", "hbm_util": 0.1}]}
    rf = str(tmp_path / "roofline.json")
    json.dump(summary, open(rf, "w"))
    proc = _run_tool("perf_gate.py",
                     "--bench", _bench_fixture(tmp_path),
                     "--roofline", rf, "--baseline", bl)
    assert proc.returncode == 1
    assert "roofline hist/pallas" in proc.stderr


def test_perf_gate_write_baseline_roundtrip(tmp_path):
    bl = str(tmp_path / "ledger.json")
    bench = _bench_fixture(tmp_path, n=5)
    proc = _run_tool("perf_gate.py", "--bench", bench,
                     "--write-baseline", "--baseline", bl)
    assert proc.returncode == 0, proc.stderr
    ledger = json.load(open(bl))
    assert ledger["metrics"]["higgs_mrows_iter_s"]["baseline"] > 0
    assert ledger["history"][-1]["round"] == 5
    proc = _run_tool("perf_gate.py", "--bench", bench,
                     "--baseline", bl)
    assert proc.returncode == 0


# ------------------------------------------------- trace_check subprocess

def test_trace_check_subprocess_passes_committed_baseline():
    proc = _run_tool("trace_check.py",
                     os.path.join(FIXDIR, "trace", "rank0.trace.json"),
                     "--baseline",
                     os.path.join(FIXDIR, "trace", "baseline.json"))
    assert proc.returncode == 0, proc.stderr
    assert "OK" in proc.stdout


def test_trace_check_subprocess_breach():
    proc = _run_tool("trace_check.py",
                     os.path.join(FIXDIR, "trace", "rank0.trace.json"),
                     "--baseline",
                     os.path.join(FIXDIR, "trace", "baseline_breach.json"))
    assert proc.returncode == 1
    assert "BREACH" in proc.stderr


def test_trace_check_subprocess_unreadable(tmp_path):
    bad = str(tmp_path / "bad.json")
    open(bad, "w").write("nope")
    proc = _run_tool("trace_check.py", bad)
    assert proc.returncode == 2


# ------------------------------------------------- roofline_report tool

def test_roofline_report_subprocess(tmp_path):
    out = str(tmp_path / "roofline.json")
    proc = _run_tool("roofline_report.py", "--rows", "512",
                     "--features", "8", "--max-bin", "15",
                     "--leaves", "7", "--chain", "2",
                     "--kernels", "hist,split", "--json", out)
    # the CPU is not in the peaks table: the achieved rates are printed
    # and written, without shares, and the tool ends in an error
    assert proc.returncode == 2, proc.stderr[-2000:]
    assert "no published peaks for device kind 'cpu'" in proc.stderr
    assert "roofline report" in proc.stdout
    assert "iteration byte budget" in proc.stdout
    summary = json.load(open(out))
    assert summary["rooflines"] is None and summary["device_kind"] == "cpu"
    kernels = {k["kernel"]: k for k in summary["kernels"]}
    assert "hist/xla" in kernels and "split/xla" in kernels
    measured = [k for k in kernels.values() if "skipped" not in k]
    assert measured, "every kernel was skipped: %s" % kernels
    for row in measured:
        for key in ("hbm_bytes", "flops", "ms", "gbps", "gflops"):
            assert key in row
        assert "hbm_util" not in row and "flop_util" not in row
        assert row["ms"] > 0
    assert summary["budget"]["total_bytes"] > 0
