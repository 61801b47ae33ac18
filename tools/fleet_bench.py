"""Multi-tenant fleet bench: N models behind one byte-budgeted HBM
residency manager (serving/fleet.py) under mixed traffic — a hot subset
hammered closed-loop, the cold tail swept round-robin — reporting
aggregate throughput, per-tenant p50/p99 split by hot/cold, and the
cold-load latency distribution (load + synchronous promote per tenant).

The point of the bench is the degradation shape, not a raw number: with
a budget sized for `resident_cap` models out of `tenants`, cold tenants
must ride the host walk (slower, never failing) while the hot set stays
device-resident, and the byte accounting must never exceed the budget
(asserted on the peak high-water mark).

Usage: python tools/fleet_bench.py [--tenants 16] [--resident-cap 4]
           [--duration-s 4] [--trees 8]
Emits one BENCH-style JSON line:
  {"metric": "fleet_aggregate_qps", "value": ..., "unit": "req/s",
   "vs_baseline": ..., "detail": {...}}
"""
import argparse
import json
import sys
import threading
import time

import numpy as np

sys.path.insert(0, ".")
import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu.ops import predict as predict_ops  # noqa: E402
from lightgbm_tpu.serving import Server  # noqa: E402


def _train_bases(trees, n_bases=4, nf=8):
    strs = []
    for seed in range(n_bases):
        rng = np.random.RandomState(seed)
        X = rng.rand(400, nf)
        y = 2.0 * X[:, 0] - X[:, 1] + 0.05 * rng.randn(400)
        strs.append(lgb.train(
            {"objective": "regression", "num_leaves": 15, "verbose": -1,
             "min_data_in_leaf": 5},
            lgb.Dataset(X, label=y), num_boost_round=trees)
            .model_to_string())
    return strs


def _pcts(lat_ms):
    if not lat_ms:
        return float("nan"), float("nan")
    lat = np.asarray(lat_ms)
    return (float(np.percentile(lat, 50)), float(np.percentile(lat, 99)))


def run_bench(tenants=16, resident_cap=4, duration_s=4.0, trees=8):
    model_strs = _train_bases(trees)
    probe = lgb.Booster(model_str=model_strs[0])
    est = predict_ops.estimate_device_bytes(
        probe._gbdt.models, probe._gbdt.num_tree_per_iteration)
    budget_bytes = est * resident_cap
    srv = Server(verbosity=-1,
                 serve_min_device_work=1,
                 serve_max_models=tenants + 1,
                 serve_max_batch_rows=64,
                 serve_warmup_buckets=[16, 64],
                 tpu_fleet_hbm_budget_mb=budget_bytes / float(1 << 20))
    names = ["t%02d" % i for i in range(tenants)]
    cold_load_ms = []
    for i, name in enumerate(names):
        t0 = time.perf_counter()
        srv.load_model(name, model_str=model_strs[i % len(model_strs)])
        cold_load_ms.append((time.perf_counter() - t0) * 1e3)

    hot = names[:max(resident_cap // 2, 1)]
    cold = names[len(hot):]
    rng = np.random.RandomState(1)
    Xq = rng.rand(16, 8)
    lat = {n: [] for n in names}
    errors = [0]
    lock = threading.Lock()
    stop = threading.Event()

    def hammer(targets, pause_s):
        i = 0
        while not stop.is_set():
            name = targets[i % len(targets)]
            i += 1
            t0 = time.perf_counter()
            try:
                srv.predict(Xq, model=name)
                dt = (time.perf_counter() - t0) * 1e3
                with lock:
                    lat[name].append(dt)
            except Exception:  # noqa: BLE001 — the bench counts ANY failure
                with lock:
                    errors[0] += 1
            if pause_s:
                time.sleep(pause_s)

    threads = ([threading.Thread(target=hammer, args=(hot, 0.0),
                                 daemon=True) for _ in range(4)]
               + [threading.Thread(target=hammer, args=(cold, 0.005),
                                   daemon=True) for _ in range(2)])
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    time.sleep(duration_s)
    stop.set()
    for t in threads:
        t.join(timeout=10.0)
    wall = time.perf_counter() - t0
    snap = srv.fleet.snapshot()
    srv.shutdown()

    total = sum(len(v) for v in lat.values())
    hot_lat = [x for n in hot for x in lat[n]]
    cold_lat = [x for n in cold for x in lat[n]]
    hot_p50, hot_p99 = _pcts(hot_lat)
    cold_p50, cold_p99 = _pcts(cold_lat)
    # worst per-tenant p99 (any tenant with enough samples to call one)
    tenant_p99 = {n: _pcts(v)[1] for n, v in lat.items() if len(v) >= 20}
    load_p50, load_p99 = _pcts(cold_load_ms)
    quality_ok = (errors[0] == 0
                  and snap["peak_resident_bytes"] <= budget_bytes
                  and total > 0)
    return {
        "metric": "fleet_aggregate_qps",
        "value": round(total / wall, 1),
        "unit": "req/s",
        "vs_baseline": round(total / wall / max(len(threads), 1), 1),
        "detail": {
            "tenants": tenants,
            "resident_cap": resident_cap,
            "budget_bytes": budget_bytes,
            "duration_s": duration_s,
            "requests": total,
            "errors": errors[0],
            "hot": {"tenants": len(hot), "p50_ms": round(hot_p50, 3),
                    "p99_ms": round(hot_p99, 3)},
            "cold": {"tenants": len(cold), "p50_ms": round(cold_p50, 3),
                     "p99_ms": round(cold_p99, 3)},
            "worst_tenant_p99_ms": round(max(tenant_p99.values()), 3)
            if tenant_p99 else None,
            "cold_load_ms": {"p50": round(load_p50, 3),
                             "p99": round(load_p99, 3),
                             "max": round(max(cold_load_ms), 3)},
            "fleet": {k: snap[k] for k in
                      ("peak_resident_bytes", "resident_bytes",
                       "promotions", "evictions", "host_serves",
                       "device_hits", "promote_failures",
                       "compile_cache")},
            "quality_ok": quality_ok,
        },
    }


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="Multi-tenant fleet residency bench")
    ap.add_argument("--tenants", type=int, default=16)
    ap.add_argument("--resident-cap", type=int, default=4)
    ap.add_argument("--duration-s", type=float, default=4.0)
    ap.add_argument("--trees", type=int, default=8)
    args = ap.parse_args(argv)
    result = run_bench(tenants=args.tenants,
                       resident_cap=args.resident_cap,
                       duration_s=args.duration_s, trees=args.trees)
    print(json.dumps(result))
    return 0 if result["detail"]["quality_ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
