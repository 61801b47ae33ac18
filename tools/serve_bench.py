"""Serving throughput/latency bench: offered-load QPS vs p50/p99 at
several client concurrency levels through the micro-batching server,
against a sequential single-row baseline (one request at a time, no
coalescing benefit).

The acceptance bar: >= 5x throughput for 32 concurrent 1-row clients vs
sequential single-row predicts.  Works on any backend (JAX_PLATFORMS=cpu
is fine for CI); what it is on a TPU is not measured.

Two modes:

- closed-loop (default): N client threads, each fires the next request
  only when its previous one returns.  Measures coalescing throughput,
  but the arrival rate adapts to the server — queueing never builds up,
  so tail latency under real load is invisible (coordinated omission).
- open-loop (--open-loop): requests arrive on a Poisson process at an
  OFFERED rate the server does not control; latency is measured from
  the scheduled arrival time, so queue buildup at an overloaded QPS
  level shows up in p99 instead of being absorbed by the client.  Emits
  a p50/p99-latency-at-offered-QPS BENCH line.

A third mode sweeps replica counts (--replicas, serving/replicas.py):
one fresh server per count under the SAME open-loop offered load,
emitting p50/p99 + achieved throughput per replica count — the
capacity curve the set_replica_count lever buys.

Usage: python tools/serve_bench.py [requests_per_level] [model_trees]
       python tools/serve_bench.py --open-loop [--qps 50,200,800]
           [--duration-s 5] [--trees 64]
       python tools/serve_bench.py --replicas 1,2,4,8 [--qps ...]
           [--duration-s 5] [--runhist PATH]
Emits one BENCH-style JSON line:
  {"metric": "serve_concurrency_speedup_x32", "value": ..., "unit": "x",
   "vs_baseline": ..., "detail": {...}}
or, open-loop:
  {"metric": "serve_open_loop_p99_ms", "value": ..., "unit": "ms", ...}
or, replica sweep:
  {"metric": "serve_replicas_p99_ms", "value": ..., "unit": "ms", ...}
"""
import argparse
import json
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, ".")
import lightgbm_tpu as lgb  # noqa: E402
from lightgbm_tpu.serving import Server  # noqa: E402

LEVELS = (1, 8, 32)


def _train(trees):
    rng = np.random.RandomState(0)
    X = rng.rand(20_000, 28).astype(np.float64)
    w = rng.randn(28) / np.sqrt(28)
    y = X @ w + 0.1 * rng.randn(len(X))
    params = {"objective": "regression", "num_leaves": 63, "verbose": -1,
              "min_data_in_leaf": 20}
    return lgb.train(params, lgb.Dataset(X, label=y), num_boost_round=trees)


def _percentiles(lat_ms):
    lat = np.sort(np.asarray(lat_ms))
    return (float(np.percentile(lat, 50)), float(np.percentile(lat, 99)))


def _run_level(server, rows, concurrency, requests):
    """`requests` 1-row predicts spread over `concurrency` client
    threads; returns (qps, p50_ms, p99_ms)."""
    lat = []

    def one(i):
        t0 = time.perf_counter()
        server.predict(rows[i % len(rows)])
        lat.append((time.perf_counter() - t0) * 1e3)

    t0 = time.perf_counter()
    with ThreadPoolExecutor(concurrency) as pool:
        list(pool.map(one, range(requests)))
    wall = time.perf_counter() - t0
    p50, p99 = _percentiles(lat)
    return requests / wall, p50, p99


def _run_open_loop(server, rows, offered_qps, duration_s, rng):
    """One offered-QPS level: Poisson arrivals (exponential gaps) for
    `duration_s`, dispatched from a wide pool so a slow server cannot
    slow the ARRIVALS down.  Latency is measured from each request's
    scheduled arrival time — queue wait (including dispatcher backlog)
    counts, which is the whole point of the open loop."""
    lat, errors = [], [0]
    lock = threading.Lock()
    # enough workers that the pool itself is never the bottleneck at
    # the offered rates this bench runs
    pool = ThreadPoolExecutor(max_workers=256)
    t0 = time.perf_counter()
    # pre-draw the whole arrival schedule so the dispatcher loop does
    # no RNG work between sends
    gaps = rng.exponential(1.0 / offered_qps,
                           int(offered_qps * duration_s) + 1)
    sched = t0 + np.cumsum(gaps)
    sched = sched[sched < t0 + duration_s]

    def one(scheduled_t, i):
        try:
            server.predict(rows[i % len(rows)])
            dt = (time.perf_counter() - scheduled_t) * 1e3
            with lock:
                lat.append(dt)
        except Exception:  # noqa: BLE001 — shed/timeout counts as error
            with lock:
                errors[0] += 1

    for i, ts in enumerate(sched):
        now = time.perf_counter()
        if ts > now:
            time.sleep(ts - now)
        pool.submit(one, ts, i)
    pool.shutdown(wait=True)
    wall = time.perf_counter() - t0
    done = len(lat)
    p50, p99 = _percentiles(lat) if lat else (float("nan"), float("nan"))
    return {"offered_qps": round(offered_qps, 1),
            "achieved_qps": round(done / wall, 1),
            "sent": len(sched), "completed": done, "errors": errors[0],
            "p50_ms": round(p50, 3), "p99_ms": round(p99, 3),
            "histogram": _lat_histogram(lat)}


# log-spaced millisecond bounds wide enough for an overloaded level —
# the FULL bucket-resolution shape rides into the RUNHIST artifact so
# tools/run_diff.py compares tails, not just the p50/p99 scalars
_LAT_BOUNDS_MS = (0.25, 0.5, 1, 2, 4, 8, 16, 32, 64, 128, 256, 512,
                  1024, 2048, 4096)


def _lat_histogram(lat_ms):
    from lightgbm_tpu.obs.registry import Histogram
    h = Histogram(_LAT_BOUNDS_MS)
    for v in lat_ms:
        h.observe(v)
    return h.snapshot()


def _open_loop_main(args):
    bst = _train(args.trees)
    rng = np.random.RandomState(1)
    rows = [rng.rand(1, 28) for _ in range(64)]
    server = Server({"serve_model_name": "bench",
                     "serve_min_device_work": 0,
                     "serve_batch_wait_ms": 2.0,
                     "serve_max_batch_rows": 256,
                     "serve_request_timeout_ms": 60_000.0,
                     "serve_warmup_buckets": [1, 2, 4, 8, 16, 32, 64, 128,
                                              256]})
    server.load_model("bench", model_str=bst.model_to_string())
    _run_level(server, rows, 4, 32)   # settle the dispatch path

    qps_levels = [float(q) for q in args.qps.split(",")]
    arrivals = np.random.RandomState(7)
    levels, histograms = {}, {}
    for q in qps_levels:
        r = _run_open_loop(server, rows, q, args.duration_s, arrivals)
        histograms["latency_ms@%gqps" % q] = r.pop("histogram")
        levels["%g" % q] = r
        print("offered %8.1f qps: achieved %8.1f qps  p50=%.2f ms  "
              "p99=%.2f ms  errors=%d"
              % (q, r["achieved_qps"], r["p50_ms"], r["p99_ms"],
                 r["errors"]))
    server.shutdown()

    if args.runhist:
        from lightgbm_tpu.obs.timeseries import SeriesStore, write_runhist
        store = SeriesStore()
        for i, q in enumerate(qps_levels):
            r = levels["%g" % q]
            for field in ("achieved_qps", "p50_ms", "p99_ms", "errors"):
                store.observe("serve/%s" % field, i + 1, r[field],
                              qps="%g" % q)
        ok = write_runhist(args.runhist, {
            "kind": "serve_bench", "mode": "open_loop_poisson",
            "duration_s": args.duration_s, "trees": args.trees,
            "qps_levels": [("%g" % q) for q in qps_levels],
        }, store, histograms=histograms)
        if ok:
            print("RUNHIST written to %s (%d latency histograms)"
                  % (args.runhist, len(histograms)))

    # headline: tail latency at the highest offered level the server
    # actually sustained (achieved within 10% of offered)
    sustained = [r for r in levels.values()
                 if r["achieved_qps"] >= 0.9 * r["offered_qps"]]
    head = sustained[-1] if sustained else list(levels.values())[0]
    result = {
        "metric": "serve_open_loop_p99_ms",
        "value": head["p99_ms"],
        "unit": "ms",
        "vs_baseline": head["offered_qps"],
        "detail": {
            "mode": "open_loop_poisson",
            "duration_s": args.duration_s,
            "model_trees": args.trees,
            "levels": levels,
            "sustained_qps": head["offered_qps"],
            "quality_ok": bool(sustained),
        },
    }
    print(json.dumps(result))
    return 0 if sustained else 1


def _force_virtual_devices(n: int = 8) -> None:
    """Replica sweeps need distinct fault domains: ask the CPU platform
    for `n` virtual devices.  Call before the first use of JAX — XLA
    reads the flag when the backend starts; it does nothing to an
    accelerator backend, which serves with the devices it has."""
    import os
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=%d" % n).strip()


def _replica_sweep_main(args):
    """One fresh server per replica count, all under the same offered
    Poisson load (the HIGHEST --qps level, so queueing pressure — the
    thing extra replicas relieve — is actually present)."""
    _force_virtual_devices()
    counts = sorted({max(int(c), 1) for c in args.replicas.split(",")})
    offered = max(float(q) for q in args.qps.split(","))
    bst = _train(args.trees)
    model_str = bst.model_to_string()
    rng = np.random.RandomState(1)
    rows = [rng.rand(1, 28) for _ in range(64)]
    arrivals = np.random.RandomState(7)
    levels, histograms = {}, {}
    for n in counts:
        server = Server({"serve_model_name": "bench",
                         "serve_min_device_work": 0,
                         "serve_batch_wait_ms": 2.0,
                         "serve_max_batch_rows": 256,
                         "serve_request_timeout_ms": 60_000.0,
                         "serve_warmup_buckets": [1, 2, 4, 8, 16, 32, 64,
                                                  128, 256],
                         "tpu_replica_count": n,
                         "tpu_replica_max": max(n, 8)})
        server.load_model("bench", model_str=model_str)
        rset = server.registry.replica_set("bench")
        placed = rset.count if rset is not None else 1
        _run_level(server, rows, 4, 32)   # settle the dispatch path
        r = _run_open_loop(server, rows, offered, args.duration_s,
                           arrivals)
        server.shutdown()
        histograms["latency_ms@%dreplicas" % n] = r.pop("histogram")
        r["replicas_requested"] = n
        r["replicas_placed"] = placed
        levels[str(n)] = r
        print("replicas=%-2d (placed %d): achieved %8.1f qps  "
              "p50=%.2f ms  p99=%.2f ms  errors=%d"
              % (n, placed, r["achieved_qps"], r["p50_ms"], r["p99_ms"],
                 r["errors"]))

    if args.runhist:
        from lightgbm_tpu.obs.timeseries import SeriesStore, write_runhist
        store = SeriesStore()
        for i, n in enumerate(counts):
            r = levels[str(n)]
            for field in ("achieved_qps", "p50_ms", "p99_ms", "errors"):
                store.observe("serve_replicas/%s" % field, i + 1,
                              r[field], replicas=str(n))
        ok = write_runhist(args.runhist, {
            "kind": "serve_bench", "mode": "replica_sweep",
            "offered_qps": offered, "duration_s": args.duration_s,
            "trees": args.trees,
            "replica_counts": [str(n) for n in counts],
        }, store, histograms=histograms)
        if ok:
            print("RUNHIST written to %s (%d latency histograms)"
                  % (args.runhist, len(histograms)))

    head = levels[str(counts[-1])]
    result = {
        "metric": "serve_replicas_p99_ms",
        "value": head["p99_ms"],
        "unit": "ms",
        "vs_baseline": head["achieved_qps"],
        "detail": {
            "mode": "replica_sweep_open_loop",
            "offered_qps": offered,
            "duration_s": args.duration_s,
            "model_trees": args.trees,
            "levels": levels,
            # 1-row requests: achieved qps IS the rows/s throughput
            "rows_s": head["achieved_qps"],
            "quality_ok": all(r["errors"] == 0 for r in levels.values()),
        },
    }
    print(json.dumps(result))
    return 0 if result["detail"]["quality_ok"] else 1


def _parse_args(argv):
    ap = argparse.ArgumentParser(
        description="Serving bench: closed-loop concurrency sweep or "
                    "open-loop Poisson offered load")
    ap.add_argument("requests", nargs="?", type=int, default=256,
                    help="closed-loop requests per level (default 256)")
    ap.add_argument("trees_pos", nargs="?", type=int, default=None,
                    help="model size in trees (positional compat)")
    ap.add_argument("--trees", type=int, default=64)
    ap.add_argument("--open-loop", action="store_true",
                    help="Poisson offered-load mode")
    ap.add_argument("--qps", default="50,200,800",
                    help="comma-separated offered QPS levels")
    ap.add_argument("--duration-s", type=float, default=5.0,
                    help="seconds per offered-QPS level")
    ap.add_argument("--replicas", default="",
                    help="comma-separated replica counts; sweeps a fresh "
                         "server per count under the highest --qps level "
                         "(serving/replicas.py capacity curve)")
    ap.add_argument("--runhist", metavar="PATH", default="",
                    help="open-loop mode: write a RUNHIST artifact with "
                         "the FULL latency histogram per QPS level "
                         "(diffable by tools/run_diff.py)")
    args = ap.parse_args(argv)
    if args.trees_pos is not None:
        args.trees = args.trees_pos
    return args


def main(argv=None):
    args = _parse_args(argv)
    if args.replicas:
        return _replica_sweep_main(args)
    if args.open_loop:
        return _open_loop_main(args)
    requests, trees = args.requests, args.trees
    bst = _train(trees)
    rng = np.random.RandomState(1)
    rows = [rng.rand(1, 28) for _ in range(64)]

    server = Server({"serve_model_name": "bench",
                     "serve_min_device_work": 0,
                     "serve_batch_wait_ms": 2.0,
                     "serve_max_batch_rows": 256,
                     "serve_request_timeout_ms": 60_000.0,
                     "serve_warmup_buckets": [1, 2, 4, 8, 16, 32, 64, 128,
                                              256]})
    server.load_model("bench", model_str=bst.model_to_string())
    # settle the dispatch path
    _run_level(server, rows, 4, 32)

    # sequential single-row baseline: one in-flight request, every row
    # pays the full dispatch latency alone
    seq_qps, seq_p50, seq_p99 = _run_level(server, rows, 1, requests)
    print("sequential: %.1f qps  p50=%.2f ms  p99=%.2f ms"
          % (seq_qps, seq_p50, seq_p99))

    levels = {}
    for c in LEVELS:
        qps, p50, p99 = _run_level(server, rows, c, requests)
        levels[c] = {"qps": round(qps, 1), "p50_ms": round(p50, 3),
                     "p99_ms": round(p99, 3),
                     "speedup_vs_sequential": round(qps / seq_qps, 3)}
        print("c=%-3d %8.1f qps  p50=%.2f ms  p99=%.2f ms  (%.2fx)"
              % (c, qps, p50, p99, qps / seq_qps))

    snap = server.stats_snapshot()["models"]["bench"]
    server.shutdown()

    speedup32 = levels[32]["speedup_vs_sequential"]
    result = {
        "metric": "serve_concurrency_speedup_x32",
        "value": speedup32,
        "unit": "x",
        # acceptance bar: >= 5x for 32 concurrent 1-row clients
        "vs_baseline": round(speedup32 / 5.0, 4),
        "detail": {
            "requests_per_level": requests,
            "model_trees": trees,
            "sequential": {"qps": round(seq_qps, 1),
                           "p50_ms": round(seq_p50, 3),
                           "p99_ms": round(seq_p99, 3)},
            "levels": {str(k): v for k, v in levels.items()},
            "batches": snap["batches"],
            "device_batches": snap["device_batches"],
            "batch_p50": snap["batch_size"]["p50"],
            "quality_ok": speedup32 >= 5.0,
        },
    }
    print(json.dumps(result))
    return 0 if speedup32 >= 5.0 else 1


if __name__ == "__main__":
    sys.exit(main())
