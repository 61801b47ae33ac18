#!/usr/bin/env python
"""Per-kernel roofline report: analytic bytes/FLOPs vs measured time.

Drives each hot kernel standalone at bench-like shapes, prices it with
the analytic cost model registered next to the kernel (obs/perf), and
prints the table a perf PR argues with: analytic MB and GFLOP, measured
ms, achieved GB/s and GFLOP/s, and the share of the device's published
peaks (obs/perf.DEVICE_PEAKS, keyed by ``device_kind``).  A device that
is not in that table has no roof: the achieved rates are still printed,
without shares, and the tool ends in an error (exit 2) — every CPU run
does.  A kernel far from the bandwidth roof with low arithmetic
intensity is latency/overhead-bound — the fused-mega-kernel candidate
list; one near the roof only goes faster by moving fewer bytes — the
quantized-histogram candidate list.  The second table is the
per-iteration byte budget: where an iteration's compulsory traffic goes.

Timing (obs/perf.measure): chain K dispatches, reduce the last result
to a device scalar, ``float()`` once.

Usage:
    python tools/roofline_report.py                  # bench-like shapes
    python tools/roofline_report.py --rows 4194304 --features 28 \
        --max-bin 255 --leaves 31 --chain 8 [--json OUT.json] \
        [--kernels hist,partition]

--json writes the machine-readable summary tools/perf_gate.py ingests
for per-kernel bandwidth-utilization floors.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _build_kernels(args, interpret: bool):
    """[(name, shape_kwargs, fn, call_args)] for every requested kernel;
    construction failures degrade to a skipped row, never kill the
    report (a CPU image without one kernel still measures the rest)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from lightgbm_tpu.ops import histogram as hist_xla
    from lightgbm_tpu.ops import histogram_pallas as hist_pl
    from lightgbm_tpu.ops import partition_pallas as pp
    from lightgbm_tpu.ops import split as split_xla
    from lightgbm_tpu.ops import split_pallas as split_pl
    from lightgbm_tpu.utils.backend import on_tpu

    n, F, B, L = args.rows, args.features, args.max_bin, args.leaves
    rng = np.random.default_rng(0)
    bins = jnp.asarray(rng.integers(0, B, (n, F), dtype=np.uint8))
    grad = jnp.asarray(rng.standard_normal(n, dtype=np.float32))
    hess = jnp.asarray(rng.uniform(0.1, 1.0, n).astype(np.float32))
    leaf_ids = jnp.zeros(n, jnp.int32)
    kernels = []

    # -- histograms ------------------------------------------------------ #
    xla_impl = "compact" if on_tpu() else "scatter"
    kernels.append((
        "hist/xla", dict(rows=n, features=F, max_bin=B),
        jax.jit(functools.partial(hist_xla.leaf_histogram, max_bin=B,
                                  impl=xla_impl)),
        (bins, grad, hess, leaf_ids, 0)))
    kernels.append((
        "hist/pallas", dict(rows=n, features=F, max_bin=B),
        jax.jit(functools.partial(hist_pl.leaf_histogram, max_bin=B,
                                  interpret=interpret)),
        (bins, grad, hess, leaf_ids, 0)))

    # -- quantized histograms (ops/quantize codes, docs/Quantized.md) ---- #
    from lightgbm_tpu.ops import quantize as qz
    g_code, h_code, _gs, _hs = qz.quantize_gradients(
        grad, hess, qz.quantize_key(0, 0))
    kernels.append((
        "hist/quantized", dict(rows=n, features=F, max_bin=B),
        jax.jit(functools.partial(hist_pl.leaf_histogram_quantized,
                                  max_bin=B, interpret=interpret)),
        (bins, g_code, h_code, leaf_ids, 0)))

    # -- split scans ----------------------------------------------------- #
    hist = jnp.asarray(rng.uniform(0.0, 1.0, (F, B, 3)).astype(np.float32))
    sum_g = jnp.sum(hist[0, :, 0])
    sum_h = jnp.sum(hist[0, :, 1]) + 1.0
    num_bins = jnp.full(F, B, jnp.int32)
    default_bins = jnp.zeros(F, jnp.int32)
    missing_types = jnp.zeros(F, jnp.int32)
    params = split_xla.SplitParams()

    def split_xla_fn(h, sg, sh):
        return split_xla.best_split_for_leaf(
            h, sg, sh, n, num_bins, default_bins, missing_types, params)
    kernels.append(("split/xla", dict(features=F, max_bin=B),
                    jax.jit(split_xla_fn), (hist, sum_g, sum_h)))

    def split_pl_fn(h, sg, sh):
        return split_pl.scan_single(
            h, sg, sh, jnp.float32(n), params, num_bins=num_bins,
            default_bins=default_bins, missing_types=missing_types,
            interpret=interpret)
    kernels.append(("split/pallas", dict(features=F, max_bin=B),
                    jax.jit(split_pl_fn), (hist, sum_g, sum_h)))

    # -- partition-engine kernels ---------------------------------------- #
    C, cap = pp.arena_geometry(n, F, factor=4)
    base = -(-n // pp.TILE) * pp.TILE
    arena = pp.init_pristine(jnp.zeros((C, cap), pp.ARENA_DT), bins.T)
    pred = jnp.asarray((rng.uniform(size=cap) < 0.5).astype(np.float32)
                       )[None, :]
    dstA = pp.pristine_work0(n)                 # TILE-aligned work region
    dstB = dstA + base + pp.TILE                # disjoint from [0, n+TILE)

    part_jit = jax.jit(
        lambda a, p: pp.partition_segment(a, p, 0, n, dstA, dstB,
                                          interpret=interpret),
        donate_argnums=0)
    # the kernel aliases arena in/out, so each call consumes the previous
    # arena — a stateful closure keeps the donation chain intact
    part_state = {"arena": arena}

    def part_fn():
        out, counts = part_jit(part_state["arena"], pred)
        part_state["arena"] = out
        return counts
    kernels.append(("partition/segment", dict(rows=n, features=F),
                    part_fn, ()))

    seg_state = {"arena": None}   # filled after partition measurement

    def fresh_arena():
        if seg_state["arena"] is None:
            seg_state["arena"] = pp.init_pristine(
                jnp.zeros((C, cap), pp.ARENA_DT), bins.T)
        return seg_state["arena"]

    seg_jit = jax.jit(
        lambda a: pp.segment_histogram(a, 0, n, F, B, interpret=interpret))
    kernels.append(("partition/hist", dict(rows=n, features=F, max_bin=B),
                    lambda: seg_jit(fresh_arena()), ()))

    # quantized segment histogram: same arena with the two int8-code
    # payload planes written at rows Fp/Fp+1 (the partial-row DMA path)
    codes = pp.pack_code_planes(g_code, h_code)
    qarena_state = {"arena": None}

    def quant_arena():
        if qarena_state["arena"] is None:
            a = pp.init_pristine(jnp.zeros((C, cap), pp.ARENA_DT), bins.T)
            qarena_state["arena"] = jax.lax.dynamic_update_slice(
                a, codes, (pp.feature_channels(F), 0))
        return qarena_state["arena"]

    segq_jit = jax.jit(
        lambda a: pp.segment_histogram(a, 0, n, F, B, quantized=True,
                                       interpret=interpret))
    kernels.append(("partition/hist_quantized",
                    dict(rows=n, features=F, max_bin=B),
                    lambda: segq_jit(quant_arena()), ()))

    # fused refresh+histogram mega-kernel: aliases the arena in/out, so
    # keep the donation chain alive like partition/segment above
    fused_jit = jax.jit(
        lambda a, c: pp.fused_refresh_histogram(a, c, 0, n, num_features=F,
                                                max_bin=B,
                                                interpret=interpret),
        donate_argnums=0)
    fused_state = {"arena": None}

    def fused_fn():
        if fused_state["arena"] is None:
            fused_state["arena"] = pp.init_pristine(
                jnp.zeros((C, cap), pp.ARENA_DT), bins.T)
        out, hist = fused_jit(fused_state["arena"], codes)
        fused_state["arena"] = out
        return hist
    kernels.append(("partition/fused_root",
                    dict(rows=n, features=F, max_bin=B), fused_fn, ()))

    starts = jnp.zeros(1, jnp.int32)
    cnts = jnp.full(1, n, jnp.int32)
    comp_jit = jax.jit(
        lambda a: pp.compact_carry(a, starts, cnts, 1, dstA,
                                   interpret=interpret),
        donate_argnums=0)
    comp_state = {"arena": None}

    def comp_fn():
        if comp_state["arena"] is None:
            comp_state["arena"] = pp.init_pristine(
                jnp.zeros((C, cap), pp.ARENA_DT), bins.T)
        out, used = comp_jit(comp_state["arena"])
        comp_state["arena"] = out
        return used
    kernels.append(("partition/compact", dict(rows=n, features=F),
                    comp_fn, ()))

    # -- prediction ------------------------------------------------------ #
    # a small real booster gives the ensemble its true tree topology;
    # the measured dispatch is the jitted signature-matmul chunk itself
    # (predict_sum would pay a host transfer per call)
    import lightgbm_tpu as lgb
    from lightgbm_tpu.ops import predict as predict_ops
    pn = min(n, 65536)
    Xtr = rng.standard_normal((4096, F)).astype(np.float32)
    ytr = (Xtr[:, 0] + 0.25 * rng.standard_normal(4096) > 0).astype(
        np.float32)
    bst = lgb.train({"objective": "binary", "num_leaves": 15,
                     "max_bin": min(B, 63), "min_data_in_leaf": 5,
                     "verbose": -1},
                    lgb.Dataset(Xtr, label=ytr), num_boost_round=8)
    ens = bst._gbdt._device_ensemble()
    if ens is not None:
        X = jnp.asarray(rng.standard_normal((pn, F)).astype(np.float32))
        lv = ens.lv

        def pred_fn():
            return predict_ops._chunk_scores(
                X, None, ens.sf_flat, ens.thr_flat, ens.thr_lo,
                ens.dl_flat, ens.mt_flat, ens.ic_flat, ens.cat,
                ens.sig, ens.path_len, lv, k=ens.k, T=ens.T, N=ens.N)
        kernels.append((
            "predict/ensemble",
            dict(rows=pn, features=F, trees=ens.T, leaves=ens.L,
                 nodes=ens.N, classes=ens.k),
            pred_fn, ()))
    return kernels


def run(args) -> dict:
    import jax
    from lightgbm_tpu.obs import perf
    from lightgbm_tpu.utils.backend import pallas_interpret

    backend = jax.default_backend()
    interpret = pallas_interpret()
    device_kind = jax.devices()[0].device_kind
    roof = perf.device_roofline()
    want = [k.strip() for k in args.kernels.split(",")] if args.kernels \
        else None
    rows = []
    for name, shape_kwargs, fn, call_args in _build_kernels(args, interpret):
        if want and not any(name.startswith(w) for w in want):
            continue
        try:
            row = perf.measure_kernel(name, fn, call_args, roof=roof,
                                      chain=args.chain, **shape_kwargs)
        except Exception as exc:  # noqa: BLE001 — report the rest anyway
            row = {"kernel": name, "skipped": str(exc)[:200]}
        rows.append(row)

    budget = perf.iteration_budget(args.rows, args.features, args.max_bin,
                                   args.leaves, engine=args.engine)
    summary = {"backend": backend, "device_kind": device_kind,
               "rooflines": (None if roof is None else
                             {"hbm_gbps": roof.hbm_gbps,
                              "peak_tflops": roof.peak_tflops,
                              "source": roof.source}),
               "shapes": {"rows": args.rows, "features": args.features,
                          "max_bin": args.max_bin, "num_leaves": args.leaves,
                          "chain": args.chain},
               "kernels": rows, "budget": budget}
    if args.engine == "partition":
        # quantized-mode byte budget + the headline analytic ratio: the
        # quantized histogram kernel's compulsory bytes over the f32
        # arena histogram's, at the SAME shape (the ISSUE-8 ≤0.55 gate)
        summary["budget_quantized"] = perf.iteration_budget(
            args.rows, args.features, args.max_bin, args.leaves,
            engine="partition", quantized=True)
        perf.cost_models()          # ensure the ops registries are loaded
        # evaluate at the TPU-scale dispatch (not the interpret-mode
        # timing shape) so the fixed [F, max_bin, 3] output terms don't
        # mask the per-row stream the gate is about
        floor_rows = max(args.rows, 4194304)
        kq = perf.cost("hist/quantized", rows=floor_rows,
                       features=args.features, max_bin=args.max_bin)
        kf = perf.cost("partition/hist", rows=floor_rows,
                       features=args.features, max_bin=args.max_bin)
        summary["quantized_floor"] = {
            "rows": floor_rows,
            "quantized_kernel": kq.kernel,
            "quantized_bytes": int(kq.hbm_bytes),
            "f32_kernel": kf.kernel,
            "f32_bytes": int(kf.hbm_bytes),
            "ratio": round(kq.hbm_bytes / max(kf.hbm_bytes, 1), 4)}
    return summary


def print_report(summary: dict) -> None:
    roof = summary["rooflines"]
    sh = summary["shapes"]
    print("roofline report [backend=%s  rows=%d  features=%d  max_bin=%d  "
          "leaves=%d  chain=%d]"
          % (summary["backend"], sh["rows"], sh["features"], sh["max_bin"],
             sh["num_leaves"], sh["chain"]))
    if roof is None:
        print("roofs: none published for device kind %r — achieved rates "
              "only" % summary["device_kind"])
    else:
        print("roofs: %.0f GB/s HBM, %.0f TFLOP/s bf16 (%s)"
              % (roof["hbm_gbps"], roof["peak_tflops"], roof["source"]))
    hdr = ("%-20s %10s %10s %10s %9s %9s %7s %8s"
           % ("kernel", "MB", "GFLOP", "ms", "GB/s", "GFLOP/s",
              "%HBM", "%FLOP"))
    print(hdr)
    print("-" * len(hdr))
    for r in summary["kernels"]:
        if "skipped" in r:
            print("%-20s skipped: %s" % (r["kernel"], r["skipped"]))
            continue
        shares = ("%6.1f%% %7.2f%%" % (r["hbm_util"] * 100,
                                       r["flop_util"] * 100)
                  if "hbm_util" in r else "%7s %8s" % ("-", "-"))
        print("%-20s %10.2f %10.2f %10.3f %9.2f %9.2f %s"
              % (r["kernel"], r["hbm_bytes"] / 1e6, r["flops"] / 1e9,
                 r["ms"], r["gbps"], r["gflops"], shares))
    b = summary["budget"]
    print()

    def at_roof(nbytes):
        return ("" if roof is None else " -> %.1f ms at the HBM roof"
                % (nbytes / 1e9 / roof["hbm_gbps"] * 1e3))

    print("iteration byte budget [engine=%s]: %.1f MB, %.2f GFLOP floor%s"
          % (b["engine"], b["total_bytes"] / 1e6, b["total_flops"] / 1e9,
             at_roof(b["total_bytes"])))
    for p in b["phases"]:
        print("  %-14s %9.2f MB  %6.1f%%  %s"
              % (p["phase"], p["bytes"] / 1e6, p["share"] * 100,
                 p["note"]))
    bq = summary.get("budget_quantized")
    if bq is not None:
        print()
        print("iteration byte budget [engine=%s, quantized]: %.1f MB "
              "(%.1f%% of f32)%s"
              % (bq["engine"], bq["total_bytes"] / 1e6,
                 bq["total_bytes"] / max(b["total_bytes"], 1) * 100,
                 at_roof(bq["total_bytes"])))
        for p in bq["phases"]:
            print("  %-14s %9.2f MB  %6.1f%%  %s"
                  % (p["phase"], p["bytes"] / 1e6, p["share"] * 100,
                     p["note"]))
    qf = summary.get("quantized_floor")
    if qf is not None:
        print()
        print("quantized histogram byte floor @ %d rows: %s %.1f MB vs "
              "%s %.1f MB -> %.1f%% of the f32 path (gate: <= 55%%)"
              % (qf["rows"], qf["quantized_kernel"],
                 qf["quantized_bytes"] / 1e6, qf["f32_kernel"],
                 qf["f32_bytes"] / 1e6, qf["ratio"] * 100))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Per-kernel roofline table + iteration byte budget")
    ap.add_argument("--rows", type=int, default=0,
                    help="rows per kernel dispatch (default: 4194304 on "
                         "TPU, 4096 in interpret mode)")
    ap.add_argument("--features", type=int, default=28)
    ap.add_argument("--max-bin", type=int, default=255)
    ap.add_argument("--leaves", type=int, default=31)
    ap.add_argument("--chain", type=int, default=0,
                    help="dispatches chained per timing sync "
                         "(default Config.tpu_perf_chain)")
    ap.add_argument("--engine", choices=("partition", "label"),
                    default="partition", help="byte-budget engine model")
    ap.add_argument("--kernels", default="",
                    help="comma-separated kernel-name prefixes to run "
                         "(default: all)")
    ap.add_argument("--json", metavar="PATH",
                    help="also write the summary JSON (perf_gate input)")
    args = ap.parse_args(argv)

    import jax
    from lightgbm_tpu.config import Config
    from lightgbm_tpu.utils.backend import on_tpu
    cfg = Config()
    if args.chain <= 0:
        args.chain = cfg.tpu_perf_chain
    if args.rows <= 0:
        args.rows = 4194304 if on_tpu() else 4096

    summary = run(args)
    print_report(summary)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(summary, f, indent=1, sort_keys=True)
            f.write("\n")
        print("\nsummary written to %s" % args.json)
    if summary["rooflines"] is None:
        print("roofline_report: no published peaks for device kind %r "
              "(obs/perf.DEVICE_PEAKS): no roofline share was computed"
              % summary["device_kind"], file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
