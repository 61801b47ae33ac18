"""Roofline performance observatory: analytic cost models + measurement.

The obs stack could say *when* an iteration was slow, never *why*:
nothing attributed an iteration to individual dispatches in HBM bytes
and FLOPs against the chip's ceilings.  This module is that layer,
following the roofline methodology (Williams et al., "Roofline: An
Insightful Visual Performance Model"): every hot op registers an
ANALYTIC cost model — the minimum HBM bytes it must move and the FLOPs
it executes, derived from shapes/dtypes alone — next to its kernel, and
a measurement harness (chain K dispatches, reduce to a device scalar,
``float()`` once to sync) turns (cost, measured ms) into achieved GB/s
/ GFLOP/s and, where the device's peaks are known (DEVICE_PEAKS), "% of
roof" numbers per kernel.

Three consumers:

- ``tools/roofline_report.py`` drives the hot kernels standalone and
  prints the per-kernel roofline table + the per-iteration byte budget;
- ``TrainingRecorder`` emits a ``roofline`` section per round event and
  ``lgbm_roofline_*`` gauges (achieved GB/s of the boosting iteration
  against the analytic byte floor), plus a bytes/FLOPs-tagged span in
  the Chrome trace;
- ``tools/perf_gate.py`` ingests roofline summaries + BENCH history
  into the committed perf ledger and fails CI on regressions.

Cost models are LOWER BOUNDS by construction (compulsory traffic only:
each operand read once, each result written once — no re-streaming, no
padding waste).  Achieved/analytic utilization above ~1.0 of a roof
therefore indicates a modeling bug, and utilization far below it says
the kernel is latency- or overhead-bound, not bandwidth-bound — exactly
the distinction the byte budget exists to draw.

Everything here is read-only on training state: models train
bitwise-identically with the observatory on or off (the existing obs
guarantee; tests/test_perf.py asserts it again for the roofline path).
"""
from __future__ import annotations

from typing import Callable, Dict, List, NamedTuple, Optional

# chained dispatches per timing sync (tpu_perf_chain default).  Sized
# on an installation that no longer exists (~100 ms per blocking fetch)
# and not re-measured on the directly attached chip, where a fetch is
# ~1 ms (NOTES.md)
DEFAULT_CHAIN = 8
# perf-ledger regression tolerance (tpu_perf_gate_tolerance default);
# tools/perf_gate.py keeps its own copy so it can run without jax
DEFAULT_GATE_TOLERANCE = 0.15


class KernelCost(NamedTuple):
    """Analytic minimum cost of one kernel dispatch."""
    kernel: str          # registry name, e.g. "partition/segment"
    hbm_bytes: int       # compulsory HBM traffic (reads + writes)
    flops: int           # FLOPs executed (one MAC = 2 FLOPs)
    note: str = ""       # modeling assumptions worth showing in a table


class Roofline(NamedTuple):
    """The chip ceilings achieved numbers are compared against."""
    hbm_gbps: float       # HBM bandwidth
    peak_tflops: float    # bf16 matmul peak: what the kernels' MXU
    #                       passes run as (ops/partition_pallas.py)
    int8_tops: float
    source: str


# The one table of chip peaks, keyed by jax's `device_kind`.  Published
# figures, not measurements: measuring the roof is ROADMAP S1.  A kind
# that is not here has NO roof — no utilisation share is computed for it
# and the roofline tools end in an error — never a default.
DEVICE_PEAKS: Dict[str, Roofline] = {
    "TPU v5 lite": Roofline(
        hbm_gbps=819.0, peak_tflops=197.0, int8_tops=393.0,
        source='Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
               'bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s'),
}


def device_roofline() -> Optional[Roofline]:
    """The default device's published peaks, or None when its kind is
    not in DEVICE_PEAKS (every CPU run)."""
    import jax
    return DEVICE_PEAKS.get(jax.devices()[0].device_kind)


# -- cost-model registry ------------------------------------------------- #
# kernel name -> fn(**shape kwargs) -> KernelCost.  Ops modules register
# their models at import next to the kernel they describe, so the model
# and the kernel can be reviewed (and drift) together.
_COST_MODELS: Dict[str, Callable[..., KernelCost]] = {}


def cost_model(name: str):
    """Decorator: register fn as the analytic cost model for `name`."""
    def deco(fn: Callable[..., KernelCost]):
        _COST_MODELS[name] = fn
        return fn
    return deco


def cost(name: str, **shape_kwargs) -> KernelCost:
    """Evaluate the registered model for `name` at concrete shapes."""
    return _COST_MODELS[name](**shape_kwargs)


def cost_models() -> List[str]:
    """Registered kernel names (sorted; import side effect of ops.*)."""
    # importing the ops modules is what populates the registry — pull
    # them in lazily so `import lightgbm_tpu.obs` alone stays light
    from ..ops import (histogram, histogram_pallas, split,  # noqa: F401
                       split_pallas, partition_pallas, grow_partition,
                       predict)
    return sorted(_COST_MODELS)


def achieved(kc: KernelCost, ms: float,
             roof: Optional[Roofline] = None) -> Dict[str, float]:
    """(cost, measured ms) -> achieved GB/s and GFLOP/s, plus the roof
    shares (hbm_util, flop_util) when the device has a roof."""
    s = max(ms, 1e-9) / 1e3
    gbps = kc.hbm_bytes / 1e9 / s
    gflops = kc.flops / 1e9 / s
    row = {
        "ms": round(ms, 4),
        "hbm_bytes": int(kc.hbm_bytes),
        "flops": int(kc.flops),
        "gbps": round(gbps, 3),
        "gflops": round(gflops, 3),
        "arith_intensity": round(kc.flops / max(kc.hbm_bytes, 1), 3),
    }
    if roof is not None:
        row["hbm_util"] = round(gbps / roof.hbm_gbps, 4)
        row["flop_util"] = round(gflops / (roof.peak_tflops * 1e3), 6)
    return row


# -- measurement harness ------------------------------------------------- #
def _probe_scalar(out):
    """Device scalar depending on `out`: the SMALLEST leaf of the pytree
    summed in f32.  Forcing the smallest leaf (a partition kernel's
    counts[2], not its multi-GB arena) keeps the probe's own bandwidth
    out of the measurement while the single device stream still orders
    it after the kernel."""
    import jax
    import jax.numpy as jnp
    leaves = [x for x in jax.tree_util.tree_leaves(out)
              if hasattr(x, "dtype")]
    if not leaves:
        return jnp.float32(0)
    smallest = min(leaves, key=lambda x: getattr(x, "size", 1))
    return jnp.sum(smallest.astype(jnp.float32))


def measure(fn: Callable, args=(), chain: int = DEFAULT_CHAIN,
            warmup: int = 1) -> float:
    """Wall-clock one dispatch of `fn(*args)` in ms.

    Dispatch is async, and one blocking fetch has a cost of its own.
    So: warm up (compile) and sync once; then dispatch `chain` calls
    back-to-back and sync ONCE by reducing the last result to a device
    scalar and ``float()``-ing it — the single device stream guarantees
    every chained call finished first.  Returns amortized ms per call.
    """
    import time
    chain = max(int(chain), 1)
    out = None
    for _ in range(max(int(warmup), 1)):
        out = fn(*args)
    float(_probe_scalar(out))                  # compile + drain warmup
    t0 = time.perf_counter()
    for _ in range(chain):
        out = fn(*args)
    float(_probe_scalar(out))                  # ONE sync for the chain
    return (time.perf_counter() - t0) / chain * 1e3


def measure_kernel(name: str, fn: Callable, args=(),
                   roof: Optional[Roofline] = None,
                   chain: int = DEFAULT_CHAIN,
                   **shape_kwargs) -> Dict[str, float]:
    """measure + cost + achieved in one summary row (the roofline
    report's unit of output)."""
    kc = cost(name, **shape_kwargs)
    ms = measure(fn, args, chain=chain)
    row = {"kernel": name, "note": kc.note}
    row.update(achieved(kc, ms, roof))
    return row


# -- per-iteration byte budget ------------------------------------------- #
def iteration_budget(rows: int, features: int, max_bin: int,
                     num_leaves: int, engine: str = "partition",
                     dtype_bytes: int = 4,
                     quantized: bool = False) -> Dict:
    """Analytic HBM-byte/FLOP floor for ONE boosting iteration.

    A balanced-tree lower bound: the sum of parent-segment sizes over
    the L-1 splits is modeled as n*log2(L) rows (leaf-wise growth on
    skewed data streams fewer — this is the floor the HBM roof is
    multiplied against, not a prediction).  Phases follow the measured
    shape of the loop (NOTES.md per-iteration budget): root histogram,
    per-split partition + smaller-child histogram + split scan, then
    the fixed per-tree work (g/h refresh, carry compaction, score).

    With quantized=True (tpu_quantized_grad, partition engine only) the
    budget models the int8-code mode of docs/Quantized.md: histogram
    kernels read only the feature rows plus TWO code planes (not six
    residue planes), the root histogram is FUSED with the code-plane
    refresh (ops/partition_pallas.fused_refresh_histogram — one arena
    pass pays for both), and gh_refresh writes codes instead of residue
    planes.  Partition and carry-compact phases still move the full
    arena row (rows are relocated whole).

    Returns {"phases": [{phase, bytes, flops, note}...],
             "total_bytes", "total_flops"} — the byte-budget table.
    """
    import math
    n = max(int(rows), 1)
    F = max(int(features), 1)
    B = max(int(max_bin), 2)
    L = max(int(num_leaves), 2)
    depth = max(math.log2(L), 1.0)
    hist_out = F * B * 3 * 4                     # f32 [F, B, 3]
    phases: List[Dict] = []

    def add(phase, nbytes, flops, note=""):
        phases.append({"phase": phase, "bytes": int(nbytes),
                       "flops": int(flops), "note": note})

    if engine == "partition":
        from ..ops import partition_pallas as pp
        row_b = 2 * pp.arena_channels(F)        # bf16 arena row footprint
        Fp = pp.feature_channels(F)
        # quantized histogram kernels DMA only the feature-row stripe
        # plus the two code planes (8-row DMA granularity), never the
        # stale residue planes — the partial-row read of
        # segment_histogram(quantized=True)
        hist_row_b = (2 * min(pp.arena_channels(F), -(-(Fp + 2) // 8) * 8)
                      if quantized else row_b)
        split_rows = n * depth                  # balanced-tree bound
        if quantized:
            # fused root: ONE pass reads the Fp feature rows + the fresh
            # code array and rewrites the 8-row payload group (the code
            # planes cannot be written alone, partition_pallas._PAY_ROWS)
            # while the histogram accumulates — the separate gh_refresh
            # plane write and the full-arena root read both disappear
            add("root_hist",
                n * 2 * (Fp + 2 + 2 * pp._PAY_ROWS) + hist_out,
                2 * n * (3 + F),
                "fused code refresh + root histogram, one pass")
        else:
            # root histogram: one streamed pass over the full arena
            add("root_hist", n * row_b + hist_out, 2 * n * (3 + F),
                "one arena pass")
        # per-split partition: read parent once, write both children
        # (rows relocate WHOLE, so quantization does not shrink this)
        add("partition", 2 * split_rows * row_b,
            2 * split_rows * 2 * pp.SUB,
            "sum(parent) ~ n*log2(L); compaction MACs DMA-overlapped")
        # smaller-child histograms: half the parent rows per split
        add("child_hist", (split_rows / 2) * hist_row_b
            + (L - 1) * hist_out,
            2 * (split_rows / 2) * (3 + F),
            "smaller child only" + (", code-plane stripe" if quantized
                                    else ""))
        # split scans: histogram in, packed split row out
        add("split_scan", L * (hist_out + F * 64),
            L * F * B * 32, "L histogram scans")
        # fixed per-tree: g/h refresh + carry compaction + score
        if quantized:
            add("gh_refresh", n * (2 * dtype_bytes + 2 * 2), 8 * n,
                "grad/hess -> int8 codes (planes ride the fused root)")
        else:
            add("gh_refresh", n * (2 * dtype_bytes + 6 * 2), 8 * n,
                "grad/hess -> residue planes")
        add("carry_compact", 2 * n * row_b, 0, "ping-pong root slot")
    else:
        bins_b = n * F                          # uint8 bin matrix
        gh_b = n * (2 * dtype_bytes + 4)        # g, h, leaf ids
        add("root_hist", bins_b + gh_b + hist_out, 2 * n * F * 3,
            "one masked pass")
        split_rows = n * depth
        add("child_hist", (split_rows / 2) * (F + 2 * dtype_bytes + 4)
            + (L - 1) * hist_out, 2 * (split_rows / 2) * F * 3,
            "compact impl: smaller child rows only")
        add("split_scan", L * (hist_out + F * 64), L * F * B * 32,
            "L histogram scans")
        add("leaf_update", depth * n * 4, depth * n,
            "row->leaf label rewrites")
        add("score_update", n * 2 * dtype_bytes, 2 * n, "score += leaf out")

    total_b = sum(p["bytes"] for p in phases)
    total_f = sum(p["flops"] for p in phases)
    for p in phases:
        p["share"] = round(p["bytes"] / max(total_b, 1), 4)
    return {"engine": engine, "rows": n, "features": F, "max_bin": B,
            "num_leaves": L, "quantized": bool(quantized),
            "phases": phases,
            "total_bytes": int(total_b), "total_flops": int(total_f)}


def budget_summary(budget: Dict, wall_s: float,
                   roof: Optional[Roofline] = None) -> Dict[str, float]:
    """One iteration's budget + measured wall seconds -> the recorder's
    per-round roofline dict (achieved GB/s against the analytic floor;
    hbm_util / flop_util only when the device has a roof)."""
    s = max(float(wall_s), 1e-9)
    gbps = budget["total_bytes"] / 1e9 / s
    gflops = budget["total_flops"] / 1e9 / s
    # 6 decimals: a compile-dominated first round on a CPU backend is
    # micro-GB/s and must not round to an (apparently broken) zero
    out = {
        "analytic_mb": round(budget["total_bytes"] / 1e6, 3),
        "analytic_gflop": round(budget["total_flops"] / 1e9, 3),
        "achieved_gbps": round(gbps, 6),
        "achieved_gflops": round(gflops, 6),
    }
    if roof is not None:
        out["hbm_util"] = round(gbps / roof.hbm_gbps, 6)
        out["flop_util"] = round(gflops / (roof.peak_tflops * 1e3), 9)
    return out


# -- registry publication ------------------------------------------------ #
def publish_iteration_gauges(reg, summary: Dict[str, float]) -> None:
    """Per-round roofline gauges (set, not set_fn: the recorder owns the
    cadence — one update per boosting round)."""
    reg.gauge("lgbm_roofline_achieved_gbps",
              help="Analytic iteration bytes / measured iteration wall "
                   "(GB/s)").set(summary["achieved_gbps"])
    if "hbm_util" in summary:
        reg.gauge("lgbm_roofline_hbm_util",
                  help="Achieved GB/s over the device's published HBM "
                       "roof").set(summary["hbm_util"])
    reg.gauge("lgbm_roofline_iteration_mb",
              help="Analytic HBM-byte floor per boosting iteration "
                   "(MB)").set(summary["analytic_mb"])


def publish_kernel_summaries(reg, rows: List[Dict]) -> None:
    """Per-kernel roofline gauges (tools/roofline_report.py publishes
    these when asked to leave a scrapeable trail)."""
    for r in rows:
        labels = dict(kernel=r["kernel"])
        reg.gauge("lgbm_roofline_kernel_gbps",
                  help="Achieved HBM GB/s per kernel", **labels).set(
            r["gbps"])
        reg.gauge("lgbm_roofline_kernel_gflops",
                  help="Achieved GFLOP/s per kernel", **labels).set(
            r["gflops"])
        if "hbm_util" in r:
            reg.gauge("lgbm_roofline_kernel_hbm_util",
                      help="Per-kernel share of the HBM roof",
                      **labels).set(r["hbm_util"])
