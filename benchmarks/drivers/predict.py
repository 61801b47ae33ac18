"""Traffic of kind `predict`: `Booster.predict(batch)` back to back, one
caller, closed loop, on host float32 batches cycled from a seeded pool.
Input upload and the returned host array are inside every call, as
`task=predict` pays them.  The model is loaded from model text, as a
scoring job loads it; the text is drawn from `--seed` at the mix's stated
shape (harness/synth_model.py).
"""
import time

import numpy as np

from benchmarks.harness import synth_model
from benchmarks.reference import walker


def run(bench):
    import lightgbm_tpu as lgb
    cell = bench.cell
    traffic, data = cell.traffic, cell.config["data"]
    shape = traffic["model"]
    gen = cell.generator()
    problems = []

    with bench.phase("data"):
        pool = gen.features(data["args"], "pool", traffic["pool_rows"])
        batch_rows = traffic["batch_rows"]
        batches = [pool[i:i + batch_rows]
                   for i in range(0, len(pool) - batch_rows + 1, batch_rows)]
    with bench.phase("model"):
        rng = np.random.default_rng([bench.seed, 0x70726564])
        arrays = synth_model.draw_trees(
            rng, shape["trees"], shape["leaves"],
            synth_model.bin_edges(pool[:shape["edge_sample_rows"]],
                                  shape["bins"]),
            shape["leaf_scale"])
        text = synth_model.model_text(arrays, pool.shape[1], len(pool))
    with bench.phase("booster"):
        booster = lgb.Booster(model_str=text)
    with bench.phase("compile"):
        first = booster.predict(batches[0])       # one shape: one warm-up
    with bench.phase("check"):
        rows = traffic["walker_rows"]
        diff = float(np.max(np.abs(
            first[:rows] - walker.predict(text, batches[0][:rows]))))
        bench.hold("walker_diff", diff, traffic["walker_atol"])
        if not diff <= traffic["walker_atol"]:
            problems.append(
                "Booster.predict differs from the plain walker on the model "
                "text by %g (allowed %g)" % (diff, traffic["walker_atol"]))
    cached = getattr(booster._gbdt, "_dev_ens_cache", None)
    ensemble = cached[1] if cached else None
    served = "DeviceEnsemble" if ensemble is not None else "host walk"
    if served != traffic["expect"]["server"]:
        problems.append("path: predict was served by the %s, the cell states "
                        "%s" % (served, traffic["expect"]["server"]))
    bench.say("setup", trees=shape["trees"], leaves=shape["leaves"],
              served_by=served, walker_max_abs_diff=diff,
              batches=len(batches), batch_rows=batch_rows)

    calls, failed, call_ms = 0, 0, []
    t0 = bench.open_window()
    while time.perf_counter() - t0 < bench.seconds:
        tc = time.perf_counter()
        with bench.span("predict"):
            out = booster.predict(batches[calls % len(batches)])
        call_ms.append((time.perf_counter() - tc) * 1e3)
        failed += int(out.shape != (batch_rows,)
                      or not np.isfinite(out).all())
        calls += 1
    window_s = bench.close_window()
    bench.say("window", calls=calls, window_s=window_s, call_ms=call_ms)

    if bench.trace:
        with bench.traced():
            for i in range(traffic["trace_calls"]):
                with bench.span("predict"):
                    booster.predict(batches[i % len(batches)])

    if failed:
        problems.append("%d of %d calls returned a wrong shape or a "
                        "non-finite value" % (failed, calls))
    return {
        "attempted": calls, "failed": failed, "problems": problems,
        "end_to_end": {
            "predict_mrows_per_s": calls * batch_rows / window_s / 1e6},
        "shape": {"rows": batch_rows, "features": int(pool.shape[1]),
                  "units": calls, "traced_units": traffic["trace_calls"],
                  "ensemble": None if ensemble is None else
                  {"T": ensemble.T, "N": ensemble.N, "L": ensemble.L}},
    }
