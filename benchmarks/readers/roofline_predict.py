"""Share of the chip's bfloat16 peak the prediction path reaches: the
FLOPs of the signature match at the ensemble's padded shapes
(harness/costs.py) over the published peak (harness/peaks.py), over the
device's busy time in the traced calls.  Bound by FLOPs by design; that
the decision matrix's HBM traffic may be what actually limits it is what
this share is there to show."""
from benchmarks.harness import costs, peaks


def read(run, args):
    ens = run.shape.get("ensemble")
    if run.trace is None or not ens:
        return None
    flops = costs.predict_matmul_flops(
        run.shape["traced_units"] * run.shape["rows"],
        ens["T"], ens["L"], ens["N"])
    floor_s = flops / peaks.peaks_of(run.device_kind)["bf16_flop_per_s"]
    return 100.0 * floor_s / (run.trace.busy_s * run.trace.chips)
