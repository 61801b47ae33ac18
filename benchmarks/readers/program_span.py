"""Host time inside the program's own `lgbm:<span>` annotations
(`lightgbm_tpu.obs.tracing.span`) over the traced slice, in ms per
iteration or call: args {"span": name}, and {"self": true} for that time
less what the `lgbm:` spans nested inside it cover — what the span's own
code costs the host once the calls it names are taken out.

Read off the profiler's host plane, so on the device's clock.  Nothing
without a reduced device trace (off the chip a time is not a
measurement), or where the host plane holds no `lgbm:` span (a program
from before they existed)."""
from benchmarks.harness import xplane_names
from benchmarks.harness.trace_reduce import _self_times


def read(run, args):
    if run.trace is None:
        return None
    path = xplane_names.trace_of(run)
    threads = xplane_names.program_spans(path) if path else []
    if not threads:
        return None
    ns = 0
    for spans in threads:
        for start, end, name, self_ns in _self_times(spans):
            if name == args["span"]:
                ns += self_ns if args.get("self") else end - start
    return ns / 1e6 / run.shape["traced_units"]
