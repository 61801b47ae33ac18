"""One number of the traced slice as a whole: args {"what": ...}.

    idle_share          % of the slice in which no operation ran on the chip
    gap_ms_per_unit     device idle time per iteration or call, ms
    busy_ms_per_unit    device busy time per iteration or call, ms
    busy_ms_per_mrow    device busy time per million rows scored, ms
    programs_per_unit   top-level device programs launched per unit
"""


def read(run, args):
    t = run.trace
    if t is None:
        return None
    units = run.shape["traced_units"]
    what = args["what"]
    if what == "idle_share":
        return 100.0 * (1.0 - t.busy_s / t.window_s)
    if what == "gap_ms_per_unit":
        return (t.window_s - t.busy_s) / units * 1e3
    if what == "busy_ms_per_unit":
        return t.busy_s / units * 1e3
    if what == "busy_ms_per_mrow":
        return t.busy_s / (units * run.shape["rows"] / 1e6) * 1e3
    if what == "programs_per_unit":
        return t.programs / t.chips / units
    raise ValueError("trace_window reads no %r" % what)
