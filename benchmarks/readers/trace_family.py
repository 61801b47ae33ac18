"""Device time of a family of operations in the traced slice, in ms per
iteration or call: args {"pattern": regex over operation names,
"invert": true for everything that does not match}.  Self time, so an
operation that encloses others (a `while`) counts only its own."""


def read(run, args):
    if run.trace is None:
        return None
    seconds, calls = run.trace.family(args["pattern"],
                                      args.get("invert", False))
    if not calls:
        return None
    return seconds / run.trace.chips / run.shape["traced_units"] * 1e3
