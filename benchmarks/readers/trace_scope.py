"""Device time of the XLA operations the program put under a
`jax.named_scope("lgbm.<purpose>")`, in ms per iteration or call:
args {"scopes": regex over scope names} or {"none": true} for the
operations under no `lgbm.` scope at all.

An operation's scope is the last `lgbm.`-prefixed component of its HLO
`op_name` path (harness/xplane_names.py reads the paths; the reduced trace
holds the self times).  Pallas kernels are left out: the `kernel.*`
families count them.  So over the scopes the program uses, plus `none`,
these sums are `xla.other_ms_per_iter` taken apart by purpose.

Nothing without a reduced device trace, or where the trace names no path
at all; 0.0 where paths were read and none matched, so that a cell is not
refused over a scope its path does not run."""
import re

from benchmarks.harness import xplane_names


def read(run, args):
    if run.trace is None:
        return None
    path = xplane_names.trace_of(run)
    paths = xplane_names.label_paths(path) if path else {}
    if not any(paths.values()):
        return None
    unscoped = bool(args.get("none"))
    wanted = re.compile(args.get("scopes", ""))
    seconds = 0.0
    for label, (self_s, _calls) in run.trace.ops.items():
        if label.endswith(" mosaic"):
            continue
        scope = xplane_names.scope_of(paths.get(label, ""))
        if (scope is None) if unscoped else (
                scope is not None and wanted.search(scope) is not None):
            seconds += self_s
    return seconds / run.trace.chips / run.shape["traced_units"] * 1e3
