"""The comparison that decides `correct` for what a validation set adds:
the series `eval_valid()` returned on the timed path, and the validation
scores the booster holds after it, against the plain reference.

The reference is reference/walker.py walking the *raw* validation
features (not bins) through the saved model text, tree by tree, in
float64, and reference/metrics.py's float64 AUC of the accumulated raw
score after every iteration.  Nothing of lightgbm_tpu is imported: the
system's binning of the validation rows, its leaf lookup, its score
accumulation and its metric are all on the other side of the comparison.
"""
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from benchmarks.reference import metrics, walker

_ROWS = 1 << 16           # rows per walked block
_THREADS = 8              # after the window only, as in harness/rand.py


def reference_series(text, X, y):
    """(float64 AUC after each iteration of the model text, the raw score
    of every row after the last).  One tree per iteration."""
    header, trees = walker.parse_model(text)
    if int(header.get("num_tree_per_iteration", 1)) != 1:
        raise ValueError("one tree per iteration only")
    X = np.asarray(X, np.float64)
    raw = np.zeros(len(X))
    blocks = [slice(lo, lo + _ROWS) for lo in range(0, len(X), _ROWS)]
    series = []
    with ThreadPoolExecutor(_THREADS) as pool:
        for tree in trees:
            def add(block, tree=tree):
                raw[block] += walker._walk(tree, X[block])
            list(pool.map(add, blocks))
            series.append(metrics.auc(y, raw))
    return series, raw


def series_problems(returned, reference, atol):
    """`returned[i]`, what eval_valid() gave after iteration i, against
    the reference's value there: every iteration, each within `atol`."""
    if len(returned) != len(reference):
        return ["eval_valid() returned %d values for %d iterations"
                % (len(returned), len(reference))]
    got, ref = np.asarray(returned, np.float64), np.asarray(reference)
    if not np.isfinite(got).all():
        return ["eval_valid() returned a value that is not finite at "
                "iteration %d" % int(np.flatnonzero(~np.isfinite(got))[0])]
    off = np.abs(got - ref)
    if off.max() <= atol:
        return []
    worst = int(off.argmax())
    return ["eval_valid() differs from the reference's float64 AUC at %d of "
            "%d iterations, first at %d, most at %d: %.9f against %.9f "
            "(allowed %g)" % (int((off > atol).sum()), len(ref),
                              int(np.flatnonzero(off > atol)[0]), worst,
                              got[worst], ref[worst], atol)]


def score_problems(held, raw, atol):
    """The validation raw scores the booster holds after the last
    iteration against the walker's: AUC sees neither a constant missing
    from every row nor one factor on every leaf value, this does."""
    off = float(np.max(np.abs(np.asarray(held, np.float64) - raw)))
    return off, [] if off <= atol else [
        "the validation scores the booster holds differ from the plain "
        "walker's raw scores by up to %g (allowed %g)" % (off, atol)]


def against_reference(bench, booster, name, X, y, returned):
    c = bench.cell.config["correct"]["valid_series"]
    reference, raw = reference_series(booster.model_to_string(), X, y)
    problems = series_problems(returned, reference, c["auc_atol"])
    off, wrong = score_problems(booster._gbdt.raw_scores(name), raw,
                                c["score_atol"])
    got = np.asarray(returned, np.float64)
    worst = (float(np.max(np.abs(got - reference)))
             if len(got) == len(reference) else None)
    bench.hold("valid_auc_diff", worst, c["auc_atol"])
    bench.hold("valid_score_diff", off, c["score_atol"])
    bench.say("valid-check", rows=len(y), iterations=len(reference),
              auc_first=reference[0], auc_last=reference[-1],
              returned_last=returned[-1] if len(returned) else None,
              max_abs_auc_diff=worst, max_abs_score_diff=off)
    return problems + wrong
