"""Metrics evaluated on the device over a data set's (score, label,
weight): what metric.py's AUCMetric and BinaryLoglossMetric hand
`GBDT._eval_state`, so that `eval_valid()` brings a few numbers per metric
to the host and not the score vector.  Each returns the metric's SUMS; the
metric's class finishes them on the host in float64.

Every program sits under the scope `lgbm.valid.metric` (docs/Tracing.md).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_MAX_LOSS = -math.log(1e-15)      # the host code clips p to [1e-15, 1 - 1e-15]


def _sort_by_score(score, *payload):
    """The payload in ascending order of score ([n], or the booster's
    [1, n]), and where a row's score differs from the next row's."""
    s, *rest = jax.lax.sort((score.reshape(-1),) + payload, num_keys=1)
    return s[1:] != s[:-1], rest


def _run_span(differs, before, after):
    """Per row the `before` of the first row of its run of equal scores
    and the `after` of the last (both nondecreasing along the rows): the
    span of ranks the run shares, whose middle is every member's rank
    (binary_metric.hpp AUCMetric: equal scores share a rank).  Running
    maxima and minima, no gather."""
    first = jnp.concatenate([jnp.ones(1, bool), differs])
    last = jnp.concatenate([differs, jnp.ones(1, bool)])
    lo = jax.lax.cummax(jnp.where(first, before, before[0]))
    hi = jax.lax.cummin(jnp.where(last, after, after[-1]), reverse=True)
    return lo, hi


@jax.jit
def auc_counts(score, pos):
    """uint32 [5], rows without weights: the four 8-bit limbs of the sum
    over the positives of (first + one past last) of their run, which is
    twice their tie-averaged rank sum, and the number of positives.  All
    integers, so the AUC finished from them is the exact AUC of the
    scores; a limb's sum fits while rows <= 2^24."""
    with jax.named_scope("lgbm.valid.metric"):
        differs, (p,) = _sort_by_score(score, pos.astype(jnp.uint32))
        idx = jnp.arange(score.size, dtype=jnp.int32)
        lo, hi = _run_span(differs, idx, idx + 1)
        twice = (lo + hi).astype(jnp.uint32) * p
        limbs = [((twice >> (8 * j)) & 255).sum(dtype=jnp.uint32)
                 for j in range(4)]
        return jnp.stack(limbs + [p.sum(dtype=jnp.uint32)])


@jax.jit
def auc_weighted(score, pos, w):
    """[3] in the score's float type (float32 at least): the positives'
    weighted tie-averaged rank sum, the positives' weight, all weight.
    `w` None counts every row once (a set too long for `auc_counts`)."""
    with jax.named_scope("lgbm.valid.metric"):
        acc = jnp.promote_types(score.dtype, jnp.float32)
        w = jnp.ones(score.size, acc) if w is None else w.astype(acc)
        differs, (p, ws) = _sort_by_score(score, pos, w)
        upto = jnp.cumsum(ws)
        lo, hi = _run_span(differs, upto - ws, upto)
        wp = jnp.where(p, ws, 0)
        return jnp.stack([((lo + hi) / 2 * wp).sum(), wp.sum(), ws.sum()])


@jax.jit
def logloss_sum(score, pos, w, sigmoid):
    """Scalar: the (weighted) sum of the rows' binary log loss, from the
    raw score: log(1 + exp(-+ sigmoid * score)), so the probability's
    float32 rounding near 0 and 1 never enters."""
    with jax.named_scope("lgbm.valid.metric"):
        acc = jnp.promote_types(score.dtype, jnp.float32)
        z = score.reshape(-1).astype(acc) * sigmoid
        loss = jnp.minimum(jnp.logaddexp(0.0, jnp.where(pos, -z, z)),
                           _MAX_LOSS)
        return (loss if w is None else loss * w).sum()
