"""Device-side ranking ops: padded per-query segment batching.

The reference computes lambdarank gradients and NDCG with per-query host
loops (rank_objective.hpp:80-167 GetGradientsForOneQuery, rank_metric.hpp
NDCGMetric::Eval).  On TPU a per-query Python loop costs a host dispatch
per query, so queries are grouped by size class into padded [Q, S] blocks
(bucketed by the next power-of-two size) and each block runs as one
jitted function.  The lambda sums run over the slots in their own order:
a sum over all pairs of a query does not care where its documents lie, and
the one thing that needs the order, a document's rank under the stable
descending sort, is a count over its query (the documents with a higher
score, plus the equal ones in earlier slots).  So there is no sort and no
permutation, only dense [chunk, S, S] compare, select and sum stages with
masked padding: on the chip a per-element indexed move costs 7-10 ns, a
vector operation on the same element a thousandth of that (PERF.md §7).
NDCG still sorts: it runs once per evaluation, not per iteration.

All statics (index maps, sorted label gains, inverse max DCG) are
computed once at init; only scores stream through per iteration.
"""
from __future__ import annotations

from functools import partial
from typing import List, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

_BUCKET_MIN = 8
# pair stages are [chunk, S, S]; a chunk holds at most 2^23 pairs.
# Measured on the v5e at the MSLR shape (18 900 queries of 120 docs ->
# S = 128, float32, one call of _lambda_bucket; PERF.md §6, PR 35):
# chunk 64 = 6.62 ms, 128 = 6.64, 256 = 6.47, **512 = 5.87**, 1024 = 6.02,
# 2048 = 6.08, 4736 = 5.86.  The stages are fused temporaries, so the
# chunk moves the call by a tenth and the peak not at all; what the loop
# buys is the whole bucket not being one [Q, S, S] fusion (17.1 ms).
_CHUNK_BUDGET = 1 << 23


def _bucket_size(sz: int) -> int:
    b = _BUCKET_MIN
    while b < sz:
        b *= 2
    return b


class QueryBuckets:
    """Static padded layout of queries grouped by size class.

    For each bucket: `idx` [Q, S] int32 row indices into the data arrays
    (padding = n, a sentinel one past the end), plus the query ids [Q]
    for per-query scalars.
    """

    def __init__(self, query_boundaries: np.ndarray, num_data: int):
        qb = np.asarray(query_boundaries, np.int64)
        sizes = np.diff(qb)
        self.num_data = int(num_data)
        self.num_queries = len(sizes)
        by_bucket = {}
        for q, sz in enumerate(sizes):
            if sz <= 0:
                continue
            by_bucket.setdefault(_bucket_size(int(sz)), []).append(q)
        self.buckets = []           # list of (idx [Q,S] i32, qids [Q] i32)
        for S in sorted(by_bucket):
            qids = np.asarray(by_bucket[S], np.int32)
            idx = np.full((len(qids), S), self.num_data, np.int64)
            for r, q in enumerate(qids):
                a, b = qb[q], qb[q + 1]
                idx[r, :b - a] = np.arange(a, b)
            self.buckets.append((idx.astype(np.int32), qids))


def _chunk(Q: int, S: int) -> int:
    c = max(1, _CHUNK_BUDGET // max(S * S, 1))
    return int(min(c, Q))


def _slot_rank(neg):
    """Each slot's position under the stable descending sort of its row,
    by counting: the slots with a higher value, plus the equal ones in
    earlier slots.  neg: [Q, S], padding at -inf (it ranks last, in slot
    order); -0.0 == 0.0, as the sort has it.  Exactly
    argsort(argsort(-neg, stable=True)), with no sort and no gather.

    The counted slot lies on the last axis and the sum runs over the one
    before it, so that a row's counts are adds of whole vector registers
    and not a reduction across lanes."""
    slot = jnp.arange(neg.shape[1], dtype=jnp.int32)
    other, own = neg[:, :, None], neg[:, None, :]
    ahead = (other > own) | ((other == own) & (slot[:, None] < slot[None, :]))
    return jnp.sum(ahead, axis=1, dtype=jnp.int32)


@partial(jax.jit, static_argnames=("chunk",))
def _lambda_bucket(score_pad, lab, gains, real, inv_mdcg, disc, sigmoid,
                   *, chunk: int):
    """Lambdarank sums for one padded bucket.

    score_pad/lab/gains/real: [Q, S]; inv_mdcg: [Q]; disc: [S].
    Returns (lam, hes) [Q, S] in slot order: nothing here is sorted,
    gathered or scattered (tests/test_ranking_device.py holds it to that).
    """
    Q, S = score_pad.shape
    pad_q = (-Q) % chunk
    if pad_q:
        def p2(a):
            return jnp.pad(a, ((0, pad_q), (0, 0)))
        score_pad, lab, gains = p2(score_pad), p2(lab), p2(gains)
        real = jnp.pad(real, ((0, pad_q), (0, 0)))
        inv_mdcg = jnp.pad(inv_mdcg, (0, pad_q))
    nc = score_pad.shape[0] // chunk

    def shape(a):
        return a.reshape((nc, chunk) + a.shape[1:])

    slot = jnp.arange(S, dtype=jnp.int32)

    def one(args):
        s, l, g, r, inv = args
        neg = jnp.where(r, s, -jnp.inf)
        rank = _slot_rank(neg)
        # disc[rank] as a select-and-sum over the table (exact: one term
        # is nonzero), summed like the rank: not a gather
        d = jnp.sum(jnp.where(rank[:, None, :] == slot[:, None],
                              disc[:, None], 0.0), axis=1)
        best = jnp.max(neg, axis=1)
        worst = jnp.min(jnp.where(r, s, jnp.inf), axis=1)
        delta = s[:, :, None] - s[:, None, :]
        valid = (l[:, :, None] > l[:, None, :]) \
            & r[:, :, None] & r[:, None, :]
        dcg_gap = g[:, :, None] - g[:, None, :]
        paired = jnp.abs(d[:, :, None] - d[:, None, :])
        dndcg = dcg_gap * paired * inv[:, None, None]
        # regularize by score distance when scores differ (hpp:139-142)
        norm = (best != worst)[:, None, None]
        dndcg = jnp.where(norm, dndcg / (0.01 + jnp.abs(delta)), dndcg)
        sig = 2.0 / (1.0 + jnp.exp(
            jnp.clip(2.0 * sigmoid * delta, -80.0, 80.0)))
        p_lambda = jnp.where(valid, sig * -dndcg, 0.0)
        p_hess = jnp.where(valid, sig * (2.0 - sig) * 2.0 * dndcg, 0.0)
        lam = p_lambda.sum(axis=2) - p_lambda.sum(axis=1)
        hes = p_hess.sum(axis=2) + p_hess.sum(axis=1)
        return lam, hes

    with jax.named_scope("lgbm.gradient.pairs"):
        lam, hes = jax.lax.map(one, (shape(score_pad), shape(lab),
                                     shape(gains), shape(real),
                                     shape(inv_mdcg)))
        lam = lam.reshape(-1, S)[:Q]
        hes = hes.reshape(-1, S)[:Q]
    return lam, hes


class DeviceLambdarank:
    """Per-iteration lambdarank gradients fully on device."""

    def __init__(self, query_boundaries, labels, label_gain,
                 inverse_max_dcgs, sigmoid: float, dtype=jnp.float32):
        labels = np.asarray(labels)
        n = len(labels)
        self.n = n
        self.dtype = dtype
        self.sigmoid = float(sigmoid)
        self.qb = QueryBuckets(query_boundaries, n)
        gain_tab = np.asarray(label_gain, np.float64)
        inv = np.asarray(inverse_max_dcgs, np.float64)
        self._buckets = []
        for idx, qids in self.qb.buckets:
            lab_pad = np.full(idx.shape, -1, np.int32)
            real = idx < n
            lab_pad[real] = labels[idx[real]].astype(np.int32)
            self._buckets.append(dict(
                idx=jnp.asarray(idx),
                lab=jnp.asarray(lab_pad.astype(np.float64), dtype),
                gains=jnp.asarray(
                    np.where(real, gain_tab[np.clip(lab_pad, 0, None)], 0.0),
                    dtype),
                real=jnp.asarray(real),
                inv=jnp.asarray(inv[qids], dtype),
                disc=jnp.asarray(
                    1.0 / np.log2(2.0 + np.arange(idx.shape[1])), dtype),
                chunk=_chunk(*idx.shape)))

    def __call__(self, score) -> tuple:
        score = jnp.asarray(score, self.dtype).reshape(-1)
        ext = jnp.concatenate(
            [score, jnp.asarray([-jnp.inf], self.dtype)])
        grad = jnp.zeros(self.n + 1, self.dtype)
        hess = jnp.zeros(self.n + 1, self.dtype)
        for b in self._buckets:
            with jax.named_scope("lgbm.gradient.scatter"):
                sp = ext[b["idx"]]
            lam, hes = _lambda_bucket(sp, b["lab"], b["gains"], b["real"],
                                      b["inv"], b["disc"],
                                      jnp.asarray(self.sigmoid, self.dtype),
                                      chunk=b["chunk"])
            with jax.named_scope("lgbm.gradient.scatter"):
                flat = jnp.where(b["real"], b["idx"], self.n).reshape(-1)
                grad = grad.at[flat].add(lam.reshape(-1), mode="drop")
                hess = hess.at[flat].add(hes.reshape(-1), mode="drop")
        return grad[:self.n], hess[:self.n]


@partial(jax.jit, static_argnames=("ks",))
def _ndcg_bucket(score_pad, gains, real, inv_mdcg_k, wq, disc, *, ks: tuple):
    """Weighted NDCG sums at each k for one bucket -> [len(ks)]."""
    neg = jnp.where(real, score_pad, -jnp.inf)
    order = jnp.argsort(-neg, axis=1, stable=True)
    g = jnp.take_along_axis(gains, order, axis=1)          # [Q, S]
    S = score_pad.shape[1]
    pos = jnp.arange(S)
    out = []
    for j, k in enumerate(ks):
        dcg = jnp.sum(g * disc * (pos < k)[None, :], axis=1)    # [Q]
        # all-negative queries (inv <= 0) count as NDCG = 1
        ndcg = jnp.where(inv_mdcg_k[:, j] > 0.0,
                         dcg * inv_mdcg_k[:, j], 1.0)
        out.append(jnp.sum(ndcg * wq))
    return jnp.stack(out)


class DeviceNDCG:
    """Vectorized NDCG@k over all queries (rank_metric.hpp:15-171)."""

    def __init__(self, query_boundaries, labels, label_gain, eval_at,
                 inverse_max_dcgs, query_weights=None):
        labels = np.asarray(labels)
        n = len(labels)
        self.n = n
        self.ks = tuple(int(k) for k in eval_at)
        self.qb = QueryBuckets(query_boundaries, n)
        # zero-row queries are in no bucket but still count as NDCG = 1
        # (maxDCG <= 0 rule, rank_metric.hpp NDCGMetric::Eval)
        sizes = np.diff(np.asarray(query_boundaries, np.int64))
        gain_tab = np.asarray(label_gain, np.float64)
        inv = np.asarray(inverse_max_dcgs, np.float64)   # [num_q, K]
        qw = (np.asarray(query_weights, np.float64)
              if query_weights is not None
              else np.ones(self.qb.num_queries))
        self.sum_weights = float(qw.sum())
        self.base = float(qw[sizes <= 0].sum())
        self._buckets = []
        for idx, qids in self.qb.buckets:
            real = idx < n
            lab_pad = np.where(real, np.clip(labels, 0, None)[
                np.clip(idx, 0, n - 1)].astype(np.int64), 0)
            self._buckets.append(dict(
                idx=jnp.asarray(idx),
                gains=jnp.asarray(np.where(real, gain_tab[lab_pad], 0.0)),
                real=jnp.asarray(real),
                inv=jnp.asarray(inv[qids]),
                wq=jnp.asarray(qw[qids]),
                disc=jnp.asarray(
                    1.0 / np.log2(2.0 + np.arange(idx.shape[1])))))

    def __call__(self, score) -> List[float]:
        score = jnp.asarray(score, jnp.float64
                            if jax.config.jax_enable_x64 else jnp.float32)
        ext = jnp.concatenate([score.reshape(-1),
                               jnp.asarray([-jnp.inf], score.dtype)])
        total = jnp.zeros(len(self.ks), jnp.float64
                          if jax.config.jax_enable_x64 else jnp.float32)
        for b in self._buckets:
            total = total + _ndcg_bucket(
                ext[b["idx"]].astype(total.dtype), b["gains"].astype(total.dtype),
                b["real"], b["inv"].astype(total.dtype),
                b["wq"].astype(total.dtype), b["disc"].astype(total.dtype),
                ks=self.ks)
        return [(float(x) + self.base) / self.sum_weights
                for x in np.asarray(total)]
