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

Rows and slots meet by whole query windows.  A query's rows are contiguous
in row order, [start, start + count), count <= S.  Viewed as [R, 128]
lane-dense rows, the vector holds that window in the W = ceil((S + 127) /
128) aligned rows from start // 128 on, shifted by start % 128 lanes.  So
the scores reach the slots by a gather of W whole rows a query and a shift
by the query's lane offset (seven selects between two static slices, one
a bit of the offset); lambdas and hessians go back by the inverse shift of
zero-tailed windows and a scatter-add of whole rows, where neighbouring
queries add only exact zeros into each other's rows.  No element moves by
an index on the per-iteration path: each index moves a row of 128 (at the
MSLR shape 37 800 rows each way in place of 2.42 M elements; PERF.md §6).

All statics (window rows and shifts, label gains, inverse max DCG) are
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
# rows and slots meet in whole rows of this many lanes
LANES = 128


def _bucket_size(sz: int) -> int:
    b = _BUCKET_MIN
    while b < sz:
        b *= 2
    return b


def _window_rows(S: int) -> int:
    """Aligned rows of LANES that hold S slots starting at any lane."""
    return (S + 2 * LANES - 2) // LANES


class QueryBuckets:
    """Static padded layout of queries grouped by size class.

    For each bucket, in `buckets`: `idx` [Q, S] int32 row indices into the
    data arrays (padding = n, a sentinel one past the end), plus the query
    ids [Q] for per-query scalars.  In `windows`, the same queries as
    windows of the [num_rows, LANES] view of the data: `rows` [Q, W] int32,
    the aligned rows from start // LANES on that hold each query's rows
    (W = _window_rows(S)); `shift` [Q] int32, start % LANES, the lane at
    which the query's first row lies in the first of them.
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
        self.windows = []           # list of (rows [Q,W] i32, shift [Q] i32)
        self.num_rows = -(-self.num_data // LANES)
        for S in sorted(by_bucket):
            qids = np.asarray(by_bucket[S], np.int32)
            idx = np.full((len(qids), S), self.num_data, np.int64)
            for r, q in enumerate(qids):
                a, b = qb[q], qb[q + 1]
                idx[r, :b - a] = np.arange(a, b)
            self.buckets.append((idx.astype(np.int32), qids))
            start = qb[qids]
            rows = (start // LANES)[:, None] + np.arange(_window_rows(S))
            self.windows.append((rows.astype(np.int32),
                                 (start % LANES).astype(np.int32)))
            self.num_rows = max(self.num_rows, int(rows[:, -1].max()) + 1)

    def plan(self) -> dict:
        """What one pass between rows and slots moves, for the trace:
        per bucket `S:Q:W` (a trace argument holds no comma), the windows,
        the aligned rows moved each way and the real rows."""
        return dict(
            rank_buckets=" ".join("%d:%d:%d" % (idx.shape[1], idx.shape[0],
                                                rows.shape[1])
                                  for (idx, _), (rows, _s) in
                                  zip(self.buckets, self.windows)),
            rank_windows=sum(len(q) for _, q in self.buckets),
            rank_rows_moved=sum(rows.size for rows, _ in self.windows),
            rank_rows=sum(int((idx < self.num_data).sum())
                          for idx, _ in self.buckets))


_SHIFT_BITS = LANES.bit_length() - 1


def _bit(shift, k):
    return ((shift >> k) & 1)[:, None] == 1


def _shift_left(x, shift, S: int):
    """x [Q, >= S + LANES - 1] -> [Q, S], out[q, j] = x[q, j + shift[q]],
    shift in [0, LANES).  The high bit first: each stage selects between
    two static slices, and the lanes still needed narrow by its bit."""
    for k in reversed(range(_SHIFT_BITS)):
        w = S + (1 << k) - 1
        x = jnp.where(_bit(shift, k), x[:, 1 << k:(1 << k) + w], x[:, :w])
    return x


def _shift_right(x, shift):
    """x [..., Q, S] -> [..., Q, S + LANES - 1], out[q, j] = x[q, j -
    shift[q]] and +0.0 outside it: _shift_left's inverse, the low bit
    first, each stage a select between the two zero-padded copies."""
    lead = [(0, 0)] * (x.ndim - 1)
    for k in range(_SHIFT_BITS):
        x = jnp.where(_bit(shift, k), jnp.pad(x, lead + [(1 << k, 0)]),
                      jnp.pad(x, lead + [(0, 1 << k)]))
    return x


@jax.jit
def _to_slots(rows, win_rows, shift, real):
    """Scores [Q, S] in slot order from the [R, LANES] view of the rows:
    exactly ext[idx], -inf in every padded slot."""
    Q, S = real.shape
    win = rows[win_rows].reshape(Q, -1)
    return jnp.where(real, _shift_left(win, shift, S), -jnp.inf)


@jax.jit
def _add_rows(moved, lam, hes, win_rows, shift, real):
    """moved [2, R, LANES] plus lam and hes [Q, S] at their rows: the
    windows zero-tailed, shifted onto their aligned rows and added row by
    row.  Each element receives its one value and exact zeros."""
    Q, W = win_rows.shape
    upd = _shift_right(jnp.where(real, jnp.stack([lam, hes]), 0.0), shift)
    upd = jnp.pad(upd, ((0, 0), (0, 0), (0, W * LANES - upd.shape[-1])))
    return moved.at[:, win_rows.reshape(-1)].add(
        upd.reshape(2, Q * W, LANES))


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
        for (idx, qids), (rows, shift) in zip(self.qb.buckets,
                                              self.qb.windows):
            lab_pad = np.full(idx.shape, -1, np.int32)
            real = idx < n
            lab_pad[real] = labels[idx[real]].astype(np.int32)
            self._buckets.append(dict(
                rows=jnp.asarray(rows),
                shift=jnp.asarray(shift),
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
        R = self.qb.num_rows
        with jax.named_scope("lgbm.gradient.scatter"):
            rows = jnp.pad(score, (0, R * LANES - self.n)).reshape(R, LANES)
            moved = jnp.zeros((2, R, LANES), self.dtype)
        for b in self._buckets:
            with jax.named_scope("lgbm.gradient.scatter"):
                sp = _to_slots(rows, b["rows"], b["shift"], b["real"])
            lam, hes = _lambda_bucket(sp, b["lab"], b["gains"], b["real"],
                                      b["inv"], b["disc"],
                                      jnp.asarray(self.sigmoid, self.dtype),
                                      chunk=b["chunk"])
            with jax.named_scope("lgbm.gradient.scatter"):
                moved = _add_rows(moved, lam, hes, b["rows"], b["shift"],
                                  b["real"])
        with jax.named_scope("lgbm.gradient.scatter"):
            grad, hess = moved.reshape(2, -1)[:, :self.n]
        return grad, hess


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
