"""Lambdarank objective.

Re-design of src/objective/rank_objective.hpp:19-237 (LambdarankNDCG): the
reference's per-query O(n^2) pairwise OMP loop runs fully on device as
padded size-bucketed query blocks (ops/ranking.py DeviceLambdarank) — a
handful of jitted dispatches per iteration regardless of query count.
The device path never sorts: it sums the pairs in slot order and gets each
document's rank discount from a count over its query, and it moves scores
and gradients between rows and slots by whole query windows of aligned
128-lane rows, not element by element.  The numpy per-query
path (`_one_query`), which sorts as the reference does, is kept as the
parity oracle.

The 1M-entry sigmoid lookup table (rank_objective.hpp:181-194) is replaced
by the exact expression it approximates: GetSigmoid(d) = 2/(1+exp(2*sigmoid*d)).
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from .metric_rank import DCGCalculator
from .objective import ObjectiveFunction
from .utils import log


class LambdarankNDCG(ObjectiveFunction):
    name = "lambdarank"

    def __init__(self, config):
        super().__init__(config)
        self.sigmoid = float(config.sigmoid)
        if self.sigmoid <= 0.0:
            log.fatal("Sigmoid param %f should be greater than zero" % self.sigmoid)
        label_gain = list(config.label_gain)
        self.dcg = DCGCalculator(label_gain)
        # will optimize NDCG@optimize_pos_at_
        self.optimize_pos_at = int(config.max_position)

    def init(self, metadata, num_data):
        self.metadata = metadata
        self.num_data = num_data
        self.label_np = np.asarray(metadata.label, np.float64)
        self.dcg.check_label(self.label_np)
        self.weights_np = (np.asarray(metadata.weights, np.float64)
                           if metadata.weights is not None else None)
        if metadata.query_boundaries is None:
            log.fatal("Lambdarank tasks require query information")
        self.query_boundaries = np.asarray(metadata.query_boundaries, np.int64)
        self.num_queries = len(self.query_boundaries) - 1
        # cache inverse max DCG per query (rank_objective.hpp:55-66)
        self.inverse_max_dcgs = np.zeros(self.num_queries)
        for q in range(self.num_queries):
            a, b = self.query_boundaries[q], self.query_boundaries[q + 1]
            mdcg = self.dcg.cal_maxdcg_at_k(self.optimize_pos_at, self.label_np[a:b])
            self.inverse_max_dcgs[q] = 1.0 / mdcg if mdcg > 0.0 else 0.0
        from .ops.ranking import DeviceLambdarank
        import jax.numpy as jnp
        import jax
        dtype = (jnp.float64 if jax.config.jax_enable_x64 else jnp.float32)
        self._device = DeviceLambdarank(
            self.query_boundaries, self.label_np, self.dcg.label_gain_np,
            self.inverse_max_dcgs, self.sigmoid, dtype=dtype)
        self._weights_dev = (jnp.asarray(self.weights_np, dtype)
                            if self.weights_np is not None else None)
        # the query windows each iteration moves between rows and slots
        self.device_plan = self._device.qb.plan()

    def get_gradients(self, score):
        grad, hess = self._device(score)
        if self._weights_dev is not None:
            grad = grad * self._weights_dev
            hess = hess * self._weights_dev
        return grad, hess

    def get_gradients_host(self, score):
        """Numpy reference path (parity oracle for the device kernels)."""
        score = np.asarray(score, np.float64).reshape(-1)
        grad = np.zeros(self.num_data)
        hess = np.zeros(self.num_data)
        for q in range(self.num_queries):
            a, b = self.query_boundaries[q], self.query_boundaries[q + 1]
            g, h = self._one_query(score[a:b], self.label_np[a:b],
                                   self.inverse_max_dcgs[q])
            grad[a:b] = g
            hess[a:b] = h
        if self.weights_np is not None:
            grad *= self.weights_np
            hess *= self.weights_np
        return grad, hess

    def _one_query(self, score, label, inverse_max_dcg):
        """Vectorized GetGradientsForOneQuery (rank_objective.hpp:80-167).

        Builds the [cnt, cnt] pair matrices in sorted order: entry (i, j)
        is the pair with the rank-i doc as `high` and rank-j doc as `low`;
        only pairs where label[high] > label[low] contribute.
        """
        cnt = len(score)
        if cnt == 0 or inverse_max_dcg == 0.0:
            return np.zeros(cnt), np.zeros(cnt)
        # stable sort by descending score (ties keep original order)
        sorted_idx = np.argsort(-score, kind="stable")
        s = score[sorted_idx]
        lab = label[sorted_idx].astype(np.int64)
        gains = self.dcg.label_gain_np[lab]
        disc = self.dcg.discount(np.arange(cnt))

        best_score, worst_score = s[0], s[-1]
        delta = s[:, None] - s[None, :]                       # high - low
        valid = lab[:, None] > lab[None, :]
        dcg_gap = gains[:, None] - gains[None, :]
        paired_disc = np.abs(disc[:, None] - disc[None, :])
        dndcg = dcg_gap * paired_disc * inverse_max_dcg
        # regularize the delta NDCG by score distance (hpp:139-142)
        if best_score != worst_score:
            dndcg = dndcg / (0.01 + np.abs(delta))
        sig = 2.0 / (1.0 + np.exp(np.clip(2.0 * self.sigmoid * delta, -500, 500)))
        p_lambda = sig * -dndcg * valid
        p_hess = sig * (2.0 - sig) * 2.0 * dndcg * valid

        lam_s = p_lambda.sum(axis=1) - p_lambda.sum(axis=0)   # high gets +, low -
        hes_s = p_hess.sum(axis=1) + p_hess.sum(axis=0)
        lam = np.zeros(cnt)
        hes = np.zeros(cnt)
        lam[sorted_idx] = lam_s
        hes[sorted_idx] = hes_s
        return lam, hes

    def is_constant_hessian(self) -> bool:
        return False

    def need_accurate_prediction(self) -> bool:
        return False

    def to_string(self) -> str:
        return "lambdarank"
