"""Random forest mode (src/boosting/rf.hpp:18-209)."""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp

from ..ops import grow as grow_ops
from ..utils import log
from .gbdt import GBDT, K_EPSILON
from .tree import Tree


class RF(GBDT):
    """Bagged trees with no shrinkage and averaged output: gradients are
    always computed against the constant boost-from-average score, and the
    train/valid scores hold the running average of tree outputs."""

    def __init__(self, config, train_set, objective, metrics=()):
        if not (config.bagging_freq > 0 and 0.0 < config.bagging_fraction < 1.0):
            log.fatal("Random forest mode requires bagging "
                      "(bagging_freq > 0 and bagging_fraction in (0, 1))")
        super().__init__(config, train_set, objective, metrics)
        self.average_output = True
        self.shrinkage_rate = 1.0
        self._rf_init_scores = [0.0] * max(self.num_tree_per_iteration, 1)
        self._rf_grad = None

    # -- resilience hooks (resilience/checkpoint.py) -----------------------
    def _restore_aux_extra(self, state):
        # RF keeps no extra persistent RNG: the base bagging streams are
        # restored by GBDT.restore_aux_state and _rf_grad is a pure
        # function of the objective, lazily recomputed.  Clearing it here
        # just documents that a restored booster starts from scratch.
        self._rf_grad = None

    def _compute_rf_gradients(self):
        """Gradients against the constant init score (rf.hpp:75-93)."""
        k = self.num_tree_per_iteration
        n = self.num_data
        for kk in range(k):
            self._rf_init_scores[kk] = (
                self.objective.boost_from_score(kk)
                if self.config.boost_from_average and self.objective else 0.0)
        tmp = jnp.asarray(np.repeat(np.asarray(self._rf_init_scores, np.float64)
                                    .reshape(k, 1), n, axis=1), self.dtype)
        grad, hess = self.objective.get_gradients(tmp if k > 1 else tmp[0])
        self._rf_grad = (jnp.reshape(grad, (k, n)).astype(self.dtype),
                         jnp.reshape(hess, (k, n)).astype(self.dtype))

    def _train_one_iter_impl(self, gradients=None, hessians=None) -> bool:
        # overrides the impl (not the telemetry shell, GBDT.train_one_iter)
        if gradients is not None or hessians is not None:
            log.fatal("RF mode does not support custom objective")
        if self._rf_grad is None:
            self._compute_rf_gradients()
        grad, hess = self._rf_grad
        k = self.num_tree_per_iteration
        row_init = self._bagging(self.iter)

        for kk in range(k):
            new_tree = Tree(1)
            if (self.objective is None or self.objective.class_need_train(kk)) \
               and self.train_set.num_features > 0:
                arrays, leaf_ids = self._grow_one_tree(grad[kk], hess[kk],
                                                       row_init)
                # one bulk device->host fetch (see GBDT.train_one_iter)
                host_arrays = grow_ops.fetch_tree_arrays(arrays)
                if int(host_arrays.num_leaves) > 1:
                    new_tree = Tree.from_arrays(host_arrays, self.train_set)
                self._record_split_ledger(new_tree, self.iter,
                                          len(self.models))
            if new_tree.num_leaves > 1:
                self._renew_tree_output(new_tree, kk, leaf_ids)
                if abs(self._rf_init_scores[kk]) > K_EPSILON:
                    new_tree.add_bias(self._rf_init_scores[kk])
                self._average_in(new_tree, kk, arrays, leaf_ids)
            else:
                output = self._rf_init_scores[kk]
                new_tree.as_constant(output)
                self._average_in(new_tree, kk, None, None)
            self.models.append(new_tree)
        self.iter += 1
        return False

    def _average_in(self, tree: Tree, class_id: int, arrays, leaf_ids):
        """score <- (score*iter + tree)/(iter+1) (rf.hpp:130-134)."""
        it = self.iter
        self.train_state.score = self.train_state.score.at[class_id].multiply(it)
        if arrays is not None:
            self._update_train_score(tree, class_id, arrays, leaf_ids)
        else:
            self.train_state.add_constant(float(tree.leaf_value[0]), class_id)
        self.train_state.score = self.train_state.score.at[class_id].multiply(
            1.0 / (it + 1))
        for _, vs, _m in self.valid_states:
            vs.score = vs.score.at[class_id].multiply(it)
            from .gbdt import _add_tree_score
            _add_tree_score(vs, tree, class_id, self)
            vs.score = vs.score.at[class_id].multiply(1.0 / (it + 1))

    def _renew_baseline_score(self, class_id: int) -> np.ndarray:
        # RF residuals are against the constant init score, not the running
        # ensemble average (rf.hpp:126 passes init_scores_[class])
        return np.full(self.num_data, self._rf_init_scores[class_id])
