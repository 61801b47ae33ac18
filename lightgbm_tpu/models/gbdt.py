"""GBDT boosting driver.

Re-design of src/boosting/gbdt.{h,cpp}: the per-iteration loop —
boost-from-average, gradient computation, bagging, per-class tree growth,
shrinkage, score updates, metric evaluation — orchestrated on host with every
hot step jitted on device.  Scores, gradients and the binned matrix stay
device-resident across iterations; only metric evaluation pulls scores back.

Model text IO follows the reference v2 format (gbdt_model_text.cpp:244-343)
so models round-trip with the reference's parsers.
"""
from __future__ import annotations

import contextlib
import gc
import time
from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from ..config import Config
from ..io.dataset import BinnedDataset
from ..io.file_io import atomic_write_text, v_open
from ..metric import Metric
from ..objective import ObjectiveFunction
from ..ops import grow as grow_ops
from ..ops import predict as predict_ops
from ..ops.split import SplitParams
from ..obs import device as obs_device
from ..obs import scaling as obs_scaling
from ..obs import tracing as obs_tracing
from ..utils import log
from ..utils.backend import on_tpu, pallas_interpret
from .tree import Tree

K_EPSILON = 1e-15
# deferred-pipeline drain cadence (iterations between bulk tree fetches).
# Each drain is a blocking fetch, so the cadence is a per-iteration tax.
# 48 was sized on an installation that no longer exists (a remotely
# attached chip whose blocking fetch cost ~100 ms) and has not been
# re-measured on the directly attached one, where the fetch is far
# cheaper (NOTES.md).  Degenerate-stop detection is still exact on
# drain (unchanged scores make every pending iteration degenerate too).
_DRAIN_EVERY = 48


def _dense_matrix(X) -> np.ndarray:
    """Raw-feature prediction inputs as a dense f64 matrix (scipy sparse
    accepted; the hot predict path chunk-densifies instead, predict_raw)."""
    from ..io.dataset import _issparse
    if _issparse(X):
        return np.asarray(X.todense(), np.float64)
    return np.asarray(X, np.float64)


class _DatasetState:
    """Device-side per-dataset state (ScoreUpdater, score_updater.hpp:17-120).

    `score` may be LAZY: the carried-arena fast path keeps scores as
    arena channels and sets a materializer thunk instead of the array;
    any read (metrics, snapshots, the bench's sync fetch) transparently
    reconstructs the row-ordered score first.
    """

    def __init__(self, ds: BinnedDataset, num_classes: int, dtype):
        self.ds = ds
        self.bins = ds.device_bins()
        self.num_bins = jnp.asarray(ds.feature_num_bins())
        self.default_bins = jnp.asarray(
            np.array([m.default_bin for m in ds.bin_mappers], np.int32))
        self.missing_types = jnp.asarray(
            np.array([m.missing_type for m in ds.bin_mappers], np.int32))
        self._score = jnp.zeros((num_classes, ds.num_data), dtype)
        self._score_thunk = None
        self._score_written = False
        self.bundle = _bundle_maps(ds)
        self._bins_t = None

    def feature_major_bins(self):
        """[G, rows in whole blocks] feature-major bins in the arena's
        type, what ops/valid_score.py reads; made at the first fused
        iteration that scores this (validation) set."""
        if self._bins_t is None:
            from ..ops import partition_pallas as _pp
            from ..ops import valid_score as _vs
            n = self.ds.num_data
            self._bins_t = jnp.pad(_pp.feature_major(self.bins),
                                   ((0, 0), (0, _vs.padded_rows(n) - n)))
        return self._bins_t

    @property
    def score(self):
        if self._score_thunk is not None:
            self._score = self._score_thunk()
            self._score_thunk = None
        return self._score

    @score.setter
    def score(self, value):
        self._score = value
        self._score_thunk = None
        # external writes invalidate any arena-resident score planes;
        # the carried fast path checks this flag and demotes itself
        self._score_written = True

    def defer_score(self, thunk) -> None:
        """Install a materializer; the next `score` read calls it."""
        self._score_thunk = thunk

    @property
    def hist_max_bin(self) -> int:
        """Bins per histogram column: bundled group columns can carry up
        to 256 bins regardless of config max_bin."""
        if self.ds.bundle is not None:
            return int(self.ds.bundle.group_num_bins.max())
        return (int(self.ds.feature_num_bins().max())
                if self.ds.num_features else 2)

    def add_constant(self, val: float, class_id: int) -> None:
        self.score = self.score.at[class_id].add(val)


def _bundle_maps(ds: BinnedDataset, scan_space: Optional[str] = None,
                 feature_too: bool = False):
    """Host BundleInfo -> device BundleMaps for the grow loop (or None).
    scan_space: None builds neither scan map (a validation set reads
    none); the training set's is the one GBDT._scan_space names, chosen
    with the engine (_setup_tree_engine).  feature_too: forced splits
    unbundle whatever space the scan works in."""
    if ds.bundle is None:
        return None
    return grow_ops.bundle_maps(
        ds.bundle, ds.feature_num_bins(),
        np.array([m.missing_type for m in ds.bin_mappers], np.int32),
        int(ds.bundle.group_num_bins.max()),
        feature_scan=scan_space == "feature" or feature_too,
        group_scan=scan_space == "group")


class GBDT:
    """The main boosting driver (gbdt.h:24-470)."""

    sub_model_name = "tree"

    def __init__(self, config: Config, train_set: Optional[BinnedDataset],
                 objective: Optional[ObjectiveFunction],
                 metrics: Sequence[Metric] = ()):
        self.config = config
        self.objective = objective
        self.train_metrics = list(metrics)
        self.models: List[Tree] = []
        self.iter = 0
        self.num_class = config.num_class
        self.num_tree_per_iteration = (
            objective.num_model_per_iteration if objective is not None
            else config.num_class)
        self.shrinkage_rate = config.learning_rate
        self.average_output = False
        self.max_feature_idx = 0
        self.label_idx = 0
        self.feature_names: List[str] = []
        self.feature_infos: List[str] = []
        self.loaded_parameter = ""
        self.dtype = jnp.float64 if config.tpu_double_precision else jnp.float32
        self.train_state: Optional[_DatasetState] = None
        self.valid_states: List[Tuple[str, _DatasetState, List[Metric]]] = []
        self.best_iteration = 0
        self._feat_rng = np.random.RandomState(config.feature_fraction_seed)
        # deferred-tree pipeline state (train_one_iter/_drain_inflight);
        # subclasses that need host trees within the iteration opt out
        self._allow_deferred = True
        # where the validation scores were updated by the last iteration
        # that had any: "device" inside the fused iteration, from the
        # device tree (ops/valid_score.py); "host" by the unfused spine,
        # from a fetched host tree (_update_valid_scores)
        self._valid_scoring = "host"
        self._inflight: List[dict] = []
        self._deferred_stopped = False
        # per-phase timers (TIMETAG analogue); sync_fn charges async
        # dispatch to the phase that launched it.  Telemetry-only runs
        # enable the profiler WITHOUT the sync: phases then measure
        # dispatch time, but the training stream is untouched (the
        # telemetry contract is a bitwise-identical model).
        from ..utils.profiling import Profiler, TraceSession
        telemetry_path = getattr(config, "tpu_telemetry_path", "")
        runhist_path = getattr(config, "tpu_runhist_path", "")
        federated = bool(getattr(config, "tpu_federation", False)
                         or getattr(config, "tpu_alert", False))
        self.profiler = Profiler(
            enabled=(config.tpu_profile or bool(telemetry_path)
                     or bool(runhist_path) or federated),
            sync_fn=self._profile_sync if config.tpu_profile else None)
        self._trace = TraceSession(config.tpu_profile_trace_dir)
        # span timeline (obs/tracing.py): arming the process tracer makes
        # every Profiler.phase site a nested span; like the recorder it
        # never touches the training stream (bitwise-identical model)
        self._tracing = obs_tracing.configure_from_config(config) is not None
        if self._tracing:
            obs_tracing.get_tracer().set_metadata(
                tree_learner=config.tree_learner,
                boosting=config.boosting,
                objective=getattr(config, "objective", ""))
        # per-iteration JSONL event log (obs/recorder.py); recorder
        # failures demote to a warning and disable themselves — they can
        # never fail a training run
        self.recorder = None
        self._bag_count: Optional[int] = None
        if telemetry_path or getattr(config, "tpu_runhist_path", ""):
            # a RUNHIST artifact alone also needs the recorder (it owns
            # the per-run series store); with no telemetry_path the
            # JSONL stream is simply skipped
            try:
                from ..obs.recorder import TrainingRecorder
                self.recorder = TrainingRecorder(telemetry_path, config)
            except Exception as exc:  # noqa: BLE001
                log.warning("telemetry disabled: recorder init failed (%s)",
                            exc)
        # cluster observability plane (obs/federation.py): per-round
        # digest exchange + critical-path ledger + alert ticks; same
        # degrade-to-warning, bitwise-identical-model contract as the
        # recorder
        self.federation = None
        if federated:
            try:
                from ..obs.federation import Federation
                self.federation = Federation(config)
            except Exception as exc:  # noqa: BLE001
                log.warning("cluster federation disabled: init failed (%s)",
                            exc)
        # runtime sync sentinel (obs/scaling.py): tpu_sync_guard=log|fail
        # wraps each round's training impl so implicit device->host
        # fetches become counted, stack-attributed sync_event telemetry;
        # None in the default "off" mode (zero overhead)
        self.sync_sentinel = obs_scaling.SyncSentinel.from_config(config)

        if train_set is not None:
            self._setup_train(train_set)

    # ------------------------------------------------------------------ #
    def _profile_sync(self):
        """Device sync for phase timing: a dependent scalar fetch."""
        if self.train_state is not None:
            # the ONE sanctioned per-phase sync; scoped exemption keeps
            # the sentinel's fail mode usable alongside tpu_profile
            with obs_scaling.exempt():
                float(jnp.sum(self.train_state.score[:, :1]))

    def profile_report(self):
        return self.profiler.report(header="tpu_profile")

    def finish_telemetry(self) -> None:
        """Drain the pipeline and close the telemetry surfaces: the JSONL
        event log (flushes the last pending event, backfills deferred
        tree stats, writes the summary), the jax profiler session, and
        the span-trace file.  Idempotent; engine.train calls it in a
        `finally` so even a raising training loop cannot leak a live
        profiler session or an unwritten trace, and __del__ covers
        direct Booster.update users."""
        recorder, self.recorder = self.recorder, None
        if recorder is not None:
            try:
                self._sync_model()
                recorder.finalize(self)
            except Exception as exc:  # noqa: BLE001 — telemetry never raises
                log.warning("telemetry finalize failed: %s", exc)
        federation, self.federation = self.federation, None
        if federation is not None:
            try:
                federation.close()
            except Exception as exc:  # noqa: BLE001 — telemetry never raises
                log.warning("federation close failed: %s", exc)
        try:
            self._trace.stop()
        except Exception as exc:  # noqa: BLE001
            log.debug("trace stop failed during finalize: %s", exc)
        if getattr(self, "_tracing", False):
            self._tracing = False
            try:
                path = obs_tracing.get_tracer().flush()
                if path:
                    log.info("trace: span timeline written to %s", path)
            except Exception as exc:  # noqa: BLE001
                log.warning("trace flush failed: %s", exc)

    def __del__(self):
        try:
            if (getattr(self, "recorder", None) is not None
                    or getattr(self, "federation", None) is not None
                    or getattr(self, "_tracing", False)):
                self.finish_telemetry()
            # teardown report only for explicit tpu_profile runs: a
            # telemetry-only profiler is an implementation detail of the
            # event log, not a request for the console report
            if getattr(self, "profiler", None) is not None \
                    and getattr(getattr(self, "config", None),
                                "tpu_profile", False):
                self.profile_report()
            if getattr(self, "_trace", None) is not None:
                self._trace.stop()
        # __del__ runs at interpreter teardown where even logging
        # can raise; stay silent by design.
        # tpulint: disable-next-line=except-swallow
        except Exception:  # noqa: BLE001 — teardown must never raise
            pass

    # ------------------------------------------------------------------ #
    def _setup_train(self, train_set: BinnedDataset) -> None:
        # the fused-iteration jit closes over THIS train set's categorical
        # flags, hist slots and forced splits as trace-time constants (the
        # bundle maps are arguments; their structure is part of the trace);
        # a ResetTrainingData with a same-shaped dataset would
        # otherwise reuse the stale trace and silently train on the old
        # dataset's structure (c_api.cpp ResetTrainingData contract)
        self._fused_fn = None
        self._fused_key = None
        self._fused_fields = None
        self._fused_validated = False
        self._partition_validated = False
        # carried-arena state is dataset-bound too: drop the trace and
        # let eligibility re-engage against the new arena (BinaryLogloss
        # is gated on exact type like L2's carry_fields, see objective.py)
        self._carried_active = None
        self._carried_fn = None
        self._carried_key = None
        self._carry_mat_fn = None
        # a booster that stopped on the OLD data (no splittable leaves)
        # must be trainable again on the new data
        self._deferred_stopped = False
        self.train_set = train_set
        self.num_data = train_set.num_data
        self.max_feature_idx = train_set.num_total_features - 1
        self.feature_names = list(train_set.feature_names)
        self.feature_infos = _feature_infos(train_set)
        self.train_state = _DatasetState(train_set, self.num_tree_per_iteration,
                                         self.dtype)
        if self.objective is not None:
            self.objective.init(train_set.metadata, self.num_data)
        for m in self.train_metrics:
            m.init(train_set.metadata, self.num_data)
        self.max_bin = self.train_state.hist_max_bin
        F = max(train_set.num_features, 1)
        self._feature_mask_all = jnp.ones(F, bool)
        self._refresh_split_params()
        # [F] bin-type vector; None when the dataset is purely numerical so
        # the grow loop skips the categorical scan entirely
        cat_flags = np.array([m.bin_type == 1 for m in train_set.bin_mappers],
                             bool) if train_set.num_features else np.zeros(0, bool)
        self.is_categorical = (jnp.asarray(cat_flags) if cat_flags.any()
                               else None)
        self.monotone = (jnp.asarray(train_set.monotone_constraints, jnp.int32)
                         if train_set.monotone_constraints is not None else None)
        self.penalty = (jnp.asarray(train_set.feature_penalty, self.dtype)
                        if train_set.feature_penalty is not None else None)
        # CEGB coupled feature penalties (config.h:427-431): indexed by real
        # (total) feature id in the config, mapped to used features here;
        # feature_used lives for the whole ensemble like the reference's
        # SerialTreeLearner member (serial_tree_learner.cpp:534-536)
        self._cegb_coupled = None
        coupled = self.config.cegb_penalty_feature_coupled
        if coupled:
            if len(coupled) != train_set.num_total_features:
                log.fatal("cegb_penalty_feature_coupled size (%d) must equal "
                          "num_total_features (%d)"
                          % (len(coupled), train_set.num_total_features))
            vec = np.array([coupled[train_set.real_feature_index[f]]
                            for f in range(F)], np.float64)
            self._cegb_coupled = jnp.asarray(
                self.config.cegb_tradeoff * vec, self.dtype)
        self._cegb_used = np.zeros(F, bool)
        if self.config.cegb_penalty_feature_lazy:
            log.warning("cegb_penalty_feature_lazy is not supported yet; "
                        "ignoring it")
        self._forced_splits = self._load_forced_splits()
        # distributed learner selection (TreeLearner::CreateTreeLearner,
        # src/treelearner/tree_learner.cpp:9-33): None = serial
        from ..parallel import learners as par_learners
        self._grower = par_learners.make_grower(self.config,
                                                train_set.num_features)
        if self._grower is not None:
            # donation forensics ride the telemetry opt-in: the audit
            # costs one extra lowering per partition build, so it arms
            # only when an observer (recorder/tracer) will consume it
            self._grower.audit_donation = (self.recorder is not None
                                           or self._tracing)
        self._setup_tree_engine()
        # the training set's scan map, for the space _setup_tree_engine
        # chose with the engine; the grow loop follows the map it is given
        self.train_state.bundle = _bundle_maps(
            train_set, self._scan_space,
            feature_too=bool(self._forced_splits))
        # bagging state: the drawn bag (ops/bagging.py), its index, and
        # its row-order mask, made by _bagging for the unfused spine
        self._bag = None
        self._bag_index: Optional[int] = None
        self._bag_mask = None
        self._row_all_in = jnp.zeros(self.num_data, jnp.int32)
        # init scores seed the training scores unconditionally (the reference
        # seeds ScoreUpdater at construction, score_updater.hpp:40-55), so
        # custom-fobj training also starts from them
        if train_set.metadata.init_score is not None:
            self._apply_init_scores()

    def _refresh_split_params(self) -> None:
        """(Re)build the growth-time parameter record from config — must
        be called whenever config changes mid-training (reset_parameter)."""
        self.split_params = SplitParams(
            lambda_l1=self.config.lambda_l1, lambda_l2=self.config.lambda_l2,
            max_delta_step=self.config.max_delta_step,
            min_data_in_leaf=self.config.min_data_in_leaf,
            min_sum_hessian_in_leaf=self.config.min_sum_hessian_in_leaf,
            min_gain_to_split=self.config.min_gain_to_split,
            max_cat_to_onehot=self.config.max_cat_to_onehot,
            cat_smooth=self.config.cat_smooth,
            cat_l2=self.config.cat_l2,
            min_data_per_group=self.config.min_data_per_group,
            cegb_split_penalty=(self.config.cegb_tradeoff
                                * self.config.cegb_penalty_split))

    def add_valid(self, name: str, valid_set: BinnedDataset,
                  metrics: Sequence[Metric]) -> None:
        self._sync_model()
        for m in metrics:
            m.init(valid_set.metadata, valid_set.num_data)
        # the plan again, now with what is validated: the sets, their rows
        # and the metrics whose class evaluates on the device (the others
        # are fed a fetched score vector, _eval_state)
        rows = [st.ds.num_data for _n, st, _m in self.valid_states]
        rows.append(valid_set.num_data)
        plan = dict(
            getattr(self, "_engine_plan", None) or {},
            valid_sets=len(rows), valid_rows=",".join(map(str, rows)),
            device_metrics=",".join(
                m.name for m in metrics
                if type(m).eval_device is not Metric.eval_device))
        with obs_tracing.span("engine_plan", "setup", **plan):
            state = _DatasetState(valid_set, self.num_tree_per_iteration,
                                  self.dtype)
            if valid_set.metadata.init_score is not None:
                init = _expand_init_score(valid_set.metadata.init_score,
                                          self.num_tree_per_iteration,
                                          valid_set.num_data)
                state.score = state.score + jnp.asarray(init, self.dtype)
            # replay existing model onto the new validation scores
            for it in range(len(self.models) // self.num_tree_per_iteration):
                for k in range(self.num_tree_per_iteration):
                    tree = self.models[it * self.num_tree_per_iteration + k]
                    _add_tree_score(state, tree, k, self)
        self.valid_states.append((name, state, list(metrics)))

    # ------------------------------------------------------------------ #
    # Bagging (gbdt.cpp:159-241)
    # ------------------------------------------------------------------ #
    def _bagging(self, it: int) -> jnp.ndarray:
        """The row mask of iteration `it` (0 in the bag, -1 out): a new
        bag every bagging_freq iterations, drawn on the device as a
        function of bagging_seed and the bag's index (ops/bagging.py), so
        both spines and a resumed booster draw the same rows."""
        self._draw_bag(it)
        if self._bag is None:
            return self._row_all_in
        if self._bag_mask is None:
            from ..ops import bagging
            self._bag_mask = bagging.row_mask(self._bag,
                                              num_data=self.num_data)
        return self._bag_mask

    def _bag_in_use(self) -> int:
        """Rows of every bag under the config, 0 without bagging."""
        cfg = self.config
        if cfg.bagging_freq <= 0 or cfg.bagging_fraction >= 1.0:
            return 0
        from ..ops import bagging
        return max(bagging.bag_rows(cfg.bagging_fraction, self.num_data), 1)

    def _draw_bag(self, it: int) -> None:
        count = self._bag_in_use()
        if not count:
            self._bag = self._bag_index = self._bag_count = None
            self._bag_mask = None
            return
        index = it // self.config.bagging_freq
        if self._bag is not None and self._bag_index == index \
                and self._bag_count == count:
            return
        from ..ops import bagging
        self._bag = bagging.draw(self.config.bagging_seed % (1 << 32), index,
                                 num_data=self.num_data, count=count)
        self._bag_index = index
        self._bag_count = count          # telemetry: rows in this bag
        self._bag_mask = None

    def _feature_sample(self) -> jnp.ndarray:
        frac = self.config.feature_fraction
        F = self.train_set.num_features
        if frac >= 1.0 or F == 0:
            return self._feature_mask_all
        with obs_tracing.span("feature_sample", "train"):
            used = max(1, int(round(F * frac)))
            idx = self._feat_rng.choice(F, used, replace=False)
            mask = np.zeros(F, bool)
            mask[idx] = True
            return jnp.asarray(mask)

    # ------------------------------------------------------------------ #
    # One boosting iteration (gbdt.cpp:333-412)
    # ------------------------------------------------------------------ #
    def train_one_iter(self, gradients: Optional[np.ndarray] = None,
                       hessians: Optional[np.ndarray] = None) -> bool:
        """Returns True when training cannot continue (no splittable
        leaves).  Thin telemetry shell around _train_one_iter_impl (which
        subclasses override): times the round and hands the recorder one
        event per iteration, for every boosting mode."""
        it = self.iter
        # the sentinel wraps ONLY the training impl: telemetry's own
        # bulk fetches (recorder/federation, below) run outside the
        # guard, so a clean round reports zero sync events
        sentinel = self.sync_sentinel
        guard = (sentinel.guard(it) if sentinel is not None
                 else contextlib.nullcontext())
        t0 = time.perf_counter()
        with obs_tracing.span("train/iteration", "train", iter=it), guard:
            finished = self._train_one_iter_impl(gradients, hessians)
        wall = time.perf_counter() - t0
        if self.recorder is not None:
            try:
                self.recorder.on_iteration(self, it, wall, finished)
            except Exception as exc:  # noqa: BLE001 — telemetry must not kill train
                log.warning("telemetry recorder failed (%s); disabling it",
                            exc)
                self.recorder = None
        if self.federation is not None:
            try:
                self.federation.on_round(self, it, wall)
            except Exception as exc:  # noqa: BLE001 — telemetry must not kill train
                # a changed world is the elastic supervisor's signal to
                # re-form — let it through; anything else degrades to a
                # warning and disables federation
                if type(exc).__name__ == "WorldChangedError":
                    raise
                log.warning("cluster federation failed (%s); disabling it",
                            exc)
                self.federation = None
        return finished

    def _train_one_iter_impl(self, gradients: Optional[np.ndarray] = None,
                             hessians: Optional[np.ndarray] = None) -> bool:
        """One boosting round (the body of the reference's TrainOneIter)."""
        # Materialize pending deferred trees only every _DRAIN_EVERY
        # iterations: each drain pays a host round-trip, and a degenerate
        # iteration detected late is harmless — with unchanged scores every
        # subsequent pending iteration is degenerate too (zero-valued
        # trees), so the stop point is recovered exactly on drain.
        if len(self._inflight) >= self.num_tree_per_iteration * _DRAIN_EVERY:
            if self._drain_inflight():
                self._deferred_stopped = True
        if self._deferred_stopped:
            return True

        self._trace.start()
        k = self.num_tree_per_iteration
        init_scores = [0.0] * k
        custom = gradients is not None and hessians is not None
        if not custom:
            for kk in range(k):
                init_scores[kk] = self._boost_from_average(kk)
        # deferred (pipelined) tree materialization: only when nothing needs
        # the host tree inside this iteration.  A validation set does not:
        # the fused iteration scores it from the device tree.  (Training
        # metrics do: on the carried spine the training score is a sort
        # over all rows away.)
        deferred_ok = (self._allow_deferred
                       and not self.train_metrics
                       and self._cegb_coupled is None
                       and (self.objective is None
                            or not self.objective.is_renew_tree_output()))
        # the unfused spine scores validation sets from the host tree
        # (_update_valid_scores), so there a validation set still keeps
        # the tree in the iteration
        defer_unfused = deferred_ok and not self.valid_states
        # the partition engine can then fuse the score update into its
        # label-recovery scatter (emit="score"), skipping the per-row
        # leaf-value gather entirely (serial-gather cost on TPU)
        self._score_emit_ok = defer_unfused

        # single-dispatch fast path: gradients + tree + score update fused;
        # a row bag rides it as an argument (drawn below, _draw_bag)
        fused_ok = self._fused_eligible(deferred_ok, k, custom)
        # carried-arena lifecycle: any iteration that will NOT run the
        # carried path (custom gradients, lost fused eligibility) — or an
        # external score write (rollback, refit, merge) — must demote NOW,
        # firing the deferred materializer while the arena planes are still
        # valid; the upcoming tree clobbers the carry slots.  Demotion
        # rewrites the pristine block (carried mode roots in it), so the
        # standard paths resume seamlessly.
        if getattr(self, "_carried_active", False):
            if not fused_ok or self.train_state._score_written:
                _ = self.train_state.score   # fire the thunk while valid
                self._demote_carried()
        if fused_ok:
            # no guard here: once the fused path is chosen, a lowering or
            # device failure propagates with its message — a booster that
            # quietly changed engine would be measured as something else
            if getattr(self, "_carried_active", None) is None:
                self._carried_active = False
                if self._carried_ok(k):
                    self._init_carried()
            self._draw_bag(self.iter)
            with self.profiler.phase("fused_iter"):
                if self._carried_active:
                    packed_per_class = self._run_fused_iter_carried()
                else:
                    packed_per_class = self._run_fused_iter()
            if self.valid_states:
                self._valid_scoring = "device"
            for packed in packed_per_class:
                for p in packed:
                    p.copy_to_host_async()
            for kk, packed in enumerate(packed_per_class):
                self.models.append(None)
                self._inflight.append(dict(
                    packed=packed, max_leaves=self.config.num_leaves,
                    cat_bins=(self.max_bin
                              if self.is_categorical is not None else 0),
                    init_score=init_scores[kk],
                    has_trunc_flag=True, it=self.iter,
                    slot=len(self.models) - 1))
            self.iter += 1
            return False

        with self.profiler.phase("boosting(gradients)"):
            if not custom:
                grad, hess = self.objective.get_gradients(
                    self.train_state.score if k > 1
                    else self.train_state.score[0])
                grad = jnp.reshape(grad, (k, self.num_data)).astype(self.dtype)
                hess = jnp.reshape(hess, (k, self.num_data)).astype(self.dtype)
            else:
                grad = jnp.reshape(jnp.asarray(gradients, self.dtype),
                                   (k, self.num_data))
                hess = jnp.reshape(jnp.asarray(hessians, self.dtype),
                                   (k, self.num_data))

        # row-sampling hook: GOSS rescales gradients and sets the row mask
        # here (goss.hpp:87-135); default is identity
        with self.profiler.phase("bagging/sampling"):
            grad, hess = self._sample_gradients(grad, hess)
            row_init = self._bagging(self.iter)

        should_continue = False
        deferred_any = False
        for kk in range(k):
            new_tree = Tree(1)
            class_ok = (self.objective is None
                        or self.objective.class_need_train(kk))
            if class_ok and self.train_set.num_features > 0:
                with self.profiler.phase("tree_grow"):
                    arrays, leaf_ids = self._grow_one_tree(grad[kk], hess[kk],
                                                           row_init)
                if defer_unfused:
                    packed = self._pack_tree_with_flag(arrays)
                    for p in packed:
                        p.copy_to_host_async()
                    with self.profiler.phase("score_update"):
                        self._update_train_score_device(arrays, kk, leaf_ids)
                    self.models.append(None)       # placeholder; drained next
                    self._inflight.append(dict(
                        packed=packed, max_leaves=arrays.max_leaves,
                        cat_bins=arrays.cat_mask.shape[1],
                        init_score=init_scores[kk],
                        has_trunc_flag=self._last_truncated is not None,
                        it=self.iter,
                        slot=len(self.models) - 1))
                    deferred_any = True
                    continue
                # ONE bulk device->host fetch per tree; per-field reads
                # would pay a host round-trip each (remote-attached TPUs).
                # The arena-truncation flag rides the same fetch.
                packed = self._pack_tree_with_flag(arrays)
                with self.profiler.phase("tree_fetch"):
                    ivec, fvec = jax.device_get(packed)   # ONE bulk transfer
                host_arrays = grow_ops.unpack_tree_vectors(
                    ivec, fvec, arrays.max_leaves, arrays.cat_mask.shape[1])
                if self._last_truncated is not None and ivec[-1]:
                    self._emit_truncation_warning(int(host_arrays.num_leaves))
                if int(host_arrays.num_leaves) > 1:
                    new_tree = Tree.from_arrays(host_arrays, self.train_set)
                self._record_split_ledger(new_tree, self.iter,
                                          len(self.models))

            if new_tree.num_leaves > 1:
                should_continue = True
                if self._cegb_coupled is not None:
                    self._cegb_used[new_tree.split_feature_inner[
                        :new_tree.num_leaves - 1]] = True
                with self.profiler.phase("renew_tree_output"):
                    self._renew_tree_output(new_tree, kk, leaf_ids)
                new_tree.shrink(self.shrinkage_rate)
                with self.profiler.phase("score_update"):
                    self._update_train_score(new_tree, kk, arrays, leaf_ids)
                    self._update_valid_scores(new_tree, kk)
                    if self.valid_states:
                        self._valid_scoring = "host"
                if abs(init_scores[kk]) > K_EPSILON:
                    new_tree.add_bias(init_scores[kk])
            else:
                if len(self.models) < k:
                    if not class_ok and self.objective is not None:
                        output = self.objective.boost_from_score(kk)
                    else:
                        output = init_scores[kk]
                    new_tree.as_constant(output)
                    self.train_state.add_constant(output, kk)
                    for _, vs, _m in self.valid_states:
                        vs.add_constant(output, kk)
            self.models.append(new_tree)

        if deferred_any:
            # continuation decided when this iteration drains
            self.iter += 1
            return False
        if not should_continue:
            log.warning("Stopped training because there are no more leaves "
                        "that meet the split requirements")
            if len(self.models) > k:
                del self.models[-k:]
            return True
        self.iter += 1
        return False

    # ------------------------------------------------------------------ #
    # Fused fast-path iteration: gradients -> tree growth -> score update
    # in ONE compiled dispatch.  The per-iteration spine (gbdt.cpp:333-412)
    # otherwise costs 3-4 separate device programs whose dispatch gaps
    # dominate on remote-attached TPUs.
    # ------------------------------------------------------------------ #
    def _fused_eligible(self, deferred_ok: bool, k: int, custom: bool) -> bool:
        return (deferred_ok and not custom
                and getattr(self, "_use_partition_engine", False)
                and self.objective is not None
                and all(self.objective.class_need_train(kk)
                        for kk in range(k))
                and type(self)._sample_gradients is GBDT._sample_gradients
                and self.train_set.num_features > 0)

    def _objective_device_fields(self):
        """[(holder, attr)] of every array the objective's gradient math
        closes over — including multiclass internals (_label_int, OVA
        per-class sub-objectives).  Swapped for traced ARGUMENTS inside
        the fused program so they are not baked into the executable as
        constants (multi-MB label planes at the headline shape)."""
        holders = [self.objective] + list(
            getattr(self.objective, "binary_loss", []) or [])
        fields = []
        for h in holders:
            for name, v in vars(h).items():
                if isinstance(v, (jnp.ndarray, np.ndarray)) and v.ndim > 0:
                    fields.append((h, name))
        return fields

    def _build_fused_iter(self):
        from ..ops import grow_partition as gp
        from ..ops import quantize as qz
        from ..ops import valid_score
        objective = self.objective
        interpret = pallas_interpret()
        k = max(self.num_tree_per_iteration, 1)
        quantized = getattr(self, "_quantized", False)
        self._fused_fields = self._objective_device_fields()
        fields = self._fused_fields
        bag_rows = self._bag_in_use()

        def fused(arena, bins_t, score, field_vals, row0, fmasks,
                  num_bins, default_bins, missing_types, sparams, monotone,
                  penalty, bundle, shrink, qkey, vscores, vbins, vbundles):
            # score is [k, n]; gradients come back class-major and every
            # class's tree grows in the SAME program, reusing the one
            # donated arena; each class gets its own feature mask (the
            # eager path samples per tree).  vscores / vbins / vbundles:
            # one entry per validation set (_valid_args), empty lists for
            # a booster without one, which then traces the same program
            # as before validation was scored here.  row0: the drawn bag
            # (ops/bagging.py) under bagging, else unread
            olds = [getattr(h, a) for h, a in fields]
            for (h, a), v in zip(fields, field_vals):
                setattr(h, a, v)
            n = score.shape[1]
            try:
                with jax.named_scope("lgbm.gradient"):
                    grad, hess = objective.get_gradients(
                        score if k > 1 else score[0])
                    grad = jnp.asarray(grad, jnp.float32).reshape(k, n)
                    hess = jnp.asarray(hess, jnp.float32).reshape(k, n)
            finally:
                for (h, a), v in zip(fields, olds):
                    setattr(h, a, v)
            ivecs, fvecs, deltas = [], [], []
            for kk in range(k):
                g_in, h_in, qsc = grad[kk], hess[kk], None
                if quantized:
                    # in-program quantization: codes + scales never leave
                    # the device; the key is folded per class so every
                    # tree draws independent rounding noise
                    g_in, h_in, _gs, _hs = qz.quantize_gradients(
                        grad[kk], hess[kk], jax.random.fold_in(qkey, kk))
                    qsc = (_gs, _hs)
                arrays, delta, arena, trunc = gp.grow_tree_partition_impl(
                    arena, bins_t, g_in, h_in, row0, fmasks[kk],
                    num_bins, default_bins, missing_types, sparams,
                    monotone, penalty,
                    None, None, self.is_categorical, bundle,
                    max_leaves=self.config.num_leaves,
                    max_depth=self.config.max_depth,
                    max_bin=self.max_bin, emit="score",
                    full_bag=not bag_rows,
                    max_cat_threshold=self.config.max_cat_threshold,
                    hist_slots=self._hist_slots,
                    forced_splits=self._forced_splits,
                    pristine=True, quantized=quantized,
                    quant_scales=qsc, bag_rows=bag_rows,
                    interpret=interpret)
                with jax.named_scope("lgbm.finish"):
                    ivec, fvec = grow_ops.pack_tree_arrays(arrays)
                    ivecs.append(jnp.concatenate(
                        [ivec, trunc.astype(jnp.int32)[None]]))
                fvecs.append(fvec)
                deltas.append(delta.astype(score.dtype))
                vscores = valid_score.add_tree(
                    vscores, kk, vbins, arrays, shrink, num_bins,
                    default_bins, vbundles)
            with jax.named_scope("lgbm.score"):
                new_score = score + shrink * jnp.stack(deltas)
            return ivecs, fvecs, new_score, arena, vscores

        return jax.jit(fused, donate_argnums=(0, 2, 15))

    def _run_fused_iter(self):
        """One fused iteration; returns per-class packed (ivec, fvec)
        device arrays with the truncation flag appended (the _inflight
        payloads)."""
        # the jitted fn bakes these in at trace time; rebuild if a
        # reset_parameter callback changed them mid-training
        key = (self.config.num_leaves, self.config.max_depth, self.max_bin,
               self.config.max_cat_threshold, self._bag_in_use())
        rebuilt = (getattr(self, "_fused_fn", None) is None
                   or getattr(self, "_fused_key", None) != key)
        if rebuilt:
            self._fused_fn = self._build_fused_iter()
            self._fused_key = key
        sh = jnp.asarray(self.shrinkage_rate, self.dtype)
        k = max(self.num_tree_per_iteration, 1)
        fmasks = jnp.stack([self._feature_sample() for _ in range(k)])
        field_vals = [getattr(h, a) for h, a in self._fused_fields]
        from ..ops import quantize as _qz
        # pure function of (config seed, restored iteration counter):
        # kill-and-resume replays the identical rounding noise
        qkey = _qz.quantize_key(getattr(self, "_quant_seed", 0), self.iter)
        args = (self._arena, self._bins_t, self.train_state.score,
                field_vals, self._row0(), fmasks,
                self.train_state.num_bins, self.train_state.default_bins,
                self.train_state.missing_types, self.split_params,
                self.monotone, self.penalty, self.train_state.bundle, sh,
                qkey) + self._valid_args()
        if rebuilt and getattr(self, "_tracing", False) \
                and getattr(self.config, "tpu_trace_xla_analysis", True):
            # kernel attribution: one "compile" span per retrace carrying
            # flops / bytes / peak-HBM estimates for the fused step,
            # tagged with the shape signature that triggered the rebuild.
            # Must run BEFORE the executing call — arena and score are
            # donated, so their buffers are dead afterwards.
            # resident flattened leaves: bins_t (1), the dataset field
            # planes (3..) and row_all_in — persistent across rounds, so
            # un-donatable by design; arena (0) and score (2) ARE donated
            n_field = len(jax.tree_util.tree_leaves(field_vals))
            obs_device.analyze_compiled(
                self._fused_fn, args,
                signature="leaves=%d depth=%d bin=%d cat=%d bag=%d rows=%d"
                % (key + (self.num_data,)),
                donation_resident=(1, *range(3, 4 + n_field)))
        ivecs, fvecs, new_score, arena, vscores = self._fused_fn(*args)
        self._store_valid_scores(vscores)
        if not getattr(self, "_fused_validated", False):
            # force materialization once so a device runtime fault raises
            # HERE, at the dispatch that caused it, instead of at a later
            # async fetch
            with obs_scaling.exempt():   # one-shot fault-surfacing sync
                int(ivecs[0][-1])
            self._fused_validated = True
        self._arena = arena
        self.train_state.score = new_score
        self._last_truncated = jnp.asarray(False)   # flag rides ivec[-1]
        return list(zip(ivecs, fvecs))

    def _row0(self):
        """The fused programs' row argument: the drawn bag under bagging,
        else the all-rows mask (which a full-bag program does not read)."""
        return self._bag if self._bag is not None else self._row_all_in

    def _valid_args(self):
        """(scores, feature-major bins, bundle maps) of the validation
        sets, one list entry per set: the fused iteration's last three
        arguments.  The scores are donated."""
        states = [st for _n, st, _m in self.valid_states]
        return ([st.score for st in states],
                [st.feature_major_bins() for st in states],
                [st.bundle for st in states])

    def _store_valid_scores(self, vscores) -> None:
        for (_n, st, _m), sc in zip(self.valid_states, vscores):
            st.score = sc

    # ---- carried-arena fast path -----------------------------------------
    # Scores and the objective's per-row constants ride the arena as
    # bf16 residue-plane channels, permuted along with the rows, so the
    # per-tree boundary needs NO row-order recovery: the finished tree's
    # segments are compacted (full channels) into the other root slot
    # and the next tree roots there.  This removes the O(n log^2 n)
    # rowid sort from every iteration (~64 ms at 10.5M rows); the
    # row-ordered score is reconstructed lazily on first read.

    def _carried_ok(self, k: int) -> bool:
        if (k != 1 or self.objective is None
                or getattr(self, "_grower", None) is not None
                or self._bins_t is None):
            return False
        spec = self.objective.carry_fields()
        if spec is None:
            return False
        from ..ops import partition_pallas as _pp
        from ..ops import valid_score as _vs
        G = self._bins_t.shape[0]
        base = _pp.feature_channels(G) + _pp.N_AUX
        need = 3 + sum(p for _a, p in spec)
        C, cap = self._arena.shape
        if C - base < need:
            return False
        # the bump region must keep enough headroom for a tree's child
        # allocations: demand >= 2n so eligibility never trades the sort
        # for truncation (always true at tpu_arena_factor >= 4)
        n_al = -(-self._bins_t.shape[1] // _pp.TILE) * _pp.TILE
        bump0 = self._carried_layout()[1]
        # under a bag the out-of-bag rows are dumped at bump0 and scored
        # there in whole blocks (ops/grow_partition.py)
        oob = self.num_data - (self._bag_in_use() or self.num_data)
        return (cap - bump0 >= 2 * n_al
                and bump0 + (_vs.padded_rows(oob) if oob else 0) <= cap)

    def _carried_layout(self):
        """((root slot 0, root slot 1), first bump column).  The two
        ping-pong root slots are the pristine block itself and the slot
        after it: a carried booster never reads the pristine rows again,
        and a third copy of the rows would cost the bump region a full
        row footprint — at 10.5M x 28 and tpu_arena_factor=6 that left
        3n columns for child segments and truncated the 255-leaf trees
        at ~160 leaves (chip run, PR 21)."""
        from ..ops import partition_pallas as _pp
        stride = _pp.pristine_work0(self._bins_t.shape[1])   # n_al + TILE
        return (0, stride), 2 * stride

    def _init_carried(self):
        from ..ops import partition_pallas as _pp
        G = self._bins_t.shape[0]
        self._carry_base = _pp.feature_channels(G) + _pp.N_AUX
        self._carry_slots, self._carry_bump0 = self._carried_layout()
        self._carry_parity = 0
        spec = self.objective.carry_fields()
        planes = []
        for arr, np_ in spec:
            if np_ == 1:
                planes.append(jnp.asarray(arr, _pp.ARENA_DT)[None, :])
            else:
                planes.append(jnp.stack(
                    _pp.split_f32(jnp.asarray(arr, jnp.float32))))
        score0 = jnp.asarray(self.train_state.score[0], jnp.float32)
        payload = jnp.concatenate(
            [jnp.stack(_pp.split_f32(score0))] + planes, axis=0)
        # root slot 0 IS the pristine block (bins + rowids in row order,
        # zero padding rows): only the carry planes are added, in place —
        # the arena is donated, never copied
        self._arena = _write_planes(self._arena,
                                    payload.astype(_pp.ARENA_DT),
                                    self._carry_base)
        self.train_state._score_written = False
        self._carried_active = True

    def _demote_carried(self):
        """Leave carried mode: the standard paths root every tree in the
        pristine block, which carried trees have overwritten."""
        from ..ops import partition_pallas as _pp
        self._carried_active = False
        self._arena = _pp.init_pristine(self._arena, self._bins_t)

    def _build_fused_iter_carried(self):
        from ..ops import grow_partition as gp
        from ..ops import partition_pallas as _pp
        from ..ops import quantize as qz
        from ..ops import valid_score
        objective = self.objective
        quantized = getattr(self, "_quantized", False)
        interpret = pallas_interpret()
        n = self._bins_t.shape[1]
        base = self._carry_base
        bump0 = self._carry_bump0
        spec = objective.carry_fields()
        n_planes = [p for _a, p in spec]
        L = self.config.num_leaves
        bag_rows = self._bag_in_use()
        self._fused_fields = self._objective_device_fields()
        fields_io = self._fused_fields

        def merge(planes):
            return sum(planes[i].astype(jnp.float32)
                       for i in range(planes.shape[0]))

        def fused(arena, bins_t, root0, dst, field_vals, row0, fmask,
                  num_bins, default_bins, missing_types, sparams,
                  monotone, penalty, bundle, shrink, qkey,
                  vscores, vbins, vbundles):
            olds = [getattr(h, a) for h, a in fields_io]
            for (h, a), v in zip(fields_io, field_vals):
                setattr(h, a, v)
            try:
                with jax.named_scope("lgbm.gradient"):
                    score = merge(jax.lax.dynamic_slice(
                        arena, (jnp.int32(base), root0), (3, n)))
                    off = base + 3
                    fields = []
                    for np_ in n_planes:
                        fields.append(merge(jax.lax.dynamic_slice(
                            arena, (jnp.int32(off), root0), (np_, n))))
                        off += np_
                    grad, hess = objective.carry_gradients(score, fields)
                    g_in = jnp.asarray(grad, jnp.float32)
                    h_in = jnp.asarray(hess, jnp.float32)
            finally:
                for (h, a), v in zip(fields_io, olds):
                    setattr(h, a, v)
            qsc = None
            if quantized:
                # grad/hess are in CARRIED (arena) row order here, and so
                # are the codes — the fused root kernel writes them next
                # to the rows they belong to
                g_in, h_in, _gs, _hs = qz.quantize_gradients(
                    g_in, h_in, qkey)
                qsc = (_gs, _hs)
            arrays, oob, arena, trunc = gp.grow_tree_partition_impl(
                arena, bins_t, g_in, h_in, row0, fmask,
                num_bins, default_bins, missing_types, sparams,
                monotone, penalty, None, None, self.is_categorical,
                bundle,
                max_leaves=L, max_depth=self.config.max_depth,
                max_bin=self.max_bin, emit="carry", full_bag=not bag_rows,
                max_cat_threshold=self.config.max_cat_threshold,
                hist_slots=self._hist_slots,
                forced_splits=self._forced_splits,
                pristine=False, carried_root=root0, carry_dst=dst,
                carried_bump0=bump0, quantized=quantized,
                quant_scales=qsc, bag_rows=bag_rows, interpret=interpret)
            # per-row leaf value over the compacted order (leaf-index
            # segments): boundary scatter + cumsum, no gather.  Under a bag
            # the in-bag rows are the first bag_rows, the out-of-bag rows
            # follow with the values the tree gave them (oob)
            with jax.named_scope("lgbm.score"):
                lv = arrays.leaf_value.astype(jnp.float32)
                lc = arrays.leaf_count
                bounds = jnp.cumsum(lc)
                diffs = jnp.zeros((n,), jnp.float32).at[0].add(lv[0])
                diffs = diffs.at[bounds[:-1]].add(lv[1:] - lv[:-1],
                                                  mode="drop")
                delta = jnp.cumsum(diffs)
                if bag_rows:
                    delta = jnp.concatenate(
                        [delta[:bag_rows], oob.astype(jnp.float32)])
                sc_new = merge(jax.lax.dynamic_slice(
                    arena, (jnp.int32(base), dst), (3, n))) + shrink * delta
                arena = jax.lax.dynamic_update_slice(
                    arena, jnp.stack(_pp.split_f32(sc_new)).astype(
                        _pp.ARENA_DT), (jnp.int32(base), dst))
            with jax.named_scope("lgbm.finish"):
                ivec, fvec = grow_ops.pack_tree_arrays(arrays)
                ivec = jnp.concatenate(
                    [ivec, trunc.astype(jnp.int32)[None]])
            vscores = valid_score.add_tree(
                vscores, 0, vbins, arrays, shrink, num_bins, default_bins,
                vbundles)
            return ivec, fvec, arena, vscores

        return jax.jit(fused, donate_argnums=(0, 16))

    def _run_fused_iter_carried(self):
        key = (self.config.num_leaves, self.config.max_depth, self.max_bin,
               self.config.max_cat_threshold, self._bag_in_use())
        if (getattr(self, "_carried_fn", None) is None
                or getattr(self, "_carried_key", None) != key):
            self._carried_fn = self._build_fused_iter_carried()
            self._carried_key = key
        sh = jnp.asarray(self.shrinkage_rate, self.dtype)
        fmask = self._feature_sample()
        field_vals = [getattr(h, a) for h, a in self._fused_fields]
        p = self._carry_parity
        root0 = jnp.int32(self._carry_slots[p])
        dst = jnp.int32(self._carry_slots[1 - p])
        from ..ops import quantize as _qz
        qkey = _qz.quantize_key(getattr(self, "_quant_seed", 0), self.iter)
        ivec, fvec, arena, vscores = self._carried_fn(
            self._arena, self._bins_t, root0, dst, field_vals,
            self._row0(), fmask,
            self.train_state.num_bins, self.train_state.default_bins,
            self.train_state.missing_types, self.split_params,
            self.monotone, self.penalty, self.train_state.bundle, sh, qkey,
            *self._valid_args())
        self._store_valid_scores(vscores)
        if not getattr(self, "_fused_validated", False):
            with obs_scaling.exempt():   # one-shot fault-surfacing sync
                int(ivec[-1])
            self._fused_validated = True
        self._arena = arena
        self._carry_parity = 1 - p
        self._last_truncated = jnp.asarray(False)
        self.train_state.defer_score(self._materialize_carried_score)
        self.train_state._score_written = False   # defer isn't a write
        return [(ivec, fvec)]

    def _materialize_carried_score(self):
        """Row-ordered [1, n] score from the arena's rowid + score
        planes (one sort; only paid when something reads the score)."""
        from ..ops import partition_pallas as _pp
        if getattr(self, "_carry_mat_fn", None) is None:
            n = self._bins_t.shape[1]
            base = self._carry_base
            fp6 = _pp.feature_channels(self._bins_t.shape[0]) + 6
            dtype = self.dtype

            @jax.jit
            def mat(arena, root):
                rid_pl = jax.lax.dynamic_slice(
                    arena, (jnp.int32(fp6), root), (3, n))
                rid = (rid_pl[0].astype(jnp.float32) * 65536.0
                       + rid_pl[1].astype(jnp.float32) * 256.0
                       + rid_pl[2].astype(jnp.float32)).astype(jnp.int32)
                sc_pl = jax.lax.dynamic_slice(
                    arena, (jnp.int32(base), root), (3, n))
                sc = (sc_pl[0].astype(jnp.float32)
                      + sc_pl[1].astype(jnp.float32)
                      + sc_pl[2].astype(jnp.float32))
                _, sv = jax.lax.sort((rid, sc), num_keys=1)
                return sv[None, :].astype(dtype)

            self._carry_mat_fn = mat
        with obs_tracing.span("materialize_score", "train"):
            return self._carry_mat_fn(
                self._arena,
                jnp.int32(self._carry_slots[self._carry_parity]))

    def _rebuild_train_score(self):
        """Recompute training scores from the materialized model (a
        drain that rolls back degenerate iterations needs it)."""
        st = self.train_state
        st.score = jnp.zeros((max(self.num_tree_per_iteration, 1),
                              self.num_data), self.dtype)
        if self.train_set.metadata.init_score is not None:
            self._apply_init_scores()
        k = max(self.num_tree_per_iteration, 1)
        for i, tree in enumerate(self.models):
            if tree is not None:
                self._update_train_score_full(tree, i % k)

    def _rebuild_valid_scores(self):
        """Replay the full model onto every attached validation set's
        scores — needed when the ensemble changes other than by boosting
        (e.g. LGBM_BoosterMerge), or eval reports pre-change metrics."""
        k = max(self.num_tree_per_iteration, 1)
        for _name, state, _metrics in self.valid_states:
            state.score = jnp.zeros((k, state.ds.num_data), self.dtype)
            if state.ds.metadata.init_score is not None:
                init = _expand_init_score(state.ds.metadata.init_score,
                                          k, state.ds.num_data)
                state.score = state.score + jnp.asarray(init, self.dtype)
            for i, tree in enumerate(self.models):
                if tree is not None:
                    _add_tree_score(state, tree, i % k, self)

    def _pack_tree_with_flag(self, arrays):
        """Pack TreeArrays into (ivec, fvec) for one bulk host fetch; the
        partition engine's arena-truncation bool rides the int vector (a
        separate scalar read would pay a full host round-trip per tree)."""
        packed = grow_ops.pack_tree_arrays(arrays)
        if self._last_truncated is not None:
            packed = (jnp.concatenate(
                [packed[0], self._last_truncated.astype(jnp.int32)[None]]),
                packed[1])
        return packed

    def _emit_truncation_warning(self, num_leaves: int) -> None:
        if self._truncation_warned:
            return
        self._truncation_warned = True
        log.warning("Tree growth truncated at %d leaves by partition-"
                    "arena overflow; raise tpu_arena_factor (or use "
                    "tpu_tree_engine=label)", num_leaves)

    def _update_train_score_device(self, arrays, class_id: int, leaf_ids):
        """Score update straight from device TreeArrays (deferred path) —
        equivalent to shrink + _update_train_score on the host tree."""
        if getattr(self, "_last_emit", "leaf_ids") == "score":
            # leaf values already scattered per row by the grow kernel
            self.train_state.score = self.train_state.score.at[class_id].add(
                jnp.asarray(self.shrinkage_rate, self.dtype) * leaf_ids)
            return
        lv = arrays.leaf_value * jnp.asarray(self.shrinkage_rate, self.dtype)
        lids = leaf_ids
        if self._bag_mask is not None:
            with obs_tracing.span("oob_walk", "train"):
                walked = grow_ops.predict_leaf_inner(
                    self.train_state.bins, arrays,
                    self.train_state.num_bins,
                    self.train_state.default_bins, self.train_state.bundle)
                lids = jnp.where(lids >= 0, lids, walked)
        self.train_state.score = self.train_state.score.at[class_id].add(
            lv[jnp.clip(lids, 0, arrays.max_leaves - 1)])

    def _record_split_ledger(self, tree: Tree, iteration: int,
                             slot: int) -> float:
        """A trained tree has reached the host: its split ledger into the
        process's ring (obs/device.py).  The one helper of the three
        sites that materialise a trained tree (the drain, the unfused
        spine's fetch, rf.py); loading a model comes nowhere near it.
        Returns the tree's passes over the rows."""
        partition_rows, histogram_rows = tree.split_ledger()
        obs_device.record_split_ledger(iteration, slot, self.num_data,
                                       partition_rows, histogram_rows)
        return float(partition_rows.sum()) / max(self.num_data, 1)

    def _drain_inflight(self) -> bool:
        """Materialize pending deferred trees (possibly several
        iterations' worth).  Returns True when a drained iteration was
        degenerate (no splittable leaves): its models and every later
        pending tree are removed and the iteration count rolled back,
        mirroring the eager stop.  Later pending iterations are
        necessarily degenerate too — the degenerate iteration added zero
        leaf values, so they trained on identical scores — and their
        device score updates were all zero, so scores need no undo."""
        with self.profiler.phase("drain_inflight") as span:
            if not self._inflight:
                return False
            pending, self._inflight = self._inflight, []
            stopped, row_passes = self._materialize(pending)
            # tree depth beside the drain, in an operator's trace
            span.set_metadata(trees=len(pending), row_passes=row_passes)
            return stopped

    def _materialize(self, pending) -> Tuple[bool, float]:
        """(a drained iteration was degenerate, the drained trees' passes
        over the rows) of _drain_inflight."""
        k = self.num_tree_per_iteration
        row_passes = 0.0
        groups: Dict[int, list] = {}
        for ent in pending:
            groups.setdefault(ent["it"], []).append(ent)
        for it in sorted(groups):
            any_grew = False
            for ent in groups[it]:
                ivec, fvec = (np.asarray(ent["packed"][0]),
                              np.asarray(ent["packed"][1]))
                host_arrays = grow_ops.unpack_tree_vectors(
                    ivec, fvec, ent["max_leaves"], ent["cat_bins"])
                if ent.get("has_trunc_flag") and ivec[-1]:
                    self._emit_truncation_warning(int(host_arrays.num_leaves))
                new_tree = Tree(1)
                if int(host_arrays.num_leaves) > 1:
                    new_tree = Tree.from_arrays(host_arrays, self.train_set)
                    new_tree.shrink(self.shrinkage_rate)
                    if abs(ent["init_score"]) > K_EPSILON:
                        new_tree.add_bias(ent["init_score"])
                    any_grew = True
                elif ent["slot"] < k:
                    # degenerate FIRST iteration keeps the boost-from-average
                    # prior as a constant tree, like the eager else-branch
                    new_tree.as_constant(ent["init_score"])
                    for st in [self.train_state] + [
                            vs for _n, vs, _m in self.valid_states]:
                        st.add_constant(ent["init_score"],
                                        ent["slot"] % max(k, 1))
                row_passes += self._record_split_ledger(new_tree, it,
                                                        ent["slot"])
                self.models[ent["slot"]] = new_tree
            if not any_grew:
                log.warning("Stopped training because there are no more "
                            "leaves that meet the split requirements")
                first_slot = min(e["slot"] for e in groups[it])
                # the very first iteration's constant trees are kept,
                # like the eager path
                del self.models[max(first_slot, k):]
                self.iter = it
                # under stochastic row sampling (GOSS/bagging) iterations
                # AFTER a degenerate one can still have grown real trees
                # whose device score updates were applied before this
                # rollback deleted them — recompute the training scores
                # from the surviving model so post-stop metrics and any
                # further training see a consistent state
                self._rebuild_train_score()
                return True, row_passes
        return False, row_passes

    def _load_forced_splits(self) -> tuple:
        """forcedsplits_filename JSON -> static BFS plan of
        (leaf_id, inner_feature, threshold_bin, default_left) tuples
        (ForceSplits, serial_tree_learner.cpp:593-751).  Real-valued
        thresholds are mapped to bins host-side with the BinMapper."""
        fname = self.config.forcedsplits_filename
        if not fname:
            return ()
        import json
        from collections import deque

        with v_open(fname) as f:
            root = json.load(f)
        if not root:
            return ()
        raw_to_inner = {raw: inner for inner, raw in
                        enumerate(self.train_set.real_feature_index)}
        plan = []
        num_leaves = 1
        q = deque([(0, root)])
        while q:
            leaf, node = q.popleft()
            raw_f = int(node["feature"])
            if raw_f not in raw_to_inner:
                log.warning("forced split on unused feature %d skipped", raw_f)
                continue
            inner = raw_to_inner[raw_f]
            mapper = self.train_set.bin_mappers[inner]
            thr_bin = int(mapper.value_to_bin(float(node["threshold"])))
            plan.append((leaf, inner, thr_bin,
                         bool(node.get("default_left", False))))
            right_leaf = num_leaves
            num_leaves += 1
            if "left" in node and node["left"]:
                q.append((leaf, node["left"]))
            if "right" in node and node["right"]:
                q.append((right_leaf, node["right"]))
        return tuple(plan)

    def _setup_tree_engine(self) -> None:
        """Choose label vs partition growth engine (config.tpu_tree_engine).

        The partition engine (ops/grow_partition.py: arena-resident rows,
        O(child) per split) is the TPU fast path; the label engine keeps
        full generality (CPU/f64/categorical/distributed learners)."""
        cfg = self.config
        eng = cfg.tpu_tree_engine
        # the space the split scan's rows are in, decided in this function
        # and nowhere else: the plan says it, _bundle_maps builds that
        # space's map, and the grow loop follows the map it is given
        self._scan_space = "feature"
        base_ok = (self.dtype == jnp.float32
                   and self.max_bin <= 256
                   and self.train_set.num_features > 0
                   and self.num_data < (1 << 24))
        if self._grower is not None:
            # distributed learners: the partition engine runs under
            # shard_map inside ParallelGrower (local arenas per device,
            # all three modes); forced splits / CEGB stay on the label
            # engine (leaf-indexed cache injection + coupled penalties
            # are serial-path features, matching the reference where
            # they live in SerialTreeLearner)
            self._use_partition_engine = False
            self._bins_t = None
            self._last_truncated = None
            self._truncation_warned = False
            self._hist_slots = 0
            backend = self._grower.collective.backend
            grower_ok = (base_ok and not self._forced_splits
                         and self._cegb_coupled is None)
            if eng == "partition" and not grower_ok:
                log.warning("tpu_tree_engine=partition not applicable to "
                            "this distributed config; using label engine")
            if backend in ("socket", "hybrid") and not grower_ok:
                log.fatal("the %s collective backend requires the "
                          "partition engine (f32, max_bin<=256, no forced "
                          "splits/coupled CEGB); this config is not "
                          "eligible" % backend)
            # the socket/hybrid backends have no label-engine path, so
            # they imply the partition engine regardless of
            # tpu_tree_engine
            want = (eng == "partition" or backend in ("socket", "hybrid")
                    or (eng == "auto" and on_tpu()))
            partition_on = grower_ok and want
            if partition_on:
                self._grower.enable_partition(
                    arena_factor=max(cfg.tpu_arena_factor, 4))
            else:
                self._grower.disable_partition()
            # quantized distributed training: legal whenever the grower
            # runs the partition engine — the collective backend agrees
            # the code scales globally (ops/quantize.global_scales), so
            # the psum'd integer histograms stay synchronized.  Only a
            # label-engine grower still clears the flag.
            self._quantized = bool(cfg.tpu_quantized_grad and partition_on)
            self._quant_seed = int(cfg.tpu_quantized_seed or cfg.seed)
            if cfg.tpu_quantized_grad and not self._quantized:
                log.warning("tpu_quantized_grad requires the partition "
                            "engine, which is unavailable under the %s "
                            "collective backend for this config; training "
                            "unquantized on the label engine", backend)
            return
        eligible = base_ok
        if eng == "partition" and not eligible:
            log.warning("tpu_tree_engine=partition not applicable here "
                        "(needs serial learner, f32, max_bin<=256); "
                        "using label engine")
            eng = "label"
        from ..ops import partition_pallas as pp
        # the arena stores the (possibly EFB-bundled) GROUP columns
        n_groups = (self.train_state.bins.shape[1]
                    if self.train_set.num_features else 1)
        # pristine layout reserves the read-only pristine block + the
        # redirected root copy before the bump region — needs factor >= 4
        # (a user-set tpu_arena_factor=3, the legacy minimum, would
        # silently halve the child-segment budget and truncate trees)
        C, cap = pp.arena_geometry(self.num_data, n_groups,
                                   max(cfg.tpu_arena_factor, 4))
        # histogram pooling (HistogramPool, feature_histogram.hpp:646-818):
        # bound the per-leaf histogram cache by histogram_pool_size MB (or
        # auto-cap at a fraction of HBM for wide datasets) — spilled
        # parents are recomputed from their arena segments
        L = max(self.config.num_leaves, 2)
        entry_bytes = n_groups * max(self.max_bin, 2) * 3 * 4
        budget = _device_memory_budget()
        pool_mb = cfg.histogram_pool_size
        if pool_mb > 0:
            slots = int(pool_mb * (1 << 20) / max(entry_bytes, 1))
        elif L * entry_bytes > 0.25 * budget:
            slots = int(0.25 * budget / max(entry_bytes, 1))
        else:
            slots = L
        self._hist_slots = 0 if slots >= L else max(4, slots)
        pooling_blocked = False
        if self._forced_splits and self._hist_slots:
            # the forced-split injection indexes the histogram cache by
            # leaf id, which requires the dense (one slot per leaf) cache
            self._hist_slots = 0
            pooling_blocked = True
        hist_cache_bytes = (self._hist_slots or L) * entry_bytes
        arena_bytes = (C * cap * 2 + self.num_data * C * 2
                       + hist_cache_bytes)      # bf16 arena + bins_t + hists
        # width bounds no engine choice: the arena kernels cut a wide arena
        # into channel blocks (ops/partition_pallas.engine_plan).  What
        # bounds the partition engine is device memory.
        plan = None
        if eng == "auto":
            bounds = (
                ("needs f32, max_bin <= 256, > 0 features and < 2^24 rows",
                 eligible),
                ("arena %.2f GB >= %.2f GB device budget"
                 % (arena_bytes / 1e9, budget / 1e9), arena_bytes < budget))
            failed = [why for why, ok in bounds if not ok]
            if not on_tpu():
                eng = "label"      # Mosaic kernels lower on a TPU only
            elif failed:
                # a ~10x slower path nobody asked for: say so, and why
                log.warning("tpu_tree_engine=auto is using the label "
                            "engine on this TPU: %s", "; ".join(failed))
                eng = "label"
            else:
                eng = "partition"
        if eng == "partition":
            try:
                plan = pp.engine_plan(n_groups, max(self.max_bin, 2),
                                      bool(cfg.tpu_quantized_grad))
            except ValueError as e:
                # a width the kernels cannot serve is an error, never a
                # quiet move to the slow engine
                log.fatal("the partition engine has no block plan for %d "
                          "columns: %s" % (n_groups, e))
            if (self.train_set.bundle is not None
                    and self.is_categorical is None):
                # the serial partition engine's numerical scan reads the
                # bundled histogram itself; the label engine, a grower and
                # the categorical scan unbundle to one row per feature
                self._scan_space = "group"
            plan.update(arena_bytes=C * cap * 2,
                        bins_t_bytes=self.num_data * n_groups * 2,
                        hist_cache_bytes=hist_cache_bytes,
                        device_budget_bytes=budget,
                        # the arena's columns, the data set's, and which of
                        # the two the split scan's rows are
                        groups=n_groups,
                        features=self.train_set.num_features,
                        scan_space=self._scan_space,
                        # what the objective moves on the device each
                        # iteration (lambdarank's query windows)
                        **getattr(self.objective, "device_plan", {}))
            if self._bag_in_use():
                # rows every tree grows on: the bag's (ops/bagging.py)
                plan.update(bag_rows=self._bag_in_use())
            log.info("partition engine plan: %s", ", ".join(
                "%s=%s" % kv for kv in plan.items()))
        self._engine_plan = plan
        self._use_partition_engine = eng == "partition"
        if pooling_blocked and self._use_partition_engine:
            log.warning("forced splits disable histogram pooling (dense "
                        "per-leaf cache required)")
        self._bins_t = None
        self._last_truncated = None     # device bool from the last grown tree
        self._truncation_warned = False
        self._quantized = bool(cfg.tpu_quantized_grad
                               and self._use_partition_engine)
        self._quant_seed = int(cfg.tpu_quantized_seed or cfg.seed)
        if cfg.tpu_quantized_grad and not self._use_partition_engine:
            log.warning("tpu_quantized_grad requires the partition engine; "
                        "training unquantized on the label engine")
        if self._quantized:
            from ..ops import quantize as _qz
            bits = int(cfg.tpu_quantized_bits)
            if not _qz.overflow_safe(self.num_data, bits=bits):
                # bin-count-aware guard: only the FULLEST bin's occupancy
                # bounds integer exactness, and n rows is its worst case
                log.warning(
                    "tpu_quantized_grad: %d rows exceed the single-bin "
                    "integer-exactness envelope (%d rows/bin); histogram "
                    "code sums may round in f32 if one bin captures more "
                    "than that (docs/Quantized.md)",
                    self.num_data, _qz.exact_rows(bits))
        if self._use_partition_engine:
            from ..ops import grow_partition as gp
            from ..ops import partition_pallas as _pp
            # the span's arguments are the plan: what was derived from the
            # width rides the profiler's trace beside what it sized
            with obs_tracing.span("engine_plan", "setup", **plan):
                # An arena may be most of the chip.  What an earlier
                # booster of this process still holds through reference
                # cycles (its own arena and histogram cache) is let go
                # first, and the feature-major bins are one program's
                # output, waited for: no row-major copy in the arena's
                # type is still alive when the arena is allocated.  Else
                # the peak follows the host's timing (400 000 x 2 000:
                # 12.27, 13.86 or 16.36 GB of 16.9, by how long ago the
                # collector ran; PERF.md, PR 27).
                gc.collect()
                self._bins_t = jax.block_until_ready(
                    _pp.feature_major(self.train_state.bins))
                # pristine layout: bins + rowid planes written ONCE here;
                # per-tree assembly refreshes only the g/h payload planes
                # and the first split is redirected off the pristine block
                self._arena = _pp.init_pristine(
                    jnp.zeros((C, cap), _pp.ARENA_DT), self._bins_t)
            from functools import partial as _ppart
            self._grow_partition = _ppart(gp.grow_tree_partition,
                                          pristine=True)

    def _grow_one_tree(self, grad, hess, row_init):
        """Grow one tree via the selected learner (serial or distributed) —
        the single dispatch point shared by GBDT/DART/GOSS/RF."""
        cegb_used = (jnp.asarray(self._cegb_used)
                     if self._cegb_coupled is not None else None)
        if self._use_partition_engine:
            self._last_emit = ("score" if (getattr(self, "_score_emit_ok",
                                                   False)
                                           and self._bag_mask is None)
                               else "leaf_ids")
            g_in, h_in, qsc = grad, hess, None
            if self._quantized:
                from ..ops import quantize as _qz
                g_in, h_in, _gs, _hs = _qz.quantize_gradients(
                    grad, hess,
                    _qz.quantize_key(self._quant_seed, self.iter))
                qsc = (_gs, _hs)
            arrays, out, self._arena, self._last_truncated = \
                self._grow_partition(
                    self._arena, self._bins_t, g_in, h_in, row_init,
                    self._feature_sample(),
                    self.train_state.num_bins, self.train_state.default_bins,
                    self.train_state.missing_types,
                    self.split_params, self.monotone, self.penalty,
                    self._cegb_coupled, cegb_used,
                    self.is_categorical, self.train_state.bundle,
                    max_leaves=self.config.num_leaves,
                    max_depth=self.config.max_depth,
                    max_bin=self.max_bin,
                    emit=self._last_emit,
                    full_bag=self._bag_mask is None,
                    max_cat_threshold=self.config.max_cat_threshold,
                    hist_slots=self._hist_slots,
                    forced_splits=self._forced_splits,
                    quantized=self._quantized, quant_scales=qsc,
                    interpret=pallas_interpret())
            if not getattr(self, "_partition_validated", False):
                # force materialization once: async dispatch would
                # otherwise surface a device runtime fault later, at an
                # unrelated device_get (one host round trip, first tree
                # only).  No guard: the failure propagates.
                with obs_scaling.exempt():
                    int(arrays.num_leaves)
                self._partition_validated = True
            return arrays, out
        self._last_emit = "leaf_ids"
        grow_fn = (self._grower if self._grower is not None
                   else grow_ops.grow_tree)
        from functools import partial as _partial
        if self._grower is None and self._cegb_coupled is not None:
            grow_fn = _partial(grow_fn, cegb_coupled=self._cegb_coupled,
                               cegb_used_init=cegb_used)
        if self._grower is None and self._forced_splits:
            grow_fn = _partial(grow_fn, forced_splits=self._forced_splits)
        g_in, h_in, extra = grad, hess, {}
        if self._grower is not None and getattr(self, "_quantized", False):
            # distributed quantized path: code scales must be agreed
            # across the world BEFORE encoding (ops/quantize docstring)
            from ..ops import quantize as _qz
            coll = self._grower.collective
            key = _qz.quantize_key(self._quant_seed, self.iter)
            if coll.backend == "mesh":
                # single controller: host grad/hess are already global,
                # so global quantization IS the serial computation —
                # mesh quantized training is bitwise-identical to serial
                g_in, h_in, _gs, _hs = _qz.quantize_gradients(grad, hess,
                                                              key)
            else:
                _gs, _hs = _qz.global_scales(grad, hess, coll)
                ids = getattr(self.train_set, "dist_row_ids", None)
                if ids is not None and len(ids) == int(grad.shape[0]):
                    # randomly pre-partitioned shard: gather the noise
                    # at this rank's global row indices
                    g_in, h_in = _qz.encode_with_scales(
                        grad, hess, key, _gs, _hs,
                        global_rows=self.train_set.dist_global_rows,
                        row_ids=ids)
                else:
                    global_n, row0 = coll.row_layout(int(grad.shape[0]))
                    g_in, h_in = _qz.encode_with_scales(
                        grad, hess, key, _gs, _hs,
                        global_rows=global_n, row_start=row0)
            extra = dict(quantized=True, quant_scales=(_gs, _hs))
        result = grow_fn(
            self.train_state.bins, g_in, h_in, row_init,
            self._feature_sample(),
            self.train_state.num_bins, self.train_state.default_bins,
            self.train_state.missing_types,
            self.split_params, self.monotone, self.penalty,
            self.is_categorical,
            bundle=self.train_state.bundle,
            max_leaves=self.config.num_leaves,
            max_depth=self.config.max_depth,
            max_bin=self.max_bin,
            hist_impl=self.config.tpu_histogram_impl,
            rows_per_chunk=self.config.tpu_rows_per_tile,
            max_cat_threshold=self.config.max_cat_threshold,
            **extra)
        if self._grower is not None:
            # the grower's shard_map'd partition path reports arena
            # truncation the same way the serial path does — surface it
            # so the "raise tpu_arena_factor" warning fires here too
            self._last_truncated = self._grower.last_truncated
        return result

    def _sample_gradients(self, grad: jnp.ndarray, hess: jnp.ndarray):
        """Per-iteration gradient/row sampling hook (overridden by GOSS)."""
        return grad, hess

    def _global_init_score(self, class_id: int) -> float:
        """Init score for boost_from_average, synced across ranks.

        On the socket/hybrid paths the objective sees only the
        rank-local shard, so boost_from_score would seed every rank from
        a different average (the C++ reference syncs it through
        Network::GlobalSyncUpBy*).  Allreduce the objective's sufficient
        statistics and recompute from the totals; objectives without
        compact stats (percentile-based) fall back to the rank-local
        score."""
        coll = self._grower.collective if self._grower is not None else None
        backend = getattr(coll, "backend", "none")
        if (coll is None or backend not in ("socket", "hybrid")
                or coll.world <= 1):
            return self.objective.boost_from_score(class_id)
        stats = self.objective.boost_stats(class_id)
        if stats is None:
            if self.objective.name in ("regression_l1", "quantile", "mape"):
                log.warning(
                    "boost_from_average: %s has no distributable sufficient "
                    "statistics; using the rank-local init score",
                    self.objective.name)
            return self.objective.boost_from_score(class_id)
        total = coll.allreduce(np.asarray(stats, np.float64), op="sum")
        return self.objective.boost_from_stats(total, class_id)

    def _boost_from_average(self, class_id: int) -> float:
        if self.models or self.objective is None:
            return 0.0
        if self.train_set.metadata.init_score is not None:
            return 0.0  # already seeded at setup
        if self.config.boost_from_average or self.train_set.num_features == 0:
            init_score = self._global_init_score(class_id)
            if abs(init_score) > K_EPSILON:
                self.train_state.add_constant(init_score, class_id)
                for _, vs, _m in self.valid_states:
                    vs.add_constant(init_score, class_id)
                log.info("Start training from score %f", init_score)
                return init_score
        elif self.objective.name in ("regression_l1", "quantile", "mape"):
            log.warning("Disabling boost_from_average in %s may cause the slow "
                        "convergence", self.objective.name)
        return 0.0

    def _apply_init_scores(self) -> None:
        init = _expand_init_score(self.train_set.metadata.init_score,
                                  self.num_tree_per_iteration, self.num_data)
        self.train_state.score = self.train_state.score + jnp.asarray(init, self.dtype)

    def _renew_tree_output(self, tree: Tree, class_id: int,
                           leaf_ids) -> None:
        """Percentile leaf refits for L1-family objectives
        (serial_tree_learner.cpp:850-928), all leaves in one device pass
        (ops/quantile.py) — the reference scans rows per leaf on host."""
        obj = self.objective
        if obj is None or not obj.is_renew_tree_output():
            return
        from ..ops.quantile import renew_leaf_percentiles
        label = jnp.asarray(self.train_set.metadata.label, self.dtype)
        residual = label - jnp.asarray(
            self._renew_baseline_score(class_id), self.dtype)
        weights = (jnp.asarray(self.train_set.metadata.weights, self.dtype)
                   if self.train_set.metadata.weights is not None else None)
        if obj.name == "mape":
            weights = jnp.asarray(obj.label_weight, self.dtype)
        alpha = float(getattr(obj, "alpha", 0.5))
        vals = renew_leaf_percentiles(
            residual, jnp.asarray(leaf_ids), jnp.asarray(alpha, self.dtype),
            L=self.config.num_leaves, weights=weights)
        nl = tree.num_leaves
        tree.leaf_value[:nl] = np.asarray(vals, np.float64)[:nl]

    def _renew_tree_output_host(self, tree: Tree, class_id: int,
                                leaf_ids) -> None:
        """Numpy per-leaf path (parity oracle for renew_leaf_percentiles)."""
        obj = self.objective
        if obj is None or not obj.is_renew_tree_output():
            return
        label = np.asarray(self.train_set.metadata.label, np.float64)
        residual = label - np.asarray(self._renew_baseline_score(class_id),
                                      np.float64)
        lids = np.asarray(leaf_ids)
        weights = (np.asarray(self.train_set.metadata.weights, np.float64)
                   if self.train_set.metadata.weights is not None else None)
        if obj.name == "mape":
            weights = np.asarray(obj.label_weight, np.float64)
        for leaf in range(tree.num_leaves):
            rows = np.flatnonzero(lids == leaf)
            if len(rows) == 0:
                continue
            res = residual[rows]
            w = weights[rows] if weights is not None else None
            tree.leaf_value[leaf] = obj._renew_percentile(res, w)

    def _renew_baseline_score(self, class_id: int):
        """Score baseline for percentile leaf refits (device array; no
        host transfer); RF overrides with its constant init score
        (rf.hpp:126 passes init_scores_[class])."""
        return self.train_state.score[class_id]

    # ------------------------------------------------------------------ #
    # Score updates (ScoreUpdater::AddScore paths)
    # ------------------------------------------------------------------ #
    def _update_train_score(self, tree: Tree, class_id: int, arrays, leaf_ids):
        leaf_values = jnp.asarray(tree.leaf_value[:max(tree.num_leaves, 1)],
                                  self.dtype)
        lids = leaf_ids
        if self._bag_mask is not None:
            # out-of-bag rows need a traversal (gbdt.cpp UpdateScore OOB path)
            walked = grow_ops.predict_leaf_inner(
                self.train_state.bins, arrays, self.train_state.num_bins,
                self.train_state.default_bins, self.train_state.bundle)
            lids = jnp.where(lids >= 0, lids, walked)
        self.train_state.score = self.train_state.score.at[class_id].add(
            leaf_values[jnp.clip(lids, 0, tree.num_leaves - 1)])

    def _update_valid_scores(self, tree: Tree, class_id: int):
        for _, vs, _m in self.valid_states:
            _add_tree_score(vs, tree, class_id, self)

    # ------------------------------------------------------------------ #
    # Evaluation (gbdt.cpp:476-533)
    # ------------------------------------------------------------------ #
    def _sync_model(self) -> None:
        """Materialize any deferred trees before the model is read; a stop
        detected here must still end training on the next update."""
        with obs_tracing.span("sync_model", "train"):
            if self._drain_inflight():
                self._deferred_stopped = True

    def eval_train(self) -> Dict[str, List[float]]:
        self._sync_model()
        return self._eval_state(self.train_state, self.train_metrics)

    def eval_valid(self) -> Dict[str, Dict[str, List[float]]]:
        # no _sync_model: the validation scores are on the device whichever
        # spine updated them, and a metric reads nothing of self.models
        with obs_tracing.span("eval_valid", "eval"):
            return {name: self._eval_state(vs, metrics)
                    for name, vs, metrics in self.valid_states}

    def _eval_state(self, state: _DatasetState, metrics) -> Dict[str, List[float]]:
        """A metric whose class evaluates on the device (metric.py
        `eval_device`) hands back a few sums; every other metric is fed
        the score vector, fetched once for all of them and only if one
        asks.  One blocking read brings both."""
        if not metrics:
            return {}
        sums = [m.eval_device(state.score, self.objective)
                if self.num_tree_per_iteration == 1 else None
                for m in metrics]
        host_fed = any(s is None for s in sums)
        with obs_tracing.span("valid/metric_fetch", "eval"), \
                self.profiler.phase("metric_eval(fetch)"):
            sums, score = jax.device_get(
                (sums, state.score if host_fed else None))
        if host_fed:
            score = np.asarray(score, np.float64)
            flat = (score.reshape(-1) if self.num_tree_per_iteration > 1
                    else score[0])
        return {m.name: (m.eval(flat, self.objective) if s is None
                         else m.finish_device(s))
                for m, s in zip(metrics, sums)}

    # ------------------------------------------------------------------ #
    # Prediction on raw features (gbdt_prediction.cpp)
    # ------------------------------------------------------------------ #
    def predict_raw(self, X: np.ndarray, num_iteration: int = -1,
                    early_stop: bool = False, early_stop_freq: int = 10,
                    early_stop_margin: float = 10.0,
                    device: Optional[bool] = None) -> np.ndarray:
        """device: None = auto by MIN_DEVICE_WORK; True forces the
        batched device ensemble (host walk only if the ensemble cannot
        build); False forces the host walk (the serving fallback path
        needs the choice pinned per batch, not per global threshold)."""
        self._sync_model()
        from ..io.dataset import _issparse
        if _issparse(X):
            # chunked densify: sparse inputs predict without ever holding
            # the full dense matrix (c_api.cpp CSR predict analogue)
            step = max(1, (1 << 24) // max(X.shape[1], 1))
            parts = [self.predict_raw(
                np.asarray(X[i:i + step].todense()), num_iteration,
                early_stop=early_stop, early_stop_freq=early_stop_freq,
                early_stop_margin=early_stop_margin, device=device)
                for i in range(0, X.shape[0], step)]
            return np.concatenate(parts, axis=0)
        X = np.ascontiguousarray(np.asarray(X, np.float64))
        if X.ndim != 2 or X.shape[1] <= self.max_feature_idx:
            log.fatal("The number of features in data (%d) is not the same as "
                      "it was in training data (%d)"
                      % (X.shape[1] if X.ndim == 2 else 0,
                         self.max_feature_idx + 1))
        k = self.num_tree_per_iteration
        total_iters = len(self.models) // max(k, 1)
        iters = total_iters if num_iteration <= 0 else min(num_iteration, total_iters)
        n = X.shape[0]
        # batched device walk for real workloads (gbdt_prediction.cpp
        # redesign, ops/predict.py): all (tree, row) pairs in parallel;
        # the host loop below keeps early-stop and small-input duty
        want_device = (device if device is not None
                       else n * max(len(self.models), 1)
                       >= predict_ops.MIN_DEVICE_WORK)
        if not early_stop and want_device:
            ens = self._device_ensemble()
            if ens is not None:
                out = ens.predict_sum(X, iters)
                if self.average_output:
                    out /= max(iters, 1)
                return out[0] if k == 1 else out.T
        out = np.zeros((k, n), np.float64)
        # margin-based prediction early stop (prediction_early_stop.cpp:
        # 14-89): rows whose margin clears the threshold stop traversing
        # further trees.  The reference counts individual TREES between
        # checks (round_period, gbdt_prediction.cpp traversal loop), so
        # with k trees per iteration the counter advances by k per step.
        use_es = early_stop and not self.average_output and k >= 1
        active = np.ones(n, bool) if use_es else None
        es_counter = 0
        for it in range(iters):
            if use_es and es_counter >= max(early_stop_freq, 1) \
               and active.any():
                es_counter = 0
                if k == 1:
                    # binary margin is 2*|score| (prediction_early_stop
                    # .cpp:30-41)
                    margin = 2.0 * np.abs(out[0])
                else:
                    part = np.partition(out, k - 2, axis=0)
                    margin = part[k - 1] - part[k - 2]  # top1 - top2
                active &= margin < early_stop_margin
                if not active.any():
                    break
            rows = X[active] if use_es else X
            if rows.shape[0] == 0:
                break
            for kk in range(k):
                pred = self.models[it * k + kk].predict(rows)
                if use_es:
                    out[kk, active] += pred
                else:
                    out[kk] += pred
            es_counter += k
        if self.average_output:
            # RF semantics survive model reload (gbdt_model_text.cpp writes
            # the average_output token; rf.hpp averages tree outputs)
            out /= max(iters, 1)
        return out[0] if k == 1 else out.T  # [n] or [n, k]

    def _device_ensemble(self):
        """Cached stacked-ensemble device arrays (rebuilt when the model
        grows or leaf values mutate in place, e.g. refit); None when the
        ensemble cannot run on device (giant categorical ids / node
        counts)."""
        key = (len(self.models), getattr(self, "_model_gen", 0))
        cached = getattr(self, "_dev_ens_cache", None)
        if cached is not None and cached[0] == key:
            return cached[1]
        ens = predict_ops.DeviceEnsemble(self.models,
                                         self.num_tree_per_iteration)
        if not ens.ok:
            ens = None
        self._dev_ens_cache = (key, ens)
        return ens

    def predict(self, X: np.ndarray, num_iteration: int = -1,
                raw_score: bool = False, early_stop: bool = False,
                early_stop_freq: int = 10,
                early_stop_margin: float = 10.0,
                device: Optional[bool] = None) -> np.ndarray:
        raw = self.predict_raw(X, num_iteration, early_stop=early_stop,
                               early_stop_freq=early_stop_freq,
                               early_stop_margin=early_stop_margin,
                               device=device)
        return self._convert_output(raw, raw_score)

    def _convert_output(self, raw: np.ndarray, raw_score: bool) -> np.ndarray:
        if raw_score or self.objective is None:
            return raw
        if self.num_tree_per_iteration > 1:
            return np.asarray(self.objective.convert_output_multi(raw))
        return np.asarray(self.objective.convert_output(jnp.asarray(raw)))

    def predict_bucketed(self, X: np.ndarray, num_iteration: int = -1,
                         raw_score: bool = False,
                         max_bucket: int = 1 << 20,
                         ensemble=None) -> np.ndarray:
        """Serving hot path: rows padded to the power-of-two bucket so
        concurrent request sizes share ONE compiled executable per
        bucket (ops/predict.py predict_bucketed).  Per-row outputs are
        bitwise identical to the device path of predict(); falls back
        to the host walk when the ensemble cannot run on device.

        `ensemble`: dispatch on THIS DeviceEnsemble instead of the
        cached one — the fleet residency manager checks an ensemble out
        under its byte accounting and must not let a concurrent eviction
        trigger a silent (unaccounted) rebuild through the cache."""
        self._sync_model()
        X = np.ascontiguousarray(np.asarray(X, np.float64))
        if X.ndim != 2 or X.shape[1] <= self.max_feature_idx:
            log.fatal("The number of features in data (%d) is not the same as "
                      "it was in training data (%d)"
                      % (X.shape[1] if X.ndim == 2 else 0,
                         self.max_feature_idx + 1))
        ens = ensemble if ensemble is not None else self._device_ensemble()
        if ens is None:
            return self.predict(X, num_iteration, raw_score=raw_score,
                                device=False)
        k = self.num_tree_per_iteration
        total_iters = len(self.models) // max(k, 1)
        iters = (total_iters if num_iteration <= 0
                 else min(num_iteration, total_iters))
        out = ens.predict_bucketed(X, iters, max_bucket=max_bucket)
        if self.average_output:
            out /= max(iters, 1)
        raw = out[0] if k == 1 else out.T
        return self._convert_output(raw, raw_score)

    def get_leaf_output(self, tree_id: int, leaf_id: int) -> float:
        """Raw output of one leaf (Booster.get_leaf_output, python-package
        basic.py -> LGBM_BoosterGetLeafValue)."""
        self._sync_model()
        if not 0 <= tree_id < len(self.models):
            log.fatal("tree_id %d out of range [0, %d)" % (tree_id,
                                                           len(self.models)))
        tree = self.models[tree_id]
        if not 0 <= leaf_id < tree.num_leaves:
            log.fatal("leaf_id %d out of range [0, %d)" % (leaf_id,
                                                           tree.num_leaves))
        return float(tree.leaf_value[leaf_id])

    def model_from_string(self, text: str) -> "GBDT":
        """Replace this booster's model in place from model text — the
        post-constructor reload path (LGBM_BoosterLoadModelFromString
        semantics on an existing handle); caches (device ensemble,
        fused trace) are invalidated by load_model_from_string."""
        self.load_model_from_string(text)
        return self

    def predict_contrib(self, X: np.ndarray, num_iteration: int = -1) -> np.ndarray:
        self._sync_model()
        from .shap import predict_contrib as _shap
        return _shap(self, X, num_iteration)

    def predict_leaf_index(self, X: np.ndarray, num_iteration: int = -1) -> np.ndarray:
        self._sync_model()
        X = _dense_matrix(X)
        k = self.num_tree_per_iteration
        total_iters = len(self.models) // max(k, 1)
        iters = total_iters if num_iteration <= 0 else min(num_iteration, total_iters)
        out = np.zeros((X.shape[0], iters * k), np.int32)
        for i in range(iters * k):
            out[:, i] = self.models[i].predict_leaf_index(X)
        return out

    # ------------------------------------------------------------------ #
    # Importance / model IO
    # ------------------------------------------------------------------ #
    def feature_importance(self, importance_type: str = "split",
                           num_iteration: int = -1) -> np.ndarray:
        self._sync_model()
        n_feat = self.max_feature_idx + 1
        imp = np.zeros(n_feat, np.float64)
        k = max(self.num_tree_per_iteration, 1)
        total_iters = len(self.models) // k
        iters = total_iters if num_iteration <= 0 else min(num_iteration, total_iters)
        for tree in self.models[:iters * k]:
            for node in range(tree.num_leaves - 1):
                if importance_type == "split":
                    imp[tree.split_feature[node]] += 1
                else:
                    imp[tree.split_feature[node]] += max(tree.split_gain[node], 0)
        return imp

    def dump_model(self, num_iteration: int = -1) -> dict:
        """JSON-style model dump (GBDT::DumpModel,
        src/boosting/gbdt_model_text.cpp:15-58)."""
        self._sync_model()
        k = max(self.num_tree_per_iteration, 1)
        total_iters = len(self.models) // k
        iters = total_iters if num_iteration <= 0 else min(num_iteration,
                                                           total_iters)
        return {
            "name": "tree",
            "version": "v2",
            "num_class": self.num_class,
            "num_tree_per_iteration": self.num_tree_per_iteration,
            "label_index": self.label_idx,
            "max_feature_idx": self.max_feature_idx,
            "objective": (self.objective.to_string()
                          if self.objective is not None else "none"),
            "average_output": self.average_output,
            "feature_names": list(self.feature_names),
            "feature_infos": list(self.feature_infos),
            "tree_info": [self.models[i].to_json(i)
                          for i in range(iters * k)],
        }

    def save_model_to_string(self, start_iteration: int = 0,
                             num_iteration: int = -1) -> str:
        self._sync_model()
        ss = [self.sub_model_name, "version=v2",
              "num_class=%d" % self.num_class,
              "num_tree_per_iteration=%d" % self.num_tree_per_iteration,
              "label_index=%d" % self.label_idx,
              "max_feature_idx=%d" % self.max_feature_idx]
        if self.objective is not None:
            ss.append("objective=%s" % self.objective.to_string())
        if self.average_output:
            ss.append("average_output")
        ss.append("feature_names=" + " ".join(self.feature_names))
        ss.append("feature_infos=" + " ".join(self.feature_infos))

        k = max(self.num_tree_per_iteration, 1)
        total_iteration = len(self.models) // k
        start_iteration = min(max(start_iteration, 0), total_iteration)
        num_used = len(self.models)
        if num_iteration > 0:
            num_used = min((start_iteration + num_iteration) * k, num_used)
        start_model = start_iteration * k

        tree_strs = []
        for i in range(start_model, num_used):
            tree_strs.append("Tree=%d\n%s\n" % (i - start_model,
                                                self.models[i].to_string()))
        ss.append("tree_sizes=" + " ".join(str(len(s)) for s in tree_strs))
        ss.append("")
        body = "\n".join(ss) + "\n" + "".join(tree_strs) + "end of trees\n"

        imps = self.feature_importance("split", num_iteration)
        pairs = [(int(v), self.feature_names[i]) for i, v in enumerate(imps) if v > 0]
        pairs.sort(key=lambda p: -p[0])
        body += "\nfeature importances:\n"
        body += "".join("%s=%d\n" % (nm, v) for v, nm in pairs)
        return body

    def save_model_to_file(self, filename: str, start_iteration: int = 0,
                           num_iteration: int = -1) -> None:
        # atomic (tmp + fsync + os.replace for local paths): a crash
        # mid-save never leaves a truncated model file behind
        atomic_write_text(
            filename, self.save_model_to_string(start_iteration,
                                                num_iteration))
        log.info("Saved model to %s", filename)

    def load_model_from_string(self, text: str) -> None:
        # replacing the model invalidates any cached device ensemble
        self._model_gen = getattr(self, "_model_gen", 0) + 1
        """LoadModelFromString (gbdt_model_text.cpp:343+)."""
        lines = text.split("\n")
        header: Dict[str, str] = {}
        i = 0
        while i < len(lines):
            line = lines[i].strip()
            if line.startswith("Tree=") or line == "end of trees":
                break
            if "=" in line:
                kk, v = line.split("=", 1)
                header[kk.strip()] = v.strip()
            elif line == "average_output":
                header["average_output"] = "1"
            i += 1
        if "version" not in header or header["version"] != "v2":
            log.warning("Unknown model version %s", header.get("version"))
        self.num_class = int(header.get("num_class", "1"))
        self.num_tree_per_iteration = int(header.get("num_tree_per_iteration",
                                                     str(self.num_class)))
        self.label_idx = int(header.get("label_index", "0"))
        self.max_feature_idx = int(header.get("max_feature_idx", "0"))
        self.average_output = "average_output" in header
        self.feature_names = header.get("feature_names", "").split()
        self.feature_infos = header.get("feature_infos", "").split()
        if "objective" in header and self.objective is None:
            from ..objective import create_objective
            obj_str = header["objective"].split()
            params = {}
            for tok in obj_str[1:]:
                if ":" in tok:
                    pk, pv = tok.split(":", 1)
                    params[{"sigmoid": "sigmoid", "num_class": "num_class",
                            "alpha": "alpha", "tweedie_variance_power":
                            "tweedie_variance_power"}.get(pk, pk)] = pv
            params["num_class"] = params.get("num_class", self.num_class)
            try:
                self.objective = create_objective(obj_str[0], Config(params))
            except Exception:
                self.objective = None
        # parse trees
        self.models = []
        blocks = text.split("Tree=")
        for blk in blocks[1:]:
            body = blk.split("\n\n")[0]
            body = body[body.index("\n") + 1:]  # drop the tree number line
            if "end of trees" in body:
                body = body[:body.index("end of trees")]
            self.models.append(Tree.from_string(body))
        self.iter = len(self.models) // max(self.num_tree_per_iteration, 1)

    # ------------------------------------------------------------------ #
    # Resilience state hooks (lightgbm_tpu/resilience/checkpoint.py)
    # ------------------------------------------------------------------ #
    def capture_aux_state(self) -> Dict:
        """Everything a deterministic resume needs BEYOND the model
        string: round index, shrinkage, and every RNG stream that feeds
        future rounds.  Drains the deferred-tree pipeline first so the
        model string cut right after this is complete."""
        self._sync_model()
        state: Dict = {
            "round": int(self.iter),
            "boosting": type(self).__name__.lower(),
            "shrinkage_rate": float(self.shrinkage_rate),
            "feat_rng": _rng_state_to_json(self._feat_rng),
        }
        state.update(self._aux_state_extra())
        return state

    def restore_aux_state(self, state: Dict) -> None:
        """Inverse of capture_aux_state, applied after
        load_model_from_string on a freshly constructed booster bound to
        the same (identically binned) training set."""
        if int(state["round"]) != self.iter:
            raise ValueError(
                "aux state is for round %d but the loaded model holds %d "
                "iterations" % (int(state["round"]), self.iter))
        self.shrinkage_rate = float(state["shrinkage_rate"])
        self._feat_rng = _rng_state_from_json(state["feat_rng"])
        self._restore_aux_extra(state)

    def _aux_state_extra(self) -> Dict:
        """Subclass hook: persistent state beyond the base RNG streams
        (DART drop history/weights, GOSS sampling key)."""
        return {}

    def _restore_aux_extra(self, state: Dict) -> None:
        """Subclass hook, inverse of _aux_state_extra."""

    def capture_score_arrays(self) -> Dict[str, np.ndarray]:
        """Exact raw score planes for train + every valid set.  Restored
        verbatim (not replayed through tree prediction) so resumed
        gradients match the uninterrupted run to the last ulp."""
        out: Dict[str, np.ndarray] = {}
        if self.train_state is not None:
            out["train"] = np.asarray(self.train_state.score)
        for name, vs, _m in self.valid_states:
            out["valid:%s" % name] = np.asarray(vs.score)
        return out

    def restore_score_arrays(self, scores: Dict[str, np.ndarray]) -> None:
        if self.train_state is not None and "train" in scores:
            self.train_state.score = jnp.asarray(scores["train"])
        for name, vs, _m in self.valid_states:
            key = "valid:%s" % name
            if key in scores:
                vs.score = jnp.asarray(scores[key])

    def rebuild_score_from_raw(self, raw_X: np.ndarray) -> None:
        """Reshard-tolerant train-plane rebuild for elastic resume.

        The exact plane saved by capture_score_arrays is keyed to the
        row shard the checkpoint was cut on; after an elastic
        re-formation this rank holds a DIFFERENT shard, so the plane is
        recomputed instead: the construction-time baseline (zeros plus
        per-row init_score — boost_from_average is baked into tree 0 via
        add_bias, so it rides in with the trees) plus a host raw-score
        walk over the loaded ensemble (text-loaded trees carry no
        bin-space thresholds, so the bin-replay path is unavailable;
        predict_raw's raw-threshold walk is shard-size work once per
        re-formation).  Matches the uninterrupted plane up to float
        summation order, which is what a degraded-world resume can
        promise — the topology itself changed.
        """
        if self.train_state is None:
            return
        n = self.train_state.ds.num_data
        if raw_X is None or len(raw_X) != n:
            raise ValueError(
                "rebuild_score_from_raw needs the raw feature matrix of "
                "this rank's CURRENT shard (%d rows), got %s"
                % (n, "None" if raw_X is None else len(raw_X)))
        k = self.num_tree_per_iteration
        base = np.zeros((k, n), np.float64)
        if self.train_set.metadata.init_score is not None:
            base += np.asarray(_expand_init_score(
                self.train_set.metadata.init_score, k, n), np.float64)
        if self.models:
            pred = np.asarray(self.predict_raw(raw_X, device=False),
                              np.float64)
            base += pred[None, :] if k == 1 else pred.T
        self.train_state.score = jnp.asarray(base, self.dtype)

    # ------------------------------------------------------------------ #
    def refit(self, X: np.ndarray, label: np.ndarray,
              weight=None, group=None) -> None:
        """Renew every tree's leaf values on new data while keeping the
        structure (GBDT::RefitTree, gbdt.cpp:263-286 +
        SerialTreeLearner::FitByExistingTree, serial_tree_learner.cpp:235-265).
        """
        self._sync_model()
        from ..io.metadata import Metadata

        X = _dense_matrix(X)
        n = len(X)
        if self.objective is None:
            log.fatal("Cannot refit without an objective")
        meta = Metadata(n)
        meta.set_label(np.asarray(label))
        if weight is not None:
            meta.set_weights(np.asarray(weight))
        if group is not None:
            meta.set_query(np.asarray(group))
        self.objective.init(meta, n)

        leaf_preds = np.column_stack([
            t.predict_leaf_index(X) if t.num_leaves > 1
            else np.zeros(n, np.int32) for t in self.models])
        self.refit_with_leaf_preds(leaf_preds, n)

    def refit_with_leaf_preds(self, leaf_preds: np.ndarray, n: int) -> None:
        """Renew leaf values from a precomputed [n, num_models] row->leaf
        map (the LGBM_BoosterRefit entry, c_api.cpp) against the
        objective's current labels."""
        from ..ops.split import calculate_splitted_leaf_output
        self._sync_model()
        self._model_gen = getattr(self, "_model_gen", 0) + 1
        k = max(self.num_tree_per_iteration, 1)
        cfg = self.config
        decay = cfg.refit_decay_rate
        score = jnp.zeros((k, n), self.dtype)
        for it in range(len(self.models) // k):
            grad, hess = self.objective.get_gradients(
                score if k > 1 else score[0])
            grad = np.reshape(np.asarray(grad), (k, n))
            hess = np.reshape(np.asarray(hess), (k, n))
            for kk in range(k):
                tree = self.models[it * k + kk]
                lp = leaf_preds[:, it * k + kk]
                nl = tree.num_leaves
                sum_g = np.bincount(lp, weights=grad[kk], minlength=nl)[:nl]
                sum_h = np.bincount(lp, weights=hess[kk], minlength=nl)[:nl] \
                    + K_EPSILON
                out = np.asarray(calculate_splitted_leaf_output(
                    jnp.asarray(sum_g), jnp.asarray(sum_h),
                    cfg.lambda_l1, cfg.lambda_l2, cfg.max_delta_step))
                tree.leaf_value[:nl] = (decay * tree.leaf_value[:nl]
                                        + (1.0 - decay) * out * tree.shrinkage)
                score = score.at[kk].add(
                    jnp.asarray(tree.leaf_value[lp], self.dtype))

    def model_to_if_else(self) -> str:
        self._sync_model()
        """Standalone C++ if-else prediction code for the trained model
        (ModelToIfElse, src/boosting/gbdt_model_text.cpp:60-242)."""
        from .codegen import model_to_if_else
        return model_to_if_else(self)

    def rollback_one_iter(self) -> None:
        self._sync_model()
        # dropping trees invalidates any cached device ensemble
        self._model_gen = getattr(self, "_model_gen", 0) + 1
        if self.iter <= 0:
            return
        k = self.num_tree_per_iteration
        for kk in range(k):
            tree = self.models[-k + kk]
            tree.shrink(-1.0)
            # subtract the (now negated) tree from all scores
            self._update_train_score_full(tree, kk)
            for _, vs, _m in self.valid_states:
                _add_tree_score(vs, tree, kk, self)
            tree.shrink(-1.0)
        del self.models[-k:]
        self.iter -= 1

    def _update_train_score_full(self, tree: Tree, class_id: int):
        _add_tree_score(self.train_state, tree, class_id, self)

    def raw_scores(self, name: str) -> np.ndarray:
        """Current raw scores of a dataset ('training' or a valid name), as
        the flat class-major layout custom fobj/feval expect."""
        if name == "training":
            state = self.train_state
        else:
            state = next(vs for nm, vs, _m in self.valid_states if nm == name)
        score = np.asarray(state.score, np.float64)
        return score[0] if score.shape[0] == 1 else score.reshape(-1)

    @property
    def current_iteration(self) -> int:
        # count WITHOUT draining: deferred placeholders already occupy
        # their slots in self.models, so the count is exact while the
        # pipeline stays unflushed — a per-iteration caller (user
        # callbacks) must not serialize training with a host round-trip.
        # (Rolled-back/degenerate trees are trimmed on drain, but a drain
        # only ever REMOVES whole trailing iterations that subsequent
        # boosting re-runs; accessors returning tree CONTENTS still sync.)
        return len(self.models) // max(self.num_tree_per_iteration, 1)

    def num_trees(self) -> int:
        self._sync_model()
        return len(self.models)

    def num_model_per_iteration(self) -> int:
        return self.num_tree_per_iteration


@partial(jax.jit, static_argnums=(2,), donate_argnums=(0,))
def _write_planes(arena, planes, row0: int):
    """arena with `planes` written at rows [row0, ...) of columns
    [0, n) — in place (donated)."""
    return jax.lax.dynamic_update_slice(arena, planes, (row0, 0))


def _device_memory_budget() -> int:
    """HBM budget for the partition engine's arena, its feature-major
    bins and the histogram cache: three quarters of what the default
    device reports (the rest holds the row-major bins, the scores and the
    growth loop's transient copies of the cache; at 60 % a 400 000 x 2 000
    data set, 11.8 GB of a v5e's 16.9, was turned away).  A TPU that reports nothing is an error —
    the arena would be sized against a guess; other backends (the CPU
    reports no stats) never run the arena at a size where it matters
    and get a nominal 8 GB."""
    stats = jax.devices()[0].memory_stats() or {}
    total = stats.get("bytes_limit") or stats.get("bytes_reservable_limit")
    if total:
        return int(total * 0.75)
    if on_tpu():
        raise RuntimeError(
            "TPU device %r reports no bytes_limit in memory_stats(); "
            "refusing to size the arena against a guess"
            % jax.devices()[0].device_kind)
    return 8 << 30


def _expand_init_score(init_score, k: int, n: int) -> np.ndarray:
    """Flat init score -> [k, n] class-major matrix: either one block per
    class (len == k*n) or one shared block tiled across classes."""
    init = np.asarray(init_score, np.float64)
    return init.reshape(k, n) if init.size == k * n else \
        np.tile(init.reshape(1, -1), (k, 1))


def _add_tree_score(state: _DatasetState, tree: Tree, class_id: int, gbdt: GBDT):
    """Add a (host) tree's output to a dataset's device scores via binned
    traversal on device."""
    if tree.num_leaves <= 1:
        state.add_constant(float(tree.leaf_value[0]), class_id)
        return
    arrays = _tree_to_device(tree, gbdt.dtype, gbdt.max_bin)
    leaf = grow_ops.predict_leaf_inner(state.bins, arrays, state.num_bins,
                                       state.default_bins, state.bundle)
    leaf_values = jnp.asarray(tree.leaf_value[:tree.num_leaves], gbdt.dtype)
    state.score = state.score.at[class_id].add(leaf_values[leaf])


def _tree_to_device(tree: Tree, dtype, max_bin: int = 0) -> grow_ops.TreeArrays:
    # pad node/leaf arrays to a power-of-two bucket so predict_leaf_inner's
    # jit cache sees stable shapes across trees of different sizes
    nl_true = max(tree.num_leaves, 1)
    nl = max(2, 1 << (nl_true - 1).bit_length())
    n, n_true = nl - 1, max(tree.num_leaves - 1, 1)

    def padn(a, fill=0):
        out = np.full(n, fill, np.asarray(a[:1]).dtype if len(a) else np.int32)
        out[:n_true] = a[:n_true]
        return jnp.asarray(out)

    def padl(a, dt=None):
        out = np.zeros(nl, dt or np.asarray(a[:1]).dtype)
        out[:nl_true] = a[:nl_true]
        return jnp.asarray(out)

    mt = (tree.decision_type.astype(np.int32) >> 2) & 3
    dl = (tree.decision_type & 2) > 0
    # categorical bitsets -> [N, max_bin] membership masks for the device walk
    W = max_bin if tree.num_cat > 0 else 0
    is_cat_np = np.zeros(n, bool)
    cat_mask_np = np.zeros((n, W), bool)
    if W:
        from .tree import K_CATEGORICAL_MASK
        word_idx, bit_idx = np.arange(W) // 32, np.arange(W) % 32
        for node in range(min(n_true, len(tree.decision_type))):
            if not (tree.decision_type[node] & K_CATEGORICAL_MASK):
                continue
            is_cat_np[node] = True
            ci = int(tree.threshold_in_bin[node])
            lo = tree.cat_boundaries_inner[ci]
            hi = tree.cat_boundaries_inner[ci + 1]
            bits = np.asarray(tree.cat_threshold_inner[lo:hi], np.uint32)
            if len(bits):
                valid = word_idx < len(bits)
                cat_mask_np[node] = valid & (
                    (bits[np.minimum(word_idx, len(bits) - 1)]
                     >> bit_idx) & 1).astype(bool)
    return grow_ops.TreeArrays(
        is_cat=jnp.asarray(is_cat_np),
        cat_mask=jnp.asarray(cat_mask_np),
        split_feature=padn(tree.split_feature_inner),
        threshold_bin=padn(tree.threshold_in_bin),
        default_left=padn(dl),
        missing_type=padn(mt),
        left_child=padn(tree.left_child, fill=~0),
        right_child=padn(tree.right_child, fill=~0),
        split_gain=jnp.asarray(np.pad(tree.split_gain[:n_true].astype(np.float64),
                                      (0, n - n_true)), dtype),
        internal_value=jnp.asarray(np.pad(tree.internal_value[:n_true].astype(np.float64),
                                          (0, n - n_true)), dtype),
        internal_count=padn(tree.internal_count),
        leaf_value=jnp.asarray(np.pad(tree.leaf_value[:nl_true].astype(np.float64),
                                      (0, nl - nl_true)), dtype),
        leaf_count=padl(tree.leaf_count),
        leaf_parent=jnp.zeros(nl, jnp.int32),
        leaf_depth=jnp.zeros(nl, jnp.int32),
        num_leaves=jnp.asarray(tree.num_leaves, jnp.int32),
    )


def _feature_infos(ds: BinnedDataset) -> List[str]:
    """'[min:max]' per raw feature; 'none' for unused (dataset.cpp)."""
    out = []
    for raw in range(ds.num_total_features):
        inner = ds.used_feature_map[raw]
        if inner < 0:
            out.append("none")
            continue
        m = ds.bin_mappers[inner]
        if m.bin_type == 1:  # categorical
            out.append(":".join(str(c) for c in sorted(m.bin_2_categorical)))
        else:
            out.append("[%s:%s]" % (_repr_g(m.min_val), _repr_g(m.max_val)))
    return out


def _repr_g(v: float) -> str:
    return np.format_float_positional(v, precision=17, trim="-", fractional=False)


def _rng_state_to_json(rng: np.random.RandomState) -> Dict:
    """np.random.RandomState state tuple -> JSONable dict (the 624-word
    Mersenne key round-trips exactly as a list of ints)."""
    name, keys, pos, has_gauss, cached = rng.get_state()
    return {"name": str(name), "keys": np.asarray(keys).tolist(),
            "pos": int(pos), "has_gauss": int(has_gauss),
            "cached_gaussian": float(cached)}


def _rng_state_from_json(d: Dict) -> np.random.RandomState:
    rng = np.random.RandomState()
    rng.set_state((d["name"], np.asarray(d["keys"], np.uint32),
                   int(d["pos"]), int(d["has_gauss"]),
                   float(d["cached_gaussian"])))
    return rng
