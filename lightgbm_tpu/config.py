"""Config / flag system.

TPU-native re-implementation of the reference's single flat parameter struct
(include/LightGBM/config.h:27-799) and its alias machinery
(src/io/config_auto.cpp:4-157, config.h:856-895).  One declarative table is the
single source of truth (the reference generates config_auto.cpp from doc
comments; here the table *is* the schema).  Parameters flow as key=value
strings / dicts through every API surface, exactly like the reference.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

from .utils import log

# ---------------------------------------------------------------------------
# Schema: (name, type, default).  Types: str, int, float, bool,
# "vec_double", "vec_int", "vec_string".
# Mirrors include/LightGBM/config.h:98-787 field-for-field.
# ---------------------------------------------------------------------------
_SCHEMA = [
    # --- core parameters (config.h:98-206)
    ("config", str, ""),
    ("task", str, "train"),
    ("objective", str, "regression"),
    ("boosting", str, "gbdt"),
    ("data", str, ""),
    ("valid", "vec_string", []),
    ("num_iterations", int, 100),
    ("learning_rate", float, 0.1),
    ("num_leaves", int, 31),
    ("tree_learner", str, "serial"),
    ("num_threads", int, 0),
    ("device_type", str, "tpu"),
    ("seed", int, 0),
    # --- learning control (config.h:208-437)
    ("max_depth", int, -1),
    ("min_data_in_leaf", int, 20),
    ("min_sum_hessian_in_leaf", float, 1e-3),
    ("bagging_fraction", float, 1.0),
    ("bagging_freq", int, 0),
    ("bagging_seed", int, 3),
    ("feature_fraction", float, 1.0),
    ("feature_fraction_seed", int, 2),
    ("early_stopping_round", int, 0),
    ("max_delta_step", float, 0.0),
    ("lambda_l1", float, 0.0),
    ("lambda_l2", float, 0.0),
    ("min_gain_to_split", float, 0.0),
    ("drop_rate", float, 0.1),
    ("max_drop", int, 50),
    ("skip_drop", float, 0.5),
    ("xgboost_dart_mode", bool, False),
    ("uniform_drop", bool, False),
    ("drop_seed", int, 4),
    ("top_rate", float, 0.2),
    ("other_rate", float, 0.1),
    ("min_data_per_group", int, 100),
    ("max_cat_threshold", int, 32),
    ("cat_l2", float, 10.0),
    ("cat_smooth", float, 10.0),
    ("max_cat_to_onehot", int, 4),
    ("top_k", int, 20),
    ("monotone_constraints", "vec_int", []),
    ("feature_contri", "vec_double", []),
    ("forcedsplits_filename", str, ""),
    ("refit_decay_rate", float, 0.9),
    ("cegb_tradeoff", float, 1.0),
    ("cegb_penalty_split", float, 0.0),
    ("cegb_penalty_feature_lazy", "vec_double", []),
    ("cegb_penalty_feature_coupled", "vec_double", []),
    # --- IO parameters (config.h:439-607)
    ("verbosity", int, 1),
    ("max_bin", int, 255),
    ("min_data_in_bin", int, 3),
    ("bin_construct_sample_cnt", int, 200000),
    ("histogram_pool_size", float, -1.0),
    ("data_random_seed", int, 1),
    ("output_model", str, "LightGBM_model.txt"),
    ("snapshot_freq", int, -1),
    ("input_model", str, ""),
    ("output_result", str, "LightGBM_predict_result.txt"),
    ("initscore_filename", str, ""),
    ("valid_data_initscores", "vec_string", []),
    ("pre_partition", bool, False),
    ("enable_bundle", bool, True),
    ("max_conflict_rate", float, 0.0),
    ("is_enable_sparse", bool, True),
    ("sparse_threshold", float, 0.8),
    ("use_missing", bool, True),
    ("zero_as_missing", bool, False),
    ("two_round", bool, False),
    ("save_binary", bool, False),
    ("enable_load_from_binary_file", bool, True),
    ("header", bool, False),
    ("label_column", str, ""),
    ("weight_column", str, ""),
    ("group_column", str, ""),
    ("ignore_column", str, ""),
    ("categorical_feature", str, ""),
    ("predict_raw_score", bool, False),
    ("predict_leaf_index", bool, False),
    ("predict_contrib", bool, False),
    ("num_iteration_predict", int, -1),
    ("pred_early_stop", bool, False),
    ("pred_early_stop_freq", int, 10),
    ("pred_early_stop_margin", float, 10.0),
    ("convert_model_language", str, ""),
    ("convert_model", str, "gbdt_prediction.cpp"),
    # --- objective parameters (config.h:609-705)
    ("num_class", int, 1),
    ("is_unbalance", bool, False),
    ("scale_pos_weight", float, 1.0),
    ("sigmoid", float, 1.0),
    ("boost_from_average", bool, True),
    ("reg_sqrt", bool, False),
    ("alpha", float, 0.9),
    ("fair_c", float, 1.0),
    ("poisson_max_delta_step", float, 0.7),
    ("tweedie_variance_power", float, 1.5),
    ("max_position", int, 20),
    ("label_gain", "vec_double", []),
    # --- metric parameters (config.h:707-755)
    ("metric", "vec_string", []),
    ("metric_freq", int, 1),
    ("is_provide_training_metric", bool, False),
    ("eval_at", "vec_int", [1, 2, 3, 4, 5]),
    # --- network parameters (config.h:757-777)
    ("num_machines", int, 1),
    ("machine_rank", int, -1),  # this process's rank; -1 = resolve from
    #   machine-list address match (parallel/distributed.resolve_rank)
    ("local_listen_port", int, 12400),
    ("time_out", int, 120),
    ("machine_list_filename", str, ""),
    ("machines", str, ""),
    # --- device parameters (config.h:779-799); gpu_* kept for API compat,
    #     tpu_* are this framework's own knobs.
    ("gpu_platform_id", int, -1),
    ("gpu_device_id", int, -1),
    ("gpu_use_dp", bool, False),
    # TPU-native knobs (no reference analogue)
    ("tpu_double_precision", bool, False),   # f64 histogram accumulate (gpu_use_dp analogue)
    ("tpu_histogram_impl", str, "auto"),     # auto|compact|onehot|scatter|pallas
    ("tpu_rows_per_tile", int, 2048),        # Pallas row-tile size
    ("tpu_tree_engine", str, "auto"),        # auto|label|partition — partition =
    #   arena-resident pallas engine (O(child) per split; any width device
    #   memory holds: wide arenas run in channel blocks); label = masked-pass
    #   engine (works everywhere: CPU, f64, categorical, distributed)
    ("tpu_arena_factor", int, 6),            # partition-engine arena size, x rows
    ("tpu_profile", bool, False),            # per-phase host timers, report at teardown
    #   (TIMETAG analogue, serial_tree_learner.cpp:15-42; adds a device
    #   sync per phase, so only enable when measuring)
    ("tpu_profile_trace_dir", str, ""),      # non-empty -> jax.profiler trace of training
    ("num_devices", int, 0),                 # 0 = use all local devices for parallel learners
    # --- telemetry parameters (no reference analogue)
    # Unified observability layer (lightgbm_tpu/obs): per-iteration JSONL
    # event log + metrics registry; see docs/Observability.md.
    ("tpu_telemetry_path", str, ""),         # non-empty -> append one JSONL event per
    #   boosting iteration (metrics, phase times, tree shape, compile counts);
    #   training output is bitwise-identical with it on or off
    ("tpu_telemetry_device_stats", bool, True),  # sample live-buffer/jit-cache
    #   gauges into each iteration event
    ("tpu_log_json", bool, False),           # structured JSON log lines with bound
    #   context fields (utils/log.set_json_mode)
    ("tpu_trace_path", str, ""),             # non-empty -> record a structured span
    #   timeline (Chrome trace-event JSON, openable in Perfetto /
    #   chrome://tracing); distributed runs write one file per rank
    #   (<path>.rankN) fusable with tools/trace_merge.py.  Training
    #   output is bitwise-identical with it on or off
    ("tpu_trace_max_events", int, 500000),   # in-memory span buffer cap; overflow
    #   is counted and reported in the trace metadata, never unbounded
    ("tpu_trace_xla_analysis", bool, True),  # attach XLA cost/memory analysis
    #   (flops, bytes accessed, peak HBM) to each fused-iter retrace span
    # --- serving parameters (no reference analogue)
    # task=serve: TPU-resident inference server (lightgbm_tpu/serving) —
    # adaptive micro-batching over the compiled signature-matmul
    # predictor; see docs/Serving.md for tuning guidance.
    ("serve_host", str, "127.0.0.1"),        # HTTP bind address
    ("serve_port", int, 9109),               # HTTP port (0 = ephemeral)
    ("serve_model_name", str, "default"),    # registry name for input_model
    ("serve_max_batch_rows", int, 256),      # coalesced batch cap (rounded up to pow2)
    ("serve_batch_wait_ms", float, 2.0),     # max wait to fill a batch before dispatch
    ("serve_queue_rows", int, 4096),         # bounded queue (rows); beyond -> 429/fallback
    ("serve_request_timeout_ms", float, 1000.0),  # per-request deadline incl. queue wait
    ("serve_max_models", int, 4),            # registry capacity; LRU eviction beyond
    ("serve_warmup_buckets", "vec_int", []),  # row buckets to pre-compile; [] = pow2 up to max batch
    ("serve_min_device_work", int, 1 << 22),  # per-batch rows*trees floor for the device path
    ("serve_host_fallback", bool, True),     # overflow/small traffic -> host walk instead of 429
    ("serve_fallback_max_rows", int, 16),    # biggest request served host-side under overload
    # --- resilience parameters (no reference analogue)
    # Checkpoint/resume + comm retry (lightgbm_tpu/resilience): periodic
    # atomic snapshots with deterministic restart — a resumed run's model
    # file is byte-identical to the uninterrupted run; see
    # docs/Resilience.md.
    ("tpu_checkpoint_path", str, ""),        # non-empty -> checkpoint every
    #   tpu_checkpoint_interval rounds into this directory; the CLI
    #   auto-resumes from the newest valid checkpoint found there
    ("tpu_checkpoint_interval", int, 10),    # rounds between checkpoints
    ("tpu_checkpoint_keep", int, 3),         # retention: keep newest N checkpoints
    ("tpu_comm_retries", int, 4),            # comm op retries after the first attempt
    ("tpu_comm_backoff_ms", float, 50.0),    # first-retry backoff (doubles per retry)
    ("tpu_comm_backoff_max_ms", float, 2000.0),  # backoff cap
    ("tpu_comm_op_timeout_s", float, 0.0),   # per send/recv cap; 0 = inherit setup timeout
    ("tpu_comm_heartbeat_s", float, 0.0),    # >0 -> rank-liveness probe every N seconds
    ("tpu_comm_backend", str, "auto"),       # auto|mesh|socket|hybrid —
    #   collective backend for the parallel learners
    #   (parallel/collective.py): `mesh` = in-process shard_map/psum
    #   over the local device mesh (single controller, histograms never
    #   leave HBM); `socket` = the cross-host SocketComm wire behind
    #   the same Collective interface (retry/heartbeat/elastic fencing
    #   preserved); `hybrid` = mesh within each host composed with the
    #   socket wire between per-host leaders (parallel/hybrid.py) —
    #   host-granular fault domains; `auto` = mesh when >1 local
    #   device, else serial.  See docs/Distributed.md.
    ("tpu_hybrid_local_devices", int, 0),    # inner-mesh size per host for
    #   tpu_comm_backend=hybrid (0 = every visible local device)
    ("tpu_hybrid_slow_ms", float, 0.0),      # >0 -> straggler detection: a
    #   host whose leader-phase wait exceeds this is marked *slow* in
    #   obs/recorder (per-round, before heartbeat conviction would mark
    #   it dead); 0 disables the timer
    ("tpu_hybrid_slow_rounds", int, 3),      # consecutive slow rounds before
    #   the demotion policy fires
    ("tpu_hybrid_slow_policy", str, "observe"),  # observe|demote — what to do
    #   after tpu_hybrid_slow_rounds consecutive slow marks: `observe`
    #   keeps emitting telemetry only; `demote` fences the straggler
    #   host (it exits the formation exactly like a convicted host and
    #   the survivors re-form)
    ("tpu_dist_find_bin", bool, True),       # distributed find-bin: each rank
    #   samples only its own row shard and bin boundaries are merged via
    #   one allgather (bitwise-identical to single-rank binning; dense
    #   inputs only — sparse falls back to full-matrix sampling)
    # --- elasticity parameters (no reference analogue)
    # Elastic distributed training (lightgbm_tpu/resilience/elastic):
    # active liveness protocol, generation-fenced collectives, and
    # degraded-world recovery — a dead rank is detected, fenced, and the
    # survivors re-form and resume from the newest checkpoint; see
    # docs/Elasticity.md.
    ("tpu_elastic", bool, False),            # run training under the elastic
    #   supervisor (requires a machine list and tpu_checkpoint_path for
    #   cross-failure resume)
    ("tpu_elastic_heartbeat_ms", float, 200.0),  # control-channel ping interval
    ("tpu_elastic_suspect_ms", float, 1000.0),   # silence before a rank is
    #   declared dead (detection latency upper bound, rounded up to whole
    #   heartbeat intervals)
    ("tpu_elastic_rejoin_s", float, 3.0),    # re-formation window for restarted
    #   ranks to rejoin before the world proceeds at reduced size
    ("tpu_elastic_min_world", int, 1),       # abort instead of re-forming below
    #   this many surviving ranks
    ("tpu_elastic_max_reforms", int, 3),     # abort after this many world
    #   re-formations in one run
    ("tpu_elastic_sync_every", int, 1),      # rounds between liveness-bearing
    #   allgathers (the failure-propagation seam; higher = less comm, slower
    #   failure detection at the training loop level)
    # --- serving admission-control parameters (no reference analogue)
    # Load shedding + circuit breaking for task=serve (serving/admission);
    # see docs/Elasticity.md for the semantics.
    ("tpu_serve_shed_queue_rows", int, 0),   # queue-depth watermark: reject new
    #   requests with 429 + Retry-After once this many rows are queued
    #   (0 = shed only at the hard serve_queue_rows bound)
    ("tpu_serve_shed_retry_after_s", float, 1.0),  # Retry-After hint on 429/503
    ("tpu_serve_breaker_failures", int, 5),  # consecutive device-path failures
    #   that open the circuit breaker (then requests ride the host walk)
    ("tpu_serve_breaker_reset_s", float, 30.0),  # open -> half-open probe delay
    ("tpu_serve_drain_timeout_s", float, 10.0),  # SIGTERM: max wait for in-flight
    #   requests before the server exits
    # --- fleet residency parameters (no reference analogue)
    # Multi-tenant HBM residency manager (serving/fleet.py): a byte-
    # accounted device budget with LRU spill to a host-RAM tier and
    # asynchronous re-promotion, a fleet-wide shape-bucketed compile
    # cache, and per-tenant admission quotas.  See docs/Fleet.md.
    ("tpu_fleet_hbm_budget_mb", float, 0.0),  # device-byte budget for resident
    #   prediction ensembles; 0 disables the residency manager (every loaded
    #   model stays device-resident forever — the pre-fleet behavior)
    ("tpu_fleet_high_watermark", float, 0.9),  # budget fraction that triggers
    #   LRU eviction BEFORE a new ensemble is built (never after an OOM)
    ("tpu_fleet_low_watermark", float, 0.7),  # eviction target: spill LRU
    #   tenants until resident bytes fit under this fraction of the budget
    ("tpu_fleet_promote_retries", int, 3),   # async promotion retry budget;
    #   exponential backoff between attempts, exhaustion degrades the tenant
    #   to the host walk (counted, never raised to clients)
    ("tpu_fleet_promote_backoff_ms", float, 50.0),  # first-retry backoff for
    #   failed promotions (doubles per attempt, jittered)
    ("tpu_fleet_tenant_qps", float, 0.0),    # per-tenant admission quota in
    #   requests/s (token bucket; 0 = no quota).  A breaching tenant sheds
    #   with 429 + Retry-After and a per-tenant counter — one noisy tenant
    #   cannot starve the fleet
    ("tpu_fleet_tenant_burst", float, 0.0),  # token-bucket burst depth
    #   (0 = 2x the qps quota, floor 1)
    # --- replica serving parameters (no reference analogue)
    # Device-fault-domain replica sets (serving/replicas.py): N copies of
    # a tenant's frozen ensemble committed to distinct local devices,
    # least-outstanding-rows routing, per-replica circuit breakers with
    # liveness probes, loss-free failover.  See docs/Replicas.md.
    ("tpu_replica_count", int, 1),           # per-device replicas per tenant;
    #   1 keeps the exact single-device serving path (no ReplicaSet built,
    #   byte-identical output), >1 places copies round-robin across the
    #   local devices
    ("tpu_replica_min", int, 1),             # lower bound for the
    #   set_replica_count control-plane lever
    ("tpu_replica_max", int, 8),             # upper bound for the
    #   set_replica_count control-plane lever (the local-device fleet size
    #   is the natural ceiling)
    ("tpu_replica_probe_interval_s", float, 0.0),  # per-replica liveness probe
    #   cadence (a tiny one-row dispatch per replica); 0 disables the probe
    #   thread — recovery then rides the router's organic half-open probe
    ("tpu_replica_probe_deadline_ms", float, 1000.0),  # a probe slower than
    #   this counts as a failure (a stuck device must not pass its probe)
    ("tpu_replica_breaker_failures", int, 3),  # consecutive dispatch/probe
    #   failures that open ONE replica's breaker (the tenant keeps serving
    #   on its sibling replicas — capacity degrades, availability doesn't)
    ("tpu_replica_breaker_reset_s", float, 5.0),  # per-replica breaker
    #   open -> half-open probe delay
    # --- quantized histogram training parameters (no reference analogue)
    # Quantized gradient/hessian histogram accumulation (docs/Quantized.md):
    # g/h become int8 codes carried as TWO arena payload planes instead of
    # six f32-residue planes, histogram radix payload shrinks 7 -> 3
    # components, and leaf outputs are recovered exactly from the integer
    # bin sums via per-tree scales.  HBM bytes drop, FLOPs are unchanged
    # (this chip runs every dtype at the same ~24 TFLOP/s — bytes are the
    # binding resource, NOTES.md).
    ("tpu_quantized_grad", bool, False),  # enable quantized histogram
    #   training (partition engine only; falls back off with a warning
    #   when the engine is unavailable)
    ("tpu_quantized_bits", int, 8),       # gradient code width; only 8 is
    #   implemented (int8 codes in [-127, 127])
    ("tpu_quantized_seed", int, 0),       # stochastic-rounding seed for the
    #   gradient codes (0 = derive from the main `seed`); folded with the
    #   iteration index so checkpoint resume is bitwise-identical
    # --- continuous-learning parameters (no reference analogue)
    # Streaming refit -> shadow eval -> gated hot-swap with automatic
    # rollback (resilience/supervisor.py + serving/shadow.py); the CLI
    # face is `task=serve tpu_continuous_learning=true`.  See
    # docs/ContinuousLearning.md for the loop and failure matrix.
    ("tpu_continuous_learning", bool, False),  # run the supervisor loop next
    #   to task=serve: POST /ingest feeds fresh labeled rows, candidates
    #   are produced/shadow-scored/promoted automatically
    ("tpu_refit_interval_s", float, 30.0),   # min seconds between candidate
    #   builds (the loop also waits for tpu_refit_min_rows)
    ("tpu_refit_min_rows", int, 256),        # buffered training rows required
    #   before a candidate is produced
    ("tpu_refit_mode", str, "refit"),        # refit|continue — leaf-value
    #   renewal via Booster.refit vs continued training (init_model) with
    #   tpu_refit_rounds extra trees; continue falls back to refit when
    #   no base dataset is available for frozen-mapper binning
    ("tpu_refit_rounds", int, 10),           # continue-mode boosting rounds
    #   added per candidate
    ("tpu_refit_buffer_rows", int, 100000),  # bounded ingest buffer: beyond
    #   this many buffered rows the OLDEST rows are shed (counted on
    #   lgbm_ingest_shed_total{reason=overflow}), never the loop crashed
    ("tpu_refit_holdout_fraction", float, 0.2),  # fraction of ingested rows
    #   diverted to the held-out shadow-metric window (never trained on)
    ("tpu_promote_min_delta", float, 0.0),   # quality floor: candidate loss
    #   must beat live loss by MORE than this on the held-out window
    ("tpu_promote_min_samples", int, 200),   # min held-out rows scored before
    #   a promote/reject verdict (smaller windows keep the candidate in
    #   shadow)
    ("tpu_promote_watch_s", float, 60.0),    # post-promotion watch window:
    #   live metrics breaching the floor inside it trigger auto-rollback
    ("tpu_promote_rollback_delta", float, 0.0),  # rollback floor: watch-window
    #   live loss may exceed the pre-promote baseline by at most this
    #   before the prior registry version is reinstalled
    # --- cluster observability parameters (no reference analogue)
    # Telemetry federation + per-round critical-path ledger + SLO alerting
    # (lightgbm_tpu/obs/federation.py, critical_path.py, alerts.py): each
    # rank ships a compact per-round digest to the hub, the hub decomposes
    # round wall time and names the critical (rank, phase), and a rule
    # engine watches the MetricsRegistry.  Strictly read-only on training
    # state — models are bitwise-identical with all of it on or off.  See
    # docs/ClusterObservability.md.
    ("tpu_federation", bool, False),         # per-round telemetry digest
    #   federation: every rank assembles phase deltas / comm-wait share /
    #   heartbeat RTT / HBM bytes and ships them to the hub (one extra
    #   small allgather on the socket/hybrid wire; gathered in-process on
    #   mesh/serial).  The hub publishes lgbm_cluster_* gauges, appends
    #   `cluster` + `round_ledger` telemetry events and feeds
    #   tools/round_report.py
    ("tpu_federation_every", int, 1),        # rounds between digest exchanges
    #   (the ledger covers only federated rounds; higher = less wire)
    ("tpu_federation_port", int, 0),         # >0 -> the hub serves GET
    #   /cluster, /alerts and /metrics on this port while training
    #   (0 = no hub HTTP endpoint; the serving server has its own)
    ("tpu_federation_top_phases", int, 6),   # phase deltas per digest: only
    #   the top-N phases by round time ride the wire
    ("tpu_alert", bool, False),              # evaluate the alert rule engine
    #   over the MetricsRegistry each federated round (training hub) and
    #   each stats tick (serving); fires `alert` telemetry events and the
    #   lgbm_alerts_active{rule} gauge
    ("tpu_alert_rules", str, ""),            # JSON rules file ("" = built-in
    #   rules: persistent straggler, comm-wait share, breaker flaps,
    #   shed/quota-shed rate, promotion failures, heartbeat miss streak);
    #   see docs/ClusterObservability.md for the rule syntax
    ("tpu_alert_sustain_rounds", int, 3),    # default `for` of sustained
    #   rules: consecutive breaching ticks before the alert fires
    ("tpu_alert_burn_window", int, 16),      # burn-rate rule window in
    #   evaluation ticks (rate = counter delta / window)
    ("tpu_alert_comm_wait_share", float, 0.5),  # built-in comm-wait rule:
    #   fraction of round wall a host may spend blocked on peers
    ("tpu_alert_shed_rate", float, 5.0),     # built-in shed-rate rule:
    #   shed (+ quota-shed) requests per evaluation tick
    # --- closed-loop control plane (control/): the policy engine turns
    #   alert transitions + round-ledger signals into recorded,
    #   rate-limited actions through the process actuator.  See
    #   docs/ControlPlane.md
    ("tpu_policy", bool, False),             # evaluate policy rules each
    #   federated round (hub) and dispatch actions through the actuator;
    #   requires tpu_federation + tpu_alert for the training-side rules
    ("tpu_policy_rules", str, ""),           # JSON policy rule file ("" =
    #   built-in rules: straggler demote, scale-up admit, shed pre-spill,
    #   promote-floor tighten); same spirit as tpu_alert_rules
    ("tpu_policy_dry_run", bool, False),     # record every decision as a
    #   policy_action event with status=dry_run but dispatch NOTHING —
    #   training stays bitwise-identical to tpu_policy=false
    ("tpu_policy_rate_limit", float, 4.0),   # global action token bucket:
    #   actions allowed per tpu_policy_rate_window_s across ALL rules
    ("tpu_policy_rate_window_s", float, 60.0),  # token bucket refill window
    ("tpu_policy_cooldown_rounds", int, 8),  # default per-rule cooldown in
    #   federated rounds between dispatches (rules may override)
    ("tpu_elastic_scale_up", bool, False),   # keep the formation listener
    #   open after formation: a fenced/fresh host petitions to rejoin and
    #   is admitted at the next formation epoch boundary (hub re-forms the
    #   full world, rows re-shard, training resumes from the newest
    #   checkpoint via resume_mode="reshard")
    ("tpu_elastic_scale_up_wait_s", float, 60.0),  # how long a petitioning
    #   host waits for an epoch before giving up (ElasticFenced)
    ("tpu_elastic_petition_poll_s", float, 2.0),  # how long a parked
    #   petitioner blocks on the hub socket per poll, waiting for the
    #   epoch wake the hub pushes when expand_world admits it — bounds
    #   rejoin latency to ~one poll instead of a blind sleep/re-knock
    # --- trend observatory (obs/timeseries.py): bounded per-metric
    #   time-series sampled each federated round / serving stats tick,
    #   feeding `trend` alert rules, policy trend guards, per-leg ledger
    #   trends and the end-of-run RUNHIST artifact.  Strictly read-only —
    #   training is bitwise-identical with it on or off.  See
    #   docs/TrendObservatory.md
    ("tpu_trend", bool, False),              # keep ring-buffer series on
    #   the hub (training) / server (serving), annotate the round ledger
    #   and /cluster with slope/EWMA per leg, and arm the built-in
    #   straggler_share_trend alert rule
    ("tpu_trend_window", int, 64),           # ring capacity per series and
    #   the default analytics window, in ticks (federated rounds /
    #   serving stats ticks)
    ("tpu_trend_metrics", str, ""),          # comma-separated glob list
    #   restricting which registry families are sampled ("" = all)
    ("tpu_alert_trend_slope", float, 0.01),  # built-in trend rule: fires
    #   when the round's straggler-wait share grows faster than this
    #   per round over the trend window
    ("tpu_policy_trend_guard", bool, False),  # arm the trend guard on the
    #   built-in demote_straggler policy rule: demote only when the
    #   straggler-wait share is GROWING over the trend window, not on
    #   any single sustained breach
    ("tpu_runhist_path", str, ""),           # write the end-of-run RUNHIST
    #   JSON artifact (per-phase + per-metric windowed summaries and
    #   series tails) here; tools/run_diff.py diffs two artifacts with
    #   tolerance bands and a nonzero exit on regression
    # --- runtime sync sentinel (obs/scaling.py); strictly read-only:
    #   training is bitwise-identical with it on or off.  See
    #   docs/ScalingForensics.md
    ("tpu_sync_guard", str, "off"),          # runtime sync sentinel mode:
    #   "off" (default, zero overhead), "log" (count + stack-attribute
    #   every implicit device->host scalar fetch inside the round as a
    #   sync_event), or "fail" (raise at the first un-exempted sync)
]

# alias -> canonical name (src/io/config_auto.cpp:4-157)
ALIAS_TABLE: Dict[str, str] = {
    "config_file": "config",
    "task_type": "task",
    "objective_type": "objective", "app": "objective", "application": "objective",
    "boosting_type": "boosting", "boost": "boosting",
    "train": "data", "train_data": "data", "train_data_file": "data", "data_filename": "data",
    "test": "valid", "valid_data": "valid", "valid_data_file": "valid",
    "test_data": "valid", "test_data_file": "valid", "valid_filenames": "valid",
    "num_iteration": "num_iterations", "n_iter": "num_iterations",
    "num_tree": "num_iterations", "num_trees": "num_iterations",
    "num_round": "num_iterations", "num_rounds": "num_iterations",
    "num_boost_round": "num_iterations", "n_estimators": "num_iterations",
    "shrinkage_rate": "learning_rate", "eta": "learning_rate",
    "num_leaf": "num_leaves", "max_leaves": "num_leaves", "max_leaf": "num_leaves",
    "tree": "tree_learner", "tree_type": "tree_learner", "tree_learner_type": "tree_learner",
    "num_thread": "num_threads", "nthread": "num_threads",
    "nthreads": "num_threads", "n_jobs": "num_threads",
    "device": "device_type",
    "random_seed": "seed", "random_state": "seed",
    "min_data_per_leaf": "min_data_in_leaf", "min_data": "min_data_in_leaf",
    "min_child_samples": "min_data_in_leaf",
    "min_sum_hessian_per_leaf": "min_sum_hessian_in_leaf",
    "min_sum_hessian": "min_sum_hessian_in_leaf", "min_hessian": "min_sum_hessian_in_leaf",
    "min_child_weight": "min_sum_hessian_in_leaf",
    "sub_row": "bagging_fraction", "subsample": "bagging_fraction", "bagging": "bagging_fraction",
    "subsample_freq": "bagging_freq",
    "bagging_fraction_seed": "bagging_seed",
    "sub_feature": "feature_fraction", "colsample_bytree": "feature_fraction",
    "early_stopping_rounds": "early_stopping_round", "early_stopping": "early_stopping_round",
    "max_tree_output": "max_delta_step", "max_leaf_output": "max_delta_step",
    "reg_alpha": "lambda_l1",
    "reg_lambda": "lambda_l2", "lambda": "lambda_l2",
    "min_split_gain": "min_gain_to_split",
    "rate_drop": "drop_rate",
    "topk": "top_k",
    "mc": "monotone_constraints", "monotone_constraint": "monotone_constraints",
    "feature_contrib": "feature_contri", "fc": "feature_contri",
    "fp": "feature_contri", "feature_penalty": "feature_contri",
    "fs": "forcedsplits_filename", "forced_splits_filename": "forcedsplits_filename",
    "forced_splits_file": "forcedsplits_filename", "forced_splits": "forcedsplits_filename",
    "verbose": "verbosity",
    "subsample_for_bin": "bin_construct_sample_cnt",
    "hist_pool_size": "histogram_pool_size",
    "data_seed": "data_random_seed",
    "model_output": "output_model", "model_out": "output_model",
    "save_period": "snapshot_freq",
    "telemetry_path": "tpu_telemetry_path",
    "telemetry_file": "tpu_telemetry_path",
    "trace_path": "tpu_trace_path",
    "trace_file": "tpu_trace_path",
    "model_input": "input_model", "model_in": "input_model",
    "predict_result": "output_result", "prediction_result": "output_result",
    "predict_name": "output_result", "prediction_name": "output_result",
    "pred_name": "output_result", "name_pred": "output_result",
    "init_score_filename": "initscore_filename", "init_score_file": "initscore_filename",
    "init_score": "initscore_filename", "input_init_score": "initscore_filename",
    "valid_data_init_scores": "valid_data_initscores",
    "valid_init_score_file": "valid_data_initscores", "valid_init_score": "valid_data_initscores",
    "is_pre_partition": "pre_partition",
    "is_enable_bundle": "enable_bundle", "bundle": "enable_bundle",
    "is_sparse": "is_enable_sparse", "enable_sparse": "is_enable_sparse",
    "sparse": "is_enable_sparse",
    "two_round_loading": "two_round", "use_two_round_loading": "two_round",
    "is_save_binary": "save_binary", "is_save_binary_file": "save_binary",
    "load_from_binary_file": "enable_load_from_binary_file",
    "binary_load": "enable_load_from_binary_file", "load_binary": "enable_load_from_binary_file",
    "has_header": "header",
    "label": "label_column",
    "weight": "weight_column",
    "group": "group_column", "group_id": "group_column",
    "query_column": "group_column", "query": "group_column", "query_id": "group_column",
    "ignore_feature": "ignore_column", "blacklist": "ignore_column",
    "cat_feature": "categorical_feature", "categorical_column": "categorical_feature",
    "cat_column": "categorical_feature",
    "is_predict_raw_score": "predict_raw_score", "predict_rawscore": "predict_raw_score",
    "raw_score": "predict_raw_score",
    "is_predict_leaf_index": "predict_leaf_index", "leaf_index": "predict_leaf_index",
    "is_predict_contrib": "predict_contrib", "contrib": "predict_contrib",
    "convert_model_file": "convert_model",
    "num_classes": "num_class",
    "unbalance": "is_unbalance", "unbalanced_sets": "is_unbalance",
    "metrics": "metric", "metric_types": "metric",
    "output_freq": "metric_freq",
    "training_metric": "is_provide_training_metric",
    "is_training_metric": "is_provide_training_metric",
    "train_metric": "is_provide_training_metric",
    "ndcg_eval_at": "eval_at", "ndcg_at": "eval_at",
    "map_eval_at": "eval_at", "map_at": "eval_at",
    "num_machine": "num_machines",
    "local_port": "local_listen_port", "port": "local_listen_port",
    "machine_list_file": "machine_list_filename", "machine_list": "machine_list_filename",
    "mlist": "machine_list_filename",
    "workers": "machines", "nodes": "machines",
    "serving_host": "serve_host", "serve_address": "serve_host",
    "serving_port": "serve_port",
    "serve_max_batch": "serve_max_batch_rows",
    "serve_max_wait_ms": "serve_batch_wait_ms",
    "serve_queue_size": "serve_queue_rows",
    "serve_timeout_ms": "serve_request_timeout_ms",
    "checkpoint_path": "tpu_checkpoint_path",
    "checkpoint_dir": "tpu_checkpoint_path",
    "checkpoint_interval": "tpu_checkpoint_interval",
    "checkpoint_freq": "tpu_checkpoint_interval",
    "elastic": "tpu_elastic", "elastic_training": "tpu_elastic",
    "elastic_rejoin_window_s": "tpu_elastic_rejoin_s",
    "serve_shed_queue_rows": "tpu_serve_shed_queue_rows",
    "serve_drain_timeout_s": "tpu_serve_drain_timeout_s",
    "checkpoint_keep": "tpu_checkpoint_keep",
    "keep_last_n": "tpu_checkpoint_keep",
    "comm_retries": "tpu_comm_retries",
    "comm_backoff_ms": "tpu_comm_backoff_ms",
    "comm_heartbeat_s": "tpu_comm_heartbeat_s",
    "comm_backend": "tpu_comm_backend",
    "collective_backend": "tpu_comm_backend",
    "hybrid_local_devices": "tpu_hybrid_local_devices",
    "hybrid_slow_ms": "tpu_hybrid_slow_ms",
    "hybrid_slow_rounds": "tpu_hybrid_slow_rounds",
    "hybrid_slow_policy": "tpu_hybrid_slow_policy",
    "dist_find_bin": "tpu_dist_find_bin",
    "distributed_find_bin": "tpu_dist_find_bin",
    "continuous_learning": "tpu_continuous_learning",
    "refit_interval_s": "tpu_refit_interval_s",
    "refit_min_rows": "tpu_refit_min_rows",
    "refit_mode": "tpu_refit_mode",
    "promote_min_delta": "tpu_promote_min_delta",
    "promote_watch_s": "tpu_promote_watch_s",
    "fleet_hbm_budget_mb": "tpu_fleet_hbm_budget_mb",
    "hbm_budget_mb": "tpu_fleet_hbm_budget_mb",
    "fleet_tenant_qps": "tpu_fleet_tenant_qps",
    "tenant_qps": "tpu_fleet_tenant_qps",
    "replica_count": "tpu_replica_count",
    "replicas": "tpu_replica_count",
    "replica_min": "tpu_replica_min",
    "replica_max": "tpu_replica_max",
    "replica_probe_interval_s": "tpu_replica_probe_interval_s",
    "replica_probe_deadline_ms": "tpu_replica_probe_deadline_ms",
    "replica_breaker_failures": "tpu_replica_breaker_failures",
    "replica_breaker_reset_s": "tpu_replica_breaker_reset_s",
    "federation": "tpu_federation",
    "telemetry_federation": "tpu_federation",
    "federation_every": "tpu_federation_every",
    "federation_port": "tpu_federation_port",
    "alerts": "tpu_alert",
    "alerting": "tpu_alert",
    "alert_rules": "tpu_alert_rules",
    "alert_sustain_rounds": "tpu_alert_sustain_rounds",
    "policy": "tpu_policy",
    "policy_engine": "tpu_policy",
    "policy_rules": "tpu_policy_rules",
    "policy_dry_run": "tpu_policy_dry_run",
    "elastic_scale_up": "tpu_elastic_scale_up",
    "scale_up": "tpu_elastic_scale_up",
    "trend": "tpu_trend",
    "trends": "tpu_trend",
    "trend_window": "tpu_trend_window",
    "trend_guard": "tpu_policy_trend_guard",
    "runhist": "tpu_runhist_path",
    "runhist_path": "tpu_runhist_path",
    "sync_guard": "tpu_sync_guard",
    "transfer_guard": "tpu_sync_guard",
}

PARAMETER_TYPES: Dict[str, Any] = {name: typ for name, typ, _ in _SCHEMA}
PARAMETER_DEFAULTS: Dict[str, Any] = {name: dflt for name, _, dflt in _SCHEMA}
PARAMETER_SET = frozenset(PARAMETER_TYPES)

_TRUE_SET = frozenset(("1", "t", "true", "yes", "y", "on", "+"))
_FALSE_SET = frozenset(("0", "f", "false", "no", "n", "off", "-"))


def _parse_bool(v: Any) -> bool:
    if isinstance(v, bool):
        return v
    s = str(v).strip().lower()
    if s in _TRUE_SET:
        return True
    if s in _FALSE_SET:
        return False
    log.fatal("Cannot parse '%s' as bool" % (v,))
    return False


def _parse_vec(v: Any, elem) -> list:
    if isinstance(v, (list, tuple)):
        return [elem(x) for x in v]
    s = str(v).strip()
    if not s:
        return []
    return [elem(x) for x in s.replace(":", ",").split(",") if x != ""]


def _coerce(name: str, typ: Any, value: Any) -> Any:
    if typ is str:
        return str(value)
    if typ is int:
        return int(float(value)) if not isinstance(value, int) or isinstance(value, bool) else value
    if typ is float:
        return float(value)
    if typ is bool:
        return _parse_bool(value)
    if typ == "vec_double":
        return _parse_vec(value, float)
    if typ == "vec_int":
        return _parse_vec(value, int)
    if typ == "vec_string":
        if isinstance(value, (list, tuple)):
            return [str(x) for x in value]
        return [x for x in str(value).split(",") if x]
    raise AssertionError(name)


def str2map(text: str) -> Dict[str, str]:
    """Parse 'k1=v1 k2=v2' / config-file lines into a dict
    (reference Config::Str2Map, src/io/config.cpp:12-41).  Comments are
    stripped at line level before tokenizing."""
    out: Dict[str, str] = {}
    for line in text.splitlines():
        line = line.split("#", 1)[0]
        for token in line.split():
            kv2map(out, token)
    return out


def kv2map(params: Dict[str, str], token: str) -> None:
    """One 'k=v' token into the map; first value wins with a warning on
    duplicates, quotes trimmed (src/io/config.cpp:15-29)."""
    token = token.strip()
    if not token:
        return
    if "=" not in token:
        log.warning("Unknown token %s in parameters, ignored", token)
        return
    k, v = token.split("=", 1)
    k = k.strip().strip("\"'")
    v = v.strip().strip("\"'")
    if k in params:
        log.warning("%s is set=%s, %s=%s will be ignored. Current value: %s=%s",
                    k, params[k], k, v, k, params[k])
    else:
        params[k] = v


def alias_transform(params: Dict[str, Any]) -> Dict[str, Any]:
    """Resolve aliases to canonical names; longest (then lexicographically
    greatest) alias wins on conflict; explicit canonical always wins
    (config.h:856-895)."""
    out: Dict[str, Any] = {}
    pending: Dict[str, str] = {}
    for k in params:
        canon = ALIAS_TABLE.get(k)
        if canon is not None:
            prev = pending.get(canon)
            if prev is None or (len(prev), prev) < (len(k), k):
                if prev is not None:
                    log.warning("%s is set with %s and %s; using %s", canon, prev, k, k)
                pending[canon] = k
            else:
                log.warning("%s is set with %s and %s; using %s", canon, k, prev, prev)
        elif k not in PARAMETER_SET:
            log.warning("Unknown parameter: %s", k)
            out[k] = params[k]
        else:
            out[k] = params[k]
    for canon, src in pending.items():
        if canon in out:
            log.warning("%s is set=%s, %s=%s will be ignored.",
                        canon, out[canon], src, params[src])
        else:
            out[canon] = params[src]
    return out


# Params parsed for conf-file compatibility but without effect in this
# build (warned once per process when set to a non-default value).  Keep
# this in sync as features land: a key must leave this table the moment
# it starts acting.
_INERT_PARAMS: Dict[str, str] = {
    "is_enable_sparse": "bin storage is always dense on TPU (EFB bundles "
                        "sparse features into dense groups instead)",
    "sparse_threshold": "bin storage is always dense on TPU",
}
_INERT_WARNED: set = set()


class Config:
    """Flat parameter struct; fields mirror the reference Config
    (include/LightGBM/config.h:98-799)."""

    # populated dynamically from _SCHEMA below
    def __init__(self, params: Optional[Dict[str, Any]] = None, **kwargs):
        for name, typ, dflt in _SCHEMA:
            setattr(self, name, list(dflt) if isinstance(dflt, list) else dflt)
        merged: Dict[str, Any] = {}
        if params:
            merged.update(params)
        merged.update(kwargs)
        self.raw_params: Dict[str, Any] = dict(merged)
        self.set(merged)

    def set(self, params: Dict[str, Any]) -> None:
        params = alias_transform(params)
        if params.get("verbosity") is not None:
            # the log level follows an explicit verbosity as the params
            # are parsed, like the reference's Config::Set
            # (src/io/config.cpp): < 0 fatal only, 0 warnings, 1 info,
            # > 1 debug.  First, so that what the rest of set() and the
            # Dataset / Booster built from this Config log is routed by it
            log.set_level(max(log.FATAL, min(
                _coerce("verbosity", int, params["verbosity"]), log.DEBUG)))
        for k, v in params.items():
            if k in PARAMETER_SET and v is not None:
                setattr(self, k, _coerce(k, PARAMETER_TYPES[k], v))
                if k in _INERT_PARAMS and k not in _INERT_WARNED \
                        and getattr(self, k) != PARAMETER_DEFAULTS[k]:
                    # accepted-but-inert knobs must warn, not silently
                    # no-op (the reference either acts on or rejects them)
                    _INERT_WARNED.add(k)
                    log.warning("%s is accepted but has no effect: %s",
                                k, _INERT_PARAMS[k])
        self._resolve_names()
        self.check_param_conflict()

    def _resolve_names(self) -> None:
        # objective aliases resolved at use sites; boosting aliases here
        # (src/boosting/boosting.cpp:30-63 name dispatch)
        b = self.boosting
        if b in ("gbrt",):
            self.boosting = "gbdt"
        elif b in ("random_forest",):
            self.boosting = "rf"
        # tree-learner spellings (GetTreeLearnerType, src/io/config.cpp:139-152)
        tl = self.tree_learner.lower()
        tl_map = {"serial": "serial",
                  "feature": "feature", "feature_parallel": "feature",
                  "data": "data", "data_parallel": "data",
                  "voting": "voting", "voting_parallel": "voting"}
        if tl not in tl_map:
            log.fatal("Unknown tree learner type %s" % self.tree_learner)
        self.tree_learner = tl_map[tl]
        self.tpu_comm_backend = self.tpu_comm_backend.lower()

    def check_param_conflict(self) -> None:
        """Cross-parameter validation (src/io/config.cpp:230-260)."""
        # only the explicit num_devices=1 is decided here: counting the
        # local devices would initialise the backend (and so take the
        # chip) in any process that merely builds a Config.  With
        # num_devices=0 the learner factory, which runs in the training
        # process, finds one device and trains serially with a warning
        # (parallel/learners.make_grower).
        if (self.is_single_machine() and self.tree_learner != "serial"
                and self.num_devices == 1):
            log.warning("num_devices=1: using serial tree learner instead "
                        "of %s", self.tree_learner)
            self.tree_learner = "serial"
        if self.num_leaves < 2:
            log.fatal("num_leaves must be >= 2, got %d" % self.num_leaves)
        if self.max_bin < 2:
            log.fatal("max_bin must be >= 2, got %d" % self.max_bin)
        if not (0.0 < self.bagging_fraction <= 1.0):
            log.fatal("bagging_fraction must be in (0, 1], got %g" % self.bagging_fraction)
        if not (0.0 < self.feature_fraction <= 1.0):
            log.fatal("feature_fraction must be in (0, 1], got %g" % self.feature_fraction)
        if self.boosting == "goss" and self.top_rate + self.other_rate > 1.0:
            log.fatal("top_rate + other_rate must be <= 1.0 for GOSS")
        if self.top_k <= 0:
            log.fatal("top_k must be > 0, got %d" % self.top_k)
        if self.serve_max_batch_rows < 1:
            log.fatal("serve_max_batch_rows must be >= 1, got %d"
                      % self.serve_max_batch_rows)
        if self.serve_queue_rows < self.serve_max_batch_rows:
            log.fatal("serve_queue_rows (%d) must be >= serve_max_batch_rows "
                      "(%d)" % (self.serve_queue_rows,
                                self.serve_max_batch_rows))
        if self.serve_batch_wait_ms < 0 or self.serve_request_timeout_ms <= 0:
            log.fatal("serve_batch_wait_ms must be >= 0 and "
                      "serve_request_timeout_ms > 0")
        if self.tpu_checkpoint_path:
            if self.tpu_checkpoint_interval < 1:
                log.fatal("tpu_checkpoint_interval must be >= 1, got %d"
                          % self.tpu_checkpoint_interval)
            if self.tpu_checkpoint_keep < 1:
                log.fatal("tpu_checkpoint_keep must be >= 1, got %d"
                          % self.tpu_checkpoint_keep)
        if self.tpu_comm_retries < 0:
            log.fatal("tpu_comm_retries must be >= 0, got %d"
                      % self.tpu_comm_retries)
        if self.tpu_comm_backoff_ms < 0 or self.tpu_comm_backoff_max_ms < 0:
            log.fatal("tpu_comm_backoff_ms / tpu_comm_backoff_max_ms must "
                      "be >= 0")
        if self.tpu_comm_backend not in ("auto", "mesh", "socket", "hybrid"):
            log.fatal("tpu_comm_backend must be auto, mesh, socket or "
                      "hybrid, got %r" % self.tpu_comm_backend)
        if self.tpu_hybrid_local_devices < 0:
            log.fatal("tpu_hybrid_local_devices must be >= 0, got %d"
                      % self.tpu_hybrid_local_devices)
        if self.tpu_hybrid_slow_ms < 0:
            log.fatal("tpu_hybrid_slow_ms must be >= 0, got %g"
                      % self.tpu_hybrid_slow_ms)
        if self.tpu_hybrid_slow_rounds < 1:
            log.fatal("tpu_hybrid_slow_rounds must be >= 1, got %d"
                      % self.tpu_hybrid_slow_rounds)
        if self.tpu_hybrid_slow_policy not in ("observe", "demote"):
            log.fatal("tpu_hybrid_slow_policy must be observe or demote, "
                      "got %r" % self.tpu_hybrid_slow_policy)
        if self.tpu_trace_max_events < 1024:
            log.fatal("tpu_trace_max_events must be >= 1024, got %d"
                      % self.tpu_trace_max_events)
        if self.tpu_elastic:
            if self.tpu_elastic_heartbeat_ms <= 0:
                log.fatal("tpu_elastic_heartbeat_ms must be > 0, got %g"
                          % self.tpu_elastic_heartbeat_ms)
            if self.tpu_elastic_suspect_ms < self.tpu_elastic_heartbeat_ms:
                log.fatal("tpu_elastic_suspect_ms (%g) must be >= "
                          "tpu_elastic_heartbeat_ms (%g)"
                          % (self.tpu_elastic_suspect_ms,
                             self.tpu_elastic_heartbeat_ms))
            if self.tpu_elastic_min_world < 1:
                log.fatal("tpu_elastic_min_world must be >= 1, got %d"
                          % self.tpu_elastic_min_world)
            if self.tpu_elastic_sync_every < 1:
                log.fatal("tpu_elastic_sync_every must be >= 1, got %d"
                          % self.tpu_elastic_sync_every)
            if self.tpu_elastic_rejoin_s < 0:
                log.fatal("tpu_elastic_rejoin_s must be >= 0, got %g"
                          % self.tpu_elastic_rejoin_s)
        if self.tpu_serve_shed_queue_rows < 0:
            log.fatal("tpu_serve_shed_queue_rows must be >= 0, got %d"
                      % self.tpu_serve_shed_queue_rows)
        if self.tpu_serve_breaker_failures < 1:
            log.fatal("tpu_serve_breaker_failures must be >= 1, got %d"
                      % self.tpu_serve_breaker_failures)
        if (self.tpu_serve_shed_retry_after_s < 0
                or self.tpu_serve_breaker_reset_s < 0
                or self.tpu_serve_drain_timeout_s < 0):
            log.fatal("tpu_serve_shed_retry_after_s / "
                      "tpu_serve_breaker_reset_s / tpu_serve_drain_timeout_s "
                      "must be >= 0")
        if self.tpu_fleet_hbm_budget_mb < 0:
            log.fatal("tpu_fleet_hbm_budget_mb must be >= 0, got %g"
                      % self.tpu_fleet_hbm_budget_mb)
        if not (0.0 < self.tpu_fleet_low_watermark
                <= self.tpu_fleet_high_watermark <= 1.0):
            log.fatal("fleet watermarks must satisfy 0 < low <= high <= 1, "
                      "got low=%g high=%g"
                      % (self.tpu_fleet_low_watermark,
                         self.tpu_fleet_high_watermark))
        if (self.tpu_fleet_promote_retries < 0
                or self.tpu_fleet_promote_backoff_ms < 0):
            log.fatal("tpu_fleet_promote_retries / "
                      "tpu_fleet_promote_backoff_ms must be >= 0")
        if self.tpu_fleet_tenant_qps < 0 or self.tpu_fleet_tenant_burst < 0:
            log.fatal("tpu_fleet_tenant_qps / tpu_fleet_tenant_burst must "
                      "be >= 0")
        if self.tpu_replica_count < 1:
            log.fatal("tpu_replica_count must be >= 1, got %d"
                      % self.tpu_replica_count)
        if not 1 <= self.tpu_replica_min <= self.tpu_replica_max:
            log.fatal("replica bounds must satisfy 1 <= min <= max, got "
                      "min=%d max=%d" % (self.tpu_replica_min,
                                         self.tpu_replica_max))
        if self.tpu_replica_probe_interval_s < 0:
            log.fatal("tpu_replica_probe_interval_s must be >= 0, got %g"
                      % self.tpu_replica_probe_interval_s)
        if self.tpu_replica_probe_deadline_ms <= 0:
            log.fatal("tpu_replica_probe_deadline_ms must be > 0, got %g"
                      % self.tpu_replica_probe_deadline_ms)
        if self.tpu_replica_breaker_failures < 1:
            log.fatal("tpu_replica_breaker_failures must be >= 1, got %d"
                      % self.tpu_replica_breaker_failures)
        if self.tpu_replica_breaker_reset_s < 0:
            log.fatal("tpu_replica_breaker_reset_s must be >= 0, got %g"
                      % self.tpu_replica_breaker_reset_s)
        if self.tpu_quantized_bits != 8:
            log.fatal("tpu_quantized_bits: only 8-bit codes are "
                      "implemented, got %d" % self.tpu_quantized_bits)
        if self.tpu_quantized_seed < 0:
            log.fatal("tpu_quantized_seed must be >= 0, got %d"
                      % self.tpu_quantized_seed)
        if self.tpu_refit_mode not in ("refit", "continue"):
            log.fatal("tpu_refit_mode must be 'refit' or 'continue', got %r"
                      % self.tpu_refit_mode)
        if not 0 <= self.tpu_refit_holdout_fraction < 1:
            log.fatal("tpu_refit_holdout_fraction must be in [0, 1), got %g"
                      % self.tpu_refit_holdout_fraction)
        if self.tpu_continuous_learning:
            if self.tpu_refit_interval_s <= 0:
                log.fatal("tpu_refit_interval_s must be > 0, got %g"
                          % self.tpu_refit_interval_s)
            if self.tpu_refit_min_rows < 1:
                log.fatal("tpu_refit_min_rows must be >= 1, got %d"
                          % self.tpu_refit_min_rows)
            if self.tpu_refit_rounds < 1:
                log.fatal("tpu_refit_rounds must be >= 1, got %d"
                          % self.tpu_refit_rounds)
            if self.tpu_refit_buffer_rows < self.tpu_refit_min_rows:
                log.fatal("tpu_refit_buffer_rows (%d) must be >= "
                          "tpu_refit_min_rows (%d)"
                          % (self.tpu_refit_buffer_rows,
                             self.tpu_refit_min_rows))
            if self.tpu_promote_min_samples < 1:
                log.fatal("tpu_promote_min_samples must be >= 1, got %d"
                          % self.tpu_promote_min_samples)
            if self.tpu_promote_watch_s < 0:
                log.fatal("tpu_promote_watch_s must be >= 0, got %g"
                          % self.tpu_promote_watch_s)
        if self.tpu_federation_every < 1:
            log.fatal("tpu_federation_every must be >= 1, got %d"
                      % self.tpu_federation_every)
        if not 0 <= self.tpu_federation_port <= 65535:
            log.fatal("tpu_federation_port must be in [0, 65535], got %d"
                      % self.tpu_federation_port)
        if self.tpu_federation_top_phases < 1:
            log.fatal("tpu_federation_top_phases must be >= 1, got %d"
                      % self.tpu_federation_top_phases)
        if self.tpu_alert_sustain_rounds < 1:
            log.fatal("tpu_alert_sustain_rounds must be >= 1, got %d"
                      % self.tpu_alert_sustain_rounds)
        if self.tpu_alert_burn_window < 2:
            log.fatal("tpu_alert_burn_window must be >= 2, got %d"
                      % self.tpu_alert_burn_window)
        if not 0 < self.tpu_alert_comm_wait_share <= 1:
            log.fatal("tpu_alert_comm_wait_share must be in (0, 1], got %g"
                      % self.tpu_alert_comm_wait_share)
        if self.tpu_alert_shed_rate < 0:
            log.fatal("tpu_alert_shed_rate must be >= 0, got %g"
                      % self.tpu_alert_shed_rate)
        if self.tpu_policy_rate_limit <= 0:
            log.fatal("tpu_policy_rate_limit must be > 0, got %g"
                      % self.tpu_policy_rate_limit)
        if self.tpu_policy_rate_window_s <= 0:
            log.fatal("tpu_policy_rate_window_s must be > 0, got %g"
                      % self.tpu_policy_rate_window_s)
        if self.tpu_policy_cooldown_rounds < 0:
            log.fatal("tpu_policy_cooldown_rounds must be >= 0, got %d"
                      % self.tpu_policy_cooldown_rounds)
        if self.tpu_elastic_scale_up_wait_s < 0:
            log.fatal("tpu_elastic_scale_up_wait_s must be >= 0, got %g"
                      % self.tpu_elastic_scale_up_wait_s)
        if self.tpu_elastic_petition_poll_s <= 0:
            log.fatal("tpu_elastic_petition_poll_s must be > 0, got %g"
                      % self.tpu_elastic_petition_poll_s)
        if self.tpu_trend_window < 4:
            log.fatal("tpu_trend_window must be >= 4, got %d"
                      % self.tpu_trend_window)
        if self.tpu_alert_trend_slope <= 0:
            log.fatal("tpu_alert_trend_slope must be > 0, got %g"
                      % self.tpu_alert_trend_slope)
        if self.tpu_sync_guard not in ("off", "log", "fail"):
            log.fatal("tpu_sync_guard must be 'off', 'log' or 'fail', "
                      "got %r" % self.tpu_sync_guard)

    def is_single_machine(self) -> bool:
        return self.num_machines <= 1

    def to_dict(self) -> Dict[str, Any]:
        return {name: getattr(self, name) for name in PARAMETER_SET}

    def __repr__(self) -> str:
        diffs = {k: v for k, v in self.to_dict().items()
                 if v != PARAMETER_DEFAULTS.get(k)}
        return "Config(%s)" % (diffs,)


def param_dict_to_str(params: Optional[Dict[str, Any]]) -> str:
    """Python-side dict -> 'k=v k2=v2' string (python-package basic.py:128)."""
    if not params:
        return ""
    pairs: List[str] = []
    for k, v in params.items():
        if isinstance(v, (list, tuple, set)):
            pairs.append("%s=%s" % (k, ",".join(map(str, v))))
        elif isinstance(v, bool):
            pairs.append("%s=%s" % (k, "true" if v else "false"))
        elif v is None:
            continue
        else:
            pairs.append("%s=%s" % (k, v))
    return " ".join(pairs)
