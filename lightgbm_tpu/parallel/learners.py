"""Distributed tree learners over a JAX device mesh.

The TPU-native replacement for the reference's parallel learner family +
socket/MPI network stack (src/treelearner/{feature,data,voting}_parallel_
tree_learner.cpp, src/network/): instead of hand-rolled Bruck/recursive-
halving collectives over TCP (network.cpp:64-243), the grow loop runs inside
`jax.shard_map` over a 1-D mesh axis and exchanges histograms/splits with
XLA collectives (psum / all_gather) that ride ICI on a pod.

Modes (Config.tree_learner):
- "data":    rows sharded across devices (the primary TPU mode);
- "feature": data replicated, the split *search* sharded by features;
- "voting":  rows sharded + top-k vote to cap collective volume.

The reference requires a machine file and a port handshake
(linkers_socket.cpp:77-121); here the "machines" are the mesh devices and
rank = `jax.lax.axis_index`.  Multi-host pods work transparently: the same
shard_map over a mesh spanning hosts emits DCN/ICI collectives via XLA.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..ops import grow as grow_ops
from ..utils import log
from ..utils.backend import pallas_interpret
from . import collective as coll_mod
from .collective import AXIS  # noqa: F401 — canonical home moved there
from .collective import shard_mapped as _shard_mapped


def resolve_num_machines(config, available: Optional[int] = None) -> int:
    """Device count for the parallel learners: min(num_machines, devices),
    defaulting to every local device (a pod slice is the natural 'cluster';
    there is no machine-list file, cf. config.h:748-755 machine_list_filename)."""
    if available is None:
        available = jax.device_count()
    want = config.num_machines if config.num_machines > 1 else available
    if want > available:
        log.warning("num_machines=%d > available devices=%d; clamping",
                    want, available)
    return max(1, min(want, available))


class ParallelGrower:
    """Callable matching grow_ops.grow_tree's contract, running the grow
    loop shard_map'd over a device mesh.

    Pads rows (data/voting) or features (feature) to a multiple of the
    device count; padded rows enter with leaf id -1 (never in-bag), padded
    features get num_bins=0 + feature_mask=False so no scan can pick them.
    """

    def __init__(self, mode: str, num_machines: int, top_k: int = 20,
                 devices=None, collective=None):
        assert mode in ("data", "feature", "voting"), mode
        self.mode = mode
        self.d = num_machines
        self.top_k = top_k
        if collective is None:
            collective = coll_mod.MeshCollective(num_machines,
                                                 devices=devices)
        self.collective = collective
        if collective.backend == "mesh":
            self.mesh = collective.mesh
            self._axis = AXIS
        elif collective.backend == "hybrid":
            # host-first then device-second: this process holds its
            # host's row shard, shard_map splits it over the local mesh,
            # and the HybridAxis composes psum-over-ICI with the leader
            # wire — rows are pre-partitioned across hosts, so only the
            # data learner is meaningful (parallel/hybrid.py)
            if mode != "data":
                raise ValueError(
                    "tpu_comm_backend=hybrid supports tree_learner=data "
                    "only (rows are pre-partitioned across hosts); got %r"
                    % mode)
            self.mesh = collective.mesh
            self._axis = collective.axis()
        else:
            # cross-host: every rank runs the SAME grow program over its
            # local shard, collectives rendezvous on the wire through the
            # SocketAxis handle — rows are already pre-partitioned, so
            # only the data learner is meaningful here
            if mode != "data":
                raise ValueError(
                    "tpu_comm_backend=socket supports tree_learner=data "
                    "only (rows are pre-partitioned across hosts); got %r"
                    % mode)
            self.mesh = None
            self._axis = collective.axis()
        self._cache = {}
        # partition (arena) engine fast path — opted in by the GBDT
        # driver when the dataset is eligible (f32, max_bin<=256, n<2^24,
        # no forced splits); all three modes run on it, the label engine
        # serves the configs that are not eligible
        self._partition = None
        self._pcache = {}
        self._arena = None
        self._bins_t = None
        self._bins_key = None
        self.last_truncated = None
        # donation forensics (obs/device.donation_audit): the GBDT driver
        # flips audit_donation on when telemetry is armed; each partition
        # executable is walked once per build, against the raw jitted fn
        # kept in _praw (the bind/reshard wrappers cannot .lower())
        self.audit_donation = False
        self._praw = {}
        self._audited = set()

    # ------------------------------------------------------------------ #
    def enable_partition(self, arena_factor: int, hist_slots: int = 0):
        """arena_factor: local arena columns per local row (config
        tpu_arena_factor).  The root compaction takes 2n, the rest is the
        bump region; under row sharding the overflow bound is the LOCAL
        PARENT size, so at the sizing minimum of 3 a shard of millions
        of rows stops at 3 leaves (four-chip run, PR 21 — the 16-tile
        tail hides it at test sizes)."""
        self._partition = dict(hist_slots=hist_slots,
                               arena_factor=arena_factor)

    def disable_partition(self):
        self._partition = None
        self._pcache = {}
        self._arena = None
        self._bins_t = None
        self._bins_key = None

    # ------------------------------------------------------------------ #
    def _build(self, statics: tuple):
        fn = self._cache.get(statics)
        if fn is not None:
            return fn
        if self.mesh is None or self.collective.backend == "hybrid":
            raise RuntimeError(
                "the %s collective backend requires the partition "
                "engine (label-engine collectives are mesh-only)"
                % self.collective.backend)
        (max_leaves, max_depth, max_bin, hist_impl, rows_per_chunk,
         max_cat_threshold) = statics
        inner = partial(grow_ops.grow_tree_impl,
                        max_leaves=max_leaves, max_depth=max_depth,
                        max_bin=max_bin, hist_impl=hist_impl,
                        rows_per_chunk=rows_per_chunk,
                        learner=self.mode, axis_name=AXIS,
                        num_machines=self.d, top_k=self.top_k,
                        max_cat_threshold=max_cat_threshold)
        if self.mode in ("data", "voting"):
            row = P(AXIS)
            in_specs = (P(AXIS, None), row, row, row,
                        P(), P(), P(), P(), P(), P(), P(), P(),
                        P(), P(), P())
            out_specs = (P(), P(AXIS))
        else:  # feature: everything replicated, search sharded internally
            in_specs = tuple(P() for _ in range(15))
            out_specs = (P(), P())
        fn = jax.jit(_shard_mapped(inner, self.mesh, in_specs, out_specs))
        fn = self.collective.bind(("label",) + statics, fn) \
            if isinstance(self.collective, coll_mod.MeshCollective) else fn
        self._cache[statics] = fn
        return fn

    # ------------------------------------------------------------------ #
    def __call__(self, bins, grad, hess, row_leaf_init, feature_mask,
                 num_bins, default_bins, missing_types, params,
                 monotone=None, penalty=None, is_categorical=None,
                 bundle=None, *,
                 max_leaves: int, max_depth: int = -1, max_bin: int,
                 hist_impl: str = "auto", rows_per_chunk: int = 16384,
                 max_cat_threshold: int = 32,
                 quantized: bool = False, quant_scales=None):
        n, F = bins.shape
        if bundle is not None and self.mode == "feature":
            raise ValueError("feature-parallel learner does not support "
                             "EFB-bundled datasets")
        d = self.d
        if self._partition is not None:
            # the engine was chosen at setup; a failure here propagates
            return self._call_partition(
                bins, grad, hess, row_leaf_init, feature_mask,
                num_bins, default_bins, missing_types, params,
                monotone, penalty, is_categorical, bundle,
                max_leaves=max_leaves, max_depth=max_depth,
                max_bin=max_bin, max_cat_threshold=max_cat_threshold,
                quantized=quantized, quant_scales=quant_scales)
        if quantized:
            raise RuntimeError("quantized codes require the partition "
                               "engine; it is not enabled on this grower")
        self.last_truncated = None      # label engine never truncates
        if self.mode in ("data", "voting"):
            pad = (-n) % d
            if pad:
                bins = jnp.pad(bins, ((0, pad), (0, 0)))
                grad = jnp.pad(grad, (0, pad))
                hess = jnp.pad(hess, (0, pad))
                row_leaf_init = jnp.pad(row_leaf_init, (0, pad),
                                        constant_values=-1)
        else:  # feature
            pad = (-F) % d
            if pad:
                bins = jnp.pad(bins, ((0, 0), (0, pad)))
                feature_mask = jnp.pad(feature_mask, (0, pad))
                num_bins = jnp.pad(num_bins, (0, pad))
                default_bins = jnp.pad(default_bins, (0, pad))
                missing_types = jnp.pad(missing_types, (0, pad))
                if monotone is not None:
                    monotone = jnp.pad(monotone, (0, pad))
                if penalty is not None:
                    penalty = jnp.pad(penalty, (0, pad),
                                      constant_values=1.0)
                if is_categorical is not None:
                    is_categorical = jnp.pad(is_categorical, (0, pad))

        fn = self._build((max_leaves, max_depth, max_bin, hist_impl,
                          rows_per_chunk, max_cat_threshold))
        tree, leaf_ids = fn(bins, grad, hess, row_leaf_init, feature_mask,
                            num_bins, default_bins, missing_types, params,
                            monotone, penalty, is_categorical,
                            None, None, bundle)
        if self.mode in ("data", "voting") and leaf_ids.shape[0] != n:
            leaf_ids = leaf_ids[:n]
        return tree, leaf_ids


    # ------------------------------------------------------------------ #
    # Partition (arena) engine under shard_map: the flagship kernels run
    # per device over local arenas — data/voting shard rows, feature
    # replicates them — so the distributed modes keep the serial fast
    # path's asymptotics instead of dropping to the label engine's
    # masked full-n passes (VERDICT r3 weak #3).
    # ------------------------------------------------------------------ #
    def _build_partition(self, statics: tuple):
        fn = self._pcache.get(statics)
        if fn is not None:
            return fn
        from ..ops import grow_partition as gp
        (max_leaves, max_depth, max_bin, max_cat_threshold, C, cap,
         hist_slots, interpret, quantized) = statics
        d, mode, top_k = self.d, self.mode, self.top_k
        axis = self._axis      # AXIS for mesh, the HybridAxis for hybrid
        row_shard = mode in ("data", "voting")

        def shard_fn(arena, bins_t, g, h, r0, fmask, nb, db, mt, sparams,
                     mono, pen, icat, bnd, qsc):
            t, l, arena_out, trunc = gp.grow_tree_partition_impl(
                arena[0], bins_t, g, h, r0, fmask, nb, db, mt, sparams,
                mono, pen, None, None, icat, bnd,
                max_leaves=max_leaves, max_depth=max_depth,
                max_bin=max_bin, emit="leaf_ids", full_bag=False,
                max_cat_threshold=max_cat_threshold, axis_name=axis,
                learner=mode, num_machines=d, top_k=top_k,
                hist_slots=hist_slots, interpret=interpret,
                quantized=quantized,
                quant_scales=(qsc[0], qsc[1]) if quantized else None)
            return t, l, arena_out[None], trunc

        rp = P(AXIS) if row_shard else P()
        in_specs = (P(AXIS, None, None),
                    P(None, AXIS) if row_shard else P(),
                    rp, rp, rp,
                    P(), P(), P(), P(), P(), P(), P(), P(), P(), P())
        out_specs = (P(), rp, P(AXIS, None, None), P())
        jit_kw = {}
        if not isinstance(axis, str):
            # hybrid: the ordered io_callbacks inside thread an XLA token
            # through the entry computation, adding a hidden parameter;
            # with inferred shardings XLA's spmd-propagation-to-parameters
            # vector is sized to the USER parameters only and the
            # mismatch is a fatal CHECK (sharding_propagation.cc) that
            # aborts the process.  Explicit shardings sidestep the
            # propagation pass entirely.
            def _ns(spec):
                return jax.sharding.NamedSharding(self.mesh, spec)
            jit_kw = dict(in_shardings=tuple(_ns(s) for s in in_specs),
                          out_shardings=tuple(_ns(s) for s in out_specs))
        # donate_argnums=(0,): the arena is the ONLY donated input.
        # bins_t and the bag mask persist across rounds; grad/hess are
        # the caller's (the driver's score update may still read them).
        # The donation audit marks them resident instead of un-donated.
        fn = jax.jit(_shard_mapped(shard_fn, self.mesh, in_specs,
                                   out_specs),
                     donate_argnums=(0,), **jit_kw)
        self._praw[statics] = fn
        if jit_kw:
            # explicit in_shardings REFUSE already-committed args whose
            # sharding differs (e.g. a replicated grad plane rebuilt by
            # an elastic restore); device_put reshards them and is a
            # no-op when the sharding already matches — the donated
            # arena passes through untouched on the steady-state path
            shardings = jit_kw["in_shardings"]
            jitted = fn

            def fn(*args):
                args = tuple(a if a is None else jax.device_put(a, s)
                             for a, s in zip(args, shardings))
                return jitted(*args)
        fn = self.collective.bind(("partition",) + statics, fn)
        self._pcache[statics] = fn
        return fn

    def _build_partition_socket(self, statics: tuple):
        """Socket twin of _build_partition: no shard_map — each rank jits
        the grow program over its LOCAL arena with the SocketAxis handle
        as axis_name, so every collective inside rendezvouses on the
        wire.  Programs are identical across ranks (same statics), which
        is what keeps the ordered callbacks symmetric."""
        fn = self._pcache.get(statics)
        if fn is not None:
            return fn
        from ..ops import grow_partition as gp
        (max_leaves, max_depth, max_bin, max_cat_threshold, C, cap,
         hist_slots, interpret, quantized) = statics
        d, mode, top_k, axis = self.d, self.mode, self.top_k, self._axis

        def local_fn(arena, bins_t, g, h, r0, fmask, nb, db, mt, sparams,
                     mono, pen, icat, bnd, qsc):
            t, l, arena_out, trunc = gp.grow_tree_partition_impl(
                arena[0], bins_t, g, h, r0, fmask, nb, db, mt, sparams,
                mono, pen, None, None, icat, bnd,
                max_leaves=max_leaves, max_depth=max_depth,
                max_bin=max_bin, emit="leaf_ids", full_bag=False,
                max_cat_threshold=max_cat_threshold, axis_name=axis,
                learner=mode, num_machines=d, top_k=top_k,
                hist_slots=hist_slots, interpret=interpret,
                quantized=quantized,
                quant_scales=(qsc[0], qsc[1]) if quantized else None)
            return t, l, arena_out[None], trunc

        # arena-only donation, same residency argument as _build_partition
        jitted = jax.jit(local_fn, donate_argnums=(0,))
        self._praw[statics] = jitted

        def wrapped(*args):
            out = jitted(*args)
            # surface wire failures parked by the host callbacks —
            # WorldChangedError re-raises here with the fence intact
            jax.block_until_ready(out[3])
            axis.check_failure()
            return out

        self._pcache[statics] = wrapped
        return wrapped

    def _call_partition(self, bins, grad, hess, row_leaf_init, feature_mask,
                        num_bins, default_bins, missing_types, params,
                        monotone, penalty, is_categorical, bundle, *,
                        max_leaves: int, max_depth: int, max_bin: int,
                        max_cat_threshold: int,
                        quantized: bool = False, quant_scales=None):
        import jax.numpy as jnp

        from ..ops import partition_pallas as pp
        n, G = bins.shape
        F = num_bins.shape[0]
        socket = self.mesh is None
        # socket ranks hold only their local shard: one local arena, no
        # cross-rank padding (the wire doesn't care about row counts)
        d = 1 if socket else self.d
        row_shard = self.mode in ("data", "voting")
        if socket:
            pad_r, pad_f = 0, 0
        elif row_shard:
            pad_r, pad_f = (-n) % d, 0
        else:
            # FP shards the SEARCH by features: pad features to d; data
            # (and the arena channel set) is replicated
            pad_r, pad_f = 0, (-F) % d
        n_pad, F_pad = n + pad_r, F + pad_f
        n_loc = n_pad // d if row_shard else n_pad
        G_pad = G + pad_f                  # G == F for FP (no EFB)
        C, cap = pp.arena_geometry(n_loc, G_pad,
                                   self._partition["arena_factor"])

        # the key holds a STRONG reference to the bins array: a bare
        # id() could be recycled after a dataset swap + GC, silently
        # reusing the previous dataset's transposed bins
        key = (bins, n, G, self.mode)
        if not (self._bins_key is not None
                and self._bins_key[0] is key[0]
                and self._bins_key[1:] == key[1:]):
            bt = jnp.asarray(bins, pp.ARENA_DT)
            if pad_r or pad_f:
                bt = jnp.pad(bt, ((0, pad_r), (0, pad_f)))
            self._bins_t = bt.T
            self._bins_key = key
            self._arena = None
        if self._arena is None or self._arena.shape != (d, C, cap):
            self._arena = jnp.zeros((d, C, cap), pp.ARENA_DT)
        if pad_r:
            grad = jnp.pad(grad, (0, pad_r))
            hess = jnp.pad(hess, (0, pad_r))
            row_leaf_init = jnp.pad(row_leaf_init, (0, pad_r),
                                    constant_values=-1)
        if pad_f:
            feature_mask = jnp.pad(feature_mask, (0, pad_f))
            num_bins = jnp.pad(num_bins, (0, pad_f))
            default_bins = jnp.pad(default_bins, (0, pad_f))
            missing_types = jnp.pad(missing_types, (0, pad_f))
            if monotone is not None:
                monotone = jnp.pad(monotone, (0, pad_f))
            if penalty is not None:
                penalty = jnp.pad(penalty, (0, pad_f), constant_values=1.0)
            if is_categorical is not None:
                is_categorical = jnp.pad(is_categorical, (0, pad_f))

        interpret = pallas_interpret()
        statics = (max_leaves, max_depth, max_bin, max_cat_threshold, C,
                   cap, self._partition["hist_slots"], interpret,
                   bool(quantized))
        # the builder returns a donating jit but does NOT donate
        # `statics` (a hashable int tuple, the cache key); bind the
        # audit key up front so nothing re-reads `statics` past the
        # build, which the donation-use-after checker cannot tell apart
        # from a donated-buffer read
        audit_key = statics if self.audit_donation else None
        fn = (self._build_partition_socket(statics) if socket
              else self._build_partition(statics))
        if quantized:
            qsc = jnp.stack([jnp.asarray(quant_scales[0], jnp.float32),
                             jnp.asarray(quant_scales[1], jnp.float32)])
        else:
            qsc = jnp.zeros((2,), jnp.float32)
        call_args = (self._arena, self._bins_t, grad, hess, row_leaf_init,
                     feature_mask, num_bins, default_bins, missing_types,
                     params, monotone, penalty, is_categorical, bundle, qsc)
        audit_raw = None
        if audit_key is not None and audit_key not in self._audited:
            self._audited.add(audit_key)
            audit_raw = self._praw.get(audit_key)
        tree, leaf_ids, self._arena, self.last_truncated = fn(*call_args)
        if audit_raw is not None:
            # AFTER the call: .lower() before the first execution would
            # populate the jaxpr cache outside capture_traced and starve
            # the collective byte accounting; post-call it is a cache hit
            from ..obs import device as obs_device
            # resident leaves 1-4: bins_t (dataset plane), grad/hess
            # (the caller's), row_leaf_init (the bag mask, reused until
            # the next bagging round).  call_args[0] was
            # donated into the call just made; lower with the
            # (identically-shaped) output arena instead
            obs_device.donation_audit(
                audit_raw, (self._arena,) + call_args[1:],
                label="partition/%s_w%d%s" % (
                    self.mode, self.d, "_q" if quantized else ""),
                resident=(1, 2, 3, 4))
        if leaf_ids.shape[0] != n:
            leaf_ids = leaf_ids[:n]
        return tree, leaf_ids


def make_grower(config, dataset_num_features: int):
    """GBDT-facing factory (TreeLearner::CreateTreeLearner,
    src/treelearner/tree_learner.cpp:9-33): returns None for the serial
    learner, else a ParallelGrower over the resolved Collective backend
    (mesh when the local devices allow it, socket when a cross-host comm
    is attached and tpu_comm_backend selects it — see
    parallel/collective.py and docs/Distributed.md)."""
    mode = config.tree_learner
    if mode == "serial":
        return None
    collective = coll_mod.make_collective(config)
    if collective is None:
        log.warning("tree_learner=%s requested but no collective backend "
                    "is available (one device, no attached comm); using "
                    "serial learner", mode)
        return None
    # the grower's machine count is the SHARD_MAP width: the local mesh
    # for hybrid (host payloads ride the leader wire at host rank/world),
    # the full world otherwise
    d = (collective.local_world if collective.backend == "hybrid"
         else collective.world)
    if mode == "feature" and dataset_num_features < d:
        log.warning("feature-parallel with fewer features (%d) than devices "
                    "(%d); padded features will idle some devices",
                    dataset_num_features, d)
    return ParallelGrower(mode, d, top_k=config.top_k,
                          collective=collective)
