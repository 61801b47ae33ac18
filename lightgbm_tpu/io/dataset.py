"""The binned training dataset.

TPU-native analogue of the reference Dataset (include/LightGBM/dataset.h:281-634,
src/io/dataset.cpp): raw feature columns are mapped through per-feature
BinMappers into a dense device-resident bin matrix `[num_data, num_features]`
(uint8 when every feature has <=256 bins, else uint16).  Histograms are flat
`[total_bins, 3]` arrays addressed by per-feature offsets — the dense layout
replaces the reference's FeatureGroup/sparse-bin machinery, which does not map
to TPU (the reference's own GPU learner also densifies sparse groups); EFB
bundling (io/efb.py) keeps the column count down for sparse-wide data.
"""
from __future__ import annotations

import json as _json
import os
from concurrent.futures import ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence

import numpy as np

from ..obs import tracing
from ..utils import log
from .bin_mapper import CATEGORICAL, NUMERICAL, BinMapper
from .file_io import v_open
from .metadata import Metadata

_BINARY_MAGIC = "lightgbm_tpu_dataset_v1"


def _issparse(X) -> bool:
    try:
        import scipy.sparse as sp
        return sp.issparse(X)
    except ImportError:
        return False


def concat_fill(a, b, n0: int, n1: int, fill: float):
    """Concatenate two optional per-row vectors, filling the absent side
    with `fill` (labels 0.0, weights the NEUTRAL 1.0) — the single home
    of the add_data_from fill semantics (shared with basic.Dataset)."""
    if a is None and b is None:
        return None
    a = np.full(n0, fill, np.float64) if a is None else np.asarray(a)
    b = np.full(n1, fill, np.float64) if b is None else np.asarray(b)
    return np.concatenate([a, b])


class IngestError(ValueError):
    """A streaming-ingest block was rejected at the validation boundary.

    `reason` is the shed-counter label: "feature_mismatch", "bad_shape"
    or "bad_label"."""

    def __init__(self, reason: str, msg: str):
        super().__init__(msg)
        self.reason = reason


def _shed(reason: str, rows: int) -> None:
    from ..obs import default_registry
    default_registry().counter(
        "lgbm_ingest_shed_total",
        help="ingest rows shed at the validation boundary",
        reason=reason).inc(rows)


def validate_ingest_block(X, label=None, weight=None, *, num_features: int,
                          shed: bool = False):
    """Validate one raw ingest block against the frozen feature schema.

    Returns ``(X, label, weight)`` as float64 arrays.  Block-level
    malformations — wrong rank, feature-count mismatch, label/weight
    length mismatch — raise :class:`IngestError`: there is no defensible
    per-row repair, and letting them through is exactly how NaNs reach
    the score planes.  Per-row bad labels (NaN/inf) also raise unless
    ``shed=True``, in which case only the offending rows are dropped.
    Every rejected row lands on ``lgbm_ingest_shed_total{reason=...}``.
    """
    X = np.asarray(X, dtype=np.float64)
    if X.ndim == 1:
        X = X.reshape(1, -1)
    if X.ndim != 2:
        raise IngestError("bad_shape",
                          "ingest block must be 2-D, got ndim=%d" % X.ndim)
    n = int(X.shape[0])
    if X.shape[1] != num_features:
        _shed("feature_mismatch", n)
        raise IngestError("feature_mismatch",
                          "ingest block has %d features, dataset expects %d"
                          % (X.shape[1], num_features))
    if label is not None:
        label = np.asarray(label, dtype=np.float64).reshape(-1)
        if label.shape[0] != n:
            _shed("bad_shape", n)
            raise IngestError("bad_shape", "%d labels for %d rows"
                              % (label.shape[0], n))
    if weight is not None:
        weight = np.asarray(weight, dtype=np.float64).reshape(-1)
        if weight.shape[0] != n:
            _shed("bad_shape", n)
            raise IngestError("bad_shape", "%d weights for %d rows"
                              % (weight.shape[0], n))
    if label is not None:
        bad = ~np.isfinite(label)
        nbad = int(bad.sum())
        if nbad:
            _shed("bad_label", nbad)
            if not shed:
                raise IngestError("bad_label",
                                  "%d of %d rows carry NaN/inf labels"
                                  % (nbad, n))
            keep = ~bad
            X, label = X[keep], label[keep]
            if weight is not None:
                weight = weight[keep]
    return X, label, weight


_BIN_BLOCK_ROWS = 16384


def _map_columns(fn, items) -> list:
    """[fn(i) for i in items], the calls spread over the host's cores.
    Finding one column's bins or binning one block of rows depends on
    nothing else, so the results are those of the serial loop; the work
    is numpy sorts and searches, which release the interpreter lock."""
    items = list(items)
    workers = min(len(items), os.cpu_count() or 1, 32)
    if workers <= 1:
        return [fn(i) for i in items]
    with ThreadPoolExecutor(max_workers=workers) as pool:
        return list(pool.map(fn, items))


class BinnedDataset:
    """Binned feature matrix + per-feature mappers + metadata."""

    def __init__(self):
        self.num_data: int = 0
        self.num_total_features: int = 0          # raw column count
        self.used_feature_map: List[int] = []      # raw idx -> inner idx or -1
        self.real_feature_index: List[int] = []    # inner idx -> raw idx
        self.bin_mappers: List[BinMapper] = []     # per inner feature
        self.bins: Optional[np.ndarray] = None     # [n, F_used] uint8/16 host
        #   (with EFB bundling active: [n, num_groups] bundled columns —
        #    see io/efb.py for the encoding; self.bundle holds the layout)
        self.bundle = None                         # Optional[efb.BundleInfo]
        self.feature_offsets: Optional[np.ndarray] = None  # [F_used+1] i32
        self.metadata = Metadata()
        self.feature_names: List[str] = []
        self.monotone_constraints: Optional[np.ndarray] = None  # [F_used] i8
        self.feature_penalty: Optional[np.ndarray] = None       # [F_used] f64
        self.max_bin: int = 255
        # distributed row-partition identity (parallel/dist_data.py):
        # this shard's rows' GLOBAL indices and the global row count.
        # Quantized data-parallel training draws its stochastic-rounding
        # noise from the global stream at these indices so the union of
        # every rank's codes is bitwise a single encoder's output.
        self.dist_row_ids: Optional[np.ndarray] = None
        self.dist_global_rows: Optional[int] = None
        self._device_cache: Dict[str, object] = {}

    # ------------------------------------------------------------------ #
    # Construction
    # ------------------------------------------------------------------ #
    @classmethod
    def construct(cls, X: np.ndarray, config, metadata: Optional[Metadata] = None,
                  categorical_features: Sequence[int] = (),
                  feature_names: Optional[Sequence[str]] = None,
                  reference: Optional["BinnedDataset"] = None,
                  sample_indices: Optional[np.ndarray] = None,
                  find_bin_comm=None,
                  sample_override=None,
                  bin_rows: bool = True) -> "BinnedDataset":
        """Build from a raw float matrix.

        With `reference` given, reuse its bin mappers (validation-set path,
        dataset.h CreateValid / basic.py reference alignment).

        X may be a scipy.sparse matrix: binning then works column-wise on
        the stored entries only (the CSR/CSC ingestion of c_api.cpp:
        602-747) — the dense [n, F] float matrix is never materialized,
        and with EFB the binned output is [n, num_groups] directly.
        """
        # datasets are binned before the booster exists, so this is the
        # earliest call site that can arm the tracer from the config —
        # without it the data/* spans of a tpu_trace_path run would be
        # lost to an unarmed tracer
        tracing.configure_from_config(config)
        with tracing.span("data/construct", "data",
                          reference=reference is not None):
            return cls._construct_impl(
                X, config, metadata=metadata,
                categorical_features=categorical_features,
                feature_names=feature_names, reference=reference,
                sample_indices=sample_indices, find_bin_comm=find_bin_comm,
                sample_override=sample_override, bin_rows=bin_rows)

    @classmethod
    def _construct_impl(cls, X, config, metadata=None,
                        categorical_features=(), feature_names=None,
                        reference=None, sample_indices=None,
                        find_bin_comm=None, sample_override=None,
                        bin_rows: bool = True) -> "BinnedDataset":
        if _issparse(X):
            import scipy.sparse as sp
            X = X.tocsr()
        else:
            X = np.asarray(X)
            if X.ndim != 2:
                log.fatal("Input data must be 2-dimensional")
        n, num_raw = X.shape
        ds = cls()
        ds.num_data = n
        ds.num_total_features = num_raw
        ds.metadata = metadata if metadata is not None else Metadata(n)
        ds.metadata.init(n)

        if reference is not None:
            if num_raw != reference.num_total_features:
                log.fatal("The number of features in data (%d) is not the same "
                          "as it was in training data (%d)"
                          % (num_raw, reference.num_total_features))
            ds.used_feature_map = list(reference.used_feature_map)
            ds.real_feature_index = list(reference.real_feature_index)
            ds.bin_mappers = reference.bin_mappers
            ds.feature_names = list(reference.feature_names)
            ds.feature_offsets = reference.feature_offsets
            ds.monotone_constraints = reference.monotone_constraints
            ds.feature_penalty = reference.feature_penalty
            ds.max_bin = reference.max_bin
            ds.bundle = reference.bundle     # same bundled layout
            ds._bin_all(X)
            return ds

        ds.max_bin = config.max_bin
        cat_set = set(int(c) for c in categorical_features)
        # --- sample rows for bin finding (bin_construct_sample_cnt) -------
        if sample_override is not None:
            # distributed ingest pre-assembled the sample from per-rank
            # row shards (dist_data.exchange_sample_rows): same indices
            # and values the local extraction below would produce, so
            # everything downstream is bitwise-identical
            sample_indices, Xs = sample_override
            sample_indices = np.asarray(sample_indices)
        else:
            sample_cnt = min(config.bin_construct_sample_cnt, n)
            if sample_indices is None:
                rng = np.random.RandomState(config.data_random_seed)
                sample_indices = (np.arange(n) if sample_cnt >= n else
                                  np.sort(rng.choice(n, sample_cnt,
                                                     replace=False)))
            Xs = X[sample_indices]
        if _issparse(Xs):
            Xs = Xs.tocsc()   # column access for find-bin / bundling

        # --- find bins per raw feature ------------------------------------
        # trivial-feature filter count scales with the sampling fraction
        # (dataset_loader.cpp:849-850)
        filter_cnt = max(1, int(config.min_data_in_leaf * len(sample_indices) / n))

        # a dense sample is read a column at a time: transpose it once, so
        # that a column is contiguous and not one value per cache line
        XsT = None if _issparse(Xs) else np.ascontiguousarray(
            np.asarray(Xs).T)

        def _find_one(f: int) -> BinMapper:
            if XsT is None:
                # stored entries only — implicit zeros are not "nonzero"
                col = np.asarray(
                    Xs.data[Xs.indptr[f]:Xs.indptr[f + 1]], np.float64)
            else:
                col = np.asarray(XsT[f], dtype=np.float64)
            nonzero = col[(np.abs(col) > 1e-35) | np.isnan(col)]
            m = BinMapper()
            m.find_bin(nonzero, Xs.shape[0],
                       config.max_bin, config.min_data_in_bin,
                       filter_cnt,
                       CATEGORICAL if f in cat_set else NUMERICAL,
                       config.use_missing, config.zero_as_missing)
            return m

        if find_bin_comm is not None:
            # distributed find-bin (dataset_loader.cpp:873-955): each rank
            # finds bins only for its contiguous feature shard, then the
            # serialized mappers are allgathered and merged — compute
            # sharding, identical mappers to a single-rank load
            rank, world, allgather = find_bin_comm
            with tracing.span("data/find_bin", "data", features=num_raw,
                              distributed=True):
                per = -(-num_raw // world)
                lo, hi = rank * per, min((rank + 1) * per, num_raw)
                mine = {f: m.to_state() for f, m in zip(
                    range(lo, hi), _map_columns(_find_one, range(lo, hi)))}
                merged: dict = {}
                for part in allgather(mine):
                    # normalize keys: a byte transport (e.g. JSON) may have
                    # stringified the int feature ids
                    merged.update({int(k): v for k, v in part.items()})
                missing = [f for f in range(num_raw) if f not in merged]
                if missing:
                    log.fatal("distributed find-bin allgather is missing "
                              "mappers for features %s" % missing[:10])
                mappers: List[BinMapper] = [BinMapper.from_state(merged[f])
                                            for f in range(num_raw)]
        else:
            with tracing.span("data/find_bin", "data", features=num_raw,
                              distributed=False):
                mappers = _map_columns(_find_one, range(num_raw))

        # --- drop trivial features (dataset.cpp Construct) ----------------
        ds.used_feature_map = [-1] * num_raw
        for f, m in enumerate(mappers):
            if not m.is_trivial:
                ds.used_feature_map[f] = len(ds.real_feature_index)
                ds.real_feature_index.append(f)
                ds.bin_mappers.append(m)
        if not ds.real_feature_index:
            log.warning("There are no meaningful features, as all feature "
                        "values are constant.")
        ds.feature_names = (list(feature_names) if feature_names
                            else ["Column_%d" % i for i in range(num_raw)])
        ds._set_offsets()
        ds._resolve_constraints(config)
        ds._find_bundles(Xs, config)
        if bin_rows:
            ds._bin_all(X)
        # else: mapper-only construction (distributed ingest — the caller
        # bins its row shard against these mappers via `reference`)
        return ds

    def _find_bundles(self, Xs: np.ndarray, config) -> None:
        """EFB grouping from the sampled rows (FastFeatureBundling,
        dataset.cpp:139-212).  Decided on the sample so the full
        per-feature matrix never needs materializing for wide data."""
        if not config.enable_bundle or self.num_features <= 1:
            return
        if config.tree_learner == "feature":
            # feature-parallel shards scan units by raw feature; bundled
            # columns would shard groups instead — keep features separate
            log.debug("EFB disabled for feature-parallel tree learner")
            return
        from . import efb
        F = self.num_features
        S = Xs.shape[0]
        # the sample's non-default rows per feature are on the mappers
        # already (sparse_rate is the default bin's share of the same
        # sample).  Two features whose counts sum past the sample plus the
        # allowed conflicts can never share a group (efb.find_groups): when
        # that holds for the two sparsest, no column is sparse enough to
        # bundle, and the search, which would first bin every sampled
        # column again and mark F * S rows, is skipped with its result.
        dense = sorted(S * (1.0 - m.sparse_rate) for m in self.bin_mappers)
        if dense[0] + dense[1] > S + int(S * config.max_conflict_rate) + 2:
            log.debug("EFB skipped: no two of %d features are sparse "
                      "enough to share a column", F)
            return
        with tracing.span("data/bundle", "data", features=F,
                          dropped_trivial=self.num_total_features - F) as sp_:
            nonzero_rows = []
            for inner, raw in enumerate(self.real_feature_index):
                m = self.bin_mappers[inner]
                if _issparse(Xs):
                    j0, j1 = Xs.indptr[raw], Xs.indptr[raw + 1]
                    rows = Xs.indices[j0:j1]
                    b = m.values_to_bins(
                        np.asarray(Xs.data[j0:j1], np.float64))
                    nonzero_rows.append(rows[b != m.default_bin])
                else:
                    b = m.values_to_bins(np.asarray(Xs[:, raw], np.float64))
                    nonzero_rows.append(np.flatnonzero(b != m.default_bin))
            self.bundle = efb.fast_feature_bundling(
                nonzero_rows, S, [m.num_bin for m in self.bin_mappers],
                [m.default_bin for m in self.bin_mappers],
                config.max_conflict_rate, config.min_data_in_leaf,
                self.num_data)
            found = dict(groups=F, largest_group_bins=0, conflicts=0)
            if self.bundle is not None:
                found = dict(
                    groups=self.bundle.num_groups,
                    largest_group_bins=int(self.bundle.group_num_bins.max()),
                    conflicts=self.bundle.conflicts)
                log.info("EFB bundled %d features into %d groups",
                         F, self.bundle.num_groups)
            # what the search found rides the span with what was known
            # at its start
            sp_.set_metadata(**found)

    def _set_offsets(self) -> None:
        nb = [m.num_bin for m in self.bin_mappers]
        self.feature_offsets = np.concatenate([[0], np.cumsum(nb)]).astype(np.int32)

    def _resolve_constraints(self, config) -> None:
        F = self.num_features
        if config.monotone_constraints:
            if len(config.monotone_constraints) != self.num_total_features:
                log.fatal("monotone_constraints has %d entries but data has %d "
                          "features" % (len(config.monotone_constraints),
                                        self.num_total_features))
            self.monotone_constraints = np.array(
                [config.monotone_constraints[raw] for raw in self.real_feature_index],
                dtype=np.int8)
        if config.feature_contri:
            if len(config.feature_contri) != self.num_total_features:
                log.fatal("feature_contri has %d entries but data has %d features"
                          % (len(config.feature_contri), self.num_total_features))
            self.feature_penalty = np.array(
                [config.feature_contri[raw] for raw in self.real_feature_index],
                dtype=np.float64)

    def bin_block(self, X) -> np.ndarray:
        """Bin a dense row block against the fitted mappers:
        [k, num_raw] floats -> [k, num_groups_or_features] packed bins.
        Used by _bin_all and by the two_round streaming loader (chunks
        binned straight into a preallocated matrix)."""
        n = X.shape[0]
        F = self.num_features
        if self.bundle is not None:
            # bundled build: one column at a time straight into its group
            # column (later features of a group win conflicts, matching
            # sequential FeatureGroup::PushData) — the full [n, F] matrix
            # is never materialized
            info = self.bundle
            dtype = (np.uint8 if int(info.group_num_bins.max()) <= 256
                     else np.uint16)
            bins = np.zeros((n, info.num_groups), dtype)
            for g, feats in enumerate(info.groups):
                if len(feats) == 1:
                    inner = feats[0]
                    raw = self.real_feature_index[inner]
                    bins[:, g] = self.bin_mappers[inner].values_to_bins(
                        np.asarray(X[:, raw], np.float64)).astype(dtype)
                    continue
                col = np.zeros(n, np.int64)
                for inner in feats:
                    raw = self.real_feature_index[inner]
                    b = self.bin_mappers[inner].values_to_bins(
                        np.asarray(X[:, raw], np.float64)).astype(np.int64)
                    nz = b != int(info.feature_default[inner])
                    col = np.where(nz, b + int(info.feature_shift[inner]), col)
                bins[:, g] = col.astype(dtype)
            return bins
        max_nb = max((m.num_bin for m in self.bin_mappers), default=2)
        dtype = np.uint8 if max_nb <= 256 else np.uint16
        bins = np.empty((n, F), dtype=dtype)
        X = np.asarray(X)

        # blocks of rows, each transposed so that a column is contiguous
        # on the way in and on the way out (a column of a row-major matrix
        # is one value per cache line), and binned concurrently: numpy's
        # search runs outside the interpreter lock
        def _bin_rows(lo: int) -> None:
            hi = min(lo + _BIN_BLOCK_ROWS, n)
            XT = np.ascontiguousarray(X[lo:hi].T, dtype=np.float64)
            out = np.empty((F, hi - lo), dtype)
            for inner, raw in enumerate(self.real_feature_index):
                out[inner] = self.bin_mappers[inner].values_to_bins(XT[raw])
            bins[lo:hi] = out.T

        _map_columns(_bin_rows, range(0, n, _BIN_BLOCK_ROWS))
        return bins

    def _bin_all(self, X) -> None:
        with tracing.span("data/bin", "data", rows=self.num_data,
                          sparse=_issparse(X)):
            if _issparse(X):
                self._bin_all_sparse(X)
                return
            self.bins = self.bin_block(np.asarray(X))
            self._device_cache.clear()

    def _bin_all_sparse(self, X) -> None:
        """Column-wise binning from CSC stored entries (c_api.cpp:602-747
        CSR/CSC ingestion): implicit zeros land in each feature's default
        bin (== ValueToBin(0), bin.h GetDefaultBin) without materializing
        the dense matrix."""
        Xc = X.tocsc()
        n = Xc.shape[0]
        info = self.bundle

        def col_entries(inner):
            raw = self.real_feature_index[inner]
            j0, j1 = Xc.indptr[raw], Xc.indptr[raw + 1]
            rows = Xc.indices[j0:j1]
            b = self.bin_mappers[inner].values_to_bins(
                np.asarray(Xc.data[j0:j1], np.float64))
            return rows, b

        if info is not None:
            dtype = (np.uint8 if int(info.group_num_bins.max()) <= 256
                     else np.uint16)
            bins = np.zeros((n, info.num_groups), dtype)

            # a group's column depends on no other group's, and the scatter
            # runs outside the interpreter lock: groups spread over the
            # cores, each with one [n] scratch in the column's own type
            def _group_column(g: int) -> None:
                feats = info.groups[g]
                if len(feats) == 1:
                    inner = feats[0]
                    rows, b = col_entries(inner)
                    col = np.full(n, self.bin_mappers[inner].default_bin,
                                  dtype)
                    col[rows] = b.astype(dtype)
                    bins[:, g] = col
                    return
                col = np.zeros(n, dtype)         # 0 = all defaults
                for inner in feats:              # later features win
                    rows, b = col_entries(inner)
                    nz = b != int(info.feature_default[inner])
                    col[rows[nz]] = (b[nz].astype(np.int64) + int(
                        info.feature_shift[inner])).astype(dtype)
                bins[:, g] = col

            _map_columns(_group_column, range(info.num_groups))
        else:
            F = self.num_features
            max_nb = max((m.num_bin for m in self.bin_mappers), default=2)
            dtype = np.uint8 if max_nb <= 256 else np.uint16
            bins = np.empty((n, F), dtype)
            for inner in range(F):
                rows, b = col_entries(inner)
                col = np.full(n, self.bin_mappers[inner].default_bin, dtype)
                col[rows] = b.astype(dtype)
                bins[:, inner] = col
        self.bins = bins
        self._device_cache.clear()

    def create_valid(self, X: np.ndarray, metadata: Optional[Metadata] = None
                     ) -> "BinnedDataset":
        return BinnedDataset.construct(np.asarray(X), config=None,
                                       metadata=metadata, reference=self)

    # ------------------------------------------------------------------ #
    # Constructed-dataset merges (Dataset::addFeaturesFrom,
    # src/io/dataset.cpp:983; Dataset::addDataFrom used by the
    # distributed append path)
    # ------------------------------------------------------------------ #
    def add_features_from(self, other: "BinnedDataset") -> None:
        """Append `other`'s BINNED feature columns to this dataset.

        Both datasets stay constructed: mappers, bins, names, bundle
        layout and per-feature vectors are merged in place — the binned
        equivalent of column-stacking the raw matrices, without ever
        re-binning."""
        if self.bins is None or other.bins is None:
            log.fatal("add_features_from requires constructed datasets")
        if self.num_data != other.num_data:
            log.fatal("Cannot add features from other Dataset with "
                      "a different number of rows")
        F0 = len(self.bin_mappers)
        raw0 = self.num_total_features
        self.used_feature_map += [(-1 if v < 0 else v + F0)
                                  for v in other.used_feature_map]
        self.real_feature_index += [r + raw0
                                    for r in other.real_feature_index]
        self.bin_mappers = list(self.bin_mappers) + list(other.bin_mappers)
        self.num_total_features = raw0 + other.num_total_features
        self._set_offsets()
        names_o = (list(other.feature_names) if other.feature_names
                   else ["Column_%d" % (raw0 + i)
                         for i in range(other.num_total_features)])
        self.feature_names = list(self.feature_names) + names_o

        def _cat(a, b, F_a, F_b, neutral, dtype):
            if a is None and b is None:
                return None
            a = np.full(F_a, neutral, dtype) if a is None else np.asarray(a)
            b = np.full(F_b, neutral, dtype) if b is None else np.asarray(b)
            return np.concatenate([a, b])

        Fo = len(other.bin_mappers)
        self.monotone_constraints = _cat(
            self.monotone_constraints, other.monotone_constraints,
            F0, Fo, 0, np.int8)
        self.feature_penalty = _cat(
            self.feature_penalty, other.feature_penalty, F0, Fo, 1.0,
            np.float64)
        # merged bundle layout: either side without EFB contributes
        # singleton groups; merged feature ids are shifted by F0
        if self.bundle is not None or other.bundle is not None:
            from . import efb

            def _groups(ds, shift, count):
                # NB: self.bin_mappers is already merged here — group
                # counts must come from the PRE-merge feature counts
                if ds.bundle is not None:
                    return [[f + shift for f in grp]
                            for grp in ds.bundle.groups]
                return [[f + shift] for f in range(count)]

            nb = [m.num_bin for m in self.bin_mappers]
            db = [m.default_bin for m in self.bin_mappers]
            self.bundle = efb.BundleInfo(
                _groups(self, 0, F0) + _groups(other, F0, Fo), nb, db)
        dt = (np.uint16 if (self.bins.dtype == np.uint16
                            or other.bins.dtype == np.uint16) else np.uint8)
        self.bins = np.column_stack([self.bins.astype(dt, copy=False),
                                     other.bins.astype(dt, copy=False)])
        self._device_cache.clear()

    def add_data_from(self, other: "BinnedDataset") -> None:
        """Append `other`'s ROWS; both must share the same bin mappers
        (the reference checks alignment via Dataset::CheckAlign)."""
        if self.bins is None or other.bins is None:
            log.fatal("add_data_from requires constructed datasets")
        if len(self.bin_mappers) != len(other.bin_mappers) or any(
                a.num_bin != b.num_bin
                for a, b in zip(self.bin_mappers, other.bin_mappers)):
            log.fatal("Cannot add data from misaligned Dataset "
                      "(bin mappers differ)")
        if self.bins.shape[1] != other.bins.shape[1]:
            log.fatal("Cannot add data from Dataset with a different "
                      "bundled layout")
        self.bins = np.vstack([self.bins, other.bins])
        n0, n1 = self.num_data, other.num_data
        self.num_data = n0 + n1
        md, mo = self.metadata, other.metadata

        def _rows(a, b, fill=0.0):
            return concat_fill(a, b, n0, n1, fill)

        # query metadata must stay consistent (query_boundaries[-1] ==
        # num_data is a fatal Metadata invariant): appending unranked
        # rows to a ranking dataset has no defensible semantics
        if (md.query_boundaries is None) != (mo.query_boundaries is None):
            log.fatal("Cannot add data from Dataset: only one side has "
                      "query (group) information")
        md.num_data = self.num_data
        md.label = _rows(md.label, mo.label)
        if md.weights is not None or mo.weights is not None:
            # the unweighted side's rows carry the NEUTRAL weight 1.0 —
            # zero would silently erase them from training
            md.weights = _rows(md.weights, mo.weights, fill=1.0)
        if md.query_boundaries is not None and mo.query_boundaries is not None:
            md.query_boundaries = np.concatenate(
                [md.query_boundaries[:-1],
                 mo.query_boundaries + int(md.query_boundaries[-1])])
            # query_weights are derived from per-row weights — recompute
            # over the merged boundaries
            md._update_query_weights()
        if md.init_score is not None or mo.init_score is not None:
            k = 1
            if md.init_score is not None and n0:
                k = md.init_score.size // n0
            elif mo.init_score is not None and n1:
                k = mo.init_score.size // n1
            a = (np.zeros(n0 * k) if md.init_score is None
                 else np.asarray(md.init_score).reshape(k, n0))
            b = (np.zeros(n1 * k) if mo.init_score is None
                 else np.asarray(mo.init_score).reshape(k, n1))
            md.init_score = np.concatenate(
                [a.reshape(k, n0), b.reshape(k, n1)], axis=1).reshape(-1)
        self._device_cache.clear()

    def append_raw(self, X, label=None, weight=None) -> int:
        """Bin and append a block of RAW rows against the frozen mappers —
        the streaming-ingest edge (continuous-learning supervisor).

        Strict: any malformation raises :class:`IngestError` (lenient
        callers shed upstream via `validate_ingest_block(shed=True)`),
        ranking datasets refuse unranked rows, and sharded datasets
        refuse appends that would desync the global row partition.
        Returns the number of appended rows."""
        if self.bins is None:
            log.fatal("append_raw requires a constructed dataset")
        if self.metadata.query_boundaries is not None:
            raise IngestError("bad_shape", "cannot stream-append unranked "
                              "rows to a ranking dataset")
        if self.dist_row_ids is not None:
            raise IngestError("bad_shape", "cannot stream-append to a "
                              "distributed row shard")
        X, label, weight = validate_ingest_block(
            X, label, weight, num_features=self.num_total_features)
        n1 = int(X.shape[0])
        if n1 == 0:
            return 0
        new_bins = self.bin_block(X)
        self.bins = np.vstack([self.bins,
                               new_bins.astype(self.bins.dtype, copy=False)])
        n0 = self.num_data
        self.num_data = n0 + n1
        md = self.metadata
        md.num_data = self.num_data
        md.label = concat_fill(md.label, label, n0, n1, 0.0)
        if md.weights is not None or weight is not None:
            md.weights = concat_fill(md.weights, weight, n0, n1, 1.0)
        if md.init_score is not None:
            # appended rows start at a zero init score on every class plane
            k = md.init_score.size // n0 if n0 else 1
            a = np.asarray(md.init_score).reshape(k, n0)
            md.init_score = np.concatenate(
                [a, np.zeros((k, n1))], axis=1).reshape(-1)
        self._device_cache.clear()
        return n1

    # ------------------------------------------------------------------ #
    # Accessors
    # ------------------------------------------------------------------ #
    @property
    def num_features(self) -> int:
        return len(self.bin_mappers)

    @property
    def num_total_bin(self) -> int:
        return int(self.feature_offsets[-1]) if self.feature_offsets is not None else 0

    def feature_num_bins(self) -> np.ndarray:
        return np.array([m.num_bin for m in self.bin_mappers], dtype=np.int32)

    def inner_feature_index(self, raw_idx: int) -> int:
        return self.used_feature_map[raw_idx]

    def device_bins(self):
        """Device-resident bin matrix [n, F] int8/int16 (cached)."""
        if "bins" not in self._device_cache:
            import jax.numpy as jnp
            self._device_cache["bins"] = jnp.asarray(self.bins)
        return self._device_cache["bins"]

    # ------------------------------------------------------------------ #
    # Binary cache (reference: Dataset::SaveBinaryFile dataset.cpp:615-708)
    # ------------------------------------------------------------------ #
    def save_binary(self, filename: str) -> None:
        d = {
            "magic": np.array(_BINARY_MAGIC),
            "bins": self.bins,
            "feature_offsets": self.feature_offsets,
            "used_feature_map": np.array(self.used_feature_map, dtype=np.int32),
            "real_feature_index": np.array(self.real_feature_index, dtype=np.int32),
            "feature_names": np.array(self.feature_names),
            "num_total_features": np.array(self.num_total_features),
            "max_bin": np.array(self.max_bin),
            "mapper_states": np.array([_json.dumps(m.to_state()) for m in self.bin_mappers]),
        }
        if self.bundle is not None:
            d["bundle_state"] = np.array(self.bundle.to_state())
        if self.monotone_constraints is not None:
            d["monotone_constraints"] = self.monotone_constraints
        if self.feature_penalty is not None:
            d["feature_penalty"] = self.feature_penalty
        d.update(self.metadata.to_npz_dict())
        # v_open: binary datasets ride the same backend seam as text IO,
        # so save/load works against registered remote filesystems too
        with v_open(filename, "wb") as f:  # exact filename, no .npz append
            np.savez_compressed(f, **d)
        log.info("Saved binary dataset to %s", filename)

    @classmethod
    def load_binary(cls, filename: str) -> "BinnedDataset":
        with v_open(filename, "rb") as f:
            # eager dict(): NpzFile reads lazily, but the backing file
            # (possibly a remote backend handle) closes with the `with`
            d = dict(np.load(f, allow_pickle=False))
        if str(d["magic"]) != _BINARY_MAGIC:
            log.fatal("%s is not a lightgbm_tpu binary dataset file" % filename)
        ds = cls()
        ds.bins = d["bins"]
        ds.num_data = ds.bins.shape[0]
        ds.feature_offsets = d["feature_offsets"]
        ds.used_feature_map = d["used_feature_map"].tolist()
        ds.real_feature_index = d["real_feature_index"].tolist()
        ds.feature_names = [str(x) for x in d["feature_names"]]
        ds.num_total_features = int(d["num_total_features"])
        ds.max_bin = int(d["max_bin"])
        ds.bin_mappers = [BinMapper.from_state(_json.loads(str(s)))
                          for s in d["mapper_states"]]
        if "bundle_state" in d:
            from .efb import BundleInfo
            ds.bundle = BundleInfo.from_state(
                str(d["bundle_state"]),
                [m.num_bin for m in ds.bin_mappers],
                [m.default_bin for m in ds.bin_mappers])
        if "monotone_constraints" in d:
            ds.monotone_constraints = d["monotone_constraints"]
        if "feature_penalty" in d:
            ds.feature_penalty = d["feature_penalty"]
        ds.metadata = Metadata.from_npz_dict(d, ds.num_data)
        return ds

    def subset(self, indices: np.ndarray) -> "BinnedDataset":
        """Row-subset copy sharing mappers (dataset.h CopySubset)."""
        out = BinnedDataset()
        out.num_data = len(indices)
        out.num_total_features = self.num_total_features
        out.used_feature_map = list(self.used_feature_map)
        out.real_feature_index = list(self.real_feature_index)
        out.bin_mappers = self.bin_mappers
        out.bins = self.bins[indices]
        out.feature_offsets = self.feature_offsets
        out.feature_names = list(self.feature_names)
        out.monotone_constraints = self.monotone_constraints
        out.feature_penalty = self.feature_penalty
        out.max_bin = self.max_bin
        out.bundle = self.bundle
        out.metadata = self.metadata.subset(np.asarray(indices))
        return out
