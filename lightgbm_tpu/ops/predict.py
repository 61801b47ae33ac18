"""Batched device prediction over raw features — signature-matmul design.

The reference predicts tree-by-tree, row-by-row on the host
(gbdt_prediction.cpp + Tree::Predict, tree.h:429-512).  A literal
vectorized node WALK on TPU is gather-bound (per-(tree,row) table reads
lower to scalar gathers).  Instead, prediction is restructured to ride
the MXU:

1. decisions for ALL nodes of ALL trees are computed densely:
   D[row, t*n] = +-1 from one contiguous column-take of X + elementwise
   missing/categorical handling;
2. each leaf's root-to-leaf path is a signature row A[t, leaf, node] in
   {+1 (expects left), -1 (expects right), 0 (off path)}; a row reaches
   the leaf iff  sum_n A[l,n] * D[n] == path_len[l] — ONE batched bf16
   matmul per chunk (inputs are +-1/0 so bf16 is exact, sums <= depth);
3. leaf values dot the 0/1 match indicator (f32, exact).

500 trees x 1M rows is then a few TFLOP of bf16 matmul instead of 1e9
serial gathers.  Shapes are quantized (trees padded to a power of two,
rows chunked) so repeated predicts reuse the compiled executable.
Prediction early stop stays on the host path (inherently row-dependent
pruning, predict_raw in models/gbdt.py).
"""
from __future__ import annotations

from functools import partial
from typing import List

import numpy as np
import jax
import jax.numpy as jnp

MISSING_NONE = 0
MISSING_ZERO = 1
MISSING_NAN = 2
K_ZERO_THRESHOLD = 1e-35
_MAX_CAT_W = 4096
_MAX_SIG_ELEMS = 1 << 30   # cap on the [T, L, N] signature tensor

# device-path threshold: below this many (tree x row) pairs the host walk
# is cheaper than a compile + dispatch
MIN_DEVICE_WORK = 1 << 22
# bound D ([rows, T*N]) to ~2^27 elements per chunk
_CHUNK_BUDGET = 1 << 27


def _next_pow2(x: int) -> int:
    p = 1
    while p < x:
        p *= 2
    return p


def bucket_rows(n: int, max_bucket: int = 1 << 20) -> int:
    """Row-count bucket for executable reuse: the next power of two,
    capped so giant requests chunk through predict_sum instead of
    compiling a bespoke one-off executable."""
    return min(_next_pow2(max(n, 1)), _next_pow2(max_bucket))


def pow2_buckets(max_batch: int) -> List[int]:
    """All power-of-two bucket sizes up to (and including) max_batch —
    the default warmup set for serving."""
    out, b = [], 1
    top = _next_pow2(max(max_batch, 1))
    while b <= top:
        out.append(b)
        b *= 2
    return out


def ensemble_layout(trees: List, num_classes: int) -> dict:
    """The padded device-array shapes DeviceEnsemble will build for
    these trees, computed WITHOUT touching the device.  Trees are padded
    to k * pow2(iterations) — keeps the per-class reshape exact and
    quantizes shapes for executable reuse.  ``ok`` False means the
    ensemble cannot run on device (giant signature tensor / category
    ids) and the host walk keeps prediction duty.

    The serving residency manager (serving/fleet.py) sizes ensembles
    from this layout BEFORE building them, so eviction happens ahead of
    allocation instead of after an OOM."""
    k = max(num_classes, 1)
    T = k * _next_pow2(max(-(-len(trees) // k), 1))
    N = max(max((t.num_leaves - 1 for t in trees), default=1), 1)
    L = _next_pow2(N + 1)
    any_cat = any(t.num_cat > 0 for t in trees)
    # O(trees * leaves^2) signature tensor must fit; the categorical
    # bitset tensor [T*N, W] has its own budget
    ok = T * L * N <= _MAX_SIG_ELEMS
    W = 0
    if ok and any_cat:
        if T * N * _MAX_CAT_W > _MAX_SIG_ELEMS:
            ok = False
        else:
            mx = 31
            for t in trees:
                if t.num_cat > 0:
                    bits = np.asarray(t.cat_threshold, np.uint32)
                    nz = np.flatnonzero(bits)
                    if len(nz):
                        mx = max(mx, 32 * int(nz[-1]) + 31)
            W = _next_pow2(mx + 1)
            if W > _MAX_CAT_W:
                ok = False          # enormous category ids: host path
    return {"k": k, "T": T, "N": N, "L": L, "W": W,
            "any_cat": any_cat, "ok": ok}


def estimate_device_bytes(trees: List, num_classes: int,
                          x64: bool = None) -> int:
    """HBM bytes the DeviceEnsemble for `trees` will hold, from the
    layout alone — exact (matches device_bytes() of the built ensemble),
    so byte-budget reservations made before the build never drift from
    the accounting after it.  None when the ensemble is host-only."""
    lay = ensemble_layout(trees, num_classes)
    if not lay["ok"]:
        return None
    if x64 is None:
        x64 = bool(jax.config.jax_enable_x64)
    T, N, L, W = lay["T"], lay["N"], lay["L"], lay["W"]
    fb = 8 if x64 else 4
    total = T * N * 4                       # sf_flat  int32
    total += T * N * fb                     # thr_flat f64/f32
    if not x64:
        total += T * N * 4                  # thr_lo   f32 (double-single)
    total += T * N * 1                      # dl_flat  bool
    total += T * N * 4                      # mt_flat  int32
    if lay["any_cat"]:
        total += T * N * 1                  # ic_flat  bool
        total += T * N * max(W, 1) * 1      # cat bitset bool
    total += T * L * N * 2                  # sig      bf16
    total += T * L * 4                      # path_len f32
    total += T * L * fb                     # lv       f64/f32
    return int(total)


class DeviceEnsemble:
    """Stacked ensemble for device prediction; built once per model state
    (callers cache on len(models)).

    `device`: commit the ensemble's arrays to that jax device
    (``jax.device_put``).  Committed constants force every jit dispatch
    onto that device (uncommitted row inputs follow), which is how the
    serving replica sets (serving/replicas.py) pin one copy per fault
    domain.  None keeps the historical uncommitted ``jnp.asarray``
    placement — the default-device path, byte-identical to pre-replica
    behavior."""

    def __init__(self, trees: List, num_classes: int, device=None):
        lay = ensemble_layout(trees, num_classes)
        self.k = lay["k"]
        self.num_trees = len(trees)
        self.ok = lay["ok"]
        self.device = device
        T, N, L, W = lay["T"], lay["N"], lay["L"], lay["W"]
        self.T, self.N, self.L, self.W = T, N, L, W
        if not self.ok:
            return

        sf = np.zeros((T, N), np.int64)
        thr = np.zeros((T, N), np.float64)
        dl = np.zeros((T, N), bool)
        mt = np.zeros((T, N), np.int8)
        ic = np.zeros((T, N), bool)
        sig = np.zeros((T, L, N), np.int8)
        path_len = np.full((T, L), -1, np.int32)  # -1: no such leaf
        lv = np.zeros((T, L), np.float64)

        any_cat = lay["any_cat"]
        cat = np.zeros((T * N, max(W, 1)), bool) if any_cat else None

        for ti, t in enumerate(trees):
            n_nodes = t.num_leaves - 1
            lv[ti, :max(t.num_leaves, 1)] = t.leaf_value[:max(t.num_leaves, 1)]
            if n_nodes <= 0:
                path_len[ti, 0] = 0      # constant tree: leaf 0, empty path
                continue
            sf[ti, :n_nodes] = t.split_feature[:n_nodes]
            thr[ti, :n_nodes] = t.threshold[:n_nodes]
            d = np.asarray(t.decision_type[:n_nodes], np.int64)
            ic[ti, :n_nodes] = (d & 1) > 0         # K_CATEGORICAL_MASK
            dl[ti, :n_nodes] = (d & 2) > 0         # K_DEFAULT_LEFT_MASK
            mt[ti, :n_nodes] = (d >> 2) & 3
            # root-to-leaf signatures (iterative DFS)
            stack = [(0, [], [])]
            while stack:
                node, nodes, dirs = stack.pop()
                if node < 0:
                    leaf = ~node
                    sig[ti, leaf, nodes] = dirs
                    path_len[ti, leaf] = len(nodes)
                    continue
                stack.append((int(t.left_child[node]),
                              nodes + [node], dirs + [1]))
                stack.append((int(t.right_child[node]),
                              nodes + [node], dirs + [-1]))
            if t.num_cat > 0:
                for nd in np.flatnonzero(ic[ti, :n_nodes]):
                    ci = int(t.threshold[nd])
                    lo = t.cat_boundaries[ci]
                    hi = t.cat_boundaries[ci + 1]
                    bits = np.asarray(t.cat_threshold[lo:hi], np.uint32)
                    vals = np.arange(min(len(bits) * 32, W))
                    member = (bits[vals // 32] >> (vals % 32)) & 1
                    cat[ti * N + nd, :len(vals)] = member.astype(bool)

        self.x64 = bool(jax.config.jax_enable_x64)
        fdt = jnp.float64 if self.x64 else jnp.float32

        def _dev(a, dtype=None):
            arr = jnp.asarray(a) if dtype is None else jnp.asarray(a, dtype)
            return arr if device is None else jax.device_put(arr, device)

        self.sf_flat = _dev(sf.reshape(-1).astype(np.int32))
        self.thr_flat = _dev(thr.reshape(-1), fdt)
        if self.x64:
            self.thr_lo = None
        else:
            # double-single threshold split: comparisons against the f64
            # thresholds stay ~2^-48-exact in f32 (the host walk compares
            # in f64; a plain f32 downcast would flip boundary rows)
            t_hi = thr.reshape(-1).astype(np.float32)
            self.thr_lo = _dev(
                (thr.reshape(-1) - t_hi.astype(np.float64))
                .astype(np.float32))
        self.dl_flat = _dev(dl.reshape(-1))
        self.mt_flat = _dev(mt.reshape(-1).astype(np.int32))
        self.ic_flat = _dev(ic.reshape(-1)) if any_cat else None
        self.cat = _dev(cat) if any_cat else None
        self.sig = _dev(sig, jnp.bfloat16)                 # +-1/0 exact
        self.path_len = _dev(path_len.astype(np.float32))
        self.lv = _dev(lv, fdt)

    def predict_sum(self, X: np.ndarray, num_iteration: int) -> np.ndarray:
        """[k, n] summed raw scores over the first num_iteration*k trees."""
        n = X.shape[0]
        k = self.k
        use_T = num_iteration * k
        tmask = (np.arange(self.T) < use_T)
        lv = self.lv * jnp.asarray(tmask[:, None], self.lv.dtype)
        chunk = max(256, _CHUNK_BUDGET // max(self.T * self.N, 1))
        X64 = np.asarray(X, np.float64)
        if self.x64:
            Xd = jnp.asarray(X64)
            Xlo = None
        else:
            hi = X64.astype(np.float32)
            Xd = jnp.asarray(hi)
            Xlo = jnp.asarray((X64 - hi.astype(np.float64))
                              .astype(np.float32))
        parts = []
        for a in range(0, n, chunk):
            b = min(n, a + chunk)
            xc = Xd[a:b]
            xl = None if Xlo is None else Xlo[a:b]
            if b - a < chunk and n > chunk:
                xc = jnp.pad(xc, ((0, chunk - (b - a)), (0, 0)))
                if xl is not None:
                    xl = jnp.pad(xl, ((0, chunk - (b - a)), (0, 0)))
            parts.append(_chunk_scores(
                xc, xl, self.sf_flat, self.thr_flat, self.thr_lo,
                self.dl_flat, self.mt_flat, self.ic_flat,
                self.cat, self.sig, self.path_len, lv,
                k=k, T=self.T, N=self.N))
        # ONE host transfer at the end — a per-chunk np.asarray would pay
        # a blocking device sync per chunk (remote-attached TPUs)
        out = np.array(jnp.concatenate(parts, axis=1), np.float64)
        return out[:, :n]

    # -- serving hooks ----------------------------------------------- #
    def device_bytes(self) -> int:
        """HBM bytes held by this ensemble's device arrays (0 when the
        ensemble is host-only) — the residency manager's accounting
        unit; equals estimate_device_bytes() for the same trees."""
        if not self.ok:
            return 0
        arrs = (self.sf_flat, self.thr_flat, self.thr_lo, self.dl_flat,
                self.mt_flat, self.ic_flat, self.cat, self.sig,
                self.path_len, self.lv)
        return int(sum(a.nbytes for a in arrs if a is not None))

    def shape_signature(self, num_features: int) -> tuple:
        """Executable identity for the fleet compile cache: two
        ensembles with equal signatures hit the SAME `_chunk_scores`
        executables per row bucket — the jit statics (k, T, N) and every
        traced array shape/dtype are functions of these values, so equal
        signatures cannot false-share and unequal ones cannot collide."""
        return (self.k, self.T, self.N, self.L, self.W,
                int(num_features), self.x64)

    def predict_bucketed(self, X: np.ndarray, num_iteration: int,
                         max_bucket: int = 1 << 20) -> np.ndarray:
        """predict_sum with rows padded to the power-of-two bucket, so
        every request size between buckets reuses ONE compiled
        executable (the serving hot path; per-row results are unchanged
        by padding — reductions are row-independent).  Returns [k, n]."""
        n = X.shape[0]
        B = bucket_rows(n, max_bucket)
        if B > n:
            Xp = np.zeros((B, X.shape[1]), X.dtype)
            Xp[:n] = X
        else:
            Xp = X
        return self.predict_sum(Xp, num_iteration)[:, :n]

    def warmup_buckets(self, num_features: int, buckets,
                       num_iteration: int) -> List[int]:
        """Pre-compile the per-bucket executables a server will hit, so
        the first real request never waits on XLA.  Returns the bucket
        sizes actually compiled."""
        done = []
        for b in sorted(set(int(x) for x in buckets)):
            if b <= 0:
                continue
            self.predict_sum(np.zeros((b, num_features), np.float64),
                             num_iteration)
            done.append(b)
        return done


@partial(jax.jit, static_argnames=("k", "T", "N"))
def _chunk_scores(X, X_lo, sf_flat, thr_flat, thr_lo, dl_flat, mt_flat,
                  ic_flat, cat, sig, path_len, lv, *, k: int, T: int, N: int):
    """[k, rows] summed scores for one row chunk."""
    rows = X.shape[0]
    # dense decisions for every node: contiguous column take, elementwise
    # missing handling (NumericalDecision, tree.h:429-465)
    fv = jnp.take(X, sf_flat, axis=1)                    # [rows, T*N]
    nan_mask = jnp.isnan(fv)
    zero_nan = nan_mask & (mt_flat != MISSING_NAN)[None, :]
    fv_num = jnp.where(zero_nan, 0.0, fv)
    is_zero = jnp.abs(fv_num) <= K_ZERO_THRESHOLD
    missing = ((mt_flat == MISSING_ZERO)[None, :] & is_zero) | \
              ((mt_flat == MISSING_NAN)[None, :] & jnp.isnan(fv_num))
    if X_lo is None:
        le = fv_num <= thr_flat[None, :]
    else:
        # double-single comparison: lexicographic on (hi, lo) pairs keeps
        # the f64 threshold semantics without x64
        fv_lo = jnp.where(zero_nan, 0.0, jnp.take(X_lo, sf_flat, axis=1))
        th = thr_flat[None, :]
        le = (fv_num < th) | ((fv_num == th) & (fv_lo <= thr_lo[None, :]))
    go_left = jnp.where(missing, dl_flat[None, :], le)
    if ic_flat is not None:
        # categorical membership: per-(row, cat-node) bitset lookup
        # (CategoricalDecision, tree.h:249-267).  int truncation like
        # static_cast<int> (so -0.5 tests category 0); ids beyond the
        # bitset width are non-members, not clipped
        nan_fv = jnp.isnan(fv)
        iv_raw = jnp.where(nan_fv, 0.0, fv).astype(jnp.int32)
        in_range = (~nan_fv) & (iv_raw >= 0) & (iv_raw < cat.shape[1])
        iv = jnp.clip(iv_raw, 0, cat.shape[1] - 1)
        member = _cat_member(cat, iv) & in_range
        go_left = jnp.where(ic_flat[None, :], member, go_left)
    D = jnp.where(go_left, 1.0, -1.0).astype(jnp.bfloat16)
    D3 = D.reshape(rows, T, N)
    # per-tree signature match: s[t, l, r] = sum_n sig[t,l,n] * D[r,t,n]
    s = jnp.einsum("tln,rtn->tlr", sig, D3,
                   preferred_element_type=jnp.float32)
    ind = (s == path_len[:, :, None]).astype(lv.dtype)   # exactly one per t
    vals = jnp.einsum("tlr,tl->tr", ind, lv,
                      precision=jax.lax.Precision.HIGHEST)
    return jnp.sum(vals.reshape(T // k, k, rows), axis=0)


def _cat_member(cat, iv):
    """cat: [T*N, W] bool; iv: [rows, T*N] -> [rows, T*N] membership."""
    # gather per (node, value): transpose so the node axis aligns
    return jnp.take_along_axis(cat[None, :, :],
                               iv.astype(jnp.int32)[:, :, None],
                               axis=2)[:, :, 0]
