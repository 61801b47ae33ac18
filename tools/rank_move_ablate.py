"""Step 0 of lambdarank's move between rows and query slots: each direction
in three forms, alone, on one chip.

Usage: python tools/rank_move_ablate.py [shape ...] [--forms f,g] [--rehearse]

Forms (`ops/ranking.py`; per iteration the gradient makes one gather of
scores into [Q, S] slots and one scatter-add of lambdas and hessians back
to rows):
  element   the form the windows replaced: `ext[idx]`, and two
            `.at[].add` of Q x S single elements
  rows      whole aligned 128-lane rows a query (`_to_slots`, `_add_rows`:
            a row gather / scatter-add and a shift by the query's lane
            offset, seven selects between static slices); `rows_only` is
            its row gather without the shift
  window    one unaligned window of S elements a query (`lax.gather` /
            `lax.scatter_add` with an S-element slice at the query's start)

Shapes, all at MSLR's 2 268 000 rows: `mslr` (18 900 queries of 120, S =
128), `short` (378 000 of 6, S = 8), `s16` (189 000 of 12), `s32` (94 500
of 24), `long` (11 340 of 200, S = 256); all five by default.  `--forms`
keeps the element form and the forms named.  It prints ms a call (the mean
of 20 back-to-back calls, 3 where one call takes over 50 ms; best of
three) and ns a window, and checks every form's result equal, bit for bit,
to the element form's.  A time comes from a TPU only: elsewhere it exits
1, unless `--rehearse` runs every form at a tiny size to check the script.
"""
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from lightgbm_tpu.ops import ranking  # noqa: E402

LANES = ranking.LANES
SHAPES = {"mslr": 120, "short": 6, "s16": 12, "s32": 24, "long": 200}


def layout(n, size):
    qb = np.arange(0, n + 1, size)
    qbk = ranking.QueryBuckets(qb, n)
    (idx, _), = qbk.buckets
    (rows, shift), = qbk.windows
    real = idx < n
    return dict(idx=jnp.asarray(idx), rows=jnp.asarray(rows),
                shift=jnp.asarray(shift), real=jnp.asarray(real),
                start=jnp.asarray(qb[:-1].astype(np.int32)),
                R=qbk.num_rows, n=n)


def gathers(L):
    n, R, S = L["n"], L["R"], L["real"].shape[1]

    def element(s):
        ext = jnp.concatenate([s, jnp.asarray([-jnp.inf], s.dtype)])
        return ext[L["idx"]]

    def rows(s):
        v = jnp.pad(s, (0, R * LANES - n)).reshape(R, LANES)
        return ranking._to_slots(v, L["rows"], L["shift"], L["real"])

    def rows_only(s):
        v = jnp.pad(s, (0, R * LANES - n)).reshape(R, LANES)
        return v[L["rows"]]

    def window(s):
        ext = jnp.pad(s, (0, S))
        dn = jax.lax.GatherDimensionNumbers(
            offset_dims=(1,), collapsed_slice_dims=(), start_index_map=(0,))
        w = jax.lax.gather(ext, L["start"][:, None], dn, slice_sizes=(S,))
        return jnp.where(L["real"], w, -jnp.inf)
    return dict(element=element, rows=rows, rows_only=rows_only,
                window=window)


def scatters(L):
    n, R, S = L["n"], L["R"], L["real"].shape[1]
    real = L["real"]

    def element(lam, hes):
        flat = jnp.where(real, L["idx"], n).reshape(-1)
        g = jnp.zeros(n + 1, lam.dtype).at[flat].add(lam.reshape(-1),
                                                     mode="drop")
        h = jnp.zeros(n + 1, lam.dtype).at[flat].add(hes.reshape(-1),
                                                     mode="drop")
        return jnp.stack([g[:n], h[:n]])

    def rows(lam, hes):
        moved = jnp.zeros((2, R, LANES), lam.dtype)
        moved = ranking._add_rows(moved, lam, hes, L["rows"], L["shift"],
                                  real)
        return moved.reshape(2, -1)[:, :n]

    def window(lam, hes):
        upd = jnp.where(real, jnp.stack([lam, hes]), 0.0)
        dn = jax.lax.ScatterDimensionNumbers(
            update_window_dims=(0, 2), inserted_window_dims=(),
            scatter_dims_to_operand_dims=(1,))
        out = jax.lax.scatter_add(jnp.zeros((2, n + S), lam.dtype),
                                  L["start"][:, None], upd, dn)
        return out[:, :n]
    return dict(element=element, rows=rows, window=window)


def timed(fn, *args):
    f = jax.jit(fn)
    out = f(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    jax.block_until_ready(f(*args))
    reps = 20 if time.perf_counter() - t0 < 0.05 else 3
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = f(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / reps)
    return best, np.asarray(out)


def main():
    args = sys.argv[1:]
    rehearse = "--rehearse" in args
    forms = None
    if "--forms" in args:
        forms = args[args.index("--forms") + 1].split(",")
    shapes = [a for a in args if a in SHAPES] or list(SHAPES)
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, flush=True)
    if dev.platform != "tpu" and not rehearse:
        print("no TPU: a time from another device is no reading")
        return 1
    n = 4 * 1000 + 8 if rehearse else 2_268_000
    rng = np.random.RandomState(0)
    for name in shapes:
        size = SHAPES[name]
        L = layout(n - n % size, size)
        Q, S = L["real"].shape
        score = jnp.asarray(rng.randn(L["n"]), jnp.float32)
        lam, hes = (jnp.where(L["real"], jnp.asarray(rng.randn(Q, S),
                                                     jnp.float32), 0.0)
                    for _ in range(2))
        for direction, fns, xs in (("gather", gathers(L), (score,)),
                                   ("scatter", scatters(L), (lam, hes))):
            want = None
            for form, fn in fns.items():
                if forms and form not in forms and form != "element":
                    continue
                t, out = timed(fn, *xs)
                if want is None:
                    want = out
                same = ("-" if out.shape != want.shape else
                        bool(np.array_equal(out.view(np.uint32),
                                            want.view(np.uint32))))
                print("[step0] %s S=%d Q=%d %s %s: %.4f ms, %.2f ns a "
                      "window, bit-equal %s" % (name, S, Q, direction, form,
                                                t * 1e3, t * 1e9 / Q, same),
                      flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
