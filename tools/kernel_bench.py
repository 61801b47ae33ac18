"""Microbenchmark for the partition-engine kernels on the real chip.

Times partition_segment (decision mode) and segment_histogram in
isolation on a Higgs-shaped arena (28 features, B=255), chaining many
calls per device sync (one dependent scalar fetch).

Usage: python tools/kernel_bench.py [rows_millions]
"""
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")
from lightgbm_tpu.ops import partition_pallas as pp  # noqa: E402


def sync(x):
    return float(jnp.sum(x[..., :1]))


def main():
    n = int(float(sys.argv[1]) * 1e6) if len(sys.argv) > 1 else 4_000_000
    F = 28
    B = 255
    rng = np.random.default_rng(0)
    bins = rng.integers(0, B, size=(F, n), dtype=np.uint8)
    grad = rng.standard_normal(n).astype(np.float32)
    hess = rng.random(n).astype(np.float32) + 0.1

    C, cap = pp.arena_geometry(n, F)
    print(f"n={n} F={F} C={C} cap={cap} SUB={pp.SUB} TILE={pp.TILE} "
          f"FLUSH_W={pp.FLUSH_W} CARRY_W={pp.CARRY_W}")
    arena0 = jnp.zeros((C, cap), pp.ARENA_DT)
    Fp = pp.feature_channels(F)
    chans = [jnp.asarray(bins, pp.ARENA_DT)]
    if Fp > F:
        chans.append(jnp.zeros((Fp - F, n), pp.ARENA_DT))
    chans += [c[None] for c in pp.split_f32(jnp.asarray(grad))]
    chans += [c[None] for c in pp.split_f32(jnp.asarray(hess))]
    chans += [c[None] for c in pp.split_rowid(jnp.arange(n, dtype=jnp.int32))]
    if C > Fp + pp.N_AUX:
        chans.append(jnp.zeros((C - Fp - pp.N_AUX, n), pp.ARENA_DT))
    arena = jax.lax.dynamic_update_slice(
        arena0, jnp.concatenate(chans, axis=0), (0, 0))
    sync(arena)

    pred_dummy = jnp.zeros((1, pp.TILE), jnp.float32)
    # a balanced decision mask on feature 0
    mask = (jnp.arange(256) < B // 2).astype(jnp.float32)
    decision = (jnp.int32(0), mask, jnp.int32(0))
    dstB = ((n + pp.TILE - 1) // pp.TILE) * pp.TILE + pp.TILE

    reps = 10

    @jax.jit
    def run_partition(arena):
        def body(i, ar):
            ar, cnts = pp.partition_segment(
                ar, pred_dummy, jnp.int32(0), jnp.int32(n),
                jnp.int32(0), jnp.int32(dstB), decision=decision)
            return ar
        return jax.lax.fori_loop(0, reps, body, arena)

    @jax.jit
    def run_hist(arena):
        def body(i, acc):
            h = pp.segment_histogram(arena, jnp.int32(0), jnp.int32(n),
                                     num_features=F, max_bin=B)
            return acc + jnp.sum(h)
        return jax.lax.fori_loop(0, reps, body, jnp.float32(0.0))

    # warm up (compile)
    t0 = time.time()
    a2 = run_partition(arena)
    sync(a2)
    print(f"partition compile+first: {time.time()-t0:.1f}s")
    t0 = time.time()
    a2 = run_partition(arena)
    sync(a2)
    dt = time.time() - t0
    print(f"partition_segment: {dt/reps*1000:.2f} ms/pass "
          f"({n/(dt/reps)/1e6:.0f} Mrows/s)")

    t0 = time.time()
    s = run_hist(arena)
    float(s)
    print(f"hist compile+first: {time.time()-t0:.1f}s")
    t0 = time.time()
    s = run_hist(arena)
    float(s)
    dt = time.time() - t0
    print(f"segment_histogram: {dt/reps*1000:.2f} ms/pass "
          f"({n/(dt/reps)/1e6:.0f} Mrows/s)")


if __name__ == "__main__":
    main()
