"""Stage-ablation profile of the partition kernel — measures cumulative
cost of each pipeline stage by compiling stripped variants (a checksum
into cnt_ref keeps Mosaic from DCE-ing live stages).

Usage: python tools/kernel_ablate.py [rows_millions [features]]
"""
import functools
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

sys.path.insert(0, ".")
from lightgbm_tpu.ops import partition_pallas as pp  # noqa: E402

SUB, TILE = pp.SUB, pp.TILE
ARENA_DT = pp.ARENA_DT

# cumulative stages of the tile body.  A stage's checksum reads one element
# of what it computed, so the compiler may drop the rest: `pbuild` may keep
# one of the K permutation one-hots; `matmul` reads one element of every
# product, which keeps all K builds and matmuls; `chunks` adds the A/B split
# and is everything but the appends; `full` is the shipped kernel itself
# (pp.partition_segment), appends, flushes and write-back included
STAGES = ("dma", "decide", "scan", "pbuild", "matmul", "chunks", "full")


def _kernel(sc_ref, feat_onehot_ref, mask_ref, arena_any, out_any, cnt_ref,
            in_buf, read_sems, *, C: int, tile: int, stage: str):
    """The shipped kernel's read pipeline and parallel region, cut after
    `stage`; nothing is written back."""
    s, cnt = sc_ref[0], sc_ref[1]
    xr = sc_ref[5]
    n_tiles = jax.lax.div(cnt + jnp.int32(tile - 1), jnp.int32(tile))
    K = tile // SUB

    def read_dma(j, slot):
        src = pl.multiple_of(s + j * tile, 128)
        return pltpu.make_async_copy(
            arena_any.at[:, pl.ds(src, tile)], in_buf.at[slot],
            read_sems.at[slot])

    @pl.when(n_tiles > 0)
    def _():
        read_dma(0, 0).start()
        read_dma(0, 0).wait()

    def after_read(block, valid):
        if stage == "dma":
            return jnp.sum(block[0:1, 0:1].astype(jnp.float32))
        on = pp._decide(block, feat_onehot_ref, mask_ref, xr) > 0.5
        predA = jnp.where(valid & on, jnp.float32(1.0), jnp.float32(0.0))
        predB = jnp.where(valid & ~on, jnp.float32(1.0), jnp.float32(0.0))
        if stage == "decide":
            return jnp.sum(predA)
        pred2 = jnp.concatenate(
            [predA.reshape(K, SUB), predB.reshape(K, SUB)], axis=0)
        pref2 = pp._prefix_scan_lanes(pred2)
        if stage == "scan":
            return pref2[0, 0]
        P_all = pp._sort_P(pref2, pred2, K)
        if stage == "pbuild":
            return jnp.sum(P_all[0, 0:1, 0:1].astype(jnp.float32))
        comps = [jax.lax.dot(block[:, k * SUB:(k + 1) * SUB], P_all[k],
                             preferred_element_type=jnp.float32)
                 for k in range(K)]
        if stage == "matmul":
            return sum(c[0, 0] for c in comps)
        cnt2 = pref2[:, SUB - 1].astype(jnp.int32)
        lane_s = jax.lax.broadcasted_iota(jnp.int32, (1, SUB), 1)
        chunksA = [jnp.where(lane_s < cnt2[k], comps[k], jnp.float32(0.0))
                   for k in range(K)]
        chunksB = [comps[k] - chunksA[k] for k in range(K)]
        return jnp.sum(sum(chunksA) - sum(chunksB))

    def loop(j, chk):
        slot = jax.lax.rem(j, jnp.int32(2))
        nslot = jax.lax.rem(j + jnp.int32(1), jnp.int32(2))

        @pl.when(j + 1 < n_tiles)
        def _():
            read_dma(j + 1, nslot).start()

        valid = jax.lax.broadcasted_iota(
            jnp.int32, (1, tile), 1) < (cnt - j * tile)
        chk = chk + after_read(in_buf[slot], valid)

        @pl.when(j + 1 < n_tiles)
        def _():
            read_dma(j + 1, nslot).wait()
        return chk

    chk = jax.lax.fori_loop(0, n_tiles, loop, jnp.float32(0.0))
    cnt_ref[0] = chk.astype(jnp.int32)
    cnt_ref[1] = jnp.int32(0)


@functools.partial(jax.jit, static_argnames=("stage", "n", "reps"))
def run_stage(arena, decision, *, stage, n, reps):
    C, cap = arena.shape
    feat, mask_vec, xr = decision
    dstB = ((n + TILE - 1) // TILE) * TILE + TILE
    if stage == "full":
        pred = jnp.zeros((1, TILE), jnp.float32)
        return jax.lax.fori_loop(
            0, reps,
            lambda i, ar: pp.partition_segment(ar, pred, 0, n, 0, dstB,
                                               decision=decision)[0],
            arena)
    feat_onehot = (jnp.arange(C, dtype=jnp.int32)[None, :]
                   == feat).astype(ARENA_DT)
    mv = jnp.asarray(mask_vec, jnp.float32).reshape(1, -1)
    goleft = jnp.pad(mv, ((0, 0), (0, 256 - mv.shape[1]))).astype(ARENA_DT)
    sc = jnp.asarray([0, n, 0, dstB, 1, 0, 0], jnp.int32)
    kernel = functools.partial(_kernel, C=C, tile=TILE, stage=stage)

    def body(i, ar):
        ar, cnts = pl.pallas_call(
            kernel,
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pltpu.SMEM)),
            out_shape=(jax.ShapeDtypeStruct((C, cap), ARENA_DT),
                       jax.ShapeDtypeStruct((2,), jnp.int32)),
            scratch_shapes=[
                pltpu.VMEM((2, C, TILE), ARENA_DT),
                pltpu.SemaphoreType.DMA((2,)),
            ],
            input_output_aliases={3: 0},
            compiler_params=pltpu.CompilerParams(has_side_effects=True),
        )(sc, feat_onehot, goleft, ar)
        return ar
    return jax.lax.fori_loop(0, reps, body, arena)


def main():
    n = int(float(sys.argv[1]) * 1e6) if len(sys.argv) > 1 else 4_000_000
    F = int(sys.argv[2]) if len(sys.argv) > 2 else 28
    B = 255
    rng = np.random.default_rng(0)
    C, cap = pp.arena_geometry(n, F)
    print(f"n={n} C={C} SUB={SUB} TILE={TILE} FLUSH_W={pp.FLUSH_W}")
    arena = jnp.asarray(
        rng.integers(0, B, size=(C, cap)).astype(np.float32), ARENA_DT)
    float(jnp.sum(arena[:, :1]))
    mask = (jnp.arange(256) < B // 2).astype(jnp.float32)
    decision = (jnp.int32(0), mask, jnp.int32(0))
    reps = 10
    prev = 0.0
    for stage in STAGES:
        out = run_stage(arena, decision, stage=stage, n=n, reps=reps)
        float(jnp.sum(out[:, :1]))
        t0 = time.time()
        out = run_stage(arena, decision, stage=stage, n=n, reps=reps)
        float(jnp.sum(out[:, :1]))
        dt = (time.time() - t0) / reps * 1000
        print(f"{stage:8s}: {dt:7.2f} ms/pass (+{dt-prev:6.2f})")
        prev = dt


if __name__ == "__main__":
    main()
