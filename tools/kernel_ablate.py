"""Stage-ablation profile of the partition kernel — measures cumulative
cost of each pipeline stage by compiling stripped variants (a checksum
into cnt_ref keeps Mosaic from DCE-ing live stages).

Usage: python tools/kernel_ablate.py [rows_millions [features]]
       python tools/kernel_ablate.py hist <rows> <features> <max_bin> <q|f32>

On a TPU only: the partition stages die in Mosaic lowering off the chip and
the `hist` mode refuses to start (a time from interpret mode is no reading).
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


# ------------------------------------------------------------------ #
# the histogram kernels' tile body (pp._radix_accumulate), cumulative
# ------------------------------------------------------------------ #
# `hist-dma` is the read pipeline alone; `hist-radix` adds the hi/lo digit
# arithmetic of every 8-feature block (pp._radix_digits); `hist-rhs` the
# one-hot right operand (pp._radix_rhs); `hist-lhs` the masked payload
# planes and the left operand (pp._radix_planes, _radix_hits, _radix_lhs);
# `hist-dot` the MXU products, each added to one accumulator block;
# `hist-full` is the shipped pp.segment_histogram (its own accumulator,
# copy-out and epilogue).  The operand builders are the kernel's own
# functions and cannot drift from it.  The read pipeline and the loop nest
# around them are a copy of `_seg_hist_kernel`'s and `_radix_accumulate`'s
# (the stages cut them where the kernel has no seam) and have to be kept
# up with them: `hist-dot` against `hist-full` is the check (they agreed to
# 1 % at three shapes: PERF.md, PR 31).  What a stage below `hist-dot`
# makes is summed into a VMEM accumulator, one add per (packed) vreg, so
# that none of it can be dropped (a store into a scratch that is only
# overwritten was dropped: PERF.md, PR 31): `hist-rhs` and `hist-lhs` read
# that much too high (8 adds per block and 128-lane column for the right
# operand, 6 per group for a 96-row left one), `hist-dot` not.
HIST_STAGES = ("hist-dma", "hist-radix", "hist-rhs", "hist-lhs", "hist-dot",
               "hist-full")


def _hist_kernel(sc_ref, arena_any, chk_ref, in_buf, pay_buf, read_sems,
                 pay_sems, sink32, sinki, sink_part, *,
                 stage: str, nb: int, k: int, m: int, lo_n: int, hi_n: int,
                 payload: int, pay_row: int, tile: int):
    """`_seg_hist_kernel`'s grid variant (feature rows of this step's nb
    radix blocks + the 8-row payload group per tile, double-buffered), its
    tile body cut after `stage`."""
    s, cnt = sc_ref[0], sc_ref[1]
    n_tiles = jax.lax.div(cnt + jnp.int32(tile - 1), jnp.int32(tile))
    f_blk = k * m
    rows = nb * f_blk
    row0 = pl.multiple_of(pl.program_id(0) * rows, 8)
    level = HIST_STAGES.index(stage)

    def read_dmas(j, slot):
        src = pl.multiple_of(s + j * tile, 128)
        return (pltpu.make_async_copy(
                    arena_any.at[pl.ds(row0, rows), pl.ds(src, tile)],
                    in_buf.at[slot], read_sems.at[slot]),
                pltpu.make_async_copy(
                    arena_any.at[pl.ds(pay_row, 8), pl.ds(src, tile)],
                    pay_buf.at[slot], pay_sems.at[slot]))

    for ref in (sink32, sinki, sink_part):
        ref[:] = jnp.zeros_like(ref)

    def consume(x):
        """Sum a bf16 [.., rows, tile] operand's packed vregs into sinki."""
        x = x.reshape(-1, tile)
        x = pltpu.bitcast(x[:x.shape[0] // 16 * 16], jnp.int32)
        sinki[:] = sinki[:] + sum(x[r:r + 8] for r in range(0, x.shape[0], 8))

    @pl.when(n_tiles > 0)
    def _():
        for d in read_dmas(0, 0):
            d.start()
        for d in read_dmas(0, 0):
            d.wait()

    def body(block, pay, valid):
        """pp._radix_accumulate's loops around its own pieces."""
        sink32[0:1, :] = sink32[0:1, :] + (block[0:1, :].astype(jnp.float32)
                                           + pay[0:1, :].astype(jnp.float32))
        if level >= 3:
            gh = pp._radix_planes(
                block, valid, [pay[i:i + 1, :] for i in range(payload - 1)],
                n_blocks=nb, payload=payload)
        chunks = pp._comp_chunks(hi_n, m, payload)
        for b in range(nb if level >= 1 else 0):
            hi, lo = pp._radix_digits(block[b * f_blk:(b + 1) * f_blk, :],
                                      lo_n)
            if level == 1:
                sink32[:] = sink32[:] + hi + lo
                continue
            rhs = pp._radix_rhs(lo, k=k, lo_n=lo_n)
            if level <= 3:
                consume(rhs)
            if level == 2:
                continue
            for kk, hits in enumerate(pp._radix_hits(hi, k=k, m=m,
                                                     hi_n=hi_n)):
                c0 = 0
                for csz in chunks:
                    lhs = pp._radix_lhs(hits, gh, c0, csz)
                    M = csz * hi_n * m
                    if level == 3:
                        consume(lhs)
                    else:
                        sink_part[0:M, :] = sink_part[0:M, :] + (
                            jax.lax.dot_general(
                                lhs, rhs[kk],
                                dimension_numbers=(((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32))
                    c0 += csz

    def loop(j, _):
        slot = jax.lax.rem(j, jnp.int32(2))
        nslot = jax.lax.rem(j + jnp.int32(1), jnp.int32(2))

        @pl.when(j + 1 < n_tiles)
        def _():
            for d in read_dmas(j + 1, nslot):
                d.start()

        valid = (jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
                 < (cnt - j * tile)).astype(jnp.float32)
        body(in_buf[slot], pay_buf[slot], valid)

        @pl.when(j + 1 < n_tiles)
        def _():
            for d in read_dmas(j + 1, nslot):
                d.wait()
        return 0

    jax.lax.fori_loop(0, n_tiles, loop, 0)
    chk_ref[...] = (sink32[:, 0:128] + sink_part[0:8, :]
                    + sinki[:, 0:128].astype(jnp.float32))[None]


@functools.partial(jax.jit, static_argnames=("stage", "n", "F", "B", "quant"))
def run_hist_stage(arena, *, stage, n, F, B, quant):
    if stage == "hist-full":
        return pp.segment_histogram(arena, 0, n, num_features=F, max_bin=B,
                                    quantized=quant)
    payload = 3 if quant else 7
    lo_n, hi_n, m, f_blk, k, n_blocks, nb = pp._hist_plan(F, B, payload)
    steps = n_blocks // nb
    kernel = functools.partial(
        _hist_kernel, stage=stage, nb=nb, k=k, m=m, lo_n=lo_n, hi_n=hi_n,
        payload=payload, pay_row=n_blocks * f_blk, tile=TILE)
    return pl.pallas_call(
        kernel,
        grid=(steps,),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 8, 128), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((steps, 8, 128), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((2, nb * f_blk, TILE), ARENA_DT),
            pltpu.VMEM((2, 8, TILE), ARENA_DT),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((8, TILE), jnp.float32),
            pltpu.VMEM((8, TILE), jnp.int32),
            pltpu.VMEM((128, lo_n * m), jnp.float32),
        ],
    )(jnp.asarray([0, n], jnp.int32), arena)


def hist_main(argv):
    """hist <rows> <features> <max_bin> <q|f32> [reps]"""
    if jax.default_backend() != "tpu":
        raise SystemExit(
            "kernel_ablate hist: backend is %r, not tpu; the stages are "
            "device times and are not taken in interpret mode (the pieces "
            "are tested there by tests/test_radix_operand.py)"
            % jax.default_backend())
    n, F, B = int(float(argv[0])), int(argv[1]), int(argv[2])
    quant = argv[3] == "q"
    reps = int(argv[4]) if len(argv) > 4 else 5
    payload = 3 if quant else 7
    lo_n, hi_n, m, f_blk, k, n_blocks, nb = pp._hist_plan(F, B, payload)
    C = pp.arena_channels(F)
    n_al = -(-n // TILE) * TILE
    tiles = n_al // TILE
    groups = tiles * n_blocks * k
    print(f"hist device={jax.devices()[0].device_kind!r} "
          f"n={n} F={F} B={B} {'q' if quant else 'f32'} C={C} "
          f"plan lo_n={lo_n} hi_n={hi_n} m={m} k={k} n_blocks={n_blocks} "
          f"nb={nb} tiles={tiles} groups/tile={n_blocks * k} "
          f"chunks={pp._comp_chunks(hi_n, m, payload)}", flush=True)
    arena = jax.random.randint(jax.random.PRNGKey(0), (C, n_al), 0, B,
                               dtype=jnp.int32).astype(ARENA_DT)
    arena.block_until_ready()
    prev = 0.0
    for stage in HIST_STAGES:
        run = functools.partial(run_hist_stage, stage=stage, n=n, F=F, B=B,
                                quant=quant)
        run(arena).block_until_ready()
        t0 = time.time()
        for _ in range(reps):
            out = run(arena)
        out.block_until_ready()
        dt = (time.time() - t0) / reps * 1000
        print(f"{stage:10s}: {dt:8.3f} ms/pass (+{dt - prev:7.3f})  "
              f"{dt * 1e3 / groups:6.3f} us/group "
              f"(+{(dt - prev) * 1e3 / groups:6.3f})", flush=True)
        prev = dt


def main():
    if len(sys.argv) > 1 and sys.argv[1] == "hist":
        return hist_main(sys.argv[2:])
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
