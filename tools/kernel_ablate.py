"""Stage profile of the partition kernel's tile body, and of the histogram
kernels' (`hist`): step 0 of a kernel PR.

Usage: python tools/kernel_ablate.py [rows_millions [features [sub]]]
       python tools/kernel_ablate.py hist <rows> <features> <max_bin> <q|f32>

`10.5 28` is higgs (C = 48), `13.18 37` Allstate (C = 64), `2.27 137` MSLR
(C = 160).  `sub` (128 or 256) re-traces every stage, the shipped kernel
among them, with `SUB` = `FLUSH_W` = sub: the constant's re-test, for this
tool alone (the package has one value and no switch).

The partition stages are ADDITIVE: the compute stages run over a tile that
is RESIDENT in VMEM (two tiles are read once, before the loop; tile j
computes on slot j % 2, so nothing can be hoisted out of the loop), where
no read hides them: a stage's increment over the stage before is what it
adds to a compute-bound tile body, RUN ALONE: since PR 37 the shipped loop
makes tile j + 1's `column`, `lookup` and `scan` inside iteration j, beside
tile j's products and appends, where the scan's eight dependent lane
rotations (a chain of latencies, not of instructions) cost next to
nothing.  (Until PR 33 every stage ran under the
read pipeline and read max(read, compute): `decide` = 4.60 ms beside `dma`
= 4.56 was taken for 0.03 ms of work.)  `dma` is the read ring alone
(three slots, tile j + 2 started when tile j + 1 has been waited, as the
shipped loop does), and `full` the shipped kernel (`pp.partition_segment`:
read, appends, flushes and write-back included), so what the appends and
flushes cost is `full` less `chunks` less whatever of the read the tile
body does not hide.  Every stage sums what it makes into the loop's carry,
so all of it stays alive; `pbuild` sums all K permutation operands, as
bf16 values, into a VMEM sink, one add per packed vreg (32 a subblock at
SUB = 256: it reads that much too high, and it makes the kernel SELECT and
PACK operands that `matmul` hands to the MXU as push masks, so `matmul`
may read below `pbuild`).

On a TPU only: it prints the device kind and exits 1 elsewhere (a time from
interpret mode is no reading).
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

TILE = pp.TILE
ARENA_DT = pp.ARENA_DT

# `loop` is the empty stage loop (its time is subtracted from the others);
# `column` adds the split feature's bin values (pp._split_column), `lookup`
# the go-left mask's lookup and the two streams' predicates (pp._go_left),
# `scan` the batched prefix scan and the rows' sorted positions
# (pp._sort_pos), `pbuild` all K permutation operands (pp._sort_P),
# `matmul` the K sort products in place of the sink, `chunks` the A/B split:
# everything but the appends
STAGES = ("loop", "column", "lookup", "scan", "pbuild", "matmul", "chunks")


def tile_body(stage, in_buf, slot, j, sc_ref, mask_ref, sink):
    """Tile j's body up to `stage`, made from the kernel's own pieces;
    returns an [8, 128] f32 digest of what the stage made."""
    SUB, K = pp.SUB, TILE // pp.SUB
    valid = (jax.lax.broadcasted_iota(jnp.int32, (K, SUB), 0) * SUB
             + jax.lax.broadcasted_iota(jnp.int32, (K, SUB), 1)
             ) < sc_ref[1] - j * TILE

    def digest(x):
        x = x.astype(jnp.float32)
        x = x.reshape(-1, x.shape[-1])
        rows = sum(x[r:r + 8] for r in range(0, x.shape[0], 8))
        return sum(rows[:, c:c + 128] for c in range(0, x.shape[1], 128))

    group = in_buf[slot, pl.ds(pl.multiple_of(sc_ref[7], 16), 16), :]
    col = pp._split_column(group, sc_ref[8], K)
    if stage == "column":
        return digest(col)
    on = pp._go_left(col, mask_ref) ^ sc_ref[5]
    predA = jnp.where(valid, on, 0)
    predB = jnp.where(valid, 1 - on, 0)
    if stage == "lookup":
        return digest(predA - predB)
    pred2 = jnp.concatenate([predA, predB], axis=0)
    pref2 = pp._prefix_scan_lanes(pred2)
    pos = pp._sort_pos(pref2, pred2, K)
    if stage == "scan":
        return digest(pos)
    if stage == "pbuild":
        for k in range(K):
            sink[:] = sink[:] + pltpu.bitcast(pp._sort_P(pos, k), jnp.int32)
        return digest(pos)
    block = in_buf[slot]
    comps = [jax.lax.dot_general(block[:, k * SUB:(k + 1) * SUB],
                                 pp._sort_P(pos, k), pp._SORT_DIMS,
                                 preferred_element_type=jnp.float32)
             for k in range(K)]
    if stage == "matmul":
        return digest(sum(comps))
    lane_s = jax.lax.broadcasted_iota(jnp.int32, (1, SUB), 1)
    chunksA = [jnp.where(lane_s < pref2[k, SUB - 1], comps[k],
                         jnp.float32(0.0)) for k in range(K)]
    chunksB = [comps[k] - chunksA[k] for k in range(K)]
    return digest(sum(chunksA) - sum(chunksB))


def _kernel(sc_ref, mask_ref, arena_any, out_any, cnt_ref, in_buf, sink,
            read_sems, *, tile: int, stage: str):
    """`dma`: the shipped one-block loop's read ring alone.  Every other
    stage: two tiles read once, then the stage loop over the resident
    tiles; nothing is written back.  sc_ref and mask_ref are the shipped
    kernel's."""
    s, cnt = sc_ref[0], sc_ref[1]
    n_tiles = jax.lax.div(cnt + jnp.int32(tile - 1), jnp.int32(tile))

    def read_dma(j, slot):
        src = pl.multiple_of(s + j * tile, 128)
        return pltpu.make_async_copy(
            arena_any.at[:, pl.ds(src, tile)], in_buf.at[slot],
            read_sems.at[slot])

    sink[:] = jnp.zeros_like(sink)
    zero = jnp.zeros((8, 128), jnp.float32)
    if stage == "dma":
        # the shipped loop's reads (PR 37): a ring of three, tile j + 1
        # waited and tile j + 2 started at the top of iteration j
        for t in range(2):
            @pl.when(n_tiles > t)
            def _(t=t):
                read_dma(t, t).start()

        @pl.when(n_tiles > 0)
        def _():
            read_dma(0, 0).wait()

        def loop(j, chk):
            @pl.when(j + 1 < n_tiles)
            def _():
                read_dma(j + 1, jax.lax.rem(j + 1, jnp.int32(3))).wait()

            @pl.when(j + 2 < n_tiles)
            def _():
                read_dma(j + 2, jax.lax.rem(j + 2, jnp.int32(3))).start()
            return chk + in_buf[jax.lax.rem(j, jnp.int32(3)), 0:8,
                                0:128].astype(jnp.float32)
    else:
        for t in range(2):
            read_dma(t, t).start()
            read_dma(t, t).wait()

        def loop(j, chk):
            slot = jax.lax.rem(j, jnp.int32(2))
            if stage == "loop":
                return chk + in_buf[slot, 0:8, 0:128].astype(jnp.float32)
            return chk + tile_body(stage, in_buf, slot, j, sc_ref,
                                   mask_ref, sink)

    chk = jax.lax.fori_loop(0, n_tiles, loop, zero)
    cnt_ref[0] = (jnp.sum(chk) + jnp.sum(sink[:].astype(jnp.float32))
                  ).astype(jnp.int32)
    cnt_ref[1] = jnp.int32(0)


@functools.partial(jax.jit, static_argnames=("stage", "n", "reps"),
                   donate_argnums=(0,))
def run_stage(arena, decision, *, stage, n, reps):
    """`reps` passes of one stage over the first n rows; the arena is
    donated and comes back (aliased through every call: no copy of it is
    timed)."""
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
    goleft, group_and_row = pp._decision_operands(feat, mask_vec)
    sc = jnp.stack([jnp.asarray(v, jnp.int32)
                    for v in [0, n, 0, dstB, 1, xr, 0] + group_and_row])
    kernel = functools.partial(_kernel, tile=TILE, stage=stage)

    def body(i, ar):
        ar, cnts = pl.pallas_call(
            kernel,
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pltpu.VMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                       pl.BlockSpec(memory_space=pltpu.SMEM)),
            out_shape=(jax.ShapeDtypeStruct((C, cap), ARENA_DT),
                       jax.ShapeDtypeStruct((2,), jnp.int32)),
            scratch_shapes=[
                pltpu.VMEM((3, C, TILE), ARENA_DT),
                pltpu.VMEM((pp.SUB // 2, pp.SUB), jnp.int32),
                pltpu.SemaphoreType.DMA((3,)),
            ],
            input_output_aliases={2: 0},
            compiler_params=pltpu.CompilerParams(has_side_effects=True),
        )(sc, goleft, ar)
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
    _require_tpu("hist")
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


def _require_tpu(what):
    if jax.default_backend() != "tpu":
        raise SystemExit(
            "kernel_ablate %s: backend is %r, not tpu; the stages are device "
            "times and are not taken in interpret mode (the pieces are "
            "tested there by tests/test_partition_engine.py and "
            "tests/test_radix_operand.py)" % (what, jax.default_backend()))


def main():
    if len(sys.argv) > 1 and sys.argv[1] == "hist":
        return hist_main(sys.argv[2:])
    _require_tpu("partition")
    n = int(float(sys.argv[1]) * 1e6) if len(sys.argv) > 1 else 4_000_000
    F = int(sys.argv[2]) if len(sys.argv) > 2 else 28
    if len(sys.argv) > 3:
        pp.SUB = pp.FLUSH_W = int(sys.argv[3])
    B = 255
    rng = np.random.default_rng(0)
    C, cap = pp.arena_geometry(n, F)
    tiles = -(-n // TILE)
    print(f"partition device={jax.devices()[0].device_kind!r} n={n} C={C} "
          f"SUB={pp.SUB} TILE={TILE} FLUSH_W={pp.FLUSH_W} tiles={tiles}",
          flush=True)
    arena = jnp.asarray(
        rng.integers(0, B, size=(C, cap)).astype(np.float32), ARENA_DT)
    float(jnp.sum(arena[:, :1]))
    mask = (jnp.arange(256) < B // 2).astype(jnp.float32)
    decision = (jnp.int32(F // 2), mask, jnp.int32(0))
    reps = 10
    times = {}
    for stage in STAGES + ("dma", "full"):
        arena = run_stage(arena, decision, stage=stage, n=n, reps=reps)
        float(jnp.sum(arena[:, :1]))
        t0 = time.time()
        arena = run_stage(arena, decision, stage=stage, n=n, reps=reps)
        float(jnp.sum(arena[:, :1]))
        times[stage] = dt = (time.time() - t0) / reps * 1000
        if stage in ("loop", "dma", "full"):
            print(f"{stage:8s}: {dt:7.2f} ms/pass  "
                  f"{dt * 1e3 / tiles:6.3f} us/tile", flush=True)
            continue
        own = dt - times["loop"]
        more = dt - times[STAGES[STAGES.index(stage) - 1]]
        print(f"{stage:8s}: {own:7.2f} ms/pass (+{more:6.2f})  "
              f"{own * 1e3 / tiles:6.3f} us/tile "
              f"(+{more * 1e3 / tiles:6.3f})", flush=True)
    rest = times["full"] - (times["chunks"] - times["loop"])
    print(f"full - chunks: {rest:7.2f} ms/pass  "
          f"{rest * 1e3 / tiles:6.3f} us/tile (appends, flushes and the part "
          f"of the {times['dma'] * 1e3 / tiles:.3f} us read the tile body "
          "does not hide)", flush=True)


if __name__ == "__main__":
    main()
