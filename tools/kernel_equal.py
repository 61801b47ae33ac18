"""Two trees' `partition_segment` on equal inputs, compared bit for bit.

Usage: python tools/kernel_equal.py <other tree> [--interpret] [--seed N]
           <rows>,<features>[,<max_bin>] [...]

`<other tree>` is the root of a second checkout (a `git archive` of the
parent commit unpacked under a git-ignored directory of this one, so that
the chip tool copies it); this tree is the working directory.  Of each tree
only `lightgbm_tpu/ops/partition_pallas.py` and the `histogram_pallas.py`
beside it are loaded, as a package of their own, so neither tree's kernels
can reach the other's.  `10500000,28` is higgs (C = 48), `13184290,37`
Allstate (C = 64), `2270000,137` MSLR (C = 160) and `400000,2000,63`
Epsilon (C = 2 016, six channel blocks).

Per shape one seeded arena (bin values below `max_bin` on every channel:
bfloat16-exact, as an arena's payloads are) and, on it, both kernels in
mode 1 (the in-kernel decision, both `xr`) and mode 0 (by `pred`), in
place and to a second destination, without and with the fused histogram
(`hist_stream` 0 and 1), over segments of one row, one tile less and more
a row, a few tiles and the whole data set (that one in place only, as
the engine writes stream A).  The arenas' bits, the counts and the
histograms' bits must be equal; the first unequal element is reported (which output of which case, where, both values) and the exit
code is 1.  `--interpret` runs the kernels in interpret mode (the CPU: a
rehearsal of this script at small shapes, and no statement about Mosaic).
"""
import argparse
import importlib.util
import itertools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np


def tree_ops(root, alias):
    """`<root>/lightgbm_tpu/ops/partition_pallas.py` as module
    `<alias>.partition_pallas` of a package that holds nothing but that
    directory: its relative imports stay inside its own tree."""
    ops = os.path.join(root, "lightgbm_tpu", "ops")
    pkg = importlib.util.module_from_spec(
        importlib.util.spec_from_loader(alias, loader=None, is_package=True))
    pkg.__path__ = [ops]
    sys.modules[alias] = pkg
    return importlib.import_module(alias + ".partition_pallas")


_STEP = 1 << 19      # columns the seeded arena is made in at a time


def seeded_arena(pp, C, cap, max_bin, seed):
    """[C, cap] arena of integers in [0, max_bin), made on the device in
    chunks of _STEP columns (the generator's 32-bit draws are twice the
    arena's bytes)."""
    step = min(_STEP, cap)
    fill = jax.jit(
        lambda arena, key, at: jax.lax.dynamic_update_slice(
            arena, jax.random.randint(key, (C, step), 0, max_bin).astype(
                pp.ARENA_DT), (0, at)), donate_argnums=(0,))
    arena = jnp.zeros((C, cap), pp.ARENA_DT)
    for i, at in enumerate(range(0, cap, step)):
        # (the last chunk is moved back to end at the arena's end)
        arena = fill(arena, jax.random.fold_in(jax.random.PRNGKey(seed), i),
                     min(at, cap - step))
    return arena


@jax.jit
def _differ(a, b):
    """(any element's bits differ, the first column that holds one), the
    arrays taken as [rows, columns] in the layout they have: a flattened
    arena is a second arena."""
    bits = {2: jnp.uint16, 4: jnp.uint32}[a.dtype.itemsize]
    columns = jnp.any(jax.lax.bitcast_convert_type(a, bits)
                      != jax.lax.bitcast_convert_type(b, bits), axis=0)
    return jnp.any(columns), jnp.argmax(columns)


def first_unequal(a, b):
    """None, or (row, column, a's element, b's element) of an element
    whose bits differ: the first such column's first such row."""
    a, b = (x.reshape(-1, x.shape[-1]) for x in (a, b))
    any_differ, col = _differ(a, b)
    if not bool(any_differ):
        return None
    col = int(col)
    ca, cb = (np.asarray(x[:, col].astype(jnp.float32)) for x in (a, b))
    row = int(np.argmax(ca.view(np.uint32) != cb.view(np.uint32)))
    return row, col, float(ca[row]), float(cb[row])


def compare(shape, this, other, interpret, seed):
    rows, F = shape[:2]
    max_bin = shape[2] if len(shape) > 2 else 255
    tile = this.TILE
    base = -(-rows // tile) * tile
    # the segment, then stream B's destination; a second destination for
    # stream A lies behind stream B's rows wherever both fit (the engine
    # itself writes stream A in place)
    start, dst_b = tile, base + 2 * tile
    cap = 2 * base + 4 * tile
    C = this.arena_channels(F)
    assert C == other.arena_channels(F) and tile == other.TILE
    arena = seeded_arena(this, C, cap, max_bin, seed)
    pred = (jax.random.uniform(jax.random.PRNGKey(seed + 1), (1, cap))
            < 0.37).astype(jnp.float32)
    dummy = jnp.zeros((1, tile), jnp.float32)
    mask = (np.random.RandomState(seed).rand(256) < 0.5).astype(np.float32)
    counts = sorted({1, tile - 1, tile, tile + 1, 2 * tile, 3 * tile + 5,
                     7 * tile, rows // 7, rows} & set(range(1, rows + 1)))
    cases = unequal = 0
    for cnt, mode, xr, in_place, hist in itertools.product(
            counts, (1, 0), (0, 1), (True, False), (None, 0, 1)):
        dst_far = dst_b + -(-cnt // tile) * tile + tile
        if (mode == 0 and xr) or (
                not in_place and dst_far + cnt + tile > cap):
            continue
        kw = dict(interpret=interpret)
        if mode:
            kw["decision"] = (F // 2, jnp.asarray(mask), xr)
        if hist is not None:
            kw.update(hist_stream=hist, num_features=F, max_bin=max_bin,
                      quantized=True)
        args = (pred if mode == 0 else dummy, start, cnt,
                start if in_place else dst_far, dst_b)
        outs = [m.partition_segment(arena, *args, **kw)
                for m in (this, other)]
        cases += 1
        for name, a, b in zip(("arena", "counts", "histogram"), *outs):
            found = first_unequal(a, b)
            if found is None:
                continue
            unequal += 1
            print("UNEQUAL rows=%d C=%d cnt=%d mode=%d xr=%d in_place=%s "
                  "hist_stream=%s: %s at [%d, %d]: %r here, %r there" % (
                      (rows, C, cnt, mode, xr, in_place, hist, name)
                      + found), flush=True)
            break
        del outs
    print("rows=%d features=%d C=%d (block %d): %d cases, %d unequal" % (
        rows, F, C, this.partition_channel_block(C), cases, unequal),
        flush=True)
    return unequal


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("other", help="root of the second tree")
    ap.add_argument("shapes", nargs="+", metavar="rows,features[,max_bin]")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--interpret", action="store_true")
    args = ap.parse_args()
    if not args.interpret and jax.default_backend() != "tpu":
        raise SystemExit("kernel_equal: backend is %r, not tpu (pass "
                         "--interpret for a rehearsal on the CPU)"
                         % jax.default_backend())
    this = tree_ops(os.getcwd(), "_kernel_equal_here")
    other = tree_ops(args.other, "_kernel_equal_there")
    print("device=%r here=%s there=%s" % (
        jax.devices()[0].device_kind, this.__file__, other.__file__),
        flush=True)
    unequal = sum(compare(tuple(int(v) for v in shape.split(",")), this,
                          other, args.interpret, args.seed)
                  for shape in args.shapes)
    print("kernel_equal: %s" % ("EQUAL" if not unequal
                                else "%d cases UNEQUAL" % unequal))
    return 1 if unequal else 0


if __name__ == "__main__":
    sys.exit(main())
