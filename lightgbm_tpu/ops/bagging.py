"""Row bagging drawn on the device (gbdt.cpp Bagging, bagging_freq /
bagging_fraction).

A bag holds exactly `count = int(bagging_fraction * n)` rows, as the
reference's `bag_data_cnt_` does, and is a pure function of
`bagging_seed` and the bag's index (iteration // bagging_freq): a resumed
or re-run booster draws the same bags, whichever spine grows its trees.

Each row gets a 32-bit key, a keyed bijection of its row id (two rounds
of an xor-shift-multiply mixer under the bag's two key words), so no two
rows share a key.  The bag is the `count` rows of smallest key: a
threshold found by a 32-step binary search of counts (one reduction over
the keys a step, no sort, no gather).  The drawn bag is three words
(key0, key1, threshold); whether a row is in it is an elementwise test of
its row id, so the test runs on rows in any order: row order for the
unfused spine's mask, the carried arena's permuted order for the fused
spine's root (ops/grow_partition.py).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def bag_rows(fraction: float, num_data: int) -> int:
    """Rows in every bag (gbdt.cpp: bagging_fraction * num_data_, cut)."""
    return int(fraction * num_data)


def _mix(x):
    """A bijection of uint32 with full avalanche (Wellons' lowbias32)."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def row_keys(bag, rowids):
    """uint32 keys of `rowids` (any shape, int) under the bag's key words:
    distinct rows, distinct keys."""
    x = _mix(rowids.astype(jnp.uint32) ^ bag[0])
    return _mix(x ^ bag[1])


def member(bag, rowids):
    """bool, same shape as `rowids`: the rows in the bag."""
    return row_keys(bag, rowids) <= bag[2]


@partial(jax.jit, static_argnames=("num_data", "count"))
def draw(seed, index, *, num_data: int, count: int):
    """The bag of draw `index` under `seed`: uint32 [3] (key0, key1,
    threshold), `count` rows of `num_data` in it (0 < count <= num_data)."""
    with jax.named_scope("lgbm.bag"):
        words = jax.random.key_data(jax.random.fold_in(
            jax.random.PRNGKey(seed), index)).astype(jnp.uint32)
        keys = row_keys(words, jnp.arange(num_data, dtype=jnp.int32))

        # the count-th smallest key, bit by bit from the top: t is the
        # largest value with fewer than `count` keys below it
        def step(i, t):
            trial = t | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(
                jnp.uint32)))
            below = jnp.sum(keys < trial, dtype=jnp.int32)
            return jnp.where(below < count, trial, t)

        t = jax.lax.fori_loop(0, 32, step, jnp.uint32(0))
        return jnp.stack([words[0], words[1], t])


@partial(jax.jit, static_argnames=("num_data",))
def row_mask(bag, *, num_data: int):
    """int32 [num_data] in row order: 0 in the bag, -1 out (the unfused
    spine's `row_leaf_init`)."""
    with jax.named_scope("lgbm.bag"):
        rows = jnp.arange(num_data, dtype=jnp.int32)
        return jnp.where(member(bag, rows), 0, -1).astype(jnp.int32)
