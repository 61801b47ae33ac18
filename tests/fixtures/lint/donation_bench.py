"""A timing script's use of the real donating grower — lint fixture, clean.

The shape tools/phase_bench.py had: a closure rebinds a donated arena
through `nonlocal`, calling `gp.grow_tree_partition` from the real
lightgbm_tpu/ops/grow_partition.py (cross-file `donate_argnums`
resolution).  Never imported; tests/test_lint.py seeds a read after the
donation into a copy of it.
"""
import jax.numpy as jnp

from lightgbm_tpu.ops import grow_partition as gp
from lightgbm_tpu.ops import partition_pallas as pp


def time_trees(measure, bins_dev, g_dev, h_dev, row0, fmask, nb, zb,
               params, n, F, B, interp, reps):
    C, cap = pp.arena_geometry(n, F)
    arena = jnp.zeros((C, cap), pp.ARENA_DT)

    def grow_at(leaves, emit):
        def run():
            nonlocal arena
            arrays, out_ids, arena, _ = gp.grow_tree_partition(
                arena, bins_dev, g_dev, h_dev, row0, fmask, nb, zb, zb,
                params, max_leaves=leaves, max_bin=B, emit=emit,
                interpret=interp)
            return out_ids
        return run

    return {leaves: measure(grow_at(leaves, "score"), reps)
            for leaves in (2, 64, 255)}
