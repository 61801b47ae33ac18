"""Seeded random streams shared by the data generators."""
import zlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np

_BLOCK = 1 << 19          # rows per independently seeded block
_THREADS = 8              # set-up only; the measured window is one thread


def stream(seed, *names):
    """Entropy for `default_rng`: the seed and a tag per name, so streams
    with different names never overlap."""
    return [int(seed)] + [zlib.crc32(str(n).encode()) for n in names]


def normal_f32(entropy, rows, cols):
    """[rows, cols] float32 standard normals.  Block b is drawn from
    `default_rng(entropy + [b])`, so the result depends on the entropy
    alone, not on how many threads filled it (the generator fills `out`
    with the GIL released)."""
    out = np.empty((rows, cols), np.float32)

    def fill(b):
        view = out[b * _BLOCK:(b + 1) * _BLOCK]
        np.random.default_rng(list(entropy) + [b]).standard_normal(
            view.shape, dtype=np.float32, out=view)

    blocks = range(-(-rows // _BLOCK))
    with ThreadPoolExecutor(_THREADS) as pool:
        list(pool.map(fill, blocks))
    return out
