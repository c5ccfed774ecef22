"""Operations and bytes of a dense Greedy selection, from its shapes alone.

As in ``bench/work.py``, nothing here knows a tile, a padded lane or a
kernel, so the counts stay the same whatever implements the selection.
"""
from __future__ import annotations


def dense_greedy(n: int, d: int, k: int, itemsize: int = 4):
    """One selection of k by dense Greedy over n points of width d.

    FLOPs: the Gram term of every (point, live candidate) pair of every
    round, Σ_{r<k} 2·n·(n − r)·d, and the distance column of each winner
    but the last folded into the cache, (k − 1)·2·n·d.
    Bytes: each round reads V (n·d at ``itemsize``) once, reads and writes
    the (n,) float32 cache and writes the (n,) float32 gains:
    k·(n·d·itemsize + 3·n·4).
    """
    flops = sum(2 * n * (n - r) * d for r in range(k)) + (k - 1) * 2 * n * d
    nbytes = k * (n * d * itemsize + 3 * n * 4)
    return flops, nbytes
