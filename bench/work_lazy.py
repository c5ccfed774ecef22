"""Operations and bytes of a CELF (lazy greedy) selection, from its shapes
and its checked evaluation count alone.

CELF's work depends on the data: how many candidates it re-scores. The
count used here is ``OptResult.evaluations``, which the cell's ``correct``
holds to the plain reference's (``bench/reference/lazy.py``); no timing
and no counter of the program enters. As in ``bench/work.py``, nothing
here knows a tile, a padded lane or a kernel.
"""
from __future__ import annotations


def passes(n: int, evaluations: int, top_b: int) -> int:
    """Scoring passes over the ground set: the bound seeding (n scores)
    and one re-score trip per ``top_b`` of the rest."""
    return 1 + -(-(evaluations - n) // top_b)


def scoring(n: int, d: int, evaluations: int, top_b: int,
            itemsize: int = 4):
    """The seeding and re-score scoring of one selection.

    FLOPs: the Gram term of every (point, scored candidate) pair,
    evaluations · 2·n·d.
    Bytes: each pass reads V (n·d at ``itemsize``) and the (n,) float32
    cache once; each scored candidate's row is read and its float32 gain
    written once:
    passes·(n·d·itemsize + 4·n) + evaluations·(d·itemsize + 4).
    """
    p = passes(n, evaluations, top_b)
    flops = evaluations * 2 * n * d
    nbytes = p * (n * d * itemsize + 4 * n) + evaluations * (d * itemsize + 4)
    return flops, nbytes


def celf(n: int, d: int, k: int, evaluations: int, top_b: int,
         itemsize: int = 4):
    """One selection of k by CELF over n points of width d: the scoring,
    plus the distance column of each winner but the last folded into the
    cache ((k − 1)·2·n·d FLOPs; V read, the cache read and written), plus
    the (n,) float32 bounds, written by the seeding and read by each
    trip's choice of candidates (passes·4·n bytes)."""
    flops, nbytes = scoring(n, d, evaluations, top_b, itemsize)
    flops += (k - 1) * 2 * n * d
    nbytes += (k - 1) * (n * d * itemsize + 8 * n)
    nbytes += passes(n, evaluations, top_b) * 4 * n
    return flops, nbytes
