"""Operations and bytes an algorithm needs, from its shapes alone.

Nothing here knows a tile, a padded lane or a kernel variant, so the counts
stay the same whatever implements the work. A roofline share divides the
least time these allow on a chip by the time the chip took.
"""
from __future__ import annotations


def multiset_eval(n: int, l: int, k: int, d: int, itemsize: int = 4):
    """L(S_j ∪ {e0}) for l sets of k over n points of width d.

    FLOPs: the Gram term of every (point, set member) pair, 2·n·l·k·d.
    Bytes: V (n·d) and the packed sets S (l·k·d) read once at ``itemsize``,
    and the (l,) float32 output written once.
    """
    flops = 2 * n * l * k * d
    nbytes = (n * d + l * k * d) * itemsize + l * 4
    return flops, nbytes


def min_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time ``peak`` (an entry of ``peaks.json``) allows: the
    larger of FLOPs over the bf16 peak and bytes over HBM bandwidth. The
    bf16 peak is used at every precision: an fp32 contraction at HIGHEST
    takes several MXU passes, so fp32 work reads well below 100%."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
