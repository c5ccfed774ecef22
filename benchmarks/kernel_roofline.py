"""TPU roofline model of the exemplar-evaluation kernels (paper problem sizes).

No TPU is attached, so this derives the kernel's three roofline terms
analytically from the exact tile/grid configuration the Pallas wrapper picks
(the same numbers `ops.kernel_config` uses), for the paper's problem grid.
It quantifies the two TPU-side design decisions:

  * MXU reformulation: FLOPs = 2·n·l·k·d (Gram) vs scalar-loop FMA count —
    identical count but ~128× higher attainable throughput (MXU vs VPU);
    the term that matters is arithmetic intensity.
  * fused vs two-pass W: HBM bytes drop by l·n·4 (the work matrix) per
    evaluation — the dominant traffic term for large l·n.

Derived column: arithmetic intensity (FLOP/byte) and the bound.
"""
from __future__ import annotations

from benchmarks.common import emit
from repro.core.precision import FP32, BF16, FP16
from repro.kernels.ops import kernel_config

PEAK = 197e12
HBM = 819e9


def kernel_terms(n, l, k, d, policy, fused: bool, mode: str = "traffic_opt"):
    d_pad = ((d + 127) // 128) * 128
    # fused is modelled at the loop variant's tile: flat has none at k=500
    cfgk = kernel_config(k, d_pad, policy, l, n, mode=mode,
                         variant="loop" if fused else "two_pass")
    cs = policy.itemsize
    flops = 2.0 * n * l * k * d_pad              # Gram matmul (dominant)
    # HBM traffic: V read once per l-tile row; S read once per n-tile column
    grid_l = (l + cfgk.block_l - 1) // cfgk.block_l
    grid_n = (n + cfgk.block_n - 1) // cfgk.block_n
    bytes_v = n * d_pad * cs * grid_l            # V re-read per l tile
    bytes_s = l * k * d_pad * cs * grid_n        # S re-read per n tile
    bytes_out = l * 4
    if not fused:
        bytes_out += 2 * l * n * 4               # W write + read (paper mode)
    total_bytes = bytes_v + bytes_s + bytes_out
    return flops, total_bytes, cfgk


def run(quick: bool = False):
    rows = []
    grid = [(50_000, 5_000, 10, 100), (400_000, 5_000, 10, 100),
            (50_000, 40_000, 10, 100), (50_000, 5_000, 500, 100)]
    if quick:
        grid = grid[:2]
    for n, l, k, d in grid:
        for pol in (FP32, BF16, FP16):
            for fused in (True, False):
                for mode in ("paper", "traffic_opt"):
                    fl, by, cfgk = kernel_terms(n, l, k, d, pol, fused,
                                                mode=mode)
                    t_c = fl / PEAK
                    t_m = by / HBM
                    ai = fl / by
                    bound = "compute" if t_c > t_m else "memory"
                    tag = (f"kernel[n={n},l={l},k={k}]"
                           f"_{pol.name}_{'fused' if fused else 'two_pass'}"
                           f"_{mode}")
                    rows.append((tag, max(t_c, t_m) * 1e6,
                                 f"AI={ai:.0f};bound={bound};"
                                 f"Bl={cfgk.block_l};Bn={cfgk.block_n}"))
    emit(rows)
    return rows
