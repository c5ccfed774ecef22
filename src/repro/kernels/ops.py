"""Jit'd public wrappers around the Pallas kernels.

Handles everything the CUDA host code in the paper handles:

* **kernel configuration** (paper's ``C = (D_g, D_b)`` formula): block sizes
  are chosen from a VMEM budget exactly like the paper chooses ``b_x = min(
  ⌊1024/b_y⌋, ⌊β/γ⌋)`` from the shared-memory budget β — see
  :func:`kernel_config`.
* **padding / layout** (paper's vectorization routine §IV-B-2): d is padded to
  the 128-lane boundary, n/l to block multiples, and the multiset is
  pre-transposed to k-major layout (the TPU analogue of round-robin
  interleaving).
* **chunking** (paper §IV-B-3): an optional memory budget splits the multiset.

``interpret=True`` runs a kernel body in Python (bit-accurate, any backend);
the default compiles it for the TPU. Nothing here picks interpret mode from
the backend: callers choose it (``backend="pallas_interpret"``), so a run
that was meant for the chip can never fall back to the interpreter unseen.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import tracing
from repro.core.evaluator import plan_chunks
from repro.core.precision import FP32, PrecisionPolicy, check_kernel_policy
from repro.kernels import exemplar_eval as _ee
from repro.kernels import marginal_gain as _mg

LANE = 128
SUBLANE = 8
#: default per-operand VMEM budget for the multiset tile (bytes)
VMEM_S_BUDGET = 4 * 1024 * 1024
#: seconds the loop variant takes per distance, per running-min element
#: per k chunk, and per byte of the traffic model: a least-squares fit on
#: a TPU v5e to 16 timed (Bn, Bl=128, kc) tiles at k=45, 100 and 500
#: (fp32, N=50,000, l=5,000, d=100), every tile within 2.8% of the fit
LOOP_COST_S = (8.3e-12, 15.4e-12, 0.35e-12)
#: the gain kernels' tile edges (Bn and Bm), the range GAIN_COST_S was
#: fitted over
GAIN_TILES = (256, 512, 1024, 2048)
#: seconds the fused gain kernel takes per distance, per grid step, per
#: ground-set row per candidate tile (n_pad·m_tiles: V, its norms, the
#: winner's column and the cache, once per step) and per candidate row
#: per ground-set tile (m_pad·n_tiles: C, its norms and the gain column):
#: a least-squares fit on a TPU v5e to 16 timed (Bn, Bm) tiles of
#: ``gain_update_eval`` at n = m = 50,000 (fp32, d=100, min fold), every
#: tile within 1.4% of the fit (6.4% at m = 5,000, which it reads low)
GAIN_COST_S = (7.88e-12, 0.229e-6, 1.19e-9, 0.040e-9)


@dataclasses.dataclass(frozen=True)
class KernelConfig:
    """TPU analogue of the paper's kernel configuration C = (D_g, D_b)."""

    block_n: int   # ground vectors per tile (paper: b_x)
    block_l: int   # evaluation sets per tile (paper: b_y)
    block_k: int   # k slices per grid step: all k, or a chunk ("loop")

    def grid(self, n_pad: int, l_pad: int) -> tuple[int, int]:
        # paper eq. 8: g_x = ⌈|V|/b_x⌉, g_y = ⌈|S_multi|/b_y⌉
        return (l_pad // self.block_l, n_pad // self.block_n)


def kernel_config(k: int, d_pad: int, policy: PrecisionPolicy,
                  l: int, n: int,
                  s_budget_bytes: int = VMEM_S_BUDGET,
                  mode: str = "traffic_opt",
                  variant: str = "loop") -> KernelConfig:
    """Pick block dims (paper's b_x/b_y computation, then one step further).

    ``mode="paper"`` reproduces the paper's greedy rule: maximize the number
    of sets per block first (b_y), then fill b_x — optimal for reuse of the
    shared-memory-staged V rows on a GPU.

    ``mode="traffic_opt"`` (default, §Perf K4): minimize total HBM traffic
      T(Bn, Bl) = n·d·cs·⌈l/Bl⌉  (V re-read per l-tile row)
                + l·k·d·cs·⌈n/Bn⌉ (S re-read per n-tile column)
    subject to the VMEM working set of the kernel ``variant`` the tile is
    for: the V and S tiles twice (the Pallas pipeline double-buffers
    inputs) and the f32 distance tile three times per live slice (the
    matmul result, its masked copy and the min's operand live at once; a
    v5e compile of the paper's size refused the single-count model).
    ``"flat"`` holds all k slices at once and needs a lane-dense Bl for its
    (Bn, k, Bl) split; ``"two_pass"`` holds one slice of a whole-k S tile
    and writes a double-buffered (Bl, Bn) W tile, so large k shrinks its Bl
    to one sublane tile. The paper's rule fixes Bn=256 and spends all VMEM
    on Bl, which over-weights the V term; for l·k ≫ n the S term dominates
    and a balanced split is up to ~1.9× less traffic by this model.

    ``"loop"`` runs flat's tile body on chunks of kc slices tiled across the
    grid (k padded to k_pad, a multiple of kc), with one more (Bn, Bl) f32
    tile for the running min, so its S tile is (kc, Bl, d) and Bl stays
    lane-dense at any k. Its (Bn, Bl, kc) minimizes the time the chip was
    measured to take (``LOOP_COST_S``): per distance n_pad·l_pad·k_pad,
    per running-min element loaded and stored once per chunk
    (n_pad·l_pad·k_pad/kc), and per byte of the traffic above at k_pad.
    Smaller chunks pay the running min more often, larger tiles leave room
    for fewer slices; at the paper's size and fp32 it picks (256, 128, 20)
    at k=500. Ties go to the fewest grid steps. What the chip reaches is
    measured against the work of the call from its shapes alone
    (bench/work.py, ``roofline.multiset``).

    Raises ``ValueError`` when no tile of the variant fits the working-set
    budget (``"flat"`` from k≈39 at fp32, d_pad=128).
    """
    cs = policy.itemsize
    # Bl is lane-dense: the flat body splits its (Bn, kc·Bl) distance
    # tile into (Bn, kc, Bl), which Mosaic reshapes only at whole lane rows
    cap_l = min(512, _round_up(l, LANE))
    if mode == "paper":
        per_set = k * d_pad * cs
        bl = max(s_budget_bytes // per_set, LANE)
        bl = min(bl, cap_l)
        bl = (bl // LANE) * LANE
        return KernelConfig(block_n=min(256, _round_up(n, SUBLANE)),
                            block_l=bl, block_k=k)

    if variant not in ("flat", "loop", "two_pass"):
        raise ValueError(f"unknown variant {variant!r}")
    vmem_cap = 3 * s_budget_bytes  # total working-set budget (~12 MiB)
    best, best_key = None, None
    # the two-pass W tile (Bl, Bn) is lane-dense: Bn is a multiple of 128,
    # or the whole padded n when n is smaller than one lane row
    bn_opts = [b for b in (128, 256, 512, 1024)
               if b <= _round_up(n, SUBLANE)] or [_round_up(n, SUBLANE)]
    bl_opts = [b for b in (128, 256, 512) if b <= cap_l]
    if variant == "two_pass":
        # one slice at a time keeps Bl on sublanes, so large k may shrink
        # it to one sublane tile of S: 8 rows at fp32, 16 at bf16
        bl_opts = [b for b in (8, 16, 32, 64)
                   if SUBLANE * 4 // cs <= b < cap_l] + bl_opts
    kc_opts = range(1, k + 1) if variant == "loop" else (k,)
    for bn in bn_opts:
        for bl in bl_opts:
            for kc in kc_opts:
                if variant == "two_pass":   # one live slice, W tile twice
                    tiles = 3 + 2
                else:                       # kc live slices (+ running min)
                    tiles = 3 * kc + (variant == "loop")
                work = (2 * (bn * d_pad * cs + bl * kc * d_pad * cs)  # V + S
                        + tiles * bn * bl * 4)                # f32 tiles
                if work > vmem_cap:
                    break                   # grows with kc
                k_pad = _round_up(k, kc)
                traffic = (n * d_pad * cs * math.ceil(l / bl)
                           + l * k_pad * d_pad * cs * math.ceil(n / bn))
                steps = (math.ceil(l / bl) * math.ceil(n / bn)
                         * (k_pad // kc))
                cost = traffic
                if variant == "loop":
                    dists = _round_up(n, bn) * _round_up(l, bl) * k_pad
                    per_dist, per_min, per_byte = LOOP_COST_S
                    cost = (dists * (per_dist + per_min / kc)
                            + traffic * per_byte)
                if best_key is None or (cost, steps) < best_key:
                    best, best_key = (bn, bl, kc), (cost, steps)
    if best is None:
        raise ValueError(
            f"no {variant!r} tile fits the {vmem_cap >> 20} MiB VMEM working "
            f"set at k={k}, d_pad={d_pad}, {policy.name}"
            + ("; variant 'loop' tiles k in chunks" if variant == "flat"
               else ""))
    return KernelConfig(block_n=best[0], block_l=best[1], block_k=best[2])


def multiset_tile(k: int, d_pad: int, policy: PrecisionPolicy, l: int,
                  n: int, mode: str, variant: str) -> tuple[str, KernelConfig]:
    """The multiset kernel variant and tile that run ``mode``/``variant``:
    ``"flat"`` becomes ``"loop"`` where no flat tile fits VMEM (from k≈39
    at fp32, d_pad=128); both compute the same minima."""
    tile_variant = "two_pass" if mode == "two_pass" else variant
    try:
        return variant, kernel_config(k, d_pad, policy, l, n,
                                      variant=tile_variant)
    except ValueError:
        if tile_variant != "flat":
            raise
    return "loop", kernel_config(k, d_pad, policy, l, n, variant="loop")


def gain_working_set(block_n: int, block_m: int, d_pad: int,
                     policy: PrecisionPolicy) -> int:
    """VMEM bytes the gain kernels hold at tile (Bn, Bm): the V and C
    tiles twice (the pipeline double-buffers them) and f32 columns at 128
    lanes, which is how VMEM holds a (B, 1) block: ten of Bn rows (the
    cache in and out, twice each, and the kernel's own columns: norms,
    the winner's distances, the folded cache) and three of Bm (the gains
    twice, and their row sum). The (Bn, Bm) distance tile takes no copy.
    Both match what a v5e compile of the fused kernel asks for at four
    requests, fp32, d_pad=128, to 0.125 MiB: 8.4 MiB at 1024×1024,
    14.5 MiB at 2048×1024 and 17.0 MiB at 2048×2048, of which the distance
    tile alone would be 16 MiB. At (1024, 2048) the count says 11.5 MiB
    and four requests ask for 9.96; one request asks for 6.93, so for
    B = 1 the count is a conservative cap.
    """
    return (2 * (block_n + block_m) * d_pad * policy.itemsize
            + (10 * block_n + 3 * block_m) * LANE * 4)


def gain_tile(n: int, m: int, d_pad: int,
              policy: PrecisionPolicy) -> tuple[int, int]:
    """(Bn, Bm) of the gain kernels for n ground-set rows and m candidates.

    Minimizes the time fitted on the chip (``GAIN_COST_S``: per distance
    n_pad·m_pad, per grid step, per ground-set row each candidate tile
    reads again and per candidate row each ground-set tile reads again)
    over the tile edges ``GAIN_TILES``, each clamped to the padded
    problem (``_round_up(n, 8)``, ``_round_up(m, 8)``), with the working
    set of :func:`gain_working_set` under the multiset plan's cap (3 ×
    ``VMEM_S_BUDGET``, 12 MiB; a v5e kernel may take 16). Larger tiles
    take fewer steps and re-read less, until the working set leaves
    VMEM. Ties go to the fewest steps. Where no tile fits (d in the
    thousands) the smallest is returned. Every gain kernel, for one
    request, for B and on a mesh shard, tiles by this one plan, so equal
    shapes score in one accumulation order.
    """
    bn_opts = sorted({min(b, _round_up(n, SUBLANE)) for b in GAIN_TILES})
    bm_opts = sorted({min(b, _round_up(m, SUBLANE)) for b in GAIN_TILES})
    per_dist, per_step, per_v_row, per_c_row = GAIN_COST_S
    best, best_key = (bn_opts[0], bm_opts[0]), None
    for bn in bn_opts:
        for bm in bm_opts:
            if gain_working_set(bn, bm, d_pad, policy) > 3 * VMEM_S_BUDGET:
                continue
            n_tiles, m_tiles = -(-n // bn), -(-m // bm)
            steps = n_tiles * m_tiles
            cost = (steps * (bn * bm * per_dist + per_step
                             + bn * per_v_row + bm * per_c_row))
            if best_key is None or (cost, steps) < best_key:
                best, best_key = (bn, bm), (cost, steps)
    return best


def _gain_blocks(n: int, m: int, d: int, policy: PrecisionPolicy,
                 block_n: Optional[int], block_m: Optional[int]
                 ) -> tuple[int, int]:
    """The gain kernels' (Bn, Bm): :func:`gain_tile`, or an explicit
    override clamped to the padded problem as the plan's tiles are."""
    plan = gain_tile(n, m, _round_up(d, LANE), policy)
    bn = plan[0] if block_n is None else min(block_n, _round_up(n, SUBLANE))
    bm = plan[1] if block_m is None else min(block_m, _round_up(m, SUBLANE))
    return bn, bm


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _pad_axis(x: jax.Array, target: int, axis: int,
              value: float = 0.0) -> jax.Array:
    pad = target - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _check_compiled_policy(policy: PrecisionPolicy, interpret: bool) -> None:
    if not interpret:
        check_kernel_policy(policy)


# ---------------------------------------------------------------------------
# exemplar_eval
# ---------------------------------------------------------------------------


@functools.partial(
    jax.jit,
    static_argnames=("policy", "mode", "variant", "interpret", "rbf_gamma",
                     "n_total", "cfgk"),
)
def _exemplar_eval_padded(V, S, lengths, d_e0, *, policy, mode, variant,
                          interpret, rbf_gamma, n_total, cfgk: KernelConfig):
    l = lengths.shape[0]
    k = S.shape[1]
    d_pad = _round_up(S.shape[2], LANE)
    bl, bn, bk = cfgk.block_l, cfgk.block_n, cfgk.block_k
    l_pad = _round_up(l, bl)
    n_pad = _round_up(V.shape[0], bn)

    # tiles reach VMEM at the compute dtype kernel_config sized them for;
    # zero slices pad k to whole chunks, and the length mask drops them
    cdt = policy.compute_dtype
    Vp = _pad_axis(_pad_axis(V.astype(cdt), n_pad, 0), d_pad, 1)
    Sp = _pad_axis(_pad_axis(S.astype(cdt), l_pad, 0), d_pad, 2)
    Sp = _pad_axis(Sp, _round_up(k, bk), 1)
    Sp = jnp.transpose(Sp, (1, 0, 2))  # k-major (paper's interleave)
    lens_p = _pad_axis(lengths.astype(jnp.int32), l_pad, 0)[:, None]
    e0_p = _pad_axis(d_e0.astype(jnp.float32), n_pad, 0)

    if mode == "fused":
        out = _ee.fused_eval(
            Vp, Sp, lens_p, e0_p[:, None], n_total=n_total, policy=policy,
            block_n=bn, block_l=bl, block_k=bk, variant=variant,
            rbf_gamma=rbf_gamma, interpret=interpret)
        return out[:l, 0]
    elif mode == "two_pass":
        W = _ee.two_pass_eval(
            Vp, Sp, lens_p, e0_p[None, :], n_total=n_total, policy=policy,
            block_n=bn, block_l=bl, rbf_gamma=rbf_gamma, interpret=interpret)
        # second pass: the paper's W·1 row reduction
        return jnp.sum(W, axis=1)[:l]
    raise ValueError(f"unknown mode {mode!r}")


@functools.partial(jax.profiler.annotate_function, name=tracing.EXEMPLAR_EVAL)
def exemplar_eval(
    V: jax.Array,
    S: jax.Array,            # (l, k, d)
    lengths: jax.Array,      # (l,)
    d_e0: jax.Array,         # (n,)
    *,
    policy: PrecisionPolicy = FP32,
    mode: str = "fused",
    variant: str = "flat",
    interpret: bool = False,
    memory_budget_bytes: Optional[int | str] = None,  # int | None | "auto"
    rbf_gamma: Optional[float] = None,
) -> jax.Array:
    """L(S_j ∪ {e0}) for the packed multiset — (l,) float32. The variant
    and tile come from :func:`multiset_tile`. The whole call, plan,
    slices and dispatch, is a host span (:mod:`repro.core.tracing`)."""
    _check_compiled_policy(policy, interpret)
    n, d = V.shape
    l, k, _ = S.shape
    d_pad = _round_up(d, LANE)
    variant, cfgk = multiset_tile(k, d_pad, policy, l, n, mode, variant)
    chunks = plan_chunks(l, n, k, d, policy, mode, memory_budget_bytes)
    outs = []
    for start, stop in chunks:
        outs.append(
            _exemplar_eval_padded(
                V, S[start:stop], lengths[start:stop], d_e0,
                policy=policy, mode=mode, variant=variant,
                interpret=interpret, rbf_gamma=rbf_gamma, n_total=n,
                cfgk=cfgk,
            )
        )
    return jnp.concatenate(outs) if len(outs) > 1 else outs[0]


# ---------------------------------------------------------------------------
# marginal_gain
# ---------------------------------------------------------------------------


def _pad_gain_operands(V, C, cache, block_n, block_m, cache_pad: float = 0.0):
    """Pad (B, …) V/C/cache to lane- and block-aligned shapes for the gain
    kernels. The batch axis is never padded here (bucket padding is the
    serving layer's job).

    ``cache_pad`` is the dead-row sentinel for the padded cache entries: 0
    under the min template (relu(0 − d) = 0 for d ≥ 0) and +inf under the
    max template (relu(s − inf) = 0 — a zero-padded V row has *positive*
    similarity to candidates, so only an infinite cache entry keeps pad rows
    inert).
    """
    d_pad = _round_up(V.shape[2], LANE)
    n_pad = _round_up(V.shape[1], block_n)
    m_pad = _round_up(C.shape[1], block_m)
    Vp = _pad_axis(_pad_axis(V, n_pad, 1), d_pad, 2)
    Cp = _pad_axis(_pad_axis(C, m_pad, 1), d_pad, 2)
    cache_p = _pad_axis(cache.astype(jnp.float32), n_pad, 1,
                        value=cache_pad)[:, :, None]
    return Vp, Cp, cache_p, d_pad


@functools.partial(
    jax.jit,
    static_argnames=("policy", "interpret", "rbf_gamma", "n_total",
                     "block_n", "block_m", "fold", "score_affine"),
)
def _marginal_gain_padded(V, C, cache, *, policy, interpret, rbf_gamma,
                          n_total, block_n, block_m, fold, score_affine):
    m = C.shape[1]
    Vp, Cp, cache_p, _ = _pad_gain_operands(
        V, C, cache, block_n, block_m,
        cache_pad=float("inf") if fold == "max" else 0.0)
    out = _mg.gain_eval(
        Vp, Cp, cache_p, n_total=n_total, policy=policy,
        block_n=block_n, block_m=block_m, rbf_gamma=rbf_gamma,
        fold=fold, affine=score_affine, interpret=interpret)
    return out[:, :m, 0]


def marginal_gain(
    V: jax.Array,
    C: jax.Array,
    mincache: jax.Array,
    *,
    policy: PrecisionPolicy = FP32,
    interpret: bool = False,
    rbf_gamma: Optional[float] = None,
    block_n: Optional[int] = None,
    block_m: Optional[int] = None,
    n_total: Optional[int] = None,
    fold: str = "min",
    score_affine: Optional[tuple] = None,
) -> jax.Array:
    """Δ(c_j | S) for all candidates — (m,) float32, or (B, m) for B
    requests.

    ``V (n, d)``, ``C (m, d)`` and ``mincache (n,)`` score one request;
    ``V (B, n, d)``, ``C (B, m, d)`` and ``mincache (B, n)`` score B
    independent requests in one launch. One request is the B = 1 case of
    the same kernel (:mod:`repro.kernels.marginal_gain`).

    ``n_total`` overrides the |V| normalizer: pass the *global* ground-set
    size when V is one row-shard of a mesh-sharded ground set, so per-shard
    partial gains ``psum`` to the exact global gains. Inside shard_map a
    (B, n_loc, d) V is B tenants' row shards, and the (B, m) output is that
    shard's exact partial of the round's single O(B·m) psum (the sharded
    plans in core/distributed.py).

    ``fold``/``score_affine`` select the kernel template (see
    :mod:`repro.kernels.marginal_gain`): the default ``"min"`` scores the
    exemplar min-distance cache; ``("max", (α, β))`` scores
    relu((α + β·d) − cache) against a max-similarity cache.

    The tile is :func:`gain_tile`'s for (n, m); ``block_n``/``block_m``
    override it.
    """
    _check_compiled_policy(policy, interpret)
    if V.ndim == 2:
        return marginal_gain(
            V[None], C[None], mincache[None], policy=policy,
            interpret=interpret, rbf_gamma=rbf_gamma, block_n=block_n,
            block_m=block_m, n_total=n_total, fold=fold,
            score_affine=score_affine)[0]
    n = V.shape[1]
    bn, bm = _gain_blocks(n, C.shape[1], V.shape[2], policy, block_n, block_m)
    return _marginal_gain_padded(
        V, C, mincache, policy=policy, interpret=interpret,
        rbf_gamma=rbf_gamma, n_total=n_total if n_total is not None else n,
        block_n=bn, block_m=bm, fold=fold,
        score_affine=None if score_affine is None else tuple(score_affine))


@functools.partial(
    jax.jit,
    static_argnames=("policy", "interpret", "rbf_gamma", "n_total",
                     "block_n", "block_m", "fold", "score_affine"),
)
def _fused_gain_update_padded(V, C, cache, winner, w_valid, *, policy,
                              interpret, rbf_gamma, n_total, block_n,
                              block_m, fold, score_affine):
    n, m = V.shape[1], C.shape[1]
    Vp, Cp, cache_p, d_pad = _pad_gain_operands(
        V, C, cache, block_n, block_m,
        cache_pad=float("inf") if fold == "max" else 0.0)
    w_p = _pad_axis(winner[:, None, :], d_pad, 2)
    wv = jnp.reshape(w_valid.astype(jnp.float32), (-1, 1, 1))
    gains, new_cache = _mg.gain_update_eval(
        Vp, Cp, cache_p, w_p, wv, n_total=n_total, policy=policy,
        block_n=block_n, block_m=block_m, rbf_gamma=rbf_gamma,
        fold=fold, affine=score_affine, interpret=interpret)
    return gains[:, :m, 0], new_cache[:, :n, 0]


def fused_gain_update(
    V: jax.Array,
    C: jax.Array,
    mincache: jax.Array,
    winner: jax.Array,       # (d,) previous round's winning candidate
    *,
    policy: PrecisionPolicy = FP32,
    interpret: bool = False,
    rbf_gamma: Optional[float] = None,
    block_n: Optional[int] = None,
    block_m: Optional[int] = None,
    n_total: Optional[int] = None,
    fold: str = "min",
    score_affine: Optional[tuple] = None,
    w_valid: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array]:
    """Fused greedy step (device engine): fold ``winner`` into the cache
    (min: cache ← min(cache, d(·, w)); max: cache ← max(cache, s(·, w))),
    then Δ(c_j | S) against the updated cache. Returns ``(gains, new_cache)``.

    ``n_total`` is the sharding-aware normalizer (see :func:`marginal_gain`):
    with V a row-shard, gains come back divided by the *global* n and the
    updated cache shard stays local — exactly the engine's psum contract.

    ``w_valid`` (traced, default 1) gates the fold: pass 0 on the round-0
    step where no previous winner exists. The min fold is idempotent
    against its own seed so exemplar callers may omit it, but the max fold
    is not — generic callers must gate.

    B requests: pass ``V (B, n, d)``, ``C (B, m, d)``, ``mincache (B, n)``,
    ``winner (B, d)`` and ``w_valid (B,)`` — one launch folds and scores
    all B; the per-request ``w_valid`` lane doubles as the ragged-k gate (a
    request past its effective k passes 0 and its cache stays frozen
    in-kernel). One request, 2-D, is the B = 1 case.

    The tile is :func:`gain_tile`'s for (n, m); ``block_n``/``block_m``
    override it.
    """
    _check_compiled_policy(policy, interpret)
    if V.ndim == 2:
        gains, new_cache = fused_gain_update(
            V[None], C[None], mincache[None], winner[None], policy=policy,
            interpret=interpret, rbf_gamma=rbf_gamma, block_n=block_n,
            block_m=block_m, n_total=n_total, fold=fold,
            score_affine=score_affine, w_valid=w_valid)
        return gains[0], new_cache[0]
    n = V.shape[1]
    bn, bm = _gain_blocks(n, C.shape[1], V.shape[2], policy, block_n, block_m)
    if w_valid is None:
        w_valid = jnp.ones((V.shape[0],), jnp.float32)
    return _fused_gain_update_padded(
        V, C, mincache, winner, w_valid, policy=policy, interpret=interpret,
        rbf_gamma=rbf_gamma, n_total=n_total if n_total is not None else n,
        block_n=bn, block_m=bm, fold=fold,
        score_affine=None if score_affine is None else tuple(score_affine))


# ---------------------------------------------------------------------------
# sieve_gain — streaming sieve engine's fused table × element scoring
# ---------------------------------------------------------------------------


def sieve_gains(
    table: jax.Array,      # (r, n) float32 per-element cache rows
    dvec: jax.Array,       # (n,) float32 one element's distances to V
    *,
    n_total: Optional[int] = None,
    interpret: bool = False,
    block_s: int = 64,
    block_n: int = 512,
    fold: str = "min",
    score_affine: Optional[tuple] = None,
) -> jax.Array:
    """Per-row gains of a cache table vs one stream element — (r,), or
    (P, r) for P partition tables ``(P, r, n)`` scored against P elements
    ``(P, n)`` in one launch (one table is the P = 1 case).

    min template (default): row r gets
    ``n_total⁻¹ Σ_i relu(table[r, i] − dvec[i])``; max template
    (``fold="max"``, ``score_affine=(α, β)``):
    ``n_total⁻¹ Σ_i relu((α + β·dvec[i]) − table[r, i])``. Row = a sieve's
    cache → its marginal gain Δ(e | S_r); row = the seed → the singleton
    gain Δ(e | ∅). Unlike the jnp scan body, the (r, n) intermediate never
    reaches HBM. NOT jit-wrapped: the streaming engine traces it inside its
    per-block scan (and the host mirror inside the per-element step), so a
    wrapper jit would only add dispatch layers.

    Column padding matches the template: zeros under min (relu(0 − d) = 0),
    +inf under max for BOTH operands — a zero-padded dvec column would score
    relu(α − t) > 0 against finite rows, while a +inf column drives the
    affine to −inf before the relu.
    """
    if table.ndim == 2:
        return sieve_gains(
            table[None], dvec[None], n_total=n_total, interpret=interpret,
            block_s=block_s, block_n=block_n, fold=fold,
            score_affine=score_affine)[0]
    _, r, n = table.shape
    bs = min(block_s, _round_up(r, SUBLANE))
    bn = min(block_n, _round_up(n, LANE))
    pad = float("inf") if fold == "max" else 0.0
    Tp = _pad_axis(
        _pad_axis(table.astype(jnp.float32), _round_up(r, bs), 1, value=pad),
        _round_up(n, bn), 2, value=pad)
    dp = _pad_axis(dvec.astype(jnp.float32), _round_up(n, bn), 1,
                   value=pad)[:, None, :]
    out = _mg.sieve_gain_eval(
        Tp, dp, n_total=n_total if n_total is not None else n,
        block_s=bs, block_n=bn, fold=fold,
        affine=None if score_affine is None else tuple(score_affine),
        interpret=interpret)
    return out[:, :r, 0]
