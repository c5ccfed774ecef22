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
    """Pad V/C/cache to lane- and block-aligned shapes for the gain kernels.

    ``cache_pad`` is the dead-row sentinel for the padded cache entries: 0
    under the min template (relu(0 − d) = 0 for d ≥ 0) and +inf under the
    max template (relu(s − inf) = 0 — a zero-padded V row has *positive*
    similarity to candidates, so only an infinite cache entry keeps pad rows
    inert).
    """
    d_pad = _round_up(V.shape[1], LANE)
    n_pad = _round_up(V.shape[0], block_n)
    m_pad = _round_up(C.shape[0], block_m)
    Vp = _pad_axis(_pad_axis(V, n_pad, 0), d_pad, 1)
    Cp = _pad_axis(_pad_axis(C, m_pad, 0), d_pad, 1)
    cache_p = _pad_axis(cache.astype(jnp.float32), n_pad, 0,
                        value=cache_pad)[:, None]
    return Vp, Cp, cache_p, d_pad


def _pad_gain_operands_batched(V, C, cache, block_n, block_m,
                               cache_pad: float = 0.0):
    """Batched (B-leading) analogue of :func:`_pad_gain_operands` — pads the
    row/candidate/feature axes; the batch axis is never padded here (bucket
    padding is the serving layer's job)."""
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
    m = C.shape[0]
    Vp, Cp, cache_p, _ = _pad_gain_operands(
        V, C, cache, block_n, block_m,
        cache_pad=float("inf") if fold == "max" else 0.0)
    out = _mg.gain_eval(
        Vp, Cp, cache_p, n_total=n_total, policy=policy,
        block_n=block_n, block_m=block_m, rbf_gamma=rbf_gamma,
        fold=fold, affine=score_affine, interpret=interpret)
    return out[:m, 0]


def marginal_gain(
    V: jax.Array,
    C: jax.Array,
    mincache: jax.Array,
    *,
    policy: PrecisionPolicy = FP32,
    interpret: bool = False,
    rbf_gamma: Optional[float] = None,
    block_n: int = 256,
    block_m: int = 256,
    n_total: Optional[int] = None,
    fold: str = "min",
    score_affine: Optional[tuple] = None,
) -> jax.Array:
    """Δ(c_j | S) for all candidates — (m,) float32.

    ``n_total`` overrides the |V| normalizer: pass the *global* ground-set
    size when V is one row-shard of a mesh-sharded ground set, so per-shard
    partial gains ``psum`` to the exact global gains. The two axes compose:
    a 3-D ``V`` of shape (B, n_loc, d) inside shard_map is B tenants' row
    shards scored by ONE grid-over-(B, m_tiles, local-n_tiles) launch whose
    (B, m) output is that shard's exact partial of the round's single
    O(B·m) psum (the batched-sharded plans in core/distributed.py).

    ``fold``/``score_affine`` select the kernel template (see
    :mod:`repro.kernels.marginal_gain`): the default ``"min"`` scores the
    exemplar min-distance cache; ``("max", (α, β))`` scores
    relu((α + β·d) − cache) against a max-similarity cache.

    Batched dispatch: pass ``V (B, n, d)``, ``C (B, m, d)``, and
    ``mincache (B, n)`` and the call routes to the grid-over-B kernel —
    one launch scores all B requests with per-request block shapes identical
    to the unbatched path (bit-compatible per-request gains).
    """
    _check_compiled_policy(policy, interpret)
    if V.ndim == 3:
        n = V.shape[1]
        bn = min(block_n, _round_up(n, SUBLANE))
        bm = min(block_m, _round_up(C.shape[1], SUBLANE))
        return _marginal_gain_padded_batched(
            V, C, mincache, policy=policy, interpret=interpret,
            rbf_gamma=rbf_gamma, n_total=n_total if n_total is not None else n,
            block_n=bn, block_m=bm, fold=fold,
            score_affine=None if score_affine is None else tuple(score_affine))
    n = V.shape[0]
    bn = min(block_n, _round_up(n, SUBLANE))
    bm = min(block_m, _round_up(C.shape[0], SUBLANE))
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
def _marginal_gain_padded_batched(V, C, cache, *, policy, interpret,
                                  rbf_gamma, n_total, block_n, block_m,
                                  fold, score_affine):
    m = C.shape[1]
    Vp, Cp, cache_p, _ = _pad_gain_operands_batched(
        V, C, cache, block_n, block_m,
        cache_pad=float("inf") if fold == "max" else 0.0)
    out = _mg.gain_eval_batched(
        Vp, Cp, cache_p, n_total=n_total, policy=policy,
        block_n=block_n, block_m=block_m, rbf_gamma=rbf_gamma,
        fold=fold, affine=score_affine, interpret=interpret)
    return out[:, :m, 0]


@functools.partial(
    jax.jit,
    static_argnames=("policy", "interpret", "rbf_gamma", "n_total",
                     "block_n", "block_m", "fold", "score_affine"),
)
def _fused_gain_update_padded(V, C, cache, winner, w_valid, *, policy,
                              interpret, rbf_gamma, n_total, block_n,
                              block_m, fold, score_affine):
    n, m = V.shape[0], C.shape[0]
    Vp, Cp, cache_p, d_pad = _pad_gain_operands(
        V, C, cache, block_n, block_m,
        cache_pad=float("inf") if fold == "max" else 0.0)
    w_p = _pad_axis(winner[None, :], d_pad, 1)
    wv = jnp.reshape(w_valid.astype(jnp.float32), (1, 1))
    gains, new_cache = _mg.gain_update_eval(
        Vp, Cp, cache_p, w_p, wv, n_total=n_total, policy=policy,
        block_n=block_n, block_m=block_m, rbf_gamma=rbf_gamma,
        fold=fold, affine=score_affine, interpret=interpret)
    return gains[:m, 0], new_cache[:n, 0]


def fused_gain_update(
    V: jax.Array,
    C: jax.Array,
    mincache: jax.Array,
    winner: jax.Array,       # (d,) previous round's winning candidate
    *,
    policy: PrecisionPolicy = FP32,
    interpret: bool = False,
    rbf_gamma: Optional[float] = None,
    block_n: int = 256,
    block_m: int = 256,
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

    ``w_valid`` (traced scalar, default 1) gates the fold: pass 0 on the
    round-0 step where no previous winner exists. The min fold is idempotent
    against its own seed so exemplar callers may omit it, but the max fold
    is not — generic callers must gate.

    Batched dispatch: pass ``V (B, n, d)``, ``C (B, m, d)``,
    ``mincache (B, n)``, ``winner (B, d)``, and ``w_valid (B,)`` — one
    launch folds+scores all B requests; the per-request ``w_valid`` lane
    doubles as the ragged-k gate (a request past its effective k passes 0
    and its cache stays frozen in-kernel).
    """
    _check_compiled_policy(policy, interpret)
    if V.ndim == 3:
        n = V.shape[1]
        bn = min(block_n, _round_up(n, SUBLANE))
        bm = min(block_m, _round_up(C.shape[1], SUBLANE))
        if w_valid is None:
            w_valid = jnp.ones((V.shape[0],), jnp.float32)
        return _fused_gain_update_padded_batched(
            V, C, mincache, winner, w_valid, policy=policy,
            interpret=interpret, rbf_gamma=rbf_gamma,
            n_total=n_total if n_total is not None else n,
            block_n=bn, block_m=bm, fold=fold,
            score_affine=None if score_affine is None else tuple(score_affine))
    n = V.shape[0]
    bn = min(block_n, _round_up(n, SUBLANE))
    bm = min(block_m, _round_up(C.shape[0], SUBLANE))
    if w_valid is None:
        w_valid = jnp.float32(1.0)
    return _fused_gain_update_padded(
        V, C, mincache, winner, w_valid, policy=policy, interpret=interpret,
        rbf_gamma=rbf_gamma, n_total=n_total if n_total is not None else n,
        block_n=bn, block_m=bm, fold=fold,
        score_affine=None if score_affine is None else tuple(score_affine))


@functools.partial(
    jax.jit,
    static_argnames=("policy", "interpret", "rbf_gamma", "n_total",
                     "block_n", "block_m", "fold", "score_affine"),
)
def _fused_gain_update_padded_batched(V, C, cache, winner, w_valid, *,
                                      policy, interpret, rbf_gamma, n_total,
                                      block_n, block_m, fold, score_affine):
    n, m = V.shape[1], C.shape[1]
    Vp, Cp, cache_p, d_pad = _pad_gain_operands_batched(
        V, C, cache, block_n, block_m,
        cache_pad=float("inf") if fold == "max" else 0.0)
    w_p = _pad_axis(winner[:, None, :], d_pad, 2)
    wv = jnp.reshape(w_valid.astype(jnp.float32), (-1, 1, 1))
    gains, new_cache = _mg.gain_update_eval_batched(
        Vp, Cp, cache_p, w_p, wv, n_total=n_total, policy=policy,
        block_n=block_n, block_m=block_m, rbf_gamma=rbf_gamma,
        fold=fold, affine=score_affine, interpret=interpret)
    return gains[:, :m, 0], new_cache[:, :n, 0]


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
    """Per-row gains of a cache table vs one stream element — (r,).

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
    r, n = table.shape
    bs = min(block_s, _round_up(r, SUBLANE))
    bn = min(block_n, _round_up(n, LANE))
    pad = float("inf") if fold == "max" else 0.0
    Tp = _pad_axis(
        _pad_axis(table.astype(jnp.float32), _round_up(r, bs), 0, value=pad),
        _round_up(n, bn), 1, value=pad)
    dp = _pad_axis(dvec.astype(jnp.float32), _round_up(n, bn), 0,
                   value=pad)[None, :]
    out = _mg.sieve_gain_eval(
        Tp, dp, n_total=n_total if n_total is not None else n,
        block_s=bs, block_n=bn, fold=fold,
        affine=None if score_affine is None else tuple(score_affine),
        interpret=interpret)
    return out[:r, 0]


def sieve_gains_batched(
    tables: jax.Array,     # (P, r, n) float32 per-partition cache rows
    dvecs: jax.Array,      # (P, n) float32 per-partition element distances
    *,
    n_total: Optional[int] = None,
    interpret: bool = False,
    block_s: int = 64,
    block_n: int = 512,
    fold: str = "min",
    score_affine: Optional[tuple] = None,
) -> jax.Array:
    """Batched :func:`sieve_gains` — P partition tables scored against P
    stream elements in ONE grid-over-P kernel launch; returns (P, r).

    Tile sizes, padding sentinels, and per-partition accumulation order
    match the unbatched wrapper exactly, so each partition's gains are
    bit-identical to its own :func:`sieve_gains` call — the invariant the
    batched multi-stream sieve engine's parity rests on. Like the unbatched
    wrapper it is NOT jit-wrapped (it traces inside the batched per-block
    scan).
    """
    P, r, n = tables.shape
    bs = min(block_s, _round_up(r, SUBLANE))
    bn = min(block_n, _round_up(n, LANE))
    pad = float("inf") if fold == "max" else 0.0
    Tp = _pad_axis(
        _pad_axis(tables.astype(jnp.float32), _round_up(r, bs), 1,
                  value=pad),
        _round_up(n, bn), 2, value=pad)
    dp = _pad_axis(dvecs.astype(jnp.float32), _round_up(n, bn), 1,
                   value=pad)[:, None, :]
    out = _mg.sieve_gain_eval_batched(
        Tp, dp, n_total=n_total if n_total is not None else n,
        block_s=bs, block_n=bn, fold=fold,
        affine=None if score_affine is None else tuple(score_affine),
        interpret=interpret)
    return out[:, :r, 0]
