"""Pallas TPU kernels for optimizer-aware greedy marginal gains (beyond paper).

For Greedy, every candidate set shares the base S, so with a per-element
cache the marginal gain collapses to one (n × m) distance matrix (a single
Gram matmul) + a ReLU/sum epilogue, fused here so the distance matrix never
reaches HBM. Grid ``(m_tiles, n_tiles)`` with n innermost, accumulating into
the (Bm, 1) output block.

ONE kernel template serves the whole function zoo (see
:func:`repro.core.functions.kernel_template`), parameterized by the fold
direction and an in-tile affine of the distance:

* ``fold="min"`` — the exemplar min-distance cache
  ``m_i = min_{s∈S∪{e0}} d(v_i, s)``:

      Δ(c_j | S) = |V|⁻¹ Σ_i relu(m_i − d(v_i, c_j))

* ``fold="max"`` + ``affine=(α, β)`` — the max-similarity dual (facility
  location's cache; graph cut scores through it against a static baseline):

      Δ(c_j | S) = |V|⁻¹ Σ_i relu((α + β·d(v_i, c_j)) − c_i)

  The similarity s = relu(α + β·d) needs no inner relu in-tile: the cache is
  ≥ 0, so relu(relu(x) − c) ≡ relu(x − c). Padding rows carry +inf cache
  sentinels (relu(s − inf) = 0) — see ``_pad_gain_operands`` in
  :mod:`repro.kernels.ops`.

Two gain kernels:

* :func:`gain_eval` — gains against a given cache (one greedy round's scoring).
* :func:`gain_update_eval` — the fused *gain + cache-update* step used by the
  device-resident greedy engine. The previous round's winner ``w`` rides along
  as an extra (1, d) operand; the epilogue recomputes ``d(v_i, w)`` in-tile,
  folds it into the cache (min: ``m_i ← min(m_i, d_iw)``; max:
  ``c_i ← max(c_i, relu(α + β·d_iw))``) and scores the current round's gains
  against the *updated* cache — so the winner's distance column never
  re-materializes in HBM (only the (n,) cache itself, which is required
  state, is written back). A (1, 1) ``w_valid`` operand gates the fold:
  round 0 has no previous winner, and unlike the idempotent min fold the max
  fold must NOT re-apply a seed row.

Both gain kernels also come in a *batched* variant (:func:`gain_eval_batched`,
:func:`gain_update_eval_batched`) whose grid grows a leading axis over B
independent requests — ``(B, m_tiles, n_tiles)`` — so a multi-tenant bucket
amortizes ONE kernel launch. Per-request tile partitioning, block shapes, and
accumulation order are identical to the unbatched kernels, which keeps batched
selections bit-compatible with the unbatched engine; ragged-k masking rides in
the per-request ``w_valid`` gate, so padded requests never fold.

A third kernel serves the streaming sieve engine:

* :func:`sieve_gain_eval` — the fused gain of a whole sieve cache *table*
  against one stream element's distance row: for every table row r,
  ``|V|⁻¹ Σ_i relu(T[r, i] − dvec[i])`` (min) or
  ``|V|⁻¹ Σ_i relu((α + β·dvec[i]) − T[r, i])`` (max). The (S, n)
  intermediate the jnp scan body materializes per element never exists;
  table tiles stream past the resident (Bs, 1) accumulator exactly like
  :func:`gain_eval` streams V tiles past the gain block. No matmul (the
  distances are already computed) — this is a VPU reduction kernel, fused
  for HBM traffic.

All kernels normalize by an explicit ``n_total`` rather than ``V.shape[0]``:
passed the *global* ground-set size, they are callable on one row-shard of a
mesh-sharded V (cache sharded alongside), and the per-shard outputs are exact
gain partials that an O(m) ``psum`` turns into the global gains — the
contract the ``device_sharded`` execution plan in :mod:`repro.core.engine`
builds on.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.precision import PrecisionPolicy
from repro.kernels.exemplar_eval import _dist_tile


def _score_tile(cache, d2, n_total: int, fold: str, affine):
    """Scoring epilogue shared by the gain kernels.

    min: |V|⁻¹ Σ relu(m_i − d_ij) — the relu runs in the distance dtype
    (matches ref.marginal_gain_ref). max: |V|⁻¹ Σ relu((α + β·d_ij) − c_i)
    — the affine runs in the distance dtype, the subtraction against the
    float32 cache in float32 (matches the jnp promotion in
    ``functions.gains_rows``). Accumulation is always float32.
    """
    if fold == "min":
        g = jnp.maximum(cache.astype(d2.dtype) - d2, 0.0)
    else:
        a, b = affine
        g = jnp.maximum((a + b * d2) - cache.astype(jnp.float32), 0.0)
    return jnp.sum(g.astype(jnp.float32), axis=0) / n_total


def _fold_tile(cache, dw, fold: str, affine):
    """Winner fold of a float32 cache tile against the winner's distance
    column ``dw`` (computed in-tile at policy precision)."""
    if fold == "min":
        return jnp.minimum(cache, dw.astype(jnp.float32))
    a, b = affine
    return jnp.maximum(cache, jnp.maximum(a + b * dw.astype(jnp.float32), 0.0))


def _winner_dist(v, w, policy: PrecisionPolicy, rbf_gamma):
    """(Bn, d)×(1, d) → (Bn, 1) distances to the previous winner.

    The winner row is tiled to one sublane group (8 rows) so the contraction
    lowers to the same MXU matmul as the candidate tile. A lone row lowers
    to a broadcast-multiply instead, whose half-precision → float32 widening
    Mosaic rejects (``vector.broadcast`` element-type mismatch at bf16).
    """
    w8 = jnp.broadcast_to(w, (8, w.shape[1]))
    return _dist_tile(v, w8, policy, rbf_gamma)[:, :1]


def _gain_kernel(v_ref, c_ref, cache_ref, out_ref, *,
                 n_total: int, policy: PrecisionPolicy, rbf_gamma,
                 fold: str, affine):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    v = v_ref[...].astype(policy.compute_dtype)      # (Bn, d)
    c = c_ref[...].astype(policy.compute_dtype)      # (Bm, d)
    d2 = _dist_tile(v, c, policy, rbf_gamma)         # (Bn, Bm)
    partial = _score_tile(cache_ref[...], d2, n_total, fold, affine)
    out_ref[...] += partial[:, None]


def gain_eval(
    V: jax.Array,          # (n_pad, d_pad)
    C: jax.Array,          # (m_pad, d_pad)
    cache: jax.Array,      # (n_pad, 1) float32 (transformed if rbf)
    *,
    n_total: int,
    policy: PrecisionPolicy,
    block_n: int,
    block_m: int,
    rbf_gamma: Optional[float] = None,
    fold: str = "min",
    affine: Optional[tuple] = None,
    interpret: bool = False,
) -> jax.Array:
    """Returns (m_pad, 1) float32 marginal gains."""
    n_pad, d_pad = V.shape
    m_pad = C.shape[0]
    grid = (m_pad // block_m, n_pad // block_n)
    kern = functools.partial(
        _gain_kernel, n_total=n_total, policy=policy, rbf_gamma=rbf_gamma,
        fold=fold, affine=affine)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d_pad), lambda i, j: (j, 0)),
            pl.BlockSpec((block_m, d_pad), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m_pad, 1), jnp.float32),
        interpret=interpret,
        name="gain_eval",
    )(V, C, cache)


def _gain_update_kernel(v_ref, c_ref, cache_ref, w_ref, wv_ref,
                        gain_ref, cache_out_ref,
                        *, n_total: int, policy: PrecisionPolicy, rbf_gamma,
                        fold: str, affine):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        gain_ref[...] = jnp.zeros_like(gain_ref)

    v = v_ref[...].astype(policy.compute_dtype)      # (Bn, d)
    w = w_ref[...].astype(policy.compute_dtype)      # (1, d) previous winner
    cache = cache_ref[...].astype(jnp.float32)       # (Bn, 1)
    dw = _winner_dist(v, w, policy, rbf_gamma)       # (Bn, 1)
    # w_valid gates the fold (round 0 has no winner; the max fold is not
    # idempotent, so an ungated seed row would corrupt the cache)
    new_cache = jnp.where(wv_ref[0, 0] > 0,
                          _fold_tile(cache, dw, fold, affine), cache)
    cache_out_ref[...] = new_cache                   # idempotent across m tiles

    c = c_ref[...].astype(policy.compute_dtype)      # (Bm, d)
    d2 = _dist_tile(v, c, policy, rbf_gamma)         # (Bn, Bm)
    partial = _score_tile(new_cache, d2, n_total, fold, affine)
    gain_ref[...] += partial[:, None]


def gain_update_eval(
    V: jax.Array,          # (n_pad, d_pad)
    C: jax.Array,          # (m_pad, d_pad)
    cache: jax.Array,      # (n_pad, 1) float32 — cache *before* the winner
    winner: jax.Array,     # (1, d_pad) — previous round's winning candidate
    w_valid: jax.Array,    # (1, 1) float32 — 0 disables the fold (round 0)
    *,
    n_total: int,
    policy: PrecisionPolicy,
    block_n: int,
    block_m: int,
    rbf_gamma: Optional[float] = None,
    fold: str = "min",
    affine: Optional[tuple] = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused greedy step: fold ``winner`` into the cache, score all candidates.

    Returns ``(gains (m_pad, 1), new_cache (n_pad, 1))`` — both float32.
    """
    n_pad, d_pad = V.shape
    m_pad = C.shape[0]
    grid = (m_pad // block_m, n_pad // block_n)
    kern = functools.partial(
        _gain_update_kernel, n_total=n_total, policy=policy,
        rbf_gamma=rbf_gamma, fold=fold, affine=affine)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d_pad), lambda i, j: (j, 0)),
            pl.BlockSpec((block_m, d_pad), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((1, d_pad), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((block_m, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j: (j, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((m_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        ),
        interpret=interpret,
        name="gain_update_eval",
    )(V, C, cache, winner, w_valid)


def _gain_kernel_batched(v_ref, c_ref, cache_ref, out_ref, *,
                         n_total: int, policy: PrecisionPolicy, rbf_gamma,
                         fold: str, affine):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    v = v_ref[0].astype(policy.compute_dtype)        # (Bn, d)
    c = c_ref[0].astype(policy.compute_dtype)        # (Bm, d)
    d2 = _dist_tile(v, c, policy, rbf_gamma)         # (Bn, Bm)
    partial = _score_tile(cache_ref[0], d2, n_total, fold, affine)
    out_ref[...] += partial[None, :, None]


def gain_eval_batched(
    V: jax.Array,          # (B, n_pad, d_pad)
    C: jax.Array,          # (B, m_pad, d_pad)
    cache: jax.Array,      # (B, n_pad, 1) float32
    *,
    n_total: int,
    policy: PrecisionPolicy,
    block_n: int,
    block_m: int,
    rbf_gamma: Optional[float] = None,
    fold: str = "min",
    affine: Optional[tuple] = None,
    interpret: bool = False,
) -> jax.Array:
    """Batched :func:`gain_eval` — B independent requests, ONE kernel launch.

    The grid grows a leading batch axis: ``(B, m_tiles, n_tiles)`` with n
    still innermost, so each (b, i) output block accumulates over its own
    request's V tiles exactly as the unbatched kernel does — per-request tile
    partitioning and accumulation order are identical, which is what makes
    batched selections bit-compatible with the unbatched engine. Returns
    (B, m_pad, 1) float32 gains.

    Under the batched-sharded plans this runs INSIDE shard_map on each
    device's (B, n_loc, d) row shard: the grid is (B, m_tiles,
    local-n_tiles), ``n_total`` stays the GLOBAL ground-set size so each
    shard's normalized gain tile is an exact psum partial (zero-padded rows
    score exact-zero partials), and the per-shard outputs stack into the
    round's single O(B·m) collective — same template as the unbatched
    sharded path, with the batch axis riding the grid and the payload.
    """
    B, n_pad, d_pad = V.shape
    m_pad = C.shape[1]
    grid = (B, m_pad // block_m, n_pad // block_n)
    kern = functools.partial(
        _gain_kernel_batched, n_total=n_total, policy=policy,
        rbf_gamma=rbf_gamma, fold=fold, affine=affine)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n, d_pad), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_m, d_pad), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_n, 1), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_m, 1), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, m_pad, 1), jnp.float32),
        interpret=interpret,
        name="gain_eval_batched",
    )(V, C, cache)


def _gain_update_kernel_batched(v_ref, c_ref, cache_ref, w_ref, wv_ref,
                                gain_ref, cache_out_ref,
                                *, n_total: int, policy: PrecisionPolicy,
                                rbf_gamma, fold: str, affine):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        gain_ref[...] = jnp.zeros_like(gain_ref)

    v = v_ref[0].astype(policy.compute_dtype)        # (Bn, d)
    w = w_ref[0].astype(policy.compute_dtype)        # (1, d) request's winner
    cache = cache_ref[0].astype(jnp.float32)         # (Bn, 1)
    dw = _winner_dist(v, w, policy, rbf_gamma)       # (Bn, 1)
    # per-request w_valid gate: requests whose previous round was masked
    # (ragged k) or round 0 must not fold
    new_cache = jnp.where(wv_ref[0, 0, 0] > 0,
                          _fold_tile(cache, dw, fold, affine), cache)
    cache_out_ref[...] = new_cache[None]             # idempotent across m tiles

    c = c_ref[0].astype(policy.compute_dtype)        # (Bm, d)
    d2 = _dist_tile(v, c, policy, rbf_gamma)         # (Bn, Bm)
    partial = _score_tile(new_cache, d2, n_total, fold, affine)
    gain_ref[...] += partial[None, :, None]


def gain_update_eval_batched(
    V: jax.Array,          # (B, n_pad, d_pad)
    C: jax.Array,          # (B, m_pad, d_pad)
    cache: jax.Array,      # (B, n_pad, 1) float32 — caches *before* winners
    winner: jax.Array,     # (B, 1, d_pad) — per-request previous winner
    w_valid: jax.Array,    # (B, 1, 1) float32 — per-request fold gate
    *,
    n_total: int,
    policy: PrecisionPolicy,
    block_n: int,
    block_m: int,
    rbf_gamma: Optional[float] = None,
    fold: str = "min",
    affine: Optional[tuple] = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Batched :func:`gain_update_eval`: per-request fold + score, one launch.

    Every request carries its own winner row and its own ``w_valid`` gate
    (round 0, and rounds past a request's effective k under ragged-k
    masking, pass 0 so the fold is a no-op for that request only). Returns
    ``(gains (B, m_pad, 1), new_cache (B, n_pad, 1))``.
    """
    B, n_pad, d_pad = V.shape
    m_pad = C.shape[1]
    grid = (B, m_pad // block_m, n_pad // block_n)
    kern = functools.partial(
        _gain_update_kernel_batched, n_total=n_total, policy=policy,
        rbf_gamma=rbf_gamma, fold=fold, affine=affine)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_n, d_pad), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_m, d_pad), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_n, 1), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, 1, d_pad), lambda b, i, j: (b, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda b, i, j: (b, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, block_m, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_n, 1), lambda b, i, j: (b, j, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((B, m_pad, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, n_pad, 1), jnp.float32),
        ),
        interpret=interpret,
        name="gain_update_eval_batched",
    )(V, C, cache, winner, w_valid)


def _sieve_gain_kernel(t_ref, dvec_ref, out_ref, *, n_total: int,
                       fold: str, affine):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    t = t_ref[...].astype(jnp.float32)               # (Bs, Bn) cache rows
    dv = dvec_ref[...].astype(jnp.float32)           # (1, Bn) element row
    if fold == "min":
        g = jnp.maximum(t - dv, 0.0)
    else:
        a, b = affine
        g = jnp.maximum((a + b * dv) - t, 0.0)
    out_ref[...] += (jnp.sum(g, axis=1) / n_total)[:, None]


def sieve_gain_eval(
    T: jax.Array,          # (s_pad, n_pad) float32 cache-table rows
    dvec: jax.Array,       # (1, n_pad) float32 distance row of one element
    *,
    n_total: int,
    block_s: int,
    block_n: int,
    fold: str = "min",
    affine: Optional[tuple] = None,
    interpret: bool = False,
) -> jax.Array:
    """Returns (s_pad, 1) float32 per-row gains.

    Rows are arbitrary per-element caches (live sieves, stale slots, or the
    seed empty-set cache whose gain is the singleton Δ(e | ∅)); callers mask
    rows downstream. Padding contributes exactly 0 in both directions: the
    min template zero-pads rows/columns (``relu(0 − d) = 0`` for d ≥ 0), the
    max template pads them +inf (``relu(s − inf) = 0``, and a +inf dvec
    column drives the affine to −inf before the relu) — :func:`ops.sieve_gains`
    applies the matching sentinel.
    """
    s_pad, n_pad = T.shape
    grid = (s_pad // block_s, n_pad // block_n)
    kern = functools.partial(_sieve_gain_kernel, n_total=n_total,
                             fold=fold, affine=affine)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_s, block_n), lambda i, j: (i, j)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_s, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((s_pad, 1), jnp.float32),
        interpret=interpret,
        name="sieve_gain_eval",
    )(T, dvec)


def _sieve_gain_kernel_batched(t_ref, dvec_ref, out_ref, *, n_total: int,
                               fold: str, affine):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    t = t_ref[0].astype(jnp.float32)                 # (Bs, Bn) cache rows
    dv = dvec_ref[0].astype(jnp.float32)             # (1, Bn) element row
    if fold == "min":
        g = jnp.maximum(t - dv, 0.0)
    else:
        a, b = affine
        g = jnp.maximum((a + b * dv) - t, 0.0)
    out_ref[...] += (jnp.sum(g, axis=1) / n_total)[None, :, None]


def sieve_gain_eval_batched(
    T: jax.Array,          # (P, s_pad, n_pad) float32 per-partition tables
    dvec: jax.Array,       # (P, 1, n_pad) float32 per-partition element rows
    *,
    n_total: int,
    block_s: int,
    block_n: int,
    fold: str = "min",
    affine: Optional[tuple] = None,
    interpret: bool = False,
) -> jax.Array:
    """Batched :func:`sieve_gain_eval` — P partition tables, ONE launch.

    The grid grows a leading partition axis ``(P, s_tiles, n_tiles)``
    mirroring :func:`gain_eval_batched`: each (p, i) output block
    accumulates over its own partition's n tiles in the same order as the
    unbatched kernel, so per-partition gains are bit-identical to P separate
    :func:`sieve_gain_eval` calls. Returns (P, s_pad, 1) float32.
    """
    P, s_pad, n_pad = T.shape
    grid = (P, s_pad // block_s, n_pad // block_n)
    kern = functools.partial(_sieve_gain_kernel_batched, n_total=n_total,
                             fold=fold, affine=affine)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_s, block_n), lambda p, i, j: (p, i, j)),
            pl.BlockSpec((1, 1, block_n), lambda p, i, j: (p, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, block_s, 1), lambda p, i, j: (p, i, 0)),
        out_shape=jax.ShapeDtypeStruct((P, s_pad, 1), jnp.float32),
        interpret=interpret,
        name="sieve_gain_eval_batched",
    )(T, dvec)
