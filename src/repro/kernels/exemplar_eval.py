"""Pallas TPU kernel for multiset exemplar-clustering evaluation.

This is the TPU-native version of the paper's GPU kernel (Algorithm 3):

* The CUDA kernel assigns one *thread* per work-matrix cell ``W[j,i]``; here
  one *grid step* computes a ``(Bl × Bn)`` tile of cells.
* Shared-memory staging of ``v_i`` becomes a ``BlockSpec``-driven HBM→VMEM
  copy of a ``(Bn, d)`` tile of V (double-buffered by the Pallas pipeline).
* The per-thread scalar loop ``min over s ∈ S_j`` becomes, per k-step, an MXU
  contraction ``(Bn, d) · (d, Bl)`` through the Gram identity
  ``‖v−s‖² = ‖v‖² + ‖s‖² − 2⟨v,s⟩`` — see DESIGN.md §2.

Every kernel reads S pre-transposed to ``(k, l, d)`` ("k-major"), the TPU
analogue of the paper's round-robin interleave (§IV-B-2): vector *lanes* hold
consecutive sets for a fixed k, and the k-th slice of an S tile is a
leading-axis index. Two compute orders (kernel *variants*) share one tile
body, which contracts a ``(Bn, d)`` V tile with a chunk of ``kc`` slices in
a single ``(Bn, d)·(d, kc·Bl)`` matmul, splits the result into a
``(Bn, kc, Bl)`` tile and takes a sublane min over kc:

* ``flat`` — the chunk is all k slices: one grid step per (set, vector)
  tile, on a ``(l_tiles, n_tiles)`` grid.
* ``loop`` — k is tiled across a third, innermost grid axis in chunks of
  ``kc`` slices (k zero-padded to a multiple of kc; the length mask drops
  the pad), and a ``(Bn, Bl)`` VMEM scratch carries the running min from
  chunk to chunk (the paper's per-set loop over k, a chunk at a time). The
  set tile stays lane-dense (Bl ≥ 128) at any k: the S block is
  ``(kc, Bl, d)``, not all k slices, and the V block does not change
  across the chunks, so the pipeline copies it once per (set, vector)
  tile.

Two reduction modes:

* ``fused`` (beyond paper) — the row-sum over n is accumulated across grid
  steps directly in the output block; W never reaches HBM.
* ``two_pass`` (paper-faithful) — W tiles are written to HBM and reduced by a
  second pass, exactly like the paper's ``W·1`` GEMV. Its kernel loops over
  the k slices of a whole-k ``(k, Bl, d)`` S tile, one ``(Bl,d)·(d,Bn)``
  matmul per slice.

Grid: l tiles, then n tiles (then k chunks for ``loop``), innermost last, so
the fused accumulator block (indexed by the l tile only) stays resident
while n and the chunks stream past.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.precision import PrecisionPolicy, matmul_precision

_BIG = 3.0e38  # +inf stand-in that survives fp32 math


def _maybe_rbf(d2, rbf_gamma):
    if rbf_gamma is None:
        return d2
    return 2.0 * (1.0 - jnp.exp(-rbf_gamma * d2))


def _sq_norms(x, accum_dtype):
    xa = x.astype(accum_dtype)
    return jnp.sum(xa * xa, axis=-1)


def _dist_tile(v, s, policy: PrecisionPolicy, rbf_gamma):
    """(Bn, d)×(B, d) → (Bn, B) squared distances via the MXU."""
    g = jax.lax.dot_general(
        v, s, (((1,), (1,)), ((), ())),
        precision=matmul_precision(v.dtype),
        preferred_element_type=policy.accum_dtype,
    )
    vn = _sq_norms(v, policy.accum_dtype)
    sn = _sq_norms(s, policy.accum_dtype)
    d2 = jnp.maximum(vn[:, None] + sn[None, :] - 2.0 * g, 0.0)
    return _maybe_rbf(d2, rbf_gamma)


# ---------------------------------------------------------------------------
# fused kernels
# ---------------------------------------------------------------------------


def _chunk_min(v, s3, len_ref, policy: PrecisionPolicy, rbf_gamma,
               start=None):
    """Masked min over the slices of a k-major ``(kc, Bl, d)`` S chunk
    whose first slice is ``start`` (None: the first of k), against the
    (Bl, 1) lengths. One matmul for all (set, slice) pairs; returns the
    ``(Bn, Bl)`` tile."""
    kk, bl, d = s3.shape
    bn = v.shape[0]
    s2 = s3.reshape(kk * bl, d)                          # merge leading dims
    d2 = _dist_tile(v, s2, policy, rbf_gamma)            # (Bn, kc·Bl)
    d2 = d2.reshape(bn, kk, bl)                          # lane dim (Bl) intact
    lens = len_ref[...][:, 0]                            # (Bl,)
    if start is not None:
        lens = lens - start                              # slices left
    kidx = jax.lax.broadcasted_iota(jnp.int32, (1, kk, bl), 1)
    valid = kidx < lens[None, None, :]
    d2 = jnp.where(valid, d2, _BIG)
    return jnp.min(d2, axis=1)                           # (Bn, Bl)


def _fused_loop_kernel(v_ref, s_ref, len_ref, e0_ref, out_ref, min_ref, *,
                       n_total: int, policy: PrecisionPolicy, rbf_gamma):
    """S tile is one (kc, Bl, d) chunk; ``min_ref`` carries the running
    (Bn, Bl) min across the chunks of grid axis 2."""
    j, c = pl.program_id(1), pl.program_id(2)
    kc = s_ref.shape[0]

    @pl.when((j == 0) & (c == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(c == 0)
    def _seed():
        e0 = e0_ref[...].astype(policy.accum_dtype)      # (Bn, 1)
        min_ref[...] = jnp.broadcast_to(e0, min_ref.shape).astype(
            min_ref.dtype)

    v = v_ref[...].astype(policy.compute_dtype)          # (Bn, d)
    s3 = s_ref[...].astype(policy.compute_dtype)         # (kc, Bl, d)
    dmin = _chunk_min(v, s3, len_ref, policy, rbf_gamma, start=c * kc)
    min_ref[...] = jnp.minimum(min_ref[...], dmin.astype(jnp.float32))

    @pl.when(c == pl.num_programs(2) - 1)
    def _fold():
        partial = jnp.sum(min_ref[...], axis=0) / n_total
        out_ref[...] += partial[:, None]


def _fused_flat_kernel(v_ref, s_ref, len_ref, e0_ref, out_ref, *,
                       n_total: int, policy: PrecisionPolicy, rbf_gamma):
    """S tile is (k, Bl, d) "k-major": one matmul for all (set, k) pairs."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    v = v_ref[...].astype(policy.compute_dtype)          # (Bn, d)
    s3 = s_ref[...].astype(policy.compute_dtype)         # (k, Bl, d)
    dmin = _chunk_min(v, s3, len_ref, policy, rbf_gamma)
    e0 = e0_ref[...].astype(dmin.dtype)                  # (Bn, 1)
    dmin = jnp.minimum(dmin, e0)
    partial = jnp.sum(dmin.astype(jnp.float32), axis=0) / n_total
    out_ref[...] += partial[:, None]


# ---------------------------------------------------------------------------
# two-pass (paper-faithful) kernel: materialize W tiles
# ---------------------------------------------------------------------------


def _loop_min(v, s_ref, lens, e0, *, k: int, policy: PrecisionPolicy,
              rbf_gamma, unroll: int):
    """Running min over the k slices of a k-major ``(k, Bl, d)`` S tile.

    Returns the ``(Bl, Bn)`` tile — rows are sets, as in the paper's W — with
    ``lens`` a (Bl, 1) column and ``e0`` a (1, Bn) row, so no operand or
    result needs a sublane↔lane relayout (Mosaic spends minutes and more
    VMEM than the chip has on the transposed orientation).
    """
    minval = jnp.broadcast_to(e0.astype(policy.accum_dtype),
                              (lens.shape[0], e0.shape[1]))

    def body(kk, minval):
        s = s_ref[kk].astype(policy.compute_dtype)        # (Bl, d)
        d2 = _dist_tile(s, v, policy, rbf_gamma)          # (Bl, Bn)
        d2 = jnp.where(kk < lens, d2, _BIG)
        return jnp.minimum(minval, d2.astype(policy.accum_dtype))

    if k <= unroll:
        for kk in range(k):
            minval = body(kk, minval)
        return minval
    return jax.lax.fori_loop(0, k, body, minval)


def _two_pass_kernel(v_ref, s_ref, len_ref, e0_ref, w_ref, *,
                     k: int, n_total: int, policy: PrecisionPolicy,
                     rbf_gamma, unroll: int):
    v = v_ref[...].astype(policy.compute_dtype)          # (Bn, d)
    minval = _loop_min(v, s_ref, len_ref[...], e0_ref[...], k=k,
                       policy=policy, rbf_gamma=rbf_gamma, unroll=unroll)
    # W[j, i] = min-dist / n (paper eq. 5)
    w_ref[...] = (minval / n_total).astype(w_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call builders
# ---------------------------------------------------------------------------


def fused_eval(
    V: jax.Array,            # (n_pad, d_pad)
    S: jax.Array,            # (k_pad, l_pad, d_pad) k-major
    lengths: jax.Array,      # (l_pad, 1) int32
    d_e0: jax.Array,         # (n_pad, 1) float32
    *,
    n_total: int,
    policy: PrecisionPolicy,
    block_n: int,
    block_l: int,
    block_k: Optional[int] = None,
    variant: str = "flat",
    rbf_gamma: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    """Returns (l_pad, 1) float32 sums L(S_j ∪ {e0}).

    ``d_e0`` (already transformed) is a column. ``flat`` reads all k slices
    per step; ``loop`` reads ``block_k`` (default all), which must divide
    ``k_pad``.
    """
    k, l_pad, d_pad = S.shape
    n_pad = V.shape[0]
    if variant == "flat":
        kern = functools.partial(
            _fused_flat_kernel, n_total=n_total, policy=policy,
            rbf_gamma=rbf_gamma)
        return pl.pallas_call(
            kern,
            grid=(l_pad // block_l, n_pad // block_n),
            in_specs=[
                pl.BlockSpec((block_n, d_pad), lambda i, j: (j, 0)),
                pl.BlockSpec((k, block_l, d_pad), lambda i, j: (0, i, 0)),
                pl.BlockSpec((block_l, 1), lambda i, j: (i, 0)),
                pl.BlockSpec((block_n, 1), lambda i, j: (j, 0)),
            ],
            out_specs=pl.BlockSpec((block_l, 1), lambda i, j: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((l_pad, 1), jnp.float32),
            interpret=interpret,
            name="exemplar_eval_flat",
        )(V, S, lengths, d_e0)
    if variant != "loop":
        raise ValueError(f"unknown variant {variant!r}")
    kc = k if block_k is None else block_k
    if k % kc:
        raise ValueError(f"block_k={kc} does not divide k_pad={k}")
    kern = functools.partial(
        _fused_loop_kernel, n_total=n_total, policy=policy,
        rbf_gamma=rbf_gamma)
    return pl.pallas_call(
        kern,
        grid=(l_pad // block_l, n_pad // block_n, k // kc),
        in_specs=[
            pl.BlockSpec((block_n, d_pad), lambda i, j, c: (j, 0)),
            pl.BlockSpec((kc, block_l, d_pad), lambda i, j, c: (c, i, 0)),
            pl.BlockSpec((block_l, 1), lambda i, j, c: (i, 0)),
            pl.BlockSpec((block_n, 1), lambda i, j, c: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_l, 1), lambda i, j, c: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((l_pad, 1), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_n, block_l), jnp.float32)],
        interpret=interpret,
        name="exemplar_eval_loop",
    )(V, S, lengths, d_e0)


def two_pass_eval(
    V: jax.Array,            # (n_pad, d_pad)
    S: jax.Array,            # (k, l_pad, d_pad) k-major
    lengths: jax.Array,      # (l_pad, 1)
    d_e0: jax.Array,         # (1, n_pad) row
    *,
    n_total: int,
    policy: PrecisionPolicy,
    block_n: int,
    block_l: int,
    rbf_gamma: Optional[float] = None,
    unroll: int = 16,
    interpret: bool = False,
) -> jax.Array:
    """Paper-faithful: materialize W (l_pad, n_pad) in HBM; caller reduces.

    The W tile ``(block_l, block_n)`` is lane-dense: ``block_n`` must be a
    multiple of 128 or the whole padded n (see ``ops.kernel_config``).
    """
    k, l_pad, d_pad = S.shape
    n_pad = V.shape[0]
    grid = (l_pad // block_l, n_pad // block_n)
    kern = functools.partial(
        _two_pass_kernel, k=k, n_total=n_total, policy=policy,
        rbf_gamma=rbf_gamma, unroll=unroll)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, d_pad), lambda i, j: (j, 0)),
            pl.BlockSpec((k, block_l, d_pad), lambda i, j: (0, i, 0)),
            pl.BlockSpec((block_l, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_l, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((l_pad, n_pad), jnp.float32),
        interpret=interpret,
        name="exemplar_eval_two_pass",
    )(V, S, lengths, d_e0)
