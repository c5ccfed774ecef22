"""Distributed submodular evaluation over a device mesh (shard_map).

The paper's decomposition L(S) = Σ_i L_{v_i}(S) (eq. 5/6) is *exactly* a
data-parallel sum over the ground set: shard V's rows over the mesh's data
axes, evaluate partial work-matrix column blocks locally, ``psum`` the row
sums. This scales the technique from one GPU to a pod: each chip holds
n/|data| ground vectors of the *working* distance/cache state, the multiset
payload is replicated (it is l·k·d ≪ n·d), and the only communication is one
(l,)-sized all-reduce per evaluation — the technique is embarrassingly
scalable along exactly the axis that grows with corpus size.

This module is the **sharded backend of the selection engine**
(:mod:`repro.core.engine`): the whole k-round greedy scan runs *inside*
``shard_map``, with V's rows and the min-distance cache sharded over the
mesh's data axes. Three execution plans live here:

* ``device_sharded`` — the candidate payload replicates (O(n·d) resident per
  device; fine for sampled/lazy candidate sets, the documented tradeoff for
  dense greedy). Each scored candidate batch reduces its (m,) per-shard gain
  partials with ONE ``psum`` of O(m) bytes (the trajectory scalar rides in
  the same collective): dense/stochastic rounds issue exactly one; a CELF
  round issues one per top-B re-scoring iteration.
* ``device_sharded_pool`` — **no O(n·d) array is ever replicated**: the
  candidate payload row-shards exactly like V (for the selection engine it
  *is* V's shard — zero extra resident bytes), taking per-device memory to
  O(n/p·d). Candidate scoring blocks psum-materialize transiently from
  their owning shards (one O(Bm·d) collective per block), and the round
  winner's column is all-gathered by the same ``take`` (one O(d) psum per
  round) instead of riding a resident replica — the CELF top-B re-score and
  its ub0 seeding pass stream through the identical blocked takes. Only the
  O(n) *scalar* CELF bound state (and the argmax) stays replicated.
* ``greedi`` — Mirzasoleiman et al.'s distributed partition-then-merge for
  dense greedy: each shard greedily solves its own V-partition in-place (no
  collectives), the p·k partial solutions all-gather in one O(p·k·d) psum,
  and a merge greedy over that small replicated pool runs under the
  sharded-cache callbacks. Selections carry the GreeDi constant-factor
  guarantee rather than matching centralized greedy exactly.

The argmax — and for CELF the stale-bound state — stays replicated in every
plan. The standalone ``make_distributed_*`` evaluators remain as the
one-collective-per-call building blocks for external drivers.
"""
from __future__ import annotations

from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro.analysis.contracts import contract
from repro.core import distances as dist_mod
from repro.core import functions as fx
from repro.core.engine import (DEVICE_TRACE_COUNTS, _device_block_m,
                               _score_blocked, drive_selection_scan,
                               drive_selection_scan_batched,
                               mesh_tiles_per_memory)
from repro.core.evaluator import EvalConfig
from repro.core.functions import FnSpec, gains_formula
from repro.core.multiset import PackedMultiset
from repro.core.precision import resolve as resolve_policy


def shard_ground_set(V: jax.Array, mesh: Mesh,
                     data_axes: Sequence[str] = ("data",)) -> jax.Array:
    """Place V row-sharded over the mesh's data axes (replicated over model)."""
    spec = P(tuple(data_axes), None)
    return jax.device_put(V, NamedSharding(mesh, spec))


def make_distributed_eval(mesh: Mesh, cfg: EvalConfig,
                          data_axes: Sequence[str] = ("data",)):
    """Build a jitted distributed L(S_j ∪ {e0}) evaluator.

    Returns fn(V_sharded, data, lengths, d_e0_sharded) -> (l,) float32,
    where V is row-sharded over ``data_axes`` and the multiset is replicated.
    """
    policy = resolve_policy(cfg.policy)
    pair = dist_mod.resolve_pairwise(cfg.distance)
    axes = tuple(data_axes)

    def local_eval(V_loc, data, lengths, d_e0_loc, n_global):
        l, k, d = data.shape
        D = pair(V_loc, data.reshape(l * k, d), policy).reshape(V_loc.shape[0], l, k)
        mask = jnp.arange(k)[None, :] < lengths[:, None]
        big = jnp.asarray(jnp.finfo(D.dtype).max, D.dtype)
        D = jnp.where(mask[None, :, :], D, big)
        dmin = jnp.minimum(jnp.min(D, axis=-1), d_e0_loc[:, None].astype(D.dtype))
        partial_sum = jnp.sum(dmin, axis=0).astype(jnp.float32)  # (l,)
        total = jax.lax.psum(partial_sum, axes)
        return total / n_global

    smapped = shard_map(
        local_eval,
        mesh=mesh,
        in_specs=(P(axes, None), P(None, None, None), P(None), P(axes), P()),
        out_specs=P(None),
        check_vma=False,
    )

    @jax.jit
    def run(V_sharded, data, lengths, d_e0_sharded):
        n_global = jnp.asarray(V_sharded.shape[0], jnp.float32)
        return smapped(V_sharded, data, lengths, d_e0_sharded, n_global)

    return run


def make_distributed_gains(mesh: Mesh, cfg: EvalConfig,
                           data_axes: Sequence[str] = ("data",)):
    """Distributed marginal gains Δ(c_j | S) against a sharded min-cache."""
    policy = resolve_policy(cfg.policy)
    pair = dist_mod.resolve_pairwise(cfg.distance)
    axes = tuple(data_axes)

    def local_gains(V_loc, cands, cache_loc, n_global):
        # the engine's shared gain reduction with the global-n normalizer:
        # per-shard partials psum to the exact global gains
        g = gains_formula(V_loc, cands, cache_loc, pair, policy,
                          n_total=n_global)
        return jax.lax.psum(g.astype(jnp.float32), axes)

    smapped = shard_map(
        local_gains,
        mesh=mesh,
        in_specs=(P(axes, None), P(None, None), P(axes), P()),
        out_specs=P(None),
        check_vma=False,
    )

    @jax.jit
    def run(V_sharded, cands, cache_sharded):
        n_global = jnp.asarray(V_sharded.shape[0], jnp.float32)
        return smapped(V_sharded, cands, cache_sharded, n_global)

    return run


def make_distributed_cache_update(mesh: Mesh, cfg: EvalConfig,
                                  data_axes: Sequence[str] = ("data",)):
    """min-cache update m ← min(m, d(V, x)) with both sharded the same way."""
    policy = resolve_policy(cfg.policy)
    pair = dist_mod.resolve_pairwise(cfg.distance)
    axes = tuple(data_axes)

    def local_update(V_loc, x, cache_loc):
        D = pair(V_loc, x[None, :], policy)[:, 0]
        return jnp.minimum(cache_loc, D.astype(cache_loc.dtype))

    smapped = shard_map(
        local_update,
        mesh=mesh,
        in_specs=(P(axes, None), P(None), P(axes)),
        out_specs=P(axes),
        check_vma=False,
    )
    return jax.jit(smapped)


# ---------------------------------------------------------------------------
# Mesh-sharded selection scan — the engine's device_sharded execution plan.
# All k rounds run in ONE dispatch inside shard_map; each scored candidate
# batch crosses the mesh as exactly one psum of O(m) bytes (one per
# dense/stochastic round, one per CELF re-scoring iteration).
# ---------------------------------------------------------------------------

_SELECTION_SCAN_CACHE: dict = {}


@contract(
    "distributed.selection_scan[sharded]",
    factory=True,
    collective_kinds=("psum",),
    claim="one dispatch; the round body streams blocked O(Bm·d) takes and "
          "ONE O(m) gains psum — no collective ever carries O(n·d) or "
          "O(n·m) bytes; candidate payload resident O(n/p·d) per device")
@contract(
    "distributed.selection_scan[replicated]",
    factory=True,
    collective_kinds=("psum",),
    claim="one dispatch; ONE O(m) gains psum per scored batch (plus graph "
          "cut's owner-gather fold); v0 seeding and the final trajectory "
          "value are the only round-independent collectives")
def make_selection_scan(
    mesh: Mesh,
    data_axes: Sequence[str],
    *,
    fn: FnSpec = FnSpec(),   # the function's static identity
    kind: str,               # "dense" | "stochastic" | "lazy"
    k: int,                  # selection rounds
    top_b: int,              # CELF re-score width (lazy only)
    n_total: int,            # global ground-set size (the gain normalizer)
    block_m: int,            # per-shard candidate block (bounds the tile)
    distance: str,
    policy_name: str,
    counter_key: str,
    backend: str = "jnp",    # "jnp" | "pallas" | "pallas_interpret"
    rbf_gamma: Optional[float] = None,
    pool_plan: str = "replicated",  # "replicated" | "sharded"
):
    """Build (and cache) the jitted mesh-sharded k-round selection scan.

    Returns ``run(V_sh, pool, seed_sh, aux_sh, cand_rounds, w0) -> (sel,
    traj, n_scored)`` where ``V_sh``/``seed_sh``/``aux_sh`` are row-sharded
    over ``data_axes`` (the function's cache seed and static per-row
    auxiliary, padded with its sentinel values — see
    :func:`functions.pad_seed` / :func:`functions.pad_row_aux`) and
    ``cand_rounds`` is (k, m) int32 for stochastic, ONE (1, m) row for dense
    (closed over by every round, never replicated k times), (1, 0) for lazy.
    The builder is cached per (mesh, fn, statics) so repeat runs reuse one
    traced executable; ``fn`` rides the cache key exactly like a jit static.

    The cache is the function's ``(vec, aux)`` pytree: the vec row-shards
    with V, the scalar aux (graph cut's pairwise penalty) stays replicated —
    its winner-indexed update is an owner-shard gather psum'd in
    :func:`functions.fold_aux` (executed unconditionally so the collective
    pattern is uniform across shards, then gated on winner validity). Graph
    cut's index-addressed gain extra is a per-shard partial by construction
    (the owner contributes the one real term, every other shard 0), so it
    rides the existing per-batch gains psum with no extra collective.

    ``pool_plan`` picks the candidate-payload memory plan:

    * ``"replicated"`` — ``pool`` is the full candidate payload, resident
      on every device; candidate rows gather locally and each scored batch
      costs one O(m) psum.
    * ``"sharded"`` — ``pool`` row-shards over ``data_axes`` exactly like V
      (callers pass V's own shard — zero extra resident bytes, O(n/p·d) per
      device). Candidate *indices* resolve through a ``take`` that
      psum-materializes only the requested columns from their owning shards
      (zero-padded rows elsewhere make the psum an exact gather): scoring
      streams ⌈m/block_m⌉ such O(Bm·d) collectives per batch and the round
      winner's (d,) column is all-gathered the same way, so no shard ever
      holds more than one candidate block. The CELF ub0 seeding pass and
      top-B re-scores run through the identical blocked takes; the O(n)
      scalar bound state stays replicated (it is the documented exception —
      bounds are per-candidate scalars, not payload).

    On ``backend="pallas"``/``"pallas_interpret"`` each shard scores its
    local (n_loc, m) tile through the shared min/max Pallas kernel template
    (:func:`repro.kernels.ops.fused_gain_update` for dense/stochastic
    rounds of fused-eligible functions — the winner fold rides in-tile —
    and ``marginal_gain`` for CELF re-scoring and graph cut's add-fold
    rounds; the sharded pool streams take-blocks through ``marginal_gain``
    with an explicit fold, since a block materializes only after the fold's
    winner column is gathered). The kernels already normalize by the
    *global* ``n_total``, so the per-shard outputs are exact gain partials
    and the one-psum-per-batch collective pattern is byte-identical to the
    jnp path. Shard-tile blocking note: ``block_m`` bounds the *jnp* path's
    streamed HBM tile (and the sharded pool's take-block width) only; the
    kernels tile their own VMEM blocks from the local shard height (padding
    n_loc/m to block multiples in-wrapper), so the MXU tiling is per-shard
    and never sees mesh topology.
    """
    axes = tuple(data_axes)
    key = (mesh, axes, fn, kind, k, top_b, n_total, block_m, distance,
           policy_name, counter_key, backend, rbf_gamma, pool_plan)
    if key in _SELECTION_SCAN_CACHE:
        return _SELECTION_SCAN_CACHE[key]
    if pool_plan not in ("replicated", "sharded"):
        raise ValueError(f"unknown pool_plan {pool_plan!r}")
    policy = resolve_policy(policy_name)
    pair = dist_mod.resolve_pairwise(distance)
    tmpl = fx.kernel_template(fn)
    use_kernel = backend in ("pallas", "pallas_interpret") and tmpl is not None
    sharded_pool = pool_plan == "sharded"
    if use_kernel:
        from repro.kernels import ops as kops

    def local_scan(V_loc, pool, seed_loc, aux_loc, cand_rounds, w0):
        n_loc = V_loc.shape[0]
        off = jax.lax.axis_index(axes) * n_loc
        seedf = seed_loc.astype(jnp.float32)
        v0 = jax.lax.psum(
            jnp.sum(fx.stat_rows(fn, seedf, aux_loc)), axes) / n_total
        psum_ = lambda x: jax.lax.psum(x, axes)  # noqa: E731

        def value_of(cache):
            vec, aux = cache
            mean_stat = jax.lax.psum(
                jnp.sum(fx.stat_rows(fn, vec, aux_loc)) / n_total, axes)
            return fx.value_from_stat(fn, v0, mean_stat, aux, n_total)

        def fold(cache, w):
            vec, aux = cache
            row, gidx = w
            dw = pair(V_loc, row[None, :], policy)[:, 0]
            folded = fx.fold_vec_rows(fn, vec, dw.astype(jnp.float32))
            # aux advances from the PRE-fold vec; its psum (graph cut's
            # owner gather) executes unconditionally so every shard issues
            # the same collectives, and the where gates after
            new_aux = fx.fold_aux(fn, vec, aux, gidx, off, n_loc, psum=psum_)
            ok = gidx >= 0
            return (jnp.where(ok, folded, vec), jnp.where(ok, new_aux, aux))

        def psum_gains_val(g_part, cache):
            """ONE O(m)-byte collective per scored batch: the (m,) per-shard
            gain partials plus the shard's stat row-sum ride one psum."""
            vec, aux = cache
            payload = jnp.concatenate(
                [g_part.astype(jnp.float32),
                 (jnp.sum(fx.stat_rows(fn, vec, aux_loc)) / n_total)[None]])
            out = jax.lax.psum(payload, axes)
            return out[:-1], fx.value_from_stat(fn, v0, out[-1], aux, n_total)

        def score_part(vec, C):
            # per-shard gain partials: the kernel path tiles VMEM blocks
            # itself, the jnp path streams (n_loc, block_m) tiles — neither
            # materializes an (n_loc, m) distance block on any shard
            sc = fx.score_cache_rows(fn, vec, aux_loc)
            if use_kernel:
                return kops.marginal_gain(
                    V_loc, C, sc, policy=policy, rbf_gamma=rbf_gamma,
                    fold=tmpl[0], score_affine=tmpl[1],
                    interpret=(backend != "pallas"), n_total=n_total)
            return _score_blocked(V_loc, C, sc, pair, policy, block_m,
                                  n_total=n_total, fn=fn, row_aux=aux_loc)

        cache0 = (seedf, jnp.float32(0.0))
        w0c = (w0.astype(pool.dtype), jnp.asarray(-1, jnp.int32))

        if sharded_pool:
            n_loc_pool = pool.shape[0]
            off_pool = jax.lax.axis_index(axes) * n_loc_pool

            def take_rows(idxv):
                """Materialize pool rows for *global* indices: one psum of
                the owner's rows against everyone else's zeros (exact — the
                psum adds one real row and p−1 zero rows)."""
                rel = idxv - off_pool
                own = (rel >= 0) & (rel < n_loc_pool)
                rows = pool[jnp.clip(rel, 0, n_loc_pool - 1)]
                return jax.lax.psum(
                    jnp.where(own[:, None], rows, jnp.zeros_like(rows)),
                    axes)

            def take(j):
                return take_rows(jnp.atleast_1d(j))[0], j

            def score_idx(cache, idx):
                # stream index blocks: take-materialize (block_m, d), score
                # the local tile, never hold two blocks at once
                vec, _aux = cache
                m = idx.shape[0]
                bm = min(block_m, m)
                m_pad = -(-m // bm) * bm
                idx_p = jnp.pad(idx, (0, m_pad - m))
                parts = jax.lax.map(
                    lambda ib: score_part(vec, take_rows(ib)),
                    idx_p.reshape(-1, bm)).reshape(-1)[:m]
                extra = fx.gains_index_extra(fn, vec, idx, off, n_loc,
                                             n_total)
                return parts if extra is None else parts + extra

            def score_idx_val(cache, idx):
                return psum_gains_val(score_idx(cache, idx), cache)

            def fold_score_val(cache, w_prev, cand_t):
                # the fold stays explicit: the winner column was already
                # gathered last round, and candidate blocks only
                # materialize inside the streamed scoring below
                cache = fold(cache, w_prev)
                gains, val = score_idx_val(cache, cand_t)
                return gains, cache, val

            def seed_val(cache):
                return score_idx_val(
                    cache, jnp.arange(n_total, dtype=jnp.int32))

            return drive_selection_scan(
                kind=kind, k=k, top_b=top_b, n_global=n_total, take=take,
                n_pool=n_total, seed_val=seed_val,
                score_idx_val=score_idx_val, cand_rounds=cand_rounds,
                cache0=cache0, w0=w0c, fold=fold,
                fold_score_val=fold_score_val, value_of=value_of)

        def score_idx_val(cache, idx):
            vec, _aux = cache
            g = score_part(vec, pool[idx])
            extra = fx.gains_index_extra(fn, vec, idx, off, n_loc, n_total)
            return psum_gains_val(g if extra is None else g + extra, cache)

        if use_kernel and fx.kernel_fused_ok(fn):

            def fold_score_val(cache, w_prev, cand_t):
                # fused dense/stochastic round: the winner fold happens
                # inside the kernel on the local shard tile (fused-eligible
                # functions carry no aux and no index extra)
                vec, aux = cache
                row, gidx = w_prev
                g_part, vec2 = kops.fused_gain_update(
                    V_loc, pool[cand_t], vec, row, policy=policy,
                    rbf_gamma=rbf_gamma, fold=tmpl[0], score_affine=tmpl[1],
                    interpret=(backend != "pallas"), n_total=n_total,
                    w_valid=(gidx >= 0).astype(jnp.float32))
                cache2 = (vec2, aux)
                gains, val = psum_gains_val(g_part, cache2)
                return gains, cache2, val
        else:

            def fold_score_val(cache, w_prev, cand_t):
                cache2 = fold(cache, w_prev)
                gains, val = score_idx_val(cache2, cand_t)
                return gains, cache2, val

        return drive_selection_scan(
            kind=kind, k=k, top_b=top_b, n_global=n_total, pool=pool,
            cand_rounds=cand_rounds, cache0=cache0, w0=w0c, fold=fold,
            score_idx_val=score_idx_val, fold_score_val=fold_score_val,
            value_of=value_of)

    smapped = shard_map(
        local_scan,
        mesh=mesh,
        in_specs=(P(axes, None),
                  P(axes, None) if sharded_pool else P(None, None),
                  P(axes), P(axes), P(None, None), P(None)),
        out_specs=(P(None), P(None), P()),  # sel, traj, scalar n_scored
        check_vma=False,
    )

    @jax.jit
    def run(V_sh, pool, seed_sh, aux_sh, cand_rounds, w0):
        DEVICE_TRACE_COUNTS[counter_key] += 1
        return smapped(V_sh, pool, seed_sh, aux_sh, cand_rounds, w0)

    _SELECTION_SCAN_CACHE[key] = run
    return run


def _resolve_mesh(mesh: Optional[Mesh], data_axes: Sequence[str]) -> Mesh:
    if mesh is None:
        if len(data_axes) != 1:
            raise ValueError(
                "the default mesh is 1-D; pass an explicit mesh to shard "
                f"over multiple axes {tuple(data_axes)}")
        mesh = jax.make_mesh((jax.device_count(),), tuple(data_axes),
                             axis_types=(AxisType.Auto,))
    return mesh


def _mesh_extent(mesh: Mesh, axes: Sequence[str]) -> int:
    ndev = 1
    for a in axes:
        ndev *= mesh.shape[a]
    return ndev


def _placed_sharded(f, mesh: Mesh, axes: tuple, replicated_pool: bool):
    """Shard-place (and cache on ``f``) V's padded rows plus the function's
    cache seed and per-row auxiliary.

    V pads with zero rows; the seed and row_aux pad with the function's
    sentinel values (:func:`functions.pad_seed` / ``pad_row_aux``) so pad
    rows contribute nothing to gains or stat sums — 0 for the min/additive
    caches, +inf dead-row markers for the max-cache functions (a zero V row
    is a *real-looking* point whose similarity to candidates is positive,
    so only the sentinel makes it inert). The placement is cached on the
    function instance (V, seed and aux are immutable) so repeat runs pay no
    transfer; delete ``f._sharded_placement_cache`` to release the device
    memory. Only the MOST RECENT (mesh, axes) is kept, and the
    **replicated** candidate pool — O(n·d) resident per device, the
    ``device_sharded`` plan's documented tradeoff — is built lazily, only
    when that plan actually runs: the sharded-pool and greedi plans never
    pin it.
    """
    n = f.n
    ndev = _mesh_extent(mesh, axes)
    n_pad = ((n + ndev - 1) // ndev) * ndev
    placed = getattr(f, "_sharded_placement_cache", None)
    if placed is None or placed[0] != (mesh, axes):
        Vp = jnp.pad(f.V, ((0, n_pad - n), (0, 0)))
        seedp = jnp.pad(f.cache_seed, (0, n_pad - n),
                        constant_values=fx.pad_seed(f.spec))
        auxp = jnp.pad(f.row_aux, (0, n_pad - n),
                       constant_values=fx.pad_row_aux(f.spec))
        placed = f._sharded_placement_cache = ((mesh, axes), {
            "V_sh": jax.device_put(Vp, NamedSharding(mesh, P(axes, None))),
            "seed_sh": jax.device_put(seedp, NamedSharding(mesh, P(axes))),
            "aux_sh": jax.device_put(auxp, NamedSharding(mesh, P(axes))),
        })
    entry = placed[1]
    if replicated_pool and "pool" not in entry:
        entry["pool"] = jax.device_put(
            f.V, NamedSharding(mesh, P(None, None)))
    return entry


def run_sharded_selection(
    f,                       # SubmodularFunction (untyped: avoids circularity)
    cand_rounds: jax.Array,  # (k, m) int32 global candidate indices
    w0: jax.Array,
    *,
    kind: str,
    k: int,
    top_b: int,
    counter_key: str,
    m_widest: int,
    block_m: Optional[int] = None,
    mesh: Optional[Mesh] = None,
    data_axes: Sequence[str] = ("data",),
    backend: str = "jnp",
    rbf_gamma: Optional[float] = None,
    pool_plan: str = "replicated",
):
    """Place operands on the mesh and run the sharded selection scan.

    ``pool_plan="replicated"`` keeps the candidate payload resident on
    every device (O(n·d) — fine for sampled/lazy candidates);
    ``pool_plan="sharded"`` passes V's own row-shard as the pool (zero
    extra resident bytes — O(n/p·d) per device total) and
    psum-materializes candidate blocks on demand (see
    :func:`make_selection_scan`). The per-shard gain tile is bounded by
    ``block_m``: autotuned from the *local* shard height n/p (never global
    n — that would under-fill every shard's memory p×), the widest
    candidate round ``m_widest``, and the number of shards whose tiles
    share one physical memory space (forced host devices: p tiles carve
    one allocator pool — sizing each from the full probe would over-commit
    p×). Under the sharded pool the take-block width is additionally
    capped at n_loc so the transient gathered block never exceeds the
    resident shard — the O(n/p) peak-memory claim covers transients too.
    Returns ``(sel, traj, n_scored)`` device arrays.
    """
    mesh = _resolve_mesh(mesh, data_axes)
    axes = tuple(data_axes)
    ndev = _mesh_extent(mesh, axes)
    n = f.n
    n_pad = ((n + ndev - 1) // ndev) * ndev
    n_loc = n_pad // ndev
    bm = block_m if block_m is not None \
        else _device_block_m(n_loc, m_widest, mesh_tiles_per_memory(mesh))
    if pool_plan == "sharded":
        bm = min(bm, max(8, n_loc))
    entry = _placed_sharded(f, mesh, axes, pool_plan == "replicated")
    V_sh, seed_sh, aux_sh = entry["V_sh"], entry["seed_sh"], entry["aux_sh"]
    pool = entry["pool"] if pool_plan == "replicated" else V_sh
    scan = make_selection_scan(
        mesh, axes, fn=f.spec, kind=kind, k=k, top_b=top_b, n_total=n,
        block_m=bm, distance=f.cfg.distance,
        policy_name=f.cfg.resolved_policy().name, counter_key=counter_key,
        backend=backend, rbf_gamma=rbf_gamma, pool_plan=pool_plan)
    return scan(V_sh, pool, seed_sh, aux_sh, cand_rounds, w0)


# ---------------------------------------------------------------------------
# Batched × sharded composition — B tenants in one (B, n/p) mesh dispatch.
# Every per-request (n,) state from the single-device batched engine gains
# its mesh layout here: the B min-caches row-shard WITH V ((B, n_loc) per
# device), and each scored batch's B per-request gain partials stack into
# the SAME psum (O(B·m) bytes, trajectory scalars riding along) instead of
# issuing B collectives. Ragged k stays the k_eff freeze mask, so bucket
# padding is inert on every shard.
# ---------------------------------------------------------------------------

_SELECTION_SCAN_BATCHED_CACHE: dict = {}


@contract(
    "distributed.selection_scan_batched[sharded]",
    factory=True,
    collective_kinds=("psum",),
    donate=("seed_sh",),
    claim="B tenants, one dispatch, O(B·n/p·d) resident per device; the "
          "round body streams blocked O(B·m·d) takes and ONE O(B·m) gains "
          "psum — per-tenant partials stack into the same collective, "
          "never B collectives; the (B, n/p) cache seed is donated")
@contract(
    "distributed.selection_scan_batched[replicated]",
    factory=True,
    collective_kinds=("psum",),
    donate=("seed_sh",),
    claim="B tenants, one dispatch; ONE O(B·m) gains psum per scored batch "
          "(every tenant's partials + trajectory scalars in one collective "
          "— not B psums); the (B, n/p) sharded cache seed is donated and "
          "aliased onto the final cache output")
def make_selection_scan_batched(
    mesh: Mesh,
    data_axes: Sequence[str],
    *,
    fn: FnSpec = FnSpec(),   # the function's static identity
    kind: str,               # "dense" | "stochastic" | "lazy"
    k: int,                  # shared scan length (max per-request k)
    top_b: int,              # CELF re-score width (lazy only)
    n_total: int,            # global ground-set size (the gain normalizer)
    block_m: int,            # per-shard candidate block (bounds the tile)
    distance: str,
    policy_name: str,
    counter_key: str,
    backend: str = "jnp",    # "jnp" | "pallas" | "pallas_interpret"
    rbf_gamma: Optional[float] = None,
    pool_plan: str = "replicated",  # "replicated" | "sharded"
):
    """Build (and cache) the jitted batched mesh-sharded k-round scan.

    The batched composition of :func:`make_selection_scan`: B same-signature
    requests lay their state out as (B, n/p) per device — ``V_sh`` is
    (B, n_pad, d) sharded ``P(None, data_axes, None)``, the B per-tenant
    cache seeds / row auxiliaries row-shard with it, and ``cand_rounds`` is
    the (B, k, m) per-request candidate indices. Returns ``run(V_sh, pool,
    seed_sh, aux_sh, cand_rounds, w0, k_eff) -> (sel (k, B), traj (k, B),
    n_scored (B,), cache_vec (B, n_pad))``.

    Collective budget: each scored candidate batch reduces ALL B requests'
    (m,) per-shard gain partials in ONE psum of O(B·m) bytes — the batch
    axis rides the collective's payload, never its count — with each
    request's stat row-sum (the trajectory scalar) concatenated into the
    same payload. The per-request column of that payload is byte-identical
    to the unbatched plan's (m+1,) psum, which is why batched-sharded
    selections, trajectories, AND per-request eval counts are bit-equal to
    B separate sharded runs. Batched CELF shares the unbatched step's
    certification loop via :func:`engine.make_batched_lazy_step_val` (the
    trajectory value rides the re-score psum; frozen requests' values
    emerge from the same collective, masked out of bound/count updates).

    ``seed_sh`` is DONATED: the final folded (B, n_pad) cache vector rides
    out with the same NamedSharding, so XLA aliases the carry onto the
    seed's per-device buffers — warm buckets reuse O(B·n/p) bytes instead
    of allocating per dispatch. ``k_eff`` (B,) int32 is the ragged-k freeze
    mask (0 = inert bucket-padding slot).

    On kernel backends each shard scores its local (B, n_loc, m) tile
    through the grid-over-(B, m_tiles, n_tiles) batched Pallas kernels
    (:mod:`repro.kernels.marginal_gain`) with the *global* ``n_total``
    normalizer, so per-shard tiles stay exact psum partials exactly like
    the unbatched sharded kernels. ``pool_plan`` has the same two memory
    plans as the unbatched factory — ``"sharded"`` passes V's own (B, n/p)
    shard as the pool and psum-materializes (B, block) candidate slabs
    from their owning shards.
    """
    axes = tuple(data_axes)
    key = (mesh, axes, fn, kind, k, top_b, n_total, block_m, distance,
           policy_name, counter_key, backend, rbf_gamma, pool_plan)
    if key in _SELECTION_SCAN_BATCHED_CACHE:
        return _SELECTION_SCAN_BATCHED_CACHE[key]
    if pool_plan not in ("replicated", "sharded"):
        raise ValueError(f"unknown pool_plan {pool_plan!r}")
    policy = resolve_policy(policy_name)
    pair = dist_mod.resolve_pairwise(distance)
    tmpl = fx.kernel_template(fn)
    use_kernel = backend in ("pallas", "pallas_interpret") and tmpl is not None
    sharded_pool = pool_plan == "sharded"
    if use_kernel:
        from repro.kernels import ops as kops

    def local_scan(V_loc, pool, seed_loc, aux_loc, cand_rounds, w0, k_eff):
        B, n_loc, _d = V_loc.shape
        off = jax.lax.axis_index(axes) * n_loc
        seedf = seed_loc.astype(jnp.float32)
        # (B,) per-request v0 in ONE psum — the batch axis is payload
        v0 = jax.lax.psum(
            jnp.sum(fx.stat_rows(fn, seedf, aux_loc), axis=1), axes) / n_total
        psum_ = lambda x: jax.lax.psum(x, axes)  # noqa: E731

        def value_of(cache):
            vec, aux = cache
            mean_stat = jax.lax.psum(
                jnp.sum(fx.stat_rows(fn, vec, aux_loc), axis=1) / n_total,
                axes)
            return fx.value_from_stat(fn, v0, mean_stat, aux, n_total)

        def fold(cache, w):
            vec, aux = cache
            row, gidx = w
            dw = jax.vmap(
                lambda Vb, rb: pair(Vb, rb[None, :], policy)[:, 0])(V_loc, row)
            folded = fx.fold_vec_rows(fn, vec, dw.astype(jnp.float32))
            # aux advances from the PRE-fold vec. vmapping the per-request
            # fold batches graph cut's owner-gather psum OPERAND to (B,) —
            # still ONE collective — and every shard executes it
            # unconditionally before the gate, exactly like unbatched
            new_aux = jax.vmap(
                lambda vb, ab, gb: fx.fold_aux(fn, vb, ab, gb, off, n_loc,
                                               psum=psum_))(vec, aux, gidx)
            ok = gidx >= 0
            return (jnp.where(ok[:, None], folded, vec),
                    jnp.where(ok, new_aux, aux))

        def psum_gains_val(g_part, cache):
            """ONE O(B·m)-byte collective per scored batch: all B requests'
            (m,) gain partials plus their stat row-sums ride one psum —
            each request's column is byte-identical to the unbatched
            plan's (m+1,) payload."""
            vec, aux = cache
            stat = (jnp.sum(fx.stat_rows(fn, vec, aux_loc), axis=1)
                    / n_total)[:, None]
            out = jax.lax.psum(
                jnp.concatenate([g_part.astype(jnp.float32), stat], axis=1),
                axes)
            return out[:, :-1], fx.value_from_stat(fn, v0, out[:, -1], aux,
                                                   n_total)

        def score_part(vec, C):
            # per-shard (B, m) gain partials: the batched kernels tile
            # grid-over-(B, m_tiles, n_tiles) VMEM blocks themselves; the
            # jnp path vmaps the (n_loc, block_m)-streamed reduction —
            # neither materializes a (B, n_loc, m) block on any shard
            sc = fx.score_cache_rows(fn, vec, aux_loc)
            if use_kernel:
                return kops.marginal_gain(
                    V_loc, C, sc, policy=policy, rbf_gamma=rbf_gamma,
                    fold=tmpl[0], score_affine=tmpl[1],
                    interpret=(backend != "pallas"), n_total=n_total)
            return jax.vmap(
                lambda Vb, Cb, scb, rb: _score_blocked(
                    Vb, Cb, scb, pair, policy, block_m, n_total=n_total,
                    fn=fn, row_aux=rb))(V_loc, C, sc, aux_loc)

        def gains_extra(vec, idx):
            # graph cut's index-addressed per-shard partial, per request
            # (None for every other function — vmap passes None through)
            return jax.vmap(
                lambda vb, ib: fx.gains_index_extra(fn, vb, ib, off, n_loc,
                                                    n_total))(vec, idx)

        cache0 = (seedf, jnp.zeros((B,), jnp.float32))
        w0c = (w0.astype(pool.dtype), jnp.full((B,), -1, jnp.int32))

        if sharded_pool:
            n_loc_pool = pool.shape[1]
            off_pool = jax.lax.axis_index(axes) * n_loc_pool

            def take_rows(idxv):
                """Materialize (B, mb, d) pool slabs for *global* indices:
                one psum of each owner's rows against everyone else's
                zeros, all B requests in the same collective."""
                rel = idxv - off_pool
                own = (rel >= 0) & (rel < n_loc_pool)
                rows = jnp.take_along_axis(
                    pool, jnp.clip(rel, 0, n_loc_pool - 1)[:, :, None],
                    axis=1)
                return jax.lax.psum(
                    jnp.where(own[:, :, None], rows, jnp.zeros_like(rows)),
                    axes)

            def take(j):
                return take_rows(j[:, None])[:, 0], j

            def score_idx(cache, idx):
                # stream per-request index blocks in lockstep: one
                # take-materialized (B, bm, d) slab at a time, never two
                vec, _aux = cache
                m = idx.shape[1]
                bm = min(block_m, m)
                m_pad = -(-m // bm) * bm
                idx_p = jnp.pad(idx, ((0, 0), (0, m_pad - m)))
                blocks = jnp.moveaxis(idx_p.reshape(B, -1, bm), 1, 0)
                parts = jax.lax.map(
                    lambda ib: score_part(vec, take_rows(ib)), blocks)
                parts = jnp.moveaxis(parts, 0, 1).reshape(B, -1)[:, :m]
                extra = gains_extra(vec, idx)
                return parts if extra is None else parts + extra

            def score_idx_val(cache, idx):
                return psum_gains_val(score_idx(cache, idx), cache)

            def fold_score_val(cache, w_prev, cand_t):
                cache = fold(cache, w_prev)
                gains, val = score_idx_val(cache, cand_t)
                return gains, cache, val

            def seed_val(cache):
                return score_idx_val(cache, jnp.broadcast_to(
                    jnp.arange(n_total, dtype=jnp.int32), (B, n_total)))

            sel, traj, n_scored, cache_f = drive_selection_scan_batched(
                kind=kind, k=k, top_b=top_b, n_global=n_total, k_eff=k_eff,
                take=take, n_pool=n_total, seed_val=seed_val,
                cand_rounds=cand_rounds, cache0=cache0, w0=w0c, fold=fold,
                score_idx_val=score_idx_val, fold_score_val=fold_score_val,
                value_of=value_of)
            return sel, traj, n_scored, cache_f[0]

        def score_idx_val(cache, idx):
            vec, _aux = cache
            g = score_part(vec, jnp.take_along_axis(
                pool, idx[:, :, None], axis=1))
            extra = gains_extra(vec, idx)
            return psum_gains_val(g if extra is None else g + extra, cache)

        if use_kernel and fx.kernel_fused_ok(fn):

            def fold_score_val(cache, w_prev, cand_t):
                # fused dense/stochastic round: each request's winner fold
                # happens inside the batched kernel on its local shard tile
                vec, aux = cache
                row, gidx = w_prev
                g_part, vec2 = kops.fused_gain_update(
                    V_loc, jnp.take_along_axis(
                        pool, cand_t[:, :, None], axis=1),
                    vec, row, policy=policy, rbf_gamma=rbf_gamma,
                    fold=tmpl[0], score_affine=tmpl[1],
                    interpret=(backend != "pallas"), n_total=n_total,
                    w_valid=(gidx >= 0).astype(jnp.float32))
                cache2 = (vec2, aux)
                gains, val = psum_gains_val(g_part, cache2)
                return gains, cache2, val
        else:

            def fold_score_val(cache, w_prev, cand_t):
                cache2 = fold(cache, w_prev)
                gains, val = score_idx_val(cache2, cand_t)
                return gains, cache2, val

        sel, traj, n_scored, cache_f = drive_selection_scan_batched(
            kind=kind, k=k, top_b=top_b, n_global=n_total, k_eff=k_eff,
            pool=pool, cand_rounds=cand_rounds, cache0=cache0, w0=w0c,
            fold=fold, score_idx_val=score_idx_val,
            fold_score_val=fold_score_val, value_of=value_of)
        return sel, traj, n_scored, cache_f[0]

    smapped = shard_map(
        local_scan,
        mesh=mesh,
        in_specs=(P(None, axes, None),
                  P(None, axes, None) if sharded_pool else P(None, None, None),
                  P(None, axes), P(None, axes), P(None, None, None),
                  P(None, None), P(None)),
        out_specs=(P(None), P(None), P(None), P(None, axes)),
        check_vma=False,
    )

    @partial(jax.jit, donate_argnums=(2,))
    def run(V_sh, pool, seed_sh, aux_sh, cand_rounds, w0, k_eff):
        DEVICE_TRACE_COUNTS[counter_key] += 1
        return smapped(V_sh, pool, seed_sh, aux_sh, cand_rounds, w0, k_eff)

    _SELECTION_SCAN_BATCHED_CACHE[key] = run
    return run


def stage_sharded_batch(
    fs,                      # Sequence[SubmodularFunction]
    *,
    mesh: Optional[Mesh] = None,
    data_axes: Sequence[str] = ("data",),
    pool_plan: str = "replicated",
):
    """Pad, stack, and shard-place a bucket of B same-signature requests.

    Host-stacks each request's V/seed/aux (padding rows to the mesh extent
    with the function's inert sentinels, exactly like the unbatched
    :func:`_placed_sharded`) and issues ONE ``jax.device_put`` per operand
    with its (B, n/p) NamedSharding — async on accelerators, so a serving
    loop can stage the NEXT bucket while the current dispatch runs. No
    placement is cached on the functions: the seed must be FRESH per
    dispatch (the batched scan donates it) and bucket composition changes
    call to call. The returned payload is single-use and carries the
    (mesh, axes, pool_plan) it was staged for.
    """
    mesh = _resolve_mesh(mesh, data_axes)
    axes = tuple(data_axes)
    ndev = _mesh_extent(mesh, axes)
    f0 = fs[0]
    n = f0.n
    n_pad = ((n + ndev - 1) // ndev) * ndev
    V_np = [np.asarray(f.V) for f in fs]
    Vp = np.stack([np.pad(v, ((0, n_pad - n), (0, 0))) for v in V_np])
    seedp = np.stack([
        np.pad(np.asarray(f.cache_seed, np.float32), (0, n_pad - n),
               constant_values=fx.pad_seed(f.spec)) for f in fs])
    auxp = np.stack([
        np.pad(np.asarray(f.row_aux), (0, n_pad - n),
               constant_values=fx.pad_row_aux(f.spec)) for f in fs])
    if all(f.e0 is None for f in fs):
        w0_b = np.zeros((len(fs), f0.dim), V_np[0].dtype)
    else:
        w0_b = np.stack([
            np.asarray(f.e0, V_np[0].dtype) if f.e0 is not None
            else np.zeros((f.dim,), V_np[0].dtype) for f in fs])
    payload = {
        "mesh": mesh, "axes": axes, "pool_plan": pool_plan,
        "V_sh": jax.device_put(Vp, NamedSharding(mesh, P(None, axes, None))),
        "seed_sh": jax.device_put(seedp, NamedSharding(mesh, P(None, axes))),
        "aux_sh": jax.device_put(auxp, NamedSharding(mesh, P(None, axes))),
        "w0": jax.device_put(w0_b, NamedSharding(mesh, P(None, None))),
    }
    if pool_plan == "replicated":
        # UNPADDED (B, n, d): the replicated pool is candidate payload, and
        # lazy's ub0 seeding scores every pool row — a padded row would be
        # a real-looking candidate (matches the unbatched "pool" entry)
        payload["pool"] = jax.device_put(
            np.stack(V_np), NamedSharding(mesh, P(None, None, None)))
    return payload


def run_sharded_selection_batch(
    fs,                      # Sequence[SubmodularFunction]
    cand_rounds: jax.Array,  # (B, k, m) int32 global candidate indices
    ks: Sequence[int],
    *,
    kind: str,
    k: int,
    top_b: int,
    counter_key: str,
    m_widest: int,
    block_m: Optional[int] = None,
    mesh: Optional[Mesh] = None,
    data_axes: Sequence[str] = ("data",),
    backend: str = "jnp",
    rbf_gamma: Optional[float] = None,
    pool_plan: str = "replicated",
    staged: Optional[dict] = None,
):
    """Place a bucket's (B, n/p) operands and run the batched sharded scan.

    The gain tile autotunes from B·n_loc rows — the LOCAL shard height
    times the batch (never B·n global, which would under-fill every shard
    p×) — divided once by the number of shard tiles that share one
    physical memory space; under the sharded pool the take-slab width is
    additionally capped at n_loc so the (B, bm, d) transient never exceeds
    the resident shard. ``staged`` optionally passes the payload a prior
    :func:`stage_sharded_batch` already transferred (it is re-staged here
    if its mesh/axes/pool_plan disagree). Returns ``(sel (k, B),
    traj (k, B), n_scored (B,))`` device arrays.
    """
    mesh = _resolve_mesh(mesh, data_axes)
    axes = tuple(data_axes)
    ndev = _mesh_extent(mesh, axes)
    f0 = fs[0]
    B = len(fs)
    n = f0.n
    n_pad = ((n + ndev - 1) // ndev) * ndev
    n_loc = n_pad // ndev
    bm = block_m if block_m is not None \
        else _device_block_m(n_loc, m_widest, mesh_tiles_per_memory(mesh),
                             n_batch=B)
    if pool_plan == "sharded":
        bm = min(bm, max(8, n_loc))
    if staged is None or staged["mesh"] != mesh or staged["axes"] != axes \
            or staged["pool_plan"] != pool_plan:
        staged = stage_sharded_batch(fs, mesh=mesh, data_axes=axes,
                                     pool_plan=pool_plan)
    V_sh = staged["V_sh"]
    pool = staged["pool"] if pool_plan == "replicated" else V_sh
    scan = make_selection_scan_batched(
        mesh, axes, fn=f0.spec, kind=kind, k=k, top_b=top_b, n_total=n,
        block_m=bm, distance=f0.cfg.distance,
        policy_name=f0.cfg.resolved_policy().name, counter_key=counter_key,
        backend=backend, rbf_gamma=rbf_gamma, pool_plan=pool_plan)
    sel, traj, n_scored, _ = scan(
        V_sh, pool, staged["seed_sh"], staged["aux_sh"], cand_rounds,
        staged["w0"], jnp.asarray(np.asarray(ks, np.int32)))
    return sel, traj, n_scored


# ---------------------------------------------------------------------------
# GreeDi partition-then-merge (plan ``greedi``) — Mirzasoleiman et al.,
# "Distributed Submodular Maximization". Phase 1 runs the single-device
# one-dispatch dense greedy scan on every shard's own V-partition (no
# collectives at all); one O(p·k·d) psum all-gathers the p·k partial
# solutions; phase 2 re-runs the same drive_selection_scan as a merge round
# over that small replicated pool with the cache sharded (one O(p·k) psum
# per merge round). Per-device memory is O(n/p·d) + O(p·k·d).
# ---------------------------------------------------------------------------

_GREEDI_SCAN_CACHE: dict = {}


@contract(
    "distributed.greedi_scan",
    factory=True,
    driving_scans=2,
    collective_kinds=("psum",),
    claim="both GreeDi phases in ONE dispatch (partition greedy + p-"
          "solution global eval + merge greedy); the gathered solution "
          "pool is the largest collective payload — O(p·k·d), never O(n)")
def make_greedi_scan(
    mesh: Mesh,
    data_axes: Sequence[str],
    *,
    fn: FnSpec = FnSpec(),
    k: int,
    n_total: int,
    block_m: int,
    distance: str,
    policy_name: str,
    counter_key: str,
    backend: str = "jnp",
    rbf_gamma: Optional[float] = None,
):
    """Build (and cache) the jitted two-phase GreeDi scan.

    Returns ``run(V_sh, seed_sh, aux_sh, w0) -> (sel, traj, n_scored)``.
    Both phases run inside ONE ``shard_map`` dispatch: phase 1 is the
    single-device scan construction on the local partition (on Pallas
    backends a fused-eligible function's winner fold rides in the fused
    kernel exactly like plan ``device``; gains normalize by the *local* n so
    the partition function is self-consistent — for graph cut the penalty
    normalizer must match the gain normalizer for the argmax to be
    meaningful), driven with ``taken0`` masking the shard's zero-padding
    rows; phase 2 follows Mirzasoleiman et al.'s Alg. 2 in full: the
    gathered p·k partial solutions replicate (k·p·d ≪ n·d, the same budget
    class as the multiset payload), each partition's OWN solution is
    evaluated *globally* (p·k extra sharded folds), and a merge greedy over
    the pool runs under the sharded-cache psum callbacks — the answer is
    whichever of {merged greedy, best single-partition solution} scores
    higher (the "best-of-both" max the proven bound is stated for). The
    merge trajectory is the *global* f(S_t) (cache sharded, psum'd stat), so
    the returned trajectory is directly comparable with every other plan;
    ``n_scored`` sums the partition rounds' actually-scored candidates
    (psum) plus the merge round's plus the p·k global evaluation folds.
    Selections carry the GreeDi partition bound rather than matching
    centralized greedy.
    """
    axes = tuple(data_axes)
    key = (mesh, axes, fn, k, n_total, block_m, distance, policy_name,
           counter_key, backend, rbf_gamma)
    if key in _GREEDI_SCAN_CACHE:
        return _GREEDI_SCAN_CACHE[key]
    policy = resolve_policy(policy_name)
    pair = dist_mod.resolve_pairwise(distance)
    tmpl = fx.kernel_template(fn)
    use_kernel = backend in ("pallas", "pallas_interpret") and tmpl is not None
    if use_kernel:
        from repro.kernels import ops as kops
    p_total = _mesh_extent(mesh, axes)

    def local_scan(V_loc, seed_loc, aux_loc, w0):
        n_loc, d = V_loc.shape
        lin = jax.lax.axis_index(axes)
        off = lin * n_loc
        seedf = seed_loc.astype(jnp.float32)
        cache0 = (seedf, jnp.float32(0.0))
        w0c = (w0.astype(V_loc.dtype), jnp.asarray(-1, jnp.int32))
        psum_ = lambda x: jax.lax.psum(x, axes)  # noqa: E731

        # ---- phase 1: independent dense greedy over the local partition
        # (no collectives at all — local indices, local normalizers; the
        # phase-1 trajectory is partition-local and discarded)
        v0_loc = jnp.mean(fx.stat_rows(fn, seedf, aux_loc))

        def value_local(cache):
            vec, aux = cache
            return fx.value_from_stat(
                fn, v0_loc, jnp.mean(fx.stat_rows(fn, vec, aux_loc)), aux,
                n_loc)

        def fold_local(cache, w):
            vec, aux = cache
            row, idx = w
            dw = pair(V_loc, row[None, :], policy)[:, 0]
            folded = fx.fold_vec_rows(fn, vec, dw.astype(jnp.float32))
            new_aux = fx.fold_aux(fn, vec, aux, idx, 0, n_loc)
            ok = idx >= 0
            return (jnp.where(ok, folded, vec), jnp.where(ok, new_aux, aux))

        def score_local(vec, C, n_norm):
            sc = fx.score_cache_rows(fn, vec, aux_loc)
            if use_kernel:
                return kops.marginal_gain(
                    V_loc, C, sc, policy=policy, rbf_gamma=rbf_gamma,
                    fold=tmpl[0], score_affine=tmpl[1],
                    interpret=(backend != "pallas"), n_total=n_norm)
            return _score_blocked(V_loc, C, sc, pair, policy, block_m,
                                  n_total=n_norm, fn=fn, row_aux=aux_loc)

        if use_kernel and fx.kernel_fused_ok(fn):

            def fold_score_local(cache, w_prev, cand_t):
                vec, aux = cache
                row, idx = w_prev
                g, vec2 = kops.fused_gain_update(
                    V_loc, V_loc[cand_t], vec, row, policy=policy,
                    rbf_gamma=rbf_gamma, fold=tmpl[0], score_affine=tmpl[1],
                    interpret=(backend != "pallas"),
                    w_valid=(idx >= 0).astype(jnp.float32))
                cache2 = (vec2, aux)
                return g, cache2, value_local(cache2)
        else:

            def fold_score_local(cache, w_prev, cand_t):
                cache2 = fold_local(cache, w_prev)
                vec2, _aux2 = cache2
                g = score_local(vec2, V_loc[cand_t], None)
                extra = fx.gains_index_extra(fn, vec2, cand_t, 0, n_loc,
                                             n_loc)
                g = g if extra is None else g + extra
                return g, cache2, value_local(cache2)

        pad_taken = (jnp.arange(n_loc, dtype=jnp.int32) + off) >= n_total
        sel1, _, nsc1 = drive_selection_scan(
            kind="dense", k=k, top_b=0, n_global=n_total, pool=V_loc,
            taken0=pad_taken,
            cand_rounds=jnp.arange(n_loc, dtype=jnp.int32)[None, :],
            cache0=cache0, w0=w0c, fold=fold_local,
            fold_score_val=fold_score_local, value_of=value_local)

        # ---- all-gather the p·k partial solutions: each shard owns one
        # slot of the (p, k, ·) buffers, one psum fills them all
        sel1 = sel1.astype(jnp.int32)
        slot = jnp.arange(p_total, dtype=jnp.int32) == lin
        merged_vec = jax.lax.psum(
            jnp.where(slot[:, None, None], V_loc[sel1][None], 0),
            axes).reshape(p_total * k, d)
        merged_idx = jax.lax.psum(
            jnp.where(slot[:, None], (sel1 + off)[None], 0),
            axes).reshape(p_total * k)
        nsc1_total = jax.lax.psum(nsc1, axes)

        # ---- global cache machinery shared by the local-solution
        # evaluation and the merge greedy
        v0g = jax.lax.psum(
            jnp.sum(fx.stat_rows(fn, seedf, aux_loc)), axes) / n_total

        def value_global(cache):
            vec, aux = cache
            mean_stat = jax.lax.psum(
                jnp.sum(fx.stat_rows(fn, vec, aux_loc)) / n_total, axes)
            return fx.value_from_stat(fn, v0g, mean_stat, aux, n_total)

        def fold_global(cache, w):
            vec, aux = cache
            row, gidx = w
            dw = pair(V_loc, row[None, :], policy)[:, 0]
            folded = fx.fold_vec_rows(fn, vec, dw.astype(jnp.float32))
            new_aux = fx.fold_aux(fn, vec, aux, gidx, off, n_loc,
                                  psum=psum_)
            ok = gidx >= 0
            return (jnp.where(ok, folded, vec), jnp.where(ok, new_aux, aux))

        def psum_gains_val(g_part, cache):
            vec, aux = cache
            payload = jnp.concatenate(
                [g_part.astype(jnp.float32),
                 (jnp.sum(fx.stat_rows(fn, vec, aux_loc)) / n_total)[None]])
            out = jax.lax.psum(payload, axes)
            return out[:-1], fx.value_from_stat(fn, v0g, out[-1], aux,
                                                n_total)

        # ---- evaluate each partition's solution GLOBALLY (best-of-both):
        # p·k extra folds against fresh sharded caches; every shard runs the
        # identical p·k fold/value collectives, so the psums stay uniform
        rows_pk = merged_vec.reshape(p_total, k, d)
        idx_pk = merged_idx.reshape(p_total, k)

        def eval_solution(args):
            rows_q, idx_q = args

            def body(cache, wt):
                row_t, idx_t = wt
                cache = fold_global(cache, (row_t, idx_t))
                return cache, value_global(cache)

            _, vals = jax.lax.scan(body, cache0, (rows_q, idx_q))
            return vals

        local_trajs = jax.lax.map(eval_solution, (rows_pk, idx_pk))  # (p, k)
        best_q = jnp.argmax(local_trajs[:, -1])
        best_local_val = local_trajs[best_q, -1]

        # ---- merge greedy over the gathered pool, cache sharded
        if use_kernel and fx.kernel_fused_ok(fn):

            def fold_score_merge(cache, w_prev, cand_t):
                vec, aux = cache
                row, gidx = w_prev
                g_part, vec2 = kops.fused_gain_update(
                    V_loc, merged_vec[cand_t], vec, row, policy=policy,
                    rbf_gamma=rbf_gamma, fold=tmpl[0], score_affine=tmpl[1],
                    interpret=(backend != "pallas"), n_total=n_total,
                    w_valid=(gidx >= 0).astype(jnp.float32))
                cache2 = (vec2, aux)
                gains, val = psum_gains_val(g_part, cache2)
                return gains, cache2, val
        else:

            def fold_score_merge(cache, w_prev, cand_t):
                cache2 = fold_global(cache, w_prev)
                vec2, _aux2 = cache2
                g = score_local(vec2, merged_vec[cand_t], n_total)
                extra = fx.gains_index_extra(
                    fn, vec2, merged_idx[cand_t], off, n_loc, n_total)
                g = g if extra is None else g + extra
                gains, val = psum_gains_val(g, cache2)
                return gains, cache2, val

        sel2, traj2, nsc2 = drive_selection_scan(
            kind="dense", k=k, top_b=0, n_global=n_total,
            take=lambda j: (merged_vec[j], merged_idx[j]),
            n_pool=p_total * k,
            cand_rounds=jnp.arange(p_total * k, dtype=jnp.int32)[None, :],
            cache0=cache0, w0=w0c, fold=fold_global,
            fold_score_val=fold_score_merge, value_of=value_global)

        # ---- best-of-both: return whichever of (merged greedy, best
        # single-partition solution) scores higher globally; ties keep the
        # merged answer (strict >)
        use_local = best_local_val > traj2[-1]
        sel_out = jnp.where(use_local, idx_pk[best_q], merged_idx[sel2])
        traj_out = jnp.where(use_local, local_trajs[best_q], traj2)
        n_scored = nsc1_total + nsc2 + jnp.asarray(p_total * k, jnp.int32)
        return sel_out, traj_out, n_scored

    smapped = shard_map(
        local_scan,
        mesh=mesh,
        in_specs=(P(axes, None), P(axes), P(axes), P(None)),
        out_specs=(P(None), P(None), P()),  # sel, traj, scalar n_scored
        check_vma=False,
    )

    @jax.jit
    def run(V_sh, seed_sh, aux_sh, w0):
        DEVICE_TRACE_COUNTS[counter_key] += 1
        return smapped(V_sh, seed_sh, aux_sh, w0)

    _GREEDI_SCAN_CACHE[key] = run
    return run


def run_greedi_selection(
    f,                       # SubmodularFunction (untyped: avoids circularity)
    w0: jax.Array,
    *,
    k: int,
    counter_key: str,
    block_m: Optional[int] = None,
    mesh: Optional[Mesh] = None,
    data_axes: Sequence[str] = ("data",),
    backend: str = "jnp",
    rbf_gamma: Optional[float] = None,
):
    """Place operands and run the GreeDi partition-then-merge scan.

    Every partition must hold at least k *real* (non-padding) rows — each
    runs an independent k-round greedy whose argmax would otherwise run out
    of candidates. Returns ``(sel, traj, n_scored)`` device arrays.
    """
    mesh = _resolve_mesh(mesh, data_axes)
    axes = tuple(data_axes)
    ndev = _mesh_extent(mesh, axes)
    n = f.n
    n_pad = ((n + ndev - 1) // ndev) * ndev
    n_loc = n_pad // ndev
    tail_real = n - (ndev - 1) * n_loc
    if tail_real < k:
        raise ValueError(
            f"greedi partitions V into {ndev} shards of {n_loc} rows; the "
            f"last shard holds only {tail_real} real rows, fewer than k={k}"
            f" — its partition greedy would run out of candidates")
    bm = block_m if block_m is not None \
        else _device_block_m(n_loc, n_loc, mesh_tiles_per_memory(mesh))
    entry = _placed_sharded(f, mesh, axes, replicated_pool=False)
    scan = make_greedi_scan(
        mesh, axes, fn=f.spec, k=k, n_total=n, block_m=bm,
        distance=f.cfg.distance, policy_name=f.cfg.resolved_policy().name,
        counter_key=counter_key, backend=backend, rbf_gamma=rbf_gamma)
    return scan(entry["V_sh"], entry["seed_sh"], entry["aux_sh"], w0)


def distributed_greedy(
    mesh: Mesh,
    V: jax.Array,
    k: int,
    cfg: EvalConfig = EvalConfig(),
    data_axes: Sequence[str] = ("data",),
    candidate_batch: Optional[int] = None,
) -> tuple[list[int], float]:
    """Pod-scale greedy: V sharded over data axes, one psum per step.

    A thin wrapper over the selection engine's ``device_sharded`` plan (all
    k rounds in one dispatch). ``candidate_batch`` bounds the per-shard
    candidate *compute tile* (default: autotuned from the probed gain-tile
    cap) — candidates stream through (n_loc, batch) tiles, so no shard
    materializes an (n_loc, n) distance block. Note the engine replicates
    the candidate pool (all of V) per device, so resident memory is
    O(n·d) + O(n/p·d) per chip — unlike the pre-engine host-streamed loop;
    see the "sharded candidate pool" ROADMAP item. Returns
    (indices, f value).

    Like the original implementation, scoring always runs the jnp pairwise
    path regardless of ``cfg.backend`` (kernel backends are normalized away
    rather than rejected).
    """
    import dataclasses

    from repro.core.functions import ExemplarClustering
    from repro.core.optimizers import greedy

    if cfg.backend in ("pallas", "pallas_interpret"):
        cfg = dataclasses.replace(cfg, backend="jnp")
    f = ExemplarClustering(jnp.asarray(V), cfg)
    res = greedy(f, k, mode="device_sharded", mesh=mesh, data_axes=data_axes,
                 block_m=candidate_batch)
    return res.indices, res.value
