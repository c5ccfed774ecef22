"""The unified selection-engine layer (tentpole, beyond paper).

Every optimizer in the greedy family is the same machine viewed through two
orthogonal choices:

* a **round-candidate strategy** — which candidates get scored each round:

  - ``dense``       every (validated) candidate, every round; one candidate
                    row broadcast over all k rounds.
  - ``stochastic``  k pre-sampled candidate rows (one per round), drawn up
                    front so host and device paths consume identical
                    randomness.
  - ``lazy`` (CELF) stale upper bounds carried as an (n,) array; each round
                    re-scores the top-B stale candidates (``jax.lax.top_k``
                    inside the scan carry) and falls back to a full re-score
                    when the fresh-top invariant fails.

* an **execution plan** — where the rounds run:

  - ``host``           reference Python loop (one dispatch per round).
  - ``device``         all k rounds inside ONE jitted ``jax.lax.scan``
                       dispatch; gains, argmax and cache update never leave
                       the accelerator.
  - ``device_sharded`` the same scan, row-sharding V *and* the min-distance
                       cache over a device mesh via ``shard_map``. Per round,
                       each shard computes its (m,) gain partials and one
                       ``psum`` of O(m) bytes reduces them; the argmax (and
                       the CELF bound state) stays replicated.

The scan always runs B independent requests with a leading request axis
on every operand and carry; one request is B = 1 (:func:`run_selection`),
a bucket of tenants is B > 1 (:func:`run_selection_batch`).

The min-distance cache recurrence (see :mod:`repro.core.optimizers`) is the
shared substrate: a round is one (n × m) distance evaluation plus an O(n)
fold of the winner. On Pallas backends the fold rides inside the fused gain
kernel (:func:`repro.kernels.ops.fused_gain_update`), so the winner's
distance column never materializes in HBM.

CELF on device: submodularity means gains only shrink, so last round's gains
are upper bounds for this round. The scan carries those bounds as an (n,)
array; each round an inner ``jax.lax.while_loop`` re-scores the top-B stale
bounds and stops as soon as the fresh-top invariant certifies the winner —
*best fresh gain ≥ every remaining stale bound* ⇒ the fresh best is the true
argmax. When staleness defeats the shortcut the loop keeps taking the next
top-B batch, degenerating to a full re-score after ⌈n/B⌉ iterations — the
device mirror of the host CELF heap's pop-rescore-repeat, without the
per-batch host↔device round-trips.
"""
from __future__ import annotations

import collections
import dataclasses
from functools import partial
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.contracts import contract
from repro.core import distances as dist_mod
from repro.core import functions as fx
from repro.core import tracing
from repro.core.evaluator import free_memory_bytes
from repro.core.functions import FnSpec, SubmodularFunction
from repro.core.precision import resolve as resolve_policy


@dataclasses.dataclass
class OptResult:
    indices: list[int]
    value: float
    trajectory: list[float]
    evaluations: int
    #: CELF's re-score trips: iterations of the lazy step's while loop,
    #: summed over the rounds (0 for the dense and stochastic strategies)
    rescore_trips: int = 0

    def exemplars(self, V) -> np.ndarray:
        return np.asarray(V)[self.indices]


#: Number of times each device engine has been *traced* (not dispatched).
#: A second run with identical shapes/statics must not increment these —
#: that is the "exactly one jitted dispatch for all k rounds" property.
DEVICE_TRACE_COUNTS: collections.Counter = collections.Counter()

#: Fraction of probed free device memory the gain tile may occupy.
GAIN_TILE_MEMORY_FRACTION = 0.25


def validate_candidates(candidates, n: int) -> np.ndarray:
    """Validate a candidate-index subset at the engine boundary.

    Out-of-range indices raise; duplicates are dropped keeping first
    occurrence (a duplicated index would otherwise be scored twice and could
    even be *selected* twice by the device argmax, which masks ``taken`` by
    index, not by position).
    """
    cand = np.asarray(candidates).reshape(-1)
    if not np.issubdtype(cand.dtype, np.integer):
        raise ValueError(
            f"candidate indices must be integers, got dtype {cand.dtype}")
    cand = cand.astype(np.int64)
    if cand.size == 0:
        raise ValueError("candidates must be non-empty")
    if cand.min() < 0 or cand.max() >= n:
        raise ValueError(
            f"candidate indices must lie in [0, {n}), got range "
            f"[{cand.min()}, {cand.max()}]")
    _, first = np.unique(cand, return_index=True)
    return cand[np.sort(first)]


_GAIN_TILE_CAP_ELEMS: Optional[int] = None


def _gain_tile_cap_elems(itemsize: int = 4) -> int:
    """Max gain-tile elements, probed ONCE per process and then frozen.

    The result feeds jit *static* arguments (``block_m``), so it must not
    float with live allocator state — a per-call probe would hand every
    dispatch a slightly different block size and force a retrace each time.
    One probe at first use captures the device's capacity class; backends
    without memory stats (CPU) fall back to the 128 MiB heuristic (2^25
    float32 elements).
    """
    global _GAIN_TILE_CAP_ELEMS
    if _GAIN_TILE_CAP_ELEMS is None:
        free = free_memory_bytes()
        if free is not None:
            _GAIN_TILE_CAP_ELEMS = max(
                int(free * GAIN_TILE_MEMORY_FRACTION) // itemsize, 1)
        else:
            _GAIN_TILE_CAP_ELEMS = 1 << 25
    return _GAIN_TILE_CAP_ELEMS


def _device_block_m(n: int, m: int, tiles_per_memory: int = 1,
                    n_batch: int = 1) -> int:
    """Candidate block size bounding the (n, Bm) gain tile.

    Autotuned from the same free-memory probe ``plan_chunks`` uses
    (:func:`repro.core.evaluator.free_memory_bytes`), frozen at first use
    (see :func:`_gain_tile_cap_elems`). The floor of 8 (one TPU sublane)
    lets the cap be exceeded only at ground-set sizes where chunking V
    itself is the right tool.

    ``n`` must be the height of the tile that actually materializes — the
    *local shard* height n/p under the sharded plans, never the global n
    (sizing from global n under-fills every shard's memory by p×).
    ``tiles_per_memory`` divides the probed cap when several shards' tiles
    coexist in ONE physical memory space (forced host devices share the
    host allocator: p live tiles would over-commit the probe's free-bytes
    answer p×); real multi-chip meshes keep the default of 1 because each
    shard's tile lives in its own device memory.
    ``n_batch`` scales the effective tile height: the batched engine scores
    B requests' (n, Bm) tiles in ONE dispatch, so the live footprint is
    (B·n, Bm) — sizing a B=1024 bucket as if B=1 would over-commit memory
    B× (the same failure mode as sizing a shard tile from global n).
    """
    cap_elems = _gain_tile_cap_elems() // max(tiles_per_memory, 1)
    rows = n * max(n_batch, 1)
    if rows * m <= cap_elems:
        return m
    return max(8, min(m, cap_elems // max(rows, 1)))


def mesh_tiles_per_memory(mesh) -> int:
    """How many of ``mesh``'s shards carve tiles out of one memory space.

    Forced host devices (``--xla_force_host_platform_device_count``) all
    allocate from the same host RAM the free-memory probe measured, so a
    p-device mesh runs p concurrent gain tiles against one pool;
    accelerator meshes place one tile per device memory.
    """
    devs = list(mesh.devices.flat)
    if devs and devs[0].platform == "cpu":
        return len(devs)
    return 1


# ---------------------------------------------------------------------------
# Scoring core shared by the device and device_sharded plans
# ---------------------------------------------------------------------------


def _score_blocked(V, C, sc, pair, policy, block_m: int,
                   n_total: Optional[int] = None, fn: FnSpec = FnSpec(),
                   row_aux=None) -> jax.Array:
    """Gains of candidate payload C against score-cache rows ``sc`` in
    (n, block_m) tiles.

    Streams candidates in blocks so the distance tile stays memory-bounded;
    ``functions.gains_formula_spec`` is shared with the host path, which
    keeps the per-column reduction (and hence the argmax) identical. The
    index-addressed extra term (graph cut's penalty) is NOT included —
    callers that know the candidates' global indices add
    ``functions.gains_index_extra`` outside the payload blocking.
    """
    mc, d = C.shape
    bm = min(block_m, mc)
    m_pad = ((mc + bm - 1) // bm) * bm
    Cp = jnp.pad(C, ((0, m_pad - mc), (0, 0)))
    blocks = Cp.reshape(-1, bm, d)
    gains = jax.lax.map(
        lambda Cb: fx.gains_formula_spec(fn, V, Cb, sc, row_aux, pair,
                                         policy, n_total=n_total),
        blocks,
    ).reshape(-1)
    return gains[:mc]


# ---------------------------------------------------------------------------
# Shared round-step builders — the ONE definition of a selection round,
# consumed by the single-device scan below and the mesh-sharded scans in
# repro.core.distributed (which differ only in their score/fold callbacks).
#
# Every carry leaf has a leading request axis B ((B, n) caches, (B, n) taken
# masks, (B, d) winner rows, (B, n) CELF bounds); one request is B = 1.
# Ragged k rides as a per-request ``k_eff`` vector: rounds t ≥ k_eff[b]
# freeze request b's carry (its transient fold still produces the correct
# trajectory value f(S_{k_eff})), emit the −1 sentinel, and count zero
# evaluations — so bucket-padding slots (k_eff = 0) are completely inert,
# and each request's selections, trajectory and evaluation count are those
# of the same request run alone.
# ---------------------------------------------------------------------------


def _freeze_where(act, new, old):
    """Per-request carry gate: take ``new`` leaves where the request is
    active, keep ``old`` where it is frozen (``act`` is (B,) bool; every
    leaf carries a leading B axis)."""
    return jax.tree_util.tree_map(
        lambda a, b: jnp.where(
            act.reshape(act.shape + (1,) * (a.ndim - 1)), a, b),
        new, old)


def make_rounds_step(take, fold_score_val, k_eff):
    """Dense/stochastic scan step over per-round candidate index rows.

    ``fold_score_val(cache, w_prev, cand_t) -> (gains (B, m), cache,
    value (B,))`` folds each request's previous winner and scores its own
    candidate row; how the candidate *payload* materializes is the plan's
    business (single-device: one gather from the resident pool; sharded
    pool: index blocks psum-materialized from their owning shards, never
    all at once). ``take(idx (B,)) -> ((B, d) rows, idx)`` resolves the
    per-request winners to the next round's ``(payload row, global index)``
    carry, whose index feeds the gated fold (and index-addressed aux
    state). The ``cache`` is the function's ``(vec, aux)`` pytree.
    ``k_eff`` (B,) int32 is the ragged-k mask: the xs carry the round index
    t, and requests with t ≥ k_eff freeze.
    """
    B = k_eff.shape[0]
    rows = jnp.arange(B)

    def step(carry, xs):
        cand_t, t = xs
        cache, taken, w_prev = carry
        gains, cache2, val = fold_score_val(cache, w_prev, cand_t)
        live = ~jnp.take_along_axis(taken, cand_t, axis=1, mode="clip")
        gains = jnp.where(live, gains, -jnp.inf)
        p = jnp.argmax(gains, axis=1)
        j = jnp.take_along_axis(cand_t, p[:, None], axis=1)[:, 0]
        best = jnp.take_along_axis(gains, p[:, None], axis=1)[:, 0]
        act = t < k_eff
        # a round whose candidates are all taken has no legitimate argmax:
        # emit the -1 sentinel (the engine boundary raises on it) instead of
        # silently re-selecting whatever index argmax fell through to;
        # frozen rounds also emit −1 (demux truncates them away)
        j_out = jnp.where(act & (best > -jnp.inf), j, -1)
        new_carry = (cache2, taken.at[rows, j].set(True), take(j))
        carry = _freeze_where(act, new_carry, carry)
        scored = jnp.where(act, jnp.sum(live, axis=1).astype(jnp.int32), 0)
        # cache includes winners 0..t-1 here → val is trajectory[t-1]
        return carry, (j_out, val, scored)

    return step


def celf_max_iters(n: int, top_b: int) -> int:
    """CELF while-loop backstop shared by every execution plan: ⌈n/B⌉
    iterations re-score every candidate (the loop has then degenerated to a
    full re-score), +1 slack. The sharded plans' per-iteration psums only
    line up across shards because every plan agrees on this bound."""
    return -(-n // top_b) + 1


def make_lazy_step(take, fold, score_idx_val, top_b: int, max_iters: int,
                   k_eff):
    """CELF scan step: while-loop of top-B re-scoring over stale bounds.

    ``fold(cache, w) -> cache`` folds the previous ``(rows, idx)`` winners
    once per round (gated internally on idx ≥ 0);
    ``score_idx_val(cache, idx (B, m)) -> ((B, m) gains, (B,) value)``
    scores candidate *indices* against the already-folded cache (mesh
    plans: every request's gain partials and its stat row-sum cross the
    mesh in ONE O(B·m) psum per re-score iteration, with no separate value
    collective; the sharded pool streams blocked takes so the transient
    block never exceeds the resident shard); ``take(idx)`` resolves the
    winners (sharded pool: one psum materializing only those columns — the
    bound state itself stays a replicated (B, n) scalar array, never an
    (n, d) payload).

    Per round the step emits ``(winner, value, scored, trips)``:
    ``trips`` counts the iterations in which the request re-scored a
    batch (every iteration while it is active; 0 for a frozen round).

    Each request carries its own stale bounds and freshness, and the
    while loop runs while ANY active request fails the fresh-top invariant
    — best re-scored gain ≥ every remaining stale bound — or, on the
    first iteration, always: the value part of ``score_idx_val`` comes from
    the (loop-invariant) folded cache, so that iteration yields every
    request's f(S_t), frozen ones included. A certified (or frozen) request
    stops scoring at once (its ``live`` lanes mask out), so its evaluation
    count is the same as alone: within a round a request is active for
    consecutive iterations, and the global ``max_iters`` backstop cuts every
    request at the same iteration, because certification is monotone
    within a round. A full re-score follows after ⌈n/top_b⌉ iterations.
    """
    B = k_eff.shape[0]
    rows = jnp.arange(B)[:, None]

    def step(carry, t):
        cache, taken, w_prev, ub = carry
        cache2 = fold(cache, w_prev)
        act = t < k_eff

        def request_active(ub_c, fresh):
            stale_max = jnp.max(
                jnp.where(fresh | taken, -jnp.inf, ub_c), axis=1)
            fresh_best = jnp.max(
                jnp.where(fresh & ~taken, ub_c, -jnp.inf), axis=1)
            return (fresh_best < stale_max) & act

        def invariant_fails(st):
            ub_c, fresh, _, _, _, it = st
            return (jnp.any(request_active(ub_c, fresh)) | (it == 0)) \
                & (it < max_iters)

        def rescore_top_b(st):
            ub_c, fresh, scored, trips, _, it = st
            active = request_active(ub_c, fresh)
            stale = jnp.where(fresh | taken, -jnp.inf, ub_c)
            top_ub, top_idx = jax.lax.top_k(stale, top_b)
            live = (top_ub > -jnp.inf) & active[:, None]
            gains_b, val = score_idx_val(cache2, top_idx)
            gains_b = jnp.where(live, gains_b, -jnp.inf)
            prev = jnp.take_along_axis(ub_c, top_idx, axis=1)
            ub_c = ub_c.at[rows, top_idx].set(
                jnp.where(live, gains_b, prev))
            fresh = fresh.at[rows, top_idx].set(
                jnp.take_along_axis(fresh, top_idx, axis=1) | live)
            scored = scored + jnp.sum(live, axis=1).astype(jnp.int32)
            trips = trips + active.astype(jnp.int32)
            return ub_c, fresh, scored, trips, val, it + 1

        zeros = jnp.zeros((B,), jnp.int32)
        ub2, fresh, scored, trips, val, _ = jax.lax.while_loop(
            invariant_fails, rescore_top_b,
            (ub, jnp.zeros(taken.shape, bool), zeros, zeros,
             jnp.zeros((B,), jnp.float32), jnp.asarray(0, jnp.int32)))
        j = jnp.argmax(jnp.where(fresh & ~taken, ub2, -jnp.inf), axis=1)
        new_carry = (cache2, taken.at[rows[:, 0], j].set(True), take(j), ub2)
        carry = _freeze_where(act, new_carry, carry)
        # cache includes winners 0..t-1 here → val is trajectory[t-1]
        return carry, (jnp.where(act, j, -1), val,
                       jnp.where(act, scored, 0), jnp.where(act, trips, 0))

    return step


# ---------------------------------------------------------------------------
# Shared scan driver — ub0 seeding, n_scored accounting, final fold, and
# trajectory concat live here once; every plan supplies only callbacks.
# ---------------------------------------------------------------------------


def drive_selection_scan(*, kind, k, top_b, n_global, k_eff, pool=None,
                         take=None, n_pool=None, taken0=None, seed_val=None,
                         cand_rounds, cache0, w0, fold, score_idx_val=None,
                         fold_score_val=None, value_of=None):
    """Run k selection rounds of B requests for any execution plan, given
    its callbacks.

    The plan supplies only how a candidate batch is scored and how the
    winner folds into the (possibly sharded) cache; everything else — CELF's
    ub0 bound seeding, the dense one-row closure vs the stochastic per-round
    scan xs, ``n_scored`` accounting, the final fold, and the trajectory
    concat — is plan-independent and lives here, once. The cache is the
    function's ``(vec, aux)`` pytree with a leading request axis; the
    winner carry is a ``((B, d) payload rows, (B,) global indices)`` pair
    whose index is −1 before round 0 (folds gate on it — the max/additive
    folds of the function zoo are not idempotent).

    ``cand_rounds`` is (B, k, m) (dense: (B, 1, m), one row closed over by
    every round, never materialized k times; lazy: (B, 1, 0)); ``k_eff``
    (B,) int32 the per-request effective k (ragged-k masking —
    bucket-padding slots pass 0). The candidate payload is addressed
    through ``take(idx (B,)) -> ((B, d) rows, idx)``: pass a resident
    (B, n, d) ``pool`` (``take`` defaults to each request's own row) or an
    explicit ``take`` + ``n_pool`` when no plan-wide payload exists
    (sharded pool: ``take`` psum-materializes the requested columns from
    their owning shards) or when pool-local and global indices differ
    (GreeDi's merge round). ``taken0`` (B, n_pool) optionally pre-marks
    pool rows as taken (GreeDi partitions mask their zero-padding rows this
    way); ``seed_val`` overrides CELF's ub0 seeding pass (sharded pool:
    blocked take-and-score, so no transient ever exceeds the resident
    shard).

    Callbacks (single-device: plain jnp/kernel ops; sharded: the same ops
    on the local shard with ONE psum per scored batch riding the gains):

    * ``fold(cache, w) -> cache`` — fold the winners ``(rows, idx)`` into
      the cache (used per lazy round and for the final trajectory point),
      gated internally on idx ≥ 0.
    * ``score_idx_val(cache, idx (B, m)) -> (gains (B, m), value (B,))`` —
      score candidate indices against the already-folded cache (lazy
      rescore + ub0 seeding).
    * ``fold_score_val(cache, w_prev, cand_t) -> (gains, cache, value)`` —
      the fused dense/stochastic round step over the round's candidate
      *indices* (on Pallas backends the fold rides inside the gain kernel;
      sharded pool: blocked take-and-score).
    * ``value_of(cache) -> (B,)`` — the global f(S) of each cache.

    Returns ``(sel (k, B), traj (k, B), n_scored (B,), n_trips (B,),
    final cache)`` — ``n_trips`` sums CELF's re-score trips over the rounds
    (0 for the dense and stochastic strategies); the final cache rides out
    so a jitted dispatch can alias its vec onto the donated seed buffer.
    """
    B = k_eff.shape[0]
    if take is None:
        rows = jnp.arange(B)
        take = lambda idx: (pool[rows, idx], idx)  # noqa: E731
        n_pool = pool.shape[1]
    taken_init = taken0 if taken0 is not None \
        else jnp.zeros((B, n_pool), bool)
    ts = jnp.arange(k, dtype=jnp.int32)
    if kind == "lazy":
        step = make_lazy_step(take, fold, score_idx_val, top_b,
                              celf_max_iters(n_global, top_b), k_eff)
        # round -1: per-request singleton gains seed the bounds (counts one
        # eval per pool row for every request that runs ≥ 1 round, exactly
        # like host CELF's initial full scoring)
        if seed_val is not None:
            ub0, _ = seed_val(cache0)
        else:
            ub0, _ = score_idx_val(cache0, jnp.broadcast_to(
                jnp.arange(n_pool, dtype=jnp.int32), (B, n_pool)))
        init = (cache0, taken_init, w0, ub0)
        (cache, _, w_last, _), (sel, vals, scored, trips) = jax.lax.scan(
            step, init, ts)
        n_scored = jnp.where(
            k_eff > 0,
            jnp.asarray(n_pool, jnp.int32) + jnp.sum(scored, axis=0), 0)
        n_trips = jnp.sum(trips, axis=0)
    else:
        step = make_rounds_step(take, fold_score_val, k_eff)
        init = (cache0, taken_init, w0)
        if kind == "dense":
            cand_row = cand_rounds[:, 0, :]
            (cache, _, w_last), (sel, vals, scored) = jax.lax.scan(
                lambda carry, t: step(carry, (cand_row, t)), init, ts)
        else:
            (cache, _, w_last), (sel, vals, scored) = jax.lax.scan(
                step, init, (jnp.swapaxes(cand_rounds, 0, 1), ts))
        n_scored = jnp.sum(scored, axis=0)
        n_trips = jnp.zeros((B,), jnp.int32)

    # one final fold for the last trajectory point (frozen requests fold
    # their held winner transiently — still exactly f(S_{k_eff}))
    cache_f = fold(cache, w_last)
    final_val = value_of(cache_f)
    traj = jnp.concatenate([vals[1:], final_val[None, :]], axis=0)
    return (sel.astype(jnp.int32), traj, n_scored.astype(jnp.int32),
            n_trips.astype(jnp.int32), cache_f)


# ---------------------------------------------------------------------------
# Single-device one-dispatch scan (plan: device)
# ---------------------------------------------------------------------------


@contract(
    "engine.select_scan",
    donate=("seed",),
    memory=True,
    claim="all k rounds of B independent requests in ONE dispatch; "
          "collective-free; the (B, n) cache seed is donated and aliased "
          "onto the final cache output; gains stay in the compute dtype; "
          "per-request temp bytes stay at blocked-tile scale")
@partial(jax.jit, static_argnames=("fn", "kind", "k", "top_b", "distance",
                                   "policy_name", "block_m", "backend",
                                   "rbf_gamma", "counter_key"),
         donate_argnums=(1,))
def _select_scan(V, seed, row_aux, cand_rounds, w0, k_eff, *, fn, kind, k,
                 top_b, distance, policy_name, block_m, backend, rbf_gamma,
                 counter_key):
    """All k selection rounds of B requests in one dispatch, for any
    vec-cache function; one request is B = 1.

    ``V (B, n, d)``, ``seed / row_aux (B, n)``, ``cand_rounds (B, k, m)``,
    ``w0 (B, d)``, ``k_eff (B,)``. ``fn`` is the function's static
    :class:`~repro.core.functions.FnSpec`; ``seed``/``row_aux`` its cache
    seeds and per-row auxiliaries. The identical cache-semantics helpers
    the host protocol methods use are re-traced here around the scan (they
    broadcast over the leading axis; the two index-addressed ones, graph
    cut's ``gains_index_extra`` / ``fold_aux`` gathers, vmap per request),
    which is what makes host and device selections agree.

    ``seed`` is DONATED and the final folded (B, n) float32 cache vector
    rides out as the last output, so XLA aliases the carry's final buffer
    onto the seed's allocation: repeated same-signature calls (warm-bucket
    serving) reuse the cache buffer instead of allocating a fresh one per
    dispatch. Callers therefore pass a freshly-built seed
    (``f.cache_seed`` may alias the function's resident ``d_e0``).

    ``cand_rounds`` holds the candidate indices: (B, 1, m) for dense (ONE
    row a request, closed over by every round), (B, k, m) for stochastic
    (pre-sampled per round), (B, 1, 0) for lazy, which derives its
    candidates from the carried stale bounds. The winner is folded into the
    cache at the *start* of the next round (gated on idx ≥ 0 — round 0 has
    no winner and the max/additive folds are not idempotent) — for
    dense/stochastic on the Pallas backend with a fused-eligible function
    the fold rides inside the fused gain kernel (:mod:`repro.kernels.ops`)
    so the winner's distance column never re-materializes in HBM; lazy
    folds once explicitly because its while-loop re-scores variable
    candidate batches against the already-folded cache. Without a kernel,
    scoring is a vmapped :func:`_score_blocked`.

    Per-round ys are ``(selected index, trajectory value, #actually-scored
    candidates)`` — the last is the engine's honest ``evaluations`` unit —
    and, for lazy, CELF's re-score trips. Returns ``(sel (k, B), traj
    (k, B), n_scored (B,), n_trips (B,), cache (B, n))``.
    """
    DEVICE_TRACE_COUNTS[counter_key] += 1
    policy = resolve_policy(policy_name)
    pair = dist_mod.resolve_pairwise(distance)
    n = V.shape[1]
    seedf = seed.astype(jnp.float32)
    v0 = jnp.mean(fx.stat_rows(fn, seedf, row_aux), axis=1)

    def value_of(cache):
        vec, aux = cache
        return fx.value_from_stat(
            fn, v0, jnp.mean(fx.stat_rows(fn, vec, row_aux), axis=1),
            aux, n)

    def pair_rows(w_rows):
        # per-request distance of each request's V to its own winner row
        return jax.vmap(lambda Vb, r: pair(Vb, r[None, :], policy)[:, 0])(
            V, w_rows)

    def fold(cache, w):
        vec, aux = cache
        row, idx = w
        dw = pair_rows(row)
        folded = fx.fold_vec_rows(fn, vec, dw.astype(jnp.float32))
        new_aux = jax.vmap(
            lambda v, a, g: fx.fold_aux(fn, v, a, g, 0, n))(vec, aux, idx)
        ok = idx >= 0
        return (jnp.where(ok[:, None], folded, vec),
                jnp.where(ok, new_aux, aux))

    tmpl = fx.kernel_template(fn)
    if backend != "jnp" and tmpl is not None:
        from repro.kernels import ops as kops

        def score(sc, C):
            return kops.marginal_gain(
                V, C, sc, policy=policy, rbf_gamma=rbf_gamma,
                fold=tmpl[0], score_affine=tmpl[1],
                interpret=(backend != "pallas"))
    else:

        def score(sc, C):
            return jax.vmap(
                lambda Vb, Cb, scb, rb: _score_blocked(
                    Vb, Cb, scb, pair, policy, block_m, fn=fn, row_aux=rb)
            )(V, C, sc, row_aux)

    def score_idx(cache, idx):
        vec, _aux = cache
        # "clip" indexes like V[idx]: the default "fill" would add a select
        # over the whole gathered block
        C = jnp.take_along_axis(V, idx[..., None], axis=1, mode="clip")
        gains = score(fx.score_cache_rows(fn, vec, row_aux), C)
        if fx.gains_index_extra(fn, vec[0], idx[0], 0, n, n) is None:
            return gains
        extra = jax.vmap(
            lambda v, ix: fx.gains_index_extra(fn, v, ix, 0, n, n))(vec, idx)
        return gains + extra

    def score_idx_val(cache, idx):
        return score_idx(cache, idx), value_of(cache)

    fold_score_val = None
    if kind != "lazy":
        if backend != "jnp" and fx.kernel_fused_ok(fn) and tmpl is not None:

            def fold_score_val(cache, w_prev, cand_t):
                vec, aux = cache
                row, idx = w_prev
                C = jnp.take_along_axis(V, cand_t[..., None], axis=1,
                                        mode="clip")
                gains, vec2 = kops.fused_gain_update(
                    V, C, vec, row, policy=policy, rbf_gamma=rbf_gamma,
                    fold=tmpl[0], score_affine=tmpl[1],
                    w_valid=(idx >= 0).astype(jnp.float32),
                    interpret=(backend != "pallas"))
                cache2 = (vec2, aux)  # fused-eligible functions carry no aux
                return gains, cache2, value_of(cache2)
        else:

            def fold_score_val(cache, w_prev, cand_t):
                cache2 = fold(cache, w_prev)
                return score_idx(cache2, cand_t), cache2, value_of(cache2)

    B = V.shape[0]
    w0c = (w0.astype(V.dtype), jnp.full((B,), -1, jnp.int32))
    cache0 = (seedf, jnp.zeros((B,), jnp.float32))
    sel, traj, n_scored, n_trips, cache_f = drive_selection_scan(
        kind=kind, k=k, top_b=top_b, n_global=n, pool=V, k_eff=k_eff,
        cand_rounds=cand_rounds, cache0=cache0, w0=w0c, fold=fold,
        score_idx_val=score_idx_val, fold_score_val=fold_score_val,
        value_of=value_of)
    return sel, traj, n_scored, n_trips, cache_f[0]

# ---------------------------------------------------------------------------
# Engine entry point
# ---------------------------------------------------------------------------


@jax.jit
def _one_request(*operands):
    """One request's operands with the scan's leading request axis (B = 1),
    in one dispatch."""
    return tuple(x[None] for x in operands)


@partial(jax.profiler.annotate_function, name=tracing.RUN_SELECTION)
def run_selection(
    f: SubmodularFunction,
    *,
    kind: str,                        # "dense" | "stochastic" | "lazy"
    k: int,
    cand_rounds: Optional[np.ndarray] = None,
    top_b: int = 0,
    plan: str = "device",             # "device" | "device_sharded" |
                                      # "device_sharded_pool" | "greedi"
    counter_key: str,
    block_m: Optional[int] = None,
    mesh=None,
    data_axes: Sequence[str] = ("data",),
) -> OptResult:
    """Run a round-candidate strategy under a device execution plan.

    ``cand_rounds`` carries the per-round candidate indices for the dense
    and stochastic strategies ((k, m), global indices); the lazy strategy
    derives its candidates on device and takes ``top_b`` instead (0 → the
    default re-score width of 256). A stochastic round whose sample row is
    entirely exhausted by earlier selections raises rather than silently
    re-selecting a taken index.

    Plans: ``device`` (one-dispatch scan), ``device_sharded`` (mesh-sharded
    V + cache, candidate payload replicated), ``device_sharded_pool`` (the
    candidate payload row-shards too — O(n/p·d) resident per device; scoring
    blocks and the per-round winner column psum-materialize from their
    owning shards), ``greedi`` (dense strategy only: GreeDi
    partition-then-merge — each shard greedily solves its own partition,
    the p·k partial solutions all-gather, and a merge round over that small
    replicated pool runs under the sharded-cache callbacks; selections are
    *not* identical to host greedy but carry the GreeDi constant-factor
    guarantee).

    The whole call, its preparation up to the dispatch and the read of the
    result back to the host are host spans (:mod:`repro.core.tracing`).
    """
    if k == 0:
        return OptResult([], 0.0, [], 0)
    with jax.profiler.TraceAnnotation(tracing.RUN_SELECTION_PREPARE):
        fn = f.spec
        if fn.name not in fx.DEVICE_PLAN_ELIGIBLE:
            raise ValueError(
                f"function {fn.name!r} has no n-aligned vec cache to shard "
                f"or scan over — it runs on the host execution plans only")
        n_cand = f.n if kind == "lazy" or cand_rounds is None else len(
            np.unique(cand_rounds[0] if kind == "dense" else cand_rounds))
        if k > n_cand:
            raise ValueError(
                f"cannot select k={k} exemplars from {n_cand} distinct "
                f"candidates — once every candidate is taken the argmax "
                f"would silently re-select one")
        policy = f.cfg.resolved_policy()
        backend = f.cfg.backend \
            if f.cfg.backend in ("pallas", "pallas_interpret") else "jnp"
        if fx.kernel_template(fn) is None:
            # no kernel form (saturated coverage): jnp scoring on any backend
            backend = "jnp"
        if backend != "jnp" and f.cfg.distance not in dist_mod.MXU_ELIGIBLE:
            raise ValueError(
                f"device plans with a pallas backend support "
                f"{sorted(dist_mod.MXU_ELIGIBLE)}, got {f.cfg.distance!r}")
        rbf_gamma = dist_mod.RBF_GAMMA \
            if (backend != "jnp" and f.cfg.distance == "rbf") else None
        w0 = f.e0 if f.e0 is not None else jnp.zeros((f.dim,), f.V.dtype)

        if kind == "lazy":
            top_b = max(1, min(top_b or 256, f.n))
            cand_rounds = np.zeros((1, 0), np.int32)
            # lazy's widest scoring tile is the bound-seeding pass over all n
            # candidates (per-round tiles are top_b ≤ n)
            m_widest = f.n
        elif cand_rounds is None:
            raise ValueError(f"strategy {kind!r} needs cand_rounds")
        else:
            m_widest = cand_rounds.shape[1]

        if plan == "device":
            bm = block_m if block_m is not None \
                else _device_block_m(f.n, m_widest)
            # one request is the B = 1 case of the scan, its operands lifted
            # on the device; the lifted seed is a fresh buffer, so the
            # donation never consumes f.cache_seed (which may alias the
            # function's resident d_e0)
            V1, seed, aux1, w01 = _one_request(f.V, f.cache_seed, f.row_aux,
                                               w0)
            cand = jnp.asarray(cand_rounds[None], jnp.int32)
            k_eff = jnp.asarray([k], jnp.int32)

    if plan == "device":
        sel, traj, n_scored, n_trips, _ = _select_scan(
            V1, seed, aux1, cand, w01, k_eff,
            fn=fn, kind=kind, k=k, top_b=top_b, distance=f.cfg.distance,
            policy_name=policy.name, block_m=bm, backend=backend,
            rbf_gamma=rbf_gamma, counter_key=counter_key)
    elif plan in ("device_sharded", "device_sharded_pool"):
        from repro.core import distributed as dist_engine

        sel, traj, n_scored, n_trips = dist_engine.run_sharded_selection(
            f, jnp.asarray(cand_rounds, jnp.int32), w0, kind=kind, k=k,
            top_b=top_b, counter_key=counter_key, m_widest=m_widest,
            block_m=block_m, mesh=mesh, data_axes=data_axes,
            backend=backend, rbf_gamma=rbf_gamma,
            pool_plan="sharded" if plan == "device_sharded_pool"
            else "replicated")
    elif plan == "greedi":
        from repro.core import distributed as dist_engine

        if kind != "dense":
            raise ValueError(
                "plan 'greedi' partitions the *dense* greedy strategy; "
                f"strategy {kind!r} has no partition-then-merge form here")
        if cand_rounds.shape[1] != f.n:
            raise ValueError(
                "plan 'greedi' partitions the full ground set; candidate "
                "subsets are not supported (every V row must be eligible "
                "in its own partition)")
        sel, traj, n_scored = dist_engine.run_greedi_selection(
            f, w0, k=k, counter_key=counter_key, block_m=block_m,
            mesh=mesh, data_axes=data_axes, backend=backend,
            rbf_gamma=rbf_gamma)
        n_trips = 0
    else:
        raise ValueError(f"unknown execution plan {plan!r}")

    with jax.profiler.TraceAnnotation(tracing.RUN_SELECTION_FETCH):
        # the device plan's one request comes back as (k, 1) and (1,)
        sel = [int(x) for x in np.ravel(sel)]
        if any(s < 0 for s in sel):
            bad = sel.index(-1)
            raise ValueError(
                f"round {bad} had no untaken candidate (its sample row is "
                f"exhausted by earlier selections) — the argmax would "
                f"silently re-select a taken index")
        traj = [float(x) for x in np.ravel(traj)]
        return OptResult(sel, traj[-1] if traj else 0.0, traj,
                         int(np.ravel(n_scored)[0]),
                         int(np.ravel(n_trips)[0]))


def _stack_batch_payload(fs: Sequence[SubmodularFunction]) -> dict:
    """Host-stack B same-signature requests into one (B, …) device payload.

    Stacks through NumPy, not jnp.stack: an XLA concat over B small device
    arrays costs a dispatch per operand, which at serving batch sizes
    dwarfs the scan itself (~20ms vs ~2ms at B=64 on CPU). np.asarray of a
    committed array is a cheap transfer, np.stack is one memcpy, and the
    single jnp.asarray builds one fresh device buffer — which also keeps
    the seed donation-safe (cache_seed may alias each f's resident d_e0).
    Factored out of :func:`run_selection_batch` so the serving layer can
    stage the NEXT bucket's transfer while the current dispatch runs
    (:func:`stage_selection_batch`).
    """
    f0 = fs[0]
    B = len(fs)
    V_b = jnp.asarray(np.stack([np.asarray(f.V) for f in fs]))
    seed_b = jnp.asarray(
        np.stack([np.asarray(f.cache_seed, np.float32) for f in fs]))
    aux_b = jnp.asarray(np.stack([np.asarray(f.row_aux) for f in fs]))
    if all(f.e0 is None for f in fs):
        w0_b = jnp.zeros((B, f0.dim), f0.V.dtype)
    else:
        w0_b = jnp.asarray(np.stack([
            np.asarray(f.e0 if f.e0 is not None
                       else jnp.zeros((f.dim,), f.V.dtype))
            for f in fs]), f0.V.dtype)
    return {"V": V_b, "seed": seed_b, "aux": aux_b, "w0": w0_b}


def stage_selection_batch(
    fs: Sequence[SubmodularFunction],
    *,
    plan: str = "device",
    mesh=None,
    data_axes: Sequence[str] = ("data",),
) -> Optional[dict]:
    """Pre-stage a bucket's stacked payload ahead of its dispatch.

    Issues the host→device transfers (``jax.device_put`` under the hood —
    async on accelerators) for the payload :func:`run_selection_batch`
    would otherwise build inline, so a serving loop can overlap the NEXT
    bucket's staging with the CURRENT bucket's running dispatch. The
    returned dict is single-use: it contains the fresh donation-safe cache
    seed for exactly one ``run_selection_batch(..., staged=...)`` call.
    """
    if not fs:
        return None
    if plan == "device":
        return _stack_batch_payload(fs)
    if plan in ("device_sharded", "device_sharded_pool"):
        from repro.core import distributed as dist_engine

        return dist_engine.stage_sharded_batch(
            fs, mesh=mesh, data_axes=tuple(data_axes),
            pool_plan="sharded" if plan == "device_sharded_pool"
            else "replicated")
    raise ValueError(f"unknown batched execution plan {plan!r}")


def run_selection_batch(
    fs: Sequence[SubmodularFunction],
    *,
    kind: str,                        # "dense" | "stochastic" | "lazy"
    k: int,
    ks: Optional[Sequence[int]] = None,
    cand_rounds: Optional[np.ndarray] = None,
    top_b: int = 0,
    counter_key: str,
    block_m: Optional[int] = None,
    plan: str = "device",
    mesh=None,
    data_axes: Sequence[str] = ("data",),
    staged: Optional[dict] = None,
) -> list[OptResult]:
    """Solve B independent selection requests in ONE jitted dispatch.

    The batched device-plan entry point: every request in ``fs`` must
    share the jit signature — same function spec, same (n, d), same
    ``EvalConfig`` — which is exactly what the serving layer's bucketing
    guarantees. ``k`` is the shared scan length; ``ks`` optionally gives
    each request its own effective k ≤ k (ragged k via masking: request b
    freezes after ``ks[b]`` rounds and its results are truncated to
    ``ks[b]`` at demux; ``ks[b] = 0`` marks an inert bucket-padding slot).

    ``cand_rounds`` is (B, k, m) per-request candidate indices for the
    dense/stochastic strategies; dense may pass None for the
    full-ground-set default. Per-request selections, trajectories, and
    evaluation counts are identical to B :func:`run_selection` calls —
    each of which is this scan at B = 1 — only the dispatch is amortized.

    ``plan`` composes the batch axis with the execution plans:
    ``"device"`` (single-device, state (B, n) resident), or
    ``"device_sharded"`` / ``"device_sharded_pool"`` (state laid out
    (B, n/p) per device on ``mesh`` — B per-tenant min-caches row-shard
    with V, each round issues ONE psum of O(B·m) bytes with every
    request's partials stacked into the same collective, and per-request
    results stay bit-identical to each request's own sharded run).
    ``staged`` optionally passes a payload pre-transferred by
    :func:`stage_selection_batch` (same fs, same plan).
    """
    if not fs:
        return []
    f0 = fs[0]
    B = len(fs)
    fn = f0.spec
    for f in fs[1:]:
        if f.spec != fn:
            raise ValueError(
                f"batched requests must share one function spec, got "
                f"{fn} and {f.spec}")
        if f.V.shape != f0.V.shape or f.V.dtype != f0.V.dtype:
            raise ValueError(
                f"batched requests must share one (n, d) payload shape, "
                f"got {f0.V.shape} and {f.V.shape} — bucket by signature "
                f"before dispatching")
        if f.cfg != f0.cfg:
            raise ValueError(
                "batched requests must share one EvalConfig (distance / "
                "policy / backend enter the jit signature)")
    ks = [int(k)] * B if ks is None else [int(x) for x in ks]
    if len(ks) != B:
        raise ValueError(f"ks has {len(ks)} entries for {B} requests")
    if any(kb < 0 or kb > k for kb in ks):
        raise ValueError(f"per-request k must lie in [0, {k}], got {ks}")
    if k == 0 or all(kb == 0 for kb in ks):
        return [OptResult([], 0.0, [], 0) for _ in fs]
    if fn.name not in fx.DEVICE_PLAN_ELIGIBLE:
        raise ValueError(
            f"function {fn.name!r} has no n-aligned vec cache to batch-scan "
            f"over — it runs on the host execution plans only")
    policy = f0.cfg.resolved_policy()
    backend = f0.cfg.backend \
        if f0.cfg.backend in ("pallas", "pallas_interpret") else "jnp"
    if fx.kernel_template(fn) is None:
        backend = "jnp"
    if backend != "jnp" and f0.cfg.distance not in dist_mod.MXU_ELIGIBLE:
        raise ValueError(
            f"device plans with a pallas backend support "
            f"{sorted(dist_mod.MXU_ELIGIBLE)}, got {f0.cfg.distance!r}")
    rbf_gamma = dist_mod.RBF_GAMMA \
        if (backend != "jnp" and f0.cfg.distance == "rbf") else None
    n = f0.n

    if kind == "lazy":
        top_b = max(1, min(top_b or 256, n))
        cand_rounds = np.zeros((B, 1, 0), np.int32)
        m_widest = n
    else:
        if cand_rounds is None:
            if kind != "dense":
                raise ValueError(f"strategy {kind!r} needs cand_rounds")
            cand_rounds = np.broadcast_to(
                np.arange(n, dtype=np.int32)[None, None, :], (B, 1, n))
        cand_rounds = np.asarray(cand_rounds)
        if cand_rounds.ndim != 3 or cand_rounds.shape[0] != B:
            raise ValueError(
                f"batched cand_rounds must be (B, k, m), got "
                f"{cand_rounds.shape} for B={B}")
        if kind == "dense" and cand_rounds.shape[1] != 1:
            cand_rounds = cand_rounds[:, :1]
        for b, kb in enumerate(ks):
            if kb == 0:
                continue
            n_cand = len(np.unique(
                cand_rounds[b, 0] if kind == "dense" else cand_rounds[b]))
            if kb > n_cand:
                raise ValueError(
                    f"request {b}: cannot select k={kb} exemplars from "
                    f"{n_cand} distinct candidates")
        m_widest = cand_rounds.shape[2]

    if plan in ("device_sharded", "device_sharded_pool"):
        from repro.core import distributed as dist_engine

        sel, traj, n_scored, n_trips = \
            dist_engine.run_sharded_selection_batch(
                fs, jnp.asarray(cand_rounds, jnp.int32), ks, kind=kind, k=k,
                top_b=top_b, counter_key=counter_key, m_widest=m_widest,
                block_m=block_m, mesh=mesh, data_axes=tuple(data_axes),
                backend=backend, rbf_gamma=rbf_gamma,
                pool_plan="sharded" if plan == "device_sharded_pool"
                else "replicated", staged=staged)
    elif plan == "device":
        bm = block_m if block_m is not None \
            else _device_block_m(n, m_widest, n_batch=B)
        payload = staged if staged is not None else _stack_batch_payload(fs)
        sel, traj, n_scored, n_trips, _ = _select_scan(
            payload["V"], payload["seed"], payload["aux"],
            jnp.asarray(cand_rounds, jnp.int32), payload["w0"],
            jnp.asarray(ks, jnp.int32), fn=fn, kind=kind, k=k, top_b=top_b,
            distance=f0.cfg.distance, policy_name=policy.name, block_m=bm,
            backend=backend, rbf_gamma=rbf_gamma, counter_key=counter_key)
    else:
        raise ValueError(f"unknown batched execution plan {plan!r}")
    sel = np.asarray(sel)            # (k, B)
    traj = np.asarray(traj)          # (k, B)
    n_scored = np.asarray(n_scored)  # (B,)
    n_trips = np.asarray(n_trips)    # (B,)
    out = []
    for b, kb in enumerate(ks):
        sb = [int(x) for x in sel[:kb, b]]
        if any(s < 0 for s in sb):
            bad = sb.index(-1)
            raise ValueError(
                f"request {b}, round {bad} had no untaken candidate (its "
                f"sample row is exhausted by earlier selections)")
        tb = [float(x) for x in traj[:kb, b]]
        out.append(OptResult(sb, tb[-1] if tb else 0.0, tb,
                             int(n_scored[b]), int(n_trips[b])))
    return out
