"""Device-resident streaming sieve engine (tentpole, beyond paper).

The sieve family (SieveStreaming [4], SieveStreaming++ [19], Salsa [20])
maintains a *grid* of threshold sieves τ = (1+ε)^i and offers every arriving
stream element to all of them. After PR 1–2 moved the greedy family on device,
the sieves were the last optimizer class whose inner loop lived on host:
one Python/numpy accept decision per element per block. This module replaces
that loop with a device-resident engine: the per-sieve state lives on the
accelerator and each stream block of B elements is consumed by ONE jitted
``jax.lax.scan`` over elements — singleton gain, grid rebuild, per-sieve
accept rule, cache fold, and member bookkeeping all in the scan body.

Design: the **fixed-capacity sieve table**. Grid growth (a new max singleton
widens the threshold window) is shape-dynamic on host but must be shape-static
on device, so — the same way PR 2 turned CELF's heap into carried stale
bounds — the dynamic sieve collection becomes a table of ``S_max`` slots:

* Sieves are keyed by the **integer exponent** i of their threshold
  τ = (1+ε)^i (never by float equality of τ — the former
  ``if t not in have`` float dedupe could duplicate or miss a sieve when
  ``(1+eps)**i`` round-tripped differently across rebuilds).
* Exponent i lives in slot ``i mod S_max``. The live window
  [i_lo, i_hi] = [⌈log m / log(1+ε)⌉, ⌊log(2km) / log(1+ε)⌋] has width
  ≤ log(2k)/log(1+ε) + 1 independent of the stream, so with
  ``S_max ≥ width + 2`` every live exponent owns a distinct slot.
* A grid "rebuild" is a **masked activation**: slots whose assigned exponent
  changed are reset (cache ← seed, size ← 0, members ← −1) in-place inside
  the scan body; slots whose exponent survives keep their state — exactly the
  host semantics of dropping below-window sieves and adding new ones.
* Salsa's grid is grow-only (old sieves are never dropped), so its exponent
  span is stream-dependent; its capacity default adds headroom, and when the
  span does exceed ``S_max`` the slot collision evicts the lowest (stalest)
  exponent — a well-defined capacity rule the host mirror shares, so parity
  holds by construction even under eviction.

Function generality: the table rows carry whatever (n,)-vec cache the
objective's :mod:`repro.core.functions` protocol defines — the element step
reads gains through :func:`~repro.core.functions.sieve_gain_rows`, folds
accepts through :func:`~repro.core.functions.sieve_fold_rows`, and values
sieves through ``stat_rows``/``value_from_stat``, so one table definition
serves every :data:`~repro.core.functions.SIEVE_ELIGIBLE` objective
(exemplar's min-cache, facility location's max-cache dual, saturated
coverage's capped-sum cache). The sieve math itself (grid exponents,
thresholds, accept rules) only assumes monotone gains, which eligibility
guarantees. Graph cut is excluded: its gain needs the winner-indexed
redundancy penalty, which a stream element's cache rows alone cannot carry.

Parity: :func:`_element_step` is the ONE definition of the per-element
transition, written in pure ``jax.numpy``. The host mirror jits it per
element (the honest per-element dispatch round-trip the device engine
replaces); the device engine runs the identical function inside the per-block
scan. On kernel backends (``SieveSpec.backend``) the step's gains route
through the fused table × element Pallas kernel
(:func:`repro.kernels.ops.sieve_gains`) under the function's min/max
template — in BOTH plans, so the parity argument is unchanged. Both consume
distance rows from the same ``point_distances_block`` executable, so host
and device see bitwise-identical inputs and — all float reductions being the
same HLO — make identical accept decisions, select identical members, and
report identical evaluation counts.
"""
from __future__ import annotations

import math
from functools import partial
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.contracts import contract
from repro.core import functions as fx
from repro.core.engine import DEVICE_TRACE_COUNTS
from repro.core.functions import FnSpec

VARIANTS = ("sieve", "pp", "salsa")

#: Slot-exponent value meaning "never assigned" — far below any reachable
#: grid exponent (f32 singleton values bound |i| ≲ 1000 for ε ≥ 1e-3).
_EXP_UNSET = -(1 << 30)


class SieveSpec(NamedTuple):
    """Static (hashable → jit-static) configuration of a sieve table."""

    k: int
    eps: float
    s_max: int
    variant: str        # "sieve" | "pp" | "salsa"
    log1p_eps: float    # np.float32(log1p(eps)) — the ONE grid-log constant
    #: scoring backend for the element step's gains: "jnp" runs the plain
    #: (S_max, n) protocol reduction; "pallas"/"pallas_interpret" run the
    #: fused table × element kernel (:func:`repro.kernels.ops.sieve_gains`)
    #: under the function's min/max template. Part of the spec (not the
    #: engine) so the host mirror and the device scan share ONE definition
    #: per backend — parity by construction either way.
    backend: str = "jnp"
    #: the submodular objective the table rows cache — must be
    #: :data:`~repro.core.functions.SIEVE_ELIGIBLE`.
    fn: FnSpec = FnSpec()


class SieveState(NamedTuple):
    """Device-resident state of the fixed-capacity sieve table.

    Inactive slots carry stale arrays; every consumer masks with ``active``.
    ``members`` rows are stream ids in arrival order, -1 beyond ``sizes``.
    """

    caches: jax.Array    # (S_max, n) f32 per-sieve cache rows (fn semantics)
    slot_exp: jax.Array  # (S_max,) i32 threshold exponent i (τ = (1+ε)^i)
    active: jax.Array    # (S_max,) bool
    sizes: jax.Array     # (S_max,) i32 member counts
    members: jax.Array   # (S_max, k) i32 member slots
    m_seen: jax.Array    # () f32 max singleton gain seen
    lb: jax.Array        # () f32 best-value lower bound (pp only)
    evals: jax.Array     # () i32 engine-boundary evaluation count


def make_spec(k: int, eps: float, variant: str,
              s_max: Optional[int] = None,
              backend: str = "jnp",
              fn: FnSpec = FnSpec()) -> SieveSpec:
    if variant not in VARIANTS:
        raise ValueError(f"unknown sieve variant {variant!r}; one of {VARIANTS}")
    if k < 1:
        raise ValueError(f"sieve streaming needs k >= 1, got k={k}")
    if not 0.0 < eps < 1.0:
        raise ValueError(f"eps must lie in (0, 1), got {eps}")
    if backend not in ("jnp", "pallas", "pallas_interpret"):
        raise ValueError(
            f"unknown sieve backend {backend!r}; "
            f"'jnp', 'pallas' or 'pallas_interpret'")
    if fn.name not in fx.SIEVE_ELIGIBLE:
        raise ValueError(
            f"function {fn.name!r} is not sieve-streamable — threshold "
            f"sieves need monotone gains from the cache rows alone; "
            f"eligible: {sorted(fx.SIEVE_ELIGIBLE)}")
    if backend != "jnp" and fx.kernel_template(fn) is None:
        # no kernel form (saturated coverage's capped gain): the engine is
        # still valid, the step just scores through the jnp protocol path —
        # the same silent normalization the selection engine applies
        backend = "jnp"
    cap = s_max if s_max is not None else default_capacity(k, eps, variant)
    width = grid_width_bound(k, eps)
    if cap < width + 2:
        raise ValueError(
            f"s_max={cap} cannot hold the live threshold window "
            f"(width ≤ {width}, +2 slack required)")
    return SieveSpec(k, float(eps), int(cap), variant,
                     float(np.float32(np.log1p(np.float32(eps)))), backend,
                     fn)


def grid_width_bound(k: int, eps: float) -> int:
    """Max #live exponents in [⌈log m/L⌉, ⌊log 2km/L⌋]: ⌊log(2k)/L⌋ + 1."""
    return int(math.floor(math.log(2 * k) / math.log1p(eps))) + 1


def default_capacity(k: int, eps: float, variant: str) -> int:
    """Slot capacity: the live-window bound plus slack; Salsa's grow-only
    grid gets headroom for a 16x max-singleton drift before the capacity
    eviction rule starts firing."""
    cap = grid_width_bound(k, eps) + 2
    if variant == "salsa":
        cap += int(math.ceil(math.log(16.0) / math.log1p(eps)))
    return max(cap, 4)


def init_state(n: int, spec: SieveSpec) -> SieveState:
    """Zeroed table. Cache rows are dead until a slot's first claim resets
    them to the function's seed, so the init value never reaches a live
    gain or accept — zeros keep the born-sharded layout trivial."""
    S, k = spec.s_max, spec.k
    return SieveState(
        caches=jnp.zeros((S, n), jnp.float32),
        slot_exp=jnp.full((S,), _EXP_UNSET, jnp.int32),
        active=jnp.zeros((S,), bool),
        sizes=jnp.zeros((S,), jnp.int32),
        members=jnp.full((S, k), -1, jnp.int32),
        m_seen=jnp.float32(0.0),
        lb=jnp.float32(0.0),
        evals=jnp.int32(0),
    )


def _element_step(spec: SieveSpec, seed, v0, state: SieveState, idx, dvec,
                  valid, *, row_aux, mean_rows=None, table_gains=None):
    """The per-element sieve-table transition — ONE definition, pure jnp.

    The host mirror jits this per element; the device engine scans it per
    block. ``valid=False`` (block padding) makes the whole step a no-op.
    ``seed``/``v0``/``row_aux`` are the function's empty-set cache row, its
    empty-set baseline value, and its static per-row auxiliary (saturation
    caps). Returns ``(new_state, accepted_anywhere)``.

    The two optional callbacks are the step's only reductions over the
    ground-set axis, injectable so the mesh-sharded engine can run the
    *identical* transition on (S_max, n/p) cache shards: ``mean_rows(M)``
    is the trailing-axis mean (sharded: per-shard row sums psum'd and
    normalized by the global n — exactly how selection gains shard) and
    ``table_gains(table, dvec)`` the kernel-backend fused table × element
    gain under the function's min/max template (sharded:
    :func:`repro.kernels.ops.sieve_gains` with the global ``n_total``
    normalizer, partials psum'd). Defaults are the single-device
    reductions. Everything else in the step — thresholds, slot bookkeeping,
    member tables — is O(S_max)/O(k) state that stays replicated.
    """
    k, S = spec.k, spec.s_max
    fn = spec.fn
    L = spec.log1p_eps
    caches, slot_exp, active, sizes, members, m_seen, lb, evals = state
    if mean_rows is None:
        mean_rows = lambda M: jnp.mean(M, axis=-1)  # noqa: E731

    def values_of(table):
        return fx.value_from_stat(
            fn, v0, mean_rows(fx.stat_rows(fn, table, row_aux)))

    # singleton gain Δ(e | ∅) — the grid anchor m = max singleton seen.
    # Kernel backends score the whole table in ONE fused pass up front:
    # row 0 is the seed (the empty-set cache, whose gain IS the singleton),
    # rows 1: are the pre-rebuild sieve caches. A slot the rebuild below
    # claims is reset to exactly the seed, so its post-rebuild gain is the
    # singleton — ``where(claim, single, ...)`` recovers the post-rebuild
    # gains without a second kernel pass.
    use_kernel = spec.backend != "jnp"
    if use_kernel:
        if table_gains is None:
            from repro.kernels import ops as kops

            tmpl = fx.kernel_template(fn)
            table_gains = partial(
                kops.sieve_gains, fold=tmpl[0], score_affine=tmpl[1],
                interpret=(spec.backend != "pallas"))

        g_all = table_gains(
            jnp.concatenate([seed[None, :], caches], axis=0), dvec)
        single, gains_pre = g_all[0], g_all[1:]
    else:
        single = mean_rows(
            fx.sieve_gain_rows(fn, seed[None, :], dvec, row_aux))[0]
    new_max = valid & (single > m_seen)
    m_seen = jnp.where(new_max, single, m_seen)

    # grid rebuild: SieveStreaming/Salsa rebuild only on a new max; ++
    # re-derives its window every element because LB moves after accepts
    if spec.variant == "pp":
        rebuild = valid & (m_seen > 0.0)
        lo = jnp.maximum(lb, m_seen)
    else:
        rebuild = new_max
        lo = m_seen
    tiny = jnp.float32(1e-38)  # log(0) guard; rebuild is False while m=0
    i_lo = jnp.ceil(jnp.log(jnp.maximum(lo, tiny)) / L).astype(jnp.int32)
    i_hi = jnp.floor(
        jnp.log(jnp.maximum(2.0 * k * m_seen, tiny)) / L).astype(jnp.int32)

    # masked activation: exponent i lives in slot i mod S_max; a slot whose
    # assigned exponent changed is reset, one whose exponent survives keeps
    # its cache/members (the host rebuild's keep-and-add, shape-statically)
    slots = jnp.arange(S, dtype=jnp.int32)
    wanted_exp = i_lo + jnp.mod(slots - i_lo, S)
    wanted = wanted_exp <= i_hi
    claim = rebuild & wanted & ((slot_exp != wanted_exp) | ~active)
    if spec.variant == "sieve":
        active = jnp.where(rebuild, wanted, active)       # window replaces
    elif spec.variant == "salsa":
        active = active | (rebuild & wanted)              # grow-only
    else:  # pp: LB prune τ ≥ lo/(1+ε) ⇔ i ≥ i_lo − 1, then activation
        active = jnp.where(rebuild, active & (slot_exp >= i_lo - 1), active)
        active = active | claim
    slot_exp = jnp.where(claim, wanted_exp, slot_exp)
    caches = jnp.where(claim[:, None], seed[None, :], caches)
    sizes = jnp.where(claim, 0, sizes)
    members = jnp.where(claim[:, None], -1, members)

    # offer to every sieve: marginal gain vs each (post-rebuild) cache, one
    # accept rule
    if use_kernel:
        gains = jnp.where(claim, single, gains_pre)
    else:
        gains = mean_rows(fx.sieve_gain_rows(fn, caches, dvec, row_aux))
    taus = jnp.exp(slot_exp.astype(jnp.float32) * L)
    if spec.variant == "salsa":
        # dense-threshold schedule: rate 1/2 for the first ⌈k/2⌉ members,
        # 1/(2e) after — (k+1)//2, so k=1 still gets the early rate
        rate = jnp.where(sizes < (k + 1) // 2, 0.5, 1.0 / (2.0 * math.e))
        need = rate * taus / k
    else:
        values = values_of(caches)
        need = (taus / 2.0 - values) / jnp.maximum(k - sizes, 1)
    accept = valid & active & (sizes < k) & (gains >= need)
    caches = fx.sieve_fold_rows(fn, caches, dvec, accept)
    members = jnp.where(
        accept[:, None] & (jnp.arange(k)[None, :] == sizes[:, None]),
        idx, members)
    sizes = sizes + accept.astype(jnp.int32)
    if spec.variant == "pp":
        vals_new = values_of(caches)
        lb = jnp.maximum(lb, jnp.max(jnp.where(active, vals_new, -jnp.inf)))

    # engine-boundary accounting: one engine call scores the element against
    # every live sieve (min. 1 — the singleton gain is always computed)
    n_active = jnp.sum(active).astype(jnp.int32)
    evals = evals + jnp.where(valid, jnp.maximum(n_active, 1), 0)
    state = SieveState(caches, slot_exp, active, sizes, members, m_seen, lb,
                       evals)
    return state, jnp.any(accept)


@partial(jax.jit, static_argnames=("spec",))
def _element_step_jit(state, seed, idx, dvec, valid, *, spec, row_aux=None):
    seedf = seed.astype(jnp.float32)
    aux = jnp.zeros_like(seedf) if row_aux is None \
        else row_aux.astype(jnp.float32)
    v0 = jnp.mean(fx.stat_rows(spec.fn, seedf, aux))
    return _element_step(spec, seedf, v0, state, idx, dvec, valid,
                         row_aux=aux)


@contract(
    "streaming.offer_scan",
    donate=("state",),
    claim="one dispatch consumes a whole stream block: ONE lax.scan over "
          "its elements, collective-free, the sieve table updated in place "
          "per element — and the carried SieveState is DONATED, so the "
          "(S_max, n) table buffers alias block-to-block instead of copying")
@partial(jax.jit, static_argnames=("spec", "counter_key"),
         donate_argnums=(0,))
def _offer_block_scan(state, seed, row_aux, idxb, dmatb, validb, *, spec,
                      counter_key):
    """Consume a stream block: ONE jitted ``lax.scan`` over its elements.

    The ``state`` carry is donated: every leaf of the incoming SieveState
    aliases the matching output leaf, so the table is updated in place and
    the caller MUST rebind (``self.state = ...``) rather than reuse the
    argument — which :class:`DeviceSieveEngine` does."""
    DEVICE_TRACE_COUNTS[counter_key] += 1
    seedf = seed.astype(jnp.float32)
    auxf = row_aux.astype(jnp.float32)
    v0 = jnp.mean(fx.stat_rows(spec.fn, seedf, auxf))

    def step(st, xs):
        idx, dvec, valid = xs
        return _element_step(spec, seedf, v0, st, idx, dvec, valid,
                             row_aux=auxf)

    return jax.lax.scan(step, state, (idxb, dmatb, validb))


@partial(jax.jit, static_argnames=("fn",))
def _table_values(caches, seed, row_aux, *, fn: FnSpec):
    """Per-sieve f-values — shared by both engines' ``best`` so equal caches
    yield bit-equal values."""
    seedf = seed.astype(jnp.float32)
    auxf = row_aux.astype(jnp.float32)
    v0 = jnp.mean(fx.stat_rows(fn, seedf, auxf))
    return fx.value_from_stat(
        fn, v0, jnp.mean(fx.stat_rows(fn, caches, auxf), axis=-1))


@partial(jax.jit, static_argnames=("fn", "n_total"))
def _table_values_padded(caches, seed, row_aux, *, fn: FnSpec, n_total: int):
    """f-values of a padded (mesh-sharded) table: the function's pad
    sentinels make padding rows contribute exactly 0 to every stat sum
    (exemplar: seed/cache 0; facility location: seed/aux +inf mask;
    saturated coverage: cap 0 self-masks), so the sums are exact and only
    the normalizer must be the real n. Runs on the global sharded arrays —
    the partitioner turns the row sums into one small cross-device reduce,
    so ``best`` never gathers the (S_max, n) table to one device."""
    seedf = seed.astype(jnp.float32)
    auxf = row_aux.astype(jnp.float32)
    v0 = jnp.sum(fx.stat_rows(fn, seedf, auxf)) / n_total
    mean_stat = jnp.sum(fx.stat_rows(fn, caches, auxf), axis=-1) / n_total
    return fx.value_from_stat(fn, v0, mean_stat)


# ---------------------------------------------------------------------------
# Mesh-sharded block consumption: the (S_max, n) sieve cache table (and the
# cache seed, the row auxiliary, and per-element distance rows) column-shard
# over the mesh's data axes, taking per-device streaming state from
# O(S_max·n) to O(S_max·n/p). The scan body is the IDENTICAL _element_step
# with its two ground-set reductions swapped for psum'd per-shard partials —
# the same collective shape as the selection engine's sharded gains (2–3
# psums of O(S_max) bytes per element, distances computed shard-locally so
# the (B, n) block never exists anywhere).
# ---------------------------------------------------------------------------

_SHARDED_OFFER_CACHE: dict = {}


def _state_specs(axes):
    from jax.sharding import PartitionSpec as P

    return SieveState(
        caches=P(None, axes), slot_exp=P(None), active=P(None),
        sizes=P(None), members=P(None, None), m_seen=P(), lb=P(), evals=P())


@contract(
    "streaming.offer_scan[sharded]",
    factory=True,
    collective_kinds=("psum",),
    donate=("state",),
    claim="one dispatch per stream block; each element's table update "
          "costs O(S_max) psum'd scalars per reduction — collective bytes "
          "scale with the sieve table, never the ground set — and the "
          "sharded SieveState carry is DONATED (the per-device table shard "
          "aliases in place; V/seed/aux stay resident, never donated)")
def make_sharded_offer_scan(mesh, data_axes, *, spec: SieveSpec,
                            n_total: int, distance: str, policy_name: str,
                            counter_key: str):
    """Build (and cache) the jitted mesh-sharded per-block sieve scan.

    Returns ``fn(state, V_sh, seed_sh, aux_sh, Xb, idxb, validb) -> (state,
    accepted)`` where the state's ``caches`` (and ``V_sh``/``seed_sh``/
    ``aux_sh``) shard over ``data_axes`` and every other state leaf is
    replicated. Distance rows are computed *inside* the shard_map against
    the local V tile (each entry depends only on its own ground row, so
    per-entry arithmetic matches ``point_distances_block`` exactly).
    """
    from repro.core import distances as dist_mod
    from repro.core.precision import resolve as resolve_policy
    from jax.sharding import PartitionSpec as P
    from jax import shard_map

    axes = tuple(data_axes)
    key = (mesh, axes, spec, n_total, distance, policy_name, counter_key)
    if key in _SHARDED_OFFER_CACHE:
        return _SHARDED_OFFER_CACHE[key]
    policy = resolve_policy(policy_name)
    pair = dist_mod.resolve_pairwise(distance)
    use_kernel = spec.backend != "jnp"
    if use_kernel:
        from repro.kernels import ops as kops

        tmpl = fx.kernel_template(spec.fn)

    def local_consume(state, V_loc, seed_loc, aux_loc, Xb, idxb, validb):
        seedf = seed_loc.astype(jnp.float32)
        auxf = aux_loc.astype(jnp.float32)
        v0 = jax.lax.psum(
            jnp.sum(fx.stat_rows(spec.fn, seedf, auxf)), axes) / n_total
        dmat_loc = pair(V_loc, Xb, policy).T.astype(jnp.float32)

        def mean_rows(M):
            return jax.lax.psum(jnp.sum(M, axis=-1), axes) / n_total

        table_gains = None
        if use_kernel:

            def table_gains(table, dvec):
                part = kops.sieve_gains(
                    table, dvec, n_total=n_total,
                    fold=tmpl[0], score_affine=tmpl[1],
                    interpret=(spec.backend != "pallas"))
                return jax.lax.psum(part, axes)

        def step(st, xs):
            idx, dvec, valid = xs
            return _element_step(spec, seedf, v0, st, idx, dvec, valid,
                                 row_aux=auxf, mean_rows=mean_rows,
                                 table_gains=table_gains)

        return jax.lax.scan(step, state, (idxb, dmat_loc, validb))

    specs = _state_specs(axes)
    smapped = shard_map(
        local_consume,
        mesh=mesh,
        in_specs=(specs, P(axes, None), P(axes), P(axes), P(None, None),
                  P(None), P(None)),
        out_specs=(specs, P(None)),
        check_vma=False,
    )

    # donate ONLY the state carry: V/seed/aux are the function's resident
    # shards, reused by every block (and shared with sharded selection runs)
    @partial(jax.jit, donate_argnums=(0,))
    def run(state, V_sh, seed_sh, aux_sh, Xb, idxb, validb):
        DEVICE_TRACE_COUNTS[counter_key] += 1
        return smapped(state, V_sh, seed_sh, aux_sh, Xb, idxb, validb)

    _SHARDED_OFFER_CACHE[key] = run
    return run


# ---------------------------------------------------------------------------
# Batched multi-stream consumption: P independent sieve tables advance through
# ONE scan dispatch per block — the streaming analogue of the selection
# engine's batched multi-tenant dispatch. The scan still runs over the B
# elements of the block; each step advances all P partitions with a vmap of
# the IDENTICAL _element_step (its jnp reductions are trailing-axis-wise, so
# every partition's arithmetic is bit-identical to its own unbatched engine).
# Kernel backends score all P tables in ONE grid-over-P fused kernel launch
# (vmap cannot batch a pallas_call), injected through the step's table_gains
# hook — the gains math is the same kernel body, on a leading grid axis
# like the selection engine's gain kernels.
# ---------------------------------------------------------------------------


@contract(
    "streaming.offer_scan_batched",
    donate=("states",),
    claim="P independent stream partitions advance through ONE dispatch per "
          "block: a lax.scan over the block's elements whose step vmaps the "
          "identical element transition over partitions (kernel backends "
          "score all P tables in one grid-over-P fused launch); the batched "
          "SieveState carry is donated, so P tables alias in place")
@partial(jax.jit, static_argnames=("spec", "counter_key"),
         donate_argnums=(0,))
def _offer_block_scan_batched(states, seed, row_aux, idxb, dmatb, validb, *,
                              spec, counter_key):
    """Consume one block across P partitions: ONE jitted ``lax.scan``.

    ``states`` is a (P, …)-batched :class:`SieveState`; ``idxb``/``validb``
    are (B, P) and ``dmatb`` (B, P, n) — element-major so the scan runs over
    the block axis exactly like :func:`_offer_block_scan`. Returns
    ``(states, accepted (B, P))``. The carry is donated (callers rebind).
    """
    DEVICE_TRACE_COUNTS[counter_key] += 1
    seedf = seed.astype(jnp.float32)
    auxf = row_aux.astype(jnp.float32)
    v0 = jnp.mean(fx.stat_rows(spec.fn, seedf, auxf))
    use_kernel = spec.backend != "jnp"
    if use_kernel:
        from repro.kernels import ops as kops

        tmpl = fx.kernel_template(spec.fn)

    def step(sts, xs):
        idx, dmat, valid = xs            # (P,), (P, n), (P,)
        if use_kernel:
            # ONE batched kernel launch scores seed+table rows of all P
            # partitions; each partition's _element_step then receives its
            # own precomputed (S_max+1,) gains through the table_gains hook
            # (the hook is called exactly once per step, on the same
            # seed-stacked table the unbatched path builds).
            tables = jnp.concatenate(
                [jnp.broadcast_to(seedf, (idx.shape[0], 1, seedf.shape[0])),
                 sts.caches], axis=1)
            g_all = kops.sieve_gains(
                tables, dmat, fold=tmpl[0], score_affine=tmpl[1],
                interpret=(spec.backend != "pallas"))

            def elem(st, i, dv, va, g):
                return _element_step(spec, seedf, v0, st, i, dv, va,
                                     row_aux=auxf,
                                     table_gains=lambda _t, _d: g)

            return jax.vmap(elem)(sts, idx, dmat, valid, g_all)

        def elem(st, i, dv, va):
            return _element_step(spec, seedf, v0, st, i, dv, va,
                                 row_aux=auxf)

        return jax.vmap(elem)(sts, idx, dmat, valid)

    return jax.lax.scan(step, states, (idxb, dmatb, validb))


class _SieveEngineBase:
    """Block handling and state access shared by both execution plans.

    ``offer`` chunks the payload to ``block_size`` and pads ragged tails, so
    BOTH plans run the distance executable at the one (block_size, n) shape
    — the bitwise-parity invariant is structural, not backend luck — and
    every block reuses one traced executable. Padded elements carry
    ``valid=False`` (their step is a no-op by construction).

    ``overlap=True`` (the default) makes the block boundary sync-free:
    ``offer`` stages block t+1's padded payload with ``jax.device_put`` and
    issues its scan while block t's scan is still running — JAX's async
    dispatch pipelines them, and the only host syncs are the final accept
    masks (tiny (B,) bools, fetched once per ``offer`` call after every
    block has been issued) plus the lazy evaluation-counter fold at
    :meth:`evaluations`. ``max_in_flight`` bounds the pipeline depth so a
    long offer cannot stage an unbounded number of payload blocks on
    device. ``overlap=False`` restores the serialized baseline (block on
    each block's mask + fold its evals before staging the next) — kept so
    the overlap win stays benchmarkable.
    """

    def __init__(self, f, spec: SieveSpec, block_size: int = 64,
                 overlap: bool = True, max_in_flight: int = 4):
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        if max_in_flight < 1:
            raise ValueError(
                f"max_in_flight must be >= 1, got {max_in_flight}")
        self.f = f
        self.spec = spec
        self.block_size = block_size
        self.overlap = overlap
        self.max_in_flight = max_in_flight
        # the function's protocol arrays the element step consumes: the
        # empty-set cache row and the static per-row auxiliary
        self._seed = jnp.asarray(f.cache_seed, jnp.float32)
        self._aux = jnp.asarray(f.row_aux, jnp.float32)
        self.state = self._initial_state()
        # device state counts in int32; folding into a Python int at drain
        # points keeps unbounded streams (the service's live-sensor case)
        # exact. The fold is LAZY under overlap: each element adds at most
        # S_max evals, so int32 headroom covers tens of millions of
        # elements between drains — and every read path drains.
        self._evals = 0

    _I32 = np.iinfo(np.int32)

    def _validate_ids(self, idx) -> np.ndarray:
        """Stream ids live in the int32 member table; ids outside its range
        (the service's unbounded int64 counter can exceed it on long-lived
        streams) must raise, not silently wrap into colliding member ids."""
        idx = np.atleast_1d(np.asarray(idx))
        if idx.size and (int(idx.max()) > self._I32.max
                         or int(idx.min()) < self._I32.min):
            raise OverflowError(
                f"stream ids must fit the int32 member table "
                f"([{self._I32.min}, {self._I32.max}]); got range "
                f"[{int(idx.min())}, {int(idx.max())}]")
        return idx.astype(np.int32)

    def _initial_state(self) -> SieveState:
        """Hook: the mesh-sharded engine builds the table *born sharded* —
        the (S_max, n) zeros must never materialize on one device in the
        regime the mesh exists for."""
        return init_state(self.f.n, self.spec)

    def _stage_block(self, Xb, nb: int):
        """Pad one block to ``block_size`` rows and start its host→device
        transfer. For host-resident payloads the pad happens in numpy and
        ``jax.device_put`` issues an async copy — under overlap, block t+1
        stages while block t's scan runs. Device-resident payloads pad on
        device (no transfer to hide)."""
        B = self.block_size
        if isinstance(Xb, np.ndarray):
            Xp = np.zeros((B, Xb.shape[1]), Xb.dtype)
            Xp[:nb] = Xb
            return jax.device_put(Xp)
        return jnp.pad(Xb, ((0, B - nb), (0, 0)))

    def offer(self, idx, X) -> np.ndarray:
        idx = self._validate_ids(idx)
        if not isinstance(X, jax.Array):
            X = np.atleast_2d(np.asarray(X, np.float32))
        else:
            X = jnp.atleast_2d(X)
        B = self.block_size
        handles: list = []          # (accept handle, live count) per block
        inflight: list = []         # un-awaited handles (depth bound)
        for s in range(0, len(idx), B):
            ib, Xb = idx[s:s + B], X[s:s + B]
            nb = len(ib)
            payload = self._block_payload(self._stage_block(Xb, nb))
            idxp = np.full(B, -1, np.int32)
            idxp[:nb] = ib
            valid = np.zeros(B, bool)
            valid[:nb] = True
            acc = self._consume(idxp, payload, valid)
            handles.append((acc, nb))
            if not self.overlap:
                # serialized baseline: block on this block's mask and fold
                # its evals before staging the next — the pre-overlap cost
                jax.block_until_ready(acc)
                self._fold_evals()
            else:
                inflight.append(acc)
                if len(inflight) > self.max_in_flight:
                    jax.block_until_ready(inflight.pop(0))
        out = [np.asarray(acc)[:nb] for acc, nb in handles]
        return np.concatenate(out) if out else np.zeros(0, bool)

    def _fold_evals(self) -> None:
        """Drain the device-resident int32 evaluation counter into the exact
        Python accumulator. A host sync — called per block only on the
        serialized path; under overlap it runs lazily at read points."""
        e = int(np.asarray(self.state.evals))
        if e:
            self._evals += e
            self.state = self.state._replace(
                evals=jnp.zeros_like(self.state.evals))

    def best(self) -> tuple[list[int], float]:
        """Members and value of the best live sieve ([], 0.0 when none).

        Member slots, sizes and the active mask are replicated table state:
        one host fetch each regardless of mesh width — never a per-shard
        gather."""
        active = np.asarray(self.state.active)
        if not active.any():
            return [], 0.0
        vals = np.asarray(self._values())
        vals = np.where(active, vals, -np.inf)
        b = int(np.argmax(vals))
        size = int(np.asarray(self.state.sizes)[b])
        return [int(i) for i in np.asarray(self.state.members)[b, :size]], \
            float(vals[b])

    def _values(self) -> jax.Array:
        return _table_values(self.state.caches, self._seed, self._aux,
                             fn=self.spec.fn)

    def evaluations(self) -> int:
        self._fold_evals()
        return self._evals

    def member_ids(self) -> list[int]:
        """Ids present in any live sieve's member table (service retention)."""
        st = self.state
        live = np.asarray(st.active)[:, None] & (
            np.arange(self.spec.k)[None, :] < np.asarray(st.sizes)[:, None])
        return sorted({int(i) for i in np.asarray(st.members)[live]})

    def _distance_rows(self, X) -> jax.Array:
        # both engines consume rows from the SAME jitted executable — host
        # and device decisions see bitwise-identical distances
        return self.f.point_distances_block(X).astype(jnp.float32)

    def _block_payload(self, X) -> jax.Array:
        """What ``offer`` hands ``_consume`` per padded block: distance rows
        by default; the mesh-sharded engine passes the raw vectors through
        and computes distances shard-locally inside its scan."""
        return self._distance_rows(X)

    def _consume(self, idxp, payload, valid):
        """Advance the engine by one padded block; returns the accept mask —
        a host array (mirror) or an un-synced device value (device plans)."""
        raise NotImplementedError


class HostSieveMirror(_SieveEngineBase):
    """The exact array-semantics mirror: one dispatch per element.

    Runs the identical :func:`_element_step` the device scan runs, but jitted
    per element — the per-element host↔device round-trip the device engine
    exists to amortize, and the parity reference for it.
    """

    def _consume(self, idxp, dmat, valid) -> np.ndarray:
        accepted = np.zeros(len(idxp), bool)
        for b in range(len(idxp)):
            if not valid[b]:  # padded no-op step: state provably unchanged
                continue
            self.state, acc = _element_step_jit(
                self.state, self._seed, jnp.int32(idxp[b]), dmat[b], True,
                spec=self.spec, row_aux=self._aux)
            accepted[b] = bool(acc)
        return accepted


class DeviceSieveEngine(_SieveEngineBase):
    """Device-resident sieve table: one scan dispatch per stream block.

    State never leaves the device between blocks (beyond the accept mask
    and the evaluation-counter fold the block boundary reads anyway).

    ``mesh`` column-shards the (S_max, n) cache table — and the cache seed,
    the row auxiliary, and each element's distance row — over the mesh's
    ``data_axes``, cutting per-device streaming state to O(S_max·n/p): the
    pod-scale ground-set regime. The scan body is the identical
    :func:`_element_step`; only its two ground-set reductions become
    psum'd per-shard partials (the sieve-gain kernel already normalizes by
    an explicit global n, so per-shard table tiles psum exactly like
    selection gains). Thresholds, sizes, member slots, and the evaluation
    counter stay replicated, so ``best``/``member_ids``/snapshots read
    them with one fetch — never a per-shard gather."""

    def __init__(self, f, spec: SieveSpec, block_size: int = 64,
                 mesh=None, data_axes: Sequence[str] = ("data",),
                 overlap: bool = True, max_in_flight: int = 4):
        from jax.sharding import NamedSharding, PartitionSpec as P

        # mesh geometry first: _SieveEngineBase.__init__ asks the
        # _initial_state hook for the table, which must be born sharded
        self.mesh = mesh
        if mesh is not None:
            axes = tuple(data_axes)
            self._axes = axes
            ndev = 1
            for a in axes:
                ndev *= mesh.shape[a]
            self._n_pad = ((f.n + ndev - 1) // ndev) * ndev
            self._n_total = f.n
            self._shardings = SieveState(
                *[NamedSharding(mesh, s) for s in _state_specs(axes)])
        super().__init__(f, spec, block_size, overlap=overlap,
                         max_in_flight=max_in_flight)
        self._counter_key = f"sieve_{spec.variant}"
        if mesh is None:
            return
        self._counter_key = f"sieve_{spec.variant}_sharded"
        # padding rows carry the function's pad sentinels (exemplar: seed 0
        # so relu(0 − d) = 0; facility location: seed/aux +inf so pad gains
        # and stats vanish; saturated coverage: cap 0 self-masks) — exact
        # under the real-n normalizer. The padded placement itself is the
        # selection engine's (cached on f), so a sieve engine and a sharded
        # selection run on the same mesh share ONE resident copy of V's
        # shards.
        from repro.core.distributed import _placed_sharded

        entry = _placed_sharded(f, mesh, self._axes, replicated_pool=False)
        self._V_sh = entry["V_sh"]
        self._seed_sh = entry["seed_sh"]
        self._aux_sh = entry["aux_sh"]
        self._offer_fn = make_sharded_offer_scan(
            mesh, self._axes, spec=spec, n_total=f.n,
            distance=f.cfg.distance, policy_name=f.cfg.resolved_policy().name,
            counter_key=self._counter_key)

    def _initial_state(self) -> SieveState:
        if self.mesh is None:
            return super()._initial_state()
        # jit with out_shardings lays the zeros out sharded from birth: the
        # full (S_max, n_pad) table never exists on any single device
        return jax.jit(
            lambda: init_state(self._n_pad, self.spec),
            out_shardings=self._shardings)()

    def _block_payload(self, X) -> jax.Array:
        if self.mesh is None:
            return self._distance_rows(X)
        # raw vectors pass through replicated; distance rows are computed
        # shard-locally inside the scan, so no (B, n) block ever exists
        return jnp.asarray(X)

    def _values(self) -> jax.Array:
        if self.mesh is None:
            return _table_values(self.state.caches, self._seed, self._aux,
                                 fn=self.spec.fn)
        return _table_values_padded(self.state.caches, self._seed_sh,
                                    self._aux_sh, fn=self.spec.fn,
                                    n_total=self._n_total)

    def _consume(self, idxp, payload, valid):
        # the scan donates the state carry: the pre-call ``self.state``
        # buffers are consumed by the dispatch and the rebind below is the
        # only live reference — the table aliases in place, never copies.
        # The accept mask is returned as a DEVICE value (no host sync);
        # ``offer`` drains masks after the whole pipeline is issued.
        if self.mesh is None:
            self.state, acc = _offer_block_scan(
                self.state, self._seed, self._aux, jnp.asarray(idxp),
                payload, jnp.asarray(valid), spec=self.spec,
                counter_key=self._counter_key)
        else:
            self.state, acc = self._offer_fn(
                self.state, self._V_sh, self._seed_sh, self._aux_sh,
                payload, jnp.asarray(idxp), jnp.asarray(valid))
        return acc


class BatchedSieveEngine:
    """P independent stream partitions advanced by ONE dispatch per block.

    The streaming analogue of ``run_selection_batch``: each partition owns a
    full fixed-capacity sieve table (a (P, …)-batched :class:`SieveState`),
    and one :func:`_offer_block_scan_batched` dispatch per block advances
    all of them — the per-partition transition is the IDENTICAL
    :func:`_element_step` under ``vmap`` (its reductions are trailing-axis-
    wise), so every partition's members, values, and evaluation counts are
    bit-identical to a standalone :class:`DeviceSieveEngine` fed the same
    sub-stream. Kernel backends score all P tables in one grid-over-P fused
    launch (:func:`repro.kernels.ops.sieve_gains` on (P, r, n) tables).

    Shares the overlapped-offer pipeline semantics of
    :class:`_SieveEngineBase`: staged payloads, donated state carry, deferred
    accept masks, lazy evaluation fold.
    """

    _I32 = np.iinfo(np.int32)

    def __init__(self, f, spec: SieveSpec, n_streams: int,
                 block_size: int = 64, overlap: bool = True,
                 max_in_flight: int = 4):
        if n_streams < 1:
            raise ValueError(f"n_streams must be >= 1, got {n_streams}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.f = f
        self.spec = spec
        self.n_streams = int(n_streams)
        self.block_size = block_size
        self.overlap = overlap
        self.max_in_flight = max_in_flight
        self._seed = jnp.asarray(f.cache_seed, jnp.float32)
        self._aux = jnp.asarray(f.row_aux, jnp.float32)
        st0 = init_state(f.n, spec)
        self.states = jax.tree.map(
            lambda a: jnp.stack([a] * self.n_streams), st0)
        self._evals = np.zeros(self.n_streams, np.int64)
        self._counter_key = f"sieve_{spec.variant}_batched"

    def offer(self, idx_parts: Sequence, X_parts: Sequence
              ) -> list[np.ndarray]:
        """Offer per-partition element runs (ragged; empty allowed) and
        return per-partition accept masks. Partitions shorter than the
        longest run ride the shared blocks as ``valid=False`` padding."""
        P, B, d = self.n_streams, self.block_size, self.f.dim
        if len(idx_parts) != P or len(X_parts) != P:
            raise ValueError(
                f"expected {P} partition runs, got "
                f"{len(idx_parts)}/{len(X_parts)}")
        idxs = [_SieveEngineBase._validate_ids(self, i) for i in idx_parts]
        Xs = [np.asarray(x, np.float32).reshape(-1, d) for x in X_parts]
        for p, (i, x) in enumerate(zip(idxs, Xs)):
            if len(i) != len(x):
                raise ValueError(
                    f"partition {p}: {len(i)} ids vs {len(x)} vectors")
        L = max((len(i) for i in idxs), default=0)
        handles: list = []
        inflight: list = []
        for s in range(0, L, B):
            idxp = np.full((B, P), -1, np.int32)
            valid = np.zeros((B, P), bool)
            Xb = np.zeros((P, B, d), np.float32)
            nbs = []
            for p in range(P):
                part = idxs[p][s:s + B]
                nb = len(part)
                nbs.append(nb)
                if nb:
                    idxp[:nb, p] = part
                    valid[:nb, p] = True
                    Xb[p, :nb] = Xs[p][s:s + B]
            # ONE distance dispatch for the whole (P, B) block — the same
            # jitted executable the unbatched engines use, at P·B rows —
            # then element-major layout for the scan
            Xd = jax.device_put(Xb.reshape(P * B, d))
            dmat = self.f.point_distances_block(Xd).astype(jnp.float32)
            dmatb = dmat.reshape(P, B, -1).transpose(1, 0, 2)
            self.states, acc = _offer_block_scan_batched(
                self.states, self._seed, self._aux, jnp.asarray(idxp),
                dmatb, jnp.asarray(valid), spec=self.spec,
                counter_key=self._counter_key)
            handles.append((acc, nbs))
            if not self.overlap:
                jax.block_until_ready(acc)
                self._fold_evals()
            else:
                inflight.append(acc)
                if len(inflight) > self.max_in_flight:
                    jax.block_until_ready(inflight.pop(0))
        out: list[list] = [[] for _ in range(P)]
        for acc, nbs in handles:
            a = np.asarray(acc)                      # (B, P)
            for p, nb in enumerate(nbs):
                if nb:
                    out[p].append(a[:nb, p])
        return [np.concatenate(o) if o else np.zeros(0, bool) for o in out]

    def _fold_evals(self) -> None:
        e = np.asarray(self.states.evals)
        if e.any():
            self._evals += e.astype(np.int64)
            self.states = self.states._replace(
                evals=jnp.zeros_like(self.states.evals))

    def evaluations(self, p: Optional[int] = None) -> int:
        self._fold_evals()
        return int(self._evals.sum()) if p is None else int(self._evals[p])

    def _values(self) -> np.ndarray:
        """(P, S_max) per-sieve f-values — one dispatch for all partitions
        (the flattened table rides the same jitted ``_table_values``)."""
        P, S, n = self.states.caches.shape
        vals = _table_values(self.states.caches.reshape(P * S, n),
                             self._seed, self._aux, fn=self.spec.fn)
        return np.asarray(vals).reshape(P, S)

    def best_all(self) -> list[tuple[list[int], float]]:
        """Per-partition (members, value) of each best live sieve."""
        active = np.asarray(self.states.active)
        sizes = np.asarray(self.states.sizes)
        members = np.asarray(self.states.members)
        vals = np.where(active, self._values(), -np.inf)
        out = []
        for p in range(self.n_streams):
            if not active[p].any():
                out.append(([], 0.0))
                continue
            b = int(np.argmax(vals[p]))
            size = int(sizes[p, b])
            out.append(([int(i) for i in members[p, b, :size]],
                        float(vals[p][b])))
        return out

    def member_ids(self) -> list[int]:
        """Ids live in any partition's member tables (service retention)."""
        st = self.states
        live = np.asarray(st.active)[:, :, None] & (
            np.arange(self.spec.k)[None, None, :]
            < np.asarray(st.sizes)[:, :, None])
        return sorted({int(i) for i in np.asarray(st.members)[live]})


def make_batched_sieve_engine(f, k: int, eps: float, n_streams: int,
                              variant: str = "sieve",
                              s_max: Optional[int] = None,
                              block_size: int = 64,
                              backend: Optional[str] = None,
                              overlap: bool = True,
                              max_in_flight: int = 4) -> BatchedSieveEngine:
    """Build the P-partition batched sieve engine (see
    :class:`BatchedSieveEngine`). ``backend=None`` inherits ``f.cfg.backend``
    exactly like :func:`make_sieve_engine`."""
    if backend is None:
        backend = f.cfg.backend \
            if f.cfg.backend in ("pallas", "pallas_interpret") else "jnp"
    spec = make_spec(k, eps, variant, s_max, backend=backend, fn=f.spec)
    return BatchedSieveEngine(f, spec, n_streams, block_size=block_size,
                              overlap=overlap, max_in_flight=max_in_flight)


def make_sieve_engine(f, k: int, eps: float, variant: str = "sieve",
                      mode: str = "device", s_max: Optional[int] = None,
                      block_size: int = 64,
                      backend: Optional[str] = None,
                      mesh=None,
                      data_axes: Sequence[str] = ("data",),
                      overlap: bool = True,
                      max_in_flight: int = 4) -> _SieveEngineBase:
    """Build a sieve engine under an execution plan (``host`` | ``device`` |
    ``device_sharded``), mirroring the selection engine's strategy×plan
    composition. The engine streams whatever SIEVE_ELIGIBLE objective ``f``
    carries (``f.spec``); ineligible functions raise at construction. Both
    plans take ``block_size`` — it shapes the (padded) distance dispatch, so
    host and device engines built with the same value run the same
    executables.

    ``backend`` picks the element step's scoring path (``None`` inherits
    ``f.cfg.backend``): kernel backends run the fused table × element gain
    under the function's min/max template
    (:func:`repro.kernels.ops.sieve_gains`) instead of the plain jnp
    reduction — in BOTH plans, so parity stays structural. A function with
    no kernel template silently scores through jnp (the same normalization
    the selection engine applies).

    ``mesh`` (or ``mode="device_sharded"``, which defaults to a 1-D mesh
    over all local devices) column-shards the sieve cache table over
    ``data_axes`` — see :class:`DeviceSieveEngine`. The host mirror is a
    per-element reference and does not shard.
    """
    if backend is None:
        backend = f.cfg.backend \
            if f.cfg.backend in ("pallas", "pallas_interpret") else "jnp"
    spec = make_spec(k, eps, variant, s_max, backend=backend, fn=f.spec)
    if mode == "device_sharded":
        from repro.core.distributed import _resolve_mesh

        mesh = _resolve_mesh(mesh, tuple(data_axes))
        mode = "device"
    if mode == "host":
        if mesh is not None:
            raise ValueError(
                "the host mirror is the per-element reference; it does not "
                "take a mesh")
        return HostSieveMirror(f, spec, block_size=block_size,
                               overlap=overlap, max_in_flight=max_in_flight)
    if mode == "device":
        return DeviceSieveEngine(f, spec, block_size=block_size, mesh=mesh,
                                 data_axes=data_axes, overlap=overlap,
                                 max_in_flight=max_in_flight)
    raise ValueError(f"unknown streaming mode {mode!r}; 'host', 'device' "
                     f"or 'device_sharded'")
