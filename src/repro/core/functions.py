"""Submodular functions behind ONE cache-semantics protocol (paper Def. 5 +).

``ExemplarClustering`` is the paper's function

    f(S) = L({e0}) − L(S ∪ {e0})

wrapped around the multiset evaluation engine, plus the *optimizer-aware
incremental interface* (min-distance cache) used by Greedy — see DESIGN.md §2
"one step further".

The paper's evaluation trick — keep an n-sized per-element cache on device
and score candidates as a fold over it — is not specific to exemplar
clustering. This module factors it into a **cache-semantics protocol** every
execution plan (host loop, one-dispatch device scan, the three mesh-sharded
plans, the streaming sieve table) consumes generically:

* ``init_cache() -> (vec, aux)`` — the empty-set cache: a per-element (n,)
  float32 vector plus one scalar of winner-dependent state (graph cut's
  pairwise penalty; 0 elsewhere).
* ``gains_from_cache(cache, idx) -> (m,)`` — marginal gains of candidate
  *indices* against the cache.
* ``fold_winner(cache, j) -> cache`` — fold one accepted winner in.
* ``value_from_cache(cache) -> float`` — f(S) from the cache alone.

Plus the streaming hooks (``point_distances_block`` and the sieve-row gain /
fold forms) and, for the device plans, the trace-level dispatch helpers
below: each function is identified by a hashable :class:`FnSpec` that rides
the jit statics, and a family of ``spec``-dispatched module functions
(``gains_rows`` / ``fold_vec_rows`` / ``stat_rows`` / ``value_from_stat`` /
…) give every plan the same arithmetic to trace.

Registered objectives (``FUNCTIONS``) and their cache semantics:

========================  ==========================  =======================
objective                 cache vec semantics          candidate gain
========================  ==========================  =======================
``exemplar``              min-distance m_i (seeded    n⁻¹ Σ relu(m_i − d_ic)
                          d(v_i, e0)); fold = min
``facility_location``     max-similarity c_i (seeded  n⁻¹ Σ relu(s_ic − c_i)
                          0); fold = max — the exact
                          dual of the min cache
``graph_cut``             coverage Σ_{j∈S} s_ij;      n⁻¹ Σ s_ic −
                          fold = add; aux carries     (λ/n)(2·c_c + s_cc)
                          the pairwise penalty
``saturated_coverage``    coverage, capped at         n⁻¹ Σ [min(c_i+s_ic,
                          cap_i = sat·Σ_j s_ij;       cap_i) − min(c_i,
                          fold = add                  cap_i)]
``feature_based``         per-feature mass Σ|v_s|     d⁻¹ Σ_t [√(acc_t+F_ct)
                          (a (d,) cache — host         − √acc_t]
                          plans only)
========================  ==========================  =======================

Similarity functions use ONE transform of the configured distance,
``s(x, y) = relu(SIM_ALPHA + SIM_BETA · d(x, y))`` — for the ``rbf``
distance (d = 2 − 2·exp(−γ‖x−y‖²) ∈ [0, 2]) this is exactly exp(−γ‖x−y‖²),
and for ``sqeuclidean`` a hinge similarity with s(x, x) = 1. Because the
transform is affine-then-relu, the Pallas gain kernels evaluate it *in-tile*
from the distance they already computed (see the shared min/max kernel
template in :mod:`repro.kernels.marginal_gain`), and every gain normalizes
by an explicit global ``n_total`` so per-shard tiles remain exact psum
partials under the sharded plans.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import distances as dist_mod
from repro.core import tracing
from repro.core.evaluator import (EvalConfig, e0_column_and_loss,
                                  evaluate_multiset)
from repro.core.multiset import PackedMultiset, pack_base_plus_candidates, pack_sets
from repro.core.precision import resolve as resolve_policy

#: Similarity transform s = relu(SIM_ALPHA + SIM_BETA · d): the ONE affine
#: the kernels evaluate in-tile. With d the rbf distance this is exactly the
#: rbf kernel value; with sqeuclidean it is a hinge similarity of range 1.
SIM_ALPHA = 1.0
SIM_BETA = -0.5
#: s(x, x) — the affine at d = 0 (every registered distance has d(x,x)=0).
SIM_SELF = 1.0

#: Functions the device execution plans (device / device_sharded /
#: device_sharded_pool / greedi) can run: an (n,)-vec cache folded by
#: winner distances. ``feature_based`` keeps a (d,)-shaped cache and is
#: host-plans-only by construction.
DEVICE_PLAN_ELIGIBLE = frozenset(
    {"exemplar", "facility_location", "graph_cut", "saturated_coverage"})

#: Functions the streaming sieve table supports: threshold sieves need
#: monotone gains from the (S_max, n) row caches alone (graph cut's gain
#: needs the winner-indexed penalty, which a stream element doesn't have).
SIEVE_ELIGIBLE = frozenset(
    {"exemplar", "facility_location", "saturated_coverage"})


class FnSpec(NamedTuple):
    """Hashable (→ jit-static) identity of a submodular objective.

    Rides the static arguments of every device-plan trace and the sharded
    scan cache keys, so each registered function compiles its own executable
    while sharing one engine construction. ``lam`` (graph cut) and ``sat``
    (saturated coverage) are the only per-function parameters that reach
    traced arithmetic.
    """

    name: str = "exemplar"
    lam: float = 0.0
    sat: float = 0.0


# ---------------------------------------------------------------------------
# Trace-level semantics, dispatched on the (static) FnSpec. These are the
# ONE definition of each objective's arithmetic: the host protocol methods,
# the single-device scan, the three sharded plans, and the sieve table all
# call the same functions, which is what makes their selections agree.
# ---------------------------------------------------------------------------


def similarity(D):
    """s = relu(SIM_ALPHA + SIM_BETA · d) applied elementwise."""
    return jnp.maximum(SIM_ALPHA + SIM_BETA * D, 0.0)


def kernel_template(spec: FnSpec):
    """The (fold, affine) parameterization of the shared Pallas gain-kernel
    template, or None when the function has no kernel form (saturated
    coverage's capped-min gain is not an affine-relu of the distance;
    feature_based never touches distances).

    ``fold="min"`` scores ``relu(cache − d)`` (the exemplar min-cache);
    ``fold="max"`` scores ``relu((α + β·d) − cache)`` — the max-cache dual,
    exact because the cache is ≥ 0 so the inner relu of the similarity is
    redundant inside the outer one.
    """
    if spec.name == "exemplar":
        return ("min", None)
    if spec.name in ("facility_location", "graph_cut"):
        return ("max", (SIM_ALPHA, SIM_BETA))
    return None


def kernel_fused_ok(spec: FnSpec) -> bool:
    """Whether the fused fold-and-score kernel applies: the fold must be the
    min/max of the template (graph cut *scores* through the max template —
    against its static row_aux — but folds by addition, outside)."""
    return spec.name in ("exemplar", "facility_location")


def pad_seed(spec: FnSpec) -> float:
    """Cache-seed value for zero-padding rows under the sharded plans.

    Exemplar pads 0 (relu(0 − d) = 0 — pads never gain). The max-cache
    functions pad +inf: a zero V row is a *real-looking* point whose
    similarity to candidates is positive, so only an infinite cache entry
    (relu(s − inf) = 0) makes pad rows inert. Additive caches pad 0 and
    rely on ``pad_row_aux`` to zero their gain/stat contributions.
    """
    return float("inf") if spec.name == "facility_location" else 0.0


def pad_row_aux(spec: FnSpec) -> float:
    """Row-auxiliary value for padding rows: the dead-row sentinel.

    facility_location / graph_cut mark pads +inf (masks their stat rows;
    graph cut additionally *scores* against row_aux, so +inf zeroes pad
    gains); saturated_coverage pads cap = 0 (a zero cap self-masks both
    gains and stat).
    """
    if spec.name in ("facility_location", "graph_cut"):
        return float("inf")
    return 0.0


def score_cache_rows(spec: FnSpec, vec, row_aux):
    """The per-row baseline the gain formula subtracts against — what the
    kernel template receives as its ``cache`` operand. Graph cut's heavy
    term Σ_i s_ic is S-independent, so it scores against the *static*
    row_aux (0 on real rows, +inf on pads) and the live cache only enters
    through the winner-indexed penalty (:func:`gains_index_extra`)."""
    if spec.name == "graph_cut":
        return row_aux
    return vec


def gains_rows(spec: FnSpec, sc, D, row_aux):
    """(n, m) per-row gain contributions (pre-normalizer) of candidates with
    distance columns ``D`` against score-cache rows ``sc``."""
    if spec.name == "exemplar":
        return jnp.maximum(sc[:, None] - D, 0.0)
    if spec.name in ("facility_location", "graph_cut"):
        # relu((α + β·d) − cache): cache ≥ 0 ⇒ identical to
        # relu(relu(α + β·d) − cache), with one relu fewer in-tile
        return jnp.maximum((SIM_ALPHA + SIM_BETA * D) - sc[:, None], 0.0)
    if spec.name == "saturated_coverage":
        s = similarity(D)
        cap = row_aux[:, None]
        return jnp.minimum(sc[:, None] + s, cap) - jnp.minimum(sc[:, None], cap)
    raise ValueError(f"no row-gain form for function {spec.name!r}")


def gains_formula_spec(spec: FnSpec, V, cands, sc, row_aux, pair, policy,
                       n_total=None):
    """Candidate gains (m,) — the generic form of :func:`gains_formula`.

    ``n_total`` overrides the |V| normalizer — pass the *global* ground-set
    size when V is one row-shard of a mesh-sharded ground set, so that the
    per-shard partials ``psum`` to the exact global gains. Graph cut's
    winner-indexed penalty is NOT included here (it needs candidate
    *indices*, not payload) — callers add :func:`gains_index_extra`.
    """
    D = pair(V, cands, policy)  # (n, m)
    rows = gains_rows(spec, sc, D, row_aux)
    return jnp.sum(rows, axis=0) / (V.shape[0] if n_total is None else n_total)


def gains_index_extra(spec: FnSpec, vec, gidx, off, n_loc, n_total):
    """Per-candidate additive gain term that reads the candidate's OWN cache
    entry (graph cut's redundancy penalty −(λ/n)(2·cov_S(c) + s_cc)); None
    for every other function.

    Shard-safe by construction: each cache row is a *complete* value on its
    owning shard (the fold adds every winner's full similarity column), so
    the owner contributes the one real term and every other shard 0 — the
    term rides the existing per-batch gains psum with no extra collective.
    """
    if spec.name != "graph_cut":
        return None
    rel = gidx - off
    own = (rel >= 0) & (rel < n_loc)
    vc = vec[jnp.clip(rel, 0, n_loc - 1)]
    return jnp.where(
        own, -(spec.lam / n_total) * (2.0 * vc + SIM_SELF), 0.0
    ).astype(jnp.float32)


def fold_vec_rows(spec: FnSpec, vec, dw):
    """Fold one winner's float32 distance column ``dw`` into the cache rows.
    Broadcasts over leading axes (the sieve table folds (S_max, n) against
    a (n,) element row)."""
    if spec.name == "exemplar":
        return jnp.minimum(vec, dw)
    if spec.name == "facility_location":
        return jnp.maximum(vec, similarity(dw))
    if spec.name in ("graph_cut", "saturated_coverage"):
        return vec + similarity(dw)
    raise ValueError(f"no vec fold for function {spec.name!r}")


def fold_aux(spec: FnSpec, vec, aux, gidx, off, n_loc, psum=None):
    """Advance the scalar aux state for winner index ``gidx`` (computed from
    the cache BEFORE the winner's column folds in). Graph cut accumulates
    its pairwise penalty P ← P + 2·cov_S(w) + s_ww via an owner-shard gather
    (``psum`` reduces it on mesh plans; pass None on single-device). Every
    other function returns ``aux`` unchanged — and issues no collective.
    """
    if spec.name != "graph_cut":
        return aux
    rel = gidx - off
    own = (rel >= 0) & (rel < n_loc)
    vw = jnp.where(own, vec[jnp.clip(rel, 0, n_loc - 1)], 0.0)
    if psum is not None:
        vw = psum(vw)
    return aux + 2.0 * vw + SIM_SELF


def stat_rows(spec: FnSpec, vec, row_aux):
    """The per-row statistic whose global mean enters the trajectory value.

    Masks dead (padding) rows through ``row_aux`` — the max-cache functions
    carry +inf sentinels there, saturated coverage a 0 cap — so zero-padded
    shards sum exactly. Broadcasts over leading axes (sieve tables).
    """
    if spec.name == "exemplar":
        return vec
    if spec.name in ("facility_location", "graph_cut"):
        return jnp.where(jnp.isinf(row_aux), 0.0, vec)
    if spec.name == "saturated_coverage":
        return jnp.minimum(vec, row_aux)
    raise ValueError(f"no stat form for function {spec.name!r}")


def value_from_stat(spec: FnSpec, v0, mean_stat, aux=0.0, n_total=1):
    """f(S) from the global stat mean: exemplar's L0 − mean(cache), the
    coverage functions' mean directly, graph cut's mean minus the aux
    penalty. ``v0`` is the empty-set baseline (mean of the REAL seed rows:
    L0 for exemplar, 0 elsewhere)."""
    if spec.name == "exemplar":
        return v0 - mean_stat
    if spec.name == "graph_cut":
        return mean_stat - spec.lam * aux / n_total
    return mean_stat


def sieve_gain_rows(spec: FnSpec, caches, dvec, row_aux):
    """(rows, n) per-element gain contributions of one stream element
    (distance row ``dvec``) against each cache row — the jnp form of the
    sieve kernel template."""
    if spec.name == "exemplar":
        return jnp.maximum(caches - dvec[None, :], 0.0)
    if spec.name == "facility_location":
        return jnp.maximum(
            (SIM_ALPHA + SIM_BETA * dvec)[None, :] - caches, 0.0)
    if spec.name == "saturated_coverage":
        s = similarity(dvec)[None, :]
        cap = row_aux[None, :]
        return jnp.minimum(caches + s, cap) - jnp.minimum(caches, cap)
    raise ValueError(f"function {spec.name!r} has no sieve-row gain form")


def sieve_fold_rows(spec: FnSpec, caches, dvec, accept):
    """Fold one element into the rows where ``accept`` holds."""
    folded = fold_vec_rows(spec, caches, dvec[None, :])
    return jnp.where(accept[:, None], folded, caches)


# ---------------------------------------------------------------------------
# Legacy exemplar-only reduction (kept: the standalone distributed
# evaluators and external callers consume it under this name)
# ---------------------------------------------------------------------------


def gains_formula(V, cands, mincache, pair, policy, n_total=None):
    """Δ(c_j | S) = |V|⁻¹ Σ_i relu(m_i − d(v_i, c_j)) for all candidates.

    The exemplar instance of :func:`gains_formula_spec`, kept under its
    original name for the standalone distributed evaluators.

    ``n_total`` overrides the |V| normalizer — pass the *global* ground-set
    size when V is one row-shard of a mesh-sharded ground set, so that the
    per-shard partials ``psum`` to the exact global gains.
    """
    D = pair(V, cands, policy)  # (n, m)
    gains = jnp.sum(jnp.maximum(mincache[:, None] - D, 0.0), axis=0)
    return gains / (V.shape[0] if n_total is None else n_total)


@partial(jax.jit, static_argnames=("distance", "policy_name", "n_total"))
def _gains_vs_cache(V, cands, mincache, distance, policy_name, n_total=None):
    pair = dist_mod.resolve_pairwise(distance)
    return gains_formula(V, cands, mincache, pair, resolve_policy(policy_name),
                         n_total=n_total)


@partial(jax.jit, static_argnames=("distance", "policy"))
def _point_distances_block(V, X, distance, policy):
    # policy rides as the static itself (frozen dataclass → hashable), so a
    # custom PrecisionPolicy object works without a registry entry
    return dist_mod.resolve_pairwise(distance)(V, X, policy).T


@partial(jax.jit, static_argnames=("distance", "policy_name"))
def _update_cache(V, new_point, mincache, distance, policy_name):
    pair = dist_mod.resolve_pairwise(distance)
    D = pair(V, new_point[None, :], resolve_policy(policy_name))[:, 0]
    return jnp.minimum(mincache, D)


# ---------------------------------------------------------------------------
# Protocol jit helpers (shared by every vec-cache function's host methods)
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("fn", "distance", "policy_name"))
def _protocol_gains_jit(V, vec, row_aux, idx, *, fn, distance, policy_name):
    pair = dist_mod.resolve_pairwise(distance)
    policy = resolve_policy(policy_name)
    n = V.shape[0]
    sc = score_cache_rows(fn, vec, row_aux)
    g = gains_formula_spec(fn, V, V[idx], sc, row_aux, pair, policy, n_total=n)
    extra = gains_index_extra(fn, vec, idx, 0, n, n)
    return g if extra is None else g + extra


@partial(jax.jit, static_argnames=("fn",))
def _protocol_extra_jit(vec, idx, *, fn, n_total):
    return gains_index_extra(fn, vec, idx, 0, vec.shape[0], n_total)


@partial(jax.jit, static_argnames=("fn", "distance", "policy_name"))
def _protocol_fold_jit(V, vec, aux, j, *, fn, distance, policy_name):
    pair = dist_mod.resolve_pairwise(distance)
    policy = resolve_policy(policy_name)
    dw = pair(V, V[j][None, :], policy)[:, 0].astype(jnp.float32)
    new_aux = fold_aux(fn, vec, aux, j, 0, V.shape[0])
    return fold_vec_rows(fn, vec, dw), new_aux


@partial(jax.jit, static_argnames=("fn",))
def _protocol_value_jit(vec, aux, row_aux, v0, *, fn, n_total):
    return value_from_stat(fn, v0, jnp.mean(stat_rows(fn, vec, row_aux)),
                           aux, n_total)


@partial(jax.jit, static_argnames=("distance", "policy_name", "block"))
def _saturation_caps(V, sat, *, distance, policy_name, block):
    """cap_i = sat · Σ_j s(d(v_i, v_j)) in (n, block) column tiles (the
    saturated-coverage ceiling — one O(n²·d) pass at construction)."""
    pair = dist_mod.resolve_pairwise(distance)
    policy = resolve_policy(policy_name)
    n = V.shape[0]
    nb = -(-n // block)
    Vp = jnp.pad(V, ((0, nb * block - n), (0, 0)))
    valid = (jnp.arange(nb * block) < n).reshape(nb, block)

    def col(args):
        Cb, vb = args
        s = similarity(pair(V, Cb, policy))
        s = jnp.where(vb[None, :], s, 0.0)
        return jnp.sum(s.astype(jnp.float32), axis=1)

    caps = jnp.sum(jax.lax.map(col, (Vp.reshape(nb, block, -1), valid)),
                   axis=0)
    return sat * caps


# ---------------------------------------------------------------------------
# The function classes
# ---------------------------------------------------------------------------


class SubmodularFunction:
    """Base of the function zoo: the cache-semantics protocol over (V, cfg).

    Subclasses set ``spec`` (their :class:`FnSpec` identity) and, where the
    defaults don't apply, override ``cache_seed`` / ``row_aux`` / ``v0``.
    The four protocol methods below are the host execution plan; the device
    plans re-derive the identical arithmetic from ``spec`` at trace time.
    """

    spec: FnSpec = FnSpec()

    def __init__(self, V: jax.Array, cfg: EvalConfig = EvalConfig(),
                 e0: Optional[jax.Array] = None):
        self.V = jnp.asarray(V)
        self.cfg = cfg
        self.e0 = e0
        self._row_aux: Optional[jax.Array] = None
        self._cache_seed: Optional[jax.Array] = None

    # -- per-function state -------------------------------------------------

    @property
    def cache_seed(self) -> jax.Array:
        """(n,) float32 empty-set cache vector (0 for coverage caches).

        Memoized: repeated access (the serving layer stacks B seeds per
        dispatch) must not pay a device op each time. Callers that donate
        must copy — the returned buffer is shared."""
        if self._cache_seed is None:
            self._cache_seed = jnp.zeros((self.n,), jnp.float32)
        return self._cache_seed

    @property
    def row_aux(self) -> jax.Array:
        """(n,) float32 static per-row auxiliary (caps / score baseline)."""
        if self._row_aux is None:
            self._row_aux = jnp.zeros((self.n,), jnp.float32)
        return self._row_aux

    @property
    def v0(self) -> float:
        """Empty-set baseline f-value term (mean of the real seed rows)."""
        return 0.0

    # -- the cache-semantics protocol ---------------------------------------

    def init_cache(self, sharding=None):
        """The empty-set cache ``(vec, aux)``.

        Stored float32 regardless of policy: the cache seeds n-sized
        reductions, which overflow in f16 for large n even though the
        distances themselves were computed at policy precision.

        ``sharding`` optionally places the vec (a ``jax.sharding.Sharding``,
        typically the same row-sharding as a mesh-sharded V — the cache is
        V-aligned state and must live wherever V's rows live); the scalar
        aux is replicated state.
        """
        vec = self.cache_seed
        if sharding is not None:
            vec = jax.device_put(vec, sharding)
        return (vec, jnp.float32(0.0))

    def gains_from_cache(self, cache, idx) -> jax.Array:
        """Δ(c | S) for candidate *indices* ``idx`` against the cache.

        Kernel backends route through the shared min/max Pallas gain-kernel
        template when the function has one (see :func:`kernel_template`);
        functions without a kernel form fall back to the jnp reduction.
        """
        vec, _aux = cache
        idx = jnp.asarray(idx, jnp.int32)
        policy = self.cfg.resolved_policy()
        tmpl = kernel_template(self.spec)
        if self.cfg.backend in ("pallas", "pallas_interpret") \
                and tmpl is not None:
            if self.cfg.distance not in dist_mod.MXU_ELIGIBLE:
                raise ValueError(
                    f"kernel marginal gains support "
                    f"{sorted(dist_mod.MXU_ELIGIBLE)}, got "
                    f"{self.cfg.distance!r}")
            from repro.kernels import ops as kops

            g = kops.marginal_gain(
                self.V, self.V[idx],
                score_cache_rows(self.spec, vec, self.row_aux),
                policy=policy, fold=tmpl[0], score_affine=tmpl[1],
                rbf_gamma=dist_mod.RBF_GAMMA
                if self.cfg.distance == "rbf" else None,
                interpret=(self.cfg.backend != "pallas"))
            if self.spec.name == "graph_cut":
                g = g + _protocol_extra_jit(vec, idx, fn=self.spec,
                                            n_total=self.n)
            return g
        return _protocol_gains_jit(
            self.V, vec, self.row_aux, idx, fn=self.spec,
            distance=self.cfg.distance, policy_name=policy.name)

    def fold_winner(self, cache, j):
        """cache after folding winner index ``j`` in."""
        vec, aux = cache
        return _protocol_fold_jit(
            self.V, vec, aux, jnp.asarray(j, jnp.int32), fn=self.spec,
            distance=self.cfg.distance,
            policy_name=self.cfg.resolved_policy().name)

    def value_from_cache(self, cache) -> float:
        vec, aux = cache
        return float(_protocol_value_jit(
            vec, aux, self.row_aux, jnp.float32(self.v0), fn=self.spec,
            n_total=self.n))

    # -- streaming hooks ----------------------------------------------------

    def point_distances(self, x: jax.Array) -> jax.Array:
        """d(v_i, x) for all i — one streaming element against the ground set."""
        pair = dist_mod.resolve_pairwise(self.cfg.distance)
        policy = self.cfg.resolved_policy()
        return pair(self.V, x[None, :], policy)[:, 0]

    def point_distances_block(self, X: jax.Array,
                              policy: "Optional[str | object]" = None
                              ) -> jax.Array:
        """d(v_i, x_b) for a block of B stream elements — (B, n).

        One jitted engine dispatch for the whole block (the batched-streaming
        path); row b matches ``point_distances(X[b])`` up to matmul
        vectorization. ``policy`` overrides the config's precision policy for
        this block (name or :class:`~repro.core.precision.PrecisionPolicy`),
        threaded through as a jit-static so each policy compiles once — the
        streaming engine ingests at the configured precision while the sieve
        state stays float32.
        """
        pol = resolve_policy(policy if policy is not None
                             else self.cfg.resolved_policy())
        return _point_distances_block(self.V, jnp.asarray(X),
                                      self.cfg.distance, policy=pol)

    # -- metadata ------------------------------------------------------------

    @property
    def n(self) -> int:
        return self.V.shape[0]

    @property
    def dim(self) -> int:
        return self.V.shape[1]


class ExemplarClustering(SubmodularFunction):
    """Monotone submodular exemplar-clustering function over a ground set V.

    Args:
      V: (n, d) ground set.
      cfg: evaluation configuration (distance, precision, mode, backend).
      e0: auxiliary vector (paper: the all-zero vector). None → zeros.
    """

    spec = FnSpec(name="exemplar")

    # a host span (:mod:`repro.core.tracing`): the e0 column and the read
    # of L({e0}) wait for the device
    @partial(jax.profiler.annotate_function, name=tracing.FUNCTION_INIT)
    def __init__(self, V: jax.Array, cfg: EvalConfig = EvalConfig(),
                 e0: Optional[jax.Array] = None):
        super().__init__(V, cfg, e0)
        # L({e0}) is S-independent; computed "conventionally" once (paper
        # §IV-B-1), in the same dispatch as the column
        self.d_e0, L0 = e0_column_and_loss(self.V, e0, cfg.distance,
                                           cfg.policy)
        self.L0 = float(L0)

    @property
    def cache_seed(self) -> jax.Array:
        if self._cache_seed is None:
            self._cache_seed = self.d_e0.astype(jnp.float32)
        return self._cache_seed

    @property
    def v0(self) -> float:
        return self.L0

    # -- generic multiset interface (the paper's engine) --------------------

    def loss_multi(self, packed: PackedMultiset) -> jax.Array:
        """L(S_j ∪ {e0}) for all sets — (l,)."""
        return evaluate_multiset(self.V, packed, self.cfg, d_e0=self.d_e0)

    def value_multi(self, packed: PackedMultiset) -> jax.Array:
        """f(S_j) for all sets — (l,)."""
        return self.L0 - self.loss_multi(packed)

    def value(self, S: jax.Array) -> float:
        """f(S) for a single (k, d) set. Empty S → 0 (paper §IV)."""
        S = jnp.asarray(S)
        if S.ndim != 2:
            raise ValueError(f"S must be (k, d), got {S.shape}")
        if S.shape[0] == 0:
            return 0.0
        packed = PackedMultiset(S[None], jnp.array([S.shape[0]], jnp.int32))
        return float(self.value_multi(packed)[0])

    def value_sets(self, sets: Sequence[np.ndarray]) -> jax.Array:
        return self.value_multi(pack_sets(sets, dtype=self.cfg.resolved_policy().compute_dtype))

    def greedy_step_values(self, base: jax.Array, candidates: jax.Array) -> jax.Array:
        """Paper-faithful greedy step: f(S ∪ {c_j}) for all candidates."""
        packed = pack_base_plus_candidates(base, candidates)
        return self.value_multi(packed)

    # -- optimizer-aware incremental interface (beyond paper) ---------------

    def init_mincache(self, sharding=None) -> jax.Array:
        """m_i = d(v_i, e0): the min-dist cache of S = ∅ (e0 always included).

        The bare-(n,) exemplar form of :meth:`init_cache`, kept for callers
        of the original min-cache interface (same float32/sharding rules).
        """
        cache = self.d_e0.astype(jnp.float32)
        if sharding is not None:
            cache = jax.device_put(cache, sharding)
        return cache

    def marginal_gains(self, candidates: jax.Array, mincache: jax.Array,
                       use_kernel: bool = False,
                       n_total: Optional[int] = None) -> jax.Array:
        """Δ(c_j | S) for all candidates given S's min-dist cache. O(n·m·d).

        ``n_total`` is the sharding-aware normalizer: when this function
        instance wraps one row-shard of a global ground set, pass the global
        n so the returned per-shard partials ``psum`` to the global gains.
        """
        policy = self.cfg.resolved_policy()
        if use_kernel or self.cfg.backend in ("pallas", "pallas_interpret"):
            if self.cfg.distance not in dist_mod.MXU_ELIGIBLE:
                raise ValueError(
                    f"kernel marginal gains support "
                    f"{sorted(dist_mod.MXU_ELIGIBLE)}, got "
                    f"{self.cfg.distance!r}")
            from repro.kernels import ops as kops

            return kops.marginal_gain(
                self.V, candidates, mincache, policy=policy,
                rbf_gamma=dist_mod.RBF_GAMMA
                if self.cfg.distance == "rbf" else None,
                interpret=(self.cfg.backend != "pallas"),
                n_total=n_total,
            )
        return _gains_vs_cache(self.V, candidates, mincache,
                               self.cfg.distance, policy.name,
                               n_total=n_total)

    def update_mincache(self, mincache: jax.Array, new_point: jax.Array) -> jax.Array:
        return _update_cache(self.V, new_point, mincache,
                             self.cfg.distance, self.cfg.resolved_policy().name)

    def value_from_mincache(self, mincache: jax.Array) -> float:
        return self.L0 - float(jnp.mean(mincache))


class FacilityLocation(SubmodularFunction):
    """Facility location f(S) = n⁻¹ Σ_i max_{s∈S} s(v_i, s) — the exact
    max-cache dual of the exemplar min cache: seed 0, fold = maximum, gains
    relu(s_ic − c_i). Monotone submodular; scores through the shared Pallas
    kernel template with ``fold="max"``."""

    spec = FnSpec(name="facility_location")


class GraphCut(SubmodularFunction):
    """Graph cut f(S) = n⁻¹ Σ_i Σ_{j∈S} s_ij − (λ/n) Σ_{j,j'∈S} s_jj'.

    The cache vec carries per-element coverage Σ_{j∈S} s_ij (additive fold);
    the scalar aux carries the pairwise penalty. ``lam`` must lie in
    (0, 0.5]: with s ≥ 0 and s(x,x) = 1, λ ≤ 0.5 keeps every marginal gain
    non-negative (monotone), which the greedy family's guarantees assume.
    """

    def __init__(self, V: jax.Array, cfg: EvalConfig = EvalConfig(),
                 e0: Optional[jax.Array] = None, lam: float = 0.5):
        if not 0.0 < lam <= 0.5:
            raise ValueError(
                f"graph_cut lam must lie in (0, 0.5] (monotonicity holds "
                f"for λ ≤ 0.5 with s(x,x)=1), got {lam}")
        self.spec = FnSpec(name="graph_cut", lam=float(lam))
        super().__init__(V, cfg, e0)


class SaturatedCoverage(SubmodularFunction):
    """Saturated coverage f(S) = n⁻¹ Σ_i min(Σ_{j∈S} s_ij, cap_i) with
    cap_i = sat · Σ_j s_ij. Monotone submodular; its capped-min gain is not
    an affine-relu of the distance, so it scores through the jnp reduction
    on every backend (the documented non-kernel member of the zoo)."""

    def __init__(self, V: jax.Array, cfg: EvalConfig = EvalConfig(),
                 e0: Optional[jax.Array] = None, sat: float = 0.25):
        if not 0.0 < sat <= 1.0:
            raise ValueError(
                f"saturated_coverage sat must lie in (0, 1], got {sat}")
        self.spec = FnSpec(name="saturated_coverage", sat=float(sat))
        super().__init__(V, cfg, e0)

    @property
    def row_aux(self) -> jax.Array:
        if self._row_aux is None:
            self._row_aux = _saturation_caps(
                self.V, jnp.float32(self.spec.sat),
                distance=self.cfg.distance,
                policy_name=self.cfg.resolved_policy().name,
                block=min(1024, max(8, self.n)))
        return self._row_aux


@partial(jax.jit, static_argnames=())
def _feature_gains_jit(F, acc, idx):
    root = jnp.sqrt(acc)[None, :]
    return jnp.mean(jnp.sqrt(acc[None, :] + F[idx]) - root, axis=1)


class FeatureBased(SubmodularFunction):
    """Feature-based f(S) = d⁻¹ Σ_t √(Σ_{s∈S} |v_s|_t): a concave-over-
    modular function whose cache is the (d,)-shaped per-feature mass — NOT
    an n-sized per-element cache, so it runs on the host plans only (the
    device plans raise; there is nothing to shard along n)."""

    spec = FnSpec(name="feature_based")

    def __init__(self, V: jax.Array, cfg: EvalConfig = EvalConfig(),
                 e0: Optional[jax.Array] = None):
        super().__init__(V, cfg, e0)
        self.F = jnp.abs(self.V).astype(jnp.float32)

    def init_cache(self, sharding=None):
        acc = jnp.zeros((self.dim,), jnp.float32)
        if sharding is not None:
            acc = jax.device_put(acc, sharding)
        return (acc, jnp.float32(0.0))

    def gains_from_cache(self, cache, idx) -> jax.Array:
        acc, _ = cache
        return _feature_gains_jit(self.F, acc, jnp.asarray(idx, jnp.int32))

    def fold_winner(self, cache, j):
        acc, aux = cache
        return (acc + self.F[jnp.asarray(j, jnp.int32)], aux)

    def value_from_cache(self, cache) -> float:
        acc, _ = cache
        return float(jnp.mean(jnp.sqrt(acc)))


#: The registered function zoo: name → constructor ``F(V, cfg=..., e0=...)``
#: (per-function parameters default sensibly; construct directly to set
#: ``lam`` / ``sat``).
FUNCTIONS = {
    "exemplar": ExemplarClustering,
    "facility_location": FacilityLocation,
    "graph_cut": GraphCut,
    "saturated_coverage": SaturatedCoverage,
    "feature_based": FeatureBased,
}
