"""Submodular maximizers built on the multiset evaluation engine.

Every optimizer here evaluates *many* sets per step — the paper's central
observation ("optimizer-aware", §IV-A). Three evaluation styles are used:

* **multiset** — the paper-faithful path: each step packs
  ``{S ∪ {c_1}, …, S ∪ {c_m}}`` and calls the work-matrix engine. O(n·k·l).
* **mincache** — the beyond-paper incremental path: gains against the
  min-distance cache. O(n·l·d) per step (k drops out).
* **device** — the mincache recurrence hoisted entirely on device: a
  ``jax.lax.scan`` runs all k greedy rounds inside ONE jitted dispatch, with
  candidate gains, argmax selection, and the cache update never leaving the
  accelerator (no per-round host↔device copies, no per-round dispatch).

The greedy family (greedy / stochastic_greedy / lazy_greedy) is built on the
**selection engine** (:mod:`repro.core.engine`): a round-candidate strategy
(dense / stochastic / lazy-CELF) composed with an execution plan (``host``
reference loop, ``device`` one-dispatch scan, ``device_sharded`` mesh-sharded
scan with one O(m) psum per round — see :mod:`repro.core.distributed`).

The min-distance cache obeys the recurrence

    m_i^(0)   = d(v_i, e0)
    m_i^(t+1) = min(m_i^(t), d(v_i, s_{t+1}))          (s_{t+1} = round-t winner)
    Δ(c | S_t) = |V|⁻¹ Σ_i max(m_i^(t) − d(v_i, c), 0)
    f(S_t)     = L({e0}) − |V|⁻¹ Σ_i m_i^(t)

so each greedy round is one (n × m) distance evaluation plus an O(n) fold of
the winner — the device engine evaluates the fold *inside* the next round's
gain kernel (see ``kernels/marginal_gain.gain_update_eval``), which means the
winner's distance column never materializes in HBM.

Optimizer modes:
  greedy               ``mode="mincache"`` (host reference, alias ``"host"``),
                       ``mode="multiset"`` (paper-faithful), ``mode="device"``,
                       ``mode="device_sharded"`` (mesh-sharded V + cache).
  stochastic_greedy    ``mode="host"`` reference loop, ``mode="device"`` or
                       ``mode="device_sharded"``; all consume the same
                       precomputed per-round candidate sample matrix, so
                       selections agree (exactly on the jnp backend; on
                       pallas backends the in-kernel winner fold can differ
                       in the last ulp from the host's jnp update, which may
                       flip a near-tie argmax at reduced precision).
  lazy_greedy          CELF lazy evaluation with stale upper bounds:
                       ``mode="host"`` reference loop (the exact host-side
                       mirror of the engine's top-B rescore policy),
                       ``mode="device"`` (re-scoring against carried stale
                       bounds inside the one-dispatch scan) or
                       ``mode="device_sharded"``.
  sieve_streaming      Badanidiyuru et al. (1/2 − ε), streaming;
                       ``mode="host"`` / ``mode="device"``.
  sieve_streaming_pp   Kazemi et al., LB-pruned sieves (1/2 − ε), less
                       memory; ``mode="host"`` / ``mode="device"``.
  three_sieves         Buschjäger et al., single adaptive sieve ((1−ε)(1−1/e)
                       w.h.p.), minimal memory; host-only.
  salsa                Norouzi-Fard et al. dense-threshold ensemble
                       (simplified: fixed dense schedules, no OPT oracle);
                       ``mode="host"`` / ``mode="device"``.

The streaming family runs on the **sieve engine**
(:mod:`repro.core.streaming`): a fixed-capacity table of threshold sieves
keyed by integer exponent, living on device. ``mode="host"`` steps the table
one jitted dispatch per element (the exact array-semantics mirror);
``mode="device"`` consumes each stream block of ``block_size`` elements with
ONE jitted ``lax.scan`` over elements — singleton gain, grid rebuild, accept
rule, cache min-update, and member bookkeeping all in the scan body. Both
plans make bit-identical decisions, so selections AND evaluation counts agree
across modes.

All return an :class:`OptResult` (indices into V, value, trajectory, and the
number of *evaluations*). For the greedy family ``evaluations`` counts
**actually-scored candidates**: candidates whose gain entered a round's
argmax (already-selected candidates are masked out before the argmax and do
not count). Host and device plans count identically, so the numbers are
directly comparable across modes — and, for stochastic greedy, comparable
with the pool-sampling formulation despite the +k per-round overdraw.
"""
from __future__ import annotations

import math
from typing import Iterable, Optional, Sequence

import jax.numpy as jnp
import numpy as np

from repro.core.engine import (DEVICE_TRACE_COUNTS, OptResult,
                               run_selection, validate_candidates)
from repro.core.functions import ExemplarClustering, SubmodularFunction


def _require_exemplar(f: SubmodularFunction, what: str) -> ExemplarClustering:
    """Paths that consume exemplar-only structure (the packed multiset
    engine, the L0/d_e0 streaming shortcuts) guard here; everything else in
    this module speaks the generic cache-semantics protocol."""
    if f.spec.name != "exemplar":
        raise ValueError(
            f"{what} is exemplar-only (it evaluates through the packed "
            f"multiset / L0 interface); function {f.spec.name!r} runs on "
            f"the cache-protocol paths instead")
    return f


# ---------------------------------------------------------------------------
# Greedy family — strategies over the selection engine
# ---------------------------------------------------------------------------


def greedy(
    f: SubmodularFunction,
    k: int,
    mode: str = "mincache",
    candidates: Optional[np.ndarray] = None,
    block_m: Optional[int] = None,
    mesh=None,
    data_axes: Sequence[str] = ("data",),
) -> OptResult:
    """Algorithm 1 of the paper. ``mode`` picks the evaluation style:

    ``"mincache"`` (alias ``"host"``) — host loop over rounds, device gains.
    ``"multiset"`` — paper-faithful: pack {S ∪ {c}} ∀c and call the engine.
    ``"device"``  — all k rounds in one jitted ``lax.scan`` dispatch.
    ``"device_sharded"`` — the same scan with V and the min-distance cache
    row-sharded over a device ``mesh`` (defaults to all local devices on a
    1-D "data" mesh); one O(m) psum per round; candidate payload replicated.
    ``"device_sharded_pool"`` — additionally row-shards the candidate
    payload (O(n/p·d) resident per device; candidate blocks and the round
    winner psum-materialize from their owning shards). Selections are
    identical to every other exact plan.
    ``"greedi"`` — GreeDi partition-then-merge (Mirzasoleiman et al.): each
    shard greedily solves its own V-partition, the p·k partial solutions
    all-gather, and a merge greedy over them yields the answer. O(n/p·d)
    per device and the cheapest collective footprint, but selections carry
    the GreeDi constant-factor guarantee instead of matching centralized
    greedy; requires the full candidate pool (no ``candidates`` subset) and
    ≥ k rows per partition.
    """
    n = f.n
    cand_idx = np.arange(n) if candidates is None \
        else validate_candidates(candidates, n)
    if k > len(cand_idx):
        raise ValueError(
            f"cannot select k={k} exemplars from {len(cand_idx)} distinct "
            f"candidates")
    if mode == "host":
        mode = "mincache"
    if mode in ("device", "device_sharded", "device_sharded_pool", "greedi"):
        # ONE candidate row: the engine closes over it for all k rounds
        cand_rounds = cand_idx[None, :]
        counter = {"device": "greedy", "device_sharded": "greedy_sharded",
                   "device_sharded_pool": "greedy_sharded_pool",
                   "greedi": "greedy_greedi"}[mode]
        return run_selection(
            f, kind="dense", k=k, cand_rounds=cand_rounds,
            plan=mode, counter_key=counter, block_m=block_m, mesh=mesh,
            data_axes=data_axes)
    selected: list[int] = []
    traj: list[float] = []
    evals = 0
    if mode == "mincache":
        cache = f.init_cache()
        for _ in range(k):
            gains = np.array(f.gains_from_cache(cache, cand_idx))
            masked = np.isin(cand_idx, selected)
            evals += len(cand_idx) - int(masked.sum())
            gains[masked] = -np.inf
            j = int(cand_idx[int(np.argmax(gains))])
            selected.append(j)
            cache = f.fold_winner(cache, j)
            traj.append(f.value_from_cache(cache))
    elif mode == "multiset":
        _require_exemplar(f, "greedy mode='multiset'")
        for _ in range(k):
            base = f.V[np.asarray(selected, dtype=np.int64)] if selected else \
                jnp.zeros((0, f.dim), f.V.dtype)
            vals = np.array(f.greedy_step_values(base, f.V[cand_idx]))
            masked = np.isin(cand_idx, selected)
            evals += len(cand_idx) - int(masked.sum())
            vals[masked] = -np.inf
            j = int(cand_idx[int(np.argmax(vals))])
            selected.append(j)
            traj.append(float(vals.max()))
    else:
        raise ValueError(f"unknown greedy mode {mode!r}")
    return OptResult(selected, traj[-1] if traj else 0.0, traj, evals)


def lazy_greedy(
    f: SubmodularFunction,
    k: int,
    batch: int = 256,
    mode: str = "host",
    mesh=None,
    data_axes: Sequence[str] = ("data",),
) -> OptResult:
    """CELF: maintain stale upper bounds (submodularity ⇒ gains only shrink).

    ``mode="host"`` is the reference loop and the exact host-side mirror of
    the engine's rescore policy: stale bounds in an (n,) array, per round a
    loop re-scores the top-``batch`` stale candidates at once (the
    evaluation engine still sees multiset-sized problems — optimizer-
    awareness preserved) until the fresh-top invariant certifies the winner.
    Because host and device run the *same* policy, selections AND
    ``evaluations`` counts agree across modes on the jnp backend (up to
    exact float ties), and so do ``rescore_trips``: the re-score batches
    taken, summed over the rounds.

    ``mode="device"`` runs CELF entirely on device: the stale bounds ride
    the one-dispatch scan carry, each iteration re-scores the top-``batch``
    of them via ``jax.lax.top_k``. ``mode="device_sharded"`` additionally
    row-shards V and the cache over a ``mesh``; the bound state stays
    replicated. ``mode="device_sharded_pool"`` also row-shards the
    candidate payload — the ub0 seeding pass and every top-B re-score
    psum-materialize their candidate blocks from the owning shards, so
    resident per-device memory is O(n/p·d) plus the O(n)-scalar bound
    state.
    """
    if k > f.n:
        raise ValueError(f"cannot select k={k} exemplars from n={f.n}")
    if batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")
    if k == 0:
        return OptResult([], 0.0, [], 0)
    if mode in ("device", "device_sharded", "device_sharded_pool"):
        counter = {"device": "lazy_greedy",
                   "device_sharded": "lazy_greedy_sharded",
                   "device_sharded_pool": "lazy_greedy_sharded_pool"}[mode]
        return run_selection(
            f, kind="lazy", k=k, top_b=batch, plan=mode,
            counter_key=counter, mesh=mesh, data_axes=data_axes)
    if mode != "host":
        raise ValueError(f"unknown lazy_greedy mode {mode!r}")
    n = f.n
    B = max(1, min(batch, n))
    cache = f.init_cache()
    all_idx = np.arange(n)
    ub = np.asarray(f.gains_from_cache(cache, all_idx), np.float32).copy()
    evals = n
    trips = 0
    taken = np.zeros(n, bool)
    selected: list[int] = []
    traj: list[float] = []
    for _ in range(k):
        fresh = np.zeros(n, bool)
        while True:
            stale_vals = np.where(fresh | taken, -np.inf, ub)
            fresh_best = np.max(np.where(fresh & ~taken, ub, -np.inf))
            if fresh_best >= stale_vals.max():
                break  # fresh-top invariant: the fresh best is the argmax
            top_idx = np.argsort(-stale_vals, kind="stable")[:B]
            top_idx = top_idx[stale_vals[top_idx] > -np.inf]
            ub[top_idx] = np.asarray(f.gains_from_cache(cache, top_idx))
            fresh[top_idx] = True
            evals += len(top_idx)
            trips += 1
        j = int(np.argmax(np.where(fresh & ~taken, ub, -np.inf)))
        selected.append(j)
        taken[j] = True
        cache = f.fold_winner(cache, j)
        traj.append(f.value_from_cache(cache))
    return OptResult(selected, traj[-1] if traj else 0.0, traj, evals, trips)


def stochastic_greedy(
    f: SubmodularFunction, k: int, eps: float = 0.05, seed: int = 0,
    mode: str = "host", block_m: Optional[int] = None,
    mesh=None, data_axes: Sequence[str] = ("data",),
) -> OptResult:
    """Sample ⌈(n/k)·ln(1/ε)⌉ candidates per round; (1−1/e−ε) in expectation.

    All k rounds' candidate samples are drawn up front (so the host and
    device paths consume identical randomness); already-selected candidates
    are masked at scoring time. Each round draws k extra candidates so that
    after masking at most k selected ones, at least the required m fresh
    candidates remain — no round can degenerate to an all-masked argmax.
    ``evaluations`` counts the candidates that actually entered each round's
    argmax (identically in every mode), which keeps the numbers comparable
    with the pool-sampling formulation despite the overdraw.
    """
    n = f.n
    if k > n:
        raise ValueError(f"cannot select k={k} exemplars from n={n}")
    if k == 0:
        return OptResult([], 0.0, [], 0)
    rng = np.random.default_rng(seed)
    m = min(n, int(math.ceil(n / k * math.log(1.0 / eps))))
    m_draw = min(n, m + k)
    samples = np.stack(
        [rng.choice(n, size=m_draw, replace=False) for _ in range(k)])
    if mode in ("device", "device_sharded", "device_sharded_pool"):
        counter = {"device": "stochastic_greedy",
                   "device_sharded": "stochastic_greedy_sharded",
                   "device_sharded_pool":
                       "stochastic_greedy_sharded_pool"}[mode]
        return run_selection(
            f, kind="stochastic", k=k, cand_rounds=samples,
            plan=mode, counter_key=counter, block_m=block_m, mesh=mesh,
            data_axes=data_axes)
    if mode != "host":
        raise ValueError(f"unknown stochastic_greedy mode {mode!r}")
    cache = f.init_cache()
    selected: list[int] = []
    traj: list[float] = []
    evals = 0
    for t in range(k):
        cand = samples[t]
        gains = np.array(f.gains_from_cache(cache, cand))
        masked = np.isin(cand, selected)
        evals += len(cand) - int(masked.sum())
        gains[masked] = -np.inf
        j = int(cand[int(np.argmax(gains))])
        selected.append(j)
        cache = f.fold_winner(cache, j)
        traj.append(f.value_from_cache(cache))
    return OptResult(selected, traj[-1], traj, evals)


# ---------------------------------------------------------------------------
# Streaming sieves — built on the streaming sieve engine
# (:mod:`repro.core.streaming`): a fixed-capacity table of threshold sieves
# keyed by integer exponent, offered every arriving element. Like the greedy
# family, each algorithm composes one accept-rule *variant* with an execution
# plan: ``mode="host"`` steps the table one jitted dispatch per element (the
# exact array-semantics mirror), ``mode="device"`` consumes each stream block
# of B elements with ONE jitted ``lax.scan`` — singleton gain, grid rebuild,
# accept rule, cache min-update, and member bookkeeping all in the scan body.
# ---------------------------------------------------------------------------


def _stream_eval_count(n_elements: int, n_sieves: int) -> int:
    """Streaming ``evaluations`` unit, identical across the sieve family:
    each arriving element is scored against every live sieve in one engine
    call (min. 1 — the singleton gain is always computed)."""
    return n_elements * max(n_sieves, 1)


def _stream(f: SubmodularFunction, order: Optional[Sequence[int]], seed: int) -> Iterable[int]:
    idx = np.arange(f.n)
    if order is None:
        np.random.default_rng(seed).shuffle(idx)
        return idx
    return np.asarray(order)


def _stream_blocks(f: SubmodularFunction, order: Optional[Sequence[int]],
                   seed: int, block: int):
    """Yield (indices, distance rows, singleton gains) per stream block.

    One engine dispatch per block computes the (B, n) distances of the next B
    stream elements against the ground set — the batched replacement for the
    per-element ``point_distances`` round-trip. Exemplar-only: the singleton
    gains read d_e0 directly (callers guard via ``_require_exemplar``).
    """
    idx = np.asarray(_stream(f, order, seed))
    d_e0 = np.asarray(f.d_e0, np.float32)
    for s in range(0, len(idx), block):
        ib = idx[s:s + block]
        dmat = np.asarray(f.point_distances_block(f.V[ib]), np.float32)
        singles = np.maximum(d_e0[None, :] - dmat, 0.0).mean(axis=1)
        yield ib, dmat, singles


def _run_sieve(f: SubmodularFunction, k: int, eps: float, variant: str,
               order, seed: int, block_size: int, mode: str,
               s_max: Optional[int], mesh=None,
               data_axes: Sequence[str] = ("data",)) -> OptResult:
    """Drive a sieve-table engine over the stream under a host/device/
    device_sharded plan."""
    from repro.core.streaming import make_sieve_engine

    idx = np.asarray(_stream(f, order, seed))
    eng = make_sieve_engine(f, k, eps, variant=variant, mode=mode,
                            s_max=s_max, block_size=block_size, mesh=mesh,
                            data_axes=data_axes)
    for s in range(0, len(idx), block_size):
        ib = idx[s:s + block_size]
        eng.offer(ib, f.V[ib])
    members, value = eng.best()
    return OptResult(members, value, [value], eng.evaluations())


def sieve_streaming(
    f: SubmodularFunction, k: int, eps: float = 0.1,
    order: Optional[Sequence[int]] = None, seed: int = 0,
    block_size: int = 64, mode: str = "host",
    s_max: Optional[int] = None, mesh=None,
    data_axes: Sequence[str] = ("data",),
) -> OptResult:
    """SieveStreaming [4]: thresholds (1+ε)^i ∈ [m, 2km], m = max singleton.

    ``mode="device"`` consumes each stream block in one jitted scan dispatch;
    ``mode="host"`` is the per-element array-semantics mirror. ``s_max``
    overrides the sieve-table capacity (see :mod:`repro.core.streaming`).
    ``mode="device_sharded"`` (or an explicit ``mesh``) column-shards the
    sieve cache table over the mesh — O(S_max·n/p) streaming state per
    device.
    """
    return _run_sieve(f, k, eps, "sieve", order, seed, block_size, mode,
                      s_max, mesh=mesh, data_axes=data_axes)


def sieve_streaming_pp(
    f: SubmodularFunction, k: int, eps: float = 0.1,
    order: Optional[Sequence[int]] = None, seed: int = 0,
    block_size: int = 64, mode: str = "host",
    s_max: Optional[int] = None, mesh=None,
    data_axes: Sequence[str] = ("data",),
) -> OptResult:
    """SieveStreaming++ [19]: prune sieves below LB = best current value.

    LB moves after every accept, so the grid window is re-derived per
    element — inside the scan body under ``mode="device"``.
    """
    return _run_sieve(f, k, eps, "pp", order, seed, block_size, mode, s_max,
                      mesh=mesh, data_axes=data_axes)


def three_sieves(
    f: SubmodularFunction, k: int, eps: float = 0.1, T: int = 50,
    order: Optional[Sequence[int]] = None, seed: int = 0,
    block_size: int = 64,
) -> OptResult:
    """ThreeSieves [18]: one sieve, threshold lowered after T rejections."""
    f = _require_exemplar(f, "three_sieves")
    cache = np.asarray(f.init_mincache(), np.float32)
    members: list[int] = []
    evals = 0
    m_seen = 0.0
    tau_idx: Optional[int] = None  # current exponent into the (1+eps) grid
    rejections = 0
    done = False
    for ib, dmat, singles in _stream_blocks(f, order, seed, block_size):
        for bi, idx in enumerate(ib):
            if singles[bi] > m_seen:
                m_seen = float(singles[bi])
                hi = k * m_seen
                tau_idx = math.floor(math.log(hi) / math.log1p(eps)) if hi > 0 else None
                rejections = 0
            if tau_idx is None or len(members) >= k:
                # no gain computed for a full/unarmed sieve — and none
                # counted: ``evaluations`` reflects work actually done
                # (the engine-boundary accounting rule)
                continue
            dvec = dmat[bi]
            gain = float(np.maximum(cache - dvec, 0.0).mean())
            evals += _stream_eval_count(1, 1)
            tau = (1 + eps) ** tau_idx
            f_cur = f.L0 - float(cache.mean())
            need = (tau - f_cur) / max(k - len(members), 1)
            if gain >= need:
                members.append(int(idx))
                cache = np.minimum(cache, dvec)
                rejections = 0
            else:
                rejections += 1
                if rejections >= T:
                    tau_idx -= 1
                    rejections = 0
                    if (1 + eps) ** tau_idx < m_seen / (2 * k):
                        done = True  # threshold exhausted
                        break
        if done:
            break
    value = f.L0 - float(cache.mean())
    return OptResult(members, value, [value], evals)


def salsa(
    f: SubmodularFunction, k: int, eps: float = 0.1,
    order: Optional[Sequence[int]] = None, seed: int = 0,
    block_size: int = 64, mode: str = "host",
    s_max: Optional[int] = None, mesh=None,
    data_axes: Sequence[str] = ("data",),
) -> OptResult:
    """Salsa [20], simplified: an ensemble of dense-threshold passes.

    The full Salsa interleaves several threshold policies tuned to an OPT
    guess. We run, per OPT guess on the (1+ε) grid, a *dense* policy that
    accepts element e into sieve S when Δ(e|S) ≥ r·OPT_guess/k with r
    following the original schedule (1/2 for the first ⌈k/2⌉ members,
    1/(2e) after — so k=1 still applies the early rate), and return the best
    sieve. Single pass, same memory as SieveStreaming. The grid is grow-only
    (old OPT guesses are never dropped); under capacity pressure the sieve
    table evicts the lowest exponent (see :mod:`repro.core.streaming`).
    """
    return _run_sieve(f, k, eps, "salsa", order, seed, block_size, mode,
                      s_max, mesh=mesh, data_axes=data_axes)


OPTIMIZERS = {
    "greedy": greedy,
    "lazy_greedy": lazy_greedy,
    "stochastic_greedy": stochastic_greedy,
    "sieve_streaming": sieve_streaming,
    "sieve_streaming_pp": sieve_streaming_pp,
    "three_sieves": three_sieves,
    "salsa": salsa,
}
