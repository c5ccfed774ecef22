"""The multiset evaluation engine — the paper's core contribution, TPU-native.

Given a ground set ``V`` (n, d) and a packed multiset ``S_multi`` (l, k, d),
computes ``L(S_j ∪ {e0})`` for all j at once by (conceptually) building the
work matrix

    W[j, i] = |V|⁻¹ · min_{s ∈ S_j ∪ {e0}} d(v_i, s)            (paper eq. 7)

and reducing rows. Two modes:

* ``two_pass`` — paper-faithful: materialize ``W`` (in chunks), then reduce.
  Kept because Sieve-family optimizers can reuse ``W`` columns and because it
  is the baseline for §Perf.
* ``fused`` — beyond-paper: the row reduction is fused into the distance
  computation; ``W`` never exists in HBM. HBM traffic drops from O(l·n) to
  O(l) on the output side.

Three backends:

* ``jnp``   — pure jnp (XLA); the oracle and the CPU baseline.
* ``naive`` — paper's Algorithm 2, a per-set loop. The single-thread CPU
  baseline for the speedup benchmarks.
* ``pallas`` / ``pallas_interpret`` — the Pallas TPU kernel (MXU Gram tile +
  fused min/sum epilogue); ``_interpret`` validates on CPU.

Chunking (paper §IV-B-3): ``memory_budget_bytes`` bounds the per-chunk working
set; chunk count follows the paper's formula, and exhaustion raises with the
paper's remediation advice (lower precision / bigger device).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import distances as dist_mod
from repro.core import tracing
from repro.core.multiset import PackedMultiset
from repro.core.precision import (PrecisionPolicy, check_kernel_policy,
                                  resolve as resolve_policy)


@dataclasses.dataclass(frozen=True)
class EvalConfig:
    """Configuration for multiset evaluation."""

    distance: str = "sqeuclidean"
    policy: str | PrecisionPolicy = "fp32"
    mode: str = "fused"  # "fused" | "two_pass"
    backend: str = "jnp"  # "jnp" | "naive" | "pallas" | "pallas_interpret"
    kernel_variant: str = "flat"  # pallas layout: "flat" (k-major) | "loop"
    #: None → no chunking; an int → hard byte budget; "auto" → derive from
    #: the free-memory probe φ (:func:`free_memory_bytes`).
    memory_budget_bytes: Optional[int | str] = None
    n_block: Optional[int] = None  # stream over V in blocks of this many rows

    def __post_init__(self):
        # Fail at construction, not deep inside the first dispatch: an
        # unknown distance used to surface as resolve_pairwise's KeyError
        # mid-trace, long after the config was built.
        if self.distance not in dist_mod.PAIRWISE:
            raise ValueError(
                f"unknown distance {self.distance!r}; registered: "
                f"{sorted(dist_mod.PAIRWISE)}")
        if self.mode not in ("fused", "two_pass"):
            raise ValueError(
                f"mode must be 'fused' or 'two_pass', got {self.mode!r}")
        if self.backend not in ("jnp", "naive", "pallas", "pallas_interpret"):
            raise ValueError(
                f"unknown backend {self.backend!r}; expected one of "
                f"'jnp', 'naive', 'pallas', 'pallas_interpret'")
        if self.kernel_variant not in ("flat", "loop"):
            raise ValueError(
                f"kernel_variant must be 'flat' or 'loop', "
                f"got {self.kernel_variant!r}")
        policy = resolve_policy(self.policy)  # raises on unknown names
        if self.backend == "pallas":
            check_kernel_policy(policy)
        if isinstance(self.memory_budget_bytes, str) \
                and self.memory_budget_bytes != "auto":
            raise ValueError(
                f"memory_budget_bytes must be an int, None, or 'auto'; "
                f"got {self.memory_budget_bytes!r}")

    def resolved_policy(self) -> PrecisionPolicy:
        return resolve_policy(self.policy)


#: Fraction of probed free memory an "auto" budget hands to the chunk planner
#: (headroom for XLA temporaries and the output buffers).
AUTO_BUDGET_FRACTION = 0.8

#: Resolved "auto" budget, probed ONCE per process and then frozen: chunk
#: boundaries feed traced shapes, so a budget floating with live allocator
#: state would change chunk lengths call-to-call and retrace every call.
_AUTO_BUDGET_BYTES: "Optional[int] | bool" = False  # False = not yet probed


def free_memory_bytes(device=None) -> Optional[int]:
    """The paper's free-memory probe φ (§IV-B-3): free bytes on ``device``.

    Uses the runtime's allocator statistics (``Device.memory_stats``), which
    accelerator backends expose and CPU does not. Returns None when the
    backend has no stats — callers fall back to their static heuristics.

    This probe is shared by the engine's gain-tile autotuner
    (``engine._device_block_m``), whose cap likewise freezes at first use.
    Its two sizing factors compose multiplicatively on top of the probed
    cap: the batched-sharded plans score (B·n_loc)-row slabs per device, so
    the tile is sized from ``n_loc`` rows × ``n_batch=B`` with the cap
    divided ONCE by ``mesh_tiles_per_memory`` (forced host devices share
    this one probed allocator; a real accelerator mesh owns one memory per
    device and divides by 1).
    """
    try:
        dev = device if device is not None else jax.local_devices()[0]
        stats = dev.memory_stats()
    except Exception:  # backend without stats support
        return None
    if not stats or "bytes_limit" not in stats:
        return None
    return max(int(stats["bytes_limit"]) - int(stats.get("bytes_in_use", 0)), 0)


def resolve_memory_budget(budget: Optional[int | str]) -> Optional[int]:
    """Resolve ``memory_budget_bytes``: pass ints/None through, probe "auto".

    The "auto" probe runs once per process and is then frozen (chunk counts
    feed traced shapes — a drifting budget would retrace every call). A
    probe that reports 0 free bytes resolves to a 0 budget (so the chunk
    planner raises :class:`ChunkingError` with the paper's remediation
    advice) rather than silently disabling chunking — only a *probeless*
    backend degrades to unchunked.
    """
    global _AUTO_BUDGET_BYTES
    if budget == "auto":
        if _AUTO_BUDGET_BYTES is False:
            free = free_memory_bytes()
            _AUTO_BUDGET_BYTES = (
                int(free * AUTO_BUDGET_FRACTION) if free is not None else None)
        return _AUTO_BUDGET_BYTES
    if isinstance(budget, str):
        raise ValueError(
            f"memory_budget_bytes must be an int, None, or 'auto'; "
            f"got {budget!r}")
    return budget


class ChunkingError(MemoryError):
    """Raised when not even a single evaluation set fits the memory budget.

    The paper (§IV-B-3): "suggests either the use of lower floating-point
    precision … or better suited hardware with larger memory."
    """


def bytes_per_set(n: int, k_max: int, d: int, policy: PrecisionPolicy, mode: str) -> int:
    """μ_s — device bytes needed per evaluation set (paper §IV-B-3).

    Counts the packed set payload, the Gram/distance block against all of V,
    and (two_pass only) the materialized W row. V itself is excluded — the
    paper pre-loads it at init and accounts it in the free-memory probe φ.
    """
    cs = policy.itemsize
    acc = jnp.dtype(policy.accum_dtype).itemsize  # Gram/W block width
    mu = k_max * d * cs + n * k_max * acc
    if mode == "two_pass":
        mu += n * acc
    return mu


def plan_chunks(
    l: int, n: int, k_max: int, d: int, policy: PrecisionPolicy, mode: str,
    budget_bytes: Optional[int | str],
) -> list[tuple[int, int]]:
    """Split l sets into chunks fitting the budget. Returns [start, stop) pairs.

    ``budget_bytes`` may be "auto", resolved via the free-memory probe φ.
    """
    budget_bytes = resolve_memory_budget(budget_bytes)
    if budget_bytes is None:
        return [(0, l)]
    mu = bytes_per_set(n, k_max, d, policy, mode)
    per_chunk = budget_bytes // mu  # n_chunk-size = ⌊φ μ_s⁻¹⌋
    if per_chunk == 0:
        raise ChunkingError(
            f"memory budget {budget_bytes}B cannot fit a single evaluation set "
            f"(μ_s={mu}B). Use a lower floating-point precision or a larger "
            f"memory budget (paper §IV-B-3)."
        )
    n_chunks = math.ceil(l / per_chunk)  # ⌈l · n_chunk-size⁻¹⌉
    return [(i * per_chunk, min((i + 1) * per_chunk, l)) for i in range(n_chunks)]


# ---------------------------------------------------------------------------
# jnp backend
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("distance", "policy_name"))
def _min_dists_block(
    V: jax.Array,
    data: jax.Array,
    lengths: jax.Array,
    d_e0: jax.Array,
    distance: str,
    policy_name: str,
) -> jax.Array:
    """(n, l) matrix of min_{s∈S_j∪{e0}} d(v_i, s) for one chunk of sets."""
    policy = resolve_policy(policy_name)
    l, k, d = data.shape
    pair = dist_mod.resolve_pairwise(distance)
    D = pair(V, data.reshape(l * k, d), policy)  # (n, l·k)
    D = D.reshape(V.shape[0], l, k)
    mask = jnp.arange(k)[None, :] < lengths[:, None]  # (l, k)
    big = jnp.asarray(jnp.finfo(D.dtype).max, D.dtype)
    D = jnp.where(mask[None, :, :], D, big)
    dmin = jnp.min(D, axis=-1)  # (n, l)
    return jnp.minimum(dmin, d_e0[:, None].astype(D.dtype))


@partial(jax.jit, static_argnames=("distance", "policy_name"))
def _fused_block(V, data, lengths, d_e0, distance, policy_name) -> jax.Array:
    """Fused: per-set L values for one chunk — W rows never materialized."""
    dmin = _min_dists_block(V, data, lengths, d_e0, distance, policy_name)
    n = V.shape[0]
    return jnp.sum(dmin, axis=0) / n  # (l,)


def _eval_jnp(
    V: jax.Array, packed: PackedMultiset, d_e0: jax.Array, cfg: EvalConfig
) -> jax.Array:
    policy = cfg.resolved_policy()
    chunks = plan_chunks(
        packed.num_sets, V.shape[0], packed.k_max, packed.dim, policy,
        cfg.mode, cfg.memory_budget_bytes,
    )
    n = V.shape[0]
    outs = []
    for start, stop in chunks:
        sub = packed.slice_sets(start, stop)
        if cfg.n_block is not None:
            outs.append(
                _eval_jnp_nblocked(V, sub, d_e0, cfg, policy)
            )
        elif cfg.mode == "two_pass":
            W = _min_dists_block(
                V, sub.data, sub.lengths, d_e0, cfg.distance, policy.name
            )  # (n, l_c) — the paper's W (transposed), materialized
            outs.append(jnp.sum(W, axis=0) / n)
        else:
            outs.append(
                _fused_block(V, sub.data, sub.lengths, d_e0, cfg.distance, policy.name)
            )
    return jnp.concatenate(outs) if len(outs) > 1 else outs[0]


def _eval_jnp_nblocked(V, packed, d_e0, cfg, policy) -> jax.Array:
    """Stream over V in blocks (bounds the n×l·k Gram block)."""
    n = V.shape[0]
    nb = cfg.n_block
    n_pad = math.ceil(n / nb) * nb
    Vp = jnp.pad(V, ((0, n_pad - n), (0, 0)))
    # padded rows contribute d_e0 = +inf-min guard: give them d_e0 = 0 and
    # subtract nothing — instead mask by weighting rows.
    d_e0p = jnp.pad(d_e0, (0, n_pad - n))
    valid = (jnp.arange(n_pad) < n).astype(jnp.float32)

    def body(carry, xs):
        vblk, eblk, wblk = xs
        dmin = _min_dists_block(
            vblk, packed.data, packed.lengths, eblk, cfg.distance, policy.name
        )
        return carry + jnp.sum(dmin * wblk[:, None], axis=0), None

    init = jnp.zeros((packed.num_sets,), jnp.float32)
    xs = (
        Vp.reshape(-1, nb, V.shape[1]),
        d_e0p.reshape(-1, nb),
        valid.reshape(-1, nb),
    )
    total, _ = jax.lax.scan(body, init, xs)
    return total / n


# ---------------------------------------------------------------------------
# naive backend — paper Algorithm 2 (single-set CPU loop), the ST baseline
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("distance", "policy_name"))
def _naive_single_set(V, sdata, slen, d_e0, distance, policy_name):
    pair = dist_mod.resolve_pairwise(distance)
    policy = resolve_policy(policy_name)

    def point_loss(v, de):
        # inner loop of Algorithm 2: t = min(t, d(s, v)) over s ∈ S
        dd = pair(v[None, :], sdata, policy)[0]
        dd = jnp.where(jnp.arange(sdata.shape[0]) < slen, dd, jnp.finfo(dd.dtype).max)
        return jnp.minimum(jnp.min(dd), de.astype(dd.dtype))

    sums = jax.lax.map(lambda args: point_loss(*args), (V, d_e0))
    return jnp.sum(sums) / V.shape[0]


def _eval_naive(V, packed, d_e0, cfg) -> jax.Array:
    policy = cfg.resolved_policy()
    vals = []
    for j in range(packed.num_sets):  # the un-parallelized outer loop
        vals.append(
            _naive_single_set(V, packed.data[j], packed.lengths[j], d_e0,
                              cfg.distance, policy.name)
        )
    return jnp.stack(vals)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


@partial(jax.jit, static_argnames=("distance", "policy_name"))
def _e0_column(V, e0, distance, policy_name):
    if e0 is None:
        e0 = jnp.zeros((V.shape[-1],), V.dtype)
    pair = dist_mod.resolve_pairwise(distance)
    d_e0 = pair(V, e0[None, :], resolve_policy(policy_name))[:, 0]
    return d_e0, jnp.mean(d_e0.astype(jnp.float32))


def e0_column_and_loss(
    V: jax.Array,
    e0: Optional[jax.Array],
    distance: str,
    policy: "str | PrecisionPolicy" = "fp32",
) -> tuple[jax.Array, jax.Array]:
    """d(v_i, e0) for all i and its float32 mean L({e0}), one dispatch.

    e0 defaults to the all-zero auxiliary vector. ``policy`` is the
    caller's precision policy: half-precision sweeps must compute the e0
    column with the same policy as the rest of the work matrix. The
    column is one compiled program per (shape, distance, policy), so a
    warm call is a single dispatch, not one per primitive.
    """
    return _e0_column(V, e0, distance, resolve_policy(policy).name)


def e0_distances(
    V: jax.Array,
    e0: Optional[jax.Array],
    distance: str,
    policy: "str | PrecisionPolicy" = "fp32",
) -> jax.Array:
    """d(v_i, e0) for all i: :func:`e0_column_and_loss` without the mean."""
    return _e0_column(V, e0, distance, resolve_policy(policy).name)[0]


@partial(jax.profiler.annotate_function, name=tracing.EVALUATE_MULTISET)
def evaluate_multiset(
    V: jax.Array,
    packed: PackedMultiset,
    cfg: EvalConfig = EvalConfig(),
    d_e0: Optional[jax.Array] = None,
    e0: Optional[jax.Array] = None,
) -> jax.Array:
    """L(S_j ∪ {e0}) for every set in the multiset. Returns (l,) float32.

    The whole call, and the e0 column it computes when ``d_e0`` is not
    given, are host spans (:mod:`repro.core.tracing`)."""
    if d_e0 is None:
        with jax.profiler.TraceAnnotation(tracing.E0_DISTANCES):
            d_e0 = e0_distances(V, e0, cfg.distance, cfg.policy)
    if cfg.backend == "jnp":
        out = _eval_jnp(V, packed, d_e0, cfg)
    elif cfg.backend == "naive":
        out = _eval_naive(V, packed, d_e0, cfg)
    elif cfg.backend in ("pallas", "pallas_interpret"):
        from repro.kernels import ops as kops  # lazy: avoid circular import

        if cfg.distance not in dist_mod.MXU_ELIGIBLE:
            raise ValueError(
                f"pallas backend supports {sorted(dist_mod.MXU_ELIGIBLE)}, "
                f"got {cfg.distance!r}"
            )
        out = kops.exemplar_eval(
            V,
            packed.data,
            packed.lengths,
            d_e0,
            policy=cfg.resolved_policy(),
            mode=cfg.mode,
            variant=cfg.kernel_variant if cfg.mode == "fused" else "loop",
            interpret=(cfg.backend == "pallas_interpret"),
            memory_budget_bytes=cfg.memory_budget_bytes,
            rbf_gamma=dist_mod.RBF_GAMMA if cfg.distance == "rbf" else None,
        )
    else:
        raise ValueError(f"unknown backend {cfg.backend!r}")
    return out.astype(jnp.float32)


def work_matrix(
    V: jax.Array,
    packed: PackedMultiset,
    cfg: EvalConfig = EvalConfig(mode="two_pass"),
    d_e0: Optional[jax.Array] = None,
    e0: Optional[jax.Array] = None,
) -> jax.Array:
    """The paper's W, shape (l, n): W[j,i] = min-dist / n. Materialized.

    Respects ``cfg.memory_budget_bytes`` via the same chunk planner as
    :func:`evaluate_multiset` — without it a large multiset OOMs here while
    the fused path with an identical config would have chunked.
    """
    if d_e0 is None:
        d_e0 = e0_distances(V, e0, cfg.distance, cfg.policy)
    policy = cfg.resolved_policy()
    chunks = plan_chunks(
        packed.num_sets, V.shape[0], packed.k_max, packed.dim, policy,
        "two_pass", cfg.memory_budget_bytes,
    )
    outs = []
    for start, stop in chunks:
        sub = packed.slice_sets(start, stop)
        outs.append(_min_dists_block(
            V, sub.data, sub.lengths, d_e0, cfg.distance, policy.name
        ))  # (n, l_c)
    dmin = jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    return dmin.T / V.shape[0]
