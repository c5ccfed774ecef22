"""Audit-case grid: every contract × its documented signature space.

The registry turns each :mod:`repro.analysis.contracts` declaration into
concrete traceable cases over the grid the engine documents — execution
plans × round strategies × the device-eligible function zoo × scoring
backends × precision policies × batch sizes — and computes the *exact*
expected numbers (scan counts, static collective counts, donation arity,
precision thresholds) the contract's prose implies for that case.

The arithmetic lives here, next to its derivation comments, so a reviewer
can trace every expected count back to the code path that issues it; the
auditor (:mod:`repro.analysis.report`) only compares.

Cases trace with abstract values (ShapeDtypeStructs): nothing here
allocates a ground set or dispatches a kernel. The separate
:func:`runtime_checks` list executes a handful of tiny concrete problems
for the claims tracing cannot see — jit-cache stability (zero retraces on
a same-signature second call) and live donation (``seed.is_deleted()``
matching the executable's aliasing table).
"""
from __future__ import annotations

import dataclasses
from collections import Counter
from typing import Callable, Optional

import jax
import numpy as np
from jax.sharding import AxisType

from repro.core import functions as fx
from repro.core.functions import FnSpec

# --- grid shapes -----------------------------------------------------------
# Chosen so every structural number is distinctive: the rounds-scan length
# (k) differs from the blocked-scoring map length (m/block) and the stream
# block size, so a scan found with length k IS the rounds scan.
N = 48          #: ground-set rows
D = 8           #: feature dim
K = 5           #: selection rounds (the driving-scan length)
M_STOCH = 16    #: stochastic per-round sample width
BLOCK_M = 16    #: jnp streaming block (dense: 48/16 = 3 map steps ≠ K)
TOP_B = 8       #: CELF re-score width
B_BLOCK = 6     #: stream block (the streaming driving-scan length)
SIEVE_K = 4
SIEVE_EPS = 0.2

#: The device-plan function zoo (DEVICE_PLAN_ELIGIBLE), as static FnSpecs.
SPECS = {
    "exemplar": FnSpec(),
    "facility_location": FnSpec("facility_location"),
    "graph_cut": FnSpec("graph_cut", lam=0.5),
    "saturated_coverage": FnSpec("saturated_coverage", sat=0.25),
}

BACKENDS = ("jnp", "pallas_interpret")
POLICIES = ("fp32", "bf16")
KINDS = ("dense", "stochastic", "lazy")


@dataclasses.dataclass
class Expect:
    """Exact expected numbers for one traced case."""

    rounds: int                       #: driving-scan trip count
    top_scans: int                    #: top-level scan eqns
    driving: int                      #: of those, trip count == rounds
    whiles: int                       #: while eqns anywhere
    collectives: Counter              #: exact static counts by primitive
    body_psums: Optional[int]         #: psums inside the driving scan body
    max_collective_bytes: Optional[int]  #: bound on any collective operand
    donated: int                      #: inputs that must alias an output
    min_widen_elems: Optional[int]    #: precision check threshold (None=skip)
    require_half_dot: bool = False
    memory_bound: Optional[int] = None  #: compiled temp-bytes bound


@dataclasses.dataclass
class AuditCase:
    contract: str
    label: str
    build: Callable[[], tuple]        #: () -> (jitted_fn, args, kwargs)
    expect: Expect


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def _eff_backend(spec: FnSpec, backend: str) -> str:
    # mirror of run_selection's normalization: a function with no kernel
    # template (saturated coverage) scores through jnp on any backend
    return backend if fx.kernel_template(spec) is not None else "jnp"


def _m_eff(kind: str) -> int:
    return {"dense": N, "stochastic": M_STOCH, "lazy": 0}[kind]


def _m_scored_max(kind: str) -> int:
    # widest single scored batch: dense scores all n every round, stochastic
    # its m-row sample, lazy seeds bounds over all n then re-scores top_b
    return {"dense": N, "stochastic": M_STOCH, "lazy": N}[kind]


def _cand_shape(kind: str, batch: Optional[int] = None):
    rows = {"dense": (1, N), "stochastic": (K, M_STOCH), "lazy": (1, 0)}[kind]
    return rows if batch is None else (batch,) + rows


def _precision_fields(policy: str, batch: int = 1):
    if policy != "bf16":
        return None, False
    # allowed widens are the O(n) accumulators: the winner's (B, n) distance
    # column folding into the f32 cache, gains payloads, trajectory scalars.
    # A distance *tile* is (B·n, block) with block ≥ 8 — safely above this.
    return 2 * batch * N + 16, True


# --- single-device selection scans -----------------------------------------


def _device_case(kind, fname, backend, policy, batch):
    from repro.core import engine as eng

    spec = SPECS[fname]
    be = _eff_backend(spec, backend)
    bm = min(BLOCK_M, max(_m_eff(kind), 1))

    def build():
        args = (_sds((batch, N, D), np.float32),
                _sds((batch, N), np.float32),
                _sds((batch, N), np.float32),
                _sds(_cand_shape(kind, batch), np.int32),
                _sds((batch, D), np.float32),
                _sds((batch,), np.int32))
        kwargs = dict(fn=spec, kind=kind, k=K, top_b=TOP_B,
                      distance="sqeuclidean", policy_name=policy,
                      block_m=bm, backend=be, rbf_gamma=None,
                      counter_key=f"audit_device_{batch}")
        return eng._select_scan, args, kwargs

    # lazy's ub0 bound seeding scores all n candidates OUTSIDE the rounds
    # scan; on the jnp backend that is _score_blocked's lax.map — one extra
    # top-level scan. Kernel backends score it in one pallas_call.
    extra_scans = 1 if (kind == "lazy" and be == "jnp") else 0
    widen, half_dot = _precision_fields(policy, batch)
    return AuditCase(
        contract="engine.select_scan",
        label=f"device[B={batch}].{kind}.{fname}.{be}.{policy}",
        build=build,
        expect=Expect(
            rounds=K, top_scans=1 + extra_scans, driving=1,
            whiles=1 if kind == "lazy" else 0,
            collectives=Counter(),          # single device: collective-free
            body_psums=None, max_collective_bytes=None,
            donated=1,                      # the cache seed
            min_widen_elems=widen, require_half_dot=half_dot))


# --- mesh-sharded selection scans ------------------------------------------


def audit_mesh():
    return jax.make_mesh((jax.device_count(),), ("data",),
                         axis_types=(AxisType.Auto,))


def _sharded_case(kind, fname, backend, policy, pool_plan, batch):
    from repro.core import distributed as dist

    spec = SPECS[fname]
    be = _eff_backend(spec, backend)
    gc = 1 if fname == "graph_cut" else 0
    nb = batch
    plan = "device_sharded" if pool_plan == "replicated" \
        else "device_sharded_pool"

    def build():
        mesh = audit_mesh()
        run = dist.make_selection_scan(
            mesh, ("data",), fn=spec, kind=kind, k=K, top_b=TOP_B,
            n_total=N, block_m=BLOCK_M, distance="sqeuclidean",
            policy_name=policy, counter_key=f"audit_b{batch}_{plan}",
            backend=be, rbf_gamma=None, pool_plan=pool_plan)
        args = (_sds((nb, N, D), np.float32), _sds((nb, N, D), np.float32),
                _sds((nb, N), np.float32), _sds((nb, N), np.float32),
                _sds(_cand_shape(kind, nb), np.int32),
                _sds((nb, D), np.float32), _sds((nb,), np.int32))
        return run, args, {}

    # Static psum census, from make_selection_scan's body:
    #   every case: v0 seeding (1) + the final trajectory value (1)
    #   dense/stoch round body: ONE gains+stat psum; graph cut's fold_aux
    #     owner-gather adds one (executed unconditionally), and the final
    #     fold adds it once more
    #   lazy: + the ub0 seeding batch (1); the round body's psum sits in the
    #     while loop (one per re-score iteration at run time, one statically)
    # The sharded pool adds the take collectives: one blocked-take psum
    # inside the streamed scoring map, and the winner-column take per round.
    # Every per-request collective batches its OPERAND to (B, …) — the
    # vmapped fold_aux gather, the stacked gains+stat payload, the
    # (B, bm, d) take slabs — so the batch axis multiplies collective
    # BYTES, never collective COUNT: the count is the same at every B, and
    # a per-tenant psum migration would show up here as count × B.
    if pool_plan == "replicated":
        total = (4 if kind == "lazy" else 3) + 2 * gc
        body = 1 + gc
        max_bytes = nb * (_m_scored_max(kind) + 1) * 4
        extra_scans = 1 if (kind == "lazy" and be == "jnp") else 0
    else:
        total = (7 if kind == "lazy" else 5) + 2 * gc
        body = 3 + gc
        bm = min(BLOCK_M, max(8, N))    # run_sharded_selection(_batch)'s cap
        max_bytes = max(nb * (_m_scored_max(kind) + 1) * 4, nb * bm * D * 4)
        # the lazy seeding pass streams blocked takes through ONE top-level
        # lax.map (jnp sub-blocking nests inside it)
        extra_scans = 1 if kind == "lazy" else 0
    widen, half_dot = _precision_fields(policy, nb)
    return AuditCase(
        contract=f"distributed.selection_scan[{pool_plan}]",
        label=f"{plan}[B={batch}].{kind}.{fname}.{be}.{policy}",
        build=build,
        expect=Expect(
            rounds=K, top_scans=1 + extra_scans, driving=1,
            whiles=1 if kind == "lazy" else 0,
            collectives=Counter({"psum": total}),
            body_psums=body, max_collective_bytes=max_bytes,
            donated=1,                  # the (B, n/p) cache seed
            min_widen_elems=widen, require_half_dot=half_dot))


def _greedi_case(fname, backend, policy):
    from repro.core import distributed as dist

    spec = SPECS[fname]
    be = _eff_backend(spec, backend)
    gc = 1 if fname == "graph_cut" else 0

    def build():
        mesh = audit_mesh()
        run = dist.make_greedi_scan(
            mesh, ("data",), fn=spec, k=K, n_total=N, block_m=BLOCK_M,
            distance="sqeuclidean", policy_name=policy,
            counter_key="audit_greedi", backend=be, rbf_gamma=None)
        args = (_sds((N, D), np.float32), _sds((N,), np.float32),
                _sds((N,), np.float32), _sds((D,), np.float32))
        return run, args, {}

    p = jax.device_count()
    # Two driving (length-k) scans — the phase-1 partition greedy and the
    # phase-2 merge greedy — plus the p-solution global-evaluation map.
    # Psums: the 3 all-gathers (solution rows, indices, n_scored) + v0g +
    # the eval-map body's trajectory value + the merge body's gains+stat +
    # the final trajectory value = 7; graph cut's fold_aux gather fires in
    # the eval body, the merge body, and the final fold (+3).
    widen, half_dot = _precision_fields(policy)
    return AuditCase(
        contract="distributed.greedi_scan",
        label=f"greedi.dense.{fname}.{be}.{policy}",
        build=build,
        expect=Expect(
            rounds=K, top_scans=3, driving=2, whiles=0,
            collectives=Counter({"psum": 7 + 3 * gc}),
            body_psums=None,        # phase-1 partition greedy: local-only
            max_collective_bytes=max(p * K * D * 4, (p * K + 1) * 4),
            donated=0, min_widen_elems=widen, require_half_dot=half_dot))


# --- streaming sieve scans -------------------------------------------------


def _sieve_state_structs(spec, n):
    from repro.core import streaming as st

    S, k = spec.s_max, spec.k
    return st.SieveState(
        caches=_sds((S, n), np.float32), slot_exp=_sds((S,), np.int32),
        active=_sds((S,), np.bool_), sizes=_sds((S,), np.int32),
        members=_sds((S, k), np.int32), m_seen=_sds((), np.float32),
        lb=_sds((), np.float32), evals=_sds((), np.int32))


def _sieve_psum_body(variant: str, use_kernel: bool) -> int:
    # _element_step's ground-set reductions, per element (statically once in
    # the scan body): jnp path = singleton gain + per-sieve gains (+ the
    # values_of reduce feeding the sieve/pp accept threshold; salsa's
    # rate-schedule threshold needs no values) (+ pp's post-accept LB
    # update). Kernel path scores seed+table rows in ONE fused psum'd pass.
    if use_kernel:
        return {"sieve": 2, "pp": 3, "salsa": 1}[variant]
    return {"sieve": 3, "pp": 4, "salsa": 2}[variant]


def _stream_case(variant, fname, backend, sharded):
    from repro.core import streaming as st

    fspec = SPECS[fname]
    spec = st.make_spec(SIEVE_K, SIEVE_EPS, variant, backend=backend,
                        fn=fspec)
    use_kernel = spec.backend != "jnp"   # make_spec normalizes no-template

    def build():
        state = _sieve_state_structs(spec, N)
        if not sharded:
            args = (state, _sds((N,), np.float32), _sds((N,), np.float32),
                    _sds((B_BLOCK,), np.int32),
                    _sds((B_BLOCK, N), np.float32),
                    _sds((B_BLOCK,), np.bool_))
            return st._offer_block_scan, args, dict(
                spec=spec, counter_key="audit_sieve")
        mesh = audit_mesh()
        run = st.make_sharded_offer_scan(
            mesh, ("data",), spec=spec, n_total=N, distance="sqeuclidean",
            policy_name="fp32", counter_key="audit_sieve_sharded")
        args = (state, _sds((N, D), np.float32), _sds((N,), np.float32),
                _sds((N,), np.float32), _sds((B_BLOCK, D), np.float32),
                _sds((B_BLOCK,), np.int32), _sds((B_BLOCK,), np.bool_))
        return run, args, {}

    if sharded:
        body = _sieve_psum_body(variant, use_kernel)
        collectives = Counter({"psum": body + 1})   # + the v0 seeding psum
        max_bytes = (spec.s_max + 1) * 4            # seed row + table rows
    else:
        body, collectives, max_bytes = None, Counter(), None
    plan = "sharded" if sharded else "device"
    return AuditCase(
        contract="streaming.offer_scan" + ("[sharded]" if sharded else ""),
        label=f"sieve_{variant}.{plan}.{fname}.{spec.backend}",
        build=build,
        expect=Expect(
            rounds=B_BLOCK, top_scans=1, driving=1, whiles=0,
            collectives=collectives, body_psums=body,
            max_collective_bytes=max_bytes,
            donated=_sieve_donated(),   # every SieveState carry leaf
            min_widen_elems=None))


def _sieve_donated() -> int:
    """The donated streaming carry is the whole SieveState — one aliased
    input per leaf, so the expected arity tracks the NamedTuple."""
    from repro.core import streaming as st

    return len(st.SieveState._fields)


SIEVE_P = 3     #: stream partitions in the batched audit case


def _stream_batched_case(variant, fname, backend):
    from repro.core import streaming as st

    fspec = SPECS[fname]
    spec = st.make_spec(SIEVE_K, SIEVE_EPS, variant, backend=backend,
                        fn=fspec)

    def build():
        base = _sieve_state_structs(spec, N)
        states = type(base)(*[
            _sds((SIEVE_P,) + leaf.shape, leaf.dtype) for leaf in base])
        args = (states, _sds((N,), np.float32), _sds((N,), np.float32),
                _sds((B_BLOCK, SIEVE_P), np.int32),
                _sds((B_BLOCK, SIEVE_P, N), np.float32),
                _sds((B_BLOCK, SIEVE_P), np.bool_))
        return st._offer_block_scan_batched, args, dict(
            spec=spec, counter_key="audit_sieve_batched")

    return AuditCase(
        contract="streaming.offer_scan_batched",
        label=f"sieve_{variant}.batched[P={SIEVE_P}].{fname}.{spec.backend}",
        build=build,
        expect=Expect(
            rounds=B_BLOCK, top_scans=1, driving=1, whiles=0,
            collectives=Counter(), body_psums=None,
            max_collective_bytes=None,
            donated=_sieve_donated(),
            min_widen_elems=None))


# --- memory-bounded compile cases ------------------------------------------

#: Shapes for the analytic-byte check: big enough that the full (n, m)
#: distance matrix (4 MiB) is an order of magnitude above the blocked
#: working set, so the bound genuinely discriminates.
MEM_N, MEM_D, MEM_BM = 1024, 8, 64


def _memory_case(batch):
    from repro.core import engine as eng

    def build():
        args = (_sds((batch, MEM_N, MEM_D), np.float32),
                _sds((batch, MEM_N), np.float32),
                _sds((batch, MEM_N), np.float32),
                _sds((batch, 1, MEM_N), np.int32),
                _sds((batch, MEM_D), np.float32),
                _sds((batch,), np.int32))
        kwargs = dict(fn=FnSpec(), kind="dense", k=K, top_b=0,
                      distance="sqeuclidean", policy_name="fp32",
                      block_m=MEM_BM, backend="jnp", rbf_gamma=None,
                      counter_key=f"audit_mem_{batch}")
        return eng._select_scan, args, kwargs

    # Working set: the streamed (B·n, block_m) distance tile plus O(B·n)
    # carries — NEVER the full (B·n, m) matrix. Bound: 6 tiles of headroom
    # (scan double-buffering, gather scratch) + 1 MiB slack; a full-matrix
    # regression costs B·n·m·4 = 4B MiB and trips it immediately.
    tile = batch * MEM_N * MEM_BM * 4
    return AuditCase(
        contract="engine.select_scan",
        label=f"memory.device[B={batch}].dense.exemplar.jnp.fp32",
        build=build,
        expect=Expect(
            rounds=K, top_scans=1, driving=1, whiles=0,
            collectives=Counter(), body_psums=None,
            max_collective_bytes=None, donated=1, min_widen_elems=None,
            memory_bound=6 * tile + (1 << 20)))


# --- the full grid ---------------------------------------------------------


def build_cases(quick: bool = False) -> list[AuditCase]:
    """The audit grid. ``quick`` keeps one exemplar case per contract (for
    smoke runs); the full grid is what CI proves green.
    """
    cases: list[AuditCase] = []
    fnames = list(SPECS)
    for kind in KINDS:
        for fname in fnames:
            for backend in BACKENDS:
                for policy in POLICIES:
                    if _eff_backend(SPECS[fname], backend) != backend:
                        continue    # satcov normalizes to jnp: skip the dup
                    for batch in (1, 64):
                        cases.append(_device_case(kind, fname, backend,
                                                  policy, batch))
                    for pool_plan in ("replicated", "sharded"):
                        for batch in (1, 4):
                            cases.append(_sharded_case(
                                kind, fname, backend, policy, pool_plan,
                                batch))
    for fname in fnames:
        for backend in BACKENDS:
            for policy in POLICIES:
                if _eff_backend(SPECS[fname], backend) != backend:
                    continue
                cases.append(_greedi_case(fname, backend, policy))
    for variant in ("sieve", "pp", "salsa"):
        for fname in sorted(fx.SIEVE_ELIGIBLE):
            for backend in BACKENDS:
                fspec = SPECS[fname]
                if backend != "jnp" and fx.kernel_template(fspec) is None:
                    continue
                for sharded in (False, True):
                    cases.append(_stream_case(variant, fname, backend,
                                              sharded))
                cases.append(_stream_batched_case(variant, fname, backend))
    cases.append(_memory_case(1))
    cases.append(_memory_case(4))
    if quick:
        seen: dict[str, AuditCase] = {}
        for c in cases:
            seen.setdefault(c.contract, c)
        return list(seen.values())
    return cases


# --- runtime checks: retrace stability + live donation ---------------------


@dataclasses.dataclass
class RuntimeCheck:
    name: str
    run: Callable[[], tuple[bool, str]]


def _rt_retrace_device() -> tuple[bool, str]:
    import jax.numpy as jnp
    from repro.core import engine as eng
    from repro.core.evaluator import EvalConfig
    from repro.core.functions import ExemplarClustering

    key = "audit_rt_device"
    rng = np.random.default_rng(0)
    V = rng.standard_normal((32, 4)).astype(np.float32)
    for _ in range(2):   # fresh function instance = fresh same-shape arrays
        f = ExemplarClustering(jnp.asarray(V), EvalConfig())
        eng.run_selection(f, kind="dense", k=3,
                          cand_rounds=np.arange(32, dtype=np.int32)[None, :],
                          plan="device", counter_key=key)
    n = eng.DEVICE_TRACE_COUNTS[key]
    return n == 1, f"traces for two same-signature calls: {n} (want 1)"


def _rt_retrace_batched() -> tuple[bool, str]:
    import jax.numpy as jnp
    from repro.core import engine as eng
    from repro.core.evaluator import EvalConfig
    from repro.core.functions import ExemplarClustering

    key = "audit_rt_batched"
    rng = np.random.default_rng(1)
    V = rng.standard_normal((4, 32, 4)).astype(np.float32)
    for _ in range(2):
        fs = [ExemplarClustering(jnp.asarray(v), EvalConfig()) for v in V]
        eng.run_selection_batch(fs, kind="dense", k=3, counter_key=key)
    n = eng.DEVICE_TRACE_COUNTS[key]
    return n == 1, f"traces for two same-signature batches: {n} (want 1)"


def _rt_retrace_sharded() -> tuple[bool, str]:
    import jax.numpy as jnp
    from repro.core import engine as eng
    from repro.core.evaluator import EvalConfig
    from repro.core.functions import ExemplarClustering

    key = "audit_rt_sharded"
    rng = np.random.default_rng(2)
    V = rng.standard_normal((32, 4)).astype(np.float32)
    for _ in range(2):
        f = ExemplarClustering(jnp.asarray(V), EvalConfig())
        eng.run_selection(f, kind="dense", k=3,
                          cand_rounds=np.arange(32, dtype=np.int32)[None, :],
                          plan="device_sharded", counter_key=key)
    n = eng.DEVICE_TRACE_COUNTS[key]
    return n == 1, f"traces for two same-signature calls: {n} (want 1)"


def _rt_retrace_sieve() -> tuple[bool, str]:
    import jax.numpy as jnp
    from repro.core import engine as eng
    from repro.core.evaluator import EvalConfig
    from repro.core.functions import ExemplarClustering
    from repro.core.streaming import make_sieve_engine

    rng = np.random.default_rng(3)
    V = rng.standard_normal((32, 4)).astype(np.float32)
    f = ExemplarClustering(jnp.asarray(V), EvalConfig())
    engine = make_sieve_engine(f, 3, 0.2, variant="sieve", mode="device",
                               block_size=8)
    before = eng.DEVICE_TRACE_COUNTS["sieve_sieve"]
    engine.offer(np.arange(8), rng.standard_normal((8, 4)))
    engine.offer(np.arange(8, 16), rng.standard_normal((8, 4)))
    n = eng.DEVICE_TRACE_COUNTS["sieve_sieve"] - before
    return n == 1, f"traces for two same-shape stream blocks: {n} (want 1)"


def _rt_donation_live() -> tuple[bool, str]:
    """The executable's aliasing table must match live behavior: the donated
    seed buffer is consumed by the dispatch (``is_deleted``), and the engine
    wrapper passes a *copy* so the function's resident seed survives."""
    import jax.numpy as jnp
    from repro.core import engine as eng
    from repro.core.evaluator import EvalConfig
    from repro.core.functions import ExemplarClustering

    rng = np.random.default_rng(4)
    V = jnp.asarray(rng.standard_normal((32, 4)).astype(np.float32))
    f = ExemplarClustering(V, EvalConfig())
    seed = f.cache_seed[None]
    out = eng._select_scan(
        f.V[None], seed, f.row_aux[None],
        jnp.arange(32, dtype=jnp.int32)[None, None, :],
        jnp.zeros((1, 4), jnp.float32), jnp.asarray([3], jnp.int32),
        fn=f.spec, kind="dense", k=3, top_b=0, distance="sqeuclidean",
        policy_name="fp32", block_m=32, backend="jnp", rbf_gamma=None,
        counter_key="audit_rt_donate")
    jax.block_until_ready(out)
    if not seed.is_deleted():
        return False, "donated seed buffer survived the dispatch"
    if f.cache_seed.is_deleted():
        return False, "the function's resident cache seed was consumed"
    return True, "seed donated and consumed; resident seed intact"


def _rt_donation_sieve() -> tuple[bool, str]:
    """The streaming carry's aliasing table must match live behavior: after
    a block dispatch the engine's PRE-call state buffers are consumed
    (``is_deleted``) and the rebound state is alive — the table aliased in
    place instead of copying."""
    import jax.numpy as jnp
    from repro.core.evaluator import EvalConfig
    from repro.core.functions import ExemplarClustering
    from repro.core.streaming import make_sieve_engine

    rng = np.random.default_rng(6)
    V = jnp.asarray(rng.standard_normal((32, 4)).astype(np.float32))
    f = ExemplarClustering(V, EvalConfig())
    engine = make_sieve_engine(f, 3, 0.2, variant="sieve", mode="device",
                               block_size=8)
    old = engine.state
    engine.offer(np.arange(8), rng.standard_normal((8, 4)))
    jax.block_until_ready(engine.state)
    if not old.caches.is_deleted():
        return False, "pre-call cache table survived the dispatch (copied)"
    if engine.state.caches.is_deleted():
        return False, "the rebound cache table was consumed"
    return True, "sieve carry donated and consumed; rebound table alive"


def _rt_overlap_sieve() -> tuple[bool, str]:
    """The overlapped offer pipeline must be free lunch: zero extra traces
    versus the serialized baseline AND identical members/value/evals."""
    import jax.numpy as jnp
    from repro.core import engine as eng
    from repro.core.evaluator import EvalConfig
    from repro.core.functions import ExemplarClustering
    from repro.core.streaming import make_sieve_engine

    rng = np.random.default_rng(7)
    V = jnp.asarray(rng.standard_normal((32, 4)).astype(np.float32))
    stream = rng.standard_normal((40, 4)).astype(np.float32)
    results = []
    before = eng.DEVICE_TRACE_COUNTS["sieve_sieve"]
    for overlap in (False, True):
        f = ExemplarClustering(jnp.asarray(V), EvalConfig())
        engine = make_sieve_engine(f, 3, 0.2, variant="sieve",
                                   mode="device", block_size=8,
                                   overlap=overlap, max_in_flight=2)
        acc = engine.offer(np.arange(len(stream)), stream)
        results.append((engine.best(), engine.evaluations(), acc.tolist()))
    traces = eng.DEVICE_TRACE_COUNTS["sieve_sieve"] - before
    if traces > 1:
        return False, f"overlap pipeline retraced: {traces} traces (want ≤1)"
    if results[0] != results[1]:
        return False, "overlap-on diverged from the serialized baseline"
    return True, "overlap-on == overlap-off (members/value/evals), ≤1 trace"


def _rt_service_bucket() -> tuple[bool, str]:
    """One service round trip: concurrent same-signature tenants must ride
    ONE batched dispatch (and a second burst must not retrace)."""
    import asyncio

    from repro.core import engine as eng
    from repro.core.evaluator import EvalConfig
    from repro.core.service import SelectionService

    rng = np.random.default_rng(5)

    async def serve():
        # linger lets each 3-request burst coalesce into ONE pow2 bucket
        async with SelectionService(EvalConfig(), max_batch=8,
                                    linger_s=0.05) as svc:
            for _ in range(2):
                await asyncio.gather(*[
                    svc.submit(rng.standard_normal((32, 4)), k=3)
                    for _ in range(3)])
            return svc.stats

    before = eng.DEVICE_TRACE_COUNTS["serve_dense"]
    stats = asyncio.run(serve())
    traces = eng.DEVICE_TRACE_COUNTS["serve_dense"] - before
    if traces != 1:
        return False, f"two same-signature bursts traced {traces}x (want 1)"
    if stats["dispatches"] != 2:
        return False, f"6 requests cost {stats['dispatches']} dispatches"
    return True, (f"{stats['batched_requests']} requests in "
                  f"{stats['dispatches']} dispatches, 1 trace")


def runtime_checks() -> list[RuntimeCheck]:
    return [
        RuntimeCheck("retrace.device", _rt_retrace_device),
        RuntimeCheck("retrace.batched", _rt_retrace_batched),
        RuntimeCheck("retrace.sharded", _rt_retrace_sharded),
        RuntimeCheck("retrace.sieve", _rt_retrace_sieve),
        RuntimeCheck("donation.live", _rt_donation_live),
        RuntimeCheck("donation.sieve", _rt_donation_sieve),
        RuntimeCheck("overlap.sieve", _rt_overlap_sieve),
        RuntimeCheck("service.bucket", _rt_service_bucket),
    ]
