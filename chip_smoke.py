#!/usr/bin/env python3
"""Smoke run of the selection engine on the TPU that JAX finds.

    python chip_smoke.py             # one chip: multiset evaluation (k=10
                                     # and across the paper's k sweep),
                                     # one-shot selection, serving, streaming
    python chip_smoke.py --chips 4   # four chips: the mesh plans only

Everything runs in this one process. Each phase drives the system through
its normal entry points at deployment sizes, checks the results against the
repository's own references on the same chip, and prints one line: the
phase, its shapes, its parity checks, and first-call / steady seconds. Those
seconds are smoke timings (the first call includes compilation; one call
each), not benchmark numbers.

The last line of standard output is ``{"ok": true, "device": {...}}``,
printed only when every phase passed. Without a TPU, or without the rest of
the repository beside this file, the script exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import math
import os
import sys
import time
import traceback

import numpy as np

SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")

#: Parity bands, the same ones the test suite holds the kernels to:
#: fp32 pallas vs the jnp oracle (tests/test_kernel_parity.py, ragged
#: fused/two-pass cases) and bf16 kernel vs jnp at the same policy
#: (POLICY_TOLS["bf16"]["kernel_atol"] there).
ATOL = {"fp32": 1e-4, "bf16": 5e-2}
#: Byte budget for the jnp multiset reference: its (n, chunk·k) distance
#: block is materialized, so it is chunked (paper §IV-B-3).
JNP_REFERENCE_BUDGET = 1 << 30


def _timed(fn):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _first_and_steady(fn):
    """Run ``fn`` twice: (first result, second result, first s, steady s)."""
    first, t_first = _timed(fn)
    steady, t_steady = _timed(fn)
    return first, steady, t_first, t_steady


class Phase:
    """Collects one phase's checks and timings into its one output line."""

    def __init__(self, name: str, shapes: str):
        self.name, self.shapes = name, shapes
        self.checks: list[str] = []
        self.times: list[str] = []
        self.ok = True

    def check(self, label: str, passed: bool, detail: str = ""):
        self.ok &= bool(passed)
        self.checks.append(
            f"{label} {'PASS' if passed else 'FAIL'}"
            + (f" ({detail})" if detail else ""))

    def time(self, label: str, first: float, steady: float):
        self.times.append(f"{label} {first:.3f}/{steady:.3f}")

    def line(self) -> str:
        return (f"[{'PASS' if self.ok else 'FAIL'}] {self.name} | "
                f"{self.shapes} | {'; '.join(self.checks)} | smoke timings, "
                f"first/steady s: {', '.join(self.times) or '-'}")


def _same_selection(a, b) -> bool:
    return a.indices == b.indices and a.evaluations == b.evaluations


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------


def phase_multiset(n: int, l: int, k: int, d: int, backend: str,
                   seed: int = 0, name: str = "multiset_eval") -> Phase:
    """Paper §V-A: L(S_j ∪ {e0}) for l sets of k over n points, on the
    kernel backend (fused fp32 and bf16 at the default variant, two-pass
    fp32 as the paper's reference) against the jnp backend at the same
    policy."""
    import jax.numpy as jnp

    from repro.core import EvalConfig, PackedMultiset, evaluate_multiset
    from repro.data.synthetic import uniform_problem

    ph = Phase(name, f"n={n} l={l} k={k} d={d}")
    V = jnp.asarray(uniform_problem(n, d, seed=seed))
    idx = np.random.default_rng(seed + 1).integers(0, n, size=(l, k))
    pk = PackedMultiset(V[jnp.asarray(idx)], jnp.full((l,), k, jnp.int32))
    refs = {}
    for mode, pol in (("fused", "fp32"), ("fused", "bf16"),
                      ("two_pass", "fp32")):
        cfg = EvalConfig(backend=backend, mode=mode, policy=pol)
        _, got, t1, t2 = _first_and_steady(
            lambda cfg=cfg: evaluate_multiset(V, pk, cfg))
        if pol not in refs:
            refs[pol] = np.asarray(evaluate_multiset(V, pk, EvalConfig(
                policy=pol, memory_budget_bytes=JNP_REFERENCE_BUDGET)))
        got = np.asarray(got)
        err = float(np.max(np.abs(got - refs[pol])))
        ph.check(f"{mode}/{pol} vs jnp",
                 got.shape == (l,) and np.all(np.isfinite(got))
                 and err <= ATOL[pol],
                 f"max|Δ|={err:.2e} ≤ {ATOL[pol]:g}")
        ph.time(f"{mode}/{pol}", t1, t2)
    return ph


def _scan_hlo(f, kind: str, k: int) -> str:
    """Compiled HLO of the one-dispatch scan ``run_selection`` issues for
    ``f`` on the device plan (same operands and statics)."""
    import jax.numpy as jnp

    from repro.core import engine

    n = f.n
    cand = (np.zeros((1, 0), np.int32) if kind == "lazy"
            else np.arange(n, dtype=np.int32)[None, :])
    return engine._select_scan.lower(
        f.V, jnp.array(f.cache_seed), f.row_aux, jnp.asarray(cand),
        jnp.zeros((f.dim,), f.V.dtype), fn=f.spec, kind=kind, k=k,
        top_b=min(256, n) if kind == "lazy" else 0,
        distance=f.cfg.distance, policy_name=f.cfg.resolved_policy().name,
        block_m=engine._device_block_m(n, n), backend=f.cfg.backend,
        rbf_gamma=None,
        counter_key="lazy_greedy" if kind == "lazy" else "greedy",
    ).compile().as_text()


def phase_selection(n: int, d: int, k: int, backend: str,
                    seed: int = 0) -> Phase:
    """One-shot selection: greedy and CELF on the device plan against the
    host plan, for the min (exemplar) and max (facility location) kernel
    templates."""
    import jax.numpy as jnp

    from repro.core import FUNCTIONS, EvalConfig, greedy, lazy_greedy
    from repro.data.synthetic import blobs

    ph = Phase("one_shot_selection", f"n={n} d={d} k={k} fp32")
    X, _ = blobs(n, d, centers=20, seed=seed)
    V = jnp.asarray(X)
    cfg = EvalConfig(backend=backend)
    for fname in ("exemplar", "facility_location"):
        f = FUNCTIONS[fname](V, cfg)
        for opt, kind in ((greedy, "dense"), (lazy_greedy, "lazy")):
            first, dev, t1, t2 = _first_and_steady(
                lambda f=f, opt=opt: opt(f, k, mode="device"))
            host = opt(f, k, mode="host")
            ph.check(f"{fname}/{kind} device==host",
                     _same_selection(dev, host) and _same_selection(first, dev),
                     f"evals={dev.evaluations}")
            if backend == "pallas":
                ph.check(f"{fname}/{kind} tpu_custom_call",
                         "tpu_custom_call" in _scan_hlo(f, kind, k))
            ph.time(f"{fname}/{kind}", t1, t2)
    return ph


def phase_serving(n: int, d: int, n_requests: int, backend: str,
                  seed: int = 0) -> Phase:
    """One burst of mixed dense/CELF requests through ``SelectionService``,
    each result against its own unbatched ``run_selection``."""
    import jax.numpy as jnp

    from repro.core import (EvalConfig, ExemplarClustering, SelectionService,
                            greedy, lazy_greedy)
    from repro.data.synthetic import blobs

    ks, kinds = (4, 8, 10), ("dense", "lazy")
    ph = Phase("serving", f"{n_requests} requests n={n} d={d} k∈{ks} "
                          f"dense+lazy max_batch=64")
    cfg = EvalConfig(backend=backend)
    reqs = [(blobs(n, d, centers=8, seed=seed + i)[0], ks[i % 3],
             kinds[i % 2]) for i in range(n_requests)]

    async def burst():
        async with SelectionService(cfg, max_batch=64) as svc:
            res = await asyncio.gather(*[
                svc.submit(X, k=k, kind=kind) for X, k, kind in reqs])
            return res, dict(svc.stats)

    (res1, stats), (res2, _), t1, t2 = _first_and_steady(
        lambda: asyncio.run(burst()))
    refs = [(greedy if kind == "dense" else lazy_greedy)(
        ExemplarClustering(jnp.asarray(X), cfg), k, mode="device")
        for X, k, kind in reqs]
    bad = [i for i, (r1, r2, ref) in enumerate(zip(res1, res2, refs))
           if not (_same_selection(r1, ref) and _same_selection(r2, ref))]
    ph.check("batched==unbatched", not bad, f"mismatched requests {bad}"
             if bad else f"{len(refs)} requests")
    ph.check("dispatches<requests",
             stats["dispatches"] < stats["requests"],
             f"{stats['dispatches']} dispatches, {stats['requests']} requests")
    ph.check("staging_failures==0", stats["staging_failures"] == 0,
             f"{stats['staging_failures']}")
    ph.time("burst", t1, t2)
    return ph


def phase_streaming(n: int, d: int, n_streams: int, blocks: int,
                    backend: str, k: int = 10, eps: float = 0.1,
                    block: int = 32, seed: int = 0) -> Phase:
    """P streams through ``MultiStreamIngestionService``; stream 0 against a
    standalone device sieve engine fed the same sub-stream."""
    import jax.numpy as jnp

    from repro.core import (EvalConfig, ExemplarClustering,
                            MultiStreamIngestionService, make_sieve_engine)
    from repro.data.synthetic import blobs

    per_round = n_streams * blocks * block
    ph = Phase("streaming", f"{n_streams} streams n={n} d={d} k={k} "
                            f"eps={eps} {blocks}×{block} elements per stream "
                            f"per round")
    X, _ = blobs(n, d, centers=8, seed=seed)
    f = ExemplarClustering(jnp.asarray(X), EvalConfig(backend=backend))
    rng = np.random.default_rng(seed + 1)
    elems = (X[rng.integers(0, n, size=2 * per_round)]
             + 0.03 * rng.normal(size=(2 * per_round, d))).astype(np.float32)

    async def run():
        async with MultiStreamIngestionService(
                f, k=k, n_streams=n_streams, eps=eps,
                block_size=block) as svc:
            snaps, secs = [], []
            for r in range(2):
                t0 = time.perf_counter()
                for j in range(r * per_round, (r + 1) * per_round):
                    await svc.offer(elems[j], stream=j % n_streams)
                await svc.drain()
                snaps.append(await svc.snapshot())
                secs.append(time.perf_counter() - t0)
            return snaps, secs

    snaps, secs = asyncio.run(run())
    ref = make_sieve_engine(f, k, eps, mode="device", block_size=block)
    ref.offer(np.arange(0, 2 * per_round, n_streams), elems[0::n_streams])
    members, value = ref.best()
    snap = snaps[-1]
    ph.check("stream0==standalone",
             snap.stream_members[0] == members
             and math.isclose(snap.stream_values[0], value, rel_tol=1e-6),
             f"{len(members)} members, value {value:.6f}")
    ph.check("ingested", snap.n_ingested == 2 * per_round,
             f"{snap.n_ingested}")
    ph.check("merge certified", snap.certified,
             f"value {snap.value:.6f} ≥ bound {snap.bound:.6f}")
    ph.time("feed+snapshot", *secs)
    return ph


# ---------------------------------------------------------------------------
# four-chip phase
# ---------------------------------------------------------------------------


def phase_mesh(n: int, d: int, k: int, backend: str, seed: int = 0) -> Phase:
    """The mesh plans over every device against the device plan on one:
    ``device_sharded_pool`` must match it exactly, GreeDi must keep its
    proven (1−1/e)/min(√k, p) bound."""
    import jax
    import jax.numpy as jnp

    from repro.core import EvalConfig, ExemplarClustering, greedy
    from repro.data.synthetic import blobs

    devs = jax.devices()
    p = len(devs)
    ph = Phase("mesh_plans", f"p={p} n={n} d={d} k={k} fp32")
    X, _ = blobs(n, d, centers=32, seed=seed)
    f = ExemplarClustering(jax.device_put(jnp.asarray(X), devs[0]),
                           EvalConfig(backend=backend))
    _, ref, t1, t2 = _first_and_steady(lambda: greedy(f, k, mode="device"))
    ph.check("reference on one device", f.V.sharding.device_set == {devs[0]})
    ph.time("device(1 chip)", t1, t2)

    _, pool, t1, t2 = _first_and_steady(
        lambda: greedy(f, k, mode="device_sharded_pool"))
    placed = f._sharded_placement_cache[1]
    spread = {name: len(placed[name].sharding.device_set)
              for name in ("V_sh", "seed_sh", "aux_sh")}
    ph.check("sharded inputs on p devices",
             all(v == p for v in spread.values()), f"{spread}")
    ph.check("pool==device", _same_selection(pool, ref),
             f"evals={pool.evaluations}")
    ph.time("device_sharded_pool", t1, t2)

    _, gd, t1, t2 = _first_and_steady(lambda: greedy(f, k, mode="greedi"))
    bound = (1.0 - 1.0 / math.e) / min(math.sqrt(k), p)
    n_loc = -(-n // p)
    evals = (p * sum(n_loc - t for t in range(k))
             + sum(p * k - t for t in range(k)) + p * k)
    ph.check("greedi bound", gd.value >= bound * ref.value,
             f"ratio {gd.value / ref.value:.6f} ≥ {bound:.4f}")
    ph.check("greedi accounting", gd.evaluations == evals,
             f"{gd.evaluations} == {evals}")
    ph.time("greedi", t1, t2)
    return ph


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the mesh-plan phase, on four chips")
    args = ap.parse_args(argv)

    import jax

    sys.path.insert(0, SRC)
    try:
        from repro.core.compile_cache import enable_compile_cache
        from repro.core.evaluator import free_memory_bytes
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is missing: {e}",
              file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()
    cache_events = {"cache_hits": 0, "cache_misses": 0}

    def count_cache_event(event: str, **_):
        name = event.rsplit("/", 1)[-1]
        if event.startswith("/jax/compilation_cache/") and name in cache_events:
            cache_events[name] += 1

    jax.monitoring.register_event_listener(count_cache_event)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devs[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devs)}", file=sys.stderr)
        return 2
    free = free_memory_bytes()
    if free is None:
        print("chip_smoke: the device reports no memory stats, so the "
              "gain-tile autotuner would size from the CPU heuristic",
              file=sys.stderr)
        return 2
    print(f"chip_smoke: {len(devs)} × {devs[0].device_kind}, "
          f"{free / 2**30:.2f} GiB free on device 0, compile cache "
          f"{cache_dir}", flush=True)

    if args.chips == 4:
        phases = [("mesh_plans", lambda: phase_mesh(262_144, 128, 10,
                                                    "pallas"))]
    else:
        from repro.configs.paper import CONFIG as PAPER, SWEEPS

        k_mid, k_max = SWEEPS["k"][1], SWEEPS["k"][-1]
        phases = [
            ("multiset_eval", lambda: phase_multiset(
                PAPER.n, PAPER.l, PAPER.k, PAPER.dim, "pallas")),
            # the paper's k sweep past the flat tile's VMEM limit (k≈39 at
            # fp32), at the sweep's smallest l for the largest k
            (f"multiset_eval_k{k_mid}", lambda: phase_multiset(
                PAPER.n, PAPER.l, k_mid, PAPER.dim, "pallas",
                name=f"multiset_eval_k{k_mid}")),
            (f"multiset_eval_k{k_max}", lambda: phase_multiset(
                PAPER.n, SWEEPS["l"][0], k_max, PAPER.dim, "pallas",
                name=f"multiset_eval_k{k_max}")),
            ("one_shot_selection", lambda: phase_selection(
                PAPER.n, PAPER.dim, PAPER.k, "pallas")),
            ("serving", lambda: phase_serving(8_192, 100, 64, "pallas")),
            ("streaming", lambda: phase_streaming(8_192, 100, 8, 4,
                                                  "pallas")),
        ]
    ok = True
    for name, run in phases:
        try:
            ph = run()
            print(ph.line(), flush=True)
            ok &= ph.ok
        except Exception as e:  # report every phase, then fail
            print(f"[FAIL] {name}: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
            ok = False
    print(f"chip_smoke: persistent compile cache {cache_dir}: "
          f"{cache_events['cache_hits']} hits, "
          f"{cache_events['cache_misses']} misses", flush=True)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
