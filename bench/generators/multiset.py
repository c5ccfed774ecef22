"""Closed-loop multiset evaluation, as an optimizer drives it.

Each call draws a fresh (l, k) index set on the device from the seed, as
an optimizer hands over new candidate sets, gathers the sets from V and
evaluates L(S_j ∪ {e0}) for all l of them through the program's
``evaluate_multiset``; the next call starts when the values are on the
device. Traffic parameters (``bench/traffic/<mix>.json``):

- ``warmup_calls``: calls made in set-up, on draws the window never uses;
- ``check_calls``: how many of the window's calls, drawn from the seed,
  are compared with the reference once the window has closed.
"""
from __future__ import annotations

import gc
import time
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import datagen
from bench.generators import base
from bench.reference import multiset as reference

#: draw streams of one seed
DATA, WINDOW, WARMUP, SAMPLE = range(4)


@partial(jax.jit, static_argnames=("l", "k"))
def draw(V, key, i, *, l: int, k: int):
    """Call ``i``'s index set (l, k) and the sets it gathers from V."""
    idx = jax.random.randint(jax.random.fold_in(key, i), (l, k), 0,
                             V.shape[0], jnp.int32)
    return idx, V[idx]


def _gc_timer(pauses):
    """A ``gc.callbacks`` hook that appends (generation, seconds) of every
    collection to ``pauses``."""
    started = []

    def hook(phase, info):
        if phase == "start":
            started[:] = [time.perf_counter()]
        elif started:
            pauses.append((info["generation"],
                           time.perf_counter() - started[0]))
    return hook


def _clock_notes(clock, t0, l, pauses):
    """Per-call host timings of the window, and every call that took over
    three times the median: when it started, how long the host took to
    hand it to the device, how long it then waited, and the process's CPU
    time meanwhile (far under the wall time: the process was not
    running)."""
    c = np.asarray(clock)
    total = (c[:, 2] - c[:, 0]) * 1e3
    p50 = float(np.median(total))
    slow = [f"#{i} at {c[i, 0] - t0:.2f} s: {total[i]:.1f} ms "
            f"(dispatch {(c[i, 1] - c[i, 0]) * 1e3:.1f}, wait "
            f"{(c[i, 2] - c[i, 1]) * 1e3:.1f}, cpu {c[i, 3] * 1e3:.1f})"
            for i in np.flatnonzero(total > 3 * p50)]
    full = [s for g, s in pauses if g == 2]
    return [f"multiset: {len(c)} calls of {l} sets, {total.mean():.3f} ms "
            f"per call (p50 {p50:.3f}, p90 {np.quantile(total, 0.9):.3f}, "
            f"max {total.max():.3f} ms); host dispatch p50 "
            f"{np.median(c[:, 1] - c[:, 0]) * 1e3:.3f} ms",
            f"multiset: garbage collections {len(pauses)}, of them full "
            f"{len(full)}, longest {max((s for _, s in pauses), default=0) * 1e3:.1f} ms",
            "multiset: slow calls: " + ("; ".join(slow) or "none")]


class Generator(base.Generator):
    span_names = ("evaluate",)

    def setup(self) -> None:
        from repro.core import EvalConfig, PackedMultiset, evaluate_multiset

        c = self.config
        self.n, self.l, self.k, self.d = c["n"], c["l"], c["k"], c["d"]
        data = c["data"]
        if data["kind"] != "uniform":
            raise ValueError(f"unknown data kind {data['kind']!r}")
        self.V = datagen.uniform(datagen.seed_key(self.seed, DATA), n=self.n,
                                 d=self.d, low=data["low"], high=data["high"])
        self.lengths = jnp.full((self.l,), self.k, jnp.int32)
        if self.control:
            def evaluate(idx, _sets):
                return reference.multiset_values(
                    self.V, idx, self.lengths, precision="high")
        else:
            cfg = EvalConfig(distance=c["distance"], policy=c["precision"],
                             mode=c["mode"], backend=c["backend"])

            def evaluate(_idx, sets):
                return evaluate_multiset(
                    self.V, PackedMultiset(sets, self.lengths), cfg)
        self.evaluate = evaluate
        warm = datagen.seed_key(self.seed, WARMUP)
        for i in range(self.traffic["warmup_calls"]):
            self._call(warm, i)
        self.key = datagen.seed_key(self.seed, WINDOW)
        self.outputs = []

    def _call(self, key, i, clock=None):
        """Call ``i``; ``clock`` collects (start, dispatched, done, process
        CPU seconds) of the host for the window's notes."""
        with self.span("evaluate"):
            t = (time.perf_counter(), time.process_time())
            idx, sets = draw(self.V, key, i, l=self.l, k=self.k)
            out = self.evaluate(idx, sets)
            dispatched = time.perf_counter()
            out = jax.block_until_ready(out)
        if clock is not None:
            clock.append((t[0], dispatched, time.perf_counter(),
                          time.process_time() - t[1]))
        return out

    def run_window(self, seconds: float) -> base.Window:
        clock = []
        pauses = []
        gc.callbacks.append(_gc_timer(pauses))
        try:
            t0 = time.perf_counter()
            while True:
                self.outputs.append(self._call(self.key, len(self.outputs),
                                               clock))
                elapsed = clock[-1][2] - t0
                if elapsed >= seconds:
                    break
        finally:
            gc.callbacks.pop()
        calls = len(self.outputs)
        itemsize = jnp.dtype(self.V.dtype).itemsize
        return base.Window(
            seconds=elapsed, attempted=calls, failed=0,
            end_to_end={"evals_per_s": calls * self.l / elapsed},
            work={"multiset_eval": dict(
                n=self.n, l=self.l, k=self.k, d=self.d, itemsize=itemsize,
                calls=calls)},
            notes=_clock_notes(clock, t0, self.l, pauses))

    def release(self) -> None:
        self.evaluate = None

    def check(self) -> dict[str, float]:
        """Largest relative gap of a value from the reference, over every
        value of ``check_calls`` calls of the window drawn from the seed."""
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, SAMPLE]))
        calls = len(self.outputs)
        sample = rng.choice(calls, size=min(self.traffic["check_calls"],
                                            calls), replace=False)
        worst = 0.0
        for i in sorted(sample):
            idx, _ = draw(self.V, self.key, int(i), l=self.l, k=self.k)
            ref = reference.multiset_values(self.V, idx, self.lengths,
                                            precision="highest")
            got = np.asarray(self.outputs[i], np.float64)
            if got.shape != ref.shape or not np.all(np.isfinite(got)):
                return {"max_rel_err": float("inf")}
            rel = np.abs(got - ref) / np.abs(ref.astype(np.float64))
            worst = max(worst, float(rel.max()))
        print(f"multiset check: {len(sample)} of {calls} calls, "
              f"{len(sample) * self.l} values", file=self.log, flush=True)
        return {"max_rel_err": worst}
