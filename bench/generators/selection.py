"""Closed-loop Greedy selections, as the paper's optimizer runs them.

Each call draws a fresh (n, d) ground set on the device from the seed,
builds the program's ``ExemplarClustering`` over it and runs one k-round
``greedy`` under the configuration's ``plan``; the next call starts when
the last one's ``OptResult`` is on the host. Building the function is the
user's own cost, so it is inside the call. Traffic parameters
(``bench/traffic/<mix>.json``):

- ``warmup_calls``: calls made in set-up, on draws the window never uses;
- ``check_calls``: how many of the window's calls, drawn from the seed,
  are compared with the plain reference once the window has closed.

A call counts the sets whose value Algorithm 1 asks for, f(S_r ∪ {v}) for
every live candidate v of every round r, Σ_{r<k} (n − r) of them: that is
``evals_per_s``'s unit, as in the multiset cells.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from bench import datagen
from bench.generators import base
from bench.reference import greedy as reference

#: draw streams of one seed
WINDOW, WARMUP, SAMPLE = 1, 2, 3


def draw(key, i, shape):
    """Call ``i``'s (n, d) ground set."""
    return datagen.uniform(jax.random.fold_in(key, i), **shape)


class Generator(base.Generator):
    span_names = ("select", "run_selection", "run_selection.prepare",
                  "run_selection.fetch", "function.init")

    def setup(self) -> None:
        from repro.core import EvalConfig, ExemplarClustering, greedy

        c = self.config
        self.n, self.d, self.k = c["n"], c["d"], c["k"]
        data = c["data"]
        if data["kind"] != "uniform":
            raise ValueError(f"unknown data kind {data['kind']!r}")
        if c["optimizer"] != "greedy" or c["strategy"] != "dense":
            raise ValueError(f"no selection generator for {c['optimizer']!r}"
                             f" with strategy {c['strategy']!r}")
        self.shape = dict(n=self.n, d=self.d, low=data["low"],
                          high=data["high"])
        if self.control:
            def select(V):
                return reference.greedy(V, self.k, precision="high")
        else:
            cfg = EvalConfig(distance=c["distance"], policy=c["precision"],
                             backend=c["backend"])

            def select(V):
                res = greedy(ExemplarClustering(V, cfg), self.k,
                             mode=c["plan"])
                return res.indices, res.trajectory, res.evaluations
        self.select = select
        warm = datagen.seed_key(self.seed, WARMUP)
        for i in range(self.traffic["warmup_calls"]):
            self._call(warm, i)
        self.key = datagen.seed_key(self.seed, WINDOW)
        self.outputs = []

    def _call(self, key, i):
        """Call ``i``: (picks, trajectory, scored count) on the host."""
        with self.span("select"):
            return self.select(draw(key, i, self.shape))

    def run_window(self, seconds: float) -> base.Window:
        clock = []
        t0 = time.perf_counter()
        while True:
            self.outputs.append(self._call(self.key, len(self.outputs)))
            clock.append(time.perf_counter())
            if clock[-1] - t0 >= seconds:
                break
        elapsed = clock[-1] - t0
        calls = len(self.outputs)
        per_call = np.diff([t0] + clock) * 1e3
        return base.Window(
            seconds=elapsed, attempted=calls, failed=0,
            end_to_end={"evals_per_s": calls * reference.scored(
                self.n, self.k) / elapsed},
            work={"dense_greedy": dict(
                n=self.n, d=self.d, k=self.k, itemsize=4,  # float32 V
                calls=calls, scored=[out[2] for out in self.outputs])},
            notes=[f"selection: {calls} calls of k={self.k} over n="
                   f"{self.n}, {per_call.mean():.3f} ms per call (p50 "
                   f"{np.median(per_call):.3f}, max {per_call.max():.3f})"])

    def release(self) -> None:
        self.select = None

    def check(self) -> dict[str, float]:
        """The largest of each reading of ``reference.readings`` over
        ``check_calls`` calls of the window drawn from the seed."""
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, SAMPLE]))
        calls = len(self.outputs)
        sample = rng.choice(calls, size=min(self.traffic["check_calls"],
                                            calls), replace=False)
        worst = {}
        for i in sorted(sample):
            V = draw(self.key, int(i), self.shape)
            got = reference.readings(V, *self.outputs[i])
            worst = {name: max(value, worst.get(name, value))
                     for name, value in got.items()}
        print(f"selection check: {len(sample)} of {calls} calls",
              file=self.log, flush=True)
        return worst
