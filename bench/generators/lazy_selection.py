"""Closed-loop CELF (lazy greedy) selections.

As ``bench/generators/selection.py``, whose loop, draws and spans this
reuses: each call draws a fresh (n, d) ground set on the device from the
seed, builds the program's ``ExemplarClustering`` over it and runs one
k-round ``lazy_greedy`` re-scoring ``top_b`` candidates a trip, under the
configuration's ``plan``; the next call starts when the last one's
``OptResult`` is on the host.

``evals_per_s`` counts what Algorithm 1 asks about, Σ_{r<k} (n − r) sets
a call, as the Greedy cell does, so the two cells compare directly; the
sets CELF actually scores (``OptResult.evaluations``, which ``correct``
checks) go into the roofline's work instead. The re-score trips
(``OptResult.rescore_trips``) are recorded where the program reports
them.
"""
from __future__ import annotations

import numpy as np

from bench import datagen
from bench.generators import base, selection
from bench.reference import lazy as reference

#: the ``OptResult`` field of CELF's re-score trips (absent in programs
#: that do not count them)
TRIPS_FIELD = "rescore_trips"


class Generator(selection.Generator):

    def setup(self) -> None:
        from repro.core import EvalConfig, ExemplarClustering, lazy_greedy

        c = self.config
        self.n, self.d, self.k = c["n"], c["d"], c["k"]
        self.top_b = c["top_b"]
        data = c["data"]
        if data["kind"] != "uniform":
            raise ValueError(f"unknown data kind {data['kind']!r}")
        if c["optimizer"] != "lazy_greedy" or c["strategy"] != "lazy":
            raise ValueError(f"no lazy selection generator for "
                             f"{c['optimizer']!r} with strategy "
                             f"{c['strategy']!r}")
        self.shape = dict(n=self.n, d=self.d, low=data["low"],
                          high=data["high"])
        if self.control:
            def select(V):
                return reference.celf(V, self.k, self.top_b,
                                      precision="high")
        else:
            cfg = EvalConfig(distance=c["distance"], policy=c["precision"],
                             backend=c["backend"])

            def select(V):
                res = lazy_greedy(ExemplarClustering(V, cfg), self.k,
                                  batch=self.top_b, mode=c["plan"])
                return (res.indices, res.trajectory, res.evaluations,
                        getattr(res, TRIPS_FIELD, None))
        self.select = select
        warm = datagen.seed_key(self.seed, selection.WARMUP)
        for i in range(self.traffic["warmup_calls"]):
            self._call(warm, i)
        self.key = datagen.seed_key(self.seed, selection.WINDOW)
        self.outputs = []

    def run_window(self, seconds: float) -> base.Window:
        window = super().run_window(seconds)
        trips = [out[3] for out in self.outputs]
        window.work = {"celf": dict(
            n=self.n, d=self.d, k=self.k, top_b=self.top_b, itemsize=4,
            calls=len(self.outputs),
            evaluations=[out[2] for out in self.outputs],
            rescore_trips=None if None in trips else trips)}
        evals = np.mean(window.work["celf"]["evaluations"])
        window.notes.append(
            f"celf: {evals:.1f} evaluations a call"
            + ("" if None in trips else
               f", {np.mean(trips):.2f} re-score trips a call"))
        return window

    def check(self) -> dict[str, float]:
        """The largest of each reading of ``reference.readings`` over
        ``check_calls`` calls of the window drawn from the seed."""
        rng = np.random.default_rng(
            np.random.SeedSequence([self.seed, selection.SAMPLE]))
        calls = len(self.outputs)
        sample = rng.choice(calls, size=min(self.traffic["check_calls"],
                                            calls), replace=False)
        worst = {}
        for i in sorted(sample):
            V = selection.draw(self.key, int(i), self.shape)
            picks, traj, evals, _ = self.outputs[i]
            got = reference.readings(V, picks, traj, evals, self.top_b)
            worst = {name: max(value, worst.get(name, value))
                     for name, value in got.items()}
        print(f"lazy selection check: {len(sample)} of {calls} calls",
              file=self.log, flush=True)
        return worst
