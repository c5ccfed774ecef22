"""Faults planted under ``run_selection``'s lazy strategy, the lazy
selection generator's timed path, to show that ``correct`` refuses them.
Nothing in a benchmark run plants one; ``tests/test_lazy_reference.py``
reads them on the CPU. ``bench/calibrate.py --fault`` knows only
``bench/faults.py``'s registry: merge this one into it first
(``faults.FAULTS.update(faults_lazy.FAULTS)``).

Each fault is a context manager that patches the program where the answer
is produced and restores it on exit. Call ``jax.clear_caches()`` before
planting one: programs traced earlier in the process would hide it.
"""
from __future__ import annotations

from bench.faults import _patched
from bench.faults_selection import stale_answer


def early_certify():
    """The re-score loop stops after its first trip in every round, as if
    the first batch always certified the winner."""
    from repro.core import engine

    def make(orig):
        def broken(take, fold, score_idx_val, top_b, max_iters, k_eff):
            return orig(take, fold, score_idx_val, top_b, 1, k_eff)
        return broken
    return _patched(engine, "make_lazy_step", make)


def skipped_fold():
    """The winner is never folded into the cache."""
    from repro.core import functions

    def make(orig):
        def broken(spec, vec, dw):
            return vec
        return broken
    return _patched(functions, "fold_vec_rows", make)


#: faults by the generator that drives a cell's timed path
FAULTS = {
    "lazy_selection": {
        "early_certify": early_certify,
        "skipped_fold": skipped_fold,
        "stale_answer": stale_answer,
    },
}
