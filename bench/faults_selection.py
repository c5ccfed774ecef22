"""Faults planted under ``run_selection``, the selection generator's timed
path, to show that ``correct`` refuses them. Nothing in a benchmark run
plants one; ``bench/tests/test_selection_cell.py`` reads them on the CPU.
``bench/calibrate.py --fault`` knows only ``bench/faults.py``'s registry.

Each fault is a context manager that patches the program where the answer
is produced and restores it on exit. Call ``jax.clear_caches()`` before
planting one: programs traced earlier in the process would hide it.
"""
from __future__ import annotations

from bench.faults import _patched


def swapped_pick():
    """Rounds 0 and 1 trade winners in the picks handed back."""
    from repro.core import engine

    def make(orig):
        def broken(*a, **kw):
            sel, *rest = orig(*a, **kw)
            return (sel.at[0].set(sel[1]).at[1].set(sel[0]), *rest)
        return broken
    return _patched(engine, "_select_scan", make)


def skipped_fold():
    """The previous winner is never folded into the cache."""
    from repro.kernels import ops

    def make(orig):
        def broken(*a, w_valid=None, **kw):
            return orig(*a, w_valid=0.0, **kw)
        return broken
    return _patched(ops, "fused_gain_update", make)


def half_the_rows():
    """The fused gain kernel sees V's first half of rows only: the gains
    are means over them, and only their cache rows fold each winner."""
    from repro.kernels import ops

    def make(orig):
        def broken(V, C, mincache, winner, **kw):
            half = V.shape[0] // 2
            gains, folded = orig(V[:half], C, mincache[:half], winner, **kw)
            return gains, mincache.at[:half].set(folded)
        return broken
    return _patched(ops, "fused_gain_update", make)


def stale_answer():
    """Each selection hands back the previous selection's result."""
    from repro.core import engine

    def make(orig):
        last = []

        def broken(*a, **kw):
            out = orig(*a, **kw)
            if last:
                out, last[0] = last[0], out
            else:
                last.append(out)
            return out
        return broken
    return _patched(engine, "_select_scan", make)


#: faults by the generator that drives a cell's timed path
FAULTS = {
    "selection": {
        "swapped_pick": swapped_pick,
        "skipped_fold": skipped_fold,
        "half_the_rows": half_the_rows,
        "stale_answer": stale_answer,
    },
}

