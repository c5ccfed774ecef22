"""Faults planted under a cell's timed path, to show that ``correct``
refuses them. Nothing in a benchmark run plants one: ``bench/calibrate.py
--fault <name>`` reads them on the chip, ``bench/tests/test_controls.py``
on the CPU.

Each fault is a context manager that patches the program where the answer
is produced and restores it on exit. Call ``jax.clear_caches()`` before
planting one: programs traced earlier in the process would hide it.
"""
from __future__ import annotations

import contextlib


@contextlib.contextmanager
def _patched(owner, name, make):
    orig = getattr(owner, name)
    setattr(owner, name, make(orig))
    try:
        yield
    finally:
        setattr(owner, name, orig)


# -- under evaluate_multiset (the multiset generator's cells) ----------------


def multiset_swapped_answer():
    """Set 0 is handed set 1's value."""
    from repro.kernels import ops

    def make(orig):
        def broken(*a, **kw):
            out = orig(*a, **kw)
            return out.at[0].set(out[1])
        return broken
    return _patched(ops, "exemplar_eval", make)


def multiset_half_the_rows():
    """The mean over V is taken over its first half of rows only."""
    from repro.kernels import ops

    def make(orig):
        def broken(V, S, lengths, d_e0, **kw):
            half = V.shape[0] // 2
            return orig(V[:half], S, lengths, d_e0[:half], **kw)
        return broken
    return _patched(ops, "exemplar_eval", make)


def multiset_stale_answer():
    """Each call returns the previous call's values (the state of the
    last step, unchanged)."""
    from repro.kernels import ops

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
    return _patched(ops, "exemplar_eval", make)


#: faults by the generator that drives a cell's timed path
FAULTS = {
    "multiset": {
        "swapped_answer": multiset_swapped_answer,
        "half_the_rows": multiset_half_the_rows,
        "stale_answer": multiset_stale_answer,
    },
}
