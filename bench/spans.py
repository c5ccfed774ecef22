"""The device's idle time in a traced window, split by the host span the
host was in.

Host spans, the harness's and the program's (``jax.profiler.
TraceAnnotation``; ``src/repro/core/tracing.py`` names the program's),
share the trace's clock with the device's operations. ``bench/trace.py``
gives each of the ten longest idle gaps to one span; this splits all of
the window's idle time, each part to the innermost span covering it.
"""
from __future__ import annotations

from bench import trace


def split_idle(idle, covered: list[tuple[str, list]]) -> dict[str, float]:
    """Length of the disjoint sorted intervals ``idle`` that each host span
    covers innermost, with the spans' disjoint intervals ``covered`` listed
    innermost first, and the rest under ``"no span"``.

    A span gets what it covers and no span before it in the list does: of
    nested spans, the innermost one.
    """
    def cover(disjoint):
        return sum(trace._overlap(disjoint, s, e) for s, e in idle)

    out, inner, before = {}, [], 0.0
    for name, intervals in covered:
        inner = trace.union(inner + list(intervals))
        now = cover(inner)
        out[name] = now - before
        before = now
    out["no span"] = sum(e - s for s, e in idle) - before
    return out


def idle_by_span(path: str, span_names, window_span: str = trace.WINDOW_SPAN
                 ) -> dict[str, float]:
    """Seconds of the window at ``path`` in which no chip ran an operation,
    split by the innermost of the host spans ``span_names`` (listed
    innermost first) that covers them, and ``"no span"``."""
    ops, _, host = trace.read_planes(path)
    windows = [(s, e) for s, e, name in host if name == window_span]
    if len(windows) != 1:
        raise ValueError(f"expected one {window_span!r} span in {path}, "
                         f"found {len(windows)}")
    lo, hi = windows[0]
    busy = trace.union(trace.clip(
        [(s, e) for events in ops.values() for s, e, _ in events], lo, hi))
    idle = trace.gaps(busy, lo, hi)
    covered = [(name, trace.union(trace.clip(
        [(s, e) for s, e, n in host if n == name], lo, hi)))
        for name in span_names]
    return {name: ns * 1e-9 for name, ns in split_idle(idle, covered).items()}

