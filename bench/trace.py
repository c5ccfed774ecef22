"""Reduce a profiler trace (``.xplane.pb``) to device busy time, the top
device operations and the device's idle gaps by host span.

What a TPU trace holds, as JAX's profiler writes it: one plane per chip,
``/device:TPU:<i>``, whose line ``XLA Ops`` has one event per operation
that ran (named by its HLO text, ``%name = shape opcode(...)``) and whose
line ``XLA Modules`` has one event per program launch (``jit_<fn>(<id>)``);
and a plane ``/host:CPU`` whose lines are host threads, where
``jax.profiler.TraceAnnotation`` spans appear under their names. Device and
host events share one clock (nanoseconds from the start of the trace).

Busy time is the union of the ``XLA Ops`` intervals inside the window, so
operations that overlap count once. Asynchronous copies (``Async XLA Ops``)
are left out: they are DMA in flight, not an operation running. The top
operations leave out control flow (``while``, ``conditional``, ``call``),
whose events span the operations of their bodies.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
#: the harness wraps its measured window in a span of this name
WINDOW_SPAN = "window"
TOP = 10
CONTROL_FLOW = re.compile(r"\s(while|conditional|call)\(")


@dataclasses.dataclass
class Reduced:
    """What the metrics read from one traced window."""

    window_s: float                       #: length of the traced window
    busy_s: float                         #: device busy, mean over chips
    devices: int                          #: chips with operations
    top_ops: list[tuple[str, float]]      #: (module:op, seconds), summed
    idle_gaps: list[tuple[str, float]]    #: (host span, seconds), longest
    spans: dict[str, tuple[int, float]]   #: host span → (count, seconds)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(log_dir: str) -> str:
    """The one ``.xplane.pb`` that a trace into ``log_dir`` wrote."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {log_dir}, found {found}")
    return found[0]


def union(intervals):
    """Merge (start, end) intervals; returns them sorted and disjoint."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def gaps(busy, lo: float, hi: float):
    """The parts of [lo, hi] that the disjoint sorted ``busy`` leaves."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _overlap(disjoint, lo: float, hi: float) -> float:
    """Length of [lo, hi] that the sorted disjoint intervals cover."""
    i = max(bisect.bisect_right(disjoint, (lo,)) - 1, 0)
    total = 0.0
    for s, e in disjoint[i:]:
        if s >= hi:
            break
        total += max(0.0, min(e, hi) - max(s, lo))
    return total


def op_label(hlo_text: str) -> str:
    """``%fusion.3 = f32[..] fusion(...)`` → ``fusion.3``."""
    return hlo_text.split(" = ", 1)[0].lstrip("%").strip()


def module_label(name: str) -> str:
    """``jit__exemplar_eval_padded(1107...)`` → ``_exemplar_eval_padded``."""
    base = name.split("(", 1)[0]
    return base[4:] if base.startswith("jit_") else base


def _events(line):
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def read_planes(path: str):
    """Device ops and modules per chip, and host spans, from the trace."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[int(m.group(1))] = _events(line)
                elif line.name == MODULES_LINE:
                    modules[int(m.group(1))] = _events(line)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(_events(line))
    return ops, modules, host


def reduce(path: str, span_names=(), window_span: str = WINDOW_SPAN
           ) -> Reduced:
    """Reduce the trace at ``path`` over the host span ``window_span``.

    ``span_names`` are the host spans that idle gaps are attributed to:
    each gap goes to the span among them that covers most of it, or to
    ``"no span"``.
    """
    ops, modules, host = read_planes(path)
    windows = [(s, e) for s, e, name in host if name == window_span]
    if len(windows) != 1:
        raise ValueError(f"expected one {window_span!r} span in {path}, "
                         f"found {len(windows)}")
    lo, hi = windows[0]
    ns = 1e-9
    busy_per_chip, totals = [], {}
    all_busy = []
    for chip, events in sorted(ops.items()):
        inside = [(s, e, name) for s, e, name in events if e > lo and s < hi]
        if not inside:
            continue
        mods = sorted(modules.get(chip, []))
        starts = [m[0] for m in mods]
        for s, e, name in inside:
            if CONTROL_FLOW.search(name.split(" = ", 1)[-1]):
                continue
            i = bisect.bisect_right(starts, s) - 1
            mod = module_label(mods[i][2]) if i >= 0 and mods[i][1] >= s \
                else "?"
            key = f"{mod}:{op_label(name)}"
            cs, ce = max(s, lo), min(e, hi)
            totals[key] = totals.get(key, 0.0) + (ce - cs) * ns
        merged = union(clip([(s, e) for s, e, _ in inside], lo, hi))
        busy_per_chip.append(sum(e - s for s, e in merged) * ns)
        all_busy.extend(merged)
    spans: dict[str, tuple[int, float]] = {}
    covered = {}           # span name → its disjoint intervals in the window
    for name in span_names:
        mine = clip([(s, e) for s, e, n in host if n == name], lo, hi)
        if mine:
            spans[name] = (len(mine), sum(e - s for s, e in mine) * ns)
            covered[name] = union(mine)
    # a gap goes to the span covering most of it; of spans covering it
    # alike, to the one that covers the least of the window (the innermost)
    by_depth = sorted(covered, key=lambda name: spans[name][1])
    any_span = union([iv for name in covered for iv in covered[name]])
    idle = []
    for gs, ge in gaps(union(all_busy), lo, hi):
        best, cover = "no span", (ge - gs) - _overlap(any_span, gs, ge)
        for name in by_depth:
            c = _overlap(covered[name], gs, ge)
            if c > cover:
                best, cover = name, c
        idle.append((best, (ge - gs) * ns))
    idle.sort(key=lambda x: -x[1])
    top = sorted(totals.items(), key=lambda x: -x[1])[:TOP]
    n_chips = len(busy_per_chip)
    return Reduced(
        window_s=(hi - lo) * ns,
        busy_s=sum(busy_per_chip) / n_chips if n_chips else 0.0,
        devices=n_chips, top_ops=top, idle_gaps=idle[:TOP], spans=spans)
