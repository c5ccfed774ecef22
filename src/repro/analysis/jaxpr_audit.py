"""Jaxpr / StableHLO walkers behind the compiled-artifact audit.

Everything here operates on *traced* artifacts — ``jitted.trace(*abstract)``
jaxprs and their lowered StableHLO text — never on live arrays, so the
whole audit runs with abstract values (ShapeDtypeStructs) and costs traces,
not executions.

The checks map one-to-one onto the engine's prose claims:

* :func:`scan_structure` — "all k rounds in ONE dispatch": the artifact
  drives exactly the declared number of top-level ``lax.scan``s whose
  static ``length`` equals the round/element count; CELF's top-B loop is
  exactly one ``while`` nested in that scan.
* :func:`collective_census` — "ONE psum of O(m) bytes per scored batch":
  exact static collective counts, per region (whole artifact vs the
  driving scan's body) and kind, plus the byte size of the largest
  collective operand. Exact equality catches a sneaked-in extra collective
  AND a silently dropped one.
* :func:`donation_audit` — "the cache seed is donated": the lowered
  module's entry signature must alias exactly the declared number of
  inputs onto outputs (``tf.aliasing_output``), and no donated buffer may
  be left un-aliased. A bare ``jax.buffer_donor`` marker is ambiguous:
  on a multi-device mesh jax defers the aliasing decision to XLA's SPMD
  partitioner, so :func:`resolve_deferred_donations` re-judges those
  markers against the compiled ``input_output_alias`` table — only a
  donor the compiled executable does not alias counts as silently
  dropped (XLA's run-time-warning-only failure mode).
* :func:`precision_flow` — "gains stay in the compute dtype": under a
  half-precision policy no ``convert_element_type`` may widen a
  distance-tile-sized half tensor to fp32 (widening rides the matmul's
  ``preferred_element_type`` instead); at least one ``dot_general`` must
  consume half-dtype operands, proving the payload actually went through
  the unit in half precision.
"""
from __future__ import annotations

import dataclasses
import re
from collections import Counter
from typing import Any, Iterator, Optional

import numpy as np
from jax.extend.core import ClosedJaxpr, Jaxpr, JaxprEqn

#: Cross-device communication primitives. ``axis_index`` is free (no data
#: movement) and deliberately excluded.
COLLECTIVE_PRIMS = frozenset({
    "psum", "psum2", "pmax", "pmin", "ppermute", "pbroadcast",
    "all_gather", "all_to_all", "reduce_scatter", "pgather",
})

#: Primitives that merely wrap an inner jaxpr in the *same* iteration space
#: — a scan inside these is still a top-level scan of the artifact.
_WRAPPER_PRIMS = frozenset({
    "pjit", "jit", "closed_call", "core_call", "xla_call", "shard_map",
    "remat", "checkpoint", "remat2", "custom_jvp_call", "custom_vjp_call",
    "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr",
    "custom_vjp_call_custom_transpose",
})

#: Control-flow primitives that repeat or branch their body — a scan inside
#: them runs per iteration, not once per dispatch.
_LOOP_PRIMS = frozenset({"scan", "while", "cond"})


def _param_jaxprs(eqn: JaxprEqn) -> Iterator[Jaxpr]:
    """All sub-jaxprs an equation carries (scan/while/cond/pjit/...)."""
    for v in eqn.params.values():
        items = v if isinstance(v, (tuple, list)) else (v,)
        for x in items:
            if isinstance(x, ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, Jaxpr):
                yield x


def iter_eqns(jaxpr: Jaxpr, *, into_loops: bool = True) -> Iterator[JaxprEqn]:
    """Depth-first equation walk; ``into_loops=False`` stops at scan/while/
    cond bodies (but still descends pjit/shard_map wrappers)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if not into_loops and eqn.primitive.name in _LOOP_PRIMS:
            continue
        for sub in _param_jaxprs(eqn):
            yield from iter_eqns(sub, into_loops=into_loops)


def _as_jaxpr(x) -> Jaxpr:
    return x.jaxpr if isinstance(x, ClosedJaxpr) else x


def top_level_scans(jaxpr) -> list[JaxprEqn]:
    """Scan equations that execute exactly once per dispatch (descending
    through pjit/shard_map wrappers, stopping at loop bodies)."""
    return [e for e in iter_eqns(_as_jaxpr(jaxpr), into_loops=False)
            if e.primitive.name == "scan"]


def scan_length(eqn: JaxprEqn) -> Optional[int]:
    return eqn.params.get("length")


def driving_scans(jaxpr, length: int) -> list[JaxprEqn]:
    """Top-level scans whose trip count is the round/element count — the
    one-dispatch claim's "the k rounds ARE the scan" half."""
    return [e for e in top_level_scans(jaxpr) if scan_length(eqn=e) == length]


def count_whiles(jaxpr) -> int:
    return sum(1 for e in iter_eqns(_as_jaxpr(jaxpr))
               if e.primitive.name == "while")


def _aval_bytes(aval) -> int:
    try:
        return int(np.prod(aval.shape, dtype=np.int64)) * aval.dtype.itemsize
    except Exception:  # pragma: no cover — non-array avals
        return 0


@dataclasses.dataclass
class CollectiveCensus:
    """Static collective counts for one region of the artifact."""

    counts: Counter               # primitive name -> static eqn count
    max_operand_bytes: int        # largest single collective operand

    @property
    def total(self) -> int:
        return sum(self.counts.values())


def collective_census(jaxpr) -> CollectiveCensus:
    counts: Counter = Counter()
    max_bytes = 0
    for eqn in iter_eqns(_as_jaxpr(jaxpr)):
        if eqn.primitive.name in COLLECTIVE_PRIMS:
            counts[eqn.primitive.name] += 1
            for v in eqn.invars:
                max_bytes = max(max_bytes, _aval_bytes(v.aval))
    return CollectiveCensus(counts, max_bytes)


def scan_body(eqn: JaxprEqn) -> Jaxpr:
    return _as_jaxpr(eqn.params["jaxpr"])


@dataclasses.dataclass
class ScanStructure:
    top_scans: int                #: top-level scan count
    driving: int                  #: of those, trip count == rounds
    whiles: int                   #: while loops anywhere
    driving_body: Optional[Jaxpr]  #: first driving scan's body (census target)


def scan_structure(jaxpr, rounds: int) -> ScanStructure:
    tops = top_level_scans(jaxpr)
    driving = [e for e in tops if scan_length(e) == rounds]
    return ScanStructure(
        top_scans=len(tops), driving=len(driving), whiles=count_whiles(jaxpr),
        driving_body=scan_body(driving[0]) if driving else None)


# ---------------------------------------------------------------------------
# Donation — parsed from the lowered StableHLO entry signature.
# ---------------------------------------------------------------------------

_MAIN_SIG = re.compile(
    r"func\.func\s+(?:public\s+)?@main\((?P<args>.*?)\)\s*->", re.S)


@dataclasses.dataclass
class DonationTable:
    aliased: int        #: inputs carrying ``tf.aliasing_output`` (donated AND aliased)
    dropped: int        #: inputs carrying ``jax.buffer_donor`` (donated, NOT aliased)

    def ok(self, expected_aliased: int) -> bool:
        return self.aliased == expected_aliased and self.dropped == 0


def donation_audit(hlo_text: str) -> DonationTable:
    """Count donated-and-aliased vs donated-but-dropped entry arguments.

    jax marks an argument it could alias onto an output with
    ``tf.aliasing_output = N`` and one it was *asked* to donate but could
    not alias with ``jax.buffer_donor = true`` — the latter is the silent
    dropped-donation signature (XLA only warns at execution time).
    """
    m = _MAIN_SIG.search(hlo_text)
    if m is None:  # pragma: no cover — lowering always emits @main
        raise ValueError("no @main entry function in lowered module")
    sig = m.group("args")
    return DonationTable(
        aliased=len(re.findall(r"tf\.aliasing_output", sig)),
        dropped=len(re.findall(r"jax\.buffer_donor", sig)))


_ALIAS_ENTRY = re.compile(r"\{\d+[^}]*\}:\s*\(\d+,")


def resolve_deferred_donations(table: DonationTable,
                               lowered) -> DonationTable:
    """Re-judge ``jax.buffer_donor`` markers against the compiled executable.

    Single-device lowering decides aliasing up front (``tf.aliasing_output``
    in the entry signature). Under a multi-device mesh jax *defers* the
    decision instead: the StableHLO carries only ``jax.buffer_donor`` and
    XLA picks the aliasing after SPMD partitioning, so the marker alone is
    ambiguous — it reads identically for "aliased at compile time" and
    "dropped". Disambiguate by parsing the compiled module's
    ``input_output_alias`` table: every donor that landed there is a real
    alias; whatever the table does not cover stays dropped. Costs one
    compile, so callers should only reach for this when the cheap static
    pass reports deferred donors.
    """
    if table.dropped == 0:
        return table
    try:
        text = lowered.compile().as_text()
    except Exception:  # pragma: no cover — backend can't print: stay strict
        return table
    # the table lives on the HLO header line: ``input_output_alias={ {0}:
    # (0, {}, may-alias), … }``; entry keys ``{N}: (M,`` are unambiguous on
    # that line (layout suffixes like ``f32[8]{0}`` are never followed by a
    # colon), so count keys rather than brace-balance the nested braces
    m = re.search(r"input_output_alias=\{(?P<line>[^\n]*)", text)
    if m is None:
        return table
    entries = len(_ALIAS_ENTRY.findall(m.group("line")))
    # the compiled table covers tf.aliasing_output params too; only the
    # surplus beyond the statically-aliased count vouches for donors
    resolved = min(table.dropped, max(0, entries - table.aliased))
    return DonationTable(aliased=table.aliased + resolved,
                         dropped=table.dropped - resolved)


# ---------------------------------------------------------------------------
# Precision flow
# ---------------------------------------------------------------------------

_HALF = ("bfloat16", "float16")


@dataclasses.dataclass
class PrecisionReport:
    widens: list[tuple[str, int]]   #: (shape str, elems) of each flagged widen
    half_dots: int                  #: dot_generals consuming half operands

    def ok(self, *, require_half_dot: bool) -> bool:
        return not self.widens and (self.half_dots > 0
                                    or not require_half_dot)


def precision_flow(jaxpr, *, min_widen_elems: int) -> PrecisionReport:
    """Flag half→fp32 ``convert_element_type`` on big tensors.

    Widening an O(n)-sized accumulator (cache rows, psum payloads,
    trajectory scalars) is the declared exception; widening anything of
    distance-tile size means the artifact materialized a half tile and
    up-converted it — the exact traffic the compute/accum dtype split
    exists to avoid (the matmul widens for free via
    ``preferred_element_type``).

    Converts *inside* ``pallas_call`` kernel bodies are exempt: a kernel
    widens VMEM-resident tiles at register level (ordinary mixed-precision
    practice — the f32 tile never reaches HBM), so the rule governs only
    the artifact-level dataflow around kernels. Half-dtype ``dot_general``
    counting still descends into kernels — the proof that the payload
    reached the unit in half precision lives wherever the matmul does.
    """
    widens: list[tuple[str, int]] = []
    half_dots = 0

    def walk(j: Jaxpr, in_kernel: bool):
        nonlocal half_dots
        for eqn in j.eqns:
            name = eqn.primitive.name
            if name == "convert_element_type" and not in_kernel:
                src = eqn.invars[0].aval
                dst = str(eqn.params.get("new_dtype"))
                elems = int(np.prod(src.shape, dtype=np.int64)) \
                    if src.shape else 1
                if (str(src.dtype) in _HALF and dst == "float32"
                        and elems >= min_widen_elems):
                    widens.append((f"{src.dtype}{list(src.shape)}", elems))
            elif name == "dot_general":
                if any(str(v.aval.dtype) in _HALF for v in eqn.invars[:2]):
                    half_dots += 1
            for sub in _param_jaxprs(eqn):
                walk(sub, in_kernel or name == "pallas_call")

    walk(_as_jaxpr(jaxpr), False)
    return PrecisionReport(widens=widens, half_dots=half_dots)


# ---------------------------------------------------------------------------
# Trace + lower helper
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TracedArtifact:
    jaxpr: Any            # ClosedJaxpr
    hlo: str              # StableHLO text
    lowered: Any          # jax.stages.Lowered (for optional compile)
    #: donations stripped at lowering. A donated buffer XLA cannot alias is
    #: dropped with only a UserWarning ("Some donated buffers were not
    #: usable") on backends without buffer-donor support — the audit
    #: captures the warning so the silent path is machine-checked too.
    dropped_donations: int = 0


def trace_artifact(fn, args, kwargs) -> TracedArtifact:
    """Trace a jitted callable with abstract values and lower it once."""
    import warnings

    traced = fn.trace(*args, **kwargs)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        lowered = traced.lower()
    dropped = sum(1 for w in caught
                  if "donated buffers were not usable" in str(w.message))
    return TracedArtifact(jaxpr=traced.jaxpr, hlo=lowered.as_text(),
                          lowered=lowered, dropped_donations=dropped)


def memory_temp_bytes(lowered) -> Optional[int]:
    """Compiled temp-buffer bytes, or None where the backend reports none.

    This is the per-device *working set* beyond arguments/outputs — the
    number the analytic byte bound constrains: an artifact that
    materializes the full (n, m) distance matrix shows up here no matter
    how honest its jaxpr looks.
    """
    try:
        ma = lowered.compile().memory_analysis()
        return int(ma.temp_size_in_bytes) if ma is not None else None
    except Exception:
        return None
