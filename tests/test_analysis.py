"""Negative fixtures for the static-analysis pass itself.

Each checker must actually *detect* the defect class it exists for: a
deliberately-bad toy artifact per claim (extra dispatch structure, second
psum, dropped donation, fp32 payload leak, tracer-branch lint) asserted to
be flagged — plus the green half: a quick run over every registered
contract, and the named regression fixtures for the violations the auditor
surfaced in the real tree when it first ran (``precision.sq-norms-upcast``).
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import jaxpr_audit as ja
from repro.analysis.lint import lint_source


def _artifact(fn, *args, **kwargs):
    return ja.trace_artifact(fn, args, kwargs)


# ---------------------------------------------------------------------------
# scan structure: the one-dispatch claim
# ---------------------------------------------------------------------------


def test_extra_driving_scan_detected():
    """A second sequential k-scan (a re-dispatched greedy loop) is caught."""

    @jax.jit
    def good(x):
        return jax.lax.scan(lambda c, _: (c + 1.0, c), x, None, length=5)

    @jax.jit
    def bad(x):
        c, ys = jax.lax.scan(lambda c, _: (c + 1.0, c), x, None, length=5)
        c, _ = jax.lax.scan(lambda c, _: (c * 2.0, c), c, None, length=5)
        return c, ys

    x = jax.ShapeDtypeStruct((), np.float32)
    ok = ja.scan_structure(_artifact(good, x).jaxpr, rounds=5)
    assert ok.top_scans == 1 and ok.driving == 1
    leak = ja.scan_structure(_artifact(bad, x).jaxpr, rounds=5)
    assert leak.top_scans == 2 and leak.driving == 2


def test_unrolled_loop_has_no_driving_scan():
    """A Python-unrolled loop (k dispatgarbage baked into the artifact)
    shows zero driving scans — the structure check fails it."""

    @jax.jit
    def unrolled(x):
        for _ in range(5):
            x = x + 1.0
        return x

    ss = ja.scan_structure(
        _artifact(unrolled, jax.ShapeDtypeStruct((), np.float32)).jaxpr,
        rounds=5)
    assert ss.top_scans == 0 and ss.driving == 0


def test_scan_inside_loop_is_not_top_level():
    """A scan nested in another loop body runs per iteration — it must not
    count as a top-level (once-per-dispatch) scan."""

    @jax.jit
    def nested(x):
        def outer(c, _):
            c2, _ = jax.lax.scan(lambda a, _: (a + 1.0, a), c, None, length=3)
            return c2, c2
        return jax.lax.scan(outer, x, None, length=7)

    jaxpr = _artifact(nested, jax.ShapeDtypeStruct((), np.float32)).jaxpr
    tops = ja.top_level_scans(jaxpr)
    assert [ja.scan_length(e) for e in tops] == [7]


# ---------------------------------------------------------------------------
# collectives: the one-psum claim
# ---------------------------------------------------------------------------


def _shmap(fn):
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((jax.device_count(),), ("data",))
    return jax.jit(shard_map(fn, mesh=mesh, in_specs=(P("data"),),
                             out_specs=P(), check_vma=False))


def test_second_psum_detected():
    def one(x):
        return jax.lax.psum(jnp.sum(x), "data")

    def two(x):
        s = jax.lax.psum(jnp.sum(x), "data")
        return s + jax.lax.psum(jnp.max(x), "data")

    x = jax.ShapeDtypeStruct((8,), np.float32)
    assert ja.collective_census(_artifact(_shmap(one), x).jaxpr).total == 1
    census = ja.collective_census(_artifact(_shmap(two), x).jaxpr)
    assert census.counts["psum"] == 2


def test_oversized_collective_operand_detected():
    """An O(n·d)-sized psum payload busts the byte bound the contracts pin."""

    def big(x):
        return jax.lax.psum(x[None, :] * jnp.ones((64, 1)), "data")

    x = jax.ShapeDtypeStruct((8,), np.float32)
    census = ja.collective_census(_artifact(_shmap(big), x).jaxpr)
    # the psum operand is the LOCAL (64, n/p) block — 8/p rows per shard
    assert census.max_operand_bytes >= 64 * (8 // jax.device_count()) * 4


def test_per_tenant_psum_migration_detected():
    """The batched-sharded O(B·m) budget: stacking B tenants' partials into
    ONE (B, m+1) psum keeps the collective count at 1. The regression this
    fixture pins is the per-tenant migration — a Python loop (or unrolled
    vmap) issuing B separate psums — which the census must report as B
    collectives, busting the batched contracts' count budget."""
    B, m = 4, 6

    def stacked(parts):                     # parts: (B, n_loc) per shard
        g = parts[:, :m]
        stat = jnp.sum(parts, axis=1)
        return jax.lax.psum(
            jnp.concatenate([g, stat[:, None]], axis=1), "data")

    def per_tenant(parts):
        out = []
        for b in range(B):                  # the migration under test
            g = parts[b, :m]
            stat = jnp.sum(parts[b])
            out.append(jax.lax.psum(
                jnp.concatenate([g, stat[None]]), "data"))
        return jnp.stack(out)

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = jax.make_mesh((jax.device_count(),), ("data",))

    def _sh(fn):
        return jax.jit(shard_map(fn, mesh=mesh, in_specs=(P(None, "data"),),
                                 out_specs=P(None, None), check_vma=False))

    x = jax.ShapeDtypeStruct((B, 8 * jax.device_count()), np.float32)
    good = ja.collective_census(_artifact(_sh(stacked), x).jaxpr)
    assert good.counts["psum"] == 1
    assert good.max_operand_bytes == B * (m + 1) * 4   # O(B·m), one payload
    bad = ja.collective_census(_artifact(_sh(per_tenant), x).jaxpr)
    assert bad.counts["psum"] == B


def test_psum_inside_scan_body_censused_per_region():
    """The per-round budget censuses the driving scan's BODY, catching a
    collective that moved from per-dispatch to per-round."""

    def per_round(x):
        def step(c, _):
            return c + jax.lax.psum(jnp.sum(x), "data"), c
        return jax.lax.scan(step, 0.0, None, length=5)

    jaxpr = _artifact(_shmap(per_round),
                      jax.ShapeDtypeStruct((8,), np.float32)).jaxpr
    ss = ja.scan_structure(jaxpr, rounds=5)
    assert ss.driving == 1
    assert ja.collective_census(ss.driving_body).total == 1


# ---------------------------------------------------------------------------
# donation: aliased vs silently dropped
# ---------------------------------------------------------------------------


def test_donation_aliased_and_dropped_detected():
    @partial(jax.jit, donate_argnums=(0,))
    def aliased(seed, x):
        return seed * 2.0 + x          # same shape/dtype: aliases onto out

    @partial(jax.jit, donate_argnums=(0,))
    def dropped(seed, x):
        # output dtype differs from the donated buffer: XLA cannot alias it
        # and silently drops the donation (warns only at run time)
        return (seed + x).astype(jnp.bfloat16)

    s = jax.ShapeDtypeStruct((16,), np.float32)
    good_art = _artifact(aliased, s, s)
    good = ja.donation_audit(good_art.hlo)
    assert good.aliased == 1 and good.dropped == 0
    assert good_art.dropped_donations == 0
    bad_art = _artifact(dropped, s, s)
    bad = ja.donation_audit(bad_art.hlo)
    assert bad.aliased == 0
    # CPU strips the unusable donation at lowering with only a warning (no
    # jax.buffer_donor marker); the artifact capture turns it into a count
    assert bad.dropped + bad_art.dropped_donations == 1
    assert not bad.ok(expected_aliased=1)


def test_deferred_donation_resolved_against_compiled_alias_table():
    """Multi-device lowering leaves only ``jax.buffer_donor`` markers and
    lets XLA pick the aliasing after SPMD partitioning; the resolver must
    credit exactly the donors the compiled ``input_output_alias`` table
    covers and keep the rest dropped."""
    header = ("HloModule jit_run, is_scheduled=true, "
              "input_output_alias={ {0}: (0, {}, may-alias), "
              "{1}: (1, {}, may-alias) }, entry_computation_layout="
              "{(f32[14,24]{1,0}, s32[14]{0})->(f32[14,24]{1,0})}, "
              "num_partitions=2\n\n%body {\n}\n")

    class _Compiled:
        def as_text(self):
            return header

    class _Lowered:
        def compile(self):
            return _Compiled()

    both = ja.resolve_deferred_donations(
        ja.DonationTable(aliased=0, dropped=2), _Lowered())
    assert both.aliased == 2 and both.dropped == 0
    # a donor the compiled table does not cover stays dropped
    partial_ = ja.resolve_deferred_donations(
        ja.DonationTable(aliased=0, dropped=3), _Lowered())
    assert partial_.aliased == 2 and partial_.dropped == 1
    # statically-aliased params already own their table entries: no
    # double-credit for deferred donors
    mixed = ja.resolve_deferred_donations(
        ja.DonationTable(aliased=2, dropped=1), _Lowered())
    assert mixed.aliased == 2 and mixed.dropped == 1
    # nothing deferred → no compile, table unchanged
    clean = ja.resolve_deferred_donations(
        ja.DonationTable(aliased=1, dropped=0), lowered=None)
    assert clean.aliased == 1 and clean.dropped == 0


def test_engine_seed_donation_live():
    """satellite fixture: ``seed.is_deleted()`` matches the aliasing table
    (the donated buffer is consumed; the function's resident seed is not)."""
    from repro.analysis.registry import _rt_donation_live

    ok, detail = _rt_donation_live()
    assert ok, detail


# ---------------------------------------------------------------------------
# precision flow
# ---------------------------------------------------------------------------


def test_fp32_leak_detected():
    @jax.jit
    def leak(v):
        vf = v.astype(jnp.float32)         # payload-sized widen: the bug
        return jnp.sum(vf * vf, axis=-1)

    rep = ja.precision_flow(
        _artifact(leak, jax.ShapeDtypeStruct((48, 8), jnp.bfloat16)).jaxpr,
        min_widen_elems=112)
    assert rep.widens and rep.widens[0][1] == 384
    assert not rep.ok(require_half_dot=True)


def test_small_accumulator_widen_allowed():
    @jax.jit
    def accum(v, g):
        d = jax.lax.dot_general(v, v, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        return jnp.sum(d) + g.astype(jnp.float32).sum()   # (8,) scalar-ish

    rep = ja.precision_flow(
        _artifact(accum, jax.ShapeDtypeStruct((48, 8), jnp.bfloat16),
                  jax.ShapeDtypeStruct((8,), jnp.bfloat16)).jaxpr,
        min_widen_elems=112)
    assert rep.ok(require_half_dot=True)
    assert rep.half_dots == 1


def test_sq_norms_upcast_fixture():
    """precision.sq-norms-upcast: the violation the auditor surfaced in the
    real tree — ``sq_norms`` materialized an fp32 copy of the bf16 payload.
    The old pattern stays detectable; the fixed pairwise stays clean."""
    from repro.core.distances import sqeuclidean_pairwise
    from repro.core.precision import resolve

    @jax.jit
    def old_pattern(X):                    # pre-fix sq_norms body
        Xa = X.astype(jnp.float32)
        return jnp.sum(Xa * Xa, axis=-1)

    bf16 = jax.ShapeDtypeStruct((48, 8), jnp.bfloat16)
    assert ja.precision_flow(_artifact(old_pattern, bf16).jaxpr,
                             min_widen_elems=112).widens

    @jax.jit
    def pairwise(X, Y):
        return sqeuclidean_pairwise(X, Y, resolve("bf16"))

    f32 = jax.ShapeDtypeStruct((48, 8), np.float32)
    rep = ja.precision_flow(_artifact(pairwise, f32, f32).jaxpr,
                            min_widen_elems=112)
    assert not rep.widens and rep.half_dots >= 1


# ---------------------------------------------------------------------------
# lint negative fixtures
# ---------------------------------------------------------------------------


def test_tracer_branch_detected():
    src = """
import jax

def step(carry, x):
    if x > 0:
        carry = carry + x
    return carry, x

def run(xs):
    return jax.lax.scan(step, 0.0, xs)
"""
    rules = {f.rule for f in lint_source(src)}
    assert "tracer-branch" in rules


def test_tracer_cast_detected():
    src = """
import jax

def step(carry, x):
    return carry + float(x), x

def run(xs):
    return jax.lax.scan(step, 0.0, xs)
"""
    assert any(f.rule == "tracer-cast" for f in lint_source(src))


def test_float_equality_detected_and_suppressable():
    src = "def f(x):\n    return x == 1.5\n"
    assert any(f.rule == "float-eq" for f in lint_source(src))
    ok = "def f(x):\n    return x == 1.5  # lint: allow(float-eq)\n"
    assert not lint_source(ok)


def test_np_on_traced_arg_detected():
    src = """
import jax
import numpy as np

@jax.jit
def f(x):
    return np.sum(x)
"""
    assert any(f.rule == "np-in-jit" for f in lint_source(src))


def test_missing_static_default_detected():
    src = """
import jax
from functools import partial

@partial(jax.jit, static_argnames=("mode",))
def f(x, mode="fast", normalize=True):
    return x
"""
    findings = [f for f in lint_source(src) if f.rule == "missing-static"]
    assert len(findings) == 1 and "normalize" in findings[0].message


def test_clean_scan_body_not_flagged():
    src = """
import jax
import jax.numpy as jnp

def step(carry, x):
    branch = jnp.where(x > 0, carry + x, carry)
    return branch, x

def run(xs):
    return jax.lax.scan(step, 0.0, xs)
"""
    assert not lint_source(src)


def test_repro_tree_is_lint_clean():
    from pathlib import Path

    import repro.analysis
    from repro.analysis.lint import lint_tree

    findings = lint_tree(Path(repro.analysis.__file__).parents[1])
    assert not findings, "\n".join(map(str, findings))


# ---------------------------------------------------------------------------
# the green half: every registered contract audits clean (quick grid)
# ---------------------------------------------------------------------------


def test_registered_contracts_audit_green():
    from repro.analysis import report as rep
    from repro.analysis.contracts import CONTRACTS
    from repro.analysis.registry import build_cases
    from repro.core import distributed, engine, service, streaming  # noqa: F401

    assert len(CONTRACTS) >= 7
    cases = build_cases(quick=True)
    covered = {c.contract for c in cases}
    for name, c in CONTRACTS.items():
        if not c.extra.get("runtime_only"):
            assert name in covered, f"contract {name} has no audit case"
    for case in cases:
        result = rep.evaluate_case(case)
        assert result.ok, (
            f"{result.label}: " + "; ".join(map(str, result.violations)))
