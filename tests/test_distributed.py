"""Distributed correctness on an 8-device host mesh (subprocess tests).

Covers: sharded multiset evaluation == single-device, distributed greedy ==
local greedy, error-feedback int8 psum, bf16 psum, and the sharding-rule
fallback logic.
"""
import numpy as np
import pytest

from tests.conftest import run_with_devices


def test_distributed_eval_matches_local():
    out = run_with_devices("""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.core import EvalConfig, evaluate_multiset, pack_sets
        from repro.core.distributed import (make_distributed_eval,
                                            shard_ground_set)
        from repro.core.evaluator import e0_distances
        from jax.sharding import NamedSharding, PartitionSpec as P

        rng = np.random.default_rng(0)
        V = jnp.asarray((rng.normal(size=(256, 32)) + 2).astype(np.float32))
        sets = [np.asarray(V[rng.choice(256, size=5, replace=False)])
                for _ in range(17)]
        pk = pack_sets(sets)
        local = np.asarray(evaluate_multiset(V, pk, EvalConfig()))

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        V_sh = shard_ground_set(V, mesh)
        d_e0 = e0_distances(V, None, "sqeuclidean")
        d_e0_sh = jax.device_put(d_e0, NamedSharding(mesh, P("data")))
        fn = make_distributed_eval(mesh, EvalConfig())
        dist = np.asarray(fn(V_sh, pk.data, pk.lengths, d_e0_sh))
        np.testing.assert_allclose(dist, local, atol=1e-5)
        print("DIST_EVAL_OK")
    """)
    assert "DIST_EVAL_OK" in out


def test_distributed_greedy_matches_local():
    out = run_with_devices("""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.core import EvalConfig, ExemplarClustering, greedy
        from repro.core.distributed import distributed_greedy

        rng = np.random.default_rng(1)
        V = jnp.asarray((rng.normal(size=(128, 16)) + 2).astype(np.float32))
        local = greedy(ExemplarClustering(V), 5)
        mesh = jax.make_mesh((8,), ("data",))
        idx, val = distributed_greedy(mesh, V, 5)
        assert idx == local.indices, (idx, local.indices)
        assert abs(val - local.value) < 1e-4
        print("DIST_GREEDY_OK")
    """)
    assert "DIST_GREEDY_OK" in out


def test_ef_int8_psum_error_feedback():
    out = run_with_devices("""
        import jax, numpy as np
        import jax.numpy as jnp
        from functools import partial
        from jax import shard_map
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import ef_int8_psum, bf16_psum

        mesh = jax.make_mesh((8,), ("data",))
        x = jnp.asarray(np.random.default_rng(2).normal(
            size=(8, 64)).astype(np.float32))
        exact = np.asarray(x).sum(0)

        @partial(shard_map, mesh=mesh, in_specs=(P("data"), P("data")),
                 out_specs=(P("data"), P("data")), check_vma=False)
        def reduce_once(xs, err):
            y, e = ef_int8_psum(xs[0], err[0], "data")
            return y[None], e[None]

        err = jnp.zeros_like(x)
        y, err = reduce_once(x, err)
        got1 = np.asarray(y)[0]
        rel1 = np.abs(got1 - exact).max() / np.abs(exact).max()
        assert rel1 < 0.05, rel1  # one-shot int8 error is bounded

        # error feedback: repeated reduction of the SAME x converges —
        # average of T steps approaches the exact sum
        acc = np.zeros_like(exact)
        err = jnp.zeros_like(x)
        for t in range(20):
            y, err = reduce_once(x, err)
            acc += np.asarray(y)[0]
        relT = np.abs(acc / 20 - exact).max() / np.abs(exact).max()
        assert relT < rel1 / 2, (relT, rel1)

        @partial(shard_map, mesh=mesh, in_specs=P("data"),
                 out_specs=P("data"), check_vma=False)
        def reduce_bf16(xs):
            return bf16_psum(xs[0], "data")[None]

        yb = np.asarray(reduce_bf16(x))[0]
        assert np.abs(yb - exact).max() / np.abs(exact).max() < 0.02
        print("COMPRESS_OK")
    """)
    assert "COMPRESS_OK" in out


def test_sharding_rules_fallback():
    out = run_with_devices("""
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.distributed.sharding import MeshRules

        mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        rules = MeshRules.for_mesh(mesh)
        # kv heads=3 not divisible by model=2 → falls through to head_dim
        spec = rules.spec((8, 128, 3, 64),
                          (("batch",), (None,), ("tp",), (None, "tp")))
        assert spec == P(("pod", "data"), None, None, "model"), spec
        # divisible case: heads take the model axis, head_dim stays unsharded
        spec2 = rules.spec((8, 128, 4, 64),
                           (("batch",), (None,), ("tp",), (None, "tp")))
        assert spec2 == P(("pod", "data"), None, "model", None), spec2
        # batch=1 (long-context decode): seq grabs the data axes instead
        spec3 = rules.spec((1, 4096, 512),
                           (("batch",), ("sp",), (None,)))
        assert spec3 == P(None, "data", None), spec3
        # vocab not divisible → embedding falls back to d_model sharding
        spec4 = rules.spec((49155, 1536), (("tp",), ("fsdp",)))
        assert spec4 == P(None, ("pod", "data")), spec4
        print("RULES_OK")
    """)
    assert "RULES_OK" in out


def test_multipod_mesh_shapes():
    out = run_with_devices("""
        import jax
        from repro.launch.mesh import make_production_mesh
        m1 = make_production_mesh()
        assert m1.shape == {"data": 16, "model": 16}
        m2 = make_production_mesh(multi_pod=True)
        assert m2.shape == {"pod": 2, "data": 16, "model": 16}
        print("MESH_OK")
    """, n_devices=512)
    assert "MESH_OK" in out


def test_moe_ep_matches_dense():
    """shard_map a2a expert parallelism == dense MoE (no-drop capacity)."""
    out = run_with_devices("""
        import dataclasses
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.configs import get_reduced_config
        from repro.distributed.sharding import MeshRules
        from repro.models import layers as L
        from repro.models.moe_ep import moe_ep, ep_applicable

        cfg = get_reduced_config("qwen3-moe-30b-a3b")
        cfg = dataclasses.replace(cfg, moe_capacity=8.0)
        p_leaf = L.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
        p = jax.tree.map(lambda l: l.value, p_leaf,
                         is_leaf=lambda x: hasattr(x, "dims"))
        B, S = 8, 16
        x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))
        dense = L.moe(p, cfg, x, cfg.act, rules=None)
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        rules = MeshRules.for_mesh(mesh)
        assert ep_applicable(cfg, rules, B, S)
        ep = jax.jit(lambda x: moe_ep(p, cfg, x, cfg.act, rules))(x)
        err = float(jnp.max(jnp.abs(dense - ep)))
        assert err < 1e-4, err
        print("MOE_EP_OK")
    """)
    assert "MOE_EP_OK" in out


def test_flash_decoding_matches_full_forward():
    """Seq-sharded KV decode (flash-decoding) == full forward, ring caches."""
    out = run_with_devices("""
        import jax, numpy as np, jax.numpy as jnp
        from repro.configs import get_reduced_config
        from repro.distributed.sharding import MeshRules
        from repro.launch.mesh import make_mesh
        from repro.models.model import init_model, forward

        cfg = get_reduced_config("gemma3-1b")  # kv=1 → seqshard on 4-way TP
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = MeshRules.for_mesh(mesh)
        params, _ = init_model(cfg, jax.random.PRNGKey(0))
        B, S, PRE = 2, 24, 4   # window 16 → ring wraps during decode
        tokens = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                                    cfg.vocab_size)
        full, _ = forward(params, cfg, {"tokens": tokens}, mode="train",
                          remat=False)
        _, caches = forward(params, cfg, {"tokens": tokens[:, :PRE]},
                            mode="prefill", cache_len=S, remat=False)

        @jax.jit   # ONE compile: pos is traced
        def step(tok, caches, pos):
            return forward(params, cfg, {"tokens": tok}, mode="decode",
                           caches=caches, pos_offset=pos, rules=rules,
                           remat=False)

        with mesh:
            errs = []
            for pos in range(PRE, S):
                lg, caches = step(tokens[:, pos:pos+1], caches,
                                  jnp.asarray(pos, jnp.int32))
                errs.append(float(jnp.max(jnp.abs(lg[:, 0] - full[:, pos]))))
        assert max(errs) < 5e-4, max(errs)
        print("FLASHDEC_OK")
    """)
    assert "FLASHDEC_OK" in out
