"""Compile the main path's Pallas kernels for a described TPU v5e.

Interpret mode accepts layouts the chip's compiler (Mosaic) refuses, so each
kernel is lowered and compiled here for a v5e that is described, not
attached, at the deployment widths (d=128 lanes, fp32 and bf16), and the
compiled program must hold the kernel (``tpu_custom_call``). The multiset
programs compile through ``kernel_config`` at the paper's §V-A size, which
is where tile choices meet the chip's VMEM and lane-tiling limits.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and pytest-xdist workers all import every test file. Where it cannot be
described the fixture skips.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import EvalConfig
from repro.core.precision import BF16, FP32, POLICIES
from repro.kernels import marginal_gain as mg
from repro.kernels import ops

N, M, D, B = 8192, 512, 128, 4          # serving-size ground set, d in lanes
BLOCK = 256
PAPER = dict(n=50_000, l=5_000, k=10, d=100)   # configs/paper.py
GREEDY = dict(n=50_000, d=100)          # the Greedy cell's ground set
LAZY = dict(n=50_000, d=100, k=10, top_b=256)   # the CELF cell
POLICY = {"fp32": FP32, "bf16": BF16}
AFFINE = {"min": None, "max": (1.0, -0.5)}


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with JAX's persistent compilation cache off: a
    compile for a described chip is written to the cache but cannot be read
    back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compiled():
    """Compiled program text by case, so that the kernel-name tests read
    the programs the compile tests already built."""
    return {}


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _has_kernel(text: str, name: str) -> bool:
    """The compiled text holds the kernel ``name`` (``%name.1 = ...``)."""
    return re.search(rf"%{re.escape(name)}(\.\d+)? = ", text) is not None


def _gain_operands(sds, policy, b):
    dt = policy.compute_dtype
    return (sds((b, N, D), dt), sds((b, M, D), dt),
            sds((b, N, 1), jnp.float32))


@pytest.mark.parametrize("fold", ["min", "max"])
@pytest.mark.parametrize("pname", ["fp32", "bf16"])
@pytest.mark.parametrize("b", [1, B])
@pytest.mark.parametrize("kernel", ["gain_eval", "gain_update_eval"])
def test_gain_kernels_compile_for_v5e(one_chip, compiled, kernel, b, pname,
                                      fold):
    assert "tpu_custom_call" in _compile_gain(one_chip, compiled, kernel, b,
                                              pname, fold)


def _compile_gain(one_chip, compiled, kernel, b, pname, fold) -> str:
    key = ("gain", kernel, b, pname, fold)
    if key in compiled:
        return compiled[key]
    policy = POLICY[pname]
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    args = _gain_operands(sds, policy, b)
    if "update" in kernel:
        args += (sds((b, 1, D), policy.compute_dtype),
                 sds((b, 1, 1), jnp.float32))
    fn = functools.partial(
        getattr(mg, kernel), n_total=N, policy=policy, block_n=BLOCK,
        block_m=BLOCK, fold=fold, affine=AFFINE[fold])
    compiled[key] = _compiled_text(fn, *args)
    return compiled[key]


@pytest.mark.parametrize("b", [1, B])
def test_gain_update_compiles_at_greedy_cell_size(one_chip, b):
    """The fused gain kernel, through its padding wrapper, at the Greedy
    cell's size (bench/configs/paper_v_a_greedy.json: N = m = 50,000,
    d = 100 in 128 lanes, fp32) with the tile ``ops.gain_tile`` picks
    there, which is where the plan meets the chip's VMEM: one request
    (the cell's B = 1) and a bucket of B."""
    n, d = GREEDY["n"], GREEDY["d"]
    bn, bm = ops.gain_tile(n, n, ops._round_up(d, ops.LANE), FP32)
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    text = ops._fused_gain_update_padded.lower(
        sds((b, n, d), jnp.float32), sds((b, n, d), jnp.float32),
        sds((b, n), jnp.float32), sds((b, d), jnp.float32),
        sds((b,), jnp.float32), policy=FP32, interpret=False,
        rbf_gamma=None, n_total=n, block_n=bn, block_m=bm, fold="min",
        score_affine=None).compile().as_text()
    assert "tpu_custom_call" in text and _has_kernel(text, "gain_update_eval")


@pytest.mark.parametrize("m", [LAZY["top_b"], LAZY["n"]],
                         ids=["rescore", "seeding"])
def test_gain_eval_compiles_at_lazy_cell_size(one_chip, m):
    """The unfused gain kernel, through its padding wrapper, at the CELF
    cell's size (bench/configs/paper_v_a_lazy.json: N = 50,000, d = 100
    in 128 lanes, fp32, one request) and both of its candidate widths:
    a re-score trip's top_b = 256 and the bound seeding's m = N, each with
    the tile ``ops.gain_tile`` picks there."""
    n, d = LAZY["n"], LAZY["d"]
    bn, bm = ops.gain_tile(n, m, ops._round_up(d, ops.LANE), FP32)
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    text = ops._marginal_gain_padded.lower(
        sds((1, n, d), jnp.float32), sds((1, m, d), jnp.float32),
        sds((1, n), jnp.float32), policy=FP32, interpret=False,
        rbf_gamma=None, n_total=n, block_n=bn, block_m=bm, fold="min",
        score_affine=None).compile().as_text()
    assert "tpu_custom_call" in text and _has_kernel(text, "gain_eval")


def test_lazy_scan_compiles_at_lazy_cell_size(one_chip):
    """The whole one-dispatch CELF selection ``run_selection`` launches at
    the CELF cell's size, on the Pallas backend: its bound seeding and
    its while loop of top-256 re-score trips both run ``gain_eval``, and
    no fused kernel (lazy folds each winner once, outside any kernel)."""
    from repro.core import engine
    from repro.core.functions import FnSpec

    n, d = LAZY["n"], LAZY["d"]
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    text = engine._select_scan.lower(
        sds((1, n, d), jnp.float32), sds((1, n), jnp.float32),
        sds((1, n), jnp.float32), sds((1, 1, 0), jnp.int32),
        sds((1, d), jnp.float32), sds((1,), jnp.int32), fn=FnSpec(),
        kind="lazy", k=LAZY["k"], top_b=LAZY["top_b"],
        distance="sqeuclidean", policy_name="fp32",
        block_m=engine._device_block_m(n, n), backend="pallas",
        rbf_gamma=None, counter_key="tpu_compile_lazy",
    ).compile().as_text()
    kernels = re.findall(r"%(gain_eval|gain_update_eval)(?:\.\d+)? = ", text)
    assert sorted(kernels) == ["gain_eval", "gain_eval"]


@pytest.mark.parametrize("p", [1, 8])
def test_sieve_kernels_compile_for_v5e(one_chip, compiled, p):
    assert "tpu_custom_call" in _compile_sieve(one_chip, compiled, p)


def _compile_sieve(one_chip, compiled, p) -> str:
    key = ("sieve", p)
    if key in compiled:
        return compiled[key]
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    fn = functools.partial(mg.sieve_gain_eval, n_total=N, block_s=64,
                           block_n=512)
    compiled[key] = _compiled_text(
        fn, sds((p, 128, N), jnp.float32), sds((p, 1, N), jnp.float32))
    return compiled[key]


@pytest.mark.parametrize("mode,variant,pname", [
    ("fused", "flat", "fp32"), ("fused", "flat", "bf16"),
    ("fused", "loop", "fp32"), ("fused", "loop", "bf16"),
    ("two_pass", "loop", "fp32")])
def test_multiset_kernels_compile_at_paper_size(one_chip, compiled, mode,
                                                variant, pname):
    """fused_eval (flat and loop) and the paper's two-pass W kernel, with
    the tiles ``kernel_config`` picks at N=50k, l=5k, k=10, d=100."""
    assert "tpu_custom_call" in _compile_multiset(
        one_chip, compiled, mode, variant, pname, PAPER["k"])


@pytest.mark.parametrize("mode,variant,pname,k", [
    ("fused", "flat", "fp32", 45), ("two_pass", "loop", "fp32", 45),
    ("fused", "flat", "bf16", 45),
    ("fused", "loop", "fp32", 500), ("fused", "loop", "bf16", 500),
    ("two_pass", "loop", "fp32", 500)])
def test_multiset_kernels_compile_across_paper_k_sweep(one_chip, compiled,
                                                       mode, variant, pname,
                                                       k):
    """The paper sweeps k to 500 (configs/paper.py): there the S tile, not
    the distance tile, bounds Bl; the two-pass kernel's one-slice loop
    shrinks it to one sublane tile, and the fused loop variant tiles k in
    chunks instead. Flat at fp32 has no tile from k≈39 and runs as loop
    (``ops.multiset_tile``)."""
    assert "tpu_custom_call" in _compile_multiset(
        one_chip, compiled, mode, variant, pname, k)


def _compile_multiset(one_chip, compiled, mode, variant, pname, k) -> str:
    """The compiled text of the multiset program at the paper's size, with
    the variant and tile ``ops.multiset_tile`` picks."""
    n, l, d = PAPER["n"], PAPER["l"], PAPER["d"]
    policy = POLICY[pname]
    variant, cfgk = ops.multiset_tile(k, ops._round_up(d, ops.LANE), policy,
                                      l, n, mode, variant)
    key = ("multiset", mode, variant, pname, k)
    if key in compiled:
        return compiled[key]
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    lowered = ops._exemplar_eval_padded.lower(
        sds((n, d), jnp.float32), sds((l, k, d), jnp.float32),
        sds((l,), jnp.int32), sds((n,), jnp.float32), policy=policy,
        mode=mode, variant=variant, interpret=False, rbf_gamma=None,
        n_total=n, cfgk=cfgk)
    compiled[key] = lowered.compile().as_text()
    return compiled[key]


@pytest.mark.parametrize("mode,variant,k,name", [
    ("fused", "flat", 10, "exemplar_eval_flat"),
    ("fused", "flat", 500, "exemplar_eval_loop"),
    ("two_pass", "loop", 10, "exemplar_eval_two_pass")])
def test_multiset_kernel_names_at_paper_size(one_chip, compiled, mode,
                                             variant, k, name):
    """Each multiset kernel carries its own name into the compiled program,
    and so into the device trace: the flat variant at k=10, the loop
    variant that flat becomes at k=500, the paper's two-pass W kernel."""
    text = _compile_multiset(one_chip, compiled, mode, variant, "fp32", k)
    assert _has_kernel(text, name)
    others = {"exemplar_eval_flat", "exemplar_eval_loop",
              "exemplar_eval_two_pass"} - {name}
    assert not any(_has_kernel(text, o) for o in others)


@pytest.mark.parametrize("pname,k", [("fp32", 45), ("fp32", 500),
                                     ("bf16", 500)])
def test_loop_kernel_chunks_k_at_paper_size(one_chip, compiled, pname, k):
    """Past flat's VMEM limit the program runs ``exemplar_eval_loop`` on
    a lane-dense set tile, k tiled across the grid in chunks."""
    policy = POLICY[pname]
    variant, cfgk = ops.multiset_tile(k, ops._round_up(PAPER["d"], ops.LANE),
                                      policy, PAPER["l"], PAPER["n"],
                                      "fused", "flat")
    assert variant == "loop"
    assert cfgk.block_l % ops.LANE == 0 and cfgk.block_k < k
    text = _compile_multiset(one_chip, compiled, "fused", "flat", pname, k)
    assert _has_kernel(text, "exemplar_eval_loop")


@pytest.mark.parametrize("b", [1, B])
@pytest.mark.parametrize("kernel", ["gain_eval", "gain_update_eval"])
def test_gain_kernel_names(one_chip, compiled, kernel, b):
    """Each gain kernel is named after its function in the compiled
    program (the fp32 min-fold program of the compile test above), for
    one request and for B."""
    assert _has_kernel(_compile_gain(one_chip, compiled, kernel, b, "fp32",
                                     "min"), kernel)


@pytest.mark.parametrize("p", [1, 8])
def test_sieve_kernel_names(one_chip, compiled, p):
    assert _has_kernel(_compile_sieve(one_chip, compiled, p),
                       "sieve_gain_eval")


@pytest.mark.parametrize("pname", ["fp16", "fp16_strict"])
def test_pallas_backend_refuses_fp16(pname):
    """v5e's Mosaic has no float16 vector load: the compiled kernel backend
    refuses fp16 where the config or kernel call is built, naming bf16;
    the jnp backend and interpret mode keep it."""
    with pytest.raises(ValueError, match="bf16"):
        EvalConfig(backend="pallas", policy=pname)
    V = jnp.ones((16, 8), jnp.float32)
    with pytest.raises(ValueError, match="bf16"):
        ops.marginal_gain(V, V, jnp.zeros(16), policy=POLICIES[pname])
    EvalConfig(backend="jnp", policy=pname)
    EvalConfig(backend="pallas_interpret", policy=pname)
