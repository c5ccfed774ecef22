"""Floating-point precision policies for submodular evaluation.

The paper studies FP16 vs FP32 evaluation on GPUs (§V-B). On TPU the native
low-precision format is bfloat16, so the framework exposes three policies and
always accumulates Gram-matrix contractions in float32
(``preferred_element_type``), which is strictly more accurate than the paper's
all-FP16 path while keeping the low-precision memory/bandwidth benefits.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """Compute/accumulate dtype pair for distance evaluation.

    Attributes:
      name: human-readable policy name.
      compute_dtype: dtype in which payload (V, S) is stored and multiplied.
      accum_dtype: dtype for contraction accumulation and reductions.
    """

    name: str
    compute_dtype: jnp.dtype
    accum_dtype: jnp.dtype

    @property
    def itemsize(self) -> int:
        return jnp.dtype(self.compute_dtype).itemsize


FP32 = PrecisionPolicy("fp32", jnp.float32, jnp.float32)
BF16 = PrecisionPolicy("bf16", jnp.bfloat16, jnp.float32)
FP16 = PrecisionPolicy("fp16", jnp.float16, jnp.float32)
# Paper-faithful FP16: accumulate in fp16 as well (the CUDA kernel's native path).
FP16_STRICT = PrecisionPolicy("fp16_strict", jnp.float16, jnp.float16)

POLICIES = {p.name: p for p in (FP32, BF16, FP16, FP16_STRICT)}


def matmul_precision(dtype):
    """Contraction precision for ``dtype`` operands.

    On TPU a float32 (or float16) matmul at DEFAULT precision rounds its
    operands to bfloat16 for one MXU pass; HIGHEST keeps them whole, so the
    fp32 and fp16 policies compute what they name, in XLA and in the Pallas
    kernels alike. bfloat16 operands are exact in one pass. CPU backends
    ignore the setting.
    """
    if jnp.dtype(dtype) == jnp.bfloat16:
        return None
    return jax.lax.Precision.HIGHEST


def check_kernel_policy(policy: PrecisionPolicy) -> None:
    """Refuse a policy the Pallas TPU kernels cannot run.

    Mosaic on TPU v5e has no float16 vector load, so ``fp16`` and
    ``fp16_strict`` exist only on the ``jnp`` backend. Raised when a config
    or kernel call is built, never as a silent fall back to ``jnp``.
    """
    if jnp.dtype(policy.compute_dtype) == jnp.float16:
        raise ValueError(
            f"precision policy {policy.name!r} stores float16, which the "
            f"Pallas TPU kernels cannot load; use policy 'bf16' for half "
            f"precision on the pallas backend, or backend 'jnp' for fp16")


def resolve(policy: "str | PrecisionPolicy") -> PrecisionPolicy:
    if isinstance(policy, PrecisionPolicy):
        return policy
    try:
        return POLICIES[policy]
    except KeyError as e:
        raise ValueError(
            f"unknown precision policy {policy!r}; options: {sorted(POLICIES)}"
        ) from e
