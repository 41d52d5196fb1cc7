"""Blocked GEMM Pallas kernel for TPU.

Grid (m, n, k), accumulating in the output window, which stays resident
across k.  Block geometry comes from the Covenant tiler
(``tiling.gemm_blocks``), so the paper's Algorithm-1 machinery literally
chooses the ``BlockSpec``s, and the scoped VMEM limit the kernel asks for
is the one the tiler budgets against (``TPU_V5E["vmem_limit_bytes"]``).
Supports bf16/f32 -> f32 and s8 -> s32 (the paper's INT8-in / INT32-out
regime, D3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.targets import TPU_V5E


def _matmul_kernel(a_ref, b_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                          preferred_element_type=o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def matmul(a: jax.Array, b: jax.Array, *, block_m: int, block_n: int,
           block_k: int, interpret: bool = False) -> jax.Array:
    """C[M,N] = A[M,K] @ B[K,N].  Dims must be divisible by the block sizes
    (ops.py pads); accumulation is f32 for float inputs, i32 for int8."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2, (a.shape, b.shape)
    assert m % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        (m, n, k), (block_m, block_n, block_k))
    acc_dtype = jnp.int32 if jnp.issubdtype(a.dtype, jnp.integer) else jnp.float32
    return pl.pallas_call(
        _matmul_kernel,
        name="matmul",
        grid=(m // block_m, n // block_n, k // block_k),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), acc_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=TPU_V5E["vmem_limit_bytes"]),
        interpret=interpret,
    )(a, b)


__all__ = ["matmul"]
