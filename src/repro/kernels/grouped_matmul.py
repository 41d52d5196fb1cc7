"""Grouped GEMM Pallas kernel for TPU: one weight matrix per group.

``x_sorted`` holds rows grouped by expert, each group padded with zero rows
to a multiple of ``block_m``; ``group_offsets[g]`` is the first row of group
g and ``group_offsets[-1]`` the end of the last.  Row block i is multiplied
by its own group's weights, ``w[g]``: the group of each row block is a
scalar-prefetched table, read by the weight BlockSpec's index map, so the
DMA fetches that group's weight block and no other.  A group of no rows
owns no row block and its weights are never read; a group of more rows
than ``block_m`` spans several row blocks.

``x_sorted`` has a static number of rows, enough for the largest number of
row blocks the routing can make.  The row blocks past the last group's end
compute nothing, and their index maps repeat the last real block's
indices, so that the pipeline fetches no operand and writes no output for
them.  Their output rows are left unwritten: only rows below
``group_offsets[-1]`` hold results.

Grid (n, row block, k), k innermost, accumulating in the resident output
window as ``matmul`` does.  Block geometry comes from the Covenant tiler
(``tiling.grouped_gemm_blocks``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.targets import TPU_V5E


def _grouped_kernel(group_ref, nblocks_ref, x_ref, w_ref, o_ref):
    i, kk = pl.program_id(1), pl.program_id(2)

    @pl.when(i < nblocks_ref[0])
    def _compute():
        @pl.when(kk == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        o_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                              preferred_element_type=o_ref.dtype)


def _block_groups(group_offsets: jax.Array, n_blocks: int,
                 block_m: int) -> tuple[jax.Array, jax.Array]:
    """The group of each of ``n_blocks`` row blocks and the number of row
    blocks that hold rows.  Blocks past the last group's end take the last
    real block's group (the last group when no group has rows)."""
    nb = (group_offsets[-1] // block_m).astype(jnp.int32)
    starts = jnp.arange(n_blocks, dtype=jnp.int32) * block_m
    last = jnp.maximum(nb - 1, 0) * block_m
    starts = jnp.minimum(starts, last)
    # the last group starting at or before the block: empty groups share
    # their start with the next group, which holds the block
    group = jnp.searchsorted(group_offsets[1:], starts, side="right")
    return jnp.minimum(group, group_offsets.shape[0] - 2).astype(jnp.int32), \
        nb.reshape(1)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n", "block_k",
                                             "interpret"))
def grouped_matmul(x_sorted: jax.Array, w: jax.Array,
                   group_offsets: jax.Array, *, block_m: int, block_n: int,
                   block_k: int, interpret: bool = False) -> jax.Array:
    """out[r] = x_sorted[r] @ w[g] for each row r of group g.

    x_sorted (rows, k) bf16, w (groups, k, n) bf16, group_offsets
    (groups + 1,) int32, each a multiple of ``block_m``.  Returns (rows, n)
    f32.  rows, n and k must be multiples of the blocks (ops.py pads)."""
    rows, k = x_sorted.shape
    groups, k2, n = w.shape
    assert k == k2, (x_sorted.shape, w.shape)
    assert rows % block_m == 0 and n % block_n == 0 and k % block_k == 0, (
        (rows, n, k), (block_m, block_n, block_k))
    n_blocks, nk = rows // block_m, k // block_k
    with jax.named_scope("layout"):
        group, nb = _block_groups(group_offsets, n_blocks, block_m)

    def row_block(i, nb):
        return jnp.where(i < nb[0], i, jnp.maximum(nb[0] - 1, 0))

    def k_block(i, kk, nb):
        return jnp.where(i < nb[0], kk, nk - 1)

    return pl.pallas_call(
        _grouped_kernel,
        name="grouped_matmul",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // block_n, n_blocks, nk),
            in_specs=[
                pl.BlockSpec((block_m, block_k), lambda j, i, kk, grp, nb: (
                    row_block(i, nb), k_block(i, kk, nb))),
                pl.BlockSpec((pl.Squeezed(), block_k, block_n),
                             lambda j, i, kk, grp, nb: (
                                 grp[i], k_block(i, kk, nb), j)),
            ],
            out_specs=pl.BlockSpec((block_m, block_n),
                                   lambda j, i, kk, grp, nb: (
                                       row_block(i, nb), j)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=TPU_V5E["vmem_limit_bytes"]),
        interpret=interpret,
    )(group, nb, x_sorted, w)


__all__ = ["grouped_matmul"]
