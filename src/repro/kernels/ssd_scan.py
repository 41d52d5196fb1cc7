"""Mamba2 SSD (state-space duality) chunked scan — Pallas kernel.

The SSD dual form splits the sequence into chunks of length L:

* intra-chunk (quadratic, MXU-bound):  Y_intra = (C B^T ⊙ Γ) X
* chunk states (GEMM):                 S_c     = (B ⊙ γ_end)^T X
* inter-chunk (tiny recurrence):       H_c     = exp(ΔA_c) H_{c-1} + S_c
* state -> output (GEMM):              Y_inter = γ_start ⊙ (C H_{c-1})

The Pallas kernel runs every stage: it walks each head's chunks in order
(the grid's inner, sequential axis) and carries H in VMEM, so per
(batch·head, chunk) grid cell it computes the chunk's cumsum of dt·A and
its decays, Y = Y_intra + Y_inter and the next H — the paper's
multi-compute-node schedule (MXU for the GEMMs, VPU for the decay masks)
on one VMEM-resident block.  The wrapper only lays out dt and dt·A as
lane-dense rows.

Shapes (head-batched): x (BH, S, P), dt (BH, S), B,C (BH, S, N), A (BH,).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_chunk_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, *refs, has_init):
    """One (bh, chunk) cell: the chunk's output and the state after it.

    ``dt`` and ``a`` = dt·A are rows (1, L).  The state window ``h`` stays
    resident over the chunk axis: at the first chunk it is set from
    ``init`` (or zeros), then each chunk reads the state entering it and
    leaves the state after it, the final state at the last chunk.  Only
    2-D ops, since Mosaic lowers no 1-D cumsum and no lane slice: the
    cumsum is a masked lane reduction."""
    init_ref, y_ref, h_ref = refs if has_init else (None, *refs)

    @pl.when(pl.program_id(1) == 0)
    def _():
        h_ref[0] = (init_ref[0].astype(jnp.float32) if has_init
                    else jnp.zeros(h_ref.shape[1:], jnp.float32))

    x = x_ref[0].astype(jnp.float32)      # (L, P)
    dt = dt_ref[0].astype(jnp.float32)    # (1, L)
    a = a_ref[0]                          # (1, L)
    bmat = b_ref[0].astype(jnp.float32)   # (L, N)
    cmat = c_ref[0].astype(jnp.float32)   # (L, N)
    L = x.shape[0]
    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    causal = jj <= ii
    # inclusive cumsum of dt·A as a column, and the same values as a row,
    # picked off the diagonal; the chunk's total log-decay
    cum = jnp.sum(jnp.where(causal, a, 0.0), axis=1, keepdims=True)
    cum_t = jnp.sum(jnp.where(ii == jj, cum, 0.0), axis=0, keepdims=True)
    total = jnp.sum(a, axis=1, keepdims=True)                    # (1, 1)
    # Γ[i,j] = exp(cum_i - cum_j) for j <= i (segment decay), else 0.
    # Mask inside the exp so the masked branch cannot overflow.
    gamma = jnp.exp(jnp.where(causal, cum - cum_t, -1e30))

    # Y = ((C B^T) ⊙ Γ) (Δ ⊙ X) + exp(cum) ⊙ (C H)
    att = jnp.dot(cmat, bmat.T, preferred_element_type=jnp.float32) * \
        gamma * dt
    h = h_ref[0]                          # (N, P): state entering the chunk
    y = jnp.dot(att, x, preferred_element_type=jnp.float32) + \
        jnp.exp(cum) * jnp.dot(cmat, h, preferred_element_type=jnp.float32)
    y_ref[0] = y.astype(y_ref.dtype)

    # H <- exp(total) H + S_c,  S_c = (B ⊙ Δ exp(total - cum))^T X
    w = dt * jnp.exp(total - cum_t)       # (1, L)
    h_ref[0] = jnp.exp(total) * h + jnp.dot(
        bmat.T * w, x, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunk_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                   C: jax.Array, *, chunk: int = 256,
                   init_state: jax.Array | None = None,
                   interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Head-batched SSD: x (BH,S,P), dt (BH,S), A (BH,), B/C (BH,S,N),
    init_state (BH,N,P).

    Returns (y (BH,S,P) in x's dtype, final_state (BH,N,P) f32).
    S % chunk == 0 (ops.py pads, with dt zero); on the TPU ``chunk`` is a
    multiple of 128 or all of S.  Everything runs in the Pallas kernel but
    the ``layout`` step, as ``ops.py`` names its wrappers' steps: dt and
    dt·A as the kernel's (BH, 1, S) row operands.
    """
    bh, s, p = x.shape
    n = B.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nck = s // chunk
    with jax.named_scope("layout"):
        a = dt.astype(jnp.float32) * A.astype(jnp.float32)[:, None]
        operands = [x, dt[:, None, :], a[:, None, :], B, C]

    def seq(w):
        return pl.BlockSpec((1, chunk, w), lambda b, c: (b, c, 0))

    row = pl.BlockSpec((1, 1, chunk), lambda b, c: (b, 0, c))
    state = pl.BlockSpec((1, n, p), lambda b, c: (b, 0, 0))
    in_specs = [seq(p), row, row, seq(n), seq(n)]
    if init_state is not None:
        operands.append(init_state)
        in_specs.append(state)

    return tuple(pl.pallas_call(
        functools.partial(_ssd_chunk_kernel,
                          has_init=init_state is not None),
        name="ssd_chunk_scan",
        grid=(bh, nck),
        in_specs=in_specs,
        out_specs=[seq(p), state],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, p), x.dtype),
            jax.ShapeDtypeStruct((bh, n, p), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*operands))


__all__ = ["ssd_chunk_scan"]
