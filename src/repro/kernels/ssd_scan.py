"""Mamba2 SSD (state-space duality) chunked scan — Pallas kernel.

The SSD dual form splits the sequence into chunks of length L:

* intra-chunk (quadratic, MXU-bound):  Y_intra = (C B^T ⊙ Γ) X
* chunk states (GEMM):                 S_c     = (B ⊙ γ_end)^T X
* inter-chunk (tiny recurrence):       H_c     = exp(ΔA_c) H_{c-1} + S_c
* state -> output (GEMM):              Y_inter = γ_start ⊙ (C H_{c-1})

The Pallas kernel fuses the two FLOPs-dominant chunk-local stages (Y_intra
and S_c) per (batch·head, chunk) grid cell — a direct port of the paper's
multi-compute-node schedule (MXU for the GEMMs, VPU for the decay masks)
onto one VMEM-resident block.  The O(chunks) recurrence and the Y_inter
GEMM run as jnp ops (they are <2% of FLOPs at L=256).

Shapes (head-batched): x (BH, S, P), dt (BH, S), B,C (BH, S, N), A (BH,).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_chunk_kernel(x_ref, dt_ref, cum_ref, cum_t_ref, decay_ref, b_ref,
                      c_ref, y_ref, state_ref):
    """One (bh, chunk) cell: intra-chunk output + end-of-chunk state.

    ``cum`` is the chunk's inclusive cumsum of dt*A, given both as a
    column (L, 1) and as a row (1, L) so that no relayout happens here;
    ``decay`` is exp(cum_L - cum), the decay of each step to the chunk's
    end."""
    x = x_ref[0].astype(jnp.float32)      # (L, P)
    dt = dt_ref[0].astype(jnp.float32)    # (L, 1)
    cum = cum_ref[0]                      # (L, 1)
    cum_t = cum_t_ref[0]                  # (1, L)
    decay_to_end = decay_ref[0]           # (L, 1)
    bmat = b_ref[0].astype(jnp.float32)   # (L, N)
    cmat = c_ref[0].astype(jnp.float32)   # (L, N)
    L = x.shape[0]
    # Γ[i,j] = exp(cum_i - cum_j) for j <= i (segment decay), else 0.
    # Mask inside the exp so the masked branch cannot overflow.
    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    gamma = jnp.exp(jnp.where(jj <= ii, cum - cum_t, -1e30))

    # Y_intra = ((C B^T) ⊙ Γ) (Δ ⊙ X)
    att = jnp.dot(cmat, bmat.T, preferred_element_type=jnp.float32) * gamma
    xdt = x * dt
    y_ref[0] = jnp.dot(att, xdt, preferred_element_type=jnp.float32
                       ).astype(y_ref.dtype)

    # S_c = (B ⊙ exp(cum_L - cum))^T (Δ ⊙ X)   -> (N, P)
    state_ref[0] = jnp.dot((bmat * decay_to_end).T, xdt,
                           preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunk_scan(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                   C: jax.Array, *, chunk: int = 256,
                   init_state: jax.Array | None = None,
                   interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """Head-batched SSD: x (BH,S,P), dt (BH,S), A (BH,), B/C (BH,S,N).

    Returns (y (BH,S,P), final_state (BH,N,P)).  S % chunk == 0 (ops.py
    pads); on the TPU ``chunk`` is a multiple of 128 or all of S.  The
    chunk-local heavy stages run in the Pallas kernel; the per-chunk
    cumsum and the cross-chunk combination are jnp, each stage under a
    step scope as ``ops.py`` names its wrappers' steps: ``decay`` (the
    cumsum and its exps), ``layout`` (the kernel's column and row
    operands), ``carry`` (the recurrence across chunks) and ``inter`` (the
    output of the states entering each chunk).
    """
    bh, s, p = x.shape
    n = B.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nck = s // chunk
    with jax.named_scope("decay"):
        dtf = dt.astype(jnp.float32).reshape(bh, nck, chunk)
        cum_in = jnp.cumsum(dtf * A.astype(jnp.float32)[:, None, None],
                            axis=-1)
        cum = cum_in.reshape(bh, s)
        decay = jnp.exp(cum_in[..., -1:] - cum_in).reshape(bh, s, 1)
    with jax.named_scope("layout"):
        operands = (x, dt[..., None], cum[..., None], cum[:, None, :], decay,
                    B, C)

    y_intra, states = pl.pallas_call(
        _ssd_chunk_kernel,
        name="ssd_chunk_scan",
        grid=(bh, nck),
        in_specs=[
            pl.BlockSpec((1, chunk, p), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, 1), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, chunk), lambda b, c: (b, 0, c)),
            pl.BlockSpec((1, chunk, 1), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, p), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, n, p), lambda b, c: (b * nck + c, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, p), jnp.float32),
            jax.ShapeDtypeStruct((bh * nck, n, p), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(*operands)

    with jax.named_scope("carry"):
        states = states.reshape(bh, nck, n, p)
        dsums = cum_in[..., -1]               # (BH, nck) chunk log-decay

        # inter-chunk recurrence over ncache states:
        # H_c = e^{dsum_c} H_{c-1} + S_c
        def comb(left, right):
            dl, sl = left
            dr, sr = right
            return dl + dr, sr + sl * jnp.exp(dr)[..., None, None]

        dcum, hstates = jax.lax.associative_scan(
            comb, (dsums.swapaxes(0, 1), states.swapaxes(0, 1)))
        hstates = hstates.swapaxes(0, 1)  # (BH, ncache, N, P): chunk ends
        if init_state is not None:
            carry = jnp.exp(dcum.swapaxes(0, 1))[..., None, None] * \
                init_state[:, None].astype(jnp.float32)
            hstates = hstates + carry
        # states entering each chunk: shift right
        h_prev = jnp.concatenate([
            (init_state[:, None].astype(jnp.float32) if init_state is not None
             else jnp.zeros_like(hstates[:, :1])),
            hstates[:, :-1]], axis=1)  # (BH, ncache, N, P)

    with jax.named_scope("inter"):
        # Y_inter[t] = exp(cum_t) * C_t @ H_prev(chunk(t))
        gamma_start = jnp.exp(cum_in)  # (BH,ncache,L)
        Cc = C.astype(jnp.float32).reshape(bh, nck, chunk, n)
        y_inter = jnp.einsum("bcln,bcnp->bclp", Cc, h_prev) * \
            gamma_start[..., None]
        y = y_intra + y_inter.reshape(bh, s, p)
        y = y.astype(x.dtype)
    with jax.named_scope("carry"):
        return y, hstates[:, -1]


__all__ = ["ssd_chunk_scan"]
