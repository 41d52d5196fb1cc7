"""Plain float32 ``jax.numpy`` references of the kernels that the passes
chain, written after the program's oracles (``kernels/ref.py``) and its
decode step (``models/ssm.py``) but importing nothing of the program.

Every function takes ``low``: None for the reference, or the control's
precisions, ``{"act": float8_e4m3fn, "state": bfloat16}``, one step below
what the configuration states (bf16 activations and weights, f32 SSM
state).  The control rounds each operand to those dtypes and computes in
f32, as a kernel with fp8 operands and f32 accumulation would.  Callers
run these under ``jax.default_matmul_precision("highest")``.  Int8
operands (``int_einsum``) are multiplied exactly; their control rounds
them to int4 first.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
BF16 = jnp.bfloat16
CONTROL = {"act": jnp.float8_e4m3fn, "state": jnp.bfloat16}


def rnd(x: jax.Array, low: dict | None, kind: str = "act") -> jax.Array:
    """``x`` in f32, first rounded to the control's dtype for ``kind``,
    saturating at its largest finite value as an fp8 kernel's cast does
    (a plain cast to float8_e4m3fn turns anything past 448 into NaN)."""
    x = x.astype(F32)
    if low is None:
        return x
    big = float(jnp.finfo(low[kind]).max)
    return jnp.clip(x, -big, big).astype(low[kind]).astype(F32)


def matmul(a: jax.Array, b: jax.Array, low=None) -> jax.Array:
    return jnp.dot(rnd(a, low), rnd(b, low))


# the longest stretch of an int8 contraction summed in f32: every partial
# sum is an integer of magnitude at most 512 * 2**14 = 2**23, which f32
# holds exactly, so no order of summation rounds it
EXACT_K = 512


def int4(x: jax.Array) -> jax.Array:
    """int8 values as int4 holds them at a scale of 16: each rounded to the
    nearest of -128, -112, ..., 112."""
    q = jnp.clip(jnp.round(x.astype(F32) / 16), -8, 7)
    return (16 * q).astype(jnp.int8)


def int_einsum(spec: str, a: jax.Array, b: jax.Array, low=None) -> jax.Array:
    """``jnp.einsum(spec, a, b)`` of int8 operands with one contracted
    axis, exactly, int32 out: the axis in stretches of ``EXACT_K``, each
    multiplied in bf16 (which holds every int8 exactly) with f32
    accumulation, and the stretches summed in int32.  Not the int8 MXU
    path that the program and XLA's int8 GEMMs take.  The control first
    rounds each operand to int4."""
    ins, out = spec.split("->")
    sa, sb = ins.split(",")
    (c,) = (set(sa) & set(sb)) - set(out)
    ia, ib = sa.index(c), sb.index(c)
    if low is not None:
        a, b = int4(a), int4(b)
    total, k = 0, a.shape[ia]
    for s in range(0, k, EXACT_K):
        e = min(s + EXACT_K, k)
        part = jnp.einsum(
            spec,
            jax.lax.slice_in_dim(a, s, e, axis=ia).astype(BF16),
            jax.lax.slice_in_dim(b, s, e, axis=ib).astype(BF16),
            preferred_element_type=F32)
        total = total + part.astype(jnp.int32)
    return total


def requant(y: jax.Array) -> jax.Array:
    """int32 -> int8 by a scale of each last-axis row's own (a per-token
    dynamic scale): the row shifted right, rounding half up, by as many
    bits as bring its largest magnitude within 8 bits, then clipped to
    [-128, 127].  Integer arithmetic throughout, so exact everywhere."""
    top = jnp.max(jnp.abs(y), axis=-1, keepdims=True)
    shift = jnp.maximum(32 - jax.lax.clz(top) - 7, 0)
    half = jnp.where(shift > 0,
                     jnp.left_shift(1, jnp.maximum(shift - 1, 0)), 0)
    r = jax.lax.shift_right_arithmetic(y + half, shift)
    return jnp.clip(r, -128, 127).astype(jnp.int8)


def attention(q, k, v, *, causal: bool, low=None) -> jax.Array:
    """q (B,Hq,S,D), k/v (B,Hkv,S,D) -> (B,Hq,S,D) f32, one sequence at a
    time; query heads share their group's K/V without a copy."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    mask = jnp.tril(jnp.ones((s, s), bool)) if causal else None

    def one(args):
        qb, kb, vb = (rnd(t, low) for t in args)
        qb = qb.reshape(hkv, g, s, d)
        logits = jnp.einsum("kgqd,ksd->kgqs", qb, kb) * d ** -0.5
        if mask is not None:
            logits = jnp.where(mask, logits, -jnp.inf)
        p = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("kgqs,ksd->kgqd", rnd(p, low), vb).reshape(hq, s, d)

    return jax.lax.map(one, (q, k, v))


def decode_attention(q, kc, vc, kv_len, low=None) -> jax.Array:
    """q (B,Hq,D) against the cache kc/vc (B,Hkv,S,D), keys at positions
    >= kv_len masked -> (B,Hq,D) f32."""
    b, hq, d = q.shape
    hkv, s = kc.shape[1], kc.shape[2]
    qg = rnd(q, low).reshape(b, hkv, hq // hkv, d)
    logits = jnp.einsum("bkgd,bksd->bkgs", qg, rnd(kc, low)) * d ** -0.5
    valid = jnp.arange(s)[None, :] < kv_len[:, None]          # (B,S)
    logits = jnp.where(valid[:, None, None], logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", rnd(p, low), rnd(vc, low))
    return out.reshape(b, hq, d)


def ssd(x, dt, A, B, C, low=None, chunk: int = 128):
    """Mamba2 SSD from a zero state, the recurrence
    h_t = exp(A dt_t) h_{t-1} + dt_t x_t B_t^T,  y_t = h_t C_t,
    summed exactly in its chunked form (Mamba2's minimal SSD): within a
    chunk the masked quadratic form, across chunks the state.  x (b,s,h,p),
    dt (b,s,h), A (h,), B/C (b,s,g,n) -> y (b,s,h,p) f32 and the final
    state (b,h,n,p) f32.  The control rounds the state carried between
    chunks to its dtype."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    L = min(chunk, s)
    if s % L:
        raise ValueError(f"sequence {s} is no multiple of chunk {L}")
    c, r = s // L, h // g
    xs = rnd(x, low).reshape(b, c, L, g, r, p)
    dts = dt.astype(F32).reshape(b, c, L, g, r)
    Bs = rnd(B, low).reshape(b, c, L, g, n)
    Cs = rnd(C, low).reshape(b, c, L, g, n)
    a = jnp.cumsum(A.astype(F32).reshape(g, r) * dts, axis=2)  # (b,c,L,g,r)
    xdt = xs * dts[..., None]
    # within a chunk: y_t = sum_{u <= t} C_t.B_u exp(a_t - a_u) dt_u x_u
    seg = a[:, :, :, None] - a[:, :, None, :]                  # (b,c,t,u,g,r)
    causal = jnp.tril(jnp.ones((L, L), bool))[:, :, None, None]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("bctgn,bcugn->bctug", Cs, Bs)
    y = jnp.einsum("bctug,bctugr,bcugrp->bctgrp", cb, decay, xdt)
    # each chunk's own contribution to the state at its end
    tail = jnp.exp(a[:, :, -1:] - a)                            # (b,c,L,g,r)
    own = jnp.einsum("bcugn,bcugr,bcugrp->bcgrnp", Bs, tail, xdt)
    total = jnp.exp(a[:, :, -1])                                # (b,c,g,r)

    def carry(state, inp):                      # state (b,g,r,n,p)
        own_c, total_c = inp
        new = rnd(state * total_c[..., None, None] + own_c, low, "state")
        return new, state

    fin, before = jax.lax.scan(carry, jnp.zeros((b, g, r, n, p), F32),
                               (own.swapaxes(0, 1), total.swapaxes(0, 1)))
    # the state that enters each chunk, decayed to each position
    y = y + jnp.einsum("bctgn,cbgrnp,bctgr->bctgrp", Cs, before, jnp.exp(a))
    return y.reshape(b, s, h, p), fin.reshape(b, h, n, p)


def ssm_state_step(state, x, dt, A, B, C, low=None):
    """One token of the SSM recurrence, as ``models/ssm.py`` decodes it.
    state (b,h,n,p) f32, x (b,h,p), dt (b,h), A (h,), B/C (b,g,n).
    Returns (new state, y (b,h,p))."""
    b, h, n, p = state.shape
    g = B.shape[1]
    Bh = jnp.repeat(rnd(B, low), h // g, axis=1)
    Ch = jnp.repeat(rnd(C, low), h // g, axis=1)
    dt = dt.astype(F32)
    new = rnd(state, low, "state") * jnp.exp(A.astype(F32) * dt)[..., None, None] \
        + Bh[..., None] * (rnd(x, low) * dt[..., None])[:, :, None, :]
    new = rnd(new, low, "state")
    return new, jnp.einsum("bhnp,bhn->bhp", new, Ch)


def silu(x):
    return x * jax.nn.sigmoid(x)


def layer_norm(x, eps: float = 1e-5):
    """LayerNorm over the last axis in f32, at its initial gain 1 and bias
    0 (StableLM 2's pre-norm, ``layer_norm_eps`` 1e-5)."""
    x = x.astype(F32)
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
