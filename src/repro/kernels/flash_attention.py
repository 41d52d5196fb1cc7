"""Flash attention Pallas kernels for TPU (forward + decode).

Online-softmax over kv blocks with running (max, sum) scratch in VMEM.
Supports causal masking, sliding windows (gemma3's 5:1 local layers) and a
single-query decode variant, whose online softmax walks the kv blocks of a
cache read row-major or with its slots on the lanes, each row's walk stopping
at its length.

Block geometry again comes from the Covenant tiler
(``tiling.attention_blocks``): the QK^T GEMM's Algorithm-1 tiling is the
flash block structure — this is the hw-codesign point of the reproduction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale: float, causal: bool, window: int | None,
               block_q: int, block_kv: int, seq_k: int, q_offset: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)          # (bq, d)
    k = k_ref[0].astype(jnp.float32)          # (bkv, d)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
        + q_offset
    kpos = kj * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < seq_k
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                        # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v_ref[0].astype(jnp.float32), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kj == pl.num_programs(2) - 1)
    def _flush():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_kv", "scale", "q_offset",
    "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    scale: float | None = None, block_q: int = 128,
                    block_kv: int = 128, q_offset: int | None = None,
                    interpret: bool = False) -> jax.Array:
    """q: (BH, Sq, D); k, v: (BH, Sk, D).  Sq % block_q == 0; Sk padded to
    block_kv by the wrapper (mask uses true seq_k).  ``q_offset`` is the kv
    position of q row 0 (pass ``true_sk - true_sq`` when q is end-padded)."""
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    scale = scale if scale is not None else (d ** -0.5)
    sk_pad = -(-sk // block_kv) * block_kv
    if sk_pad != sk:
        pad = [(0, 0), (0, sk_pad - sk), (0, 0)]
        with jax.named_scope("pad"):
            k = jnp.pad(k, pad)
            v = jnp.pad(v, pad)
    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_kv=block_kv, seq_k=sk,
        q_offset=(sk - sq) if q_offset is None else q_offset)
    return pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=(bh, sq // block_q, sk_pad // block_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


def _last_block(kv_len, block_kv: int):
    """The last K/V block that holds a valid slot; block 0 for a length of
    0.  The decode walk's ``index_map`` and ``decode_blocks_read`` both
    read it."""
    return jnp.maximum(kv_len - 1, 0) // block_kv


def decode_blocks_read(kv_len, s: int, block_kv: int) -> tuple[int, int]:
    """(K/V blocks the decode walk fetches, blocks of its grid) for the
    (BKV,) lengths ``kv_len`` over ``s`` slots: each row fetches its
    blocks up to its last valid one, and at least one."""
    n_blocks = -(-s // block_kv)
    last = _last_block(jnp.asarray(kv_len, jnp.int32), block_kv)
    read = jnp.sum(jnp.minimum(last + 1, n_blocks))
    return int(read), len(kv_len) * n_blocks


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                   acc_ref, *, scale: float, block_kv: int,
                   slots_minor: bool):
    """One (kv head, kv block) step of the online softmax.  K/V blocks are
    (bkv, d) row-major, or (d, bkv) with the slots on the lanes when
    ``slots_minor``: only the two contractions differ.  The (BKV,) lengths
    are scalar-prefetched into ``len_ref``; a step whose block starts at
    or past its row's length computes nothing (its K/V ``index_map``
    repeats the last valid block, so nothing is fetched either), and a
    length of 0 gives 0."""
    b, kj = pl.program_id(0), pl.program_id(1)
    n = len_ref[b]

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(kj * block_kv < n)
    def _step():
        q = q_ref[0].astype(jnp.float32)       # (Hg, d) — grouped q heads
        k = k_ref[0].astype(jnp.float32)
        s = jnp.dot(q, k if slots_minor else k.T,
                    preferred_element_type=jnp.float32) * scale  # (Hg, bkv)
        kpos = kj * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape,
                                                        1)
        mask = kpos < n
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        # p (Hg, bkv) against v over the slots: v's axis 0, or its lanes
        pv = jax.lax.dot_general(p, v, (((1,), (1 if slots_minor else 0,)),
                                        ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = m_new

    @pl.when(kj == pl.num_programs(1) - 1)
    def _flush():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("scale", "block_kv",
                                             "slots_minor", "interpret"))
def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 kv_len: jax.Array, *, scale: float | None = None,
                 block_kv: int = 512, slots_minor: bool = False,
                 interpret: bool = False) -> jax.Array:
    """Single-token decode attention against a KV cache.

    q: (BKV, Hg, D) — one query block per kv head (Hg = q heads per kv
    head); k, v: (BKV, S, D), or (BKV, D, S) when ``slots_minor``;
    kv_len: (BKV,) valid lengths, scalar-prefetched.  Each row's walk
    stops at its last valid block: the blocks past it are neither read nor
    computed (``decode_blocks_read`` counts the ones that are), and a
    length of 0 gives 0.
    """
    bkv, hg, d = q.shape
    at = 2 if slots_minor else 1           # the slots' axis of k and v
    s = k.shape[at]
    scale = scale if scale is not None else (d ** -0.5)
    s_pad = -(-s // block_kv) * block_kv
    if s_pad != s:
        pad = [(0, 0)] * 3
        pad[at] = (0, s_pad - s)
        with jax.named_scope("pad"):
            k = jnp.pad(k, pad)
            v = jnp.pad(v, pad)

    def kv_index(b, j, lens):
        # past the last valid block, repeat it: the pipeline fetches nothing
        j = jnp.minimum(j, _last_block(lens[b], block_kv))
        return (b, 0, j) if slots_minor else (b, j, 0)

    kv_spec = pl.BlockSpec((1, d, block_kv) if slots_minor
                           else (1, block_kv, d), kv_index)
    row_spec = pl.BlockSpec((1, hg, d), lambda b, j, lens: (b, 0, 0))
    kernel = functools.partial(_decode_kernel, scale=scale, block_kv=block_kv,
                               slots_minor=slots_minor)
    return pl.pallas_call(
        kernel,
        name="flash_decode",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(bkv, s_pad // block_kv),
            in_specs=[row_spec, kv_spec, kv_spec],
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM((hg, 1), jnp.float32),
                pltpu.VMEM((hg, 1), jnp.float32),
                pltpu.VMEM((hg, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(kv_len, q, k, v)


__all__ = ["decode_blocks_read", "flash_attention", "flash_attention_bwd",
           "flash_attention_fwd_lse", "flash_decode"]


# ---------------------------------------------------------------------------
# backward kernels (flash recompute; mirrors models/attention.py custom VJP)
# ---------------------------------------------------------------------------


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, acc_ref, *, scale, causal, window, block_q,
                      block_kv, seq_k, q_offset):
    qi, kj = pl.program_id(1), pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
        + q_offset
    kpos = kj * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < seq_k
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    p = jnp.where(mask, jnp.exp(s - lse_ref[0]), 0.0)
    dp = jnp.dot(do_ref[0].astype(jnp.float32), v.T,
                 preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0]) * scale
    acc_ref[...] += jnp.dot(ds, k, preferred_element_type=jnp.float32)

    @pl.when(kj == pl.num_programs(2) - 1)
    def _flush():
        dq_ref[0] = acc_ref[...].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                       window, block_q, block_kv, seq_k, q_offset):
    kj, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
        + q_offset
    kpos = kj * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < seq_k
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    p = jnp.where(mask, jnp.exp(s - lse_ref[0]), 0.0)
    dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0]) * scale
    dk_acc[...] += jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
    dv_acc[...] += jnp.dot(p.T, do, preferred_element_type=jnp.float32)

    @pl.when(qi == pl.num_programs(2) - 1)
    def _flush():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, out, lse, dout, *, causal=True, window=None,
                        scale=None, block_q=128, block_kv=128, seq_k=None,
                        q_offset=0, interpret=False):
    """dq, dk, dv for the flash forward.  All (BH, S, D); lse (BH, S, 1).
    Shapes must be padded to block multiples (ops wrapper handles it)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    seq_k = sk if seq_k is None else seq_k
    scale = scale if scale is not None else d ** -0.5
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), -1,
                    keepdims=True)
    nq, nkv = sq // block_q, sk // block_kv
    common = dict(scale=scale, causal=causal, window=window,
                  block_q=block_q, block_kv=block_kv, seq_k=seq_k,
                  q_offset=q_offset)
    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    kv_spec = pl.BlockSpec((1, block_kv, d), lambda b, i, j: (b, j, 0))
    row_spec = pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0))
    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, **common),
        grid=(bh, nq, nkv),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, dout, lse, delta)
    kv_q_spec = pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0))
    kv_kv_spec = pl.BlockSpec((1, block_kv, d), lambda b, j, i: (b, j, 0))
    kv_row_spec = pl.BlockSpec((1, block_q, 1), lambda b, j, i: (b, i, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, **common),
        grid=(bh, nkv, nq),
        in_specs=[kv_q_spec, kv_kv_spec, kv_kv_spec, kv_q_spec, kv_row_spec,
                  kv_row_spec],
        out_specs=[kv_kv_spec, kv_kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block_kv, d), jnp.float32),
                        pltpu.VMEM((block_kv, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, dout, lse, delta)
    return dq, dk, dv


def flash_attention_fwd_lse(q, k, v, *, causal=True, window=None, scale=None,
                            block_q=128, block_kv=128, q_offset=None,
                            interpret=False):
    """Forward that also returns lse (BH, Sq, 1) — the bwd residual."""
    bh, sq, d = q.shape
    _, sk, _ = k.shape
    scale = scale if scale is not None else d ** -0.5
    kernel = functools.partial(
        _fa_fwd_lse_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_kv=block_kv, seq_k=sk,
        q_offset=(sk - sq) if q_offset is None else q_offset)
    return pl.pallas_call(
        kernel,
        grid=(bh, sq // block_q, sk // block_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((bh, sq, 1), jnp.float32)],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


def _fa_fwd_lse_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref,
                       acc_ref, *, scale, causal, window, block_q, block_kv,
                       seq_k, q_offset):
    qi, kj = pl.program_id(1), pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) \
        + q_offset
    kpos = kj * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < seq_k
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, -1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, -1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v_ref[0].astype(jnp.float32), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(kj == pl.num_programs(2) - 1)
    def _flush():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / safe).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(safe)
