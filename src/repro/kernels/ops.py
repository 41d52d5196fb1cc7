"""Public kernel API: padding and block selection around the Pallas kernels.

The kernels are compiled for the TPU by Mosaic; ``interpret=True`` runs
them in the Pallas interpreter instead, and only a caller that asks for it
gets it (the CPU tests do).  Block geometry defaults to the Covenant
tiler's Algorithm-1 choice (``tiling.gemm_blocks`` / ``attention_blocks``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ref as _ref
from .flash_attention import flash_attention as _fa, flash_decode as _fd
from .matmul import matmul as _mm
from .ssd_scan import ssd_chunk_scan as _ssd
from .tiling import SUBLANE, attention_blocks, gemm_blocks


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    s = x.shape[axis]
    t = -(-s // mult) * mult
    if t == s:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, t - s)
    return jnp.pad(x, pads)


def covenant_matmul(a: jax.Array, b: jax.Array, *,
                    blocks: tuple[int, int, int] | None = None,
                    interpret: bool = False) -> jax.Array:
    """GEMM with Covenant-tiled BlockSpecs; pads to block multiples.
    Returns f32 for float inputs, i32 for int8."""
    m, k = a.shape
    _, n = b.shape
    if blocks is None:
        in_dt = "i8" if jnp.issubdtype(a.dtype, jnp.integer) else "bf16"
        blocks = gemm_blocks(m, n, k, in_dtype=in_dt)
    bm, bn, bk = blocks
    ap = _pad_to(_pad_to(a, 0, bm), 1, bk)
    bp = _pad_to(_pad_to(b, 0, bk), 1, bn)
    out = _mm(ap, bp, block_m=bm, block_n=bn, block_k=bk,
              interpret=interpret)
    return out[:m, :n]


def covenant_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                       causal: bool = True, window: int | None = None,
                       scale: float | None = None,
                       blocks: tuple[int, int] | None = None,
                       interpret: bool = False) -> jax.Array:
    """GQA flash attention.  q: (B,Hq,Sq,D), k/v: (B,Hkv,Sk,D)."""
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    if hkv != hq:
        k = jnp.repeat(k, hq // hkv, axis=1)
        v = jnp.repeat(v, hq // hkv, axis=1)
    if blocks is None:
        bq, bkv = attention_blocks(sq, k.shape[2], d)
    else:
        bq, bkv = blocks
    bq = min(bq, -(-sq // SUBLANE) * SUBLANE)
    qf = _pad_to(q.reshape(b * hq, sq, d), 1, bq)
    kf = k.reshape(b * hq, -1, d)
    vf = v.reshape(b * hq, -1, d)
    out = _fa(qf, kf, vf, causal=causal, window=window, scale=scale,
              block_q=bq, block_kv=bkv, q_offset=kf.shape[1] - sq,
              interpret=interpret)
    return out[:, :sq].reshape(b, hq, sq, d)


def covenant_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                              kv_len: jax.Array, *,
                              scale: float | None = None,
                              block_kv: int = 512,
                              interpret: bool = False) -> jax.Array:
    """One-token GQA decode.  q: (B,Hq,D), cache k/v: (B,Hkv,S,D),
    kv_len: (B,).  Returns (B,Hq,D)."""
    b, hq, d = q.shape
    _, hkv, s, _ = k.shape
    g = hq // hkv
    qg = q.reshape(b * hkv, g, d)
    kf = k.reshape(b * hkv, s, d)
    vf = v.reshape(b * hkv, s, d)
    lens = jnp.repeat(kv_len, hkv)
    out = _fd(qg, kf, vf, lens, scale=scale, block_kv=min(block_kv, s),
              interpret=interpret)
    return out.reshape(b, hq, d)


def covenant_ssd(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                 C: jax.Array, *, chunk: int = 64,
                 init_state: jax.Array | None = None,
                 return_state: bool = False,
                 interpret: bool = False):
    """Mamba2 SSD over (b, s, h, p) inputs with (b, s, g, n) B/C."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    ck = min(chunk, s)
    spad = -(-s // ck) * ck
    xf = _pad_to(x, 1, ck).transpose(0, 2, 1, 3).reshape(b * h, spad, p)
    dtf = _pad_to(dt, 1, ck).transpose(0, 2, 1).reshape(b * h, spad)
    Bh = jnp.repeat(B, rep, axis=2)
    Ch = jnp.repeat(C, rep, axis=2)
    Bf = _pad_to(Bh, 1, ck).transpose(0, 2, 1, 3).reshape(b * h, spad, n)
    Cf = _pad_to(Ch, 1, ck).transpose(0, 2, 1, 3).reshape(b * h, spad, n)
    Af = jnp.tile(A, b)
    st0 = None
    if init_state is not None:
        st0 = init_state.reshape(b * h, p, n).swapaxes(1, 2)  # (BH,N,P)
    y, fin = _ssd(xf, dtf, Af, Bf, Cf, chunk=ck, init_state=st0,
                  interpret=interpret)
    y = y[:, :s].reshape(b, h, s, p).transpose(0, 2, 1, 3)
    if return_state:
        return y, fin.swapaxes(1, 2).reshape(b, h, p, n)
    return y


# re-export oracles for convenience
matmul_ref = _ref.matmul_ref
attention_ref = _ref.attention_ref
ssd_ref = _ref.ssd_ref

__all__ = ["attention_ref", "covenant_attention", "covenant_decode_attention",
           "covenant_matmul", "covenant_ssd", "matmul_ref", "ssd_ref"]
