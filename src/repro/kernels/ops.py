"""Public kernel API: padding and block selection around the Pallas kernels.

The kernels are compiled for the TPU by Mosaic; ``interpret=True`` runs
them in the Pallas interpreter instead, and only a caller that asks for it
gets it (the CPU tests do).  Block geometry defaults to the Covenant
tiler's Algorithm-1 choice (``tiling.gemm_blocks`` / ``attention_blocks``).

Each wrapper runs under a named scope of its own name, and each of its
steps that is no kernel under one step scope, so that every op it makes
carries ``<wrapper>/<step>`` in its HLO ``op_name`` and a profiler trace
can attribute it:

* ``pad``: operands padded to block multiples;
* ``unpad``: the output sliced back to the caller's extent;
* ``repeat``: K/V, lengths, B/C or A repeated over heads;
* ``layout``: reshapes and transposes into and out of the kernels'
  operand layouts, the slots-minor layout a decode cache is held to,
  ``ssd_chunk_scan``'s dt and dt·A rows (its cumsum, decays and carry
  across chunks run in the kernel), and the expert
  layer's sort of (token, expert) pairs into groups, the gather of their
  rows and the group offsets;
* ``route``: the expert layer's router GEMM, top-k and gates;
* ``swiglu``: the expert layer's SiLU(gate) * up between its two GEMMs;
* ``combine``: the expert layer's gated sum of its rows back to tokens.

The wrappers are ``covenant_matmul``, ``covenant_attention``,
``covenant_decode_attention``, ``covenant_ssd`` and ``covenant_experts``.
The scopes are metadata: they change no op of the compiled program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.layout import Layout, with_layout_constraint

from . import ref as _ref
from .flash_attention import flash_attention as _fa, flash_decode as _fd
from .grouped_matmul import grouped_matmul as _gmm
from .matmul import matmul as _mm
from .ssd_scan import ssd_chunk_scan as _ssd
from .tiling import SUBLANE, attention_blocks, gemm_blocks, grouped_gemm_blocks


def _pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    s = x.shape[axis]
    t = -(-s // mult) * mult
    if t == s:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, t - s)
    return jnp.pad(x, pads)


def _heads_first(t: jax.Array, chunk: int) -> jax.Array:
    """(b, s, h, ...) -> (b·h, s', ...), s' the next multiple of ``chunk``:
    the sequence padded (``pad``), then the heads moved before it
    (``layout``)."""
    with jax.named_scope("pad"):
        t = _pad_to(t, 1, chunk)
    with jax.named_scope("layout"):
        t = jnp.swapaxes(t, 1, 2)
        return t.reshape(-1, *t.shape[2:])


@jax.named_scope("covenant_matmul")
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
    with jax.named_scope("pad"):
        ap = _pad_to(_pad_to(a, 0, bm), 1, bk)
        bp = _pad_to(_pad_to(b, 0, bk), 1, bn)
    out = _mm(ap, bp, block_m=bm, block_n=bn, block_k=bk,
              interpret=interpret)
    with jax.named_scope("unpad"):
        return out[:m, :n]


@jax.named_scope("covenant_attention")
def covenant_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                       causal: bool = True, window: int | None = None,
                       scale: float | None = None,
                       blocks: tuple[int, int] | None = None,
                       interpret: bool = False) -> jax.Array:
    """GQA flash attention.  q: (B,Hq,Sq,D), k/v: (B,Hkv,Sk,D)."""
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    if hkv != hq:
        with jax.named_scope("repeat"):
            k = jnp.repeat(k, hq // hkv, axis=1)
            v = jnp.repeat(v, hq // hkv, axis=1)
    if blocks is None:
        bq, bkv = attention_blocks(sq, k.shape[2], d)
    else:
        bq, bkv = blocks
    bq = min(bq, -(-sq // SUBLANE) * SUBLANE)
    with jax.named_scope("layout"):
        qf = q.reshape(b * hq, sq, d)
    with jax.named_scope("pad"):
        qf = _pad_to(qf, 1, bq)
    with jax.named_scope("layout"):
        kf = k.reshape(b * hq, -1, d)
        vf = v.reshape(b * hq, -1, d)
    out = _fa(qf, kf, vf, causal=causal, window=window, scale=scale,
              block_q=bq, block_kv=bkv, q_offset=kf.shape[1] - sq,
              interpret=interpret)
    with jax.named_scope("unpad"):
        out = out[:, :sq]
    with jax.named_scope("layout"):
        return out.reshape(b, hq, sq, d)


@jax.named_scope("covenant_decode_attention")
def covenant_decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                              kv_len: jax.Array, *,
                              scale: float | None = None,
                              block_kv: int = 512,
                              interpret: bool = False) -> jax.Array:
    """One-token GQA decode.  q: (B,Hq,D), cache k/v: (B,Hkv,S,D),
    kv_len: (B,).  Returns (B,Hq,D).

    Where D is no multiple of 128 and S is, the TPU holds the cache with
    its slots on the lanes (its compact layout, ``{2,3,1,0}``), and the
    kernel reads K/V as (B·Hkv, D, S), which is that layout as it lies: a
    row-major (B·Hkv, S, D) operand would cost a copy of the whole cache,
    padded from D to 256 lanes.  The cache is held to that layout here, so
    that a cache just written in another layout (a row scatter's) is
    copied back once, for the kernel and the caller alike."""
    b, hq, d = q.shape
    _, hkv, s, _ = k.shape
    g = hq // hkv
    slots_minor = d % 128 != 0 and s % 128 == 0
    with jax.named_scope("layout"):
        qg = q.reshape(b * hkv, g, d)
        if slots_minor:
            held = Layout(major_to_minor=(0, 1, 3, 2))
            kf = jnp.swapaxes(with_layout_constraint(k, held), 2, 3)
            vf = jnp.swapaxes(with_layout_constraint(v, held), 2, 3)
            kf = kf.reshape(b * hkv, d, s)
            vf = vf.reshape(b * hkv, d, s)
        else:
            kf = k.reshape(b * hkv, s, d)
            vf = v.reshape(b * hkv, s, d)
    with jax.named_scope("repeat"):
        lens = jnp.repeat(kv_len, hkv)
    out = _fd(qg, kf, vf, lens, scale=scale, block_kv=min(block_kv, s),
              slots_minor=slots_minor, interpret=interpret)
    with jax.named_scope("layout"):
        return out.reshape(b, hq, d)


@jax.named_scope("covenant_ssd")
def covenant_ssd(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
                 C: jax.Array, *, chunk: int = 256,
                 init_state: jax.Array | None = None,
                 return_state: bool = False,
                 interpret: bool = False):
    """Mamba2 SSD over (b, s, h, p) inputs with (b, s, g, n) B/C.  The
    default ``chunk`` is Mamba2's ``chunk_size``; on the TPU it is a
    multiple of 128 or at least ``s``."""
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    ck = min(chunk, s)
    xf = _heads_first(x, ck)
    dtf = _heads_first(dt, ck)
    with jax.named_scope("repeat"):
        Bh = jnp.repeat(B, rep, axis=2)
        Ch = jnp.repeat(C, rep, axis=2)
    Bf = _heads_first(Bh, ck)
    Cf = _heads_first(Ch, ck)
    with jax.named_scope("repeat"):
        Af = jnp.tile(A, b)
    st0 = None
    if init_state is not None:
        with jax.named_scope("layout"):
            st0 = init_state.reshape(b * h, p, n).swapaxes(1, 2)  # (BH,N,P)
    y, fin = _ssd(xf, dtf, Af, Bf, Cf, chunk=ck, init_state=st0,
                  interpret=interpret)
    with jax.named_scope("unpad"):
        y = y[:, :s]
    with jax.named_scope("layout"):
        y = y.reshape(b, h, s, p).transpose(0, 2, 1, 3)
        if return_state:
            return y, fin.swapaxes(1, 2).reshape(b, h, p, n)
        return y


def expert_routing(x: jax.Array, router_w: jax.Array,
                   top_k: int) -> tuple[jax.Array, jax.Array]:
    """Granite's (GraniteMoeHybrid's) routing of the tokens x (T, d) over
    all experts: the f32 router logits x @ router_w, their top_k, and a
    softmax over those k logits.  Returns the experts (T, top_k) int32 and
    their gates (T, top_k) f32, in order of falling logit."""
    logits = jnp.dot(x, router_w, preferred_element_type=jnp.float32)
    top, experts = jax.lax.top_k(logits, top_k)
    return experts, jax.nn.softmax(top, axis=-1)


@jax.named_scope("covenant_experts")
def covenant_experts(x: jax.Array, router_w: jax.Array, w_in: jax.Array,
                     w_out: jax.Array, *, top_k: int, first: int,
                     n_experts: int, interpret: bool = False) -> jax.Array:
    """The part of a dropless top-k mixture of SwiGLU experts that the
    experts ``first .. first + held - 1`` give, for an expert layer that
    holds those ``held`` of the ``n_experts``.

    x (T, d) bf16; router_w (d, n_experts), over all experts; w_in (held,
    d, 2f), the gate half first (Granite's ``input_linear``); w_out (held,
    f, d).  Every token is routed over all experts (``expert_routing``);
    each (token, expert) pair whose expert is held here is computed, none
    dropped, and the rest are left to the layers that hold their experts.
    Returns the gated sum of this layer's expert outputs per token, (T, d)
    f32.  The pairs are sorted by expert into groups padded to the
    grouped GEMM's block m, which ``grouped_matmul`` runs twice, with
    SwiGLU between."""
    t, d = x.shape
    held, _, f2 = w_in.shape
    f = f2 // 2
    pairs = t * top_k
    with jax.named_scope("route"):
        experts, gates = expert_routing(x, router_w, top_k)
    # blocks for groups of the mean rows an expert gets; both GEMMs share
    # the first one's block m, which the rows are padded to
    rows = -(-pairs // n_experts)
    bm, bn1, bk1 = grouped_gemm_blocks(rows, f2, d)
    _, bn2, bk2 = grouped_gemm_blocks(rows, d, f)
    # the most row blocks the pairs can fill: every held expert that gets
    # a pair adds at most bm - 1 rows of padding
    n_rows = (pairs + min(held, pairs) * (bm - 1)) // bm * bm
    with jax.named_scope("layout"):
        local = experts.reshape(-1) - first                    # (pairs,)
        mine = (local >= 0) & (local < held)
        group = jnp.where(mine, local, held)      # pairs not held sort last
        order = jnp.argsort(group, stable=True)
        sizes = jnp.bincount(group, length=held + 1)[:held]
        starts = jnp.cumsum(sizes) - sizes
        padded = -(-sizes // bm) * bm
        offsets = jnp.concatenate([jnp.zeros(1, jnp.int32),
                                   jnp.cumsum(padded).astype(jnp.int32)])
        g_sorted = group[order]
        g_safe = jnp.minimum(g_sorted, held - 1)
        row_sorted = jnp.where(
            g_sorted < held,
            offsets[g_safe] + jnp.arange(pairs) - starts[g_safe], n_rows)
        row = jnp.zeros(pairs, jnp.int32).at[order].set(row_sorted)
        # the token of each row of the padded layout; t (a zero row) for
        # the rows that pad a group
        token = jnp.full(n_rows, t, jnp.int32).at[row_sorted].set(
            order // top_k, mode="drop")
    with jax.named_scope("pad"):
        xz = jnp.pad(x, ((0, 1), (0, 0)))
        w_in = _pad_to(_pad_to(w_in, 1, bk1), 2, bn1)
        w_out = _pad_to(_pad_to(w_out, 1, bk2), 2, bn2)
    with jax.named_scope("layout"):
        xs = xz[token]
    with jax.named_scope("pad"):
        xs = _pad_to(xs, 1, bk1)
    h = _gmm(xs, w_in, offsets, block_m=bm, block_n=bn1, block_k=bk1,
             interpret=interpret)
    with jax.named_scope("swiglu"):
        a = (jax.nn.silu(h[:, :f]) * h[:, f:f2]).astype(x.dtype)
    with jax.named_scope("pad"):
        a = _pad_to(a, 1, bk2)
    y = _gmm(a, w_out, offsets, block_m=bm, block_n=bn2, block_k=bk2,
             interpret=interpret)
    with jax.named_scope("unpad"):
        y = y[:, :d]
    with jax.named_scope("combine"):
        w = jnp.where(mine, gates.reshape(-1), 0.0)
        yp = jnp.where(mine[:, None], y[jnp.minimum(row, n_rows - 1)], 0.0)
        return (w[:, None] * yp).reshape(t, top_k, d).sum(axis=1)


# re-export oracles for convenience
matmul_ref = _ref.matmul_ref
attention_ref = _ref.attention_ref
ssd_ref = _ref.ssd_ref
experts_ref = _ref.experts_ref

__all__ = ["attention_ref", "covenant_attention", "covenant_decode_attention",
           "covenant_experts", "covenant_matmul", "covenant_ssd",
           "expert_routing", "experts_ref", "matmul_ref", "ssd_ref"]
