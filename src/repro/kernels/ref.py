"""Pure-jnp oracles for every Pallas kernel (the ``ref.py`` contract).

These are the semantics each kernel is tested against (assert_allclose over
shape/dtype sweeps) and the fallbacks model code uses on hosts where the
kernel path is disabled.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def matmul_ref(a: jax.Array, b: jax.Array, out_dtype=jnp.float32) -> jax.Array:
    """C = A @ B with f32 (or i32) accumulation."""
    acc = jnp.int32 if jnp.issubdtype(out_dtype, jnp.integer) else jnp.float32
    return jnp.matmul(a, b, preferred_element_type=acc).astype(out_dtype)


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  causal: bool = True, window: int | None = None,
                  scale: float | None = None,
                  kv_len: jax.Array | None = None) -> jax.Array:
    """Multi-head attention oracle.

    q: (B, Hq, Sq, D); k, v: (B, Hkv, Sk, D) — GQA handled by head repeat.
    ``window``: sliding-window size (each query attends to the ``window``
    most recent keys, inclusive).  ``kv_len``: optional per-batch valid kv
    length (decode); keys at index >= kv_len are masked.
    """
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    if hkv != hq:
        rep = hq // hkv
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    scale = scale if scale is not None else (d ** -0.5)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    sk = k.shape[2]
    qpos = jnp.arange(sq)[:, None] + (sk - sq)  # align ends (decode offset)
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    if kv_len is not None:
        mask = mask[None] & (kpos[None] < kv_len[:, None, None])
        mask = mask[:, None]  # (B,1,Sq,Sk)
    logits = jnp.where(mask, logits, -jnp.inf)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)  # fully-masked rows
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


def ssd_ref(x: jax.Array, dt: jax.Array, A: jax.Array, B: jax.Array,
            C: jax.Array, D: jax.Array | None = None,
            init_state: jax.Array | None = None,
            return_state: bool = False):
    """Mamba2 SSD oracle: exact sequential recurrence.

    x:  (b, s, h, p)   — inputs per head
    dt: (b, s, h)      — softplus-activated step sizes (>0)
    A:  (h,)           — negative decay rates
    B:  (b, s, g, n)   — input projections (g groups, heads share groups)
    C:  (b, s, g, n)   — output projections
    D:  (h,) skip      — optional
    state: (b, h, p, n)

    h_t = exp(A dt_t) * h_{t-1} + dt_t * B_t x_t^T ;  y_t = h_t C_t
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=2)  # (b,s,h,n)
    Ch = jnp.repeat(C, rep, axis=2)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    decay = jnp.exp(A.astype(jnp.float32)[None, None, :] * dtf)  # (b,s,h)

    def step(state, inp):
        xt, bt, ct, dct, dtt = inp
        # state: (b,h,p,n)
        state = state * dct[..., None, None] + \
            (dtt[..., None] * xt)[..., None] * bt[:, :, None, :]
        yt = jnp.einsum("bhpn,bhn->bhp", state, ct)
        return state, yt

    s0 = jnp.zeros((b, h, p, n), jnp.float32) if init_state is None \
        else init_state.astype(jnp.float32)
    xs = (xf.transpose(1, 0, 2, 3), Bh.astype(jnp.float32).transpose(1, 0, 2, 3),
          Ch.astype(jnp.float32).transpose(1, 0, 2, 3),
          decay.transpose(1, 0, 2), dtf.transpose(1, 0, 2))
    final, ys = jax.lax.scan(step, s0, xs)
    y = ys.transpose(1, 0, 2, 3)  # (b,s,h,p)
    if D is not None:
        y = y + xf * D.astype(jnp.float32)[None, None, :, None]
    y = y.astype(x.dtype)
    if return_state:
        return y, final
    return y


def experts_ref(x: jax.Array, router_w: jax.Array, w_in: jax.Array,
                w_out: jax.Array, *, top_k: int, first: int,
                n_experts: int) -> jax.Array:
    """Oracle of ``ops.covenant_experts``: the part of a top-k mixture of
    SwiGLU experts that the held experts ``first .. first + held - 1`` of
    ``n_experts`` give, in f32 at ``highest`` precision, one expert at a
    time over every token.  Routing as GraniteMoeHybrid's: the top_k of
    the router logits over all experts, then a softmax over those k.
    x (T, d), router_w (d, n_experts), w_in (held, d, 2f) gate half first,
    w_out (held, f, d) -> (T, d) f32."""
    f32 = jnp.float32
    t = x.shape[0]
    held, _, f2 = w_in.shape
    f = f2 // 2
    with jax.default_matmul_precision("highest"):
        x = x.astype(f32)
        top, experts = jax.lax.top_k(x @ router_w.astype(f32), top_k)
        gates = jnp.zeros((t, n_experts), f32).at[
            jnp.arange(t)[:, None], experts].set(jax.nn.softmax(top, -1))
        out = jnp.zeros((t, w_out.shape[2]), f32)
        for e in range(held):
            h = x @ w_in[e].astype(f32)
            a = jax.nn.silu(h[:, :f]) * h[:, f:]
            out = out + gates[:, first + e, None] * (a @ w_out[e].astype(f32))
    return out


__all__ = ["attention_ref", "experts_ref", "matmul_ref", "ssd_ref"]
