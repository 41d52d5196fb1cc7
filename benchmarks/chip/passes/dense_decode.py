"""The dense decoder blocks of one pipeline stage over one decode step,
through the Covenant kernels.  Per layer, pre-norm as in dense_prefill:
LayerNorm -> QKV (m = batch) -> write the new K/V row into the layer's
cache -> flash decode over the cache -> out-proj (+ residual) -> LayerNorm
-> up, gate -> SiLU(gate) * up -> down (+ residual).  The
caches, one (B, Hkv, slots, hd) pair per layer, and the lengths are the
donated state.  Taps: the last layer's attention output and the stage's
output of each checked pass, and the K/V rows that the checked passes
wrote into the first and last layers' caches, read back from them."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import counts
import reference as R
import traffic as T
from common import BF16, normal, subkey
from passes import Pass, bf16_gemms
from passes.dense_prefill import (dims, ffn, ffn_ref, gemm_shapes,
                                  kept_layers, layer_weights, make_weights,
                                  norm, residual)


def split_qkv(qkv: jax.Array, g: dict):
    """(b, (hq+2hkv)*hd) -> q (b,hq,hd), k/v (b,hkv,hd)."""
    hq, hkv, hd = g["hq"], g["hkv"], g["hd"]
    q, k, v = jnp.split(qkv, [hq * hd, (hq + hkv) * hd], axis=-1)
    b = qkv.shape[0]
    return (q.reshape(b, hq, hd), k.reshape(b, hkv, hd), v.reshape(b, hkv, hd))


def write_row(cache: jax.Array, row: jax.Array, pos: jax.Array) -> jax.Array:
    """cache[b, :, pos[b]] = row[b]: one new position per sequence."""
    return cache.at[jnp.arange(cache.shape[0]), :, pos].set(row)


def read_rows(cache: jax.Array, pos: jax.Array) -> jax.Array:
    return cache[jnp.arange(cache.shape[0]), :, pos]


def body(params, state, x, *, cfg, slots, interpret):
    from repro.kernels import ops

    g = dims(cfg)
    caches, lens = state
    written = []
    for w, (kc, vc) in zip(params["layers"], caches):
        h = norm(x)
        with jax.named_scope("gemm.qkv"):
            qkv = ops.covenant_matmul(h, w["qkv"], interpret=interpret)
        q, k, v = split_qkv(qkv.astype(BF16), g)
        with jax.named_scope("kv_write"):
            kc, vc = write_row(kc, k, lens), write_row(vc, v, lens)
        written.append((kc, vc))
        with jax.named_scope("decode"):
            a = ops.covenant_decode_attention(q, kc, vc, lens + 1,
                                              interpret=interpret)
        with jax.named_scope("gemm.out"):
            o = ops.covenant_matmul(a.reshape(x.shape[0], -1), w["out"],
                                    interpret=interpret)
        x = ffn(ops, w, residual(x, o), interpret)
    lens = jnp.where(lens + 1 >= slots, params["lens0"], lens + 1)
    return (written, lens), {"attn": a, "out": x}


def layer_ref(cfg, w, kc, vc, lens, x, low):
    g = dims(cfg)
    q, k, v = split_qkv(R.matmul(R.layer_norm(x), w["qkv"], low), g)
    kc, vc = write_row(kc, k, lens), write_row(vc, v, lens)
    a = R.decode_attention(q, kc, vc, lens + 1, low)
    o = R.rnd(x + R.matmul(a.reshape(x.shape[0], -1), w["out"], low), low)
    return kc, vc, a, ffn_ref(w, o, low)


def build(cfg: dict, traffic: dict, seed: int, key, *,
          interpret: bool = False) -> Pass:
    g = dims(cfg)
    b, slots = traffic["batch"], traffic["cache_slots"]
    lens0 = T.decode_lengths(traffic, seed)
    cache_shape = (b, g["hkv"], slots, g["hd"])

    # every draw takes the key as an argument: a key closed over would be
    # a constant of the jitted program, which would then be compiled anew
    # (and its draws folded at compile time) for every seed
    def draw_x(key, i):
        return normal(subkey(key, "x", i), (b, g["d"]))

    def draw_cache(key, layer):
        k = jax.random.fold_in(subkey(key, "cache"), layer)
        return (normal(subkey(k, "k"), cache_shape),
                normal(subkey(k, "v"), cache_shape))

    @jax.jit
    def make(key, lens0):
        params = {"layers": make_weights(cfg, key), "lens0": lens0}
        return params, [draw_cache(key, l) for l in range(g["layers"])], [
            draw_x(key, i) for i in range(traffic["inputs"])]

    params, caches, inputs = make(key, jnp.asarray(lens0))
    state = (caches, jnp.asarray(lens0))
    gemms = [counts.gemm(*shape) for shape in gemm_shapes(cfg, b)]
    kept = kept_layers(cfg)
    # the positions that the checked passes (the first two) write
    pos = [jnp.asarray(lens0 + j) for j in range(2)]

    def calls(i):
        attended = T.decode_lengths_at(lens0, i, slots) + 1
        return g["layers"] * (gemms + [
            counts.decode(g["hq"], g["hkv"], g["hd"], attended)])

    def kv_rows(kc, vc):
        return jnp.stack([read_rows(c, p) for c in (kc, vc) for p in pos])

    def inspect(state, i):
        caches = state[0]
        return {"kv": jnp.stack([kv_rows(*caches[l]) for l in kept])}

    def reference(passes, low):
        n = max(passes) + 1
        xs = [draw_x(key, i % traffic["inputs"]) for i in range(n)]
        step = jax.jit(functools.partial(layer_ref, cfg, low=low))
        out, rows = {}, []
        for l in range(g["layers"]):
            w = layer_weights(cfg, key, l)
            kc, vc = (c.astype(jnp.float32) for c in draw_cache(key, l))
            for i in range(n):
                lens = jnp.asarray(T.decode_lengths_at(lens0, i, slots))
                kc, vc, a, xs[i] = step(w, kc, vc, lens, xs[i])
                if l == g["layers"] - 1 and i in passes:
                    out[f"attn@{i}"] = a
            if l in kept:
                rows.append(kv_rows(kc, vc))
        out.update({f"out@{i}": xs[i] for i in passes})
        out["kv"] = jnp.stack(rows)
        return out

    return Pass(
        params=params, state=state, inputs=inputs,
        body=functools.partial(body, cfg=cfg, slots=slots,
                               interpret=interpret),
        calls=calls, reference=reference,
        xla_gemms=bf16_gemms(gemm_shapes(cfg, b)) * g["layers"],
        check_first=2, inspect=inspect)
