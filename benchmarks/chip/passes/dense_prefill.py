"""The dense decoder blocks of one pipeline stage over a prefill batch,
through the Covenant kernels.  Per layer, pre-norm as StableLM 2:
LayerNorm -> QKV -> write the prompts' K/V into the layer's cache ->
causal GQA flash attention -> out-proj (+ residual) -> LayerNorm -> up,
gate -> SiLU(gate) * up -> down (+ residual).  GEMM outputs (f32) are cast
to bf16 between kernels.  The norms keep every layer's input at unit
scale: without them the residual stream of 5 chained blocks grows from
RMS 1 to some hundreds, the attention logits with it, and the softmax
turns so sharp that bf16 rounding picks other keys than f32 does.

The state is the stage's KV cache, one (cache_batch, Hkv, slots, hd) pair
per layer, and the next free row: each pass writes its prompts into the
next ``batch`` rows, as a server fills the cache that decoding reads.
Taps: the last layer's attention output, the stage's output, and the K/V
that the checked pass wrote into the first and last layers' caches, read
back from them."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import counts
import reference as R
from common import BF16, F32, fan_in, normal, subkey
from passes import Pass, bf16_gemms


def residual(x: jax.Array, y: jax.Array) -> jax.Array:
    """x + y, in the bf16 of the residual stream."""
    return (x.astype(F32) + y).astype(BF16)


def norm(x: jax.Array) -> jax.Array:
    """The pre-norm of a sub-block, bf16 out, as the next GEMM's operand."""
    with jax.named_scope("norm"):
        return R.layer_norm(x).astype(BF16)


def dims(cfg: dict) -> dict:
    return dict(d=cfg["hidden_size"], f=cfg["intermediate_size"],
                hq=cfg["num_attention_heads"], hkv=cfg["num_key_value_heads"],
                hd=cfg["head_dim"], layers=cfg["num_hidden_layers"])


def weight_shapes(cfg: dict) -> dict:
    g = dims(cfg)
    qkv = (g["hq"] + 2 * g["hkv"]) * g["hd"]
    return {"qkv": (g["d"], qkv), "out": (g["hq"] * g["hd"], g["d"]),
            "up": (g["d"], g["f"]), "gate": (g["d"], g["f"]),
            "down": (g["f"], g["d"])}


def layer_weights(cfg: dict, key, layer: int) -> dict:
    k = jax.random.fold_in(subkey(key, "layer"), layer)
    return {name: fan_in(subkey(k, "w", name), shape)
            for name, shape in weight_shapes(cfg).items()}


def make_weights(cfg: dict, key) -> list[dict]:
    return [layer_weights(cfg, key, l) for l in range(dims(cfg)["layers"])]


def kept_layers(cfg: dict) -> list[int]:
    """The layers whose caches the check reads back: the first and last."""
    return sorted({0, dims(cfg)["layers"] - 1})


def split_qkv(qkv: jax.Array, b: int, s: int, g: dict):
    """(b*s, (hq+2hkv)*hd) -> q (b,hq,s,hd), k/v (b,hkv,s,hd)."""
    hq, hkv, hd = g["hq"], g["hkv"], g["hd"]
    q, k, v = jnp.split(qkv, [hq * hd, (hq + hkv) * hd], axis=-1)
    heads = lambda t, h: t.reshape(b, s, h, hd).transpose(0, 2, 1, 3)
    return heads(q, hq), heads(k, hkv), heads(v, hkv)


def ffn(ops, w: dict, x: jax.Array, interpret: bool) -> jax.Array:
    """LayerNorm -> up, gate -> SiLU(gate) * up -> down, plus the residual
    x, on (m, d) bf16 rows."""
    h = norm(x)
    with jax.named_scope("gemm.up"):
        up = ops.covenant_matmul(h, w["up"], interpret=interpret)
    with jax.named_scope("gemm.gate"):
        gate = ops.covenant_matmul(h, w["gate"], interpret=interpret)
    act = (jax.nn.silu(gate) * up).astype(BF16)
    with jax.named_scope("gemm.down"):
        down = ops.covenant_matmul(act, w["down"], interpret=interpret)
    return residual(x, down)


def ffn_ref(w: dict, x: jax.Array, low) -> jax.Array:
    h = R.layer_norm(x)
    up = R.matmul(h, w["up"], low)
    gate = R.matmul(h, w["gate"], low)
    return R.rnd(x + R.matmul(R.silu(gate) * up, w["down"], low), low)


def first_row(i: int, traffic: dict) -> int:
    """The first cache row that pass i writes."""
    return (i * traffic["batch"]) % traffic["cache_batch"]


def body(params, state, x, *, cfg, traffic, interpret):
    from repro.kernels import ops

    g = dims(cfg)
    b, s = traffic["batch"], traffic["seq_len"]
    caches, row = state
    written = []
    for w, (kc, vc) in zip(params, caches):
        h = norm(x)
        with jax.named_scope("gemm.qkv"):
            qkv = ops.covenant_matmul(h, w["qkv"], interpret=interpret)
        q, k, v = split_qkv(qkv.astype(BF16), b, s, g)
        with jax.named_scope("kv_write"):
            at = (row, 0, 0, 0)
            written.append((jax.lax.dynamic_update_slice(kc, k, at),
                            jax.lax.dynamic_update_slice(vc, v, at)))
        with jax.named_scope("attn"):
            a = ops.covenant_attention(q, k, v, causal=traffic["causal"],
                                       interpret=interpret)
        a2 = a.transpose(0, 2, 1, 3).reshape(b * s, g["hq"] * g["hd"])
        with jax.named_scope("gemm.out"):
            o = ops.covenant_matmul(a2, w["out"], interpret=interpret)
        x = ffn(ops, w, residual(x, o), interpret)
    row = (row + b) % traffic["cache_batch"]
    return (written, row), {"attn": a, "out": x}


def layer_ref(cfg, traffic, w, x, low):
    g = dims(cfg)
    b, s = traffic["batch"], traffic["seq_len"]
    q, k, v = split_qkv(R.matmul(R.layer_norm(x), w["qkv"], low), b, s, g)
    a = R.attention(q, k, v, causal=traffic["causal"], low=low)
    o = R.rnd(x + R.matmul(a.transpose(0, 2, 1, 3).reshape(b * s, -1),
                           w["out"], low), low)
    return ffn_ref(w, o, low), {"attn": a, "kv": jnp.stack([k, v])}


def gemm_shapes(cfg: dict, m: int) -> list[tuple[int, int, int]]:
    """The (m, n, k) of one layer's GEMMs."""
    return [(m, n, k) for k, n in weight_shapes(cfg).values()]


def build(cfg: dict, traffic: dict, seed: int, key, *,
          interpret: bool = False) -> Pass:
    g = dims(cfg)
    b, s = traffic["batch"], traffic["seq_len"]
    m = b * s
    if traffic["cache_batch"] % b or s > traffic["cache_slots"]:
        raise ValueError("the prompts do not tile the cache")
    cache_shape = (traffic["cache_batch"], g["hkv"], traffic["cache_slots"],
                   g["hd"])

    # the key is an argument of every draw (see dense_decode.build)
    def draw_x(key, i):
        return normal(subkey(key, "x", i), (m, g["d"]))

    @jax.jit
    def make(key):
        caches = [(jnp.zeros(cache_shape, BF16), jnp.zeros(cache_shape, BF16))
                  for _ in range(g["layers"])]
        return make_weights(cfg, key), caches, [
            draw_x(key, i) for i in range(traffic["inputs"])]

    params, caches, inputs = make(key)
    state = (caches, jnp.int32(0))
    calls = g["layers"] * (
        [counts.gemm(*shape) for shape in gemm_shapes(cfg, m)] + [
            counts.attention(b, g["hq"], g["hkv"], s, s, g["hd"],
                             traffic["causal"])])
    kept = kept_layers(cfg)

    def inspect(state, i):
        caches, _ = state
        at = first_row(i, traffic)
        return {"kv": jnp.stack([jnp.stack([c[at:at + b, :, :s]
                                            for c in caches[l]])
                                 for l in kept])}

    def reference(passes, low):
        step = jax.jit(functools.partial(layer_ref, cfg, traffic, low=low))
        out = {}
        for i in passes:
            x, kv = draw_x(key, i % traffic["inputs"]), []
            for l in range(g["layers"]):
                x, taps = step(layer_weights(cfg, key, l), x)
                if l in kept:
                    kv.append(taps["kv"])
            out.update({f"attn@{i}": taps["attn"], f"out@{i}": x})
        # the cache holds what the last checked pass wrote
        out["kv"] = jnp.stack(kv)
        return out

    return Pass(
        params=params, state=state, inputs=inputs,
        body=functools.partial(body, cfg=cfg, traffic=traffic,
                               interpret=interpret),
        calls=lambda i: calls, reference=reference,
        xla_gemms=bf16_gemms(gemm_shapes(cfg, m)) * g["layers"],
        inspect=inspect)
