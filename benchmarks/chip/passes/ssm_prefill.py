"""Every layer of a Mamba2 model over a prefill batch, through the Covenant
kernels.  Per layer: in_proj -> split into z, x, B, C, dt -> SSD chunk
scan, which also gives each prompt's final state -> y * SiLU(z) ->
out_proj, added to the f32 residual stream (mamba2's ``residual_in_fp32``)
that feeds the next layer.

The state is the model's SSM state, (layers, cache_batch, h, n, p) f32,
and the next free row: each pass writes its prompts' final states into
the next ``batch`` rows of every layer, as a server fills the state that
decoding reads.  Taps: the last layer's SSD output, the model's output,
and the states that the checked pass wrote into the first and last
layers, read back from the state."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import counts
import reference as R
from common import BF16, F32, fan_in, normal, subkey
from passes import Pass, bf16_gemms

# out_proj is drawn at this multiple of fan-in scale.  No norm is run, and
# a block's output grows as the square of its input, so that 64 chained
# blocks blow up unless each adds little to the residual stream: at 0.05
# its RMS grows from 1 to about 1.06 over 64 layers of mamba2-2.7b, at 0.1
# it overflows near layer 56 (the f32 reference, batch 32, on the CPU).
OUT_GAIN = 0.05


def dims(cfg: dict) -> dict:
    return dict(d=cfg["d_model"], di=cfg["d_inner"], h=cfg["nheads"],
                p=cfg["headdim"], n=cfg["d_state"], g=cfg["ngroups"],
                nin=cfg["in_proj_size"], layers=cfg["n_layer"])


def layer_params(cfg: dict, key, layer) -> dict:
    """One layer's weights; ``layer`` may be traced (lax.map) or an int,
    and gives the same arrays either way."""
    g = dims(cfg)
    k = jax.random.fold_in(subkey(key, "layer"), layer)
    lo, hi = cfg["A_init_range"]
    dt = jnp.exp(jax.random.uniform(
        subkey(k, "dt"), (g["h"],), F32, jnp.log(cfg["dt_min"]),
        jnp.log(cfg["dt_max"])))
    return {
        "in": fan_in(subkey(k, "in"), (g["d"], g["nin"])),
        "out": (OUT_GAIN * fan_in(subkey(k, "out"), (g["di"], g["d"]),
                                  F32)).astype(BF16),
        "A": -jax.random.uniform(subkey(k, "A"), (g["h"],), F32, lo, hi),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),       # softplus^-1(dt)
    }


def make_params(cfg: dict, key) -> dict:
    """Every layer's weights, stacked on a leading axis."""
    return jax.lax.map(lambda l: layer_params(cfg, key, l),
                       jnp.arange(dims(cfg)["layers"]))


def kept_layers(cfg: dict) -> list[int]:
    """The layers whose state the check reads back: the first and last."""
    return sorted({0, dims(cfg)["layers"] - 1})


def split_in(zx: jax.Array, g: dict):
    """in_proj output (..., nin) f32 -> z, x, B, C, dt (last axis split)."""
    di, gn = g["di"], g["g"] * g["n"]
    return jnp.split(zx, [di, 2 * di, 2 * di + gn, 2 * di + 2 * gn], axis=-1)


def gate(y: jax.Array, z: jax.Array) -> jax.Array:
    return (y.astype(F32) * jax.nn.silu(z)).astype(BF16)


def first_row(i: int, traffic: dict) -> int:
    """The first state row that pass i writes."""
    return (i * traffic["batch"]) % traffic["cache_batch"]


def block(ops, cfg, traffic, params, l, u, interpret):
    """Layer l on the f32 residual stream u (b*s, d): the next u, the SSD
    output and the prompts' final states (b, h, n, p).  A weight is sliced
    from the stack inside its GEMM's scope, as the call's operand."""
    g = dims(cfg)
    b, s = traffic["batch"], traffic["seq_len"]
    with jax.named_scope("gemm.in"):
        zx = ops.covenant_matmul(u.astype(BF16), params["in"][l],
                                 interpret=interpret)
    z, xs, B, C, dt = split_in(zx, g)
    dt = jax.nn.softplus(dt + params["dt_bias"][l]).reshape(b, s, g["h"])
    with jax.named_scope("ssd"):
        y, fin = ops.covenant_ssd(
            xs.astype(BF16).reshape(b, s, g["h"], g["p"]), dt, params["A"][l],
            B.astype(BF16).reshape(b, s, g["g"], g["n"]),
            C.astype(BF16).reshape(b, s, g["g"], g["n"]),
            chunk=cfg["chunk_size"], return_state=True, interpret=interpret)
    gated = gate(y.reshape(b * s, g["di"]), z)
    with jax.named_scope("gemm.out"):
        out = ops.covenant_matmul(gated, params["out"][l],
                                  interpret=interpret)
    return u + out, y, fin.swapaxes(-1, -2)


def body(params, state, x, *, cfg, traffic, interpret):
    from repro.kernels import ops

    ssm, row = state
    last = dims(cfg)["layers"] - 1
    layer = functools.partial(block, ops, cfg, traffic, interpret=interpret)

    def write(ssm, l, fin):
        with jax.named_scope("state"):
            return jax.lax.dynamic_update_slice(ssm, fin[None].astype(F32),
                                                (l, row, 0, 0, 0))

    def step(l, carry):
        u, ssm = carry
        u, _, fin = layer(params, l, u)
        return u, write(ssm, l, fin)

    u, ssm = jax.lax.fori_loop(0, last, step, (x.astype(F32), ssm))
    u, y, fin = layer(params, last, u)
    ssm = write(ssm, last, fin)
    row = (row + traffic["batch"]) % traffic["cache_batch"]
    return (ssm, row), {"ssd": y, "out": u}


def layer_ref(cfg, traffic, w, u, low):
    g = dims(cfg)
    b, s = traffic["batch"], traffic["seq_len"]
    z, xs, B, C, dt = split_in(R.matmul(u, w["in"], low), g)
    dt = jax.nn.softplus(dt + w["dt_bias"]).reshape(b, s, g["h"])
    y, fin = R.ssd(xs.reshape(b, s, g["h"], g["p"]), dt, w["A"],
                   B.reshape(b, s, g["g"], g["n"]),
                   C.reshape(b, s, g["g"], g["n"]), low)
    out = u + R.matmul(y.reshape(b * s, g["di"]) * R.silu(z), w["out"], low)
    return out, y, fin


def gemm_shapes(cfg: dict, m: int) -> list[tuple[int, int, int]]:
    """The (m, n, k) of one layer's GEMMs."""
    g = dims(cfg)
    return [(m, g["nin"], g["d"]), (m, g["d"], g["di"])]


def build(cfg: dict, traffic: dict, seed: int, key, *,
          interpret: bool = False) -> Pass:
    g = dims(cfg)
    b, s, cb = traffic["batch"], traffic["seq_len"], traffic["cache_batch"]
    m = b * s
    if cb % b:
        raise ValueError("the prompts do not tile the state")
    state_shape = (g["layers"], cb, g["h"], g["n"], g["p"])

    # the key is an argument of every draw (see dense_decode.build)
    def draw_x(key, i):
        return normal(subkey(key, "x", i), (m, g["d"]))

    @jax.jit
    def make(key):
        return make_params(cfg, key), jnp.zeros(state_shape, F32), [
            draw_x(key, i) for i in range(traffic["inputs"])]

    params, ssm, inputs = make(key)
    calls = g["layers"] * (
        [counts.gemm(*shape) for shape in gemm_shapes(cfg, m)] + [
            counts.ssd(b, s, g["h"], g["p"], g["g"], g["n"])])
    kept = kept_layers(cfg)

    def inspect(state, i):
        at = first_row(i, traffic)
        return {"state": jnp.stack([state[0][l, at:at + b] for l in kept])}

    def reference(passes, low):
        step = jax.jit(functools.partial(layer_ref, cfg, traffic, low=low))
        out = {}
        for i in passes:
            u, fins = draw_x(key, i % traffic["inputs"]).astype(F32), []
            for l in range(g["layers"]):
                u, y, fin = step(layer_params(cfg, key, l), u)
                if l in kept:
                    fins.append(fin)
            out.update({f"ssd@{i}": y, f"out@{i}": u})
        # the state holds what the last checked pass wrote
        out["state"] = jnp.stack(fins)
        return out

    return Pass(
        params=params, state=(ssm, jnp.int32(0)), inputs=inputs,
        body=functools.partial(body, cfg=cfg, traffic=traffic,
                               interpret=interpret),
        calls=lambda i: calls, reference=reference,
        xla_gemms=bf16_gemms(gemm_shapes(cfg, m)) * g["layers"],
        inspect=inspect)
