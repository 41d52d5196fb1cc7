"""Every layer of a Mamba2 model over one decode step: per layer, in_proj
(m = batch) -> split -> the SSM state step -> y * SiLU(z) -> out_proj,
added to the f32 residual stream (mamba2's ``residual_in_fp32``) that
feeds the next layer.  The state step is this benchmark's own jnp, as
``models/ssm.py`` decodes (no Covenant kernel computes it), so its time
belongs to the harness, not to the program under test.  The f32 state of
every layer (L, b, h, n, p) is the donated state.  Taps: the model's
output of each checked pass, and the state of the first and last layers
after them."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import counts
import reference as R
from common import BF16, F32, normal, subkey
from passes import Pass, bf16_gemms
from passes.ssm_prefill import (dims, gate, gemm_shapes, kept_layers,
                                layer_params, make_params, split_in)

# the initial state's scale: about that of the state that decoding these
# weights settles to
STATE_SCALE = 0.1


def state_step(ssm, xs, dt, A, B, C):
    """ssm (b,h,n,p) f32; xs (b,h,p); dt (b,h); A (h,); B/C (b,g,n)."""
    h, g = ssm.shape[1], B.shape[1]
    Bh = jnp.repeat(B.astype(F32), h // g, axis=1)
    Ch = jnp.repeat(C.astype(F32), h // g, axis=1)
    new = ssm * jnp.exp(A * dt)[..., None, None] + \
        Bh[..., None] * (xs.astype(F32) * dt[..., None])[:, :, None, :]
    return new, jnp.einsum("bhnp,bhn->bhp", new, Ch)


def body(params, state, x, *, cfg, interpret):
    from repro.kernels import ops

    g = dims(cfg)
    b = x.shape[0]

    def layer(l, carry):
        u, ssm = carry
        h = u.astype(BF16)
        with jax.named_scope("gemm.in"):
            zx = ops.covenant_matmul(h, params["in"][l], interpret=interpret)
        z, xs, B, C, dt = split_in(zx, g)
        dt = jax.nn.softplus(dt + params["dt_bias"][l])
        with jax.named_scope("state"):
            new, y = state_step(
                ssm[l], xs.astype(BF16).reshape(b, g["h"], g["p"]), dt,
                params["A"][l], B.astype(BF16).reshape(b, g["g"], g["n"]),
                C.astype(BF16).reshape(b, g["g"], g["n"]))
            ssm = ssm.at[l].set(new)
        gated = gate(y.reshape(b, g["di"]), z)
        with jax.named_scope("gemm.out"):
            out = ops.covenant_matmul(gated, params["out"][l],
                                      interpret=interpret)
        return u + out, ssm

    u, ssm = jax.lax.fori_loop(0, g["layers"], layer,
                               (x.astype(F32), state))
    return ssm, {"out": u}


def layer_ref(cfg, w, ssm, u, low):
    g = dims(cfg)
    b = u.shape[0]
    z, xs, B, C, dt = split_in(R.matmul(u, w["in"], low), g)
    dt = jax.nn.softplus(dt + w["dt_bias"])
    new, y = R.ssm_state_step(ssm, xs.reshape(b, g["h"], g["p"]), dt, w["A"],
                              B.reshape(b, g["g"], g["n"]),
                              C.reshape(b, g["g"], g["n"]), low)
    return new, u + R.matmul(y.reshape(b, g["di"]) * R.silu(z), w["out"], low)


def build(cfg: dict, traffic: dict, seed: int, key, *,
          interpret: bool = False) -> Pass:
    g = dims(cfg)
    b, layers = traffic["batch"], g["layers"]
    state_shape = (b, g["h"], g["n"], g["p"])

    # the key is an argument of every draw (see dense_decode.build)
    def draw_x(key, i):
        return normal(subkey(key, "x", i), (b, g["d"]))

    def draw_state(key, layer):
        k = jax.random.fold_in(subkey(key, "state"), layer)
        return normal(k, state_shape, F32, STATE_SCALE)

    @jax.jit
    def make(key):
        params = make_params(cfg, key)
        state = jax.lax.map(lambda l: draw_state(key, l), jnp.arange(layers))
        return params, state, [draw_x(key, i)
                               for i in range(traffic["inputs"])]

    params, state, inputs = make(key)
    per_layer = [counts.gemm(*shape) for shape in gemm_shapes(cfg, b)] + [
        counts.ssm_state_step(b, g["h"], g["n"], g["p"])]
    calls = per_layer * layers
    kept = kept_layers(cfg)

    def inspect(state, i):
        return {"state": state[jnp.asarray(kept)]}

    def reference(passes, low):
        n = max(passes) + 1
        us = [draw_x(key, i % traffic["inputs"]) for i in range(n)]
        step = jax.jit(functools.partial(layer_ref, cfg, low=low))
        states = []
        for l in range(layers):
            w = layer_params(cfg, key, l)
            ssm = draw_state(key, l)
            for i in range(n):
                ssm, us[i] = step(w, ssm, us[i])
            if l in kept:
                states.append(ssm)
        out = {f"out@{i}": us[i] for i in passes}
        out["state"] = jnp.stack(states)
        return out

    return Pass(
        params=params, state=state, inputs=inputs,
        body=functools.partial(body, cfg=cfg, interpret=interpret),
        calls=lambda i: calls, reference=reference,
        xla_gemms=bf16_gemms(gemm_shapes(cfg, b)) * layers, check_first=2,
        inspect=inspect)
