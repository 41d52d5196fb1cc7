"""One decode step of a GraniteMoeHybrid pipeline stage (granite-4.0-h-small)
through the Covenant kernels.  Per layer, as transformers' GraniteMoeHybrid
decoder layer: RMSNorm -> the mixer -> residual + 0.22 x its output ->
RMSNorm -> the MoE block -> residual + 0.22 x its output, the residual
stream in bf16.

- A Mamba2 mixer: in_proj (m = batch) -> split into z, x, B, C, dt -> the
  SSM state step of ``ssm_decode`` -> y * SiLU(z) -> out_proj.
- The attention mixer: QKV -> write the new K/V row into the layer's cache
  -> GQA flash decode over the cache at Granite's softmax scale
  ``attention_multiplier`` (NoPE: no rotary) -> o_proj.
- The MoE block: ``covenant_experts`` over the experts this chip holds,
  routed over all of them, plus the shared SwiGLU expert, run once.

The stage's layers are the first ``num_hidden_layers`` of ``layer_types``.
A stage cut shorter than the first attention layer's depth (the CPU
tests' cut) ends at that layer instead, so that both mixers stay in it.

The donated state: each Mamba layer's f32 SSM state (b, h, n, p), each
attention layer's K/V cache pair (b, Hkv, slots, hd), the lengths, and
the experts that the last two passes routed each token to in each layer.
Taps: the last layer's expert-layer output, the attention output of the
last attention layer and the stage's output of each checked pass; the
K/V rows that the checked passes wrote into the attention layers' caches
and the state of the first and last Mamba layers, read back from the
state; and ``routing``, the largest gap of the checked passes' routing
against the reference's own top k (``pick``).

The reference is plain f32 at ``highest`` precision from the seed alone,
except that it routes as the program did wherever that routing is a
top-k of its own logits within ``EPS`` (``pick``): a token whose k-th and
(k+1)-th logits nearly tie may go either way in bf16, and the check would
otherwise compare different experts.  The control (the reference one
precision step down) stands in the program's place: its own top k of its
own logits go through ``pick`` against the f32 reference, as a program
run's do.
"""
from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp
import numpy as np

import counts
import counts_experts
import reference as R
import traffic as T
from common import BF16, F32, fan_in, normal, subkey
from passes import Pass, bf16_gemms
from passes.dense_decode import read_rows, split_qkv, write_row
from passes.ssm_decode import STATE_SCALE, state_step
from passes.ssm_prefill import gate, split_in

# How far a routing may depart from the reference's own top k (``pick``'s
# gap) and still be taken as the program's.  The router logits are of unit
# scale (an RMS-normed input against a fan-in-scaled router); the program
# computes them from its bf16 residual stream, the reference from its f32
# one.  At the cell's size on one TPU v5 lite the program's picks lay at
# most 0.031 below the reference's 10th logit (26 seeds, 16640 token
# routings), so 0.06 leaves twice that; it is the mean gap between the
# 10th and 11th of 72 unit-normal logits.  It is also the ``routing``
# tap's limit, so that a run that passes had no routing replaced.
EPS = 0.06


def dims(cfg: dict) -> dict:
    return dict(
        d=cfg["hidden_size"], di=cfg["mamba_d_inner"], h=cfg["mamba_n_heads"],
        p=cfg["mamba_d_head"], n=cfg["mamba_d_state"], g=cfg["mamba_n_groups"],
        nin=cfg["mamba_in_proj_size"], hq=cfg["num_attention_heads"],
        hkv=cfg["num_key_value_heads"], hd=cfg["head_dim"],
        f=cfg["intermediate_size"], fs=cfg["shared_intermediate_size"],
        held=cfg["num_local_experts"], first=cfg["first_held_expert"],
        experts=cfg["published"]["num_local_experts"],
        top_k=cfg["num_experts_per_tok"], scale=cfg["attention_multiplier"],
        res=cfg["residual_multiplier"], eps=cfg["rms_norm_eps"])


def stage_layers(cfg: dict) -> list[str]:
    """The mixer of each layer of the stage."""
    types, n = cfg["layer_types"], cfg["num_hidden_layers"]
    if "attention" in types[:n]:
        return list(types[:n])
    a = types.index("attention")
    return list(types[max(a - n + 1, 0):a + 1])


def kept_layers(kinds: list[str]) -> list[int]:
    """The Mamba layers (by their index among the Mamba layers) whose
    state the check reads back: the first and last."""
    return sorted({0, kinds.count("mamba") - 1})


def layer_weights(cfg: dict, key, layer, kind: str) -> dict:
    """One layer's weights; ``layer`` may be traced.  Routed expert e is
    drawn by its index among all the layer's experts, so that a chip
    holding other experts would draw its own."""
    g = dims(cfg)
    d = g["d"]
    k = jax.random.fold_in(subkey(key, "layer"), layer)
    held = g["first"] + jnp.arange(g["held"])

    def experts(name, shape):
        return jax.vmap(lambda e: fan_in(
            jax.random.fold_in(subkey(k, name), e), shape))(held)

    w = {"rout": fan_in(subkey(k, "rout"), (d, g["experts"])),
         "xin": experts("xin", (d, 2 * g["f"])),
         "xout": experts("xout", (g["f"], d)),
         "sin": fan_in(subkey(k, "sin"), (d, 2 * g["fs"])),
         "sout": fan_in(subkey(k, "sout"), (g["fs"], d))}
    if kind == "attention":
        w["qkv"] = fan_in(subkey(k, "qkv"),
                          (d, (g["hq"] + 2 * g["hkv"]) * g["hd"]))
        w["o"] = fan_in(subkey(k, "o"), (g["hq"] * g["hd"], d))
        return w
    lo, hi = cfg["A_init_range"]
    dt = jnp.exp(jax.random.uniform(
        subkey(k, "dt"), (g["h"],), F32, jnp.log(cfg["dt_min"]),
        jnp.log(cfg["dt_max"])))
    w.update({"in": fan_in(subkey(k, "in"), (d, g["nin"])),
              "out": fan_in(subkey(k, "out"), (g["di"], d)),
              "A": -jax.random.uniform(subkey(k, "A"), (g["h"],), F32, lo, hi),
              "dt_bias": dt + jnp.log(-jnp.expm1(-dt))})   # softplus^-1(dt)
    return w


def write_in_place(cache: jax.Array, row: jax.Array,
                   pos: jax.Array) -> jax.Array:
    """cache[b, :, pos[b]] = row[b]: one dynamic_update_slice of the cache
    per sequence, each in place in the cache's own layout.  (A scatter of
    all rows at once, ``dense_decode.write_row``, or the same updates in a
    loop over b, makes XLA relayout the whole cache to {3,1,2,0} and back
    around them: compiled for a described v5e.)"""
    for b in range(cache.shape[0]):
        cache = jax.lax.dynamic_update_slice(
            cache, row[b][None, :, None, :], (b, 0, pos[b], 0))
    return cache


def rms_norm(x: jax.Array, eps: float) -> jax.Array:
    """RMSNorm over the last axis in f32, at its initial gain 1."""
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def norm(x: jax.Array, eps: float) -> jax.Array:
    """The pre-norm of a sub-layer, bf16 out, as the next GEMM's operand."""
    with jax.named_scope("norm"):
        return rms_norm(x, eps).astype(BF16)


def residual(x: jax.Array, y: jax.Array, mult: float) -> jax.Array:
    """x + mult * y, in the bf16 of the residual stream."""
    return (x.astype(F32) + mult * y).astype(BF16)


def swiglu(h: jax.Array) -> jax.Array:
    """SiLU of the first half of the last axis times the second half."""
    f = h.shape[-1] // 2
    return jax.nn.silu(h[..., :f]) * h[..., f:]


def body(params, state, x, *, cfg, slots, interpret):
    from repro.kernels import ops

    g = dims(cfg)
    b = x.shape[0]
    ssm, kv, lens = list(state["ssm"]), list(state["kv"]), state["lens"]
    taps, routed = {}, []
    mamba = attention = 0
    for w, kind in zip(params["layers"], stage_layers(cfg)):
        h = norm(x, g["eps"])
        if kind == "mamba":
            with jax.named_scope("gemm.in"):
                zx = ops.covenant_matmul(h, w["in"], interpret=interpret)
            z, xs, B, C, dt = split_in(zx, g)
            dt = jax.nn.softplus(dt + w["dt_bias"])
            with jax.named_scope("state"):
                ssm[mamba], y = state_step(
                    ssm[mamba], xs.astype(BF16).reshape(b, g["h"], g["p"]),
                    dt, w["A"], B.astype(BF16).reshape(b, g["g"], g["n"]),
                    C.astype(BF16).reshape(b, g["g"], g["n"]))
            mamba += 1
            with jax.named_scope("gemm.out"):
                o = ops.covenant_matmul(gate(y.reshape(b, g["di"]), z),
                                        w["out"], interpret=interpret)
        else:
            with jax.named_scope("gemm.qkv"):
                qkv = ops.covenant_matmul(h, w["qkv"], interpret=interpret)
            q, k, v = split_qkv(qkv.astype(BF16), g)
            kc, vc = kv[attention]
            with jax.named_scope("kv_write"):
                kc = write_in_place(kc, k, lens)
                vc = write_in_place(vc, v, lens)
            kv[attention] = (kc, vc)
            attention += 1
            with jax.named_scope("decode"):
                a = ops.covenant_decode_attention(
                    q, kc, vc, lens + 1, scale=g["scale"],
                    interpret=interpret)
            taps["attn"] = a
            with jax.named_scope("gemm.o"):
                o = ops.covenant_matmul(a.reshape(b, -1), w["o"],
                                        interpret=interpret)
        x = residual(x, o, g["res"])
        h = norm(x, g["eps"])
        # the experts each token goes to, as covenant_experts routes it,
        # kept for the check
        routed.append(ops.expert_routing(h, w["rout"], g["top_k"])[0])
        with jax.named_scope("gemm.experts"):
            e = ops.covenant_experts(
                h, w["rout"], w["xin"], w["xout"], top_k=g["top_k"],
                first=g["first"], n_experts=g["experts"], interpret=interpret)
        with jax.named_scope("gemm.shared_in"):
            s = ops.covenant_matmul(h, w["sin"], interpret=interpret)
        with jax.named_scope("gemm.shared_out"):
            s = ops.covenant_matmul(swiglu(s).astype(BF16), w["sout"],
                                    interpret=interpret)
        x = residual(x, e + s, g["res"])
    taps.update(experts=e, out=x)
    lens = jnp.where(lens + 1 >= slots, params["lens0"], lens + 1)
    routes = jnp.stack([state["routes"][1], jnp.stack(routed)])
    return {"ssm": ssm, "kv": kv, "lens": lens, "routes": routes}, taps


def mixer_ref(cfg, kind, w, st, lens, x, low):
    """The mixer of one layer and its residual add, in f32: (x, the new
    state or cache pair, the attention output or None)."""
    g = dims(cfg)
    b = x.shape[0]
    h = R.rnd(rms_norm(x, g["eps"]), low)
    if kind == "mamba":
        z, xs, B, C, dt = split_in(R.matmul(h, w["in"], low), g)
        dt = jax.nn.softplus(dt + w["dt_bias"])
        st, y = R.ssm_state_step(st, xs.reshape(b, g["h"], g["p"]), dt,
                                 w["A"], B.reshape(b, g["g"], g["n"]),
                                 C.reshape(b, g["g"], g["n"]), low)
        o = R.matmul(y.reshape(b, g["di"]) * R.silu(z), w["out"], low)
        a = None
    else:
        q, k, v = split_qkv(R.matmul(h, w["qkv"], low), g)
        kc, vc = write_row(st[0], k, lens), write_row(st[1], v, lens)
        # reference.decode_attention scales by hd^-0.5: q carries the rest
        # of Granite's attention_multiplier
        a = R.decode_attention(q * (g["scale"] * g["hd"] ** 0.5), kc, vc,
                               lens + 1, low)
        o = R.matmul(a.reshape(b, -1), w["o"], low)
        st = (kc, vc)
    return R.rnd(x + g["res"] * o, low), st, a


def logits_ref(cfg, w, x, low):
    """The MoE block's input and the router logits over all experts."""
    h = R.rnd(rms_norm(x, dims(cfg)["eps"]), low)
    return h, R.matmul(h, w["rout"], low)


def experts_ref(cfg, w, h, logits, idx, low):
    """The held experts' part of the MoE block for the tokens h (T, d), each
    routed to the experts ``idx`` (T, k) with a softmax over their logits;
    every held expert computed over every token, then gated."""
    g = dims(cfg)
    gates = jax.nn.softmax(jnp.take_along_axis(logits, idx, axis=1), axis=-1)
    local = jax.nn.one_hot(idx - g["first"], g["held"], dtype=F32)
    dense = jnp.einsum("tk,tke->te", gates, local)            # (T, held)
    a = R.rnd(swiglu(jnp.einsum("td,edf->etf", h,
                                R.rnd(w["xin"], low))), low)
    y = jnp.einsum("etf,efd->etd", a, R.rnd(w["xout"], low))
    return jnp.einsum("te,etd->td", dense, y)


def moe_ref(cfg, w, x, h, logits, idx, low):
    """The MoE block and its residual add: (x, the routed experts' part)."""
    e = experts_ref(cfg, w, h, logits, idx, low)
    s = R.matmul(R.rnd(swiglu(R.matmul(h, w["sin"], low)), low), w["sout"],
                 low)
    return R.rnd(x + dims(cfg)["res"] * (e + s), low), e


def pick(logits, program, k: int, eps: float):
    """The experts the reference routes each token to.  A token's gap is
    the most by which an expert the program routed it to lies below the
    reference's own k-th largest logit, or an expert it left out lies above
    it: 0 for the reference's own top k, infinite for a routing that is not
    k distinct experts.  Where the gap is at most ``eps`` the program's
    experts, else the reference's own top k.  Returns them (T, k), the
    largest gap, and the number of tokens given the reference's own."""
    logits = np.asarray(logits)
    own = np.argsort(-logits, axis=1, kind="stable")[:, :k]
    if program is None:
        return own, 0.0, 0
    program = np.asarray(program)
    e = logits.shape[1]
    kth = np.take_along_axis(logits, own[:, -1:], axis=1)
    routed = np.zeros(logits.shape, bool)
    np.put_along_axis(routed, np.clip(program, 0, e - 1), True, axis=1)
    valid = ((program >= 0) & (program < e)).all(axis=1) & (
        routed.sum(axis=1) == k)
    below = np.where(routed, kth - logits, -np.inf).max(axis=1)
    above = np.where(routed, -np.inf, logits - kth).max(axis=1)
    gap = np.where(valid, np.maximum(below, above), np.inf)
    holds = gap <= eps
    return (np.where(holds[:, None], program, own), float(gap.max()),
            int((~holds).sum()))


def gap_tap(gap: float) -> jax.Array:
    """The reference's side of the ``routing`` tap, whose program side is
    1: rel_err reads |1 - 1/(1+gap)| / (1/(1+gap)) = gap."""
    return jnp.asarray([1.0 / (1.0 + gap)], F32)


def gemm_shapes(cfg: dict, m: int) -> dict:
    """The (m, n, k) of the dense GEMMs of a Mamba mixer, of the attention
    mixer and of an MoE block (its shared expert)."""
    g = dims(cfg)
    return {"mamba": [(m, g["nin"], g["d"]), (m, g["d"], g["di"])],
            "attention": [(m, (g["hq"] + 2 * g["hkv"]) * g["hd"], g["d"]),
                          (m, g["d"], g["hq"] * g["hd"])],
            "moe": [(m, 2 * g["fs"], g["d"]), (m, g["d"], g["fs"])]}


def pass_calls(cfg: dict, b: int, attended) -> list:
    """The work of one pass by kernel call, in the pass's order, for b
    sequences that attend to ``attended`` positions each."""
    g = dims(cfg)
    shapes = gemm_shapes(cfg, b)
    moe = [counts_experts.experts(b, g["d"], g["f"], g["experts"], g["held"],
                                  g["top_k"])] + [
        counts.gemm(*s) for s in shapes["moe"]]
    out = []
    for kind in stage_layers(cfg):
        into, outof = (counts.gemm(*s) for s in shapes[kind])
        mixer = (counts.ssm_state_step(b, g["h"], g["n"], g["p"])
                 if kind == "mamba" else
                 counts.decode(g["hq"], g["hkv"], g["hd"], attended))
        out += [into, mixer, outof] + moe
    return out


def build(cfg: dict, traffic: dict, seed: int, key, *,
          interpret: bool = False) -> Pass:
    # the program's expert layer: a tree without it fails here, before a
    # weight is drawn
    from repro.kernels.ops import covenant_experts  # noqa: F401

    g = dims(cfg)
    kinds = stage_layers(cfg)
    b, slots = traffic["batch"], traffic["cache_slots"]
    lens0 = T.decode_lengths(traffic, seed)
    state_shape = (b, g["h"], g["n"], g["p"])
    cache_shape = (b, g["hkv"], slots, g["hd"])
    n_attn = kinds.count("attention")

    # every draw takes the key as an argument (see dense_decode.build)
    def draw_x(key, i):
        return normal(subkey(key, "x", i), (b, g["d"]))

    def draw_state(key, i):
        k = jax.random.fold_in(subkey(key, "state"), i)
        return normal(k, state_shape, F32, STATE_SCALE)

    def draw_cache(key, i):
        k = jax.random.fold_in(subkey(key, "cache"), i)
        return (normal(subkey(k, "k"), cache_shape),
                normal(subkey(k, "v"), cache_shape))

    # one layer at a time, so that no more than one layer's f32 draws
    # are alive at once
    draw_layer = jax.jit(functools.partial(layer_weights, cfg),
                         static_argnums=(2,))

    def draw_weights(key, l):
        return draw_layer(key, l, kinds[l])

    @jax.jit
    def make(key):
        return ([draw_state(key, i) for i in range(len(kinds) - n_attn)],
                [draw_cache(key, i) for i in range(n_attn)],
                [draw_x(key, i) for i in range(traffic["inputs"])])

    layers = [draw_weights(key, l) for l in range(len(kinds))]
    ssm, kv, inputs = make(key)
    params = {"layers": layers, "lens0": jnp.asarray(lens0)}
    state = {"ssm": ssm, "kv": kv, "lens": jnp.asarray(lens0),
             "routes": jnp.zeros((2, len(kinds), b, g["top_k"]), jnp.int32)}

    shapes = gemm_shapes(cfg, b)
    xla_gemms = bf16_gemms(
        [s for kind in kinds for s in shapes[kind] + shapes["moe"]])

    def calls(i):
        return pass_calls(cfg, b, T.decode_lengths_at(lens0, i, slots) + 1)

    kept = kept_layers(kinds)
    # the positions that the checked passes (the first two) write
    pos = [jnp.asarray(lens0 + j) for j in range(2)]
    # the program's routing of the checked passes, (pass, layer, T, k)
    program = {}

    def kv_rows(kc, vc):
        return jnp.stack([read_rows(c, p) for c in (kc, vc) for p in pos])

    def inspect(state, i):
        program["routes"] = np.asarray(state["routes"])
        return {"kv": jnp.stack([kv_rows(*c) for c in state["kv"]]),
                "state": jnp.stack([state["ssm"][m] for m in kept]),
                "routing": jnp.ones((1,), F32)}

    def reference(passes, low):
        if low is not None:
            # the control, which the check's f32 reference (below) has
            # just run in the program's place
            done = program.pop("control", None)
            return done if done is not None else stage_ref(passes, low,
                                                            None)[0]
        routes = program.get("routes")
        if routes is None:
            # no program run behind this check: the control's, whose own
            # routing stands in for the program's
            program["control"], routes = stage_ref(passes, R.CONTROL, None)
        return stage_ref(passes, None, routes)[0]

    def stage_ref(passes, low, routes):
        """The taps of the checked passes, each token routed by ``pick``
        from ``routes`` (pass, layer, T, k) or, with None, by its own top
        k; and the routing taken, in that layout."""
        n = max(passes) + 1
        xs = [draw_x(key, i % traffic["inputs"]).astype(F32)
              for i in range(n)]
        mixer = jax.jit(functools.partial(mixer_ref, cfg, low=low),
                        static_argnums=(0,))
        route = jax.jit(functools.partial(logits_ref, cfg, low=low))
        block = jax.jit(functools.partial(moe_ref, cfg, low=low))
        out, states, rows = {}, [], []
        taken = np.zeros((n, len(kinds), b, g["top_k"]), np.int32)
        worst, off = 0.0, 0
        mamba = attention = 0
        for l, kind in enumerate(kinds):
            w = draw_weights(key, l)
            if kind == "mamba":
                st = draw_state(key, mamba)
            else:
                st = tuple(c.astype(F32) for c in draw_cache(key, attention))
            for i in range(n):
                lens = jnp.asarray(T.decode_lengths_at(lens0, i, slots))
                xs[i], st, a = mixer(kind, w, st, lens, xs[i])
                if a is not None and i in passes:
                    out[f"attn@{i}"] = a
                h, logits = route(w, xs[i])
                taken[i, l], gap, bad = pick(
                    logits, None if routes is None else routes[i, l],
                    g["top_k"], EPS)
                if i in passes:
                    worst, off = max(worst, gap), off + bad
                xs[i], e = block(w, xs[i], h, logits, jnp.asarray(taken[i, l]))
                if l == len(kinds) - 1 and i in passes:
                    out[f"experts@{i}"] = e
            if kind == "mamba":
                if mamba in kept:
                    states.append(st)
                mamba += 1
            else:
                rows.append(kv_rows(*st))
                attention += 1
        if routes is not None:
            print(f"routing: {off} of {len(passes) * len(kinds) * b} token "
                  f"routings off the reference's top {g['top_k']} by more "
                  f"than {EPS}; the largest gap {worst!r}", file=sys.stderr)
        out.update({f"out@{i}": xs[i] for i in passes})
        out["kv"] = jnp.stack(rows)
        out["state"] = jnp.stack(states)
        out["routing"] = (jnp.ones((1,), F32) if low is not None
                          else gap_tap(worst))
        return out, taken

    return Pass(
        params=params, state=state, inputs=inputs,
        body=functools.partial(body, cfg=cfg, slots=slots,
                               interpret=interpret),
        calls=calls, reference=reference, xla_gemms=xla_gemms,
        check_first=2, inspect=inspect)
