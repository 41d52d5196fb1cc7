"""The BERT-Large rows of the paper's Table 2, run as the encoder chains
them: every layer of BERT-Large over a batch of 384-token sequences, int8
in and int32 out (the paper's §5.1), each row through ``covenant_matmul``
at the shape the table gives it, one sequence (and head) a grid step of
one batched kernel call.  Per layer, h the head:

    ATN1   q, k, v = x W_q,h, x W_k,h, x W_v,h   (384, 64, 1024) each
    ATN2   s = q kᵀ                              (384, 384, 64)
    ATN3   a = s v                               (384, 64, 384)
    ATN4   o = a W_o, the heads side by side     (384, 1024, 1024)
    GEMM1  u = relu(o W_1)                       (384, 4096, 1024)
    GEMM2  x' = u W_2                            (384, 1024, 4096)

Between two rows each int32 product goes back to int8 by
``reference.requant``, a per-token dynamic scale.  That integer step stands
in for the float model's softmax, GELU and LayerNorm (nor are there
residual adds), so that the whole chain is exact and the check can ask for
every bit.  Weights are drawn over the whole int8 range, one set a layer,
from the seed.

The state is the buffer the encoder's output is written into, where the
next stage reads it.  Taps: the last layer's GEMM2 products (``out``) and
the output in the state (``state``), all sequences, and every row's
products in the first layer for the first sequence, by the row's name.
The reference computes them from the seed in exact integer arithmetic, so
every limit is 0."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

import counts
import reference as R
from common import int8, subkey
from passes import Pass

# Table 2's rows, in the order a layer runs them
ROWS = ("BERT-LG-ATN1-GEMM", "BERT-LG-ATN2-GEMM", "BERT-LG-ATN3-GEMM",
        "BERT-LG-ATN4-GEMM", "BERT-LG-GEMM1", "BERT-LG-GEMM2")
# sequences the reference runs at a time
REF_BLOCK = 32
# sequences a GEMM of XLA's own lowering of the pass covers
XLA_BLOCK = 64


def dims(cfg: dict) -> dict:
    g = dict(d=cfg["hidden_size"], h=cfg["num_attention_heads"],
             hd=cfg["head_dim"], f=cfg["intermediate_size"],
             layers=cfg["num_hidden_layers"], s=cfg["seq_len"])
    s, d, hd, f, h = g["s"], g["d"], g["hd"], g["f"], g["h"]
    rows = {r["name"]: (r["m"], r["n"], r["k"], r["heads"])
            for r in cfg["rows"]}
    if rows != dict(zip(ROWS, [(s, hd, d, h), (s, s, hd, h), (s, hd, s, h),
                               (s, d, d, 1), (s, f, d, 1), (s, d, f, 1)])):
        raise ValueError(f"the rows {rows} are not the layer's GEMMs")
    if h * hd != d:
        raise ValueError("the heads do not tile the hidden size")
    return g


def weight_shapes(cfg: dict) -> dict:
    g = dims(cfg)
    return {"qkv": (3 * g["h"], g["d"], g["hd"]), "o": (g["d"], g["d"]),
            "up": (g["d"], g["f"]), "down": (g["f"], g["d"])}


def layer_weights(cfg: dict, key, layer: int) -> dict:
    k = jax.random.fold_in(subkey(key, "layer"), layer)
    return {name: int8(subkey(k, "w", name), shape)
            for name, shape in weight_shapes(cfg).items()}


def draw_x(cfg: dict, traffic: dict, key, i: int) -> jax.Array:
    g = dims(cfg)
    return int8(subkey(key, "x", i), (traffic["batch"], g["s"], g["d"]))


def body(params, state, x, *, interpret):
    from repro.kernels import ops

    def mm(a, b):
        return ops.covenant_matmul(a, b, interpret=interpret)

    # one kernel call a row: sequences (and heads) on the grid's outer axes
    over_heads = jax.vmap(mm, in_axes=(None, 0))
    seqs_heads = jax.vmap(over_heads, in_axes=(0, None))
    pairs = jax.vmap(jax.vmap(mm))
    seqs = jax.vmap(mm, in_axes=(0, None))
    first = {}
    for w in params:
        b, s, d = x.shape
        with jax.named_scope(f"gemm.{ROWS[0]}"):
            qkv = seqs_heads(x, w["qkv"])                 # (b, 3h, s, hd)
        q, k, v = jnp.split(R.requant(qkv), 3, axis=1)
        with jax.named_scope(f"gemm.{ROWS[1]}"):
            sc = pairs(q, k.swapaxes(-1, -2))            # (b, h, s, s)
        with jax.named_scope(f"gemm.{ROWS[2]}"):
            a = pairs(R.requant(sc), v)                  # (b, h, s, hd)
        heads = R.requant(a).transpose(0, 2, 1, 3).reshape(b, s, d)
        with jax.named_scope(f"gemm.{ROWS[3]}"):
            o = seqs(heads, w["o"])
        with jax.named_scope(f"gemm.{ROWS[4]}"):
            u = seqs(R.requant(o), w["up"])
        with jax.named_scope(f"gemm.{ROWS[5]}"):
            y = seqs(R.requant(jnp.maximum(u, 0)), w["down"])
        x = R.requant(y)
        if not first:
            first = dict(zip(ROWS, (t[0] for t in (qkv, sc, a, o, u, y))))
    # written over the donated buffer: a state the pass did not read would
    # be pruned by jit, and its buffer not reused
    state = jax.lax.dynamic_update_slice(state, x, (0, 0, 0))
    return state, dict(first, out=y)


def layer_ref(w, x, low):
    """One layer over (b, s, d) int8 sequences, exactly: the next layer's
    input, the GEMM2 products, and each row's products for the first
    sequence."""
    b, s, d = x.shape
    mm = functools.partial(R.int_einsum, low=low)
    qkv = mm("bsd,hdn->bhsn", x, w["qkv"])
    q, k, v = jnp.split(R.requant(qkv), 3, axis=1)
    sc = mm("bhsn,bhtn->bhst", q, k)
    a = mm("bhst,bhtn->bhsn", R.requant(sc), v)
    a2 = R.requant(a).transpose(0, 2, 1, 3).reshape(b, s, d)
    o = mm("bsd,de->bse", a2, w["o"])
    u = mm("bsd,df->bsf", R.requant(o), w["up"])
    y = mm("bsf,fd->bsd", R.requant(jnp.maximum(u, 0)), w["down"])
    first = dict(zip(ROWS, (t[0] for t in (qkv, sc, a, o, u, y))))
    return R.requant(y), y, first


def gemm_shapes(cfg: dict, b: int) -> list[tuple]:
    """One layer's GEMMs over b sequences as the algorithm needs them, as
    (m, n, k, dtype, batch): the three projections of all heads as one
    (each sequence's x read once), the per-head products with operands of
    their own, the dense rows with the batch's tokens as m."""
    g = dims(cfg)
    m, s, hd, d, f = b * g["s"], g["s"], g["hd"], g["d"], g["f"]
    bh = b * g["h"]
    return [(m, 3 * d, d, "int8", 1), (s, s, hd, "int8", bh),
            (s, hd, s, "int8", bh), (m, d, d, "int8", 1),
            (m, f, d, "int8", 1), (m, d, f, "int8", 1)]


def xla_shapes(cfg: dict, b: int) -> list[tuple]:
    """One layer's GEMMs as the pass calls them, for XLA: a head's
    projection over the tokens (3h of them), then as counted; each over a
    block of ``XLA_BLOCK`` sequences, as many times as the batch holds
    one, so that XLA's operands and products fit beside the pass's."""
    g = dims(cfg)
    blk = math.gcd(b, XLA_BLOCK)
    head = (blk * g["s"], g["hd"], g["d"], "int8", 1)
    return ([head] * (3 * g["h"]) + gemm_shapes(cfg, blk)[1:]) * (b // blk)


def build(cfg: dict, traffic: dict, seed: int, key, *,
          interpret: bool = False) -> Pass:
    g = dims(cfg)
    b = traffic["batch"]

    # the key is an argument of every draw (see dense_decode.build)
    @jax.jit
    def make(key):
        return ([layer_weights(cfg, key, l) for l in range(g["layers"])],
                jnp.zeros((b, g["s"], g["d"]), jnp.int8),
                [draw_x(cfg, traffic, key, i)
                 for i in range(traffic["inputs"])])

    params, state, inputs = make(key)
    calls = [counts.gemm(*s) for s in gemm_shapes(cfg, b)] * g["layers"]

    def inspect(state, i):
        return {"state": jnp.copy(state)}

    def reference(passes, low):
        step = jax.jit(functools.partial(layer_ref, low=low))
        wj = jax.jit(functools.partial(layer_weights, cfg))
        xj = jax.jit(functools.partial(draw_x, cfg, traffic))
        out = {}
        for i in passes:
            x0 = xj(key, i % traffic["inputs"])
            xs, ys = [], []
            for lo in range(0, b, REF_BLOCK):
                x = x0[lo:lo + REF_BLOCK]
                for l in range(g["layers"]):
                    x, y, first = step(wj(key, l), x)
                    if l == 0 and lo == 0:
                        out.update({f"{t}@{i}": v for t, v in first.items()})
                xs.append(x)
                ys.append(y)
            out[f"out@{i}"] = jnp.concatenate(ys)
        # the state holds what the last checked pass wrote
        out["state"] = jnp.concatenate(xs)
        return out

    return Pass(
        params=params, state=state, inputs=inputs,
        body=functools.partial(body, interpret=interpret),
        calls=lambda i: calls, reference=reference,
        xla_gemms=xla_shapes(cfg, b) * g["layers"], inspect=inspect)
