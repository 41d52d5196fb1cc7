"""Shared building blocks: initializers, norms, RoPE, MLPs, embeddings.

Everything is a pure function over explicit parameter pytrees (no flax);
``init_*`` builders return nested dicts, ``apply``-style functions consume
them.  Compute happens in ``cfg.compute_dtype``; normalization statistics
and softmax always in f32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .config import ArchConfig


def cdt(cfg: ArchConfig):
    return jnp.dtype(cfg.compute_dtype)


def pdt(cfg: ArchConfig):
    return jnp.dtype(cfg.param_dtype)


# ---------------------------------------------------------------------------
# activation sharding (sequence parallelism for the residual stream)
# ---------------------------------------------------------------------------

# configured by the launcher/dry-run (requires an ambient mesh); tests and
# single-device runs leave it unset -> no-op.
_ACT_AXES: dict = {"batch": None, "seq": None, "heads": None, "vocab": None}


def current_mesh():
    """The ambient mesh set by ``jax.set_mesh``, or None when there is
    none."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def configure_activation_sharding(batch_axes=None, seq_axes=None,
                                  heads_axes=None, vocab_axes=None) -> None:
    """E.g. batch_axes=("pod","data"), seq_axes="model", heads_axes="model".
    ``seq`` shards the residual stream (sequence parallelism); ``heads``
    forces Megatron-style head-parallel attention; ``vocab`` keeps logits
    and their gradients vocab-sharded through the loss.  All None ->
    disabled."""
    _ACT_AXES["batch"] = batch_axes
    _ACT_AXES["seq"] = seq_axes
    _ACT_AXES["heads"] = heads_axes
    _ACT_AXES["vocab"] = vocab_axes


def shard_act(x: jax.Array, logical: tuple) -> jax.Array:
    """Constrain an activation; ``logical`` entries are "batch"/"seq"/
    "heads"/None per dim.  No-op unless configure_activation_sharding was
    called inside a mesh context.  A "heads" dim not divisible by its mesh
    axis falls back to unsharded."""
    if all(v is None for v in _ACT_AXES.values()):
        return x
    from jax.sharding import PartitionSpec as P

    spec = []
    for d, l in enumerate(logical):
        ax = _ACT_AXES.get(l) if isinstance(l, str) else None
        if ax is not None:
            import numpy as _np
            mesh = current_mesh()
            if mesh is None:
                ax = None
            else:
                size = int(_np.prod([mesh.shape[a] for a in
                                     ((ax,) if isinstance(ax, str) else ax)]))
                if x.shape[d] % size != 0 or x.shape[d] < size:
                    ax = None
        spec.append(ax)
    return jax.lax.with_sharding_constraint(x, P(*spec))


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------


def dense_init(key, shape, dtype, in_axis: int = 0) -> jax.Array:
    fan_in = shape[in_axis] if isinstance(in_axis, int) else \
        math.prod(shape[a] for a in in_axis)
    std = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def embed_init(key, shape, dtype) -> jax.Array:
    return (jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def keygen(key):
    while True:
        key, sub = jax.random.split(key)
        yield sub


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def init_norm(cfg: ArchConfig, dim: int | None = None) -> dict:
    d = dim or cfg.d_model
    p = {"scale": jnp.ones((d,), pdt(cfg))}
    if cfg.norm == "layernorm":
        p["bias"] = jnp.zeros((d,), pdt(cfg))
    return p


def apply_norm(cfg: ArchConfig, p: dict, x: jax.Array,
               eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.var(xf, -1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + eps)
        out = out * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:
        ms = jnp.mean(jnp.square(xf), -1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + eps) * p["scale"].astype(jnp.float32)
    return out.astype(x.dtype)


def rms_head_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6):
    """qwen3 qk-norm: RMS over the head_dim of (..., H, S, D) tensors."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), -1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)
            ).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings (with partial-rotary support)
# ---------------------------------------------------------------------------


def rope_frequencies(cfg: ArchConfig, positions: jax.Array) -> tuple:
    """(sin, cos) of shape (..., rot_dim/2) for given positions."""
    rot = int(cfg.hd * cfg.rope_frac)
    rot -= rot % 2
    inv = 1.0 / (cfg.rope_theta ** (jnp.arange(0, rot, 2, jnp.float32) / rot))
    ang = positions.astype(jnp.float32)[..., None] * inv  # (..., rot/2)
    return jnp.sin(ang), jnp.cos(ang)


def apply_rope(x: jax.Array, sin: jax.Array, cos: jax.Array) -> jax.Array:
    """x: (B, H, S, D); sin/cos: (B, S, rot/2) or (S, rot/2)."""
    rot2 = sin.shape[-1]
    rot = rot2 * 2
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    if sin.ndim == 2:
        s = sin[None, None]
        c = cos[None, None]
    else:
        s = sin[:, None]
        c = cos[:, None]
    s, c = s.astype(jnp.float32), c.astype(jnp.float32)
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    o1 = x1f * c - x2f * s
    o2 = x2f * c + x1f * s
    out = jnp.stack([o1, o2], axis=-1).reshape(xr.shape).astype(x.dtype)
    return jnp.concatenate([out, xp], -1) if xp.shape[-1] else out


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_mlp(cfg: ArchConfig, key, d_ff: int | None = None,
             d_model: int | None = None) -> dict:
    ks = keygen(key)
    dm = d_model or cfg.d_model
    ff = d_ff or cfg.d_ff
    dtype = pdt(cfg)
    if cfg.mlp in ("swiglu", "geglu"):
        return {
            "wi": dense_init(next(ks), (dm, ff), dtype),
            "wg": dense_init(next(ks), (dm, ff), dtype),
            "wo": dense_init(next(ks), (ff, dm), dtype),
        }
    return {
        "wi": dense_init(next(ks), (dm, ff), dtype),
        "wo": dense_init(next(ks), (ff, dm), dtype),
    }


def apply_mlp(cfg: ArchConfig, p: dict, x: jax.Array) -> jax.Array:
    h = x @ p["wi"].astype(x.dtype)
    if cfg.mlp == "swiglu":
        h = jax.nn.silu(h) * (x @ p["wg"].astype(x.dtype))
    elif cfg.mlp == "geglu":
        h = jax.nn.gelu(h) * (x @ p["wg"].astype(x.dtype))
    else:
        h = jax.nn.gelu(h)
    return h @ p["wo"].astype(x.dtype)


# ---------------------------------------------------------------------------
# embeddings / logits
# ---------------------------------------------------------------------------


def init_embed(cfg: ArchConfig, key) -> dict:
    ks = keygen(key)
    p = {"tokens": embed_init(next(ks), (cfg.vocab, cfg.d_model), pdt(cfg))}
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(next(ks), (cfg.d_model, cfg.vocab), pdt(cfg))
    return p


def embed_tokens(cfg: ArchConfig, p: dict, tokens: jax.Array) -> jax.Array:
    x = p["tokens"].astype(cdt(cfg))[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), cdt(cfg))
    return x


def logits_from_hidden(cfg: ArchConfig, p: dict, x: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        w = p["tokens"].astype(cdt(cfg)).T
    else:
        w = p["unembed"].astype(cdt(cfg))
    logits = (x @ w).astype(jnp.float32)
    if cfg.logit_softcap:
        c = cfg.logit_softcap
        logits = jnp.tanh(logits / c) * c
    return shard_act(logits, ("batch",) + (None,) * (logits.ndim - 2)
                     + ("vocab",))


def cross_entropy(logits: jax.Array, targets: jax.Array,
                  weights: jax.Array | None = None) -> jax.Array:
    """Mean next-token CE; logits (B,S,V), targets (B,S).

    Written without ``take_along_axis`` so a vocab-sharded logits tensor
    stays sharded: the picked logit is a masked sum (iota compare) and the
    normaliser a logsumexp — both partition cleanly under GSPMD."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    onehot = (targets[..., None] ==
              jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                       logits.ndim - 1))
    picked = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
    ll = picked - lse
    if weights is None:
        weights = jnp.ones_like(ll)
    return -(ll * weights).sum() / jnp.maximum(weights.sum(), 1.0)


__all__ = ["apply_mlp", "apply_norm", "apply_rope", "cdt", "cross_entropy",
           "dense_init", "embed_init", "embed_tokens", "init_embed",
           "init_mlp", "init_norm", "keygen", "logits_from_hidden", "pdt",
           "rms_head_norm", "rope_frequencies"]
