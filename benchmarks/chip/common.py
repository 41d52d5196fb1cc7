"""What every pass kind shares: seeded draws, the error of a tap, and the
record of one kernel call's work.

Weights, caches and inputs are drawn here from the run's seed, on the
device, in the dtype they are served in.  The plain references draw the
same arrays again with the same functions, so they take nothing that the
program under test has made.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

BF16 = jnp.bfloat16
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Call:
    """The work one kernel call needs, counted from its shapes by the
    algorithm (never by what an implementation moves).  ``family`` names
    the kernel: gemm, attn, decode, ssd, or state (the SSM decode step,
    which has no kernel).  ``peak`` is the key in ``peaks.json`` of the
    compute peak the call is judged against: ``bf16_flops``, or
    ``int8_ops`` for int8 operands."""
    family: str
    flops: float
    bytes: float
    peak: str = "bf16_flops"


def root_key(seed: int) -> jax.Array:
    """A key for any whole number up to 64 bits: the low and high words
    are folded in one after the other."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def subkey(key: jax.Array, *path: int | str) -> jax.Array:
    """A key named by a path of ints and strings, so that a draw does not
    depend on the order in which others are made."""
    for p in path:
        if isinstance(p, str):
            p = int.from_bytes(p.encode()[:4].ljust(4, b"\0"), "little")
        key = jax.random.fold_in(key, p)
    return key


def normal(key: jax.Array, shape, dtype=BF16, scale: float = 1.0):
    return (scale * jax.random.normal(key, shape, F32)).astype(dtype)


def int8(key: jax.Array, shape) -> jax.Array:
    """Uniform over the whole int8 range, [-128, 127]."""
    return jax.lax.bitcast_convert_type(
        jax.random.bits(key, shape, jnp.uint8), jnp.int8)


def fan_in(key: jax.Array, shape, dtype=BF16):
    """A weight of shape (fan_in, fan_out) with variance 1/fan_in, so that
    an input of unit variance gives an output of unit variance."""
    return normal(key, shape, dtype, float(shape[-2]) ** -0.5)


def host_rng(seed: int, name: str) -> np.random.Generator:
    """A host-side generator for draws that shape the traffic."""
    return np.random.default_rng([int(seed), *name.encode()])


@jax.jit
def rel_err(got: jax.Array, want: jax.Array) -> jax.Array:
    """The widest gap between a tap and its reference, over the largest
    magnitude of the reference: max |got - want| / max |want|.  A NaN
    anywhere gives NaN, which fails every limit.  Integer taps are
    compared exactly: an element that differs at all counts a gap of at
    least 1, which f32 would lose past 2**24."""
    if jnp.issubdtype(want.dtype, jnp.integer):
        gap = jnp.abs(got.astype(F32) - want.astype(F32))
        gap = jnp.where(got == want, 0.0, jnp.maximum(gap, 1.0))
        return jnp.max(gap) / jnp.max(jnp.abs(want.astype(F32)))
    got = got.astype(F32)
    want = want.astype(F32)
    return jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))


def errors(got: dict, want: dict) -> dict[str, float]:
    """rel_err of every tap that both sides have, by name."""
    out = {}
    for name in sorted(want):
        g, w = got[name], want[name]
        if g.shape != w.shape:
            raise ValueError(f"tap {name}: shape {g.shape} != {w.shape}")
        out[name] = float(rel_err(g, w))
    return out
