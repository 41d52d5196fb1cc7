"""Kernel micro-benchmarks: Pallas (interpret) vs jnp reference wall time +
Covenant-tiler BlockSpec report + compile-driver cache behaviour.  On CPU
the absolute times are meaningless for TPU perf; the interesting outputs are
the tiler-chosen block geometries, the (always asserted) numerical
agreement, and the cold-vs-cached ``repro.compile`` latencies."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

import repro
from repro.core import library as covenant_library
from repro.kernels import ops, ref
from repro.kernels.tiling import attention_blocks, gemm_blocks


def _driver_section(emit) -> None:
    """Covenant compile driver: per-target analytic cycles for a mid-size
    GEMM plus the content-addressed cache hit latency."""
    # the cold-timing clear must not wipe the sweep-wide store counters
    # that `benchmarks.run --expect-store-hits` audits at the end
    from repro.core import driver as _driver
    saved = {k: _driver._STATS[k] for k in ("store_hits", "store_misses")}
    repro.clear_cache()
    _driver._STATS.update(saved)
    for target in ("hvx", "dnnweaver"):
        t0 = time.perf_counter()
        art = repro.compile(covenant_library.gemm(64, 64, 64, in_dtype="u8"),
                            target)
        cold = (time.perf_counter() - t0) * 1e6
        t0 = time.perf_counter()
        again = repro.compile(covenant_library.gemm(64, 64, 64, in_dtype="u8"),
                              target)
        warm = (time.perf_counter() - t0) * 1e6
        assert again is art  # served from the cache, no pass re-ran
        emit(f"kernels/driver_compile_{target},{cold:.0f},"
             f"cycles={art.cycles():.0f} cached_us={warm:.0f}")


def _time(fn, *a, reps=3):
    fn(*a)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        r = fn(*a)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / reps * 1e6


def run(emit):
    rng = np.random.default_rng(0)
    _driver_section(emit)
    # tiler block selections for the paper-relevant GEMMs (Table-2 dims)
    for (m, n, k) in [(384, 4096, 1024), (384, 1024, 4096), (512, 512, 512),
                      (8192, 8192, 8192)]:
        bm, bn, bk = gemm_blocks(m, n, k)
        emit(f"kernels/gemm_blocks_{m}x{n}x{k},0,bm={bm} bn={bn} bk={bk}")
    bq, bkv = attention_blocks(4096, 4096, 128)
    emit(f"kernels/attn_blocks_4k,0,bq={bq} bkv={bkv}")

    a = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((256, 256)), jnp.float32)
    t_k = _time(lambda x, y: ops.covenant_matmul(x, y, blocks=(128, 128, 128),
                                            interpret=True),
                a, b)
    t_r = _time(lambda x, y: ref.matmul_ref(x, y), a, b)
    got = ops.covenant_matmul(a, b, blocks=(128, 128, 128), interpret=True)
    np.testing.assert_allclose(got, ref.matmul_ref(a, b), atol=1e-3)
    emit(f"kernels/matmul_256_interp,{t_k:.0f},ref_us={t_r:.0f} allclose=1")

    q = jnp.asarray(rng.standard_normal((1, 4, 128, 64)), jnp.float32)
    kk = jnp.asarray(rng.standard_normal((1, 2, 128, 64)), jnp.float32)
    vv = jnp.asarray(rng.standard_normal((1, 2, 128, 64)), jnp.float32)
    t_k = _time(lambda x, y, z: ops.covenant_attention(
        x, y, z, blocks=(64, 64), interpret=True), q, kk, vv)
    got = ops.covenant_attention(q, kk, vv, blocks=(64, 64), interpret=True)
    np.testing.assert_allclose(got, ref.attention_ref(q, kk, vv), atol=2e-3)
    emit(f"kernels/flash_attn_interp,{t_k:.0f},allclose=1")

    x = jnp.asarray(rng.standard_normal((1, 64, 4, 16)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (1, 64, 4)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2.0, (4,)), jnp.float32)
    B = jnp.asarray(rng.standard_normal((1, 64, 2, 8)), jnp.float32)
    C = jnp.asarray(rng.standard_normal((1, 64, 2, 8)), jnp.float32)
    t_k = _time(lambda *args: ops.covenant_ssd(*args, chunk=16, interpret=True),
                x, dt, A, B, C)
    got = ops.covenant_ssd(x, dt, A, B, C, chunk=16, interpret=True)
    np.testing.assert_allclose(got, ref.ssd_ref(x, dt, A, B, C), atol=2e-3)
    emit(f"kernels/ssd_scan_interp,{t_k:.0f},allclose=1")


__all__ = ["run"]
