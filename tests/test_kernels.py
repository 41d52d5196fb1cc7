"""Pallas kernels vs ref.py oracles: shape/dtype sweeps (interpret=True)."""
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core.targets import TPU_V5E
from repro.kernels import ops, ref
from repro.kernels.flash_attention import decode_blocks_read
from repro.kernels.tiling import attention_blocks, gemm_blocks

rng = np.random.default_rng(7)


def randn(*s, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(s), dtype)


# ---------------------------------------------------------------------------
# covenant tiler -> BlockSpec bridge
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mnk", [(512, 512, 512), (384, 4096, 1024),
                                 (8192, 8192, 8192), (100, 50, 30)])
def test_gemm_blocks_are_valid(mnk):
    m, n, k = mnk
    bm, bn, bk = gemm_blocks(m, n, k)
    assert bm >= 1 and bn >= 1 and bk >= 1
    # VMEM fit: one copy of the (a, b, f32 out) windows within the tiler's
    # third of the kernel's scoped limit (see core/targets.py)
    bytes_ = (bm * bk + bk * bn) * 2 + bm * bn * 4
    assert 3 * bytes_ <= TPU_V5E["vmem_limit_bytes"]
    # MXU-friendly unless the problem is smaller than one tile
    if n >= 128:
        assert bn % 128 == 0
    if k >= 128:
        assert bk % 128 == 0


def test_attention_blocks_bounded():
    bq, bkv = attention_blocks(4096, 4096, 128)
    assert bq % 8 == 0 and bkv % 128 == 0
    assert bq * bkv <= 256 * 1024


# The blocks the chip runs: every tiler call that each benchmark cell's pass
# makes (recorded while tracing the passes of benchmarks/chip/ with
# jax.eval_shape), and the int8 blocks of the 12 GEMM/FC rows of the paper's
# Table 2.  The values are the tiler's choices when the table was recorded;
# a change that moves one changes what a cell runs on the chip.
# (id, tiler, arguments, blocks); bf16 in and f32 out unless marked i8.
PINNED_BLOCKS = [
    ("stablelm-12b.prefill-4k/qkv", "gemm", (4096, 7680, 5120),
     (4096, 384, 128)),
    ("stablelm-12b.prefill-4k/out", "gemm", (4096, 5120, 5120),
     (1024, 1024, 1024)),
    ("stablelm-12b.prefill-4k/gate_up", "gemm", (4096, 13824, 5120),
     (1024, 1536, 128)),
    ("stablelm-12b.prefill-4k/down", "gemm", (4096, 5120, 13824),
     (1024, 1024, 1536)),
    ("stablelm-12b.prefill-4k/attention", "attention", (2048, 2048, 160),
     (512, 512)),
    ("stablelm-12b.decode-b32/qkv", "gemm", (32, 7680, 5120),
     (32, 7680, 128)),
    ("stablelm-12b.decode-b32/out", "gemm", (32, 5120, 5120),
     (32, 5120, 128)),
    ("stablelm-12b.decode-b32/gate_up", "gemm", (32, 13824, 5120),
     (32, 13824, 128)),
    ("stablelm-12b.decode-b32/down", "gemm", (32, 5120, 13824),
     (32, 5120, 512)),
    ("mamba2-2.7b.prefill-4k/in_proj", "gemm", (4096, 10576, 2560),
     (128, 10624, 256)),
    ("mamba2-2.7b.prefill-4k/out_proj", "gemm", (4096, 2560, 5120),
     (4096, 512, 128)),
    ("mamba2-2.7b.decode-b32/in_proj", "gemm", (32, 10576, 2560),
     (32, 10624, 256)),
    ("mamba2-2.7b.decode-b32/out_proj", "gemm", (32, 2560, 5120),
     (32, 2560, 1024)),
    ("granite-4.0-h-small.decode-b32/mamba_in", "gemm", (32, 16768, 4096),
     (32, 16768, 128)),
    ("granite-4.0-h-small.decode-b32/mamba_out", "gemm", (32, 4096, 8192),
     (32, 4096, 512)),
    ("granite-4.0-h-small.decode-b32/qkv", "gemm", (32, 6144, 4096),
     (32, 6144, 512)),
    ("granite-4.0-h-small.decode-b32/o", "gemm", (32, 4096, 4096),
     (32, 4096, 1024)),
    ("granite-4.0-h-small.decode-b32/shared_in", "gemm", (32, 3072, 4096),
     (32, 3072, 512)),
    ("granite-4.0-h-small.decode-b32/shared_out", "gemm", (32, 4096, 1536),
     (32, 4096, 384)),
    ("granite-4.0-h-small.decode-b32/experts_in", "grouped", (5, 1536, 4096),
     (16, 1536, 1024)),
    ("granite-4.0-h-small.decode-b32/experts_out", "grouped", (5, 4096, 768),
     (16, 4096, 768)),
    ("table2.gemm-int8/BERT-LG-GEMM1", "gemm_i8", (384, 4096, 1024),
     (384, 4096, 1024)),
    ("table2.gemm-int8/BERT-LG-GEMM2", "gemm_i8", (384, 1024, 4096),
     (384, 1024, 4096)),
    ("table2.gemm-int8/BERT-LG-ATN1-GEMM", "gemm_i8", (384, 64, 1024),
     (384, 128, 1024)),
    ("table2.gemm-int8/BERT-LG-ATN2-GEMM", "gemm_i8", (384, 384, 64),
     (384, 384, 128)),
    ("table2.gemm-int8/BERT-LG-ATN3-GEMM", "gemm_i8", (384, 64, 384),
     (384, 128, 384)),
    ("table2.gemm-int8/BERT-LG-ATN4-GEMM", "gemm_i8", (384, 1024, 1024),
     (384, 1024, 1024)),
    ("table2.gemm-int8/DLRM-FC1", "gemm_i8", (1, 367, 745), (8, 384, 768)),
    ("table2.gemm-int8/DLRM-FC2", "gemm_i8", (1, 512, 367), (8, 512, 384)),
    ("table2.gemm-int8/DLRM-FC3", "gemm_i8", (1, 256, 512), (8, 256, 512)),
    ("table2.gemm-int8/DLRM-FC4", "gemm_i8", (1, 1, 256), (8, 128, 256)),
    ("table2.gemm-int8/InceptionV3-FC1", "gemm_i8", (1, 1000, 2048),
     (8, 1024, 2048)),
    ("table2.gemm-int8/ResNet50-FC1", "gemm_i8", (1, 1000, 512),
     (8, 1024, 512)),
]


@pytest.mark.parametrize("tiler,args,want",
                         [p[1:] for p in PINNED_BLOCKS],
                         ids=[p[0] for p in PINNED_BLOCKS])
def test_tiler_blocks_pinned(tiler, args, want):
    from repro.kernels.tiling import BF16_SUBLANE, grouped_gemm_blocks

    if tiler == "attention":
        bq, bkv = attention_blocks(*args)
        assert (bq, bkv) == want
        assert bq % 8 == 0 and bkv % 128 == 0
        assert bq * bkv <= 256 * 1024
        return
    if tiler == "gemm_i8":
        got = gemm_blocks(*args, in_dtype="i8", acc_dtype="i32")
    elif tiler == "grouped":
        got = grouped_gemm_blocks(*args)
        assert got[0] % BF16_SUBLANE == 0
    else:
        got = gemm_blocks(*args, in_dtype="bf16")
    assert got == want
    _, n, k = args
    bm, bn, bk = got
    # VMEM fit: one copy of the (a, b, 4-byte out) windows within the
    # tiler's third of the kernel's scoped limit (see core/targets.py)
    in_bytes = 1 if tiler == "gemm_i8" else 2
    bytes_ = (bm * bk + bk * bn) * in_bytes + bm * bn * 4
    assert 3 * bytes_ <= TPU_V5E["vmem_limit_bytes"]
    # MXU-aligned on n and k
    assert bn % 128 == 0 and bk % 128 == 0
    assert bm % 8 == 0


# ---------------------------------------------------------------------------
# matmul
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mnk", [(64, 64, 64), (96, 130, 200), (8, 8, 8),
                                 (33, 17, 9), (256, 128, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_matmul_float(mnk, dtype):
    m, n, k = mnk
    a = randn(m, k, dtype=dtype)
    b = randn(k, n, dtype=dtype)
    got = ops.covenant_matmul(a, b, blocks=(32, 128, 128), interpret=True)
    want = ref.matmul_ref(a, b)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=5e-2 if dtype == jnp.bfloat16 else 1e-4,
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-5)


@pytest.mark.parametrize("mnk", [(64, 64, 64), (40, 50, 60)])
def test_matmul_int8(mnk):
    m, n, k = mnk
    a = jnp.asarray(rng.integers(-8, 8, (m, k)), jnp.int8)
    b = jnp.asarray(rng.integers(-8, 8, (k, n)), jnp.int8)
    got = ops.covenant_matmul(a, b, blocks=(32, 128, 128), interpret=True)
    want = np.asarray(a, np.int32) @ np.asarray(b, np.int32)
    np.testing.assert_array_equal(np.asarray(got), want)


def test_matmul_covenant_default_blocks():
    a = randn(300, 200)
    b = randn(200, 150)
    got = ops.covenant_matmul(a, b, interpret=True)  # tiler-chosen blocks
    np.testing.assert_allclose(got, ref.matmul_ref(a, b), atol=1e-3)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FA_CASES = [
    dict(b=2, hq=4, hkv=4, sq=64, sk=64, d=32, causal=True, win=None),
    dict(b=1, hq=8, hkv=2, sq=100, sk=100, d=16, causal=True, win=None),
    dict(b=2, hq=4, hkv=2, sq=64, sk=64, d=32, causal=True, win=16),
    dict(b=1, hq=4, hkv=4, sq=32, sk=96, d=32, causal=True, win=None),
    dict(b=1, hq=2, hkv=2, sq=48, sk=48, d=16, causal=False, win=None),
    dict(b=1, hq=4, hkv=1, sq=40, sk=40, d=64, causal=True, win=None),  # MQA
]


@pytest.mark.parametrize("case", FA_CASES)
def test_flash_attention_matches_ref(case):
    q = randn(case["b"], case["hq"], case["sq"], case["d"])
    k = randn(case["b"], case["hkv"], case["sk"], case["d"])
    v = randn(case["b"], case["hkv"], case["sk"], case["d"])
    got = ops.covenant_attention(q, k, v, causal=case["causal"],
                                 window=case["win"], blocks=(32, 128),
                                 interpret=True)
    want = ref.attention_ref(q, k, v, causal=case["causal"],
                             window=case["win"])
    np.testing.assert_allclose(got, want, atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    q = randn(1, 2, 64, 32, dtype=dtype)
    k = randn(1, 2, 64, 32, dtype=dtype)
    v = randn(1, 2, 64, 32, dtype=dtype)
    got = ops.covenant_attention(q, k, v, blocks=(32, 64), interpret=True)
    want = ref.attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=2e-2)


def pallas_operands(closed) -> list:
    """The operand shapes of the first Pallas kernel in a jaxpr, nested
    jits searched."""
    for eqn in closed.jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            return [x.aval.shape for x in eqn.invars]
        for p in eqn.params.values():
            if hasattr(p, "jaxpr") and hasattr(p, "consts"):
                found = pallas_operands(p)
                if found:
                    return found
    return []


# (s, d, block_kv, lengths): a head dim no multiple of 128 over a cache of
# 128-multiple slots is read slots-minor, (B·Hkv, D, S); any other shape
# row-major, (B·Hkv, S, D)
DECODE_CASES = {
    "slots_minor_d32": (256, 32, 64, [100, 256, 17]),
    "slots_minor_d160": (512, 160, 128, [1, 300, 512]),
    "row_major_d128": (40, 128, 16, [1, 33, 40]),
    "row_major_d32": (40, 32, 16, [40, 1, 21]),
}


@pytest.mark.parametrize("case", list(DECODE_CASES))
def test_flash_decode_matches_ref(case):
    s, d, block_kv, lens = DECODE_CASES[case]
    b, hq, hkv = 3, 8, 2
    q = randn(b, hq, d)
    k = randn(b, hkv, s, d)
    v = randn(b, hkv, s, d)
    kv_len = jnp.asarray(lens)
    call = functools.partial(ops.covenant_decode_attention,
                             block_kv=block_kv, interpret=True)
    # the scalar-prefetched lengths come first, then q, k and v
    kernel_k = pallas_operands(jax.make_jaxpr(call)(q, k, v, kv_len))[2]
    # the head dim's axis; the slots' (padded to blocks) is the other
    at = 1 if case.startswith("slots_minor") else 2
    assert kernel_k[0] == b * hkv and kernel_k[at] == d, kernel_k
    assert kernel_k[3 - at] >= s, kernel_k
    got = call(q, k, v, kv_len)
    want = ref.attention_ref(q[:, :, None, :], k, v, causal=False,
                             kv_len=kv_len)[:, :, 0, :]
    np.testing.assert_allclose(got, want, atol=2e-3)


# (s, d, block_kv, lengths): a length of 1, one on a block boundary, one
# past it, and the whole cache, in either form of the cache
STOP_CASES = {
    "slots_minor_d160": (512, 160, 128, [1, 128, 129, 512]),
    "row_major_d128": (64, 128, 16, [1, 16, 17, 64]),
}


@pytest.mark.parametrize("case", list(STOP_CASES))
def test_flash_decode_stops_at_each_length(case):
    """Every K/V block wholly past a row's last valid block is NaN: the
    walk reads none of them, so the output is the clean cache's."""
    s, d, block_kv, lens = STOP_CASES[case]
    b, hq, hkv = len(lens), 8, 2
    q = randn(b, hq, d)
    k = randn(b, hkv, s, d)
    v = randn(b, hkv, s, d)
    kv_len = jnp.asarray(lens)
    want = ref.attention_ref(q[:, :, None, :], k, v, causal=False,
                             kv_len=kv_len)[:, :, 0, :]
    for i, n in enumerate(lens):
        past = -(-n // block_kv) * block_kv
        k = k.at[i, :, past:].set(jnp.nan)
        v = v.at[i, :, past:].set(jnp.nan)
    got = ops.covenant_decode_attention(q, k, v, kv_len, block_kv=block_kv,
                                        interpret=True)
    np.testing.assert_allclose(got, want, atol=2e-3)


def test_decode_blocks_read_at_decode_b32_lengths():
    """32 sequences at 1024-6144 of 8192 slots, 8 KV heads each, as the
    decode-b32 traffic starts them (each length one past its context): at
    ``block_kv`` 512 the walk reads 240 of each head's 512 blocks."""
    ctx = 1024 + np.floor((np.arange(32) + 0.5) * (6144 - 1024) / 32)
    lens = np.repeat(ctx.astype(np.int32) + 1, 8)
    read, total = decode_blocks_read(lens, 8192, 512)
    assert (read, total) == (1920, 4096)
    assert read / total == 0.46875
    assert decode_blocks_read([0, 1, 512, 513, 9000], 1024, 512) == (7, 10)


def test_flash_window_equals_dense_when_window_covers_all():
    q, k, v = randn(1, 2, 64, 16), randn(1, 2, 64, 16), randn(1, 2, 64, 16)
    wide = ops.covenant_attention(q, k, v, causal=True, window=4096,
                                  blocks=(32, 64), interpret=True)
    dense = ops.covenant_attention(q, k, v, causal=True, window=None,
                                   blocks=(32, 64), interpret=True)
    np.testing.assert_allclose(wide, dense, atol=1e-5)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

SSD_CASES = [
    dict(b=2, s=64, h=4, p=16, g=2, n=8, chunk=16),
    dict(b=1, s=100, h=4, p=8, g=4, n=16, chunk=32),
    dict(b=2, s=33, h=2, p=8, g=1, n=4, chunk=16),
    dict(b=1, s=16, h=2, p=4, g=2, n=4, chunk=16),  # single chunk
    # 16 chunks under strong decay: the in-kernel cumsum and serial carry
    dict(b=1, s=256, h=2, p=8, g=1, n=8, chunk=16, a_max=8.0),
    # 9 chunks, the last one padded, four heads to a group
    dict(b=2, s=130, h=4, p=8, g=1, n=8, chunk=16, a_max=8.0),
    # two heads to a group, no final state asked for
    dict(b=2, s=48, h=8, p=8, g=4, n=8, chunk=16, state=False),
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_matches_sequential_ref(case):
    b, s, h, p, g, n = (case[k] for k in "bshpgn")
    x = randn(b, s, h, p)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (b, s, h)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, case.get("a_max", 2.0), (h,)),
                     jnp.float32)
    B = randn(b, s, g, n)
    C = randn(b, s, g, n)
    state = case.get("state", True)
    got = ops.covenant_ssd(x, dt, A, B, C, chunk=case["chunk"],
                           return_state=state, interpret=True)
    want, wst = ref.ssd_ref(x, dt, A, B, C, return_state=True)
    if state:
        got, st = got
        np.testing.assert_allclose(st, wst, atol=2e-3)
    np.testing.assert_allclose(got, want, atol=2e-3)


def test_ssd_init_state_continuation():
    """Splitting a sequence across two calls == one call (decode contract)."""
    b, s, h, p, g, n = 1, 64, 2, 8, 2, 8
    x = randn(b, s, h, p)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (b, s, h)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2.0, (h,)), jnp.float32)
    B, C = randn(b, s, g, n), randn(b, s, g, n)
    y_full, st_full = ops.covenant_ssd(x, dt, A, B, C, chunk=16,
                                       return_state=True, interpret=True)
    half = s // 2
    y1, st1 = ops.covenant_ssd(x[:, :half], dt[:, :half], A, B[:, :half],
                               C[:, :half], chunk=16, return_state=True,
                               interpret=True)
    y2, st2 = ops.covenant_ssd(x[:, half:], dt[:, half:], A, B[:, half:],
                               C[:, half:], chunk=16, init_state=st1,
                               return_state=True, interpret=True)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), y_full, atol=2e-3)
    np.testing.assert_allclose(st2, st_full, atol=2e-3)


@pytest.mark.parametrize("split", [24, 40, 56])
def test_ssd_init_state_continuation_off_chunk(split):
    """A split off the chunk grid: the second call's chunks (and, at 56,
    its single chunk of 8) start where the whole call's do not."""
    b, s, h, p, g, n = 1, 64, 4, 8, 2, 8
    x = randn(b, s, h, p)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (b, s, h)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.5, 2.0, (h,)), jnp.float32)
    B, C = randn(b, s, g, n), randn(b, s, g, n)
    want, wst = ref.ssd_ref(x, dt, A, B, C, return_state=True)
    y1, st1 = ops.covenant_ssd(x[:, :split], dt[:, :split], A,
                               B[:, :split], C[:, :split], chunk=16,
                               return_state=True, interpret=True)
    y2, st2 = ops.covenant_ssd(x[:, split:], dt[:, split:], A,
                               B[:, split:], C[:, split:], chunk=16,
                               init_state=st1, return_state=True,
                               interpret=True)
    np.testing.assert_allclose(jnp.concatenate([y1, y2], 1), want, atol=2e-3)
    np.testing.assert_allclose(st2, wst, atol=2e-3)


def test_ssd_decay_reduces_state_influence():
    """Sanity: large |A| (fast decay) -> final state smaller in norm."""
    b, s, h, p, g, n = 1, 32, 2, 4, 2, 4
    x = randn(b, s, h, p)
    dt = jnp.full((b, s, h), 0.1, jnp.float32)
    B, C = randn(b, s, g, n), randn(b, s, g, n)
    _, st_slow = ops.covenant_ssd(x, dt, jnp.asarray([-0.1, -0.1]), B, C,
                                  chunk=16, return_state=True,
                                  interpret=True)
    _, st_fast = ops.covenant_ssd(x, dt, jnp.asarray([-8.0, -8.0]), B, C,
                                  chunk=16, return_state=True,
                                  interpret=True)
    assert float(jnp.linalg.norm(st_fast)) < float(jnp.linalg.norm(st_slow))


# ---------------------------------------------------------------------------
# flash attention backward (Pallas)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("causal,win", [(True, None), (True, 16),
                                        (False, None)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_backward_matches_autodiff(causal, win, dtype):
    from repro.kernels.flash_attention import (flash_attention_bwd,
                                               flash_attention_fwd_lse)
    bh, s, d, bq, bkv = 2, 64, 32, 32, 32
    q = randn(bh, s, d, dtype=dtype)
    k = randn(bh, s, d, dtype=dtype)
    v = randn(bh, s, d, dtype=dtype)
    do = randn(bh, s, d, dtype=dtype)
    out, lse = flash_attention_fwd_lse(q, k, v, causal=causal, window=win,
                                       block_q=bq, block_kv=bkv,
                                       interpret=True)
    dq, dk, dv = flash_attention_bwd(q, k, v, out, lse, do, causal=causal,
                                     window=win, block_q=bq, block_kv=bkv,
                                     interpret=True)

    def loss(q_, k_, v_):
        o = ref.attention_ref(q_.reshape(1, bh, s, d),
                              k_.reshape(1, bh, s, d),
                              v_.reshape(1, bh, s, d),
                              causal=causal, window=win)
        return jnp.sum(o.reshape(bh, s, d).astype(jnp.float32)
                       * do.astype(jnp.float32))

    gd = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    for a, b in zip((dq, dk, dv), gd):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=tol)


def test_flash_fwd_lse_matches_plain_forward():
    from repro.kernels.flash_attention import (flash_attention,
                                               flash_attention_fwd_lse)
    bh, s, d = 2, 64, 32
    q, k, v = randn(bh, s, d), randn(bh, s, d), randn(bh, s, d)
    o1 = flash_attention(q, k, v, block_q=32, block_kv=32, interpret=True)
    o2, lse = flash_attention_fwd_lse(q, k, v, block_q=32, block_kv=32,
                                      interpret=True)
    np.testing.assert_allclose(o1, o2, atol=1e-5)
    assert lse.shape == (bh, s, 1)


# ---------------------------------------------------------------------------
# grouped GEMM and the expert layer
# ---------------------------------------------------------------------------


def test_grouped_gemm_blocks_are_the_tilers_at_whole_bf16_tiles():
    from repro.kernels.tiling import BF16_SUBLANE, grouped_gemm_blocks

    for rows, n, k in [(5, 1536, 4096), (5, 4096, 768), (569, 1536, 4096)]:
        bm, bn, bk = grouped_gemm_blocks(rows, n, k)
        assert bm % BF16_SUBLANE == 0
        want = gemm_blocks(-(-rows // BF16_SUBLANE) * BF16_SUBLANE, n, k)
        assert (bn, bk) == want[1:] and bm >= want[0]


# group sizes: uneven, an empty group, groups longer than one block of 16
# rows, and an empty last group
@pytest.mark.parametrize("sizes", [(3, 0, 40, 16), (0, 1, 17, 0), (33,)])
def test_grouped_matmul_matches_per_group_dot(sizes):
    from repro.kernels.grouped_matmul import grouped_matmul

    r = np.random.default_rng(11)
    bm, k, n = 16, 256, 384
    padded = [-(-s // bm) * bm for s in sizes]
    offsets = np.concatenate([[0], np.cumsum(padded)]).astype(np.int32)
    rows = int(offsets[-1]) + 2 * bm          # room for unused row blocks
    x = np.zeros((rows, k), np.float32)
    for g, s in enumerate(sizes):
        x[offsets[g]:offsets[g] + s] = r.standard_normal((s, k))
    x = jnp.asarray(x, jnp.bfloat16)
    w = jnp.asarray(r.standard_normal((len(sizes), k, n)), jnp.bfloat16)
    got = grouped_matmul(x, w, jnp.asarray(offsets), block_m=bm,
                         block_n=128, block_k=128, interpret=True)
    for g, s in enumerate(sizes):
        lo, hi = offsets[g], offsets[g + 1]
        want = jnp.dot(x[lo:hi], w[g], preferred_element_type=jnp.float32)
        np.testing.assert_allclose(np.asarray(got[lo:hi]), np.asarray(want),
                                   rtol=1e-5, atol=1e-3)


def moe_layer(seed, t=24, d=256, f=128, experts=16):
    """Seeded bf16 tokens and weights of a small expert layer, scaled so
    that each GEMM's output has unit variance."""
    r = np.random.default_rng(seed)

    def w(shape, fan_in):
        return jnp.asarray(r.standard_normal(shape) / np.sqrt(fan_in),
                           jnp.bfloat16)
    return (jnp.asarray(r.standard_normal((t, d)), jnp.bfloat16),
            w((d, experts), d), w((experts, d, 2 * f), d),
            w((experts, f, d), f))


@pytest.mark.parametrize("first,held", [(0, 16), (4, 8), (15, 1)])
def test_covenant_experts_matches_ref(first, held):
    x, router, w_in, w_out = moe_layer(3)
    part = dict(top_k=4, first=first, n_experts=16)
    got = ops.covenant_experts(x, router, w_in[first:first + held],
                               w_out[first:first + held], **part,
                               interpret=True)
    want = ref.experts_ref(x, router, w_in[first:first + held],
                           w_out[first:first + held], **part)
    assert got.shape == want.shape and got.dtype == jnp.float32
    # the SwiGLU activation is rounded to bf16 between the two GEMMs
    err = jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))
    assert float(err) < 1e-2, float(err)


def test_expert_shares_add_up_to_the_whole_layer():
    """Two chips holding experts 0-5 and 6-15 give parts that, with the
    shared expert counted once, add up to the uncut layer."""
    x, router, w_in, w_out = moe_layer(5)
    r = np.random.default_rng(6)
    s_in = jnp.asarray(r.standard_normal((256, 512)) / 16, jnp.bfloat16)
    s_out = jnp.asarray(r.standard_normal((256, 256)) / 16, jnp.bfloat16)

    def shared(x):
        h = ops.covenant_matmul(x, s_in, interpret=True)
        a = (jax.nn.silu(h[:, :256]) * h[:, 256:]).astype(jnp.bfloat16)
        return ops.covenant_matmul(a, s_out, interpret=True)

    parts = [ops.covenant_experts(x, router, w_in[lo:hi], w_out[lo:hi],
                                  top_k=4, first=lo, n_experts=16,
                                  interpret=True)
             for lo, hi in [(0, 6), (6, 16)]]
    got = parts[0] + parts[1] + shared(x)
    want = ref.experts_ref(x, router, w_in, w_out, top_k=4, first=0,
                           n_experts=16) + shared(x)
    err = jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want))
    assert float(err) < 1e-2, float(err)


def test_expert_routing_renormalises_over_the_top_k():
    x, router, _, _ = moe_layer(7)
    experts, gates = ops.expert_routing(x, router, 4)
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, rtol=1e-6)
    want = jax.lax.top_k(logits, 4)[1]
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(want, -1))
