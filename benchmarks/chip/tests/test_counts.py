"""The yardstick against hand-worked values for the four cells' shapes."""
import json
import os

import numpy as np
import pytest

import counts
import traffic as T
from passes import dense_prefill, ssm_prefill

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V5E = counts.peaks("TPU v5 lite")


def config(name):
    with open(os.path.join(CHIP, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        counts.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        counts.peaks("source")


def test_v5e_peaks():
    assert V5E["bf16_flops"] == 197e12
    assert V5E["int8_ops"] == 393e12
    assert V5E["hbm_bytes_per_s"] == 819e9


def test_stablelm_prefill_pass_flops():
    # 2 mnk over QKV (7680), out (5120), up and gate (13824), down (5120)
    # at m = 4096, k = 5120 (down: k = 13824), plus causal attention:
    # 4 * 2048^2 * 160 * 32 heads / 2 per sequence, 2 sequences
    cfg = config("stablelm-12b")
    calls = [counts.gemm(*s) for s in dense_prefill.gemm_shapes(cfg, 4096)]
    gemm = 2 * 4096 * 5120 * (7680 + 5120 + 2 * 13824 + 13824)
    assert sum(c.flops for c in calls) == gemm
    attn = counts.attention(2, 32, 8, 2048, 2048, 160, causal=True)
    assert attn.flops == 2 * 4 * 2048 ** 2 * 160 * 32 / 2
    total = gemm + attn.flops
    assert total == pytest.approx(2.362e12, rel=1e-3)
    assert total / V5E["bf16_flops"] == pytest.approx(12.0e-3, rel=1e-2)


def test_gemm_bytes_are_bf16_a_b_c():
    c = counts.gemm(4096, 7680, 5120)
    assert c.bytes == 2 * (4096 * 5120 + 5120 * 7680 + 4096 * 7680)
    assert c.peak == "bf16_flops"
    assert counts.roofline_s(c, V5E) == c.flops / 197e12


def test_int8_gemm_is_one_one_four_bytes_at_the_int8_peak():
    c = counts.gemm(384, 4096, 1024, "int8")
    assert c.flops == 2 * 384 * 4096 * 1024
    assert c.bytes == 384 * 1024 + 1024 * 4096 + 4 * 384 * 4096
    assert c.peak == "int8_ops"
    assert counts.roofline_s(c, V5E) == c.bytes / 819e9
    # compute-bound at m = 4096: judged against 393e12, not 197e12
    big = counts.gemm(4096, 4096, 4096, "int8")
    assert counts.roofline_s(big, V5E) == big.flops / 393e12


def test_batched_gemm_is_batch_gemms():
    one = counts.gemm(384, 384, 64, "int8")
    many = counts.gemm(384, 384, 64, "int8", 8192)
    assert (many.flops, many.bytes, many.peak) == (
        8192 * one.flops, 8192 * one.bytes, "int8_ops")
    assert counts.gemm(32, 256, 128, "bf16", 1) == counts.gemm(32, 256, 128)


def test_other_counts_are_at_the_bf16_peak():
    for c in (counts.attention(2, 32, 8, 2048, 2048, 160, causal=True),
              counts.decode(32, 8, 160, [1024] * 32),
              counts.ssd(2, 2048, 80, 64, 1, 128),
              counts.ssm_state_step(32, 80, 128, 64)):
        assert c.peak == "bf16_flops"


def test_stablelm_decode_floor():
    # weights of one block, 0.556 GB, and the valid K/V of 32 sequences at
    # a mean of 3584 tokens over 8 KV heads of 160, 0.587 GB: 1.4 ms
    cfg = config("stablelm-12b")
    weights = sum(counts.gemm(*s).bytes - 2 * 32 * (s[1] + s[2])
                  for s in dense_prefill.gemm_shapes(cfg, 32))
    assert weights == pytest.approx(0.556e9, rel=1e-2)
    lens = T.decode_lengths(T.load("decode-b32"), 12345)
    assert lens.sum() == pytest.approx(32 * 3584, rel=1e-3)
    dec = counts.decode(32, 8, 160, lens)
    assert dec.flops == 4 * 160 * 32 * lens.sum()
    kv = 2 * 2 * 8 * 160 * lens.sum()
    assert dec.bytes == kv + 2 * 2 * 32 * 32 * 160
    assert kv == pytest.approx(0.587e9, rel=1e-2)
    floor = (weights + dec.bytes) / V5E["hbm_bytes_per_s"]
    assert floor == pytest.approx(1.4e-3, rel=3e-2)


def test_mamba_prefill_gemm_floor():
    cfg = config("mamba2-2.7b")
    flops = sum(counts.gemm(*s).flops
                for s in ssm_prefill.gemm_shapes(cfg, 4096))
    assert flops == 2 * 4096 * (2560 * 10576 + 5120 * 2560)
    assert flops / V5E["bf16_flops"] == pytest.approx(1.67e-3, rel=1e-2)


def test_ssd_counts_at_reference_chunk():
    # 2 x 2048 tokens, 80 heads of 64, one group of 128: 8 chunks of 256
    c = counts.ssd(2, 2048, 80, 64, 1, 128)
    L, n, p = 256, 128, 64
    per_chunk = 2 * L * L * n + 80 * (2 * L * L * p + 4 * L * n * p + 2 * n * p)
    assert c.flops == 2 * 8 * per_chunk
    assert c.bytes == 2 * (2 * 2 * 2048 * 80 * 64 + 2 * 2 * 2048 * 128) \
        + 4 * 2 * 2048 * 80


def test_mamba_decode_floor():
    # 64 layers of weights, 5.15 GB, and the f32 state read and written
    cfg = config("mamba2-2.7b")
    layer = sum(2 * s[1] * s[2] for s in ssm_prefill.gemm_shapes(cfg, 32))
    assert 64 * layer == pytest.approx(5.15e9, rel=1e-2)
    state = counts.ssm_state_step(32, 80, 128, 64)
    assert 64 * state.bytes == pytest.approx(2 * 5.37e9, rel=1e-2)


def test_decode_lengths_are_one_set_in_seed_order():
    t = T.load("decode-b32")
    a, b = T.decode_lengths(t, 1), T.decode_lengths(t, 2 ** 40 + 7)
    assert sorted(a) == sorted(b)
    assert not np.array_equal(a, b)
    assert a.min() >= 1024 and a.max() <= 6144
    np.testing.assert_array_equal(a, T.decode_lengths(t, 1))


def test_decode_lengths_wrap_before_the_cache_ends():
    lens0 = np.array([8000, 100], np.int32)
    at = [T.decode_lengths_at(lens0, i, 8192) for i in range(200)]
    assert max(x[0] for x in at) == 8191
    assert at[192][0] == 8000 and at[192][1] == 292
