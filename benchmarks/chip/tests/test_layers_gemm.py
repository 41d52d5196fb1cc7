"""The Table 2 int8 encoder pass (``passes/layers_gemm.py``) and its
yardstick on the CPU, kernels in the Pallas interpreter: the count of its
calls, the blocks its rows get from the tiler, XLA's int8 lowering of its
shapes, the exact integer reference and requantization, and the check's
teeth: the exact comparison holds for a sound run and fails for a product
off by one and for two layers' weights swapped (``test_passes.py`` covers
the control and the faults every cell can have)."""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import common
import counts
import passes
import reference
import run
import smoke
from metrics import step_mfu
from passes import layers_gemm as lg

CELL = "table2.gemm-int8"
CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2 ** 33 + 17
V5E = counts.peaks("TPU v5 lite")
LAYERS, GEMM = smoke.CELLS["layers_gemm"]
with open(os.path.join(CHIP, "limits", f"{CELL}.json")) as f:
    LIMITS = json.load(f)["limits"]

# the blocks test_kernels.py::test_tiler_blocks_pinned pins for the
# BERT-Large rows of Table 2 (int8 in, int32 accumulator)
PINNED = {
    "BERT-LG-GEMM1": (384, 4096, 1024), "BERT-LG-GEMM2": (384, 1024, 4096),
    "BERT-LG-ATN1-GEMM": (384, 128, 1024),
    "BERT-LG-ATN2-GEMM": (384, 384, 128),
    "BERT-LG-ATN3-GEMM": (384, 128, 384),
    "BERT-LG-ATN4-GEMM": (384, 1024, 1024),
}


def config() -> dict:
    return run.load_json(CHIP, "configs", "table2.json")


def test_rows_are_table2():
    from repro.core import library

    table = [spec.key for spec in library.PAPER_LAYERS]
    cfg = config()
    run_rows = [r["name"] for r in cfg["rows"]]
    assert sorted(run_rows) == sorted(k for k in table if "BERT" in k)
    assert sorted(run_rows + cfg["not_run"]["rows"]) == sorted(table)
    assert set(run_rows) == set(lg.ROWS)
    g = lg.dims(cfg)
    assert (g["d"], g["h"], g["hd"], g["f"], g["layers"], g["s"]) == (
        1024, 16, 64, 4096, 24, 384)


def test_rows_get_the_pinned_blocks():
    """covenant_matmul asks the tiler with the f32 accumulator's default;
    the rows get the blocks pinned for the int32 accumulator."""
    from repro.kernels.tiling import gemm_blocks

    for row in config()["rows"]:
        mnk = row["m"], row["n"], row["k"]
        assert gemm_blocks(*mnk, in_dtype="i8") == PINNED[row["name"]]
        assert gemm_blocks(*mnk, in_dtype="i8", acc_dtype="i32") == \
            PINNED[row["name"]]


def test_pass_count():
    # one layer of 512 sequences: 5.26 TOP, 16.1 GB (the int32 products
    # most of it), 21.9 ms at the least; 526 ms a pass of 24 layers; the
    # dense FFN rows compute-bound, the rest memory-bound
    traffic = run.T.load("gemm-int8")
    assert traffic["batch"] == 512
    calls = [counts.gemm(*s) for s in lg.gemm_shapes(config(), 512)]
    assert sum(c.flops for c in calls) == pytest.approx(5.257e12, rel=1e-3)
    assert sum(c.flops for c in calls) / 512 == \
        2 * 384 * (4 * 1024 * 1024 + 2 * 1024 * 4096 + 2 * 384 * 1024)
    assert sum(c.bytes for c in calls) == pytest.approx(16.12e9, rel=1e-3)
    assert [c.bytes / 819e9 > c.flops / 393e12 for c in calls] == [
        True, True, True, True, False, False]
    least = sum(counts.roofline_s(c, V5E) for c in calls)
    assert least == pytest.approx(21.92e-3, rel=1e-3)
    assert 24 * least == pytest.approx(0.526, rel=1e-3)


def test_pass_calls_and_xla_shapes():
    p = lg.build(LAYERS, GEMM, 1, common.root_key(1))
    m = 3 * 32
    layer = [(m, 3 * 256, 256, "int8", 1), (32, 32, 64, "int8", 12),
             (32, 64, 32, "int8", 12), (m, 256, 256, "int8", 1),
             (m, 512, 256, "int8", 1), (m, 256, 512, "int8", 1)]
    assert p.calls(0) == p.calls(7) == [counts.gemm(*s) for s in layer] * 2
    # XLA's over one sequence at a time: 3 is no multiple of a larger block
    one = [(32, 64, 256, "int8", 1)] * 12 + [
        (32, 32, 64, "int8", 4), (32, 64, 32, "int8", 4),
        (32, 256, 256, "int8", 1), (32, 512, 256, "int8", 1),
        (32, 256, 512, "int8", 1)]
    assert p.xla_gemms == one * 3 * 2
    assert lg.xla_shapes(config(), 512)[:49] == [
        (64 * 384, 64, 1024, "int8", 1)] * 48 + [(384, 384, 64, "int8", 1024)]
    assert len(lg.xla_shapes(config(), 512)) == 8 * 53
    # the per-head projections do the work counted for all heads at once
    assert sum(counts.gemm(*s).flops for s in p.xla_gemms) == \
        sum(c.flops for c in p.calls(0))
    run.delete((p.params, p.state, p.inputs))


def test_step_mfu_takes_each_calls_peak():
    """Each call's operations over its own peak: an int8 call counts half
    what a bf16 call of the same FLOPs counts."""
    class Reading:
        window_s = 1.0
        work = {"gemm": {"peak_s": 0.0}}

    r = Reading()
    for c in (counts.gemm(384, 4096, 1024, "int8"),
              counts.gemm(384, 4096, 1024)):
        r.work["gemm"]["peak_s"] += c.flops / V5E[c.peak]
    flops = 2 * 384 * 4096 * 1024
    assert step_mfu.read(r) == pytest.approx(
        100 * (flops / 393e12 + flops / 197e12))


def old_make_xla_gemms(shapes: list, key) -> tuple:
    """``run.make_xla_gemms`` before int8 shapes: bf16 in, f32 out."""
    distinct = sorted(set(shapes), key=shapes.index)
    mult = [shapes.count(s) for s in distinct]
    operands = [(common.normal(common.subkey(key, "xa", i), (m, k)),
                 common.normal(common.subkey(key, "xb", i), (k, n)))
                for i, (m, n, k) in enumerate(distinct)]

    @jax.jit
    def xla_gemms(operands):
        outs = []
        for i, (a, b) in enumerate(operands):
            with jax.named_scope(f"xla.{i}"):
                outs.append(jnp.dot(a, b, preferred_element_type=jnp.float32))
        return outs

    return xla_gemms, operands, mult


def test_xla_gemms_of_bf16_shapes_are_unchanged():
    shapes = [(32, 256, 128), (8, 384, 128), (32, 256, 128)]
    key = common.root_key(SEED)
    f, ops, mult = run.make_xla_gemms(passes.bf16_gemms(shapes), key)
    g, want, wmult = old_make_xla_gemms(shapes, key)
    assert mult == wmult == [2, 1]
    for (a, b), (wa, wb) in zip(ops, want):
        assert a.dtype == b.dtype == jnp.bfloat16
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(wa, np.float32))
        np.testing.assert_array_equal(np.asarray(b, np.float32),
                                      np.asarray(wb, np.float32))
    assert f.lower(ops).as_text() == g.lower(want).as_text()


def test_xla_gemms_of_int8_shapes_are_int8():
    shapes = [(32, 256, 128, "bf16", 1), (1, 37, 75, "int8", 1),
              (1, 37, 75, "int8", 1), (16, 24, 8, "int8", 3)]
    key = common.root_key(SEED)
    f, ops, mult = run.make_xla_gemms(shapes, key)
    assert mult == [1, 2, 1]
    _, want, _ = old_make_xla_gemms([(32, 256, 128)], key)
    np.testing.assert_array_equal(np.asarray(ops[0][0], np.float32),
                                  np.asarray(want[0][0], np.float32))
    (a, b), (ba, bb) = ops[1:]
    assert a.dtype == b.dtype == ba.dtype == jnp.int8
    assert a.shape == (1, 75) and b.shape == (75, 37)
    assert ba.shape == (3, 16, 8) and bb.shape == (3, 8, 24)
    bf, i8, batched = f(ops)
    assert bf.dtype == jnp.float32 and i8.dtype == batched.dtype == jnp.int32
    np.testing.assert_array_equal(
        np.asarray(i8), np.asarray(a, np.int64) @ np.asarray(b, np.int64))
    np.testing.assert_array_equal(
        np.asarray(batched),
        np.asarray(ba, np.int64) @ np.asarray(bb, np.int64))


def test_draws_span_the_int8_range():
    x = np.asarray(common.int8(common.root_key(SEED), (64, 1024)))
    assert x.dtype == np.int8
    assert x.min() == -128 and x.max() == 127


def test_int_reference_is_exact():
    """Past 2**24, where f32 would round: every element at the extremes."""
    rng = np.random.default_rng(0)
    a = np.full((2, 2, 4096), 127, np.int8)
    a[:, 1] = -128
    b = rng.integers(-128, 128, (4096, 3)).astype(np.int8)
    b[:, 0] = -128
    want = a.astype(np.int64) @ b.astype(np.int64)
    assert abs(want).max() > 2 ** 24
    got = reference.int_einsum("bsk,kn->bsn", jnp.asarray(a), jnp.asarray(b))
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(np.asarray(got), want)
    # a contraction that is not the last axis of both, in a ragged stretch
    a2, b2 = a[..., :700], b[:700]
    got = reference.int_einsum("kn,bsk->bsn", jnp.asarray(b2), jnp.asarray(a2))
    np.testing.assert_array_equal(
        np.asarray(got), a2.astype(np.int64) @ b2.astype(np.int64))


def test_requant_brings_each_row_into_int8():
    y = jnp.asarray([[0, 0, 0], [127, -127, 5], [128, -1, 64],
                     [2 ** 26 - 1, -(2 ** 20), 3 * 2 ** 18],
                     [-(2 ** 26), 2 ** 25, 1]], jnp.int32)
    got = np.asarray(reference.requant(y))
    assert got.dtype == np.int8
    # no shift where the largest magnitude has 7 bits; else by the bits
    # past 7, rounding half up, and clipped
    np.testing.assert_array_equal(got, [
        [0, 0, 0], [127, -127, 5], [64, 0, 32], [127, -2, 2], [-64, 32, 0]])


def test_integer_gap_is_exact_past_f32():
    want = jnp.asarray([[2 ** 25 + 1, 7]], jnp.int32)
    assert float(common.rel_err(want, want)) == 0.0
    got = want.at[0, 0].add(1)
    # f32 holds 2**25 + 1 and 2**25 + 2 as one number
    assert float(jnp.float32(2 ** 25 + 1)) == float(jnp.float32(2 ** 25 + 2))
    assert float(common.rel_err(got, want)) == pytest.approx(1 / (2 ** 25 + 1))


def smoke_run(**kw) -> dict:
    return run.run(LAYERS, GEMM, seed=SEED, seconds=0.2, trace=False,
                   limits=LIMITS, metrics=[], device=CPU,
                   interpret=True, **kw)


def test_sound_run_is_exact():
    r = smoke_run()
    assert r["correct"] is True
    assert {t: c["value"] for t, c in r["checks"].items()} == \
        dict.fromkeys(LIMITS, 0.0)


def test_control_is_int4():
    a = jnp.arange(-128, 128, dtype=jnp.int32).astype(jnp.int8)
    q = np.asarray(reference.int4(a), np.int32)
    assert len(set(q)) == 16
    assert q.min() == -128 and q.max() == 112 and not np.any(q % 16)
    a = np.asarray(a, np.int32)
    assert np.all(np.abs(q - a)[a <= 112] <= 8)


def off_by_one(monkeypatch):
    """The first layer's ATN2 products off by 1 in one element."""
    from repro.kernels import ops

    real, seen = ops.covenant_matmul, []

    def broken(a, b, **kw):
        p = real(a, b, **kw)
        if a.shape[-1] == b.shape[0] == LAYERS["head_dim"] and not seen:
            seen.append(p.shape)
            p = p.at[1, 2].add(1)
        return p
    monkeypatch.setattr(ops, "covenant_matmul", broken)


def swapped_layers(monkeypatch):
    real = lg.body

    def broken(params, state, x, **kw):
        return real([params[1], params[0], *params[2:]], state, x, **kw)
    monkeypatch.setattr(lg, "body", broken)


@pytest.mark.parametrize("fault", [off_by_one, swapped_layers],
                         ids=["off_by_one", "swapped_layers"])
def test_broken_pass_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    r = smoke_run()
    assert r["correct"] is False, r["checks"]
