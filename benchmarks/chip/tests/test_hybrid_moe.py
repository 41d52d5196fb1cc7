"""The hybrid MoE decode pass (granite-4.0-h-small) on the CPU, kernels in
the Pallas interpreter: its run against its reference, its counts against
hand-worked values, the ``expert_roofline`` reader, and the check's teeth:
a run whose routing or state is broken comes out not correct, and so does
the control at the cell's widths.

``test_passes.py`` runs every cell of ``BENCHMARK.json`` at the smoke size
``smoke.CELLS`` gives its pass kind; this module runs the hybrid MoE kind
at the same size.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import calibrate
import counts
import counts_experts
import run
import smoke
import traffic as T
import xplane
from metrics import expert_roofline
from passes import hybrid_moe_decode as hm

CELL = "granite-4.0-h-small.decode-b32"
CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2 ** 33 + 17
V5E = counts.peaks("TPU v5 lite")

HYBRID = smoke.HYBRID


def config() -> dict:
    with open(os.path.join(CHIP, "configs", "granite-4.0-h-small.json")) as f:
        return json.load(f)


def limits() -> dict:
    with open(os.path.join(CHIP, "limits", f"{CELL}.json")) as f:
        return json.load(f)["limits"]


def smoke_run(seed: int = SEED, **kw) -> dict:
    return run.run(HYBRID, smoke.DECODE, seed=seed, seconds=0.2,
                   trace=False, limits=limits(), metrics=[], device=CPU,
                   interpret=True, **kw)


def test_configuration_states_its_cut():
    cfg = config()
    assert cfg["published"] == {"num_hidden_layers": 40,
                                "num_local_experts": 72}
    assert cfg["reduced"] == ["num_hidden_layers", "num_local_experts"]
    assert cfg["num_hidden_layers"] == 10 and cfg["num_local_experts"] == 36
    kinds = hm.stage_layers(cfg)
    assert kinds == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    # every published width as run
    g = hm.dims(cfg)
    assert (g["d"], g["di"], g["h"], g["p"], g["n"], g["g"]) == (
        4096, 8192, 128, 64, 128, 1)
    assert g["nin"] == 2 * 8192 + 2 * 128 + 128 == 16768
    assert (g["hq"], g["hkv"], g["hd"]) == (32, 8, 128)
    assert (g["f"], g["fs"], g["experts"], g["top_k"]) == (768, 1536, 72, 10)


def test_a_stage_cut_below_the_attention_layer_keeps_it():
    cfg = dict(config(), num_hidden_layers=2)
    assert hm.stage_layers(cfg) == ["mamba", "attention"]


def test_sound_run_is_correct():
    r = smoke_run()
    assert r["correct"] is True, r["checks"]
    assert set(r["checks"]) == set(limits())


def test_counts_of_the_expert_layer():
    # 32 tokens, top 10 of 72, 36 held: 160 rows expected on the held
    # experts, each 2 (4096 * 1536 + 768 * 4096) FLOPs; 36 (1 - (62/72)^32)
    # = 35.70 experts touched, 9.437e6 weights each
    c = counts_experts.experts(32, 4096, 768, 72, 36, 10)
    assert c.family == "experts"
    per_expert = 4096 * 1536 + 768 * 4096
    assert per_expert == 9_437_184
    assert c.flops == 2 * 32 * 4096 * 72 + 160 * 2 * per_expert
    assert c.flops == pytest.approx(3.039e9, rel=1e-3)
    touched = 36 * (1 - (62 / 72) ** 32)
    assert touched == pytest.approx(35.70, abs=5e-3)
    assert c.bytes == pytest.approx(
        2 * (touched * per_expert + 4096 * 72 + 2 * 160 * 4096))
    assert c.bytes == pytest.approx(0.6770e9, rel=1e-3)
    assert counts.roofline_s(c, V5E) == pytest.approx(0.827e-3, rel=1e-3)


def test_pass_floor():
    # 10 layers: experts 6.77 GB, dense weights 2.31 GB, the f32 state of
    # 9 Mamba layers read and written 2.42 GB, the valid K/V 0.47 GB:
    # 11.97 GB, 14.6 ms at 819 GB/s
    cfg = config()
    lens = T.decode_lengths(T.load("decode-b32"), 12345)
    assert lens.sum() == pytest.approx(32 * 3584, rel=1e-3)
    calls = hm.pass_calls(cfg, 32, lens)
    by = {}
    for c in calls:
        by[c.family] = by.get(c.family, 0.0) + c.bytes
    assert by["experts"] == pytest.approx(6.770e9, rel=1e-3)
    dense = sum(2 * n * k for kind in hm.stage_layers(cfg)
                for _, n, k in hm.gemm_shapes(cfg, 32)[kind]
                + hm.gemm_shapes(cfg, 32)["moe"])
    assert dense == pytest.approx(2.31e9, rel=1e-2)
    assert by["state"] == pytest.approx(9 * 2 * 4 * 32 * 128 * 128 * 64)
    assert by["state"] == pytest.approx(2.42e9, rel=1e-2)
    assert by["decode"] == pytest.approx(0.47e9, rel=1e-2)
    floor = sum(counts.roofline_s(c, V5E) for c in calls)
    assert floor == pytest.approx(14.6e-3, rel=1e-2)
    assert by["experts"] / sum(by.values()) == pytest.approx(0.57, abs=0.01)


def reading(ops_s: dict, work: dict) -> xplane.Reading:
    return xplane.Reading(
        window_s=1.0, busy_s=1.0, passes=1, call_s={"gemm": 1.0},
        glue_s=0.0, harness_s=0.0, work=work, peak=V5E, xla_s=[],
        xla_reps=0, xla_mult=[], ops_s=ops_s, gaps=[])


def test_expert_roofline_reads_the_gemm_experts_scope():
    work = {"experts": {"flops": 3e9, "bytes": 6.77e8, "roofline_s": 8e-4},
            "gemm": {"flops": 1e9, "bytes": 1e9, "roofline_s": 1e-3}}
    r = reading({"gemm.experts:grouped_matmul": 1.2e-3,
                 "gemm.experts:fusion": 0.4e-3,
                 "gemm.shared_in:matmul": 5e-3, "harness:copy": 1.0}, work)
    assert expert_roofline.read(r) == pytest.approx(50.0)
    # a trace with no expert layer in it, as the parent's
    no_experts = reading({"gemm.in:matmul": 1e-3, "state:fusion": 1e-3},
                         work)
    assert expert_roofline.read(no_experts) is None
    assert expert_roofline.read(reading(
        {"gemm.experts:grouped_matmul": 1e-3}, {})) is None


def test_pick_keeps_the_programs_near_ties_only():
    logits = np.array([[3.0, 2.0, 1.0, 0.97, -2.0]])
    own, worst, off = hm.pick(logits, None, 3, 0.1)
    assert own.tolist() == [[0, 1, 2]] and off == 0
    # the 4th expert within 0.03 of the 3rd: a near tie, kept
    got, worst, off = hm.pick(logits, np.array([[3, 0, 1]]), 3, 0.1)
    assert got.tolist() == [[3, 0, 1]] and off == 0
    assert worst == pytest.approx(0.03)
    # the last expert, or one twice: the reference's own top 3
    for bad in ([[4, 0, 1]], [[0, 0, 1]], [[0, 1, 7]]):
        got, _, off = hm.pick(logits, np.array(bad), 3, 0.1)
        assert got.tolist() == [[0, 1, 2]] and off == 1


def test_pick_keeps_every_expert_clearly_above_the_kth():
    # the top expert swapped for the 4th, which lies within 0.03 of the
    # 3rd: every routed logit is near the 3rd, but the 1st is 2 above it
    logits = np.array([[3.0, 2.0, 1.0, 0.97, -2.0],
                       [3.0, 2.0, 1.0, 0.97, -2.0]])
    got, worst, off = hm.pick(logits, np.array([[3, 1, 2], [0, 1, 2]]), 3,
                              0.1)
    assert got.tolist() == [[0, 1, 2], [0, 1, 2]] and off == 1
    assert worst == pytest.approx(2.0)
    # a routing that is not k distinct experts has no finite gap
    assert hm.pick(logits, np.array([[0, 0, 1], [0, 1, 2]]), 3, 0.1)[1] \
        == np.inf


@pytest.mark.parametrize("gap", [0.0, 0.004, 0.06, 2.0, np.inf])
def test_routing_tap_reads_the_gap(gap):
    from common import rel_err

    got = rel_err(jnp.ones((1,)), hm.gap_tap(gap))
    assert float(got) == pytest.approx(gap, rel=1e-5)


def test_routing_limit_is_eps():
    """A run that passes the ``routing`` limit had no routing replaced by
    the reference's own."""
    assert limits()["routing"] == hm.EPS


def routing_fault(kind: str):
    """ops.expert_routing with one fault, for every caller of it."""
    from repro.kernels import ops

    sound = ops.expert_routing

    def broken(x, router_w, top_k):
        logits = jnp.dot(x, router_w, preferred_element_type=jnp.float32)
        experts, gates = sound(x, router_w, top_k)
        if kind == "swapped":        # the first pick is the lowest logit
            return experts.at[:, 0].set(jnp.argmin(logits, axis=1)), gates
        if kind == "dropped":
            # the pairs past the first on each expert get no weight, as a
            # dispatch with a capacity of one row per expert drops them
            flat = experts.reshape(-1)
            seen = jnp.cumsum(jax.nn.one_hot(flat, logits.shape[1]), axis=0)
            first = seen[jnp.arange(flat.size), flat] <= 1
            return experts, jnp.where(first.reshape(experts.shape), gates, 0)
        # a softmax over all experts, not renormalised over the top k
        return experts, jnp.take_along_axis(jax.nn.softmax(logits, -1),
                                            experts, axis=1)
    return broken


@pytest.mark.parametrize("kind", ["swapped", "not_renormalised", "dropped"])
def test_routing_fault_is_not_correct(kind, monkeypatch):
    from repro.kernels import ops

    monkeypatch.setattr(ops, "expert_routing", routing_fault(kind))
    r = smoke_run()
    assert r["correct"] is False, r["checks"]
    assert r["checks"]["experts"]["value"] > r["checks"]["experts"]["limit"]
    if kind == "swapped":
        assert (r["checks"]["routing"]["value"]
                > r["checks"]["routing"]["limit"])


def test_stale_state_is_not_correct(monkeypatch):
    sound = hm.body

    def stale(params, state, x, **kw):
        new, taps = sound(params, state, x, **kw)
        return dict(new, ssm=state["ssm"]), taps

    monkeypatch.setattr(hm, "body", stale)
    r = smoke_run()
    assert r["correct"] is False, r["checks"]
    assert r["checks"]["state"]["value"] > r["checks"]["state"]["limit"]


def test_control_fails_at_the_cells_widths():
    """The control (fp8 activations and weights, bf16 state), routed by its
    own top 10 as a program run is, fails one of the cell's limits at the
    published widths, cut to a Mamba and the attention layer, 8 held
    experts, 4 sequences."""
    cfg = dict(config(), num_hidden_layers=2, num_local_experts=8,
               layer_types=["mamba", "attention"])
    traffic = dict(T.load("decode-b32"), batch=4, cache_slots=1024,
                   len_min=128, len_max=768)
    lim = limits()
    readings = calibrate.control_readings(cfg, traffic, 1)
    assert set(readings) == set(lim)
    assert any(not readings[t] <= lim[t] for t in lim), readings
    # its routing went through pick: a gap read, not the reference's own
    assert 0 < readings["routing"] < np.inf, readings


def test_control_takes_the_programs_place(monkeypatch):
    """The f32 reference of a control check routes by the control's own
    top k, through pick, as it routes by a program run's."""
    calls = []
    sound = hm.pick

    def seen(logits, program, k, eps):
        calls.append(program is None)
        return sound(logits, program, k, eps)

    monkeypatch.setattr(hm, "pick", seen)
    readings = calibrate.control_readings(HYBRID, smoke.DECODE, 3)
    layers = len(hm.stage_layers(HYBRID))
    # the control's own routing first, then the reference's from it: two
    # passes through every layer each, and the control not run again
    assert calls == [True] * (2 * layers) + [False] * (2 * layers)
    assert set(readings) == set(limits())
