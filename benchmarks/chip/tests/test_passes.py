"""Each cell's run at smoke widths on the CPU, kernels in the Pallas
interpreter: the harness's flow from the drawing of the weights to the
check, with the look for a chip skipped.

- A sound run is correct under the cell's own limits.
- The control (the reference one precision step down) fails them, at the
  cell's published widths cut in depth, batch and length.
- A run whose timed pass is broken underneath comes out not correct, once
  for each fault the cell can have: a step that returns its state (the KV
  cache or SSM state it writes) unchanged, half the batch left out, and an
  answer altered where it is produced.
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import pytest

import calibrate
import passes
import run
import smoke

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2 ** 33 + 17


def cells():
    out = []
    for w in run.load_json(run.ROOT, "BENCHMARK.json")["workloads"]:
        entry = next(c for c in run.load_json(run.ROOT, "BENCHMARK.json")
                     ["configs"] if c["name"] == w["config"])
        cfg = run.load_json(run.ROOT, entry["file"])
        kind = f"{cfg['family']}_{run.T.load(w['traffic'])['phase']}"
        out.append((w["name"], kind))
    return out


CELLS = cells()


def limits(cell: str) -> dict:
    with open(os.path.join(CHIP, "limits", f"{cell}.json")) as f:
        return json.load(f)["limits"]


def smoke_run(cell: str, kind: str, seed: int = SEED) -> dict:
    cfg, traffic = smoke.CELLS[kind]
    return run.run(cfg, traffic, seed=seed, seconds=0.2, trace=False,
                   limits=limits(cell), metrics=[], device=CPU,
                   interpret=True)


@pytest.mark.parametrize("cell,kind", CELLS)
def test_sound_run_is_correct(cell, kind):
    r = smoke_run(cell, kind)
    assert r["correct"] is True, r["checks"]
    assert r["failed"] == 0 and r["attempted"] > 0
    assert list(r)[-1] == "checks"


def cut(cell: str):
    """The cell at its published widths, cut in depth, batch and length
    (Table 2's rows: in depth and batch) to what the reference runs in
    seconds on the CPU."""
    _, cfg, traffic = run.cell_spec(run.load_json(run.ROOT, "BENCHMARK.json"),
                                    cell)
    cfg, traffic = dict(cfg), dict(traffic)
    if traffic["phase"] == "gemm":
        cfg["num_hidden_layers"] = 2
        traffic["batch"] = 1
        return cfg, traffic
    if "n_layer" in cfg:
        cfg["n_layer"] = min(cfg["n_layer"], 8)
    if "num_hidden_layers" in cfg:
        cfg["num_hidden_layers"] = min(cfg["num_hidden_layers"], 2)
    traffic["batch"] = min(traffic["batch"], 4)
    if traffic["phase"] == "prefill":
        traffic.update(seq_len=256, cache_batch=4, cache_slots=256)
    else:
        traffic.update(cache_slots=1024, len_min=128, len_max=768)
    return cfg, traffic


@pytest.mark.parametrize("cell,kind", CELLS)
def test_control_fails(cell, kind):
    """The control fails one of the cell's limits: at the cell's own size
    on the chip (PERF.md gives those readings), and here at its widths."""
    cfg, traffic = cut(cell)
    lim = limits(cell)
    for seed in (1, 2):
        readings = calibrate.control_readings(cfg, traffic, seed)
        assert any(not readings[t] <= lim[t] for t in lim), readings


def stale_state(body):
    def broken(params, state, x, **kw):
        _, taps = body(params, state, x, **kw)
        return state, taps
    return broken


def half_batch(body):
    def broken(params, state, x, **kw):
        x = jax.tree.map(lambda t: t.at[t.shape[0] // 2:].set(0), x)
        return body(params, state, x, **kw)
    return broken


def altered(body):
    def broken(params, state, x, **kw):
        state, taps = body(params, state, x, **kw)
        out = taps["out"]
        bump = (0.1 * jnp.max(jnp.abs(out))).astype(out.dtype)
        return state, dict(taps, out=out.at[0, 0].add(bump))
    return broken


FAULTS = {"stale_state": stale_state, "half_batch": half_batch,
          "altered": altered}


BROKEN = [(cell, kind, fault) for cell, kind in CELLS
          for fault in sorted(FAULTS)]


@pytest.mark.parametrize("cell,kind,fault", BROKEN)
def test_broken_pass_is_not_correct(cell, kind, fault, monkeypatch):
    mod = passes.load(kind)
    monkeypatch.setattr(mod, "body", FAULTS[fault](mod.body))
    r = smoke_run(cell, kind)
    assert r["correct"] is False, r["checks"]
