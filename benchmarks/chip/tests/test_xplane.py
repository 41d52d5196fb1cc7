"""The reduction from a trace to the per-layer metrics.

The recorded trace under ``data/`` is a few passes of a cell and its XLA
GEMMs, traced on one TPU v5e by ``record_trace.py`` when the cell's pass
ran one Mamba2 block; the reduction reads any pass's trace alike.  The
profiler wrote it twice, as an XPlane and as a Perfetto JSON; the
reduction reads the first, and these tests check it against the second.
"""
from __future__ import annotations

import glob
import gzip
import importlib
import json
import os

import pytest

import common
import counts
import run
import xplane

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "mamba2-2.7b.prefill-4k"
# the program's Pallas kernels, as the trace names their custom calls
PALLAS = {"matmul": "gemm", "flash_attention": "attn",
          "flash_decode": "decode", "ssd_chunk_scan": "ssd"}


def test_union_of_intervals():
    assert xplane.union_s([]) == 0.0
    assert xplane.union_s([(0, 1), (0.5, 2), (3, 4)]) == 3.0
    assert xplane.union_s([(3, 4), (0, 1), (1, 1.5)]) == 2.5


def op(instr, start, end, kernel=False, opcode="fusion"):
    return xplane.Op(instr, opcode, kernel, start, end, 0)


def test_idle_gaps_between_and_inside_passes():
    runs = [("jit_cell_pass(1)", 0.0, 1.0, 0), ("jit_cell_pass(1)", 1.5, 2.5, 0)]
    ops = [op("matmul.1", 0.0, 0.4, True, "custom-call"),
           op("copy.2", 0.6, 1.0, opcode="copy"),
           op("matmul.1", 1.5, 2.0, True, "custom-call")]
    names = {"copy.2": "jit(cell_pass)/ssd/transpose"}
    gaps = xplane.idle_gaps(ops, runs, names)
    assert gaps[0] == ("between passes", 0.5)
    assert ("in pass, at its end", 0.5) in gaps
    assert any(g[0] == "in pass, before ssd:copy" and
               g[1] == pytest.approx(0.2) for g in gaps)


def test_scope_and_family():
    assert xplane.scope("jit(cell_pass)/gemm.in/jit(matmul)/pallas_call") \
        == "gemm.in"
    assert xplane.scope("jit(xla_gemms)/xla.3/dot_general") == "xla.3"
    assert xplane.scope("") == "-"
    assert xplane.family("gemm.in") == "gemm"
    assert xplane.family("decode") == "decode"
    assert xplane.family("kv_write") is None
    assert xplane.family("-") is None


COPIES_HLO = """
ENTRY %main {
  %p0 = s8[48,1024,64]{2,1,0} parameter(0), metadata={op_name="params[0]"}
  %p1 = s8[8,128]{1,0} parameter(1), metadata={op_name="state"}
  %copy.1 = s8[48,1024,64]{2,1,0:S(1)} copy(%p0), metadata={op_name="params[0]"}
  %pad.2 = s8[48,1024,128]{2,1,0} fusion(%copy.1), kind=kLoop, calls=%f, metadata={op_name="jit(cell_pass)/gemm.qkv/covenant_matmul/pad"}
  %copy.3 = s8[8,128]{0,1} copy(%p1), metadata={op_name="state"}
  %mm.4 = s32[8,128]{1,0} custom-call(%copy.3, %copy.3), metadata={op_name="jit(cell_pass)/gemm.out/covenant_matmul/matmul"}
  %add.5 = s8[8,128]{1,0} add(%copy.3, %p1), metadata={op_name="jit(cell_pass)/requant/add"}
  %copy.6 = s8[8,128]{0,1} copy(%add.5), metadata={op_name="jit(cell_pass)/transpose"}
  ROOT %mm.7 = s32[8,128]{1,0} custom-call(%copy.6, %p1), metadata={op_name="jit(cell_pass)/gemm.out/covenant_matmul/matmul"}
}
"""


def test_copy_of_an_argument_for_one_call_is_the_calls():
    """A copy the compiler made of an argument, named after it, belongs to
    the Covenant call that alone reads it; one the harness also reads, and
    one inside the program's name stack, keep their names."""
    names = xplane.op_names(COPIES_HLO)
    assert names["copy.1"] == names["pad.2"]
    assert xplane.family(xplane.scope(names["copy.1"])) == "gemm"
    assert names["copy.3"] == "state"
    assert names["copy.6"] == "jit(cell_pass)/transpose"
    assert names["mm.7"].endswith("/matmul")


def recorded():
    path = os.path.join(DATA, CELL)
    with open(os.path.join(path, "inputs.json")) as f:
        inputs = json.load(f)
    work = [common.Call(*c) for c in inputs.pop("work")]
    reading = xplane.read(os.path.join(path, "trace"), work=work,
                          peak=counts.peaks("TPU v5 lite"), keep=True,
                          **inputs)
    (perfetto,) = glob.glob(os.path.join(path, "trace", "**",
                                         "*.trace.json.gz"), recursive=True)
    with gzip.open(perfetto) as f:
        return reading, json.load(f)["traceEvents"], inputs


def perfetto_device(events, thread: str):
    """(name, start s, end s) of the X events on /device:TPU:0's thread."""
    pid = next(e["pid"] for e in events if e.get("ph") == "M" and
               e["name"] == "process_name" and
               e["args"]["name"] == "/device:TPU:0")
    tid = next(e["tid"] for e in events if e.get("ph") == "M" and
               e.get("pid") == pid and e["name"] == "thread_name" and
               e["args"]["name"] == thread)
    return [(e["name"], e["ts"] * 1e-6, (e["ts"] + e["dur"]) * 1e-6)
            for e in events if e.get("ph") == "X" and e["pid"] == pid and
            e["tid"] == tid]


def test_recorded_trace_against_its_perfetto_copy():
    r, events, inputs = recorded()
    runs = sorted((s, e) for n, s, e in perfetto_device(events, "XLA Modules")
                  if n.startswith("jit_cell_pass"))[1:]
    assert len(runs) == inputs["passes"] == r.passes
    assert r.window_s == pytest.approx(max(e for _, e in runs) -
                                       min(s for s, _ in runs), rel=1e-4)
    ops = [(n, s, e) for n, s, e in perfetto_device(events, "XLA Ops")
           if any(a <= s <= b for a, b in runs)]
    assert r.busy_s == pytest.approx(
        xplane.union_s([(s, e) for _, s, e in ops]), rel=1e-4)
    kernels = {}
    for n, s, e in ops:
        fam = PALLAS.get(n.rsplit(".", 1)[0])
        if fam:
            kernels[fam] = kernels.get(fam, 0.0) + e - s
    assert set(kernels) == set(r.call_s) == {"gemm", "ssd"}
    for fam, s in kernels.items():        # a call is its kernel and more
        assert r.call_s[fam] >= s * (1 - 1e-4)
    total = sum(e - s for n, s, e in ops if not n.startswith("while"))
    assert r.glue_s + r.harness_s + sum(kernels.values()) == \
        pytest.approx(total, rel=1e-4)
    assert r.glue_s > 0 and r.harness_s > 0


def test_recorded_trace_metrics():
    r, _, _ = recorded()
    assert 0 < r.busy_s <= r.window_s
    idle = sum(s for _, s in r.gaps)
    assert r.busy_s + idle == pytest.approx(r.window_s, rel=1e-3)
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    values = {}
    for m in run.cell_metrics(bench, CELL, True):
        values[m["name"]] = importlib.import_module(
            f"metrics.{m['name']}").read(r)
    assert set(values) == {"idle_share", "step_mfu", "glue_share",
                           "harness_share", "gemm_roofline", "ssd_roofline",
                           "gemm_vs_xla"}
    for name, v in values.items():
        assert v is not None and v > 0, name
        if name.endswith("roofline") or "mfu" in name or "share" in name:
            assert v < 100, (name, v)
    b = r.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][1] >= b["device_ops"][-1][1]


# the recorded trace's metrics as the reduction read them before int8 calls
# were counted at their own peak: as the recording run printed them
# (``result.json``), but for the shares of glue and harness, which were
# split after it
RECORDED = {"idle_share": 5.340418043183382, "step_mfu": 19.153671284803405,
            "glue_share": 46.92151003207357,
            "harness_share": 14.266352545198306,
            "gemm_roofline": 85.3710211265455,
            "ssd_roofline": 2.1098288685365327,
            "gemm_vs_xla": 0.9043313987129267}


def test_recorded_trace_metrics_are_unchanged():
    r, _, _ = recorded()
    with open(os.path.join(DATA, CELL, "result.json")) as f:
        printed = json.load(f)["metrics"]
    for name, v in RECORDED.items():
        got = importlib.import_module(f"metrics.{name}").read(r)
        assert got == pytest.approx(v, rel=1e-12), name
        if name not in ("glue_share", "harness_share"):
            assert printed[name]["value"] == v


def test_reader_returns_nothing_where_there_is_nothing():
    r, _, _ = recorded()
    for name in ("attn_roofline", "decode_roofline"):
        assert importlib.import_module(f"metrics.{name}").read(r) is None
