"""Every op a Covenant wrapper makes around its kernel carries the
wrapper's name and exactly one step name in its HLO ``op_name``
(``covenant_<fn>/<step>``), which is what lets a profiler trace attribute
device time to the wrapper's pads, repeats and relayouts.

Each wrapper is compiled on the CPU (Pallas interpreter) at shapes that
need every step: a GEMM padded on m, n and k; GQA 8/2 with query and K/V
lengths no block multiple; a decode cache no block multiple, and one read
slots-minor (head dim 160, slots a multiple of 128); an SSD with
one group of four heads, a sequence no chunk multiple and an initial state;
an expert layer holding 3 of 8 experts at widths no block multiple.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops

STEPS = ("pad", "unpad", "repeat", "layout", "route", "swiglu", "combine")
# harness scopes of the on-chip benchmark; a step name must not start with
# one, or its reduction would read the step as the harness's scope
HARNESS_SCOPES = ("gemm", "attn", "decode", "ssd", "kv_write", "state",
                  "norm", "xla")
INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?[\w.\-]+ = .*?\b([a-z][\w\-]*)\(.*?'
                   r'op_name="([^"]*)"', re.M)

S = jax.ShapeDtypeStruct
BF, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32

# wrapper[.case] -> (its kernel's name, the call, argument shapes, the
# (step, opcode) pairs its compiled program must hold)
CASES = {
    "covenant_matmul": (
        "matmul",
        lambda a, b: ops.covenant_matmul(a, b, blocks=(16, 128, 128),
                                         interpret=True),
        [S((20, 200), BF), S((200, 130), BF)],
        {("pad", "pad"), ("unpad", "slice")}),
    "covenant_attention": (
        "flash_attention",
        lambda q, k, v: ops.covenant_attention(q, k, v, blocks=(16, 16),
                                               interpret=True),
        [S((1, 8, 20, 32), BF), S((1, 2, 20, 32), BF), S((1, 2, 20, 32), BF)],
        {("repeat", "broadcast"), ("pad", "pad"), ("unpad", "slice")}),
    "covenant_decode_attention": (
        "flash_decode",
        lambda q, k, v, n: ops.covenant_decode_attention(
            q, k, v, n, block_kv=16, interpret=True),
        [S((2, 8, 32), BF), S((2, 2, 40, 32), BF), S((2, 2, 40, 32), BF),
         S((2,), I32)],
        {("repeat", "broadcast"), ("pad", "pad")}),
    # d = 160 over 256 slots: the cache is read slots-minor, held to that
    # layout by a constraint (a copy on the CPU) and relaid by bitcasts
    "covenant_decode_attention.slots_minor": (
        "flash_decode",
        lambda q, k, v, n: ops.covenant_decode_attention(
            q, k, v, n, block_kv=128, interpret=True),
        [S((2, 8, 160), BF), S((2, 2, 256, 160), BF),
         S((2, 2, 256, 160), BF), S((2,), I32)],
        {("repeat", "broadcast"), ("layout", "copy")}),
    "covenant_ssd": (
        "ssd_chunk_scan",
        lambda x, dt, a, b, c, st: ops.covenant_ssd(
            x, dt, a, b, c, chunk=16, init_state=st, return_state=True,
            interpret=True),
        [S((1, 40, 4, 8), BF), S((1, 40, 4), F32), S((4,), F32),
         S((1, 40, 1, 16), BF), S((1, 40, 1, 16), BF), S((1, 4, 8, 16), F32)],
        {("pad", "pad"), ("layout", "transpose"), ("repeat", "broadcast"),
         ("unpad", "slice")}),
    "covenant_experts": (
        "grouped_matmul",
        lambda x, r, wi, wo: ops.covenant_experts(
            x, r, wi, wo, top_k=2, first=2, n_experts=8, interpret=True),
        [S((12, 200), BF), S((200, 8), BF), S((3, 200, 260), BF),
         S((3, 130, 200), BF)],
        {("route", "dot"), ("layout", "sort"), ("pad", "pad"),
         ("swiglu", "multiply"), ("unpad", "slice"), ("combine", "reduce")}),
}


def wrapper_steps(text: str, fn: str, kernel: str) -> list:
    """(opcode, steps, op_name) of each op the wrapper ``fn`` made outside
    its kernel: ``steps`` are the step names between ``fn`` and the op's
    own name in its name stack, jit parts left out.  An op of a jitted
    helper (``jit(silu)``) has no own name: its stack ends in the jit part.
    An op named by the kernel's call alone, or by the kernel's own scope
    (as the interpreter names the kernel body), is the kernel's."""
    out = []
    for opcode, op_name in INSTR.findall(text):
        parts = op_name.split("/")
        if fn not in parts:
            continue
        after = [p for p in parts[parts.index(fn) + 1:]
                 if not p.startswith("jit(")]
        if not after or after[0] == kernel:
            continue
        if not parts[-1].startswith("jit("):
            after = after[:-1]
        out.append((opcode, [p for p in after if p in STEPS], op_name))
    return out


def test_step_names_are_not_harness_scopes():
    assert not any(s.split(".")[0] in HARNESS_SCOPES for s in STEPS)


@pytest.mark.parametrize("case", list(CASES))
def test_wrapper_ops_carry_one_step(case):
    fn = case.split(".")[0]
    kernel, call, shapes, expected = CASES[case]
    text = jax.jit(call).lower(*shapes).compile().as_text()
    ops_ = wrapper_steps(text, fn, kernel)
    assert ops_, f"no op of {fn} in the compiled program"
    unscoped = [(op, name) for op, steps, name in ops_ if len(steps) != 1]
    assert not unscoped, unscoped
    seen = {(steps[0], op) for op, steps, _ in ops_}
    assert expected <= seen, sorted(expected - seen)
