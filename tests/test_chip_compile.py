"""Compile the main path's Pallas kernels for a TPU v5e that is described,
not attached: Mosaic's refusals (VMEM over budget, unaligned blocks, SMEM
layouts) show here, where interpret mode never sees them.  Each kernel
keeps its stable name, and the wrappers' step scopes survive the chip's
compiler, as a profiler trace reads them.

The topology is described inside a fixture: only the worker that runs
this file loads the TPU compiler, and it skips from there when it cannot.
"""
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels import ops
from repro.kernels.tiling import gemm_blocks
from repro.launch.mesh import make_host_mesh
from repro.launch.train import sharded_train_step
from repro.models import get_model
from repro.optim import adamw


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_compile_cache(topo):
    """JAX's persistent cache off: an entry written for the described
    chip cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def compile_for_chip(one_chip, no_compile_cache):
    """Compile ``fn`` at ``shapes`` for one v5e chip; returns the text."""
    def compile_(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()

    return compile_


def kernels(text: str) -> set:
    """The Pallas kernels (``tpu_custom_call``s) of compiled HLO text, by
    the instruction name that the kernel's ``name`` gives them and that a
    profiler trace shows."""
    return set(re.findall(r'^\s*(?:ROOT\s+)?%([A-Za-z_]\w*?)(?:\.\d+)* = '
                          r'[^\n]*custom_call_target="tpu_custom_call"',
                          text, re.M))


STEPS = {"pad", "unpad", "repeat", "layout", "route", "swiglu", "combine"}


def steps(text: str) -> set:
    """The wrapper steps (``ops.py``'s step scopes) that some op of
    compiled HLO text carries in its ``op_name``, after a ``covenant_*``
    part."""
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        parts = op_name.split("/")[:-1]
        at = [i for i, p in enumerate(parts) if p.startswith("covenant_")]
        if at:
            found.update(p for p in parts[at[0] + 1:] if p in STEPS)
    return found


# (m, n, k): BERT-LG GEMM1, qwen3-0.6b FFN in/out and LM head at 4096 tokens
GEMMS = {"bert_gemm1": (384, 4096, 1024), "qwen3_ffn_in": (4096, 3072, 1024),
         "qwen3_ffn_out": (4096, 1024, 3072),
         "qwen3_lm_head": (4096, 151936, 1024)}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.int8], ids=str)
@pytest.mark.parametrize("gemm", list(GEMMS))
def test_covenant_matmul_compiles(compile_for_chip, gemm, dtype):
    m, n, k = GEMMS[gemm]
    blocks = gemm_blocks(m, n, k,
                         in_dtype="i8" if dtype == jnp.int8 else "bf16")
    text = compile_for_chip(
        lambda a, b: ops.covenant_matmul(a, b, blocks=blocks,
                                         interpret=False),
        ((m, k), dtype), ((k, n), dtype))
    assert kernels(text) == {"matmul"}
    bm, bn, bk = blocks
    padded = m % bm or n % bn or k % bk
    assert steps(text) == ({"pad", "unpad"} if padded else set())


def test_flash_attention_compiles(compile_for_chip):
    """Causal GQA prefill: 16 q heads, 8 kv heads, head_dim 128, Sq 2048."""
    bf = jnp.bfloat16
    text = compile_for_chip(
        lambda q, k, v: ops.covenant_attention(q, k, v, causal=True,
                                               interpret=False),
        ((1, 16, 2048, 128), bf), ((1, 8, 2048, 128), bf),
        ((1, 8, 2048, 128), bf))
    assert kernels(text) == {"flash_attention"}
    assert steps(text) == {"repeat", "layout"}


def test_flash_decode_compiles(compile_for_chip):
    """Batch 8 against a 32k cache."""
    bf = jnp.bfloat16
    text = compile_for_chip(
        lambda q, k, v, n: ops.covenant_decode_attention(q, k, v, n,
                                                         interpret=False),
        ((8, 16, 128), bf), ((8, 8, 32768, 128), bf),
        ((8, 8, 32768, 128), bf), ((8,), jnp.int32))
    assert kernels(text) == {"flash_decode"}
    assert steps(text) == {"repeat", "layout"}


# the operand the decode kernel reads each cache as, by head dim, at the
# stablelm-12b decode cell's shapes (granite-4.0-h-small's head dim is 128)
HELD_AS = {160: "bf16[256,160,8192]{2,1,0}", 128: "bf16[256,8192,128]{2,1,0}"}


def copies(text: str, elems: int) -> list:
    """The shapes of the ``copy`` ops of compiled HLO text that have
    ``elems`` elements."""
    return [dims for dims in re.findall(r"= \w+\[([\d,]+)\]\S* copy\(", text)
            if math.prod(map(int, dims.split(","))) == elems]


@pytest.mark.parametrize("d", list(HELD_AS))
def test_flash_decode_reads_the_cache_as_held(compile_for_chip, d):
    """32 sequences of 32 q and 8 KV heads against 8192-slot caches: a
    head dim of 160 is held slots-minor, and the kernel reads it so; one
    of 128 is held row-major and read row-major.  Neither copies the
    cache."""
    bf = jnp.bfloat16
    cache = (32, 8, 8192, d)
    text = compile_for_chip(
        lambda q, k, v, n: ops.covenant_decode_attention(q, k, v, n,
                                                         interpret=False),
        ((32, 32, d), bf), (cache, bf), (cache, bf), ((32,), jnp.int32))
    assert kernels(text) == {"flash_decode"}
    # operands: the scalar-prefetched lengths, q, k, v
    constraints = re.search(r"flash_decode[.\d]* = .*?operand_layout_"
                            r"constraints=\{([^}]*\}[^}]*\}[^}]*\}"
                            r"[^}]*\})", text)
    assert constraints.group(1).split(", ")[2:] == [HELD_AS[d]] * 2
    assert not copies(text, 32 * 8 * 8192 * d)


def test_flash_decode_shares_the_copy_back_of_a_written_cache(
        compile_for_chip):
    """A decode step as a serving stage runs it: one row scattered into
    each d = 160 cache, which XLA does in a layout of its own, then decode
    over the caches, which are returned.  Each cache is copied into the
    scatter's layout and back once, and the kernel reads the copy back:
    no third copy into the kernel's layout."""
    bf = jnp.bfloat16
    cache = (32, 8, 8192, 160)

    def step(q, k, v, rk, rv, n):
        rows = jnp.arange(32)
        k, v = k.at[rows, :, n].set(rk), v.at[rows, :, n].set(rv)
        return ops.covenant_decode_attention(q, k, v, n + 1,
                                             interpret=False), k, v

    args = [((32, 32, 160), bf), (cache, bf), (cache, bf),
            ((32, 8, 160), bf), ((32, 8, 160), bf), ((32,), jnp.int32)]
    text = compile_for_chip(step, *args)
    assert kernels(text) == {"flash_decode"}
    assert len(copies(text, 32 * 8 * 8192 * 160)) == 4
    call = re.search(r"flash_decode[.\d]* = [^\n]*?custom-call\(([^)]*)\)",
                     text)
    k_op, v_op = call.group(1).split(", ")[2:4]
    # the entry computation comes last; its root returns the caches
    returned = re.findall(r"ROOT [^\n]* tuple\(([^)]*)\)",
                          text)[-1].split(", ")
    for op in (k_op, v_op):
        src = re.search(rf"{re.escape(op)} = \S+ bitcast\((%[\w.\-]+)\)",
                        text)
        assert src and src.group(1) in returned, (op, returned)


def test_ssd_chunk_scan_compiles(compile_for_chip):
    """mamba2-2.7b widths: 80 heads of 64, d_state 128, one group, chunk
    256, 2048 tokens."""
    bf, f32 = jnp.bfloat16, jnp.float32
    text = compile_for_chip(
        lambda x, dt, a, b, c: ops.covenant_ssd(x, dt, a, b, c, chunk=256,
                                                interpret=False),
        ((1, 2048, 80, 64), bf), ((1, 2048, 80), f32), ((80,), f32),
        ((1, 2048, 1, 128), bf), ((1, 2048, 1, 128), bf))
    assert kernels(text) == {"ssd_chunk_scan"}
    # B/C's repeat fuses into their relayout, which names the fusion
    assert steps(text) == {"layout"}
    # the cumsum, decays, carry across chunks and the states' read-out
    # run in the kernel: no op outside it names them
    outside = [name for name in re.findall(r'op_name="([^"]*)"', text)
               if {"decay", "carry", "inter"} & set(name.split("/"))]
    assert not outside, outside


@pytest.mark.parametrize("s", [2048, 128])
def test_ssd_chunk_scan_compiles_with_init_state(compile_for_chip, s):
    """Continued from a state, over 8 chunks and over one of 128 tokens:
    the kernel's state input and its single-chunk grid."""
    bf, f32 = jnp.bfloat16, jnp.float32
    text = compile_for_chip(
        lambda x, dt, a, b, c, st: ops.covenant_ssd(
            x, dt, a, b, c, chunk=256, init_state=st, return_state=True,
            interpret=False),
        ((1, s, 80, 64), bf), ((1, s, 80), f32), ((80,), f32),
        ((1, s, 1, 128), bf), ((1, s, 1, 128), bf), ((1, 80, 64, 128), f32))
    assert kernels(text) == {"ssd_chunk_scan"}
    assert steps(text) == {"layout"}


def test_covenant_experts_compiles(compile_for_chip):
    """granite-4.0-h-small's expert layer on one of two chips: 32 tokens of
    4096 routed top-10 over 72 experts, 36 held, each a SwiGLU of 768.
    Both GEMMs are one grouped kernel each, and no op but the kernels
    touches the held experts' weights: the widths need no pad (``pad`` is
    the zero row that padding rows of a group read)."""
    bf = jnp.bfloat16
    text = compile_for_chip(
        lambda x, r, wi, wo: ops.covenant_experts(
            x, r, wi, wo, top_k=10, first=0, n_experts=72, interpret=False),
        ((32, 4096), bf), ((4096, 72), bf), ((36, 4096, 1536), bf),
        ((36, 768, 4096), bf))
    assert kernels(text) == {"grouped_matmul"}
    assert len(re.findall(r'custom_call_target="tpu_custom_call"', text)) == 2
    assert steps(text) == {"route", "layout", "pad", "swiglu", "combine"}
    weights = re.findall(r"= bf16\[36,(?:4096,1536|768,4096)\]\S* ([\w-]+)\(",
                         text)
    assert set(weights) == {"parameter"}, weights


def test_sharded_train_step_keeps_its_shardings(topo, no_compile_cache):
    """On a (data=2, model=2) mesh of v5e chips the train step returns
    params and optimizer state in the shardings it takes them in, so each
    step accepts the last one's output (left to itself, XLA split some
    1024-wide vectors over ``data``).  qwen3-0.6b widths, two layers."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    model = get_model(configs.get_config("qwen3-0.6b").replace(n_layers=2))
    opt = adamw(1e-3)
    mesh = make_host_mesh(2, topo.devices)

    def placed(tree, shardings):
        return jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=s), tree, shardings)

    with jax.set_mesh(mesh):
        step, p_sh, o_sh = sharded_train_step(model, opt, mesh)
        params = jax.eval_shape(
            lambda: model.init_params(jax.random.PRNGKey(0)))
        state = jax.eval_shape(opt.init, params)
        batch = {k: jax.ShapeDtypeStruct((8, 128), dt,
                                         sharding=NamedSharding(mesh, P()))
                 for k, dt in (("tokens", jnp.int32), ("targets", jnp.int32),
                               ("weights", jnp.float32))}
        compiled = step.lower(placed(params, p_sh), placed(state, o_sh),
                              batch).compile()
    shapes = jax.tree.leaves((params, state))
    want = jax.tree.leaves((p_sh, o_sh))
    got = jax.tree.leaves(compiled.output_shardings)[:len(want)]
    assert all(g.is_equivalent_to(w, x.ndim)
               for g, w, x in zip(got, want, shapes))
