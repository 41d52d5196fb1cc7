"""Compile each cell's pass at its full size for a described TPU v5e, with
no chip attached: what the chip's compiler (Mosaic for the Pallas kernels,
XLA for the rest) would refuse, it refuses here.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/chip/tests
"""
from __future__ import annotations

import json
import os

import jax
import pytest
from jax.sharding import SingleDeviceSharding

import passes
import traffic as T

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]
HBM = 16e9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    return SingleDeviceSharding(topo.devices[0])


def cell_pass(name: str):
    """The cell's pass body and the shapes of its arguments, without
    making any array."""
    cell = next(w for w in BENCH["workloads"] if w["name"] == name)
    entry = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    with open(os.path.join(ROOT, entry["file"])) as f:
        cfg = json.load(f)
    traffic = T.load(cell["traffic"])
    kind = passes.load(f"{cfg['family']}_{traffic['phase']}")
    bodies = []

    def arrays(key):
        p = kind.build(cfg, traffic, 1, key)
        bodies.append(p.body)
        return p.params, p.state, p.inputs[0]

    shapes = jax.eval_shape(arrays, jax.random.key(1))
    return bodies[0], shapes


@pytest.mark.parametrize("name", CELLS)
def test_pass_compiles_for_v5e(name, one_chip):
    body, shapes = cell_pass(name)
    args = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        shapes)
    compiled = jax.jit(body, donate_argnums=(1,)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < HBM, (name, total)
