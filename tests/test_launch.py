"""Launch-layer tests: mesh factory, input specs, sharding assignments,
and a small-scale AOT lower+compile in a subprocess with fake devices
(a miniature of the real dry-run, fast enough for CI)."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.launch import specs as lspecs


def test_mesh_factory_shapes():
    # constructing the production meshes requires >= 512 devices, so here
    # we only check the factory's geometry logic via its source contract
    import inspect
    src = inspect.getsource(__import__("repro.launch.mesh",
                                       fromlist=["make_production_mesh"]))
    assert "(2, 16, 16)" in src and "(16, 16)" in src
    assert '("pod", "data", "model")' in src


def test_train_batch_specs_vlm_accounts_for_image_prefix():
    cfg = configs.get_config("paligemma-3b")
    from repro.models import get_model
    model = get_model(cfg)
    shape = configs.SHAPES["train_4k"]
    b = lspecs.train_batch_specs(cfg, shape, model)
    assert b["tokens"].shape == (256, 4096 - 256)
    assert b["patches"].shape == (256, 256, 1152)


def test_serve_specs_cache_shapes():
    cfg = configs.get_config("gemma3-12b")
    from repro.models import get_model
    model = get_model(cfg)
    shape = configs.SHAPES["decode_32k"]
    pre, tok, cache = lspecs.serve_specs(cfg, shape, model)
    assert tok.shape == (128,)
    # local layers: rolling window cache; global layers: full 32k
    local = cache["layers"][0]["k"]
    glob = cache["layers"][5]["k"]
    assert local.shape[3] == cfg.window
    assert glob.shape[3] == 32768


_MINI_DRYRUN = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from jax.sharding import AxisType
from repro.launch import specs
from repro import configs
from repro.models.common import configure_activation_sharding

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto, AxisType.Auto))
# shrink shapes for speed: fabricate a small ShapeSpec
configs.SHAPES["mini_train"] = configs.ShapeSpec("mini_train", "train", 64, 8)
configs.SHAPES["mini_decode"] = configs.ShapeSpec("mini_decode", "decode",
                                                  64, 8)
ok = []
with jax.set_mesh(mesh):
    configure_activation_sharding(("data",), "model", None, None)
    for arch, shape, kind in [
        ("qwen3-0.6b", "mini_train", "train"),
        ("whisper-base", "mini_train", "train"),
        ("qwen3-0.6b", "mini_decode", "decode"),
        ("mamba2-2.7b", "mini_decode", "decode"),
    ]:
        if kind == "train":
            fn, args, in_sh, out_sh = specs.train_cell(arch, shape, mesh,
                                                       microbatches=2)
            c = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                        donate_argnums=(0, 1)).lower(*args).compile()
        else:
            fn, args, in_sh, out_sh = specs.serve_cell(arch, shape, mesh,
                                                       "decode")
            c = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                        donate_argnums=(2,)).lower(*args).compile()
        assert c.cost_analysis() is not None
        ok.append(arch + ":" + kind)
    configure_activation_sharding(None, None, None, None)
print("MINI_DRYRUN_OK", ok)
"""


def test_mini_dryrun_compiles_multidevice():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", _MINI_DRYRUN],
                       capture_output=True, text=True, env=env, cwd=root,
                       timeout=900)
    assert "MINI_DRYRUN_OK" in r.stdout, (r.stdout[-2000:], r.stderr[-4000:])


def test_layer_gemms_compile_through_driver():
    """The launch layer's Covenant bridge: per-block GEMMs of an arch
    compile via repro.compile (shared cache), and the report renders."""
    import repro
    from repro.launch import layers as llayers

    repro.clear_cache()
    cfg = configs.get_config("qwen3-0.6b", smoke=True)
    pairs = llayers.compile_layer_gemms(cfg, tokens=4)
    names = [g.name for g, _ in pairs]
    assert any("attn_qkv" in n for n in names)
    assert any("lm_head" in n for n in names)
    assert all(art.cycles() > 0 for _, art in pairs)
    # second compile of the same shapes is all cache hits
    before = repro.cache_stats()["misses"]
    llayers.compile_layer_gemms(cfg, tokens=4)
    assert repro.cache_stats()["misses"] == before
    report = llayers.layer_report(cfg, tokens=4)
    assert "block total" in report and cfg.name in report
    repro.clear_cache()


def test_layer_variant_report_spans_architecture_family():
    """The launch bridge sweeps derived accelerator variants by name in
    one heterogeneous compile_many batch."""
    import repro
    from repro.launch import layers as llayers

    repro.clear_cache()
    cfg = configs.get_config("qwen3-0.6b", smoke=True)
    report = llayers.variant_report(
        cfg, tokens=4, targets=["hvx", "hvx@edge.L2.VRF.bandwidth=512"])
    assert "hvx@edge.L2.VRF.bandwidth=512" in report
    assert "lm_head" in report
    repro.clear_cache()


def test_cache_spec_prefers_heads_then_seq():
    from jax.sharding import PartitionSpec as P

    class MeshStub:
        shape = {"data": 16, "model": 16}

    cfg = configs.get_config("command-r-plus-104b")
    # kv=8 cannot shard 16-way -> sequence over model
    spec = lspecs.cache_spec_for("layers/#0/k", (64, 128, 8, 32768, 128),
                                 cfg, MeshStub())
    assert spec == P(None, ("data",) if False else "data", None, "model",
                     None) or spec == P(None, "data", None, "model", None)
    # kv=16 (deepseek) -> heads over model
    cfg2 = configs.get_config("deepseek-moe-16b")
    spec2 = lspecs.cache_spec_for("layers/#0/k", (28, 128, 16, 32768, 128),
                                  cfg2, MeshStub())
    assert spec2 == P(None, "data", "model", None, None)
