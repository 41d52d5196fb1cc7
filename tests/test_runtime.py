"""Substrate tests: data determinism, optimizer, checkpointing (atomic,
keep-k, elastic), sharding rules, fault tolerance, grad accumulation,
compression; multi-device collectives run in a subprocess with 8 fake
CPU devices (so this process keeps the single real device)."""
import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import checkpoint as ckpt
from repro import configs
from repro.data import SyntheticLM
from repro.models import get_model
from repro.optim import adamw, cosine_schedule, global_norm, int8_compressed
from repro.optim.compression import compress, decompress
from repro.runtime import make_train_step, spec_for, train_loop
from repro.runtime.fault_tolerance import StragglerMonitor


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------


def test_data_deterministic_and_host_sharded():
    a = SyntheticLM(vocab=100, seq_len=32, global_batch=8, seed=3)
    b = SyntheticLM(vocab=100, seq_len=32, global_batch=8, seed=3)
    np.testing.assert_array_equal(a.batch(5)["tokens"], b.batch(5)["tokens"])
    assert not np.array_equal(a.batch(5)["tokens"], a.batch(6)["tokens"])
    # two hosts partition the global batch exactly
    h0 = SyntheticLM(vocab=100, seq_len=32, global_batch=8, seed=3,
                     n_hosts=2, host_id=0)
    h1 = SyntheticLM(vocab=100, seq_len=32, global_batch=8, seed=3,
                     n_hosts=2, host_id=1)
    full = a.batch(2)["tokens"]
    np.testing.assert_array_equal(
        np.concatenate([h0.batch(2)["tokens"], h1.batch(2)["tokens"]]), full)


def test_data_packing_structure():
    d = SyntheticLM(vocab=64, seq_len=64, global_batch=4, seed=0)
    b = d.batch(0)
    assert b["tokens"].shape == (4, 64) and b["targets"].shape == (4, 64)
    # targets are tokens shifted by one within the packed stream
    seq = d._sequence(0, 0)
    np.testing.assert_array_equal(b["tokens"][0], seq[:-1])
    np.testing.assert_array_equal(b["targets"][0], seq[1:])
    # EOS positions are masked out of the loss
    assert np.all(b["weights"][b["targets"] == d.eos] == 0.0)
    assert b["weights"].sum() > 0
    # learnability itself is asserted end-to-end by
    # test_loop_trains_checkpoints_resumes (loss decreases).


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------


def test_adamw_descends_quadratic():
    opt = adamw(0.1, weight_decay=0.0)
    params = {"w": jnp.asarray([3.0, -2.0])}
    state = opt.init(params)
    loss = lambda p: jnp.sum(p["w"] ** 2)
    for _ in range(100):
        g = jax.grad(loss)(params)
        params, state, _ = opt.update(g, state, params)
    assert float(loss(params)) < 1e-2


def test_cosine_schedule_shape():
    lr = cosine_schedule(1.0, warmup=10, total=100, floor=0.1)
    assert float(lr(0)) == 0.0
    assert abs(float(lr(10)) - 1.0) < 1e-6
    assert float(lr(100)) == pytest.approx(0.1, abs=1e-3)
    assert float(lr(55)) < float(lr(20))


def test_grad_clipping():
    opt = adamw(0.1, max_grad_norm=1.0)
    params = {"w": jnp.ones((4,))}
    state = opt.init(params)
    big = {"w": jnp.full((4,), 1e6)}
    _, _, m = opt.update(big, state, params)
    assert float(m["grad_norm"]) > 1e5  # reported pre-clip norm


def test_compression_error_feedback_reduces_bias():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal(512) * 1e-3)
    q, s = compress(g)
    assert q.dtype == jnp.int8
    deq = decompress(q, s)
    assert float(jnp.max(jnp.abs(deq - g))) <= float(s) / 2 + 1e-9
    # accumulated error with feedback ~ accumulated error of one step
    err = jnp.zeros_like(g)
    total_fb = jnp.zeros_like(g)
    for _ in range(16):
        corrected = g + err
        q, s = compress(corrected)
        deq = decompress(q, s)
        err = corrected - deq
        total_fb = total_fb + deq
    assert float(jnp.mean(jnp.abs(total_fb / 16 - g))) < \
        float(jnp.mean(jnp.abs(decompress(*compress(g)) - g)))


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------


def _tree():
    return {"a": jnp.arange(6, dtype=jnp.float32).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.bfloat16)}}


def test_checkpoint_roundtrip_and_keep_k():
    with tempfile.TemporaryDirectory() as d:
        t = _tree()
        for s in (1, 2, 3, 4, 5):
            ckpt.save_checkpoint(d, s, t, keep=2)
        assert ckpt.latest_step(d) == 5
        kept = sorted(os.listdir(d))
        assert kept == ["step_00000004", "step_00000005"]
        loaded, step, _ = ckpt.load_checkpoint(d, t)
        assert step == 5
        np.testing.assert_array_equal(loaded["a"], np.asarray(t["a"]))


def test_checkpoint_shape_mismatch_rejected():
    with tempfile.TemporaryDirectory() as d:
        ckpt.save_checkpoint(d, 1, _tree())
        bad = {"a": jnp.zeros((3, 3)), "b": {"c": jnp.ones((4,))}}
        with pytest.raises(ValueError, match="shape"):
            ckpt.load_checkpoint(d, bad)


def test_checkpoint_atomicity_no_partial_dirs():
    with tempfile.TemporaryDirectory() as d:
        ckpt.save_checkpoint(d, 7, _tree())
        # a stale tmp dir (crashed writer) must be invisible to latest_step
        os.makedirs(os.path.join(d, ".tmp_dead"), exist_ok=True)
        open(os.path.join(d, ".tmp_dead", "arrays.npz"), "w").close()
        assert ckpt.latest_step(d) == 7


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------


def test_sharding_rules_match_expected_axes():
    from jax.sharding import PartitionSpec as P
    cases = {
        ("embed/tokens", (256000, 12288)): P("model", "data"),
        ("layers/#0/attn/wq", (8, 12288, 12288)): P(None, "data", "model"),
        ("layers/#0/mlp/wi", (8, 12288, 33792)): P(None, "data", "model"),
        ("layers/#0/mlp/wo", (8, 33792, 12288)): P(None, "model", "data"),
        ("layers/#0/ffn/wi", (16, 64, 2048, 1408)): P(None, "model", "data",
                                                      None),
        ("layers/#0/ln1/scale", (8, 12288)): P(),
        ("layers/#0/mamba/in_proj", (64, 2560, 10640)): P(None, "data",
                                                          "model"),
    }
    for (path, shape), want in cases.items():
        got = spec_for(path, shape)
        assert got == want, (path, got, want)


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_setup():
    cfg = configs.get_config("qwen3-0.6b", smoke=True)
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    opt = adamw(1e-3)
    opt_state = opt.init(params)
    step_fn = jax.jit(make_train_step(model.loss_fn, opt))
    data = SyntheticLM(vocab=cfg.vocab, seq_len=32, global_batch=4, seed=0)
    return model, params, opt_state, step_fn, data


def test_loop_trains_checkpoints_resumes(tiny_setup):
    model, params, opt_state, step_fn, data = tiny_setup
    with tempfile.TemporaryDirectory() as d:
        p, o, rep = train_loop(step_fn, params, opt_state,
                               lambda s: data.batch(s), steps=8, ckpt_dir=d,
                               ckpt_every=4, logger=lambda *a: None)
        assert rep.steps_run == 8 and rep.resumed_from is None
        p, o, rep2 = train_loop(step_fn, params, opt_state,
                                lambda s: data.batch(s), steps=12,
                                ckpt_dir=d, ckpt_every=4,
                                logger=lambda *a: None)
        assert rep2.resumed_from == 8 and rep2.steps_run == 4


def test_loop_rolls_back_on_nan(tiny_setup):
    model, params, opt_state, step_fn, data = tiny_setup
    with tempfile.TemporaryDirectory() as d:
        p, o, rep = train_loop(step_fn, params, opt_state,
                               lambda s: data.batch(s), steps=6, ckpt_dir=d,
                               ckpt_every=2, inject_nan_at=3,
                               logger=lambda *a: None)
        assert rep.rollbacks == 1
        assert all(np.isfinite(l) for l in rep.losses)


def test_loop_survives_process_failure(tiny_setup):
    """Injected crash mid-run; a fresh loop resumes from the checkpoint."""
    from repro.runtime.fault_tolerance import InjectedFailure
    model, params, opt_state, step_fn, data = tiny_setup
    with tempfile.TemporaryDirectory() as d:
        with pytest.raises(InjectedFailure):
            train_loop(step_fn, params, opt_state, lambda s: data.batch(s),
                       steps=10, ckpt_dir=d, ckpt_every=2,
                       inject_failure_at=5, logger=lambda *a: None)
        p, o, rep = train_loop(step_fn, params, opt_state,
                               lambda s: data.batch(s), steps=10, ckpt_dir=d,
                               ckpt_every=2, logger=lambda *a: None)
        assert rep.resumed_from == 4  # last checkpoint before the crash
        assert rep.steps_run == 6


def test_straggler_monitor_flags_outliers():
    mon = StragglerMonitor(threshold=3.0)
    for i in range(20):
        assert not mon.observe(i, 0.1 + 0.001 * (i % 3))
    assert mon.observe(20, 1.5)
    assert mon.slow_steps and mon.slow_steps[0][0] == 20


def test_grad_accumulation_equivalence(tiny_setup):
    model, params, opt_state, _, data = tiny_setup
    opt = adamw(1e-3)
    s1 = jax.jit(make_train_step(model.loss_fn, opt, microbatches=1))
    s2 = jax.jit(make_train_step(model.loss_fn, opt, microbatches=4))
    b = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
    p1, _, m1 = s1(params, opt.init(params), b)
    p2, _, m2 = s2(params, opt.init(params), b)
    assert float(m1["loss"]) == pytest.approx(float(m2["loss"]), rel=1e-5)
    diff = max(float(jnp.max(jnp.abs(a - b2)))
               for a, b2 in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    assert diff < 2e-5


# ---------------------------------------------------------------------------
# multi-device collectives (subprocess with 8 fake devices)
# ---------------------------------------------------------------------------

_COLLECTIVE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.runtime.collectives import compressed_psum, sharded_decode_attention
from repro.kernels.ref import attention_ref

mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto, AxisType.Auto))

# compressed psum ~= plain psum
g = {"w": jnp.asarray(np.random.default_rng(0).standard_normal((8, 16)),
                      jnp.float32)}
out = compressed_psum(g, mesh, axis="data")
want = jax.tree.map(lambda x: x * mesh.shape["data"], g)
err = float(jnp.max(jnp.abs(out["w"] - want["w"])))
rel = err / float(jnp.max(jnp.abs(want["w"])))
assert rel < 0.02, rel

# seq-sharded decode attention == dense reference
b, h, s, d = 2, 4, 64, 16
rng = np.random.default_rng(1)
q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
k = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
v = jnp.asarray(rng.standard_normal((b, h, s, d)), jnp.float32)
lens = jnp.asarray([40, 64])
got = sharded_decode_attention(q, k, v, lens, mesh, seq_axis="model")
want = attention_ref(q[:, :, None], k, v, causal=False, kv_len=lens)[:, :, 0]
assert float(jnp.max(jnp.abs(got - want))) < 2e-3
print("COLLECTIVES_OK")
"""


def test_collectives_multidevice():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    r = subprocess.run([sys.executable, "-c", _COLLECTIVE_SCRIPT],
                       capture_output=True, text=True, env=env,
                       cwd=os.path.dirname(os.path.dirname(__file__)) or ".")
    assert "COLLECTIVES_OK" in r.stdout, r.stderr[-2000:]
