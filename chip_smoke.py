"""Bring-up check on a TPU: the Covenant-tiled kernels against plain-JAX
references, then full-width qwen3-0.6b serving through the normal entry
point.

    python chip_smoke.py             # one chip: kernels, then serving
    python chip_smoke.py --chips 4   # four chips: sharded train steps only

Everything runs in this one process, which holds the chip.  Each case
prints its blocks and its error on a line of its own.  The last line is a
JSON object naming the device, printed only when every phase passed; any
mismatch or exception exits non-zero before it.  With no TPU (for example
under ``JAX_PLATFORMS=cpu``) the script exits non-zero at once.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import traceback
from typing import Callable

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# qwen3-0.6b tokens per GEMM case (one 4096-token prefill)
QWEN_TOKENS = 4096
# serving traffic: 8 requests in batches of 4, 128-token prompts, 32 new
SERVE_ARGV = ["--arch", "qwen3-0.6b", "--requests", "8", "--batch", "4",
              "--prompt-len", "128", "--max-new", "32", "--max-len", "160"]
TRAIN_STEPS = 3
# |kernel - reference| / max(1, |reference|) allowed for bf16 inputs: the
# kernels and references both accumulate in f32; what remains is output
# rounding to bf16 (2^-8) and the order of f32 sums.  int8 must be exact.
BF16_TOL = 2e-2
# serving: prefill/decode logits against a full forward over the same
# tokens, in bf16 through 28 layers (relative to max |logit|)
SERVE_TOL = 5e-2
# four chips vs one: per-step loss, relative; bf16 weights, f32 moments
TRAIN_TOL = 1e-2


def device_check(chips: int) -> dict:
    devices = jax.devices()
    d = devices[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: JAX finds no TPU (platform {d.platform!r}); "
                 "nothing was run")
    if len(devices) < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} TPU devices, "
                 f"JAX finds {len(devices)}")
    print(f"[device] {d.device_kind}, {len(devices)} device(s)", flush=True)
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


# ---------------------------------------------------------------------------
# kernels against references
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Case:
    name: str
    kernel: Callable            # jitted, compiled by Mosaic
    reference: Callable         # jitted plain JAX
    args: list                  # jax.ShapeDtypeStruct per argument
    make: Callable              # key -> arguments, drawn on the device
    blocks: tuple
    tol: float                  # 0: exact


def _normal(key, shape, dtype=jnp.bfloat16):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def _int8(key, shape):
    return jax.random.randint(key, shape, -128, 128, jnp.int32
                              ).astype(jnp.int8)


def _draw(draws: list[Callable]) -> Callable:
    def make(key):
        keys = jax.random.split(key, len(draws))
        return [d(k) for d, k in zip(draws, keys)]
    return make


def _highest(fn: Callable) -> Callable:
    """The reference at full f32 matmul precision (TPU's default for f32
    is a single bf16 pass)."""
    @functools.wraps(fn)
    def wrapped(*a):
        with jax.default_matmul_precision("highest"):
            return fn(*a)
    return wrapped


def gemm_rows() -> tuple[list[tuple], list[str]]:
    """(name, heads, m, n, k) of the GEMM/FC rows of Table 2 and of
    qwen3-0.6b's projections; and the Table-2 rows with no Pallas kernel."""
    from repro import configs
    from repro.core import library

    rows, skipped = [], []
    for spec in library.PAPER_LAYERS:
        params = {n: s.value for n, s in spec.build().surrogates.items()
                  if s.kind == "param"}
        if {"M", "N", "K", "H"} <= params.keys():
            rows.append((spec.key, params["H"], params["M"], params["N"],
                         params["K"]))
        else:
            skipped.append(spec.key)
    q = configs.get_config("qwen3-0.6b")
    t, d, qkv = QWEN_TOKENS, q.d_model, (q.n_heads + 2 * q.n_kv_heads) * q.hd
    rows += [("qwen3-0.6b-QKV", 1, t, qkv, d),
             ("qwen3-0.6b-FFN-in", 1, t, q.d_ff, d),
             ("qwen3-0.6b-FFN-out", 1, t, d, q.d_ff),
             ("qwen3-0.6b-LM-head", 1, t, q.vocab, d)]
    return rows, skipped


def gemm_case(name: str, heads: int, m: int, n: int, k: int,
              dtype) -> Case:
    from repro.kernels import ops
    from repro.kernels.tiling import gemm_blocks

    int8 = dtype == jnp.int8
    blocks = gemm_blocks(m, n, k, in_dtype="i8" if int8 else "bf16")
    kernel = functools.partial(ops.covenant_matmul, blocks=blocks)
    reference = functools.partial(
        jnp.dot, preferred_element_type=jnp.int32 if int8 else jnp.float32)
    if heads > 1:
        kernel, reference = jax.vmap(kernel), jax.vmap(reference)
    lead = (heads,) if heads > 1 else ()
    shapes = [lead + (m, k), lead + (k, n)]
    draw = _int8 if int8 else _normal
    return Case(f"{name} {'int8' if int8 else 'bf16'}", jax.jit(kernel),
                jax.jit(reference),
                [jax.ShapeDtypeStruct(s, dtype) for s in shapes],
                _draw([functools.partial(draw, shape=s) for s in shapes]),
                blocks, 0.0 if int8 else BF16_TOL)


def attention_case() -> Case:
    from repro.kernels import ops, ref
    from repro.kernels.tiling import attention_blocks

    b, hq, hkv, s, d = 1, 16, 8, 2048, 128
    blocks = attention_blocks(s, s, d)
    shapes = [(b, hq, s, d), (b, hkv, s, d), (b, hkv, s, d)]
    return Case(
        "attention causal Sq=2048 16q/8kv hd128 bf16",
        jax.jit(functools.partial(ops.covenant_attention, causal=True,
                                  blocks=blocks)),
        jax.jit(_highest(functools.partial(ref.attention_ref, causal=True))),
        [jax.ShapeDtypeStruct(x, jnp.bfloat16) for x in shapes],
        _draw([functools.partial(_normal, shape=x) for x in shapes]),
        blocks, BF16_TOL)


def decode_case() -> Case:
    from repro.kernels import ops, ref

    b, hq, hkv, s, d, block_kv = 8, 16, 8, 32768, 128, 512

    def reference(q, k, v, kv_len):
        return ref.attention_ref(q[:, :, None], k, v, causal=False,
                                 kv_len=kv_len)[:, :, 0]

    shapes = [(b, hq, d), (b, hkv, s, d), (b, hkv, s, d)]
    return Case(
        "decode batch=8 cache=32k 16q/8kv hd128 bf16",
        jax.jit(functools.partial(ops.covenant_decode_attention,
                                  block_kv=block_kv)),
        jax.jit(_highest(reference)),
        [jax.ShapeDtypeStruct(x, jnp.bfloat16) for x in shapes]
        + [jax.ShapeDtypeStruct((b,), jnp.int32)],
        _draw([functools.partial(_normal, shape=x) for x in shapes]
              + [lambda key: jax.random.randint(key, (b,), 1, s + 1)]),
        (block_kv,), BF16_TOL)


def ssd_case() -> Case:
    """mamba2-2.7b's SSD widths: 80 heads of 64, d_state 128, one group."""
    from repro.kernels import ops, ref

    b, s, h, p, g, n, chunk = 1, 2048, 80, 64, 1, 128, 256
    bf = jnp.bfloat16
    args = [jax.ShapeDtypeStruct((b, s, h, p), bf),
            jax.ShapeDtypeStruct((b, s, h), jnp.float32),
            jax.ShapeDtypeStruct((h,), jnp.float32),
            jax.ShapeDtypeStruct((b, s, g, n), bf),
            jax.ShapeDtypeStruct((b, s, g, n), bf)]
    draws = [functools.partial(_normal, shape=(b, s, h, p)),
             lambda key: jax.random.uniform(key, (b, s, h), jnp.float32,
                                            1e-3, 0.1),
             lambda key: -jax.random.uniform(key, (h,), jnp.float32, 1, 16),
             functools.partial(_normal, shape=(b, s, g, n)),
             functools.partial(_normal, shape=(b, s, g, n))]
    return Case(
        "ssd mamba2-2.7b 80h x 64, N=128, 2048 tokens",
        jax.jit(functools.partial(ops.covenant_ssd, chunk=chunk)),
        jax.jit(_highest(ref.ssd_ref)), args, _draw(draws), (chunk,),
        BF16_TOL)


def kernel_cases() -> tuple[list[Case], list[str]]:
    rows, skipped = gemm_rows()
    cases = [gemm_case(*row, dtype) for row in rows
             for dtype in (jnp.bfloat16, jnp.int8)]
    return cases + [attention_case(), decode_case(), ssd_case()], skipped


@jax.jit
def _max_err(got, want):
    if jnp.issubdtype(want.dtype, jnp.integer):
        return jnp.max(jnp.abs(got - want)), jnp.int32(1)
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return (jnp.max(jnp.abs(got - want)),
            jnp.maximum(1.0, jnp.max(jnp.abs(want))))


def run_case(case: Case, key) -> bool:
    args = case.make(key)
    got = case.kernel(*args)
    want = case.reference(*args)
    err, scale = (float(x) for x in _max_err(got, want))
    assert got.shape == want.shape, (got.shape, want.shape)
    rel = err / scale
    ok = rel <= case.tol                    # NaN fails too
    print(f"[kernel] {case.name:44s} blocks={case.blocks} "
          f"max_abs_err={err:.3e} rel={rel:.3e} tol={case.tol:g} "
          f"{'ok' if ok else 'MISMATCH'}", flush=True)
    return ok


def kernel_phase() -> list[str]:
    cases, skipped = kernel_cases()
    for name in skipped:
        print(f"[kernel] {name:44s} skipped: conv layer, no Pallas kernel",
              flush=True)
    failed = []
    key = jax.random.PRNGKey(0)
    for i, case in enumerate(cases):
        try:
            if not run_case(case, jax.random.fold_in(key, i)):
                failed.append(case.name)
        except Exception:
            traceback.print_exc()
            print(f"[kernel] {case.name:44s} FAILED", flush=True)
            failed.append(case.name)
    return failed


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def serve_phase() -> list[str]:
    from repro.launch import serve

    stats = serve.main(SERVE_ARGV)
    want = int(SERVE_ARGV[SERVE_ARGV.index("--requests") + 1])
    ok = stats["requests"] == want and stats["new_tokens"] >= want
    print(f"[serve] {stats['requests']} requests, {stats['new_tokens']} new "
          f"tokens {'ok' if ok else 'MISMATCH'}", flush=True)
    return [] if ok else ["serve counts"]


def serve_reference_phase(seed: int = 0) -> list[str]:
    """Prefill and one decode step of full-width qwen3-0.6b against the
    training forward pass over the same tokens."""
    from repro import configs
    from repro.models import get_model, transformer
    from repro.models.common import logits_from_hidden

    cfg = configs.get_config("qwen3-0.6b")
    model = get_model(cfg)
    b, s = 2, 128
    params = model.init_params(jax.random.PRNGKey(seed))
    toks = jax.random.randint(jax.random.PRNGKey(seed + 1), (b, s + 1), 2,
                              cfg.vocab)
    cache = model.init_cache(b, s + 32)
    pre, cache = jax.jit(model.prefill)(params, {"tokens": toks[:, :s]},
                                        cache)
    dec, _ = jax.jit(model.decode_step)(params, toks[:, s], cache)

    @jax.jit
    def full(p, t):
        return logits_from_hidden(cfg, p["embed"],
                                  transformer.forward(cfg, p, t))

    ref = full(params, toks)
    failed = []
    for name, got, want in (("prefill", pre, ref[:, s - 1]),
                            ("decode", dec, ref[:, s])):
        err, scale = (float(x) for x in _max_err(got, want))
        ok = err / scale <= SERVE_TOL
        print(f"[serve-ref] {name} logits vs forward: max_abs_err={err:.3e} "
              f"rel={err / scale:.3e} tol={SERVE_TOL:g} "
              f"{'ok' if ok else 'MISMATCH'}", flush=True)
        if not ok:
            failed.append(f"serve-ref {name}")
    return failed


# ---------------------------------------------------------------------------
# four chips: sharded train steps against one device
# ---------------------------------------------------------------------------


def train_run(mesh, batches) -> tuple[list[float], int, int]:
    """Losses of TRAIN_STEPS steps on ``mesh``; the devices holding
    parameter shards, and how many parameters are split across them."""
    from repro import configs
    from repro.launch.train import sharded_train_state
    from repro.models import get_model
    from repro.optim import adamw, cosine_schedule

    cfg = configs.get_config("qwen3-0.6b")
    model = get_model(cfg)
    opt = adamw(cosine_schedule(3e-3, 1, TRAIN_STEPS))
    with jax.set_mesh(mesh):
        params, opt_state, step = sharded_train_state(model, opt, mesh)
        leaves = jax.tree.leaves(params)
        devices = {s.device for x in leaves for s in x.addressable_shards}
        split = sum(any(s.data.shape != x.shape for s in x.addressable_shards)
                    for x in leaves)
        losses = []
        for batch in batches:
            params, opt_state, metrics = step(params, opt_state, batch)
            losses.append(float(metrics["loss"]))
    return losses, len(devices), split


def train_phase() -> list[str]:
    from repro import configs
    from repro.data import SyntheticLM
    from repro.launch.mesh import make_host_mesh

    cfg = configs.get_config("qwen3-0.6b")
    data = SyntheticLM(vocab=cfg.vocab, seq_len=128, global_batch=8)
    batches = [data.batch(i) for i in range(TRAIN_STEPS)]
    devices = jax.devices()[:4]
    l4, n4, split4 = train_run(make_host_mesh(2, devices), batches)
    print(f"[train] mesh data=2 model=2: losses {l4}, params on {n4} "
          f"devices, {split4} arrays split", flush=True)
    l1, n1, _ = train_run(make_host_mesh(1, devices[:1]), batches)
    print(f"[train] {devices[0]} alone: losses {l1}, params on {n1} device",
          flush=True)
    failed = []
    for i, (a, b) in enumerate(zip(l4, l1)):
        rel = abs(a - b) / abs(b)
        ok = rel <= TRAIN_TOL
        print(f"[train] step {i}: 4 chips {a:.6f} vs 1 chip {b:.6f} "
              f"rel={rel:.3e} tol={TRAIN_TOL:g} {'ok' if ok else 'MISMATCH'}",
              flush=True)
        if not ok:
            failed.append(f"train step {i}")
    if n4 != 4 or split4 == 0:
        print(f"[train] parameters not spread over 4 devices "
              f"({n4} devices, {split4} split arrays) MISMATCH", flush=True)
        failed.append("train sharding")
    return failed


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded train check on four chips")
    args = ap.parse_args(argv)
    device = device_check(args.chips)

    from repro.launch.compile_cache import enable_compile_cache
    print(f"[cache] {enable_compile_cache()}", flush=True)
    failed = []
    phases = [train_phase] if args.chips == 4 else \
        [kernel_phase, serve_phase, serve_reference_phase]
    for phase in phases:
        try:
            failed += phase()
        except Exception:
            traceback.print_exc()
            failed.append(phase.__name__)
    if failed:
        print(f"chip_smoke: FAILED {failed}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
