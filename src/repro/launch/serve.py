"""Serving driver: batched prefill + greedy decode.
``python -m repro.launch.serve --arch qwen3-0.6b``.

Requests are served in fixed batches: each batch is prefilled under
``jit``, then decoded token by token with the KV cache donated between
steps, until every sequence has hit EOS or ``--max-new`` tokens.  The mesh
is this host's devices; ``--smoke`` shrinks the config so that it runs on a
CPU.

Layer compilation is routed through the unified driver: before serving,
the model's decode-shape GEMMs are compiled with ``repro.compile`` for
``--accel-target`` (optionally with ``--accel-search`` schedule search)
and the per-layer accelerator cycle report is printed.  With
``REPRO_CACHE_DIR`` set, repeated launches replay these compiles from the
disk artifact store.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

import repro
from repro import configs
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import get_model


def main(argv: list[str] | None = None) -> dict:
    """Serve ``--requests`` random prompts; returns the counts printed."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(configs.ARCHS))
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--accel-target", default="hvx",
                    help="Covenant target name for the layer-compile report: "
                         "any repro.targets name, incl. derived variants "
                         "like 'dnnweaver@pe=32x32' ('none' disables it)")
    ap.add_argument("--accel-search", action="store_true",
                    help="schedule-search the layer compiles "
                         "(CompileOptions(search=...))")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = configs.get_config(args.arch, smoke=args.smoke)
    model = get_model(cfg)
    if args.accel_target != "none":
        from repro.launch.layers import layer_report
        opts = repro.CompileOptions(
            search=repro.SearchOptions(generations=3, population=8)
            if args.accel_search else None)
        print(layer_report(cfg, tokens=args.batch,
                           target=args.accel_target, options=opts))
    mesh = make_host_mesh()
    rng = np.random.default_rng(args.seed)

    with jax.set_mesh(mesh):
        params = model.init_params(jax.random.PRNGKey(args.seed))
        prefill = jax.jit(model.prefill, donate_argnums=(2,))
        decode = jax.jit(model.decode_step, donate_argnums=(2,))

        def new_prompt():
            return rng.integers(2, cfg.vocab, args.prompt_len)

        served = 0
        total_tokens = 0
        t0 = time.perf_counter()
        queue = [new_prompt() for _ in range(args.requests)]
        while queue:
            batch_prompts = [queue.pop() for _ in
                             range(min(args.batch, len(queue)))]
            bs = len(batch_prompts)
            toks = jnp.asarray(np.stack(batch_prompts), jnp.int32)
            batch = {"tokens": toks}
            for name, (shape_fn, dtype) in model.extra_inputs.items():
                batch[name] = jnp.asarray(
                    rng.standard_normal(shape_fn(bs, args.prompt_len)),
                    dtype)
            cache = model.init_cache(bs, args.max_len)
            logits, cache = prefill(params, batch, cache)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            done = np.zeros(bs, bool)
            for _ in range(args.max_new):
                logits, cache = decode(params, tok, cache)
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
                total_tokens += int((~done).sum())
                done |= np.asarray(tok) == 1  # EOS
                if done.all():
                    break
            served += bs
        dt = time.perf_counter() - t0
        print(f"[serve] {cfg.name}: {served} requests, {total_tokens} new "
              f"tokens in {dt:.2f}s ({total_tokens / dt:.1f} tok/s)")
    return {"requests": served, "new_tokens": total_tokens, "seconds": dt}


if __name__ == "__main__":
    main()
