"""Training driver: ``python -m repro.launch.train --arch qwen3-0.6b
--steps 200``.

Composes the whole stack: config -> model -> sharded train step (pjit) ->
synthetic data -> fault-tolerant loop (checkpoint/restart, NaN rollback,
straggler monitor).  The mesh is this host's devices as (data, model),
with ``--model-axis`` chips on the tensor axis; ``--smoke`` shrinks the
config so that it runs on a CPU.
"""
from __future__ import annotations

import argparse

import jax

from repro import configs
from repro.data import SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import get_model
from repro.optim import adamw, cosine_schedule, int8_compressed
from repro.runtime import make_train_step, sharding as shard_rules, train_loop


def sharded_train_step(model, opt, mesh, microbatches: int = 1):
    """The jitted train step on ``mesh``, and the shardings the sharding
    rules give params and optimizer state; the step takes and returns
    them in those shardings, so each step accepts the last one's output."""
    params = jax.eval_shape(lambda: model.init_params(jax.random.PRNGKey(0)))
    p_sh = shard_rules.shardings(params, mesh)
    o_sh = shard_rules.shardings(jax.eval_shape(opt.init, params), mesh)
    step_fn = jax.jit(
        make_train_step(model.loss_fn, opt, microbatches=microbatches,
                        grad_shardings=p_sh),
        in_shardings=(p_sh, o_sh, None), out_shardings=(p_sh, o_sh, None),
        donate_argnums=(0, 1))
    return step_fn, p_sh, o_sh


def sharded_train_state(model, opt, mesh, seed: int = 0,
                        microbatches: int = 1):
    """Params and optimizer state placed on ``mesh``, and the jitted step
    over them.  Call inside ``jax.set_mesh(mesh)``."""
    step_fn, p_sh, o_sh = sharded_train_step(model, opt, mesh, microbatches)
    params = jax.device_put(model.init_params(jax.random.PRNGKey(seed)), p_sh)
    opt_state = jax.device_put(opt.init(params), o_sh)
    return params, opt_state, step_fn


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(configs.ARCHS))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--model-axis", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--accel-target", default="hvx",
                    help="Covenant target name for the layer-compile report: "
                         "any repro.targets name, incl. derived variants "
                         "like 'dnnweaver@pe=32x32' ('none' disables it)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = configs.get_config(args.arch, smoke=args.smoke)
    model = get_model(cfg)
    if args.accel_target != "none":
        # layer compilation goes through the unified driver (repro.compile):
        # per-GEMM accelerator cycles at the training token count, replayed
        # from the disk artifact store when REPRO_CACHE_DIR is set
        from repro.launch.layers import layer_report
        print(layer_report(cfg, tokens=args.global_batch * args.seq_len,
                           target=args.accel_target))
    mesh = make_host_mesh(args.model_axis)
    print(f"[train] {cfg.name} on mesh {dict(mesh.shape)}")

    opt = adamw(cosine_schedule(args.lr, args.warmup, args.steps))
    if args.compress_grads:
        opt = int8_compressed(opt)

    with jax.set_mesh(mesh):
        params, opt_state, step_fn = sharded_train_state(
            model, opt, mesh, args.seed, args.microbatches)

        data = SyntheticLM(
            vocab=cfg.vocab, seq_len=args.seq_len,
            global_batch=args.global_batch, seed=args.seed,
            extras={k: ((lambda b, s, fn=fn_d: fn(b, s)), dt)
                    for k, (fn_d, dt) in model.extra_inputs.items()})

        params, opt_state, report = train_loop(
            step_fn, params, opt_state, lambda s: data.batch(s),
            steps=args.steps, ckpt_dir=f"{args.ckpt_dir}/{cfg.name}",
            ckpt_every=args.ckpt_every)
    print(f"[train] done: {report.steps_run} steps, "
          f"loss {report.losses[0]:.3f} -> {report.losses[-1]:.3f}, "
          f"{report.rollbacks} rollbacks, "
          f"{len(report.slow_steps)} straggler events")


if __name__ == "__main__":
    main()
