"""End-to-end driver: train a ~100M-param qwen3-family model on the
synthetic LM stream for a few hundred steps with the full production
stack (sharded step, checkpoints, fault tolerance).

Layer compilation runs through the unified driver first: the step's GEMMs
are compiled with ``repro.compile`` (``repro/launch/layers.py``) and the
accelerator cycle report printed; with ``REPRO_CACHE_DIR`` set, relaunches
replay the compiles from the disk artifact store.

    PYTHONPATH=src python examples/train_lm.py [--steps 300]
"""
import argparse

import jax

from repro import configs
from repro.data import SyntheticLM
from repro.launch.layers import layer_report
from repro.launch.mesh import make_host_mesh
from repro.models import get_model
from repro.optim import adamw, cosine_schedule
from repro.runtime import make_train_step, train_loop


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--ckpt-dir", default="checkpoints/train_lm")
    ap.add_argument("--accel-target", default="hvx")
    args = ap.parse_args()

    # ~100M params: qwen3 family, scaled width/depth
    cfg = configs.get_config("qwen3-0.6b").replace(
        n_layers=10, d_model=768, n_heads=12, n_kv_heads=6, d_ff=3072,
        vocab=32768, head_dim=64, param_dtype="float32",
        compute_dtype="float32", remat=False)
    model = get_model(cfg)
    from repro.roofline import param_count
    total, _ = param_count(cfg)
    print(f"[train_lm] {total / 1e6:.1f}M params")
    # per-GEMM accelerator cycles at the training token count (8 x 256),
    # compiled through the driver's pipeline/cache/store seam
    print(layer_report(cfg, tokens=8 * 256, target=args.accel_target))

    mesh = make_host_mesh()
    with jax.set_mesh(mesh):
        params = model.init_params(jax.random.PRNGKey(0))
        opt = adamw(cosine_schedule(1e-3, 30, args.steps))
        opt_state = opt.init(params)
        step = jax.jit(make_train_step(model.loss_fn, opt, microbatches=2),
                       donate_argnums=(0, 1))
        data = SyntheticLM(vocab=cfg.vocab, seq_len=256, global_batch=8,
                           seed=0)
        params, opt_state, rep = train_loop(
            step, params, opt_state, lambda s: data.batch(s),
            steps=args.steps, ckpt_dir=args.ckpt_dir, ckpt_every=100,
            log_every=25)
    print(f"[train_lm] loss {rep.losses[0]:.3f} -> {rep.losses[-1]:.3f}")
    assert rep.losses[-1] < rep.losses[0]


if __name__ == "__main__":
    main()
