import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    # keep scan bodies faithful: the CPU backend's loop-invariant code
    # motion materialises per-iteration mask tables ("wide" arrays) that a
    # TPU compile would compute in-register — it distorts the HBM-traffic
    # roofline term and bloats compile memory.
    "--xla_disable_hlo_passes=while-loop-invariant-code-motion,"
    "while-loop-expensive-invariant-code-motion")
# NOTE: the two lines above MUST run before any other import (jax locks the
# device count on first init), hence no module docstring above them and no
# `from __future__` (which would have to come first).
_DOC = """Multi-pod dry-run: AOT lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: a successful
``.lower().compile()`` on the 512-fake-device CPU backend means GSPMD found
a consistent sharding for every op, every collective is expressible, and
``memory_analysis()`` bounds per-device HBM.  ``cost_analysis()`` +
collective-bytes parsed from the optimized HLO feed §Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k \
      --mesh single --out results/dryrun
  python -m repro.launch.dryrun --all --mesh both --out results/dryrun
(--all spawns one subprocess per cell so XLA state never accumulates.)
"""

import argparse
import json
import subprocess
import sys
import time

import jax

from repro import configs


def _mesh(kind: str):
    from repro.launch.mesh import make_production_mesh
    return make_production_mesh(multi_pod=(kind == "multi"))


def run_cell(arch: str, shape_name: str, mesh_kind: str,
             microbatches: int = 4, want_hlo: bool = False,
             overrides: dict | None = None,
             zero_serve_params: bool | None = None) -> dict:
    """Lower + compile one cell; returns the roofline-ready record."""
    from repro.launch import specs
    from repro.models.common import configure_activation_sharding
    from repro.roofline.collect import collect_compiled

    ok, why = configs.applicable(arch, shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
                "status": "skipped", "reason": why}

    shape = configs.SHAPES[shape_name]
    mesh = _mesh(mesh_kind)
    t0 = time.time()
    cfg = configs.get_config(arch)
    with jax.set_mesh(mesh):
        batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
        heads = "model" if (cfg.n_heads and
                            cfg.n_heads % mesh.shape["model"] == 0) else None
        vocab = "model" if cfg.vocab % mesh.shape["model"] == 0 else None
        configure_activation_sharding(batch_axes, "model", heads, vocab)
        try:
            if shape.kind == "train":
                fn, args, in_sh, out_sh = specs.train_cell(
                    arch, shape_name, mesh, microbatches=microbatches,
                    overrides=overrides)
                jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                                 donate_argnums=(0, 1))
            else:
                kind = "prefill" if shape.kind == "prefill" else "decode"
                fn, args, in_sh, out_sh = specs.serve_cell(
                    arch, shape_name, mesh, kind, overrides=overrides,
                    zero_params=zero_serve_params)
                jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                                 donate_argnums=(2,))
            lowered = jitted.lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
        finally:
            configure_activation_sharding(None, None, None, None)

    record = collect_compiled(compiled, lowered)
    record.update({
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "status": "ok", "kind": shape.kind,
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "n_devices": mesh.size, "microbatches": microbatches,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
    })
    if want_hlo:
        record["hlo_text"] = compiled.as_text()
    return record


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--timeout", type=int, default=1800)
    ap.add_argument("--set", nargs="*", default=[],
                    help="ArchConfig overrides, e.g. ssm_chunk=128")
    ap.add_argument("--serve-sharding", default="auto",
                    choices=["auto", "zero", "replicated"])
    args = ap.parse_args()
    overrides = {}
    for kv in args.set:
        k, v = kv.split("=")
        overrides[k] = type(getattr(configs.get_config("qwen3-0.6b"), k))(
            eval(v) if v in ("True", "False") else v)             if not v.lstrip("-").isdigit() else int(v)
    os.makedirs(args.out, exist_ok=True)

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    if args.all:
        # one subprocess per cell: isolates XLA state + survives OOM/crash
        cells = [(a, s) for a, s, ok, _ in configs.cells(include_skipped=True)]
        failures = []
        for mesh_kind in meshes:
            for arch, shape in cells:
                tag = f"{arch}__{shape}__{mesh_kind}"
                out_file = os.path.join(args.out, tag + ".json")
                if os.path.exists(out_file):
                    print(f"[dryrun] {tag}: cached")
                    continue
                cmd = [sys.executable, "-m", "repro.launch.dryrun",
                       "--arch", arch, "--shape", shape, "--mesh", mesh_kind,
                       "--microbatches", str(args.microbatches),
                       "--serve-sharding", args.serve_sharding,
                       "--out", args.out] + \
                    (["--set"] + args.set if args.set else [])
                print(f"[dryrun] {tag} ...", flush=True)
                r = subprocess.run(cmd, capture_output=True, text=True,
                                   timeout=args.timeout)
                if r.returncode != 0:
                    failures.append(tag)
                    with open(os.path.join(args.out, tag + ".err"), "w") as f:
                        f.write(r.stdout[-4000:] + "\n" + r.stderr[-8000:])
                    print(f"[dryrun] {tag}: FAILED")
                else:
                    print(r.stdout.strip().splitlines()[-1]
                          if r.stdout.strip() else f"[dryrun] {tag}: ok")
        print(f"[dryrun] done; {len(failures)} failures: {failures}")
        return 1 if failures else 0

    assert args.arch and args.shape, "--arch/--shape or --all required"
    for mesh_kind in meshes:
        rec = run_cell(args.arch, args.shape, mesh_kind, args.microbatches,
                       overrides=overrides or None,
                       zero_serve_params={"auto": None, "zero": True,
                                          "replicated": False}[
                                              args.serve_sharding])
        tag = f"{args.arch}__{args.shape}__{mesh_kind}"
        with open(os.path.join(args.out, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=1)
        if rec["status"] == "ok":
            print(f"[dryrun] {tag}: ok flops={rec['flops']:.3e} "
                  f"bytes/dev={rec['bytes_per_device']:.3e} "
                  f"coll_bytes={rec['collective_bytes']:.3e} "
                  f"compile={rec['compile_s']}s")
        else:
            print(f"[dryrun] {tag}: {rec['status']} ({rec.get('reason','')})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
