#!/usr/bin/env python3
"""Read what the limits of a cell are set from, in one process on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> \
        --seeds 11,12,... --control-seeds 21,22,23 [--seconds 1]

For each of ``--seeds`` it makes a run of the cell as ``run.py`` does (a
short window, no limits) and reads the widest gap of each tap against the
plain f32 reference: the program's readings, whose largest is the lower
reading of a limit.  For each of ``--control-seeds`` it reads the same gaps
of the control, the reference computed one precision step below the
configuration's (fp8 activations and weights, bf16 state), whose smallest
is the upper reading.  Prints one JSON object with both and their ratio.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run  # sets up sys.path for the harness and the program

import common  # noqa: E402
import passes  # noqa: E402
import reference  # noqa: E402


def control_readings(cfg: dict, traffic: dict, seed: int) -> dict:
    key = common.root_key(seed)
    p = passes.load(f"{cfg['family']}_{traffic['phase']}").build(
        cfg, traffic, seed, key)
    run.delete((p.params, p.state, p.inputs))
    checked = list(range(p.check_first)) or [0]
    checks = run.check(p, {}, checked, None, low=reference.CONTROL)
    return {t: c["value"] for t, c in checks.items()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)

    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    cell, cfg, traffic = run.cell_spec(bench, args.workload)
    device = run.require_devices(cell["chips"])
    run.enable_compile_cache()
    program, control = {}, {}
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        r = run.run(cfg, traffic, seed=seed, seconds=args.seconds,
                    trace=False, limits=None, device=device, t_start=t,
                    metrics=run.cell_metrics(bench, args.workload, False))
        program[seed] = {k: c["value"] for k, c in r["checks"].items()}
        print(f"program seed {seed}: {program[seed]} {r['metrics']} "
              f"peak {r['device']['memory_peak_bytes']} "
              f"({time.perf_counter() - t:.1f} s)", file=sys.stderr, flush=True)
    for seed in (int(s) for s in args.control_seeds.split(",")):
        control[seed] = control_readings(cfg, traffic, seed)
        print(f"control seed {seed}: {control[seed]}", file=sys.stderr,
              flush=True)
    taps = sorted(next(iter(program.values())))
    lower = {t: max(v[t] for v in program.values()) for t in taps}
    upper = {t: min(v[t] for v in control.values()) for t in taps}
    print(json.dumps({
        "workload": args.workload, "device": device, "program": program,
        "control": control, "lower": lower, "upper": upper,
        "upper_over_lower": {t: upper[t] / lower[t] if lower[t] else None
                             for t in taps}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
