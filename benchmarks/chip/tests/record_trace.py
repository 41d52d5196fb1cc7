#!/usr/bin/env python3
"""Record the small trace that ``test_xplane.py`` reads, on the chip.

    python3 benchmarks/chip/tests/record_trace.py --workload <cell> \
        --seed <n> --seconds <s>

Makes one traced run of the cell exactly as ``run.py --trace 1`` does, but
keeps the trace (with its Perfetto copy, which the test reads as a second
witness) and what the reduction was given, under ``tests/data/<cell>/``.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import jax  # noqa: E402

import run  # noqa: E402
import xplane  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    out = os.path.join(HERE, "data", args.workload)
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)

    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    cell, cfg, traffic = run.cell_spec(bench, args.workload)
    device = run.require_devices(cell["chips"])
    run.enable_compile_cache()
    run.TRACE_DIR = os.path.join(out, "trace")
    jax.profiler.start_trace = functools.partial(jax.profiler.start_trace,
                                                 create_perfetto_trace=True)
    read = xplane.read

    def keep(trace_dir, **kw):
        with open(os.path.join(out, "inputs.json"), "w") as f:
            json.dump({"passes": kw["passes"], "names": kw["names"],
                       "xla_mult": kw["xla_mult"],
                       "work": [[c.family, c.flops, c.bytes]
                                for c in kw["work"]]}, f)
        return read(trace_dir, keep=True, **kw)

    run.xplane.read = keep
    result = run.run(cfg, traffic, seed=args.seed, seconds=args.seconds,
                     trace=True, limits=None, device=device,
                     metrics=run.cell_metrics(bench, args.workload, True))
    with open(os.path.join(out, "result.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
