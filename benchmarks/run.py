"""Benchmark orchestrator: one section per paper figure + ours.

``PYTHONPATH=src python -m benchmarks.run [--only fig11,...]``
Prints ``name,us_per_call,derived`` CSV lines.

With ``REPRO_CACHE_DIR`` set, every compile goes through the disk artifact
store; ``--expect-store-hits`` makes a warm re-run *assert* it recompiled
nothing (exit 1 on any store miss) — the CI warm-sweep check.

``--emit-json PATH`` additionally writes a machine-readable benchmark
snapshot: every emitted row plus a **cycle trajectory** — the analytic
cycle count of every Table-2 layer on every evaluation target at full
optimization, and their geomean.  Cycles are deterministic compiler
*output quality*, not wall time, so the snapshot is comparable across
machines; ``--baseline PATH [--max-regression 0.05]`` turns it into the
CI ``bench-trajectory`` gate: fail if the geomean cycles regress more
than 5% against the committed baseline (improvements always pass and
print so the baseline can be re-pinned).  ``--workers N`` shards the
trajectory sweep across worker processes via ``repro.sweep``.
"""
from __future__ import annotations

import argparse
import json
import math
import statistics
import sys
import time

TRAJECTORY_TARGETS = ("hvx", "dnnweaver")


def cycle_trajectory(emit, workers: int = 1) -> dict:
    """{'LAYER@target': cycles} for every paper layer at full optimization
    — the perf-gate metric, computed through the sweep coordinator."""
    import repro
    from benchmarks.paper_figs import CONFIGS
    from repro.core import library

    report = repro.sweep([s.key for s in library.PAPER_LAYERS],
                         TRAJECTORY_TARGETS,
                         options=CONFIGS["+vec+pack+unroll"],
                         workers=workers)
    cycles = {f"{r.layer}@{r.target}": r.cycles for r in report.ok}
    expect = len(library.PAPER_LAYERS) * len(TRAJECTORY_TARGETS)
    if len(cycles) != expect:
        print(f"FAIL: trajectory sweep incomplete: {report.summary()}",
              file=sys.stderr)
        sys.exit(1)
    c = report.counts()
    emit(f"trajectory/sweep,0,{c['units']} units ({c['compiled']} compiled, "
         f"{c['dedup'] + c['store'] + c['cache']} warm)")
    return cycles


def geomean(values) -> float:
    return math.exp(statistics.mean(math.log(max(v, 1e-9))
                                    for v in values))


def check_baseline(snapshot: dict, baseline_path: str,
                   max_regression: float) -> int:
    """Compare the trajectory geomean (and per-layer worst case) against a
    committed baseline snapshot; returns the number of gate failures.

    Both geomeans are computed over the *intersection* of layer keys, so
    adding/removing a paper layer shifts neither side of the ratio — the
    gate only ever measures the compiler on layers both runs compiled."""
    with open(baseline_path, "r", encoding="utf-8") as f:
        base = json.load(f)
    failures = 0
    shared = sorted(set(snapshot["cycles"]) & set(base.get("cycles", {})))
    if not shared:
        print(f"FAIL: no shared trajectory layers with {baseline_path} — "
              f"re-pin the baseline", file=sys.stderr)
        return 1
    dropped = len(snapshot["cycles"]) - len(shared)
    if dropped:
        print(f"trajectory/layer_set,0,{dropped} layer(s) not in the "
              f"baseline excluded from the gate (re-pin to include)")
    new_g = geomean(snapshot["cycles"][k] for k in shared)
    old_g = geomean(base["cycles"][k] for k in shared)
    ratio = new_g / old_g
    print(f"trajectory/geomean,0,cycles={new_g:.1f} baseline={old_g:.1f} "
          f"ratio=x{ratio:.4f} over {len(shared)} shared layers")
    if ratio > 1 + max_regression:
        print(f"FAIL: geomean cycles regressed x{ratio:.4f} "
              f"(> {1 + max_regression:.2f}) vs {baseline_path}",
              file=sys.stderr)
        failures += 1
    worst_key, worst = None, 0.0
    for k in shared:
        r = snapshot["cycles"][k] / base["cycles"][k] - 1
        if r > worst:
            worst_key, worst = k, r
    if worst_key is not None:
        print(f"trajectory/worst_layer,0,{worst_key}=+{worst * 100:.1f}%")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list of {fig11,fig12,fig12s,fig13,fig14,"
                         "trajectory}")
    ap.add_argument("--expect-store-hits", action="store_true",
                    help="fail unless every compile was a disk-store hit "
                         "(requires REPRO_CACHE_DIR and a prior warm run)")
    ap.add_argument("--emit-json", default=None, metavar="PATH",
                    help="write rows + the cycle trajectory as JSON")
    ap.add_argument("--baseline", default=None, metavar="PATH",
                    help="committed BENCH_*.json to gate the trajectory "
                         "geomean against")
    ap.add_argument("--max-regression", type=float, default=0.05,
                    help="allowed geomean cycle regression (default 5%%)")
    ap.add_argument("--workers", type=int, default=1,
                    help="shard the trajectory sweep across N worker "
                         "processes (repro.sweep)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None

    rows = []

    def emit(line: str) -> None:
        rows.append(line)
        print(line, flush=True)

    emit("name,us_per_call,derived")
    t0 = time.time()
    if only is None or "fig11" in only:
        from benchmarks.paper_figs import fig11
        fig11(emit)
    if only is None or "fig12" in only:
        from benchmarks.paper_figs import fig12
        fig12(emit)
    if only is None or "fig12s" in only:
        from benchmarks.paper_figs import fig12_search
        fig12_search(emit)
    if only is None or "fig13" in only:
        from benchmarks.paper_figs import fig13
        fig13(emit)
    if only is None or "fig14" in only:
        from benchmarks.paper_figs import fig14_variants
        fig14_variants(emit, workers=args.workers)

    snapshot = None
    if args.emit_json or args.baseline or (only and "trajectory" in only):
        cycles = cycle_trajectory(emit, workers=args.workers)
        snapshot = {
            "schema": 1,
            "targets": list(TRAJECTORY_TARGETS),
            "cycles": cycles,
            "geomean_cycles": geomean(cycles.values()),
        }
    emit(f"benchmarks/total_wall,{(time.time() - t0) * 1e6:.0f},done")

    import repro
    stats = repro.cache_stats()
    emit(f"benchmarks/store,0,hits={stats['store_hits']} "
         f"misses={stats['store_misses']}")

    failures = 0
    if args.expect_store_hits:
        if stats["store_misses"] or not stats["store_hits"]:
            print(f"FAIL: expected an all-hit warm store sweep, got "
                  f"{stats['store_hits']} hits / "
                  f"{stats['store_misses']} misses", file=sys.stderr)
            failures += 1
        else:
            emit(f"benchmarks/store_warm,0,all {stats['store_hits']} "
                 f"compiles served from the artifact store")
    if args.baseline and snapshot is not None:
        failures += check_baseline(snapshot, args.baseline,
                                   args.max_regression)
    if args.emit_json and snapshot is not None:
        snapshot["rows"] = rows
        with open(args.emit_json, "w", encoding="utf-8") as f:
            json.dump(snapshot, f, indent=1)
        print(f"wrote {args.emit_json}", file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
