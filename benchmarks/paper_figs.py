"""Paper-figure benchmarks (Fig 11 / 12 / 13 protocols).

The paper measures cycle counts on vendor cycle-accurate simulators; our
counts come from the mnemonic-faithful analytic model (``core/cost.py``),
which is validated against the executable stream machine on unrollable
layers (tests/test_codegen.py).  nnlib/TVM absolute ratios need Qualcomm's
proprietary stack (DESIGN.md D2); the figures reproduce the paper's own
*relative* protocols:

* Fig 11 — optimized Covenant schedule vs the unoptimized scalar schedule
  per Table-2 layer (the "speedup over baseline" ordering).
* Fig 12 — optimization stacking: +Vectorization, +Mnemonic Packing,
  +Loop Unrolling (the paper's 43x / 2.4x / 1.3x decomposition).
* Fig 13 — multi-target: the same layers compiled for HVX vs DNNWeaver
  (expected: systolic DNNWeaver pulls ahead on large GEMMs).
"""
from __future__ import annotations

import math
import statistics
import time

import repro
from repro.core import library

CONFIGS = {
    "vanilla": repro.CompileOptions(vectorize=False, unroll=False, pack=False),
    "+vec": repro.CompileOptions(vectorize=True, unroll=False, pack=False),
    "+vec+pack": repro.CompileOptions(vectorize=True, unroll=False, pack=True),
    "+vec+pack+unroll": repro.CompileOptions(vectorize=True, unroll=True,
                                             pack=True),
}


def layer_cycles(spec, target, cfg: repro.CompileOptions) -> float:
    """Analytic cycles via the compile driver; repeated (layer, target,
    config) points across fig11/fig12/fig13 are served from the cache."""
    return repro.compile(spec, target, cfg).cycles()


def fig11(emit) -> dict:
    """Covenant (optimized) vs unoptimized scalar baseline on HVX."""
    speedups = {}
    for spec in library.PAPER_LAYERS:
        t0 = time.perf_counter()
        base = layer_cycles(spec, "hvx", CONFIGS["vanilla"])
        opt = layer_cycles(spec, "hvx", CONFIGS["+vec+pack+unroll"])
        us = (time.perf_counter() - t0) * 1e6
        speedups[spec.key] = base / opt
        emit(f"fig11/{spec.key},{us:.0f},speedup={base / opt:.1f}")
    gmean = math.exp(statistics.mean(math.log(s) for s in speedups.values()))
    emit(f"fig11/geomean,0,speedup={gmean:.1f}")
    return speedups


def fig12(emit) -> dict:
    """Optimization stacking on HVX (the Fig-12 ablation)."""
    stages = list(CONFIGS)
    table: dict[str, dict] = {}
    for spec in library.PAPER_LAYERS:
        cycles = {}
        for stage in stages:
            cycles[stage] = layer_cycles(spec, "hvx", CONFIGS[stage])
        table[spec.key] = cycles
    # marginal factors, geometric mean across layers
    factors = {}
    for a, b in zip(stages, stages[1:]):
        fs = [table[k][a] / table[k][b] for k in table if table[k][b] > 0]
        factors[b] = math.exp(statistics.mean(math.log(max(f, 1e-9))
                                              for f in fs))
        emit(f"fig12/{b}_marginal,0,x{factors[b]:.2f}")
    total = [table[k][stages[0]] / table[k][stages[-1]] for k in table]
    gmean = math.exp(statistics.mean(math.log(t) for t in total))
    emit(f"fig12/total_stack,0,x{gmean:.1f}")
    return table


SEARCH = repro.SearchOptions(generations=4, population=10,
                             max_candidates=512)


def fig12_search(emit) -> dict:
    """Beyond-paper: §4's enabled search loop vs the one-shot heuristic —
    now a driver option.  Each paper layer gets a "+beam" row; searched
    schedules flow through the artifact cache/store like any other compile
    (a warm REPRO_CACHE_DIR replays them without re-searching)."""
    import dataclasses

    cfg = CONFIGS["+vec+pack+unroll"]
    cfg_beam = dataclasses.replace(cfg, search=SEARCH)
    gains = {}
    for spec in library.PAPER_LAYERS:
        heur = repro.compile(spec, "hvx", cfg)
        art = repro.compile(spec, "hvx", cfg_beam)
        gain = heur.cycles() / max(art.cycles(), 1e-9)
        gains[spec.key] = gain
        evaluated = art.search.evaluated if art.search is not None else 0
        emit(f"fig12s/{spec.key}+beam,0,beam_gain=x{gain:.2f} "
             f"evaluated={evaluated}")
    gmean = math.exp(statistics.mean(math.log(max(g, 1e-9))
                                     for g in gains.values()))
    stats = repro.cache_stats()
    emit(f"fig12s/geomean,0,x{gmean:.2f}")
    emit(f"fig12s/cache,0,hits={stats['hits']} misses={stats['misses']} "
         f"store_hits={stats['store_hits']} "
         f"store_misses={stats['store_misses']}")
    return gains


# Architecture family for the adaptability sweep (§2's headline claim as a
# benchmark): the registry resolves derived-variant names straight from the
# bundled covenant specs — no compiler edits, no new modules.
VARIANTS = ("dnnweaver", "dnnweaver@pe=32x32", "dnnweaver@pe=16x16")


def fig14_variants(emit, workers: int = 1) -> dict:
    """Beyond-paper: recompile paper layers across a PE-array family
    derived with ``spec.derive`` (string-addressed, content-keyed).  The
    per-variant cycle ratios quantify how much performance the 64x64 array
    buys over scaled-down family members — the design-space-sweep workload
    of arXiv 2111.15024 on top of the covenant registry.

    The sweep runs through the ``repro.sweep`` coordinator — the same
    layers x variants plan CI shards across worker processes — and the
    report's best-variant-per-layer table is emitted as ``fig14/best``
    rows."""
    cfg = CONFIGS["+vec+pack+unroll"]
    report = repro.sweep([s.key for s in library.PAPER_LAYERS], VARIANTS,
                         options=cfg, workers=workers)
    cycles = {(r.layer, r.target): r.cycles for r in report.ok}
    assert len(cycles) == len(library.PAPER_LAYERS) * len(VARIANTS), \
        report.summary()  # every unit keyed separately and succeeded
    table: dict[str, dict] = {}
    for spec in library.PAPER_LAYERS:
        table[spec.key] = {v: cycles[(spec.key, v)] for v in VARIANTS}
        ratios = " ".join(
            f"{v.partition('@')[2] or 'base'}=x"
            f"{table[spec.key][v] / table[spec.key][VARIANTS[0]]:.2f}"
            for v in VARIANTS[1:])
        emit(f"fig14/{spec.key},0,{ratios}")
    for v in VARIANTS[1:]:
        rs = [table[k][v] / table[k][VARIANTS[0]] for k in table]
        gmean = math.exp(statistics.mean(math.log(max(r, 1e-9)) for r in rs))
        emit(f"fig14/geomean_{v.partition('@')[2]},0,x{gmean:.2f}")
    for layer, best in sorted(report.best_by_layer().items()):
        emit(f"fig14/best/{layer},0,variant={best.target} "
             f"cycles={best.cycles:.0f}")
    return table


def fig13(emit) -> dict:
    """HVX vs DNNWeaver, both fully optimized (Fig-13 protocol)."""
    cfg = CONFIGS["+vec+pack+unroll"]
    ratios = {}
    for spec in library.PAPER_LAYERS:
        ch = layer_cycles(spec, "hvx", cfg)
        cd = layer_cycles(spec, "dnnweaver", cfg)
        ratios[spec.key] = ch / cd
        emit(f"fig13/{spec.key},0,hvx/dnnweaver={ch / cd:.1f}")
    gmean = math.exp(statistics.mean(
        math.log(max(r, 1e-9)) for r in ratios.values()))
    emit(f"fig13/geomean,0,ratio={gmean:.1f}")
    # the paper's headline: 490.9 / 71.8 = 6.8x mean advantage
    return ratios


__all__ = ["CONFIGS", "SEARCH", "VARIANTS", "fig11", "fig12", "fig12_search",
           "fig13", "fig14_variants", "layer_cycles"]
