"""Search-based scheduling (core/search.py): a driver subsystem — never
worse than the heuristic, functionally correct, deterministic, and
materialised exclusively through the pipeline.  The cost-bound-guided
``beam`` strategy is checked against ``exhaustive`` (the optimum oracle)
at an equal space cap, and warm-starting from the artifact store."""
import dataclasses

import numpy as np
import pytest

import repro
from repro.core import interp, library, targets
from repro.core.search import (STRATEGIES, SearchOptions, SearchResult,
                               search_schedule)
from repro.core.scheduler import schedule_space
from repro.core.store import ArtifactStore


@pytest.mark.parametrize("target", ["hvx", "dnnweaver"])
def test_search_never_worse_and_correct(target, rng):
    acg = targets.get_target(target)
    cdlt = library.gemm(24, 32, 16, in_dtype="u8")
    res = search_schedule(cdlt, acg, generations=4, population=10)
    assert res.best_cycles <= res.heuristic_cycles
    # the search's heuristic baseline is exactly the driver's schedule
    assert res.heuristic_cycles == repro.compile(cdlt, target).cycles()
    assert res.evaluated > 5
    ins = {"A": rng.integers(0, 5, (24, 16)).astype(np.uint8),
           "B": rng.integers(0, 5, (16, 32)).astype(np.uint8)}
    got = interp.run(res.best, acg, ins)
    np.testing.assert_array_equal(got["C"], cdlt.oracle(ins)["C"])


def test_search_improves_some_layer():
    """Across a few Table-2 layers the search beats the greedy heuristic on
    at least one (the heuristic's tile pick is cost-model-suboptimal
    somewhere — that gap is exactly what §4 says search should close)."""
    acg = targets.get_target("hvx")
    gains = []
    for spec in library.PAPER_LAYERS[6:10]:  # DLRM FC stack (fast)
        res = search_schedule(spec.build(), acg, generations=5,
                              population=12)
        gains.append(res.gain)
    assert max(gains) > 1.0
    assert all(g >= 1.0 - 1e-9 for g in gains)


def test_search_deterministic_trace():
    """Same inputs -> identical trace, winner and evaluation count (no
    strategy draws a random number)."""
    acg = targets.get_target("hvx")

    def run():
        return search_schedule(library.gemm(24, 32, 16, in_dtype="u8"), acg,
                               generations=4, population=10)

    a, b = run(), run()
    assert a.trace == b.trace
    assert a.point == b.point
    assert a.evaluated == b.evaluated
    assert a.best_cycles == b.best_cycles


def test_strategy_registry_complete_and_never_worse():
    assert set(STRATEGIES) == {"beam", "exhaustive"}
    assert SearchOptions().strategy == "beam"
    acg = targets.get_target("hvx")
    results = {}
    for strategy in ("beam", "exhaustive"):
        res = search_schedule(library.gemm(8, 16, 12, in_dtype="u8"), acg,
                              strategy=strategy, generations=2,
                              population=6)
        assert res.best_cycles <= res.heuristic_cycles
        assert res.strategy == strategy
        results[strategy] = res
    # exhaustive visits the whole space: nothing beats its optimum
    assert all(results["exhaustive"].best_cycles <= r.best_cycles + 1e-9
               for r in results.values())
    with pytest.raises(KeyError, match="beam.*exhaustive"):
        search_schedule(library.gemm(4, 8, 4, in_dtype="u8"), acg,
                        strategy="simulated-annealing")


def test_search_space_is_pipeline_fed():
    """schedule_space runs the whole pre-tiling pipeline prefix (honouring
    target hooks, including ones spliced after map_compute), so search
    enumerates against exactly what candidate materialisation sees."""
    acg = targets.get_target("hvx")
    seen = []
    acg.extra_passes.append(
        ("after:place", "probe-spy", lambda ctx: seen.append("early")))
    acg.extra_passes.append(
        ("after:map_compute", "late-spy", lambda ctx: seen.append("late")))
    try:
        space = schedule_space(library.gemm(8, 16, 12, in_dtype="u8"), acg)
    finally:
        acg.extra_passes.clear()
    assert seen == ["early", "late"]
    assert space.tilings and all(space.valid(t) for t in space.tilings[:20])


# ---------------------------------------------------------------------------
# determinism regression — every strategy, byte-identical
# ---------------------------------------------------------------------------


@pytest.mark.search
@pytest.mark.parametrize("strategy", sorted(STRATEGIES))
def test_every_strategy_trace_byte_identical_across_runs(strategy):
    """Same inputs => byte-identical ``SearchResult.trace`` (repr compare),
    same winner, same evaluation count — for every strategy.  This is the
    invariant that makes store entries reproducible across processes and
    sweep backends."""
    acg = targets.get_target("dnnweaver")

    def run():
        return search_schedule(library.gemm(24, 32, 16, in_dtype="u8"), acg,
                               strategy=strategy, generations=3,
                               population=8, max_candidates=256)

    a, b = run(), run()
    assert repr(a.trace).encode() == repr(b.trace).encode()
    assert a.point == b.point
    assert a.evaluated == b.evaluated
    assert a.best_cycles == b.best_cycles


# ---------------------------------------------------------------------------
# SearchResult.gain degenerate edge
# ---------------------------------------------------------------------------


def test_gain_returns_zero_at_the_zero_cycle_optimum_edge():
    """best == baseline == 0 (the seed point already hits the space
    optimum of a degenerate zero-cost schedule) must report 0.0, not
    divide by zero (or the old near-zero-division blow-up)."""
    def res(best, heur):
        return SearchResult(best=None, best_cycles=best,
                            heuristic_cycles=heur, evaluated=1, trace=[])

    assert res(0.0, 0.0).gain == 0.0
    assert res(0.0, 10.0).gain == float("inf")  # genuinely unbounded
    assert res(50.0, 100.0).gain == pytest.approx(2.0)


# ---------------------------------------------------------------------------
# warm-starting from the artifact store
# ---------------------------------------------------------------------------


@pytest.mark.search
def test_warm_start_seeds_from_store_and_never_hurts(tmp_path):
    """A store populated by a previous search seeds a later search of the
    same-shaped layer: seeds are injected (``seeded > 0``), the result is
    at least as good as cold, and a cold store yields zero seeds."""
    repro.clear_cache()
    store = ArtifactStore(str(tmp_path / "store"))
    pre = SearchOptions(strategy="beam", generations=3, population=8,
                        max_candidates=256)
    repro.compile("DLRM-FC2", "hvx",
                  repro.CompileOptions(search=pre, store=store))

    warm = SearchOptions(strategy="beam", generations=3, population=8,
                         max_candidates=256, warm_start=True)
    cold = dataclasses.replace(warm, warm_start=False)
    a_w = repro.compile("DLRM-FC2", "hvx",
                        repro.CompileOptions(search=warm, store=store))
    a_c = repro.compile("DLRM-FC2", "hvx",
                        repro.CompileOptions(search=cold, store=store))
    assert a_w.search.seeded > 0
    assert a_c.search.seeded == 0
    assert a_w.search.best_cycles <= a_c.search.best_cycles + 1e-9
    assert a_w.key != a_c.key       # warm_start is part of the identity

    # an empty store warm-starts to nothing (and must not fail)
    repro.clear_cache()
    empty = ArtifactStore(str(tmp_path / "empty"))
    a_e = repro.compile("DLRM-FC2", "hvx",
                        repro.CompileOptions(search=warm, store=empty))
    assert a_e.search.seeded == 0


@pytest.mark.search
def test_warm_start_entry_roundtrips_seeded_and_sig(tmp_path):
    """The store entry persists ``seeded``/``space_sig``; a fresh-process
    restore reports them without re-searching."""
    repro.clear_cache()
    store = ArtifactStore(str(tmp_path / "store"))
    sopts = SearchOptions(strategy="beam", generations=2, population=6,
                          max_candidates=128)
    art = repro.compile("DLRM-FC3", "hvx",
                        repro.CompileOptions(search=sopts, store=store))
    sig = art.search.space_sig
    assert sig
    repro.clear_cache()
    warm = repro.compile("DLRM-FC3", "hvx",
                         repro.CompileOptions(search=sopts, store=store))
    assert warm.ctx.executed == []          # zero-stage restore
    assert warm.search.space_sig == sig
    assert warm.search.seeded == art.search.seeded


# acceptance — beam at a 16-evaluation budget against the exhaustive oracle
# ---------------------------------------------------------------------------

FAST_LAYERS = ["DLRM-FC1", "DLRM-FC2", "DLRM-FC3"]


def _beam_and_oracle(cdlt, acg):
    """beam under a 16-evaluation budget, and exhaustive over the same
    512-tiling cap (every tiling at every unroll factor)."""
    rb = search_schedule(cdlt, acg, strategy="beam", generations=2,
                         population=8, max_candidates=512)
    rx = search_schedule(cdlt, acg, strategy="exhaustive",
                         max_candidates=512)
    return rb, rx


@pytest.mark.search
@pytest.mark.parametrize("target", ["hvx", "dnnweaver"])
def test_beam_budget_matched_on_dlrm_subset(target):
    """The CI-sized acceptance: on the DLRM subset, beam's 16 evaluations
    find cycles <= the optimum of exhaustive's whole capped space."""
    acg = targets.get_target(target)
    for key in FAST_LAYERS:
        rb, rx = _beam_and_oracle(library.paper_layer(key), acg)
        assert rb.evaluated <= 16           # the budget
        assert rx.evaluated > rb.evaluated  # the oracle saw more points
        assert rb.best_cycles <= rx.best_cycles + 1e-9, (key, target)


@pytest.mark.slow
@pytest.mark.search
@pytest.mark.parametrize("target", ["hvx", "dnnweaver"])
def test_beam_matches_or_beats_exhaustive_every_paper_layer(target):
    """Acceptance: on every Table-2 layer x both eval targets, beam's 16
    evaluations match or beat exhaustive over the same tiling cap (beam
    builds its candidates from prefixes, so it can reach points beyond
    the cap)."""
    acg = targets.get_target(target)
    for spec in library.PAPER_LAYERS:
        rb, rx = _beam_and_oracle(spec.build(), acg)
        assert rb.evaluated <= 16
        assert rb.best_cycles <= rx.best_cycles + 1e-9, (
            spec.key, target, rb.best_cycles, rx.best_cycles)


def test_driver_search_option_every_paper_layer_both_targets():
    """Acceptance: CompileOptions(search=...) returns an artifact at least
    as good as the heuristic for every paper layer on both targets, with
    the search trace attached, under the same content-addressed scheme."""
    sopts = repro.SearchOptions(strategy="beam", generations=1,
                                population=4, max_candidates=128)
    for target in ("hvx", "dnnweaver"):
        for spec in library.PAPER_LAYERS:
            heur = repro.compile(spec, target)
            art = repro.compile(spec, target,
                                repro.CompileOptions(search=sopts))
            assert art.cycles() <= heur.cycles() + 1e-9, (spec.key, target)
            assert art.search is not None and art.search.trace
            assert art.key != heur.key      # searched compile is its own key
