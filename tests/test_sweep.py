"""Multi-process sweep coordinator (core/sweep.py): deterministic plan
expansion and partitioning, dedup against the shared artifact store,
report merge identity vs sequential ``compile_many``, and the
exactly-once journal contract across worker processes."""
import json
import os
import subprocess
import sys

import pytest

import repro
from repro.core import sweep as sweep_mod
from repro.core.store import ArtifactStore
from repro.core.sweep import (SweepReport, UnitResult, expand_plan,
                              partition, plan_id)

pytestmark = pytest.mark.sweep

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LAYERS = ["DLRM-FC2", "DLRM-FC3", "DLRM-FC4"]
VARIANTS = ["dnnweaver@pe=32x32", "dnnweaver@pe=16x16"]


@pytest.fixture
def store(tmp_path):
    repro.clear_cache()
    yield ArtifactStore(str(tmp_path / "store"))
    repro.clear_cache()


# ---------------------------------------------------------------------------
# plan expansion + partition determinism
# ---------------------------------------------------------------------------


def test_expand_plan_is_deterministic_and_order_independent():
    a = expand_plan(LAYERS, VARIANTS)
    b = expand_plan(list(reversed(LAYERS)), list(reversed(VARIANTS)))
    assert [u.key for u in a] == [u.key for u in b]
    assert len(a) == len(LAYERS) * len(VARIANTS)
    assert [u.key for u in a] == sorted(u.key for u in a)
    # duplicates collapse onto the same content-addressed unit
    c = expand_plan(LAYERS + LAYERS, VARIANTS)
    assert [u.key for u in c] == [u.key for u in a]
    assert plan_id(a) == plan_id(b) == plan_id(c)


def test_partition_is_deterministic_and_complete():
    units = expand_plan(LAYERS, VARIANTS)
    shards = partition(units, 2)
    again = partition(list(reversed(units)), 2)  # input order irrelevant
    assert [[u.key for u in s] for s in shards] == \
        [[u.key for u in s] for s in again]
    flat = [u.key for s in shards for u in s]
    assert sorted(flat) == [u.key for u in units]  # complete + disjoint
    assert abs(len(shards[0]) - len(shards[1])) <= 1  # balanced
    # more workers than units: spare shards are just empty
    wide = partition(units, len(units) + 3)
    assert sum(len(s) for s in wide) == len(units)


def test_search_axis_creates_distinct_units():
    searches = [None, repro.SearchOptions(generations=2, population=4)]
    units = expand_plan(["DLRM-FC4"], ["hvx"], searches=searches)
    assert len(units) == 2
    assert {u.opt for u in units} == {"heuristic", "search:beam@g2p4"}


def test_workunit_json_roundtrip():
    searches = [repro.SearchOptions(generations=2, population=4)]
    for unit in expand_plan(LAYERS[:1], VARIANTS, searches=searches):
        back = sweep_mod.WorkUnit.from_json(
            json.loads(json.dumps(unit.to_json())))
        assert back == unit


# ---------------------------------------------------------------------------
# serial backend: merge identity vs sequential compile_many
# ---------------------------------------------------------------------------


def test_serial_sweep_matches_sequential_compile_many(store):
    pairs = [(layer, v) for layer in LAYERS for v in VARIANTS]
    arts = repro.compile_many(pairs)
    expected = {a.key: a.cycles() for a in arts}
    report = repro.sweep(LAYERS, VARIANTS, store=store)
    assert report.cycles_by_key() == expected
    assert report.counts()["ok"] == len(pairs)
    assert len(store) == len(pairs)  # every unit persisted


def test_report_merge_is_identity_and_idempotent():
    full = SweepReport(sweep_id="s", results=[
        UnitResult(key=f"{i:02x}", layer=f"L{i % 3}", target="t",
                   cycles=float(i), source="compiled")
        for i in range(6)])
    parts = [SweepReport(sweep_id="s", results=full.results[:2]),
             SweepReport(sweep_id="s", results=full.results[2:]),
             SweepReport(sweep_id="s", results=full.results[1:4])]
    merged = SweepReport.merge(parts)
    assert merged.cycles_by_key() == full.cycles_by_key()
    again = SweepReport.merge([merged, merged])
    assert again.cycles_by_key() == full.cycles_by_key()
    # an ok record beats a skipped one for the same key, whatever the order
    skip = UnitResult(key="00", layer="L0", target="t", status="skipped")
    m = SweepReport.merge([SweepReport(sweep_id="s", results=[skip]), full])
    assert m.cycles_by_key()["00"] == 0.0


def test_best_by_layer_picks_lowest_cycles():
    rep = SweepReport(sweep_id="s", results=[
        UnitResult(key="aa", layer="L", target="big", cycles=100.0),
        UnitResult(key="ab", layer="L", target="small", cycles=40.0),
        UnitResult(key="ac", layer="L", target="broken", status="failed"),
    ])
    best = rep.best_by_layer()
    assert best["L"].target == "small"
    assert "small" in rep.best_table()


# ---------------------------------------------------------------------------
# dedup against the store
# ---------------------------------------------------------------------------


def test_dedup_skips_already_stored_units(store):
    warm_key = repro.compile(LAYERS[0], VARIANTS[0],
                             repro.CompileOptions(store=store)).key
    repro.clear_cache()
    report = repro.sweep(LAYERS, VARIANTS, store=store)
    by_key = {r.key: r for r in report.results}
    assert by_key[warm_key].source == "dedup"
    assert by_key[warm_key].stages_run == 0
    assert sum(1 for r in report.results if r.source == "compiled") == \
        len(report.results) - 1
    # the journal never saw a compile for the deduped unit
    counts = store.journal(report.sweep_id).compile_counts()
    assert warm_key not in counts
    assert set(counts.values()) == {1}


def test_warm_sweep_is_all_dedup_with_zero_stages(store):
    cold = repro.sweep(LAYERS, VARIANTS, store=store)
    assert cold.counts()["compiled"] == len(cold.results)
    repro.clear_cache()
    warm = repro.sweep(LAYERS, VARIANTS, store=store)
    assert warm.counts()["dedup"] == len(warm.results)
    assert warm.stages_run() == 0
    assert warm.cycles_by_key() == cold.cycles_by_key()


# ---------------------------------------------------------------------------
# process backend + compile_many(parallel=)
# ---------------------------------------------------------------------------


def test_process_backend_compiles_each_unit_exactly_once(store):
    report = repro.sweep(LAYERS, VARIANTS, workers=2, store=store)
    c = report.counts()
    assert c["ok"] == len(LAYERS) * len(VARIANTS)
    assert c["compiled"] == c["ok"]
    assert {r.worker for r in report.results} == {"w0", "w1"}
    counts = store.journal(report.sweep_id).compile_counts()
    assert len(counts) == c["ok"] and set(counts.values()) == {1}
    # warm re-run: nothing dispatched, zero stages, same cycles
    warm = repro.sweep(LAYERS, VARIANTS, workers=2, store=store)
    assert warm.counts()["dedup"] == c["ok"]
    assert warm.stages_run() == 0
    assert warm.cycles_by_key() == report.cycles_by_key()


def test_compile_many_parallel_matches_sequential(store):
    pairs = [(layer, v) for layer in LAYERS for v in VARIANTS]
    opts = repro.CompileOptions(store=store)
    arts = repro.compile_many(pairs, options=opts, parallel=2)
    # workers prefilled the store; the ordered pass restored warm
    assert all(a.ctx.executed == [] for a in arts)
    assert repro.cache_stats()["store_hits"] == len(pairs)
    parallel_cycles = [a.cycles() for a in arts]
    repro.clear_cache()
    sequential = [a.cycles() for a in repro.compile_many(pairs)]
    assert parallel_cycles == sequential


def test_compile_many_parallel_without_store_warns_and_falls_back():
    repro.clear_cache()
    with pytest.warns(UserWarning, match="parallel"):
        arts = repro.compile_many(["DLRM-FC4"], parallel=2)
    assert arts[0].cycles() > 0
    repro.clear_cache()


# ---------------------------------------------------------------------------
# the CLI (python -m repro.sweep) — what the sweep-parallel CI job runs
# ---------------------------------------------------------------------------


def _run_cli(tmp_path, *extra):
    env = dict(os.environ, PYTHONPATH="src",
               REPRO_CACHE_DIR=str(tmp_path / "store"))
    return subprocess.run(
        [sys.executable, "-m", "repro.sweep",
         "--layers", ",".join(LAYERS), "--targets", ",".join(VARIANTS),
         "--workers", "2", *extra],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)


def test_cli_cold_then_warm_enforces_ci_contract(tmp_path):
    cold = _run_cli(tmp_path, "--assert-unique-compiles")
    assert cold.returncode == 0, cold.stdout + cold.stderr
    assert "compiled exactly once" in cold.stdout
    warm = _run_cli(tmp_path, "--assert-unique-compiles",
                    "--expect-store-hits")
    assert warm.returncode == 0, warm.stdout + warm.stderr
    assert "zero pipeline stages executed" in warm.stdout


def test_cli_expect_store_hits_fails_cold(tmp_path):
    r = _run_cli(tmp_path, "--expect-store-hits")
    assert r.returncode == 1
    assert "FAIL" in r.stderr


# ---------------------------------------------------------------------------
# search axis: option round trips + cross-backend determinism
# ---------------------------------------------------------------------------

SEARCHES = [
    repro.SearchOptions(strategy="beam", generations=2, population=6,
                        max_candidates=128),
    repro.SearchOptions(strategy="exhaustive", max_candidates=128),
]


def test_search_options_json_roundtrip_with_pr5_fields():
    from repro.core.sweep import options_from_json, options_to_json
    sopts = repro.SearchOptions(strategy="beam", beam_width=5,
                                warm_start=True)
    opts = repro.CompileOptions(search=sopts)
    rt = options_from_json(json.loads(json.dumps(options_to_json(opts))))
    assert rt.search == sopts
    assert rt.fingerprint() == opts.fingerprint()


@pytest.mark.search
def test_search_traces_byte_identical_across_fork_and_spawn(tmp_path):
    """Same plan, different worker start methods: the stored search
    digests (trace, winner, cycles) must be byte-identical — the
    determinism contract across sweep backends."""
    import multiprocessing as mp

    methods = [m for m in ("fork", "spawn")
               if m in mp.get_all_start_methods()]
    if len(methods) < 2:
        pytest.skip("platform offers a single mp start method")
    digests = {}
    for method in methods:
        repro.clear_cache()
        st = ArtifactStore(str(tmp_path / method))
        report = repro.sweep(["DLRM-FC4"], ["hvx"], store=st, workers=2,
                             searches=SEARCHES, backend="process",
                             mp_start=method)
        assert report.counts()["ok"] == 2, report.summary()
        entries = {}
        for r in report.ok:
            s = (st.peek(r.key) or {}).get("search")
            assert s is not None
            entries[r.key] = json.dumps(s, sort_keys=True)
        digests[method] = entries
    assert digests[methods[0]] == digests[methods[1]]
    repro.clear_cache()


def test_cli_rejects_malformed_search_spec(tmp_path):
    env = dict(os.environ, PYTHONPATH="src",
               REPRO_CACHE_DIR=str(tmp_path / "store"))
    for bad in ("bem", "generations=lots", "strategy=evolutionary",
                "seed=0"):
        r = subprocess.run(
            [sys.executable, "-m", "repro.sweep", "--layers", "DLRM-FC4",
             "--targets", "hvx", "--search", bad],
            capture_output=True, text=True, env=env, cwd=ROOT, timeout=600)
        assert r.returncode == 2, (bad, r.stdout, r.stderr)
        assert "error: --search" in r.stderr
        assert "Traceback" not in r.stderr
