"""Disk-backed ArtifactStore (core/store.py): warm restores run zero
pipeline stages and rebuild lazily; corrupt entries fall back to a clean
recompile; the size bound evicts LRU; ``clear_cache(disk=True)`` empties
it; and a *fresh process* replays a warm sweep as store hits only."""
import json
import os
import subprocess
import sys

import pytest

import repro
from repro.core import library
from repro.core.store import ArtifactStore

pytestmark = pytest.mark.store

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def store(tmp_path):
    repro.clear_cache()
    yield ArtifactStore(str(tmp_path / "store"))
    repro.clear_cache()


def _gemm(k=16):
    return library.gemm(24, 32, k, in_dtype="u8")


# ---------------------------------------------------------------------------
# warm restore semantics
# ---------------------------------------------------------------------------


def test_warm_restore_runs_zero_stages_and_replays_identically(store):
    opts = repro.CompileOptions(store=store)
    a1 = repro.compile(_gemm(), "hvx", opts)
    cycles = a1.cycles()
    program = [m.encode() for m in a1.program.mnemonics]
    notes = list(a1.schedule_notes)

    repro.clear_cache()  # simulate a fresh process (disk survives)
    a2 = repro.compile(_gemm(), "hvx", opts)
    assert a2.ctx.executed == []            # no pass ran on the warm hit
    assert a2.cycles() == cycles            # analytics from the stored report
    assert a2.ctx.executed == []            # ...still without any pass
    assert a2.schedule_notes == notes
    assert repro.cache_stats()["store_hits"] == 1
    # lazy rebuild: touching .program replays the stored schedule decisions
    assert [m.encode() for m in a2.program.mnemonics] == program
    assert "tile" in a2.ctx.executed


def test_searched_artifact_roundtrips_with_trace(store):
    opts = repro.CompileOptions(
        store=store, search=repro.SearchOptions(generations=3, population=8))
    a1 = repro.compile(_gemm(), "hvx", opts)
    assert a1.search is not None and a1.search.trace
    repro.clear_cache()
    a2 = repro.compile(_gemm(), "hvx", opts)
    assert a2.ctx.executed == []
    assert a2.cycles() == a1.cycles()
    assert a2.search is not None
    assert [tuple(t) for t in a2.search.trace] == \
        [tuple(t) for t in a1.search.trace]
    assert a2.search.point == a1.search.point
    # replay (no re-search) reproduces the searched program exactly
    assert [m.encode() for m in a2.program.mnemonics] == \
        [m.encode() for m in a1.program.mnemonics]


# ---------------------------------------------------------------------------
# robustness
# ---------------------------------------------------------------------------


def test_corrupt_entry_falls_back_to_clean_recompile(store):
    opts = repro.CompileOptions(store=store)
    a1 = repro.compile(_gemm(), "hvx", opts)
    path = os.path.join(store.root, a1.key + ".json")
    with open(path, "w") as f:
        f.write('{"format": 1, "key": "tru')  # truncated write
    repro.clear_cache()
    a2 = repro.compile(_gemm(), "hvx", opts)
    assert a2.cycles() == a1.cycles()
    assert a2.ctx.executed                 # really recompiled
    assert store.stats["corrupt"] == 1
    assert os.path.exists(path)            # fresh entry rewritten after


def test_stale_compiler_signature_forces_recompile(store):
    """An entry written by a different compiler version reads as a miss
    (and is deleted): persisted keys cover inputs, not the compiler."""
    opts = repro.CompileOptions(store=store)
    a1 = repro.compile(_gemm(), "hvx", opts)
    path = os.path.join(store.root, a1.key + ".json")
    entry = json.load(open(path))
    entry["compiler"] = "0badc0de0badc0de"
    json.dump(entry, open(path, "w"))
    repro.clear_cache()
    a2 = repro.compile(_gemm(), "hvx", opts)
    assert a2.ctx.executed and a2.cycles() == a1.cycles()
    assert store.stats["stale"] == 1
    assert json.load(open(path))["compiler"] != "0badc0de0badc0de"


def test_semantically_broken_entry_falls_back(store):
    opts = repro.CompileOptions(store=store)
    a1 = repro.compile(_gemm(), "hvx", opts)
    path = os.path.join(store.root, a1.key + ".json")
    entry = json.load(open(path))
    entry["reports"] = {"1": {"bogus_field": 1}}  # schema drift
    json.dump(entry, open(path, "w"))
    repro.clear_cache()
    a2 = repro.compile(_gemm(), "hvx", opts)
    assert a2.ctx.executed and a2.cycles() == a1.cycles()
    assert store.stats["corrupt"] == 1


# ---------------------------------------------------------------------------
# size bound / LRU
# ---------------------------------------------------------------------------


def test_size_bound_evicts_least_recently_used(tmp_path):
    repro.clear_cache()
    st = ArtifactStore(str(tmp_path), max_bytes=1)  # everything over budget
    opts = repro.CompileOptions(store=st)
    arts = [repro.compile(_gemm(k), "hvx", opts) for k in (8, 16, 24)]
    # bound of 1 byte: every put evicts all older entries; newest survives
    assert st.keys() == [arts[-1].key]
    assert st.stats["evictions"] == 2
    repro.clear_cache()


def test_load_bumps_lru_recency(tmp_path):
    repro.clear_cache()
    st = ArtifactStore(str(tmp_path), max_bytes=10 ** 9)
    opts = repro.CompileOptions(store=st)
    a_old = repro.compile(_gemm(8), "hvx", opts)
    a_new = repro.compile(_gemm(16), "hvx", opts)
    # age both entries, then touch the *older* one via a warm load
    for art, age in ((a_old, 2000), (a_new, 1000)):
        p = os.path.join(st.root, art.key + ".json")
        past = os.stat(p).st_mtime - age
        os.utime(p, (past, past))
    assert st.load(a_old.key) is not None   # bumps a_old to most recent
    # shrink the bound so exactly one entry must go: the LRU is now a_new
    st.max_bytes = st.size_bytes() - 1
    st._evict()
    keys = set(st.keys())
    assert a_old.key in keys
    assert a_new.key not in keys
    assert st.stats["evictions"] == 1
    repro.clear_cache()


# ---------------------------------------------------------------------------
# ACG identity: spec-fingerprint keys (no aliasing by name)
# ---------------------------------------------------------------------------


def test_same_name_variants_never_alias_in_the_store(store):
    """Regression: two derived variants sharing a base *name* must key by
    spec content, so neither can serve the other's warm entry."""
    from repro.core import targets
    from repro.core.acg import ACG

    opts = repro.CompileOptions(store=store)
    base = ACG.from_spec(targets.DNNWEAVER_SPEC)
    # same registered name 'dnnweaver', different covenant
    variant = ACG.from_spec(targets.DNNWEAVER_SPEC.derive(
        pe="32x32", name="dnnweaver"))
    assert base.name == variant.name == "dnnweaver"

    a = repro.compile("DLRM-FC1", base, opts)
    b = repro.compile("DLRM-FC1", variant, opts)
    assert a.key != b.key
    assert a.cycles() != b.cycles()
    assert len(store) == 2

    repro.clear_cache()  # fresh process; disk survives
    warm_b = repro.compile("DLRM-FC1", variant, opts)
    warm_a = repro.compile("DLRM-FC1", base, opts)
    assert warm_a.ctx.executed == [] and warm_b.ctx.executed == []
    assert warm_a.cycles() == a.cycles()
    assert warm_b.cycles() == b.cycles()
    assert repro.cache_stats()["store_hits"] == 2


def test_mutated_acg_cannot_ride_a_stale_key(store):
    """Mutating a resolved ACG — including mnemonic *field layouts*, which
    the old describe()-based hash ignored — re-fingerprints it, so the next
    compile misses instead of collecting a stale warm hit."""
    from repro.core import targets
    from repro.core.acg import MnemonicDef, ifield

    opts = repro.CompileOptions(store=store)
    acg = targets.get_target("hvx")
    a1 = repro.compile(_gemm(), acg, opts)
    old = acg.mnemonics["LOOPI"]
    acg.mnemonics["LOOPI"] = MnemonicDef(
        "LOOPI", old.opcode, (ifield("LEVEL", 16), ifield("TRIP", 32)))
    a2 = repro.compile(_gemm(), acg, opts)
    assert a2.key != a1.key
    assert a2 is not a1


def test_mutated_name_resolved_acg_is_rebuilt_pristine(store):
    """The string-name resolution path, like the spec path, rebuilds a
    pristine graph when the shared memoized instance has been mutated —
    'hvx' always compiles the architecture registered under that name."""
    from repro.core import targets
    from repro.core.acg import MnemonicDef, ifield

    opts = repro.CompileOptions(store=store)
    a1 = repro.compile(_gemm(), "hvx", opts)
    shared = a1.acg
    old = shared.mnemonics["LOOPI"]
    shared.mnemonics["LOOPI"] = MnemonicDef(
        "LOOPI", old.opcode, (ifield("LEVEL", 16), ifield("TRIP", 32)))
    a2 = repro.compile(_gemm(8), "hvx", opts)
    assert a2.acg is not shared
    assert a2.acg.to_spec().fingerprint() == targets.HVX_SPEC.fingerprint()


def test_mutated_spec_resolved_acg_is_rebuilt_pristine(store):
    """The ACGSpec resolution path memoizes the built graph, but a spec is
    a *pristine* description: if the shared instance drifts (mutation),
    the next resolve rebuilds from the spec instead of compiling the
    mutated graph under the spec's key."""
    from repro.core import targets
    from repro.core.acg import MnemonicDef, ifield

    opts = repro.CompileOptions(store=store)
    a1 = repro.compile(_gemm(), targets.HVX_SPEC, opts)
    shared = a1.acg  # the memoized instance behind the spec target
    old = shared.mnemonics["LOOPI"]
    shared.mnemonics["LOOPI"] = MnemonicDef(
        "LOOPI", old.opcode, (ifield("LEVEL", 16), ifield("TRIP", 32)))
    assert shared.to_spec().fingerprint() != targets.HVX_SPEC.fingerprint()
    # resolution detects the drift and rebuilds a faithful graph
    from repro.core.driver import _resolve_target
    acg2, fp2 = _resolve_target(targets.HVX_SPEC)
    assert acg2 is not shared
    assert fp2 == targets.HVX_SPEC.fingerprint()
    assert acg2.to_spec().fingerprint() == fp2
    # the key identity is therefore the pristine spec's, before and after:
    # a fresh process (in-process cache cleared) warm-restores a1's entry
    repro.clear_cache()
    a2 = repro.compile(_gemm(), targets.HVX_SPEC, opts)
    assert a2.key == a1.key and a2.ctx.executed == []
    assert a2.acg is not shared


# ---------------------------------------------------------------------------
# clearing
# ---------------------------------------------------------------------------


def test_clear_cache_disk_empties_store(store):
    opts = repro.CompileOptions(store=store)
    repro.compile(_gemm(), "hvx", opts)
    repro.compile(_gemm(8), "hvx", opts)
    assert len(store) == 2
    repro.clear_cache(disk=True, store=store)
    assert len(store) == 0
    assert repro.cache_stats()["size"] == 0


def test_in_process_hit_backfills_late_configured_store(tmp_path):
    """A key compiled before the store existed is persisted the next time
    it is requested with a store configured — warm replay still works."""
    repro.clear_cache()
    plain = repro.compile(_gemm(), "hvx")             # no store yet
    st = ArtifactStore(str(tmp_path))
    hit = repro.compile(_gemm(), "hvx", repro.CompileOptions(store=st))
    assert hit is plain and plain.key in st           # backfilled on the hit
    repro.clear_cache()
    warm = repro.compile(_gemm(), "hvx", repro.CompileOptions(store=st))
    assert warm.ctx.executed == [] and warm.cycles() == plain.cycles()
    repro.clear_cache()


def test_unusable_env_store_disables_disk_tier(tmp_path, monkeypatch):
    """A bad REPRO_CACHE_DIR must not fail compiles — it warns once and
    runs memory-only."""
    target = tmp_path / "blocker"
    target.write_text("not a directory")
    monkeypatch.setenv("REPRO_CACHE_DIR", str(target / "store"))
    repro.clear_cache()
    with pytest.warns(UserWarning, match="REPRO_CACHE_DIR"):
        art = repro.compile(_gemm(), "hvx")
    assert art.cycles() > 0
    repro.compile(_gemm(8), "hvx")  # no second warning, still compiles
    repro.clear_cache()


def test_env_var_names_default_store(tmp_path, monkeypatch):
    repro.clear_cache()
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path / "envstore"))
    art = repro.compile(library.gemm(12, 8, 4, in_dtype="u8"), "hvx")
    assert os.path.exists(
        os.path.join(str(tmp_path / "envstore"), art.key + ".json"))
    repro.clear_cache()


# ---------------------------------------------------------------------------
# the multi-process contract
# ---------------------------------------------------------------------------

_SWEEP = r"""
import json, sys
import repro
from repro.core import library

items = [library.gemm(24, 32, 16, in_dtype="u8"),
         library.gemm(8, 16, 12, in_dtype="u8"),
         "DLRM-FC4"]
arts = repro.compile_many(items, target="hvx")
arts += [repro.compile(
    library.gemm(24, 32, 16, in_dtype="u8"), "dnnweaver",
    repro.CompileOptions(search=repro.SearchOptions(generations=2,
                                                    population=6)))]
print(json.dumps({
    "cycles": [a.cycles() for a in arts],
    "stages_run": sum(len(a.ctx.executed) for a in arts),
    "stats": repro.cache_stats(),
}))
"""


# Two processes hammer one store whose size bound forces an eviction scan
# on every put.  The regression this guards: concurrent LRU evictions used
# to delete *each other's* just-written entries (both processes scan, both
# see the other's fresh file as LRU-eligible).  The hardened store
# serialises eviction behind a FileLock and never evicts a foreign entry
# younger than FRESH_GRACE, so every process must still see its own entry
# immediately after each put.
_EVICT_STRESS = r"""
import hashlib, os, sys
from repro.core.store import ArtifactStore

root, tag = sys.argv[1], sys.argv[2]
st = ArtifactStore(root, max_bytes=2000)  # a handful of entries
pad = "x" * 400
for i in range(30):
    key = hashlib.sha256(f"{tag}-{i}".encode()).hexdigest()
    st.put(key, {"reports": {}, "pack": True, "pad": pad})
    if not os.path.exists(os.path.join(root, key + ".json")):
        print(f"LOST fresh entry {tag}-{i}", file=sys.stderr)
        sys.exit(1)
print(f"{tag} ok evictions={st.stats['evictions']}")
"""


def test_concurrent_evicting_writers_never_lose_fresh_entries(tmp_path):
    env = dict(os.environ, PYTHONPATH="src")
    procs = [subprocess.Popen(
        [sys.executable, "-c", _EVICT_STRESS, str(tmp_path / "shared"), tag],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env, cwd=ROOT) for tag in ("alpha", "beta")]
    for p in procs:
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0, err
        assert "ok" in out


def test_own_entries_still_evict_under_size_pressure(tmp_path):
    """The foreign-fresh grace window must not break the single-process
    size bound: a process's own fresh entries remain evictable."""
    st = ArtifactStore(str(tmp_path), max_bytes=1)
    st.put("a" * 64, {"reports": {}, "pack": True})
    st.put("b" * 64, {"reports": {}, "pack": True})
    assert st.keys() == ["b" * 64]
    assert st.stats["evictions"] == 1


# ---------------------------------------------------------------------------
# locks, journal, gc
# ---------------------------------------------------------------------------


def test_filelock_excludes_and_breaks_stale(tmp_path):
    from repro.core.store import FileLock
    path = str(tmp_path / "x.lock")
    a = FileLock(path)
    assert a.acquire()
    assert not FileLock(path).acquire(timeout=0.05)  # held: excluded
    a.release()
    b = FileLock(path, stale_timeout=60)
    assert b.acquire(timeout=0.05)                   # released: free again
    # simulate a dead holder: backdate the lock past the stale timeout
    past = os.stat(path).st_mtime - 3600
    os.utime(path, (past, past))
    c = FileLock(path, stale_timeout=60)
    assert c.acquire(timeout=1.0)                    # stale lock broken
    c.release()


def test_journal_is_monotonic_and_readable(tmp_path):
    st = ArtifactStore(str(tmp_path))
    j = st.journal("sweepid")
    seqs = [j.append({"event": "compiled", "key": f"{i:064x}"})
            for i in range(5)]
    assert seqs == [1, 2, 3, 4, 5]
    recs = j.read()
    assert [r["seq"] for r in recs] == seqs
    assert st.journal("sweepid").append({"event": "dedup"}) == 6
    assert j.compile_counts() == {f"{i:064x}": 1 for i in range(5)}


def test_gc_by_age_size_and_stale_claims(tmp_path):
    st = ArtifactStore(str(tmp_path), max_bytes=10 ** 9)
    young, old = "d" * 64, "e" * 64
    for key in (young, old):
        st.put(key, {"reports": {}, "pack": True})
    past = os.stat(st._path(old)).st_mtime - 7200
    os.utime(st._path(old), (past, past))
    st.journal("s2").append({"event": "compiled", "key": old})
    sweep_dir = st.sweep_dir("s2")
    os.utime(sweep_dir, (past, past))
    out = st.gc(max_age=3600)
    assert out["aged"] == 1 and out["sweeps_reaped"] == 1
    assert st.keys() == [young]
    assert not os.path.exists(sweep_dir)
    # size-driven gc: shrink the budget so the survivor must go too
    out = st.gc(max_bytes=0)
    assert out["evicted"] >= 0  # keep-newest still protects one entry
    st.put("a1" * 32, {"reports": {}, "pack": True})
    st.put("b2" * 32, {"reports": {}, "pack": True})
    st.gc(max_bytes=1)
    assert len(st) >= 1  # bounded, but never empties the newest entry


def test_peek_reads_without_stats_or_recency(store):
    opts = repro.CompileOptions(store=store)
    art = repro.compile(_gemm(), "hvx", opts)
    hits_before = dict(store.stats)
    entry = store.peek(art.key)
    assert entry is not None and entry["key"] == art.key
    assert store.stats == hits_before          # no stats movement
    assert store.peek("0" * 64) is None        # miss is just None
    from repro.core.store import entry_cycles
    assert entry_cycles(entry) == art.cycles()


def test_second_process_warm_sweep_is_store_hits_only(tmp_path):
    """A fresh process compiling a warm sweep executes ZERO scheduling or
    search passes — every artifact restores from the disk store."""
    env = dict(os.environ, PYTHONPATH="src",
               REPRO_CACHE_DIR=str(tmp_path / "store"))

    def run():
        r = subprocess.run([sys.executable, "-c", _SWEEP],
                           capture_output=True, text=True, env=env, cwd=ROOT,
                           timeout=600)
        assert r.returncode == 0, r.stderr[-2000:]
        return json.loads(r.stdout.strip().splitlines()[-1])

    cold = run()
    warm = run()
    assert cold["stats"]["store_misses"] == 4
    assert cold["stages_run"] > 0
    assert warm["stats"]["store_hits"] == 4
    assert warm["stats"]["store_misses"] == 0
    assert warm["stages_run"] == 0          # no scheduling/search pass ran
    assert warm["cycles"] == cold["cycles"]


# ---------------------------------------------------------------------------
# warm-start index
# ---------------------------------------------------------------------------


def test_warm_start_index_built_from_journal_and_entries(store):
    """The index joins sweep-journal events with stored entries: every
    journaled compile with a tiling becomes a candidate point, and
    ``seeds`` returns only points valid for the requesting space."""
    from repro.core.scheduler import schedule_space
    from repro.core.store import WarmStartIndex

    report = repro.sweep(["DLRM-FC2", "DLRM-FC3"], ["hvx"], store=store)
    assert report.counts()["ok"] == 2
    idx = WarmStartIndex.from_store(store)
    assert len(idx) == 2

    acg = repro.targets.get("hvx")
    space = schedule_space(library.paper_layer("DLRM-FC2"), acg)
    seeds = idx.seeds(space, (1, 2, 4, 8), limit=4)
    assert seeds
    for tiling, unroll in seeds:
        assert set(tiling) == set(space.divisors)
        assert space.valid(tiling)
        assert unroll in (1, 2, 4, 8)


def test_warm_start_index_prefers_exact_space_signature(store):
    """Searched entries record their space signature; seeds from the SAME
    shape rank before merely-compatible foreign points."""
    from repro.core.scheduler import schedule_space
    from repro.core.store import WarmStartIndex
    from repro.core.search import SearchOptions

    sopts = SearchOptions(strategy="beam", generations=2, population=6,
                          max_candidates=128)
    art = repro.compile("DLRM-FC4", "hvx",
                        repro.CompileOptions(search=sopts, store=store))
    sig = art.search.space_sig
    idx = WarmStartIndex.from_store(store)
    acg = repro.targets.get("hvx")
    space = schedule_space(library.paper_layer("DLRM-FC4"), acg)
    assert space.signature() == sig
    seeds = idx.seeds(space, (1, 2, 4, 8), limit=1)
    assert seeds and seeds[0][0] == art.search.point["tiling"]


def test_warm_start_index_rejects_foreign_shapes(store):
    """Points whose loop-var set does not match the requesting space are
    never returned — a conv schedule cannot seed a GEMM."""
    from repro.core.scheduler import schedule_space
    from repro.core.store import WarmStartIndex

    repro.compile(library.elementwise("ADD", 64, "i32"), "hvx",
                  repro.CompileOptions(store=store))
    idx = WarmStartIndex.from_store(store)
    assert len(idx) >= 1
    acg = repro.targets.get("hvx")
    space = schedule_space(_gemm(), acg)
    assert idx.seeds(space, (1, 2, 4, 8), limit=4) == []
