"""Multi-process sweep coordinator over the shared artifact store.

The paper's compiler wins by evaluating *many* execution mappings per
layer per architecture; this module is that loop at fleet scale.  A
**sweep plan** is the cross product

    layers x target variants x (optional) search configs

expanded into **work units** whose identity is the driver's
content-addressed compile key — ``(codelet fingerprint, covenant-spec
fingerprint, options fingerprint, pipeline fingerprint)`` — exactly the
key the in-process cache and the disk ``ArtifactStore`` use.  That shared
identity is what makes the coordinator correct by construction:

* **dedup** — units whose key already sits in the store are reported
  straight from the stored entry (``store.peek``), never dispatched;
* **partition** — remaining units are sharded across N worker processes
  deterministically (key-sorted round robin: a function of the unit-key
  set and N only, independent of plan order);
* **merge** — every worker compiles *through the driver* with the store
  configured, so results land in the shared measurement database and the
  coordinator's ``SweepReport`` is just the union of unit records.

Two backends:

* ``serial`` — in-process, the reference semantics (``SweepReport`` merge
  identity vs a plain ``compile_many`` is a test invariant);
* ``process`` — the coordinator forks/spawns N workers
  (``multiprocessing``) over a static partition.

Every unit outcome is appended to the store's monotonic ``SweepJournal``;
CI asserts "each work unit compiled exactly once, warm re-runs recompile
nothing" as pure journal queries (``python -m repro.sweep
--assert-unique-compiles --expect-store-hits``).
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
from typing import Iterable, Sequence

from . import library as library_mod
from . import store as store_mod
from .codelet import Codelet
from .pipeline import CompileOptions
from .search import SearchOptions

# ---------------------------------------------------------------------------
# workload descriptors — the serialisable half of a work unit
# ---------------------------------------------------------------------------

# A workload is ("kind", payload) where payload is JSON-able for every
# kind except "local" (an in-memory Codelet/builder: serial backend only).
_BUILDERS = {
    "gemm": library_mod.gemm,
    "fc": library_mod.fc,
    "conv2d": library_mod.conv2d,
    "elementwise": library_mod.elementwise,
}


def workload_of(layer) -> tuple:
    """Normalise a sweep ``layers`` item into a workload descriptor.

    Accepts paper-layer keys, ``library.LayerSpec``, launch-layer GEMM
    records (anything with ``tokens``/``n``/``k``/``name``), explicit
    ``("gemm"|"fc"|"conv2d"|"elementwise", {kwargs})`` descriptors, and —
    for the serial backend only — raw Codelets or builder thunks."""
    if isinstance(layer, str):
        return ("paper", layer)
    if isinstance(layer, library_mod.LayerSpec):
        if any(s.key == layer.key for s in library_mod.PAPER_LAYERS):
            return ("paper", layer.key)
        return ("local", layer.build)
    if all(hasattr(layer, a) for a in ("tokens", "n", "k", "name")):
        # launch.layers.LayerGemm (duck-typed: launch depends on jax,
        # the sweep core must not)
        return ("gemm", {"m": int(layer.tokens), "n": int(layer.n),
                         "k": int(layer.k), "name": str(layer.name)})
    if isinstance(layer, tuple) and len(layer) == 2 \
            and layer[0] in _BUILDERS and isinstance(layer[1], dict):
        return (layer[0], dict(layer[1]))
    if isinstance(layer, Codelet) or callable(layer):
        return ("local", layer)
    raise TypeError(f"cannot express {layer!r} as a sweep workload")


def build_workload(workload: tuple) -> Codelet:
    kind, payload = workload
    if kind == "paper":
        return library_mod.paper_layer(payload)
    if kind == "local":
        return payload() if callable(payload) else payload
    builder = _BUILDERS.get(kind)
    if builder is None:
        raise ValueError(f"unknown workload kind {kind!r}")
    return builder(**payload)


def _workload_serialisable(workload: tuple) -> bool:
    return workload[0] != "local"


def _workload_label(workload: tuple) -> str:
    kind, payload = workload
    if kind == "paper":
        return payload
    if kind == "local":
        obj = payload
        name = getattr(obj, "name", None) or getattr(obj, "__name__", None)
        return str(name or "local")
    if kind == "gemm" and "name" in payload:
        return str(payload["name"])
    return f"{kind}:" + ",".join(f"{k}={v}"
                                 for k, v in sorted(payload.items()))


# ---------------------------------------------------------------------------
# options (de)serialisation — JSON plans for spawned workers
# ---------------------------------------------------------------------------

_OPTION_FIELDS = ("vectorize", "unroll", "pack", "unroll_factor",
                  "max_mnemonics")


def options_to_json(opts: CompileOptions) -> dict:
    d = {f: getattr(opts, f) for f in _OPTION_FIELDS}
    if opts.search is not None:
        d["search"] = dataclasses.asdict(opts.search)
    return d


def options_from_json(d: dict) -> CompileOptions:
    search = None
    if d.get("search") is not None:
        s = dict(d["search"])
        s["unroll_choices"] = tuple(s.get("unroll_choices", (1, 2, 4, 8)))
        search = SearchOptions(**s)
    return CompileOptions(**{f: d[f] for f in _OPTION_FIELDS if f in d},
                          search=search)


def _options_label(opts: CompileOptions) -> str:
    if opts.search is not None:
        return (f"search:{opts.search.strategy}"
                f"@g{opts.search.generations}p{opts.search.population}")
    return "heuristic"


# ---------------------------------------------------------------------------
# work units + results
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class WorkUnit:
    """One ``(codelet, target-variant, options)`` point of a sweep plan,
    identified by the driver's content-addressed compile ``key``."""

    layer: str            # display label (paper key / codelet name)
    target: str           # registry name, incl. derived variants
    workload: tuple       # serialisable descriptor (see workload_of)
    options: CompileOptions
    key: str              # = repro.core.driver.compile_key(...)

    @property
    def opt(self) -> str:
        return _options_label(self.options)

    def to_json(self) -> dict:
        assert _workload_serialisable(self.workload), \
            f"local workload {self.layer!r} cannot cross a process boundary"
        return {"layer": self.layer, "target": self.target,
                "workload": list(self.workload),
                "options": options_to_json(self.options), "key": self.key}

    @classmethod
    def from_json(cls, d: dict) -> "WorkUnit":
        return cls(layer=d["layer"], target=d["target"],
                   workload=tuple(d["workload"]),
                   options=options_from_json(d["options"]), key=d["key"])


@dataclasses.dataclass
class UnitResult:
    """Outcome of one work unit.

    ``source``: ``compiled`` (ran the pipeline/search), ``store`` (warm
    artifact-store restore — zero pipeline stages), ``cache`` (in-process
    cache hit), ``dedup`` (coordinator skipped dispatch: the key was
    already in the store), ``none`` (failed/skipped before compiling)."""

    key: str
    layer: str
    target: str
    opt: str = "heuristic"
    status: str = "ok"          # ok | failed | skipped
    source: str = "none"
    cycles: float | None = None
    stages_run: int = 0
    worker: str = "coordinator"
    error: str | None = None

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, d: dict) -> "UnitResult":
        return cls(**d)


_STATUS_RANK = {"ok": 0, "failed": 1, "skipped": 2}


@dataclasses.dataclass
class SweepReport:
    """Merged outcome of a sweep: per-unit records + roll-ups.

    ``merge`` is associative and idempotent over unit keys (the best
    record per key wins: ok > failed > skipped), so partial reports from
    any number of workers — or from a re-run — combine into the same
    final report."""

    sweep_id: str
    results: list[UnitResult] = dataclasses.field(default_factory=list)
    backend: str = "serial"
    workers: int = 1

    # -- roll-ups ------------------------------------------------------------
    def counts(self) -> dict:
        c = {"units": len(self.results), "ok": 0, "failed": 0, "skipped": 0,
             "compiled": 0, "store": 0, "cache": 0, "dedup": 0}
        for r in self.results:
            c[r.status] = c.get(r.status, 0) + 1
            if r.source in c:
                c[r.source] += 1
        return c

    @property
    def ok(self) -> list[UnitResult]:
        return [r for r in self.results if r.status == "ok"]

    def stages_run(self) -> int:
        return sum(r.stages_run for r in self.results)

    def cycles_by_key(self) -> dict:
        return {r.key: r.cycles for r in self.ok}

    def best_by_layer(self) -> dict:
        """{layer: winning UnitResult} — lowest analytic cycles across the
        target-variant x options axes (the fig14 table)."""
        best: dict[str, UnitResult] = {}
        for r in self.ok:
            if r.cycles is None:
                continue
            cur = best.get(r.layer)
            if cur is None or r.cycles < cur.cycles:
                best[r.layer] = r
        return best

    def best_table(self) -> str:
        best = self.best_by_layer()
        if not best:
            return "(no successful units)"
        width = max(len(k) for k in best)
        lines = [f"{'layer':{width}s} {'best variant':>28s} "
                 f"{'options':>24s} {'cycles':>14s}"]
        for layer in sorted(best):
            r = best[layer]
            lines.append(f"{layer:{width}s} {r.target:>28s} "
                         f"{r.opt:>24s} {r.cycles:14.0f}")
        return "\n".join(lines)

    def summary(self) -> str:
        c = self.counts()
        return (f"sweep {self.sweep_id}: {c['units']} units via "
                f"{self.backend}x{self.workers} — {c['ok']} ok "
                f"({c['compiled']} compiled, {c['store']} store, "
                f"{c['cache']} cache, {c['dedup']} dedup), "
                f"{c['failed']} failed, {c['skipped']} skipped, "
                f"{self.stages_run()} pipeline stages run")

    # -- merge ---------------------------------------------------------------
    @classmethod
    def merge(cls, reports: "Iterable[SweepReport]",
              sweep_id: str | None = None) -> "SweepReport":
        by_key: dict[str, UnitResult] = {}
        sid, backend, workers = sweep_id, "serial", 0
        for rep in reports:
            sid = sid or rep.sweep_id
            backend = rep.backend
            workers = max(workers, rep.workers)
            for r in rep.results:
                cur = by_key.get(r.key)
                if cur is None or _STATUS_RANK.get(r.status, 3) \
                        < _STATUS_RANK.get(cur.status, 3):
                    by_key[r.key] = r
        out = cls(sweep_id=sid or "?", backend=backend,
                  workers=max(workers, 1))
        out.results = sorted(by_key.values(), key=lambda r: r.key)
        return out

    # -- (de)serialisation ---------------------------------------------------
    def to_json(self) -> dict:
        return {"sweep_id": self.sweep_id, "backend": self.backend,
                "workers": self.workers,
                "results": [r.to_json() for r in self.results]}

    @classmethod
    def from_json(cls, d: dict) -> "SweepReport":
        return cls(sweep_id=d["sweep_id"], backend=d.get("backend", "?"),
                   workers=d.get("workers", 1),
                   results=[UnitResult.from_json(r) for r in d["results"]])

    def save(self, path: str) -> None:
        with open(path, "w", encoding="utf-8") as f:
            json.dump(self.to_json(), f, indent=1)

    @classmethod
    def load(cls, path: str) -> "SweepReport":
        with open(path, "r", encoding="utf-8") as f:
            return cls.from_json(json.load(f))


# ---------------------------------------------------------------------------
# plan expansion + deterministic partition
# ---------------------------------------------------------------------------


def expand_plan(layers: Iterable, targets: Sequence[str] = ("hvx",),
                options: CompileOptions | None = None,
                searches: Sequence[SearchOptions | None] | None = None,
                ) -> list[WorkUnit]:
    """layers x targets x search configs -> key-sorted, key-deduped work
    units.  ``searches`` adds an options axis: each entry replaces
    ``options.search`` (``None`` = the one-shot heuristic)."""
    from . import driver as driver_mod  # local: driver imports sweep lazily

    base = options if options is not None else CompileOptions()
    if getattr(base, "store", None) is not None:
        base = dataclasses.replace(base, store=None)  # location, not input
    axis = [base] if not searches else \
        [dataclasses.replace(base, search=s) for s in searches]
    units: dict[str, WorkUnit] = {}
    for layer in layers:
        workload = workload_of(layer)
        cdlt = build_workload(workload)
        label = _workload_label(workload)
        for target in targets:
            if not isinstance(target, str):
                raise TypeError(
                    f"sweep targets must be registry names (got "
                    f"{type(target)!r}); register the spec first")
            for opts in axis:
                key = driver_mod.compile_key(cdlt, target, opts)
                units.setdefault(key, WorkUnit(
                    layer=label, target=target, workload=workload,
                    options=opts, key=key))
    return sorted(units.values(), key=lambda u: u.key)


def partition(units: Sequence[WorkUnit],
              workers: int) -> list[list[WorkUnit]]:
    """Shard units across ``workers`` deterministically: key-sorted round
    robin.  A pure function of the unit-key set and ``workers`` — plan
    order, duplicates and process identity do not change the shards."""
    assert workers >= 1
    shards: list[list[WorkUnit]] = [[] for _ in range(workers)]
    for i, u in enumerate(sorted(units, key=lambda u: u.key)):
        shards[i % workers].append(u)
    return shards


def plan_id(units: Sequence[WorkUnit]) -> str:
    """Stable sweep id: digest of the sorted unit-key set.  Cold and warm
    runs of the same plan share a journal — "compiled exactly once" holds
    *across* runs, which is the CI invariant."""
    h = hashlib.sha256()
    for u in sorted(units, key=lambda u: u.key):
        h.update(u.key.encode())
        h.update(b"\x00")
    return h.hexdigest()[:12]


# ---------------------------------------------------------------------------
# unit execution (shared by every backend)
# ---------------------------------------------------------------------------


def _journal_safe(journal, record: dict) -> None:
    """Journaling is telemetry: a wedged/raced journal lock must never
    fail a unit whose compile already landed in the store.  A dropped
    'compiled' event is still surfaced — the CLI's
    ``--assert-unique-compiles`` reports units that compiled without a
    journal entry."""
    if journal is None:
        return
    try:
        journal.append(record)
    except Exception:
        pass


def _compile_unit(unit: WorkUnit, store, journal, worker: str) -> UnitResult:
    """Compile one unit through the driver, classify the source from the
    driver's stats delta, and journal the outcome."""
    from . import driver as driver_mod

    opts = unit.options if store is None \
        else dataclasses.replace(unit.options, store=store)
    before = driver_mod.cache_stats()
    try:
        art = driver_mod.compile(build_workload(unit.workload), unit.target,
                                 opts)
        cycles = art.cycles()
    except Exception as e:  # a broken covenant/unit must not sink the sweep
        res = UnitResult(key=unit.key, layer=unit.layer, target=unit.target,
                         opt=unit.opt, status="failed", error=str(e),
                         worker=worker)
        _journal_safe(journal, {"event": "failed", "key": unit.key,
                                "layer": unit.layer, "target": unit.target,
                                "worker": worker, "error": str(e)[:500]})
        return res
    after = driver_mod.cache_stats()
    if after["store_hits"] > before["store_hits"]:
        source, event = "store", "store_hit"
    elif after["hits"] > before["hits"]:
        source, event = "cache", "cache_hit"
    else:
        source, event = "compiled", "compiled"
    res = UnitResult(key=unit.key, layer=unit.layer, target=unit.target,
                     opt=unit.opt, status="ok", source=source, cycles=cycles,
                     stages_run=len(art.ctx.executed), worker=worker)
    _journal_safe(journal, {"event": event, "key": unit.key,
                            "layer": unit.layer, "target": unit.target,
                            "worker": worker, "cycles": cycles})
    return res


def _dedup_result(unit: WorkUnit, entry: dict, worker: str) -> UnitResult:
    return UnitResult(key=unit.key, layer=unit.layer, target=unit.target,
                      opt=unit.opt, status="ok", source="dedup",
                      cycles=store_mod.entry_cycles(entry), stages_run=0,
                      worker=worker)


# ---------------------------------------------------------------------------
# process backend
# ---------------------------------------------------------------------------


def _run_worker_shard(payload: str) -> str:
    """Top-level worker entry (spawn-importable).  JSON in, JSON out —
    no pickled live objects cross the process boundary."""
    import repro

    args = json.loads(payload)
    repro.clear_cache()  # forked workers must not inherit warm in-process
    #                      state: unit sources stay store/compiled only
    store = store_mod.resolve(args["store"]) if args["store"] else None
    journal = store.journal(args["sweep_id"]) if store is not None else None
    worker = args["worker"]
    results = []
    for d in args["units"]:
        unit = WorkUnit.from_json(d)
        results.append(_compile_unit(unit, store, journal, worker).to_json())
    return json.dumps(results)


def _process_backend(shards: list[list[WorkUnit]], store, sweep_id: str,
                     mp_start: str | None = None) -> list[UnitResult]:
    import multiprocessing as mp

    if mp_start is None:
        mp_start = "fork" if "fork" in mp.get_all_start_methods() \
            else "spawn"
    ctx = mp.get_context(mp_start)
    payloads, labels = [], []
    for i, shard in enumerate(shards):
        if not shard:
            continue
        worker = f"w{i}"
        labels.append((worker, shard))
        payloads.append(json.dumps({
            "units": [u.to_json() for u in shard],
            "store": store.root if store is not None else None,
            "sweep_id": sweep_id, "worker": worker}))
    if not payloads:
        return []
    results: list[UnitResult] = []
    # one future per shard on a ProcessPoolExecutor: a worker dying hard
    # (segfault/OOM) raises BrokenProcessPool instead of wedging the
    # coordinator (the mp.Pool failure mode), and it fails only the
    # shards that had not finished — completed shards keep their results,
    # and every finished unit is in the store either way
    from concurrent.futures import ProcessPoolExecutor

    with ProcessPoolExecutor(max_workers=len(payloads),
                             mp_context=ctx) as pool:
        futures = [pool.submit(_run_worker_shard, p) for p in payloads]
        for (worker, shard), fut in zip(labels, futures):
            try:
                out = fut.result()
            except Exception as e:
                results.extend(
                    UnitResult(key=u.key, layer=u.layer, target=u.target,
                               opt=u.opt, status="failed",
                               error=f"worker {worker} died: {e}",
                               worker=worker)
                    for u in shard)
                continue
            results.extend(UnitResult.from_json(d) for d in json.loads(out))
    return results


# ---------------------------------------------------------------------------
# the coordinator
# ---------------------------------------------------------------------------


def sweep(layers: Iterable, targets: Sequence[str] = ("hvx",), *,
          options: CompileOptions | None = None,
          searches: Sequence[SearchOptions | None] | None = None,
          workers: int = 1, store=None, backend: str | None = None,
          sweep_id: str | None = None, dedup: bool = True,
          mp_start: str | None = None) -> SweepReport:
    """Run a sweep plan and merge the outcome into a ``SweepReport``.

    ``layers`` — paper-layer keys / ``LayerSpec`` / launch GEMM records /
    ``("gemm", {...})`` descriptors (serial backend also takes raw
    Codelets); ``targets`` — registry names incl. derived variants
    (``"dnnweaver@pe=32x32"``); ``searches`` — optional third axis of
    ``SearchOptions`` (``None`` entry = heuristic).

    ``store`` (or ``REPRO_CACHE_DIR``) names the shared measurement
    database; with one configured, already-stored units are *deduplicated*
    (reported, not dispatched) and every worker compile lands in the store
    and the sweep journal.  ``backend`` defaults to ``process`` when
    ``workers > 1`` else ``serial``."""
    if store is None and options is not None \
            and getattr(options, "store", None) is not None:
        store = options.store  # honour the compile()/compile_many() idiom
    st = store_mod.resolve(store)
    units = expand_plan(layers, targets, options=options, searches=searches)
    sweep_id = sweep_id or plan_id(units)
    if backend is None:
        backend = "process" if workers > 1 else "serial"

    results: list[UnitResult] = []
    todo: list[WorkUnit] = []
    journal = st.journal(sweep_id) if st is not None else None
    for unit in units:
        entry = st.peek(unit.key) if (dedup and st is not None) else None
        if entry is not None:
            res = _dedup_result(unit, entry, "coordinator")
            if res.cycles is None:
                # entry present but unreadable analytics: recompile
                todo.append(unit)
                continue
            _journal_safe(journal, {"event": "dedup", "key": unit.key,
                                    "layer": unit.layer,
                                    "target": unit.target,
                                    "worker": "coordinator",
                                    "cycles": res.cycles})
            results.append(res)
        else:
            todo.append(unit)

    if backend == "process" and workers > 1 and todo:
        serialisable = [u for u in todo
                        if _workload_serialisable(u.workload)]
        local = [u for u in todo if not _workload_serialisable(u.workload)]
        shards = partition(serialisable, workers)
        results.extend(_process_backend(shards, st, sweep_id,
                                        mp_start=mp_start))
        for unit in local:  # raw codelets cannot cross processes
            results.append(_compile_unit(unit, st, journal, "coordinator"))
    elif backend in ("serial", "process"):
        for unit in todo:
            results.append(_compile_unit(unit, st, journal, "coordinator"))
    else:
        raise ValueError(f"unknown sweep backend {backend!r}")

    report = SweepReport.merge(
        [SweepReport(sweep_id=sweep_id, results=results)],
        sweep_id=sweep_id)
    report.backend = backend
    report.workers = workers
    return report


__all__ = ["SweepReport", "UnitResult", "WorkUnit", "build_workload",
           "expand_plan", "options_from_json", "options_to_json",
           "partition", "plan_id", "sweep", "workload_of"]
