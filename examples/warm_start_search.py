"""Cost-model-guided beam search and cross-layer warm-starting from the
artifact store.

    PYTHONPATH=src python examples/warm_start_search.py
    PYTHONPATH=src python examples/warm_start_search.py --store /tmp/ws

Three acts:

1. **Cold beam search** — ``repro.sweep(..., searches=[beam])`` searches
   each layer once and records its winning point in the store and the
   sweep journal.
2. **Warm-started search** — a later search of a same-shaped layer tries
   the store's best recorded points first (``SearchOptions(warm_start=
   True)`` via the ``WarmStartIndex`` built from the sweep journal), beside
   the same search run cold.
3. The searched schedules persist content-addressed: re-running this
   script against the same ``--store`` recompiles nothing.
"""
import argparse
import dataclasses
import tempfile

import repro

LAYERS = ["DLRM-FC1", "DLRM-FC2", "DLRM-FC3"]
BEAM = repro.SearchOptions(generations=4, population=10, max_candidates=512)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--store", default=None)
    ap.add_argument("--target", default="hvx")
    args = ap.parse_args()
    store = args.store or tempfile.mkdtemp(prefix="covenant-warm-")

    # -- act 1: one cold beam search per layer, points recorded -------------
    report = repro.sweep(LAYERS, [args.target], store=store, searches=[BEAM])
    print(report.summary())
    print()
    print(report.best_table())

    # -- act 2: warm-start a fresh search from the recorded points ----------
    print("\nInceptionV3-FC1 (same GEMM shape family), cold and warm:")
    for warm in (False, True):
        repro.clear_cache()  # make both runs search, not cache-hit
        sopts = dataclasses.replace(BEAM, warm_start=warm)
        art = repro.compile("InceptionV3-FC1", args.target,
                            repro.CompileOptions(search=sopts, store=store))
        s = art.search
        print(f"  warm_start={warm!s:5s} -> {s.best_cycles:10.0f} cycles, "
              f"{s.evaluated:3d} evaluations, {s.seeded} seed(s) injected")

    idx = repro.WarmStartIndex.from_store(repro.ArtifactStore(store))
    print(f"\nwarm-start index: {len(idx)} recorded points "
          f"(store: {store})")


if __name__ == "__main__":
    main()
