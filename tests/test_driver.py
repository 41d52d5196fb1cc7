"""The unified compile driver (repro.compile): equivalence with the legacy
manual call chain, content-addressed caching, the pluggable pass pipeline,
and the per-ACG pass-override hook."""
import numpy as np
import pytest

import repro
from repro.core import codegen, cost, library, scheduler, stream, targets
from repro.core.codegen import StreamTooLarge
from repro.core.pipeline import Pipeline

from conftest import random_inputs

CASES = [
    ("hvx", lambda: library.gemm(8, 16, 12, in_dtype="u8")),
    ("hvx", lambda: library.elementwise("ADD", 64, "i32")),
    ("dnnweaver", lambda: library.gemm(8, 16, 12, in_dtype="u8")),
    ("dnnweaver", lambda: library.elementwise("ADD", 64, "i32")),
]


# ---------------------------------------------------------------------------
# (a) equivalence with the legacy manual pipeline
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("target,build", CASES)
def test_compile_matches_legacy_chain(target, build, rng):
    """repro.compile() produces byte-identical mnemonic programs, equal
    analytic cycles, and equal stream outputs to the hand-stitched
    schedule -> generate -> run_stream -> cost chain."""
    cdlt = build()
    acg = targets.get_target(target)
    sched = scheduler.schedule(cdlt, acg)
    prog = codegen.generate(sched, acg)
    ins = random_inputs(cdlt, rng, 0, 5)
    legacy = stream.run_stream(prog, ins)
    legacy_cycles = cost.cost(sched, acg).cycles

    art = repro.compile(build(), target)
    assert [m.encode() for m in art.program.mnemonics] == \
        [m.encode() for m in prog.mnemonics]
    assert [str(m) for m in art.program.mnemonics] == \
        [str(m) for m in prog.mnemonics]
    assert art.cycles() == legacy_cycles
    res = art.run(ins)
    for k in legacy.outputs:
        np.testing.assert_array_equal(res.outputs[k], legacy.outputs[k])
    assert res.serial_cycles == legacy.serial_cycles
    assert art.verify(ins)


def test_layer_key_and_spec_resolution():
    """Paper-layer keys and LayerSpecs resolve to the same artifact as the
    built codelet (content addressing, not object identity)."""
    spec = library.PAPER_LAYERS[6]  # DLRM-FC1: small
    by_key = repro.compile(spec.key, "hvx")
    by_spec = repro.compile(spec, "hvx")
    by_cdlt = repro.compile(spec.build(), "hvx")
    assert by_key is by_spec is by_cdlt


# ---------------------------------------------------------------------------
# (b) content-addressed cache
# ---------------------------------------------------------------------------


def test_cache_hit_returns_same_artifact_without_rerunning():
    repro.clear_cache()
    a1 = repro.compile(library.gemm(8, 16, 12, in_dtype="u8"), "hvx")
    stages_run = list(a1.ctx.executed)
    a2 = repro.compile(library.gemm(8, 16, 12, in_dtype="u8"), "hvx")
    assert a2 is a1                       # same artifact object
    assert a1.ctx.executed == stages_run  # no pass re-ran
    stats = repro.cache_stats()
    assert stats["hits"] == 1 and stats["misses"] == 1


def test_cache_misses_on_any_key_component():
    repro.clear_cache()
    base = repro.compile(library.gemm(8, 16, 12, in_dtype="u8"), "hvx")
    other_target = repro.compile(library.gemm(8, 16, 12, in_dtype="u8"),
                                 "dnnweaver")
    other_opts = repro.compile(library.gemm(8, 16, 12, in_dtype="u8"), "hvx",
                               repro.CompileOptions(unroll=False))
    other_cdlt = repro.compile(library.gemm(8, 16, 13, in_dtype="u8"), "hvx")
    arts = {id(a) for a in (base, other_target, other_opts, other_cdlt)}
    assert len(arts) == 4
    assert repro.cache_stats()["misses"] == 4


def test_cache_bypass():
    repro.clear_cache()
    a1 = repro.compile(library.gemm(4, 8, 4, in_dtype="u8"), "hvx",
                       cache=False)
    a2 = repro.compile(library.gemm(4, 8, 4, in_dtype="u8"), "hvx",
                       cache=False)
    assert a1 is not a2
    assert repro.cache_stats()["size"] == 0


# ---------------------------------------------------------------------------
# (c) pluggable pipeline + per-ACG override hook
# ---------------------------------------------------------------------------


def test_acg_pass_hooks_execute():
    """A stage override and an extra pass installed on the ACG (BYOC-style)
    both actually run, in pipeline position."""
    acg = targets.get_target("hvx")
    ran = []

    def spy(ctx):
        ran.append("spy")
        ctx.cdlt.note("custom-pass: executed")

    def no_unroll(ctx):
        ran.append("unroll-override")

    acg.extra_passes.append(("after:granularize", "spy", spy))
    acg.pass_overrides["unroll"] = no_unroll
    art = repro.compile(library.gemm(4, 8, 4, in_dtype="u8"), acg,
                        cache=False)
    assert ran == ["spy", "unroll-override"]
    assert any("custom-pass: executed" in n for n in art.schedule_notes)
    assert "spy" in art.pipeline.names
    # the override suppressed unrolling: no unroll note on the codelet
    assert not any(n.startswith("unroll:") for n in art.schedule_notes)


def test_explicit_pipeline_argument():
    marks = []
    pl = Pipeline.default().insert_before(
        "codegen", "mark", lambda ctx: marks.append(ctx.cdlt.name))
    art = repro.compile(library.elementwise("MUL", 32, "i32"), "hvx",
                        pipeline=pl, cache=False)
    assert marks == [art.codelet.name]


def test_schedule_wrapper_runs_acg_hooks():
    """The thin scheduler.schedule wrapper also honours ACG hooks."""
    acg = targets.get_target("dnnweaver")
    acg.extra_passes.append(
        ("before:place", "tag", lambda ctx: ctx.cdlt.note("tag: hello")))
    sched = scheduler.schedule(library.gemm(4, 8, 4, in_dtype="u8"), acg)
    assert sched.schedule_notes[0] == "tag: hello"


# ---------------------------------------------------------------------------
# options unification + misc artifact surface
# ---------------------------------------------------------------------------


def test_schedule_config_is_compile_options():
    assert scheduler.ScheduleConfig is repro.CompileOptions
    assert hash(repro.CompileOptions()) == hash(repro.CompileOptions())


def test_max_mnemonics_option_travels_to_codegen():
    art = repro.compile(library.gemm(64, 64, 64, in_dtype="u8"), "hvx",
                        repro.CompileOptions(max_mnemonics=10), cache=False)
    with pytest.raises(StreamTooLarge):
        art.program  # codegen is lazy; the guard fires on first touch


def test_large_layer_analytics_without_program():
    """Table-2-scale layers are served by analytic cycles alone — compiling
    must not eagerly expand the (too large) mnemonic stream."""
    art = repro.compile("BERT-LG-GEMM1", "hvx")
    assert art.cycles() > 0
    assert "program" not in art.ctx.state


def test_compile_many_batches_and_caches():
    repro.clear_cache()
    items = [library.gemm(4, 8, 4, in_dtype="u8"),
             library.elementwise("ADD", 16, "i32"),
             "DLRM-FC4"]
    arts = repro.compile_many(items, target="dnnweaver")
    assert len(arts) == 3
    again = repro.compile_many(items, target="dnnweaver")
    assert all(a is b for a, b in zip(arts, again))


def test_search_option_routes_through_driver():
    """CompileOptions(search=...) produces a cached artifact with the trace
    attached, keyed separately from the heuristic compile."""
    repro.clear_cache()
    sopts = repro.SearchOptions(generations=3, population=8)
    cdlt = library.gemm(24, 32, 16, in_dtype="u8")
    heur = repro.compile(cdlt, "hvx")
    art = repro.compile(cdlt, "hvx", repro.CompileOptions(search=sopts))
    assert art.cycles() <= heur.cycles()
    assert art.search is not None
    assert art.search.trace and art.search.evaluated > 0
    assert art.search.heuristic_cycles == heur.cycles()
    assert art.key != heur.key
    again = repro.compile(cdlt, "hvx", repro.CompileOptions(search=sopts))
    assert again is art  # searched winner served from the cache, no re-search


def test_search_artifact_runs_correctly(rng):
    """The searched schedule's mnemonic stream still matches the oracle."""
    cdlt = library.gemm(8, 16, 12, in_dtype="u8")
    art = repro.compile(
        cdlt, "hvx",
        repro.CompileOptions(search=repro.SearchOptions(
            strategy="exhaustive", max_candidates=64)),
        cache=False)
    assert art.verify(random_inputs(cdlt, rng, 0, 5))


def test_store_option_accepts_path(tmp_path):
    """CompileOptions(store=<path>) resolves to a shared ArtifactStore and
    does not perturb the cache key (a store is a location, not an input)."""
    repro.clear_cache()
    stored = repro.compile(
        library.gemm(8, 16, 12, in_dtype="u8"), "hvx",
        repro.CompileOptions(store=str(tmp_path)))
    plain = repro.compile(library.gemm(8, 16, 12, in_dtype="u8"), "hvx")
    assert plain is stored  # same key: the in-process tier answered
    repro.clear_cache()
    warm = repro.compile(library.gemm(8, 16, 12, in_dtype="u8"), "hvx",
                         repro.CompileOptions(store=str(tmp_path)))
    assert warm.ctx.executed == [] and warm.cycles() == stored.cycles()


def test_search_option_must_be_search_options():
    with pytest.raises(TypeError):
        repro.compile(library.gemm(4, 8, 4, in_dtype="u8"), "hvx",
                      repro.CompileOptions(search={"strategy": "beam"}),
                      cache=False)


def test_custom_stage_fingerprint_is_process_stable(tmp_path):
    """Custom pass fns are fingerprinted by source hash, not object id, so
    a BYOC target's store keys survive process restarts.  Emulate two
    processes by importing the same hook module twice."""
    import importlib.util

    mod_file = tmp_path / "hookmod.py"
    mod_file.write_text("def no_unroll(ctx):\n    pass\n")

    def load(name):
        spec = importlib.util.spec_from_file_location(name, mod_file)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.no_unroll

    fn_a, fn_b = load("hookmod_a"), load("hookmod_b")
    assert fn_a is not fn_b
    fp_a = Pipeline.default().override("unroll", fn_a).fingerprint()
    fp_b = Pipeline.default().override("unroll", fn_b).fingerprint()
    assert fp_a == fp_b
    import re  # the custom stage carries a source-hash tag, not an id
    assert re.search(r"unroll:.*:[0-9a-f]{16}(;|$)", fp_a)


def test_closure_captures_distinguish_stage_fingerprints():
    """Two closures from one factory with different captured parameters
    must NOT alias to the same cache key."""
    def make_stage(factor):
        def stage(ctx):
            ctx.cdlt.note(f"custom: {factor}")
        return stage

    fp2 = Pipeline.default().override("unroll", make_stage(2)).fingerprint()
    fp8 = Pipeline.default().override("unroll", make_stage(8)).fingerprint()
    assert fp2 != fp8
    # and the same capture is stable across factory calls
    assert fp2 == Pipeline.default().override(
        "unroll", make_stage(2)).fingerprint()


def test_register_target():
    repro.register_target("hvx_nounroll", targets.hvx_acg,
                          pass_overrides={"unroll": lambda ctx: None})
    try:
        assert "hvx_nounroll" in repro.available_targets()
        art = repro.compile(library.gemm(8, 16, 12, in_dtype="u8"),
                            "hvx_nounroll", cache=False)
        assert not any(n.startswith("unroll:") for n in art.schedule_notes)
        # same mnemonics as an explicit unroll=False compile on stock hvx
        ref = repro.compile(library.gemm(8, 16, 12, in_dtype="u8"), "hvx",
                            repro.CompileOptions(unroll=False), cache=False)
        assert [m.encode() for m in art.program.mnemonics] == \
            [m.encode() for m in ref.program.mnemonics]
    finally:
        targets.TARGETS.pop("hvx_nounroll", None)
