"""Simulator-agreement property suite for the analytic cost model and the
``prefix_bound`` admissibility invariant beam pruning depends on.

Two invariants, the contract between ``core/cost.py`` and ``core/stream.py``:

* **exactness** — for any valid schedule point on shapes small enough to
  stream, ``cost.cost(pack=False)`` equals the stream machine's serial
  cycle count *exactly* (the model is mnemonic-faithful, not approximate);
* **admissibility** — ``cost.prefix_bound`` of any partial tiling
  commitment is never greater than the full-schedule cost of ANY
  completion, in both the packed and serial forms.  This is what makes
  beam pruning safe: a pruned prefix provably had no completion better
  than the incumbent bound ordering suggested.

The hypothesis half reuses the ``test_property_pipeline.py`` harness idiom
(random small problems, both eval targets); the seeded half mirrors the
same invariants without the hypothesis dependency, so the suite still
bites in environments without it.
"""
import random

import numpy as np
import pytest

from repro.core import codegen, cost, library, stream, targets
from repro.core.pipeline import CompileOptions, Pipeline
from repro.core.scheduler import schedule_space
from repro.core.search import materialise

pytestmark = pytest.mark.search

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # container may lack it; the seeded mirrors still run
    HAVE_HYPOTHESIS = False

TARGETS = ("hvx", "dnnweaver")
UNROLLS = (1, 2, 4, 8)


def _point_ctx(cdlt, acg, tiling, unroll):
    """Materialise one schedule point through the stock pipeline."""
    pl = Pipeline.default().with_acg_hooks(acg)
    return materialise(cdlt, acg, pl, CompileOptions(),
                       {"tiling": dict(tiling), "unroll_factor": unroll})


def _space(cdlt, acg, max_candidates=256):
    space = schedule_space(cdlt, acg, max_candidates=max_candidates)
    assert space.tilings
    return space


def _assert_admissible(space, acg, committed, full_cycles, pack):
    bound = cost.prefix_bound(space.probe, acg, space.plans, committed,
                              divisors=space.divisors, pack=pack)
    assert bound <= full_cycles + 1e-6, (
        f"prefix_bound({committed}, pack={pack}) = {bound} exceeds a "
        f"completion's cost {full_cycles}")


def _check_point(cdlt, acg, space, tiling, unroll, rng):
    """Both invariants for one (point, committed-subset) draw."""
    ctx = _point_ctx(cdlt, acg, tiling, unroll)
    sub = {v: tiling[v] for v in sorted(tiling) if rng.random() < 0.5}
    for pack in (False, True):
        full = cost.cost(ctx.cdlt, acg, pack=pack).cycles
        for committed in ({}, sub, dict(tiling)):
            _assert_admissible(space, acg, committed, full, pack)
    return ctx


# ---------------------------------------------------------------------------
# hypothesis half
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:

    @st.composite
    def gemm_point(draw):
        m = draw(st.integers(1, 12))
        n = draw(st.integers(1, 12))
        k = draw(st.integers(1, 12))
        pick = draw(st.integers(0, 10 ** 6))
        unroll = draw(st.sampled_from(UNROLLS))
        sub_seed = draw(st.integers(0, 10 ** 6))
        return m, n, k, pick, unroll, sub_seed

    @given(gemm_point(), st.sampled_from(TARGETS))
    @settings(max_examples=15, deadline=None)
    def test_cost_equals_stream_serial_cycles_exactly(prob, target):
        """Random valid schedule points on small GEMMs: the analytic model
        and the stream simulator agree EXACTLY on serial cycles."""
        m, n, k, pick, unroll, _ = prob
        acg = targets.get_target(target)
        cdlt = library.gemm(m, n, k, in_dtype="u8")
        space = _space(cdlt, acg)
        tiling = space.tilings[pick % len(space.tilings)]
        ctx = _point_ctx(cdlt, acg, tiling, unroll)
        try:
            prog = codegen.generate(ctx.cdlt, acg, max_mnemonics=60_000)
        except codegen.StreamTooLarge:
            return
        rng = np.random.default_rng(m * 131 + n * 17 + k)
        ins = {"A": rng.integers(0, 5, (m, k)).astype(np.uint8),
               "B": rng.integers(0, 5, (k, n)).astype(np.uint8)}
        res = stream.run_stream(prog, ins, pack=False)
        analytic = cost.cost(ctx.cdlt, acg, pack=False).cycles
        assert res.serial_cycles == pytest.approx(analytic, abs=1e-9)

    @given(gemm_point(), st.sampled_from(TARGETS))
    @settings(max_examples=15, deadline=None)
    def test_prefix_bound_is_admissible(prob, target):
        """prefix_bound of any committed sub-tiling never exceeds the full
        cost of any completion (both pack modes, empty/partial/full
        commitment)."""
        m, n, k, pick, unroll, sub_seed = prob
        acg = targets.get_target(target)
        cdlt = library.gemm(m, n, k, in_dtype="u8")
        space = _space(cdlt, acg)
        tiling = space.tilings[pick % len(space.tilings)]
        _check_point(cdlt, acg, space, tiling, unroll,
                     random.Random(sub_seed))


# ---------------------------------------------------------------------------
# seeded mirrors — same invariants, no hypothesis required
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("target", TARGETS)
def test_seeded_cost_stream_agreement_gemm(target, rng):
    py_rng = random.Random(17)
    checked = 0
    while checked < 6:
        m, n, k = (py_rng.randint(1, 10) for _ in range(3))
        acg = targets.get_target(target)
        cdlt = library.gemm(m, n, k, in_dtype="u8")
        space = _space(cdlt, acg)
        tiling = py_rng.choice(space.tilings)
        unroll = py_rng.choice(UNROLLS)
        ctx = _point_ctx(cdlt, acg, tiling, unroll)
        try:
            prog = codegen.generate(ctx.cdlt, acg, max_mnemonics=60_000)
        except codegen.StreamTooLarge:
            continue
        ins = {"A": rng.integers(0, 5, (m, k)).astype(np.uint8),
               "B": rng.integers(0, 5, (k, n)).astype(np.uint8)}
        res = stream.run_stream(prog, ins, pack=False)
        analytic = cost.cost(ctx.cdlt, acg, pack=False).cycles
        assert res.serial_cycles == pytest.approx(analytic, abs=1e-9), \
            (m, n, k, tiling, unroll)
        checked += 1


@pytest.mark.parametrize("target", TARGETS)
def test_seeded_prefix_bound_admissible_gemm(target):
    py_rng = random.Random(23)
    for _ in range(10):
        m, n, k = (py_rng.randint(1, 12) for _ in range(3))
        acg = targets.get_target(target)
        cdlt = library.gemm(m, n, k, in_dtype="u8")
        space = _space(cdlt, acg)
        _check_point(cdlt, acg, space, py_rng.choice(space.tilings),
                     py_rng.choice(UNROLLS), py_rng)


@pytest.mark.parametrize("target", TARGETS)
def test_seeded_prefix_bound_admissible_conv_elementwise(target):
    """Admissibility must survive clamped conv footprints (halo overlap)
    and 1-D elementwise codelets, not just perfect GEMM nests."""
    py_rng = random.Random(5)
    acg = targets.get_target(target)
    builders = [
        lambda: library.conv2d(1, py_rng.randint(6, 12),
                               py_rng.randint(6, 12), py_rng.choice([1, 3]),
                               py_rng.choice([4, 8]), 3, 3,
                               py_rng.choice([1, 2])),
        lambda: library.elementwise("ADD", py_rng.randint(2, 96), "i32"),
    ]
    for _ in range(6):
        cdlt = py_rng.choice(builders)()
        space = _space(cdlt, acg, max_candidates=128)
        _check_point(cdlt, acg, space, py_rng.choice(space.tilings),
                     py_rng.choice(UNROLLS), py_rng)


def test_prefix_bound_tightens_with_commitment():
    """Committing loops can only raise (never lower) the bound: committed
    loops cost exactly, so information monotonically narrows the
    relaxation.  Checked along random commitment chains."""
    py_rng = random.Random(11)
    acg = targets.get_target("hvx")
    cdlt = library.gemm(24, 32, 16, in_dtype="u8")
    space = _space(cdlt, acg)
    for _ in range(10):
        tiling = py_rng.choice(space.tilings)
        committed: dict = {}
        prev = cost.prefix_bound(space.probe, acg, space.plans, committed,
                                 divisors=space.divisors)
        for var in space.loop_order():
            committed[var] = tiling[var]
            cur = cost.prefix_bound(space.probe, acg, space.plans,
                                    committed, divisors=space.divisors)
            assert cur >= prev - 1e-9, (tiling, committed, cur, prev)
            prev = cur


def test_prefix_bound_is_deterministic():
    acg = targets.get_target("dnnweaver")
    cdlt = library.gemm(16, 24, 8, in_dtype="u8")
    space = _space(cdlt, acg)
    committed = {"m": 4, "k": 8}
    a = [cost.prefix_bound(space.probe, acg, space.plans, committed,
                           divisors=space.divisors) for _ in range(3)]
    assert len(set(a)) == 1
