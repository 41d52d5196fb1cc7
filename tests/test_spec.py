"""Declarative covenant specs: ACG round-trip identity, the
string-addressable target registry (incl. derived variants), and covenant
validation diagnostics (named errors, not tracebacks)."""
import dataclasses

import pytest

import repro
from repro.core import library, targets
from repro.core.acg import ACG
from repro.core.codelet import Codelet, Compute, Loop, ref, v
from repro.core.covenant import (CovenantError, check_covenant, validate_acg)
from repro.core.dtypes import dt
from repro.core.spec import (ACGSpec, SpecError, acg_spec, parse_overrides,
                             scap, scu, sedge, smem, sop, validate_spec)

EVAL_TARGETS = ("hvx", "dnnweaver")
# small enough to expand the full mnemonic stream
STREAM_LAYERS = ("DLRM-FC2", "DLRM-FC3", "DLRM-FC4")


# ---------------------------------------------------------------------------
# round-trip identity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(targets.BUNDLED_SPECS))
def test_spec_roundtrip_fingerprint_identity(name):
    """from_spec(to_spec(acg)) is fingerprint-identical, and the bundled
    spec *is* that canonical form."""
    spec = targets.BUNDLED_SPECS[name]
    acg = ACG.from_spec(spec)
    assert acg.to_spec() == spec
    assert acg.to_spec().fingerprint() == spec.fingerprint()
    again = ACG.from_spec(acg.to_spec())
    assert again.describe() == acg.describe()
    assert again.to_spec().fingerprint() == spec.fingerprint()


@pytest.mark.parametrize("name", sorted(targets.BUNDLED_SPECS))
def test_spec_json_roundtrip(name):
    spec = targets.BUNDLED_SPECS[name]
    again = ACGSpec.from_json(spec.to_json())
    assert again == spec
    assert again.fingerprint() == spec.fingerprint()


@pytest.mark.parametrize("target", EVAL_TARGETS)
def test_roundtrip_equal_compiles_every_paper_layer(target):
    """Every paper layer compiles to the same content-addressed key (hence
    the same schedule and analytics) on the round-tripped ACG."""
    base = targets.get_target(target)
    rt = ACG.from_spec(base.to_spec())
    for spec in library.PAPER_LAYERS:
        a = repro.compile(spec, base)
        b = repro.compile(spec, rt)
        assert b is a, spec.key  # same key => same cached artifact
        assert b.cycles() == a.cycles()


@pytest.mark.parametrize("target", EVAL_TARGETS)
@pytest.mark.parametrize("layer", STREAM_LAYERS)
def test_roundtrip_byte_identical_streams(target, layer):
    """Unrollable layers produce byte-identical mnemonic streams on the
    original and the round-tripped ACG."""
    a = repro.compile(layer, targets.get_target(target), cache=False)
    b = repro.compile(
        layer, ACG.from_spec(targets.get_target(target).to_spec()),
        cache=False)
    assert [m.encode() for m in a.program.mnemonics] == \
        [m.encode() for m in b.program.mnemonics]
    assert [str(m) for m in a.program.mnemonics] == \
        [str(m) for m in b.program.mnemonics]


# ---------------------------------------------------------------------------
# registry resolution
# ---------------------------------------------------------------------------


def test_spec_target_with_unhashable_attrs_compiles():
    """Mnemonic attrs may hold list values after a JSON round-trip; the
    driver's spec memo must not require the spec to be hashable."""
    spec = ACGSpec.from_json(targets.HVX_SPEC.to_json())
    from repro.core.spec import MnemonicSpec
    spec = dataclasses.replace(
        spec, name="hvx_attrs",
        mnemonics=spec.mnemonics + (
            MnemonicSpec("HALT", 0x7F, (), attrs=(("units", ["CORE"]),)),))
    with pytest.raises(TypeError):
        hash(spec)  # the precondition that used to crash the memo
    art = repro.compile("DLRM-FC4", spec)
    assert art.cycles() > 0


def test_pe_derive_only_rescales_the_pe_grid_unit():
    """pe= sweeps one design axis: the unit owning the largest matmul
    geometry (the systolic array).  The SIMD unit — whose lane count
    happens to equal the array width — keeps all its shapes."""
    d = targets.DNNWEAVER_SPEC.derive(pe="32x32")
    systolic = next(c for c in d.computes if c.name == "SYSTOLIC")
    simd = next(c for c in d.computes if c.name == "SIMD")
    gemm = next(k for k in systolic.capabilities if k.name == "GEMM")
    assert gemm.geometry == (1, 32, 32)
    assert gemm.inputs[1] == ("i8", 32, 32)
    add = next(k for k in simd.capabilities if k.name == "ADD")
    assert add.outputs[0] == ("i32", 64)  # lanes untouched
    mac = next(k for k in simd.capabilities if k.name == "MAC")
    assert mac.geometry == (1, 64, 1)     # SIMD MAC untouched too


def test_registered_derived_spec_resolves_by_its_at_name():
    """A registered spec whose *name* contains '@' must resolve exactly,
    not be re-parsed as base@overrides against an unknown base."""
    npu = targets.DNNWEAVER_SPEC.derive(pe="16x16", name="solo16@custom")
    repro.targets.register(npu)
    try:
        assert targets.get_spec("solo16@custom") == npu
        art = repro.compile("DLRM-FC4", "solo16@custom")
        assert art.target == "solo16@custom"
    finally:
        targets.TARGETS.pop("solo16@custom", None)


def test_exact_registration_shadows_variant_derivation_in_driver():
    """Registering a spec under an exact '@' name must invalidate the
    driver's memo for that name, even though the base factory is
    unchanged — the registered entry wins from then on."""
    name = "dnnweaver@pe=32x32"
    derived = repro.compile("DLRM-FC4", name)   # on-the-fly variant
    custom = targets.HVX_SPEC.derive(name=name)  # same name, hvx content
    repro.targets.register(custom)
    try:
        registered = repro.compile("DLRM-FC4", name)
        assert registered.key != derived.key
        assert registered.acg.to_spec().fingerprint() == custom.fingerprint()
    finally:
        targets.TARGETS.pop(name, None)
    # with the registration gone, the variant derivation is back
    again = repro.compile("DLRM-FC4", name)
    assert again.key == derived.key


def test_fingerprint_canonical_regardless_of_construction_order():
    """attrs / operand_ports ordering is canonicalized at fingerprint
    time, so a spec built with unsorted fields round-trips to the same
    identity (and the driver's spec memo actually hits)."""
    from repro.core.spec import MnemonicSpec

    def with_attrs(attrs):
        return dataclasses.replace(
            targets.HVX_SPEC, name="hvx_a",
            mnemonics=targets.HVX_SPEC.mnemonics + (
                MnemonicSpec("HALT", 0x7F, (), attrs=attrs),))

    a = with_attrs((("zeta", 1), ("alpha", 2)))
    b = with_attrs((("alpha", 2), ("zeta", 1)))
    assert a.fingerprint() == b.fingerprint()
    assert ACG.from_spec(a).to_spec().fingerprint() == a.fingerprint()


def test_registry_resolution_names_and_specs():
    by_name = repro.compile("DLRM-FC4", "hvx")
    by_spec = repro.compile("DLRM-FC4", targets.HVX_SPEC)
    by_acg = repro.compile("DLRM-FC4", targets.get_target("hvx"))
    assert by_name is by_spec is by_acg


def test_registry_unknown_target_names_known():
    with pytest.raises(KeyError, match="unknown target 'nonesuch'"):
        targets.get_target("nonesuch")
    with pytest.raises(KeyError, match="unknown target 'nonesuch'"):
        repro.compile("DLRM-FC4", "nonesuch@pe=8x8")


def test_register_spec_roundtrips_through_driver():
    npu = acg_spec(
        "test_npu",
        memories=[smem("DRAM", 8, 1, 1 << 24, offchip=True),
                  smem("SPM", 32, 16, 4096)],
        computes=[scu("PE", [
            scap("GEMM", sop("i32", 8),
                 [sop("i8", 8), sop("i8", 8, 8), sop("i32", 8)],
                 geometry=(1, 8, 8)),
            scap("MAC", sop("i32", 8),
                 [sop("i8", 8), sop("i8", 8, 8), sop("i32", 8)],
                 geometry=(1, 8, 8)),
        ], slot="pe")],
        edges=[sedge("DRAM", "SPM", 128, bidir=True),
               sedge("SPM", "PE", 256, bidir=True)],
    )
    repro.targets.register(npu)
    try:
        assert "test_npu" in repro.targets.list()
        art = repro.compile("DLRM-FC4", "test_npu")
        assert art.cycles() > 0
        variant = repro.compile("DLRM-FC4", "test_npu@pe=4x4")
        assert variant.key != art.key
    finally:
        targets.TARGETS.pop("test_npu", None)


def test_get_spec_of_factory_registered_target():
    """Targets registered as plain factories (legacy register_target) are
    snapshotted to specs on demand — variants derive from the snapshot."""
    repro.register_target("hvx_twin", targets.hvx_acg)
    try:
        assert targets.get_spec("hvx_twin") == targets.HVX_SPEC
        acg = targets.get_target("hvx_twin@issue_slots=1")
        assert acg.issue_slots == 1
    finally:
        targets.TARGETS.pop("hvx_twin", None)


# ---------------------------------------------------------------------------
# derived variants
# ---------------------------------------------------------------------------


def test_derive_canonical_names_merge_and_parse():
    base = targets.DNNWEAVER_SPEC
    d1 = base.derive(pe="32x32")
    assert d1.name == "dnnweaver@pe=32x32"
    d2 = d1.derive(memories={"VMEM1": {"depth": 4096}})
    assert d2.name == "dnnweaver@VMEM1.depth=4096,pe=32x32"
    # the canonical name parses back to the same spec
    assert targets.get_spec(d2.name) == d2
    # and overrides-merge is idempotent for repeated keys
    assert d1.derive(pe="32x32") == d1


def test_derived_variant_distinct_key_and_cost():
    """Acceptance: a derived variant produces a distinct store key and a
    distinct cost report from its base."""
    base = repro.compile("DLRM-FC1", "dnnweaver")
    variant = repro.compile("DLRM-FC1", "dnnweaver@pe=32x32")
    assert variant.key != base.key
    assert variant.cycles() != base.cycles()
    assert variant.target == "dnnweaver@pe=32x32"


def test_derive_rejects_unknown_entities():
    with pytest.raises(SpecError, match="no memory node 'NOPE'"):
        targets.HVX_SPEC.derive(memories={"NOPE": {"depth": 1}})
    with pytest.raises(SpecError, match="unknown field"):
        targets.HVX_SPEC.derive(memories={"VRF": {"color": 1}})
    with pytest.raises(SpecError, match="no edge"):
        targets.HVX_SPEC.derive(edges={("VRF", "GRF"): {"bandwidth": 1}})
    with pytest.raises(SpecError):
        targets.HVX_SPEC.derive(pe="3x4")  # non-square


def test_parse_overrides_grammar():
    kw = parse_overrides("pe=16x16,issue_slots=2,VRF.depth=64,"
                         "edge.L2.VRF.bandwidth=512")
    assert kw == {"pe": "16x16", "issue_slots": 2,
                  "memories": {"VRF": {"depth": 64}},
                  "edges": {("L2", "VRF"): {"bandwidth": 512}}}
    with pytest.raises(SpecError, match="not 'key=value'"):
        parse_overrides("pe")
    with pytest.raises(SpecError, match="unknown override key"):
        parse_overrides("warp=9")
    with pytest.raises(SpecError, match="must be an integer"):
        parse_overrides("issue_slots=abc")
    with pytest.raises(SpecError, match="must be an integer"):
        parse_overrides("VRF.depth=big")
    with pytest.raises(SpecError, match="look like '32x32'"):
        targets.HVX_SPEC.derive(pe="axb")
    assert parse_overrides("L2.offchip=1") == \
        {"memories": {"L2": {"offchip": True}}}
    assert parse_overrides("L2.offchip=false") == \
        {"memories": {"L2": {"offchip": False}}}
    with pytest.raises(SpecError, match="must be a boolean"):
        parse_overrides("L2.offchip=yes")


def test_compile_many_heterogeneous_pairs():
    """One batched sweep across architecture variants via (codelet, target)
    pairs."""
    repro.clear_cache()
    arts = repro.compile_many(
        [("DLRM-FC4", "dnnweaver"),
         ("DLRM-FC4", "dnnweaver@pe=32x32"),
         "DLRM-FC4"],                        # falls back to sweep target
        target="hvx")
    assert [a.target for a in arts] == \
        ["dnnweaver", "dnnweaver@pe=32x32", "hvx"]
    assert len({a.key for a in arts}) == 3
    # and pair items hit the same cache entries as direct compiles
    assert repro.compile("DLRM-FC4", "dnnweaver@pe=32x32") is arts[1]


# ---------------------------------------------------------------------------
# spec validation
# ---------------------------------------------------------------------------


def test_validate_spec_names_every_problem():
    bad = acg_spec(
        "bad",
        memories=[smem("M", 8, 1, 0), smem("M", 8, 1, 64)],  # dup + depth=0
        computes=[scu("CU", [scap("GEMM", sop("q8", 4), [sop("i8", 4)])])],
        edges=[sedge("M", "GHOST", 0)],
    )
    problems = validate_spec(bad, raise_on_error=False)
    text = "\n".join(problems)
    assert "duplicate node name(s): ['M']" in text
    assert "depth must be positive" in text
    assert "unknown dtype 'q8'" in text
    assert "unknown node 'GHOST'" in text
    assert "bandwidth must be positive" in text
    with pytest.raises(SpecError, match="invalid covenant spec 'bad'"):
        validate_spec(bad)


def test_validate_spec_names_bad_dimension_types():
    """Hand-authored JSON with string dims must get a named problem, not a
    TypeError from the comparison."""
    d = targets.EXAMPLE_SPEC.to_dict()
    d["computes"][0]["capabilities"][0]["outputs"][0] = ["i16", "1"]
    problems = validate_spec(ACGSpec.from_dict(d), raise_on_error=False)
    assert any("non-positive or non-integer dimension" in p
               for p in problems)


def test_scap_promotes_bare_operands_on_both_sides():
    k = scap("RELU", sop("i16", 1), sop("i16", 1))
    assert k.outputs == (("i16", 1),) and k.inputs == (("i16", 1),)


def test_register_spec_alias_renames_for_variant_resolution():
    """Registering under an alias renames the spec so canonical derived
    names ('alias@k=v') resolve."""
    spec = targets.get_spec("hvx").derive(name="mychip")
    registered = repro.targets.register(spec, name="alias_chip")
    try:
        assert registered.name == "alias_chip"
        v = targets.get_spec("alias_chip@issue_slots=1")
        assert v.name == "alias_chip@issue_slots=1"
        assert targets.get_target(v.name).issue_slots == 1
    finally:
        targets.TARGETS.pop("alias_chip", None)


def test_validate_spec_mnemonic_checks():
    from repro.core.spec import FieldSpec, MnemonicSpec
    spec = dataclasses.replace(
        targets.HVX_SPEC,
        mnemonics=targets.HVX_SPEC.mnemonics + (
            MnemonicSpec("XFER", 0x40, ()),              # duplicate name
            MnemonicSpec("TINY", 0x01, (                  # opcode collision
                FieldSpec("E", 1, ("a", "b", "c")),)),    # enum overflow
        ))
    problems = validate_spec(spec, raise_on_error=False)
    text = "\n".join(problems)
    assert "duplicate mnemonic 'XFER'" in text
    assert "collides" in text
    assert "enumerates 3 values in 1 bits" in text


def test_validate_acg_reachability():
    g = ACG("island")
    g.add_memory("M", 32, 1, 64, offchip=True)
    g.add_compute("CU", [scap_obj()])
    problems = validate_acg(g, raise_on_error=False)
    assert any("connected to no edge" in p for p in problems)
    assert any("unreachable from the operand home" in p for p in problems)


def scap_obj():
    from repro.core.acg import cap, ospec
    return cap("ADD", ospec("i32", 4), [ospec("i32", 4)] * 2)


def test_validate_bundled_reports_instead_of_crashing():
    """The CI reporter must emit FAIL lines for a broken bundled spec and
    keep going, never traceback on the first problem."""
    import repro.targets as facade

    broken = dataclasses.replace(targets.HVX_SPEC, issue_slots=0)
    facade.BUNDLED_SPECS["aa_broken"] = broken
    targets.TARGETS["aa_broken"] = lambda: ACG.from_spec(broken)
    lines = []
    try:
        problems = facade.validate_bundled(sweep=False, emit=lines.append)
    finally:
        facade.BUNDLED_SPECS.pop("aa_broken", None)
        targets.TARGETS.pop("aa_broken", None)
    assert problems >= 1
    assert any(l.startswith("FAIL aa_broken") and "issue_slots" in l
               for l in lines)
    assert any(l.startswith("ok   hvx") for l in lines)  # kept going


# ---------------------------------------------------------------------------
# covenant diagnostics: named errors, not deep KeyErrors
# ---------------------------------------------------------------------------


def _codelet_with_capability(capname: str) -> Codelet:
    c = Codelet(f"uses_{capname.lower()}")
    x = c.inp("x", [8], "i32")
    o = c.out("y", [8], "i32")
    op = Compute(capname, ref(o, v("n")), (ref(x, v("n")),),
                 roles={"n": ["n"]}, dtype=dt("i32"))
    c.body.append(Loop("n", 0, 8, 1, [op]))
    return c


def test_unknown_capability_is_named():
    with pytest.raises(CovenantError) as ei:
        repro.compile(_codelet_with_capability("FFT"), "hvx", cache=False)
    err = ei.value
    assert err.cdlt_name == "uses_fft" and err.acg_name == "hvx"
    (viol,) = err.violations
    assert viol.kind == "capability" and viol.subject == "FFT"
    assert "no compute node" in viol.message
    assert "GEMM" in viol.hint  # lists what the target does support


def test_missing_mnemonic_is_named():
    spec = dataclasses.replace(
        targets.HVX_SPEC, name="hvx_nomac",
        mnemonics=tuple(m for m in targets.HVX_SPEC.mnemonics
                        if m.name != "MAC"))
    with pytest.raises(CovenantError) as ei:
        repro.compile(library.gemm(8, 16, 12, in_dtype="u8"),
                      ACG.from_spec(spec), cache=False)
    viols = ei.value.violations
    assert any(v.kind == "mnemonic" and v.subject == "MAC" for v in viols)


def test_missing_transfer_mnemonic_is_named():
    spec = dataclasses.replace(
        targets.HVX_SPEC, name="hvx_noxfer",
        mnemonics=tuple(m for m in targets.HVX_SPEC.mnemonics
                        if m.name != "XFER"))
    viols = check_covenant(library.gemm(4, 8, 4, in_dtype="u8"),
                           ACG.from_spec(spec), raise_on_error=False)
    assert any(v.kind == "mnemonic" and v.subject == "XFER" for v in viols)


def test_undersized_memory_is_named():
    tiny = targets.HVX_SPEC.derive(
        name="hvx_tinyvrf",
        memories={"VRF": {"data_width": 8, "banks": 1, "depth": 16}})
    with pytest.raises(CovenantError) as ei:
        repro.compile(library.gemm(8, 16, 12, in_dtype="u8"),
                      ACG.from_spec(tiny), cache=False)
    viols = [v for v in ei.value.violations if v.kind == "memory"]
    assert viols and viols[0].subject == "VRF"
    assert "cannot hold one" in viols[0].message
    assert "grow VRF" in viols[0].hint


def test_covenant_clean_on_every_bundled_target():
    for name in targets.BUNDLED_SPECS:
        acg = targets.get_target(name)
        assert validate_acg(acg, raise_on_error=False) == []
        assert check_covenant(library.gemm(8, 16, 12, in_dtype="u8"), acg,
                              raise_on_error=False) == []
