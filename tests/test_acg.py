"""ACG structure: node/edge semantics, capability lookup, mnemonic encoding."""
import pytest

from repro.core import targets
from repro.core.acg import ACG, Mnemonic, cap, efield, ifield, ospec
from repro.core.dtypes import dt


@pytest.mark.parametrize("name", sorted(targets.TARGETS))
def test_targets_construct(name):
    g = targets.get_target(name)
    assert g.memory_nodes() and g.compute_nodes()
    assert g.describe()


def test_memory_attributes_match_paper_example():
    g = targets.example_acg()
    gsp = g.memory("GSP")
    # §2.1.1: 32 x 7 = 224-bit entries; 224 x 1024 = 229,376 bits = 28,672 B
    assert gsp.elem_bits == 224
    assert gsp.capacity_bits == 229_376
    assert gsp.capacity_bytes == 28_672


def test_dnnweaver_table3_attributes():
    g = targets.dnnweaver_acg()
    assert g.memory("WBUF").banks == 4096
    assert g.memory("IBUF").data_width == 8
    sy = g.compute("SYSTOLIC")
    gemms = sy.find("GEMM", dt("i32"))
    assert gemms and gemms[0].geometry == (1, 64, 64)
    # OBUF -> DRAM unidirectional; no DRAM -> OBUF edge
    assert g.edge("OBUF", "DRAM")
    with pytest.raises(KeyError):
        g.edge("DRAM", "OBUF")


def test_hvx_has_no_dram_node():
    # §5.1.1: HVX DRAM is hardware-managed, hence absent from the ACG
    g = targets.hvx_acg()
    assert "DRAM" not in g.nodes
    assert g.issue_slots == 4  # VLIW


def test_supporting_nodes_sorted_by_granularity():
    g = targets.example_acg()
    nodes = g.supporting_nodes("ADD", dt("i16"))
    grans = [c.out_elems for _, c in nodes]
    assert grans == sorted(grans, reverse=True)
    assert nodes[0][0].name == "VECTOR"  # 2-wide beats scalar


def test_highest_memory_is_offchip_home():
    g = targets.example_acg()
    assert g.highest_memory().name == "DRAM"
    g2 = targets.hvx_acg()
    assert g2.highest_memory().name == "L2"


def test_shortest_path_respects_direction():
    g = targets.dnnweaver_acg()
    p = g.shortest_path("DRAM", "SYSTOLIC")
    assert p[0] == "DRAM" and p[-1] == "SYSTOLIC"
    # the output path must leave through OBUF
    p2 = g.shortest_path("SYSTOLIC", "DRAM")
    assert "OBUF" in p2


def test_edge_transfer_ops():
    g = targets.example_acg()
    e = g.edge("DRAM", "GSP")
    assert e.transfer_ops(224) == 1
    assert e.transfer_ops(225) == 2
    assert e.transfer_ops(1) == 1


def test_mnemonic_field_encoding_roundtrip():
    g = targets.example_acg()
    mdef = g.mnemonics["ADD"]
    m = Mnemonic(mdef, {"SRC1_ADDR": 12, "SRC2_ADDR": 40, "DST_ADDR": 64,
                        "N": 2, "TGT": "VECTOR"})
    word = m.encode()
    assert isinstance(word, int) and word > 0
    # decode by shifting back out
    fields = list(mdef.fields)
    vals = {}
    for f in reversed(fields):
        vals[f.name] = word & ((1 << f.bits) - 1)
        word >>= f.bits
    assert word == mdef.opcode
    assert vals["SRC1_ADDR"] == 12 and vals["N"] == 2
    assert mdef.field("TGT").enum[vals["TGT"]] == "VECTOR"


def test_mnemonic_field_overflow_rejected():
    g = targets.example_acg()
    mdef = g.mnemonics["ADD"]
    m = Mnemonic(mdef, {"SRC1_ADDR": 1 << 40, "SRC2_ADDR": 0, "DST_ADDR": 0,
                        "N": 1, "TGT": "SCALAR"})
    with pytest.raises(ValueError):
        m.encode()


def test_duplicate_node_rejected():
    g = ACG("t")
    g.add_memory("M", 8, 1, 16)
    with pytest.raises(ValueError):
        g.add_memory("M", 8, 1, 16)


def test_capability_str_matches_paper_syntax():
    c = cap("ADD", ospec("i16", 2), [ospec("i16", 2), ospec("i16", 2)])
    assert str(c) == "(i16,2)=ADD((i16,2),(i16,2))"


def test_tpu_v5e_acg_mxu_alignment():
    g = targets.tpu_v5e_acg()
    mxu = g.compute("MXU")
    gemm = mxu.find("GEMM", dt("f32"))[0]
    assert gemm.geometry == (128, 128, 128)
    vmem = g.memory("VMEM")
    # addressable element = one (8,128) f32 tile = 4096 B
    assert vmem.elem_bits // 8 == 4096
    # a third of the kernels' scoped VMEM limit: every window is
    # double-buffered and Mosaic keeps a working copy besides
    limit = targets.TPU_V5E["vmem_limit_bytes"]
    assert limit - 3 * vmem.elem_bits // 8 < 3 * vmem.capacity_bytes <= limit
