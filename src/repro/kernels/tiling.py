"""Covenant -> Pallas bridge: the paper's Algorithm-1 tiler selects the
BlockSpec geometry for our TPU kernels (DESIGN.md §3, deviation D1).

The TPU-v5e ACG models the VMEM a kernel may fill (a third of the scoped
limit it asks for, see ``core/targets.py``) and the MXU's (128,128,128)
GEMM capability.  ``gemm_blocks`` runs the Covenant pipeline (placement,
compute mapping, Algorithm-1 tiling enumeration + cost-based selection) on
a GEMM codelet of the requested problem size and returns the chosen tile
as Pallas block sizes.  The paper's alignment rule — "data chunks are divisible by the
size of an addressable element" (§2.1.1) — becomes the (8,128) / MXU-128
alignment filter applied to the candidate set.
"""
from __future__ import annotations

import functools
import math

from repro.core import library, scheduler, targets
from repro.core.scheduler import enumerate_tilings, plan_operands

# MXU systolic dims / VPU lane layout on TPU v5e
MXU = 128
SUBLANE = 8
# rows of one (sublane, lane) tile of a bf16 array: two values a sublane
BF16_SUBLANE = 16


def _align_score(t: dict[str, int], dims: dict[str, int]) -> tuple:
    """Prefer MXU-aligned tiles (multiples of 128 on m/n/k, 8 on m)."""
    def sc(var, unit):
        v = t.get(var, 1)
        full = dims[var]
        if v % unit == 0 or v == full:
            return 0
        return 1
    return (sc("n", MXU) + sc("k", MXU) + sc("m", SUBLANE),)


@functools.lru_cache(maxsize=512)
def gemm_blocks(m: int, n: int, k: int, in_dtype: str = "bf16",
                acc_dtype: str = "f32") -> tuple[int, int, int]:
    """(block_m, block_n, block_k) for an (m,n,k) GEMM, chosen by the
    Covenant tiler against the TPU-v5e ACG."""
    acg = targets.tpu_v5e_acg()
    cdlt = library.gemm(m, n, k, in_dtype=in_dtype, acc_dtype=acc_dtype,
                        name=f"tpugemm_{m}x{n}x{k}")
    scheduler.place_operands(cdlt, acg)
    scheduler.map_compute(cdlt, acg, vectorize=True)
    plans = plan_operands(cdlt, acg)
    cands = enumerate_tilings(cdlt, acg, plans, max_candidates=6000)
    if not cands:
        cands = enumerate_tilings(cdlt, acg, plans, max_candidates=6000,
                                  pad_align=True)
    dims = {"m": m, "n": n, "k": k}
    best, best_key = None, None
    for t in cands:
        cost = scheduler.estimate_tiling_cost(cdlt, acg, plans, t)
        key = (_align_score(t, dims), cost)
        if best_key is None or key < best_key:
            best, best_key = t, key
    assert best is not None, f"no tiling for GEMM {m}x{n}x{k}"
    bm, bn, bk = best.get("m", m), best.get("n", n), best.get("k", k)
    # clamp to hardware-friendly minima (grid blocks must tile the padded
    # problem; ops.py pads to these multiples)
    bm = max(SUBLANE, min(bm, m if m % SUBLANE == 0 else _round_up(m, SUBLANE)))
    bn = min(_round_up(bn, MXU), _round_up(n, MXU))
    bk = min(_round_up(bk, MXU), _round_up(k, MXU))
    return bm, bn, bk


def grouped_gemm_blocks(rows: int, n: int, k: int) -> tuple[int, int, int]:
    """(block_m, block_n, block_k) for a grouped bf16 GEMM (``grouped_matmul``)
    whose groups hold about ``rows`` rows each: the tiler's choice for one
    group's (rows, n, k) GEMM, rows first rounded up to a whole bf16 tile
    of rows.  block_m is a multiple of that tile, so that padding each group
    to block_m keeps every row block aligned."""
    bm, bn, bk = gemm_blocks(_round_up(rows, BF16_SUBLANE), n, k)
    return _round_up(bm, BF16_SUBLANE), bn, bk


def _round_up(x: int, unit: int) -> int:
    return max(unit, math.ceil(x / unit) * unit)


def attention_blocks(seq_q: int, seq_k: int, head_dim: int,
                     ) -> tuple[int, int]:
    """(block_q, block_kv) for flash attention: the Covenant tiler sizes the
    q/k tiles via the equivalent QK^T GEMM (m=seq_q, n=seq_k, k=head_dim)."""
    bm, bn, _ = gemm_blocks(seq_q, seq_k, max(head_dim, MXU))
    bq = min(_round_up(bm, MXU), _round_up(seq_q, MXU)) if seq_q >= MXU \
        else _round_up(seq_q, SUBLANE)
    bkv = min(_round_up(bn, MXU), _round_up(seq_k, MXU))
    # keep combined working set within a conservative VMEM slice: the flash
    # inner block materialises (bq, bkv) logits + (bq, d) accumulators
    bq = min(bq, 4 * MXU)
    while bq * bkv > 256 * 1024 and bkv > MXU:
        bkv //= 2
    return bq, bkv


__all__ = ["BF16_SUBLANE", "MXU", "SUBLANE", "attention_blocks", "gemm_blocks",
           "grouped_gemm_blocks"]
