"""Pallas TPU kernels scheduled by the Covenant tiler (DESIGN.md §3).

``ops`` is the public API (padding + Covenant BlockSpecs; interpret mode
only when asked for); ``ref`` holds the pure-jnp oracles every kernel is tested
against; ``tiling`` is the Algorithm-1 -> BlockSpec bridge.
"""
from . import (flash_attention, grouped_matmul, matmul, ops, ref, ssd_scan,
               tiling)
from .ops import (covenant_attention, covenant_decode_attention,
                  covenant_experts, covenant_matmul, covenant_ssd)

__all__ = ["covenant_attention", "covenant_decode_attention",
           "covenant_experts", "covenant_matmul", "covenant_ssd",
           "flash_attention", "grouped_matmul", "matmul", "ops", "ref",
           "ssd_scan", "tiling"]
