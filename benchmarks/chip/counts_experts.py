"""The work of an expert layer's call (``covenant_experts``), counted from
its shapes as ``counts.py`` counts the other kernels: by the algorithm,
never by what an implementation pads or moves.

For T tokens routed top-k over E experts, of which ``held`` are computed
here, each a SwiGLU expert of width f on a hidden size d:

- FLOPs: the router GEMM over all experts, 2 T d E, and for the routed
  rows the held experts expect, T k held / E of them, the two expert
  GEMMs, 2 (d 2f + f d) each.
- Bytes, in bf16: the weights of the held experts that some token is
  expected to be routed to, held (1 - (1 - k/E)^T) of them, each d 2f +
  f d; the router's weight, d E; and the routed rows in and out, d each.
"""
from __future__ import annotations

from common import Call
from counts import ACT_BYTES


def experts(t: int, d: int, f: int, n_experts: int, held: int,
            top_k: int) -> Call:
    rows = t * top_k * held / n_experts
    per_expert = d * 2 * f + f * d
    touched = held * (1.0 - (1.0 - top_k / n_experts) ** t)
    flops = 2.0 * t * d * n_experts + rows * 2.0 * per_expert
    nbytes = ACT_BYTES * (touched * per_expert + d * n_experts + 2 * rows * d)
    return Call("experts", flops, nbytes)
