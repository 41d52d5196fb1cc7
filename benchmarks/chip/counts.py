"""The yardstick: the work each kernel call needs, counted from its shapes,
and the chip's peaks by ``device_kind``.

Counts follow the algorithm, not the implementation, so that a later change
to a kernel cannot move them:

- GEMM: 2mnk FLOPs; bytes of A, B and C, all in the activation dtype
  (bf16), or for int8 operands 1 byte an element of A and B and 4 of the
  int32 C, judged against the int8 peak.
- Attention: 4 Sq Sk d FLOPs per query head, halved when causal; bytes of
  Q and O at Hq heads and of K and V at Hkv heads (not repeated).
- Decode: 4 d Hq sum(kv_len) FLOPs; bytes of the valid K and V at Hkv
  heads, plus Q and O.
- SSD: the chunked form at a fixed chunk of 256 (Mamba2's default),
  whatever chunk the program picks; B and C at their G groups.
- SSM decode state step: the decay, the rank-1 update and the read-out,
  5 b h n p FLOPs; the f32 state read and written once.
"""
from __future__ import annotations

import json
import os

from common import Call

ACT_BYTES = 2          # bf16 activations and weights
# per dtype of a GEMM's operands: bytes of an element of A and B, of C, and
# the compute peak it is judged against
GEMM_DTYPES = {"bf16": (ACT_BYTES, ACT_BYTES, "bf16_flops"),
               "int8": (1, 4, "int8_ops")}
SSD_REF_CHUNK = 256    # Mamba2's chunk_size

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str) -> dict:
    """The published peaks of ``device_kind``; a kind not in the table is
    an error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE}")
    return table[device_kind]


def roofline_s(call: Call, peak: dict) -> float:
    """The least time the chip could take for ``call``: the larger of its
    FLOPs over its own compute peak and its bytes over the HBM
    bandwidth."""
    return max(call.flops / peak[call.peak],
               call.bytes / peak["hbm_bytes_per_s"])


def gemm(m: int, n: int, k: int, dtype: str = "bf16", batch: int = 1) -> Call:
    """``batch`` independent (m, n, k) GEMMs, each with operands of its
    own; the arguments are a ``Pass.xla_gemms`` shape."""
    ab, c, top = GEMM_DTYPES[dtype]
    return Call("gemm", 2.0 * batch * m * n * k,
                batch * (ab * (m * k + k * n) + c * m * n), top)


def attention(b: int, hq: int, hkv: int, sq: int, sk: int, d: int,
              causal: bool) -> Call:
    flops = 4.0 * sq * sk * d * hq * b
    if causal:
        flops /= 2
    nbytes = ACT_BYTES * b * d * (2 * hq * sq + 2 * hkv * sk)
    return Call("attn", flops, nbytes)


def decode(hq: int, hkv: int, d: int, kv_lens) -> Call:
    total = float(sum(int(x) for x in kv_lens))
    b = len(kv_lens)
    return Call("decode", 4.0 * d * hq * total,
                ACT_BYTES * (2 * hkv * d * total + 2 * b * hq * d))


def ssd(b: int, s: int, h: int, p: int, g: int, n: int) -> Call:
    """Chunked SSD at chunk L = 256 over (b, s, h, p) inputs with (b, s,
    g, n) B/C: per chunk and group C B^T (2 L^2 n); per chunk and head the
    masked product with X (2 L^2 p), the chunk state (2 L n p), the
    state's read-out (2 L n p) and the carry across chunks (2 n p)."""
    L = min(SSD_REF_CHUNK, s)
    nck = -(-s // L)
    flops = b * nck * (g * 2.0 * L * L * n
                       + h * (2.0 * L * L * p + 4.0 * L * n * p + 2.0 * n * p))
    nbytes = (ACT_BYTES * (2 * b * s * h * p + 2 * b * s * g * n)
              + 4 * b * s * h)                         # dt in f32
    return Call("ssd", flops, nbytes)


def ssm_state_step(b: int, h: int, n: int, p: int) -> Call:
    return Call("state", 5.0 * b * h * n * p, 2 * 4.0 * b * h * n * p)
