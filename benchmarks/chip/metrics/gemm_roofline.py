"""gemm_roofline: the covenant_matmul calls' share of their roofline, in %:
the Pallas GEMM (kernels/matmul.py) with the pads around it."""
from metrics import roofline_share


def read(r):
    return roofline_share(r, "gemm")
