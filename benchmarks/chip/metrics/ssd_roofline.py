"""ssd_roofline: the covenant_ssd calls' share of their roofline, in %: the
Pallas SSD chunk scan (kernels/ssd_scan.py) with its jnp stages and the
ops.py transposes and B/C repeat."""
from metrics import roofline_share


def read(r):
    return roofline_share(r, "ssd")
