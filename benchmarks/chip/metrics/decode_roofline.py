"""decode_roofline: the covenant_decode_attention calls' share of their
roofline, in %: the Pallas flash decode with the reshapes around it."""
from metrics import roofline_share


def read(r):
    return roofline_share(r, "decode")
