"""attn_roofline: the covenant_attention calls' share of their roofline, in
%: the Pallas flash attention with the K/V head repeat around it."""
from metrics import roofline_share


def read(r):
    return roofline_share(r, "attn")
