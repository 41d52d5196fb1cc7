"""The one traffic generator: turns a mix's parameters (``traffic/<mix>.json``)
and the seed into what a pass kind needs.  Every seed gets the same set of
sizes, in another order, so that the seed changes the values and not the
amount of work."""
from __future__ import annotations

import json
import os

import numpy as np

from common import host_rng

DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "traffic")


def load(name: str) -> dict:
    with open(os.path.join(DIR, f"{name}.json")) as f:
        return json.load(f)


def decode_lengths(traffic: dict, seed: int) -> np.ndarray:
    """The context length of each sequence at the first pass: ``batch``
    points spread evenly over [len_min, len_max], permuted by the seed."""
    b, lo, hi = traffic["batch"], traffic["len_min"], traffic["len_max"]
    if not 0 < lo <= hi < traffic["cache_slots"] - 2:
        raise ValueError(f"lengths [{lo}, {hi}] do not fit "
                         f"{traffic['cache_slots']} slots")
    even = lo + np.floor((np.arange(b) + 0.5) * (hi - lo) / b)
    return host_rng(seed, "lengths").permutation(even).astype(np.int32)


def decode_lengths_at(lens0: np.ndarray, i: int, slots: int) -> np.ndarray:
    """The lengths before pass i: each pass appends one token, and a
    length that would reach ``slots`` goes back to its first value."""
    return (lens0 + i % (slots - lens0)).astype(np.int32)
