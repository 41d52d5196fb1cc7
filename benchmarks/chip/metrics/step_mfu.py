"""step_mfu: the work that the window's passes need (all their calls, by
the algorithm's count), each call's operations over the chip's peak for
its dtype (bf16 FLOP/s, int8 OP/s), summed and over the window's length,
in %."""


def read(r):
    busy = sum(w["peak_s"] for w in r.work.values())
    if r.window_s <= 0 or busy <= 0:
        return None
    return 100.0 * busy / r.window_s
