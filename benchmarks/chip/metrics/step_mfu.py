"""step_mfu: the FLOPs that the window's passes need (all their calls, by
the algorithm's count) over the window's length times the chip's bf16
peak, in %."""


def read(r):
    flops = sum(w["flops"] for w in r.work.values())
    if r.window_s <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (r.window_s * r.peak["bf16_flops"])
