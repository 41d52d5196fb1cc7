"""glue_share: device time of the program's glue over the device's busy
time, in %: the ops that the ``kernels/ops.py`` wrappers put around the
Pallas kernels (pads, repeats, transposes, output slices, and the slice
of a stacked layer's weight that is the call's operand) and the copies
that the compiler adds around the calls.  The harness's own ops are
``harness_share``'s."""


def read(r):
    if r.busy_s <= 0:
        return None
    return 100.0 * r.glue_s / r.busy_s
