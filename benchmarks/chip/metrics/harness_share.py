"""harness_share: device time of the benchmark's own ops over the device's
busy time, in %: the KV and state writes, the SSM decode state step,
the dense blocks' LayerNorms, splits, casts, gates and residual adds that
feed one Covenant call's output to the next.  No change to the program
moves it; it says how much of ``step_ms`` the program under test does not own."""


def read(r):
    if r.busy_s <= 0 or r.harness_s <= 0:
        return None
    return 100.0 * r.harness_s / r.busy_s
