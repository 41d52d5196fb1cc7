"""Per-layer metrics, one reader each (``<name>.py``), found by the metric's
name in ``BENCHMARK.json``.  A reader takes the ``xplane.Reading`` of a
traced window and returns the metric, or None where the window holds
nothing for it to read; it never returns 0 for a share it could not
read."""


def roofline_share(r, family: str):
    """The least time the chip could take for ``family``'s calls in the
    window (``counts.roofline_s`` summed over them) over the device time of
    those calls, in %."""
    spent = r.call_s.get(family, 0.0)
    if family not in r.work or spent <= 0:
        return None
    return 100.0 * r.work[family]["roofline_s"] / spent
