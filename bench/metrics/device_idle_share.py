"""Share of the measured window in which no op ran on the device, in %.

From the profiler trace: 1 − (union of device-op intervals) / window,
averaged over the cell's chips.  Moves ``rt_streams``.
"""


def read(rec):
    t = rec.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
