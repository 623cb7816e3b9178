"""The ΔGRU kernel's share of its roofline, in %.

The least time the chip could take for the dense ΔGRU's operations (at the
peak of the configuration's numerics) and bytes over every step of the
window (``bench/costs.py``), divided by the summed device time of the ops
named as the configuration's ΔGRU kernel.  Moves ``rt_streams``.
"""
from bench import costs


def read(rec):
    t = rec.trace.seconds_of(rec.cell.config["kernels"]["gru"])
    if not t:
        return None
    peak = costs.ops_peak(rec.cell.config, rec.peaks)
    return 100.0 * costs.least_time(rec.work.gru, peak, rec.peaks) / t
