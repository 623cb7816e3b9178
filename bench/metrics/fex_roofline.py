"""The FEx kernel's share of its roofline, in %.

The least time the chip could take for the FEx's bytes over every step of
the window (``bench/costs.py``; no vector-unit peak is published, so the
bound is HBM bandwidth), divided by the summed device time of the ops
named as the configuration's FEx kernel.  Moves ``rt_streams``.
"""
from bench import costs


def read(rec):
    t = rec.trace.seconds_of(rec.cell.config["kernels"]["fex"])
    if not t:
        return None
    return 100.0 * costs.least_time(rec.work.fex, None, rec.peaks) / t
