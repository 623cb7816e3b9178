"""The ΔGRU kernel's share of its roofline, in %.

The least time the chip could take for the dense recurrent stack's
operations (at the peak of the configuration's numerics) and bytes over
every step of the window (``bench/costs.py``, which counts them for the
configuration's cell, ΔGRU or ΔLSTM), divided by the summed device time of
the ops named as the configuration's ``kernels.gru``: the stack's
recurrent kernel, whatever its cell.  Moves ``rt_streams``.
"""
from bench import costs


def read(rec):
    t = rec.trace.seconds_of(rec.cell.config["kernels"]["gru"])
    if not t:
        return None
    peak = costs.ops_peak(rec.cell.config, rec.peaks)
    return 100.0 * costs.least_time(rec.work.gru, peak, rec.peaks) / t
