"""The ΔLSTM layers above the first: their share of the roofline, in %.

For ``spartus-2l1024-int8`` that is layer 2, the layer whose int8 weights
the kernel holds once as int8 planes because their float32 image does
not fit its VMEM plan.  The same reading as ``gru_upper_roofline``, by
import: its ``upper_work`` (the stack's ``costs.gru_call`` less the same
configuration at one layer, counted for the configuration's cell) at the
peak of the configuration's numerics and HBM bandwidth, over the device
time inside the window of the ops named as the configuration's
``kernels.gru`` (``delta_lstm_seq_int``) whose event metadata carries
the scope ``kws.gru.<l>`` with l ≥ 1 (its xplane scope reader).  Absent
when the trace holds no such op.  Moves ``rt_streams``.
"""
from __future__ import annotations

import pathlib

from bench import spec

UPPER = spec.load_module(
    pathlib.Path(__file__).with_name("gru_upper_roofline.py"),
    "metric gru_upper_roofline")


def read(rec):
    return UPPER.read(rec)
