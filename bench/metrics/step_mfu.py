"""The whole step's share of the chips' peak, in %.

Useful model operations of the decisions served in the window (ADC, FEx,
dense ΔGRU and FC per served frame; ``bench/costs.py``) per second of the
window, over chips × the peak of the configuration's numerics (bf16 for
float32, int8 for int8).  Moves ``rt_streams``.
"""
from bench import costs


def read(rec):
    window = rec.served["window_s"]
    if window <= 0 or rec.work.model_ops <= 0:
        return None
    peak = costs.ops_peak(rec.cell.config, rec.peaks) * rec.cell.chips
    return 100.0 * rec.work.model_ops / window / peak
