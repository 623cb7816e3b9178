"""Host time per step assembling the block (span ``kws.assemble``).

The loop drivers: the block's allocation, the per-slot copy (utterances)
or ``np.stack`` (streams), fault injection.

Read from the engine report's ``host_ms_per_step`` (``bench/spans.py``).
Moves ``rt_streams``.
"""
from bench import spans


def read(rec):
    return spans.ms_per_step(rec, "kws.assemble")
