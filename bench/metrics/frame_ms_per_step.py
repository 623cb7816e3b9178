"""Host time per step framing the audio block (span ``kws.frame``).

``process_audio``: the carried remainder's concatenation in front of the
block and the slicing into whole 16 ms frames.

Read from the engine report's ``host_ms_per_step`` (``bench/spans.py``).
Moves ``rt_streams``.
"""
from bench import spans


def read(rec):
    return spans.ms_per_step(rec, "kws.frame")
