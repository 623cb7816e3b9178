"""Host time per step launching the fused step (span ``kws.launch``).

``process_audio``: the call of the jitted ``kws_audio_step`` (argument
handling and the asynchronous dispatch) and the rebinding of the
session's state to its outputs.

Read from the engine report's ``host_ms_per_step`` (``bench/spans.py``).
Moves ``rt_streams``.
"""
from bench import spans


def read(rec):
    return spans.ms_per_step(rec, "kws.launch")
