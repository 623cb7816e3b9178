"""Host time per step policing the audio block (span ``kws.police``).

``StreamingKwsSession._coerce_audio``: integer decode, the float32 copy
and the non-finite scan of every block ``process_audio`` is handed.

Read from the engine report's ``host_ms_per_step`` (``bench/spans.py``).
Moves ``rt_streams``.
"""
from bench import spans


def read(rec):
    return spans.ms_per_step(rec, "kws.police")
