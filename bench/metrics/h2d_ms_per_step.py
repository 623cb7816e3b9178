"""Host time per step putting the block on the device (span ``kws.h2d``).

``process_audio``'s ``jnp.asarray`` of the framed block: the host→device
transfer as the host sees it.

Read from the engine report's ``host_ms_per_step`` (``bench/spans.py``).
Moves ``rt_streams``.
"""
from bench import spans


def read(rec):
    return spans.ms_per_step(rec, "kws.h2d")
