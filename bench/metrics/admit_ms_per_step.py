"""Host time per step admitting requests (span ``kws.admit``).

``SlotScheduler.admit``: mapping queued requests onto free slots and the
one batched ``reset_streams`` wave that clears them.

Read from the engine report's ``host_ms_per_step`` (``bench/spans.py``).
Moves ``rt_streams``.
"""
from bench import spans


def read(rec):
    return spans.ms_per_step(rec, "kws.admit")
