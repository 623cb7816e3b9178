"""Host time per step in per-slot Python (span ``kws.slots``).

The loop drivers: dispatch-time eviction and vote bookkeeping, new
incarnations for admitted slots, and integrating drained results (vote
tallies, ``fires_from_events``).

Read from the engine report's ``host_ms_per_step`` (``bench/spans.py``).
Moves ``rt_streams``.
"""
from bench import spans


def read(rec):
    return spans.ms_per_step(rec, "kws.slots")
