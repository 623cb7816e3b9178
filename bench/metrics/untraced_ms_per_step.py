"""Host time per step that no program span covers.

The engine's steady-state step less the sum of its phases (every span
but ``kws.step``): loop overhead and whatever a later change leaves
outside the spans.  Near zero when the spans cover the step.

Read from the engine report's ``host_ms_per_step`` (``bench/spans.py``).
Moves ``rt_streams``.
"""
from bench import spans


def read(rec):
    return spans.ms_per_step(rec, "untraced")
