"""The program's host spans as the benchmark reads them.

The serving engine's report (``PipelinedEngine.report()``) carries
``host_ms_per_step``: host milliseconds per step in each of the
program's spans (``repro/launch/spans.py`` lists them) plus
``untraced``, the steady-state step less the phases.  A program that
predates the spans has no such key; its readings are then absent.
"""


def ms_per_step(rec, name: str) -> float | None:
    """Host ms per step in span ``name`` (or ``"untraced"``), or None
    when the run stepped nothing or its report has no such entry."""
    r = rec.served["report"]
    host = r.get("host_ms_per_step") or {}
    if not r.get("steps") or name not in host:
        return None
    return host[name]
