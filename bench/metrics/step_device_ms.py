"""Device time of the fused step per step, in ms, averaged over the chips.

The program jits its fused audio step as ``kws_audio_step``, so each
execution is one event named ``jit_kws_audio_step(<id>)`` on a device
plane's "XLA Modules" line.  The reading is the summed length of those
events on each of the cell's chips, over the steps the window
dispatched, averaged over the chips.  The harness starts the trace just
before the window and stops it just after, so every such event is one of
the window's steps.  Only the module lines are read, not the far larger
op lines.  Absent when the trace holds no such module (a program that
jits the step under another name).  Moves ``rt_streams``.
"""
from __future__ import annotations

from bench import trace

MODULE = "jit_kws_audio_step"
MODULE_LINE = "XLA Modules"


def module_seconds(path) -> dict[int, float]:
    """Device index → summed seconds of ``MODULE`` events in the
    ``.xplane.pb`` at ``path``."""
    from jax.profiler import ProfileData
    out: dict[int, float] = {}
    for plane in ProfileData.from_file(str(path)).planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name != MODULE_LINE:
                continue
            for ev in line.events:
                if ev.name.split("(", 1)[0] == MODULE:
                    dev = int(m[1])
                    out[dev] = out.get(dev, 0.0) + ev.duration_ns * 1e-9
    return out


def ms_per_step(seconds: dict[int, float], steps: int,
                chips: int) -> float | None:
    """Mean over the first ``chips`` devices of their module seconds per
    step, in ms; None when no device ran the module."""
    if not seconds or steps <= 0:
        return None
    devices = sorted(seconds)[:chips]
    return 1e3 * sum(seconds[d] for d in devices) / len(devices) / steps


def read(rec):
    # The harness's trace directory for the cell (``harness.run_cell``).
    logdir = rec.cell.root / ".bench_out" / "trace" / rec.cell.name
    try:
        path = trace.find_trace(logdir)
    except FileNotFoundError:
        return None
    return ms_per_step(module_seconds(path), rec.served["steps"],
                       rec.cell.chips)
