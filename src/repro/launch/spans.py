"""Host spans of a serving step: the program's one timing mechanism.

A span names one phase of a serving step's host work.  Entering it opens
a ``jax.profiler.TraceAnnotation`` — an event on the profiler's host
line, on the same clock as the device planes, so a trace reduction can
say which phase the host was in while the device sat idle — and, while
an engine step is open on this thread, adds the time spent (on the
engine's injectable clock) to that engine's ``Recorder``.
``PipelinedEngine.report()`` turns the recorder into
``host_ms_per_step``.

The spans, where they are entered, and the per-layer benchmark metric
(``bench/metrics/<name>.py``) that reads each:

  kws.step      ``PipelinedEngine.begin()`` … ``end()``: the whole step,
                a ``StepTraceAnnotation`` numbered by step index; the
                only span that encloses others            (``step_ms``)
  kws.assemble  loop drivers: the step's block — per-slot row copies
                into the engine's staging ring, fault injection
                                                          assemble_ms_per_step
  kws.police    ``StreamingKwsSession._coerce_audio``: decode, dtype
                cast, non-finite check                    police_ms_per_step
  kws.frame     ``process_audio``: join of a carried remainder, frame
                slicing                                   frame_ms_per_step
  kws.h2d       ``process_audio``: ``jnp.asarray`` of the block, the
                host→device put                           h2d_ms_per_step
  kws.launch    ``process_audio``: the fused step's call and the state
                rebinding                                 launch_ms_per_step
  kws.heal      ``_maybe_heal`` / ``heal``: the supervisor's blocking
                health fetch and its resets               (report only)
  kws.fetch     ``PipelinedEngine``: ``np.asarray`` of a step's result
                                                          fetch_ms_per_step
  kws.slots     loop drivers: the slot-axis array bookkeeping —
                incarnation ids at admission, real frames, eviction
                (``evict_many``) at dispatch — and a drained step's
                tally (one ``bincount``) or fire decode
                (``fires_from_event_block``)              slots_ms_per_step
  kws.admit     ``SlotScheduler.admit``: the whole admission wave
                computed in one pass from per-shard free lists,
                and its batched ``reset_streams``         admit_ms_per_step

``untraced`` in the report (``untraced_ms_per_step``) is the steady-state
step less the phases: what no span covers.

Phases — every span but ``kws.step`` — are disjoint on a thread's
timeline: opening one while another is open raises ``SpanNestingError``,
so no time is counted twice.  A phase entered outside an engine step is
traced but not counted, which keeps the counted phases inside the
steady-state window the report divides them by.  Each span is entered a
bounded number of times per step and never once per slot: it costs about
a microsecond, and there is no switch to turn it off.
"""
from __future__ import annotations

import threading
import time
from typing import Callable

import jax

STEP = "kws.step"
ASSEMBLE = "kws.assemble"
POLICE = "kws.police"
FRAME = "kws.frame"
H2D = "kws.h2d"
LAUNCH = "kws.launch"
HEAL = "kws.heal"
FETCH = "kws.fetch"
SLOTS = "kws.slots"
ADMIT = "kws.admit"

PHASES = (ASSEMBLE, POLICE, FRAME, H2D, LAUNCH, HEAL, FETCH, SLOTS, ADMIT)
NAMES = (STEP,) + PHASES
# The phases of ``process_audio``: the report's historical "dispatch".
DISPATCH = (POLICE, FRAME, H2D, LAUNCH)
UNTRACED = "untraced"


class SpanNestingError(RuntimeError):
    """A phase was opened while another phase was open."""


class Recorder:
    """Seconds and entry counts per span name, on an injectable clock."""

    def __init__(self, clock: Callable[[], float] = time.perf_counter):
        self.clock = clock
        self.reset()

    def reset(self):
        self.seconds = dict.fromkeys(NAMES, 0.0)
        self.entries = dict.fromkeys(NAMES, 0)

    def add(self, name: str, seconds: float):
        self.seconds[name] += seconds
        self.entries[name] += 1


class _Thread(threading.local):
    recorder: Recorder | None = None    # of the step open on this thread
    phase: str | None = None            # the phase open on this thread


_thread = _Thread()


class span:
    """``with span(name):`` — one phase of a step (a name in ``PHASES``).

    A class rather than a generator so that entering it costs little
    more than the ``TraceAnnotation`` itself."""

    __slots__ = ("name", "_annotation", "_recorder", "_t0")

    def __init__(self, name: str):
        if name not in PHASES:
            raise ValueError(f"{name!r} is not a phase: {PHASES}")
        self.name = name

    def __enter__(self):
        if _thread.phase is not None:
            raise SpanNestingError(f"{self.name} opened inside "
                                   f"{_thread.phase}: phases are disjoint")
        _thread.phase = self.name
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._recorder = rec = _thread.recorder
        if rec is not None:
            self._t0 = rec.clock()
        return self

    def __exit__(self, *exc):
        rec = self._recorder
        if rec is not None:
            rec.add(self.name, rec.clock() - self._t0)
        self._annotation.__exit__(*exc)
        _thread.phase = None
        return False


class Step:
    """An open ``kws.step``: while it is open, phases entered on this
    thread are counted in ``recorder``.  ``close`` ends it."""

    __slots__ = ("_annotation", "_recorder", "_outer")

    def __init__(self, recorder: Recorder, index: int):
        if _thread.phase is not None:
            raise SpanNestingError(f"{STEP} opened inside {_thread.phase}")
        self._annotation = jax.profiler.StepTraceAnnotation(STEP,
                                                            step_num=index)
        self._annotation.__enter__()
        self._recorder = recorder
        self._outer = _thread.recorder
        _thread.recorder = recorder

    def close(self, seconds: float | None):
        """End the step; ``seconds`` is its length on the recorder's
        clock, or None to count nothing (a step abandoned)."""
        _thread.recorder = self._outer
        self._annotation.__exit__(None, None, None)
        if seconds is not None:
            self._recorder.add(STEP, seconds)
