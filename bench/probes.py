"""The benchmark's own probes around its calls into the program.

``Probe`` stands in for a ``StreamingKwsSession`` in the serving loops:
it forwards every call, wraps each call into the session, scheduler and
result fetch in a ``jax.profiler.TraceAnnotation`` (the host spans the
trace reduction names idle gaps by), counts the fused steps dispatched,
and keeps a reference to every result array the loop fetched, so the
check can compare exactly what the timed path produced.

``CompileCounter`` counts JAX trace and compile events: the harness fails
a run in whose window one happened.

``hidden_values`` reads the slots' hidden state out of a session.
"""
from __future__ import annotations

import numpy as np

SPAN_DISPATCH = "bench.dispatch"       # session.process_audio
SPAN_FETCH = "bench.fetch"             # np.asarray of a step's result
SPAN_ADMIT = "bench.admit"             # SlotScheduler.admit
SPAN_RESET = "bench.reset_streams"     # session.reset_streams
SPAN_WINDOW = "bench.window"           # the measured window
SPANS = (SPAN_DISPATCH, SPAN_FETCH, SPAN_ADMIT, SPAN_RESET)


def hidden_values(session) -> np.ndarray:
    """The slots' hidden state as values, (B, L·H): each layer's ``h``
    side by side, the first layer's first, int16 codes read at 15
    fraction bits.  The session's ``state`` is the one ΔGRU layer's state
    (``h``: (B, H)), or for a stack of L layers a sequence of L such
    states, the first layer's first."""
    state = session.state
    states = [state] if hasattr(state, "h") else list(state)
    h = np.concatenate([np.asarray(s.h) for s in states], axis=-1)
    return h.astype(np.float64) * (2.0 ** -15 if h.dtype.kind == "i" else 1)


def annotation(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class Fetched:
    """A device result array as the engine sees it: its shape without a
    sync, and a fetch (``np.asarray``) inside the fetch span that leaves
    the host copy with the probe."""

    __slots__ = ("_array", "_step", "_sink")

    def __init__(self, array, step: int, sink: dict):
        self._array = array
        self._step = step
        self._sink = sink

    @property
    def shape(self):
        return self._array.shape

    def __array__(self, dtype=None, copy=None):
        with annotation(SPAN_FETCH):
            host = np.asarray(self._array)
        self._sink[self._step] = host
        return host if dtype is None else host.astype(dtype)


class Probe:
    """A session stand-in; ``field`` is the result the loop fetches."""

    def __init__(self, session, field: str):
        self._session = session
        self._field = field
        self.steps = 0
        self.fetched: dict[int, np.ndarray] = {}

    def __getattr__(self, name):
        return getattr(self._session, name)

    def process_audio(self, audio):
        with annotation(SPAN_DISPATCH):
            out = self._session.process_audio(audio)
        wrapped = Fetched(getattr(out, self._field), self.steps, self.fetched)
        self.steps += 1
        return out._replace(**{self._field: wrapped})

    def reset_streams(self, slots):
        with annotation(SPAN_RESET):
            self._session.reset_streams(slots)

    def restart(self):
        """Forget the counts of a warm-up loop."""
        self.steps = 0
        self.fetched = {}


class CompileCounter:
    """Counts JAX tracing/compilation events while ``armed``.

    JAX's monitoring listeners cannot be removed, so one counter is
    registered per process and re-armed per window."""

    _instance = None

    def __init__(self):
        self.armed = False
        self.events: list[str] = []

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            import jax
            inst = cls()
            jax.monitoring.register_event_duration_secs_listener(
                inst._on_duration)
            jax.monitoring.register_event_listener(inst._on_event)
            cls._instance = inst
        return cls._instance

    def _record(self, event: str):
        if self.armed and (event.startswith("/jax/core/compile")
                           or event.startswith("/jax/compilation_cache")):
            self.events.append(event)

    def _on_duration(self, event, duration, **kw):
        self._record(event)

    def _on_event(self, event, **kw):
        self._record(event)

    def arm(self):
        self.events = []
        self.armed = True

    def disarm(self) -> list[str]:
        self.armed = False
        return list(self.events)
