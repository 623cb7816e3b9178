"""Async pipelined serving engine: overlap host work with device steps.

The synchronous serve loops in ``launch/serve.py`` run every phase of a
step back to back — assemble the host block, dispatch the fused device
step, BLOCK on ``np.asarray(out.votes)``, then do the bookkeeping — so
audio ingest, device compute and result readback never overlap and the
fleet runs at the speed of the host's slowest phase.  JAX dispatch is
asynchronous on every backend: ``process_audio`` returns DEVICE arrays
immediately, their SHAPES are known without a sync, and only the
``np.asarray`` fetch blocks.  ``PipelinedEngine`` exploits exactly that:

    step t-1  ··· fetch ─┐                      (drain: mostly a copy)
    step t    ───────────┼─── computing on device
    step t+1  ─ assemble ┘    (admissions, faults, audio slicing, host)

While step *t* computes on device, the host assembles the block for
step *t+1* and drains step *t−1*'s votes via a fetch that by then is
(mostly) a copy, keeping up to ``depth`` steps in flight.

Bit-identity contract (DESIGN.md §14): the engine issues device
operations in EXACTLY the order the synchronous loop does — per-step
pieces, then fault/churn resets, then admission resets — and every
scheduling decision in the serve loops (eviction at ``chunks_per_utt``,
admission order, churn-storm restarts) depends only on chunk COUNTS,
which are known at dispatch time from device-array shapes.  Only the
vote VALUES arrive late, and they are tallied when their step drains
by stream *incarnation* id (a row of ``_VoteTable``, one per life on a
slot) so a slot recycled mid-flight never pollutes its predecessor's
tally.  ``depth=1`` IS the synchronous loop (dispatch, then
immediately drain); the conformance suite in
tests/test_engine.py proves ``depth>=2`` equal to ``depth=1`` decision
for decision and counter for counter, in float and int8, under churn
storms, fault plans and mesh>1.

SLO telemetry: the engine tracks p50/p99/p99.9 step and end-to-end
decision latency (assemble start → results host-visible), the
scheduler's shard-occupancy imbalance and admission waves
(``admission``: waves, slots, mean wave), and where a step's host time
goes.  Every step is a ``kws.step`` span; the phases inside it are the
program's host spans (``launch/spans.py`` lists them and where each is
entered), timed into the engine's span recorder.  Everything runs on an
injectable ``clock`` so the math is testable with a fake clock.
``report()["host_ms_per_step"]`` gives ms per step for every span plus
``untraced`` (the steady-state step less the phases);
``host_blocked_ms_per_step`` keeps its historical keys as sums of spans
(assemble; dispatch = police + frame + h2d + launch; fetch; total).
``report()`` feeds ``StreamSummary.slo`` and ``BENCH_serve.json``.

Staging ring: the loop drivers assemble each step's block into
``PipelinedEngine.staging`` — ``depth`` host buffers, allocated on first
use and again only when the block's shape or dtype changes, so a step
writes its audio once into memory that is already mapped.  A buffer is
handed out again only after the step that read it has been fetched (a
fetched result means the executable, and so its input, is done).
``report()`` counts the ring's ``staging_allocated`` and
``staging_reused`` blocks, beside the session's ``frame_joins`` (steps
that had to join a carried sample remainder; 0 for frame-aligned
chunks, where the block reaches the device with no further copy).
"""
from __future__ import annotations

import time
from typing import Any, Callable, Sequence

import numpy as np

from repro.launch import spans

__all__ = ["PipelinedEngine", "FetchedStep", "percentiles_ms",
           "warm_session", "run_audio_requests", "run_continuous_detect"]


def percentiles_ms(samples_s: Sequence[float]) -> dict:
    """p50/p99/p99.9 of a latency sample list, seconds in → ms out.
    Empty input reports zeros (a run that never stepped)."""
    if not len(samples_s):
        return {"p50": 0.0, "p99": 0.0, "p999": 0.0}
    ms = np.asarray(samples_s, np.float64) * 1e3
    return {"p50": float(np.percentile(ms, 50)),
            "p99": float(np.percentile(ms, 99)),
            "p999": float(np.percentile(ms, 99.9))}


def _aligned_empty(shape, dtype, align: int = 64) -> np.ndarray:
    """``np.empty`` starting on an ``align``-byte boundary.  On CPU,
    ``jnp.asarray`` shares such a buffer instead of copying it, so the
    device reads the staging block itself — and a block rewritten too
    early would show in the CPU tests."""
    nbytes = int(np.prod(shape)) * dtype.itemsize
    raw = np.empty(nbytes + align, np.uint8)
    off = -raw.ctypes.data % align
    return raw[off:off + nbytes].view(dtype).reshape(shape)


class FetchedStep:
    """One drained pipeline step: host-visible arrays + dispatch metadata.

    ``arrays`` holds the fetched numpy array per piece dispatched for
    the step, in dispatch order; ``piece_frames`` the per-piece frame
    counts; ``meta`` whatever the driver attached at ``submit`` time
    (e.g. the per-slot vote contributions decided at dispatch)."""

    __slots__ = ("index", "arrays", "piece_frames", "n_frames", "meta")

    def __init__(self, index, arrays, piece_frames, meta):
        self.index = index
        self.arrays = arrays
        self.piece_frames = piece_frames
        self.n_frames = sum(piece_frames)
        self.meta = meta


class _InFlight:
    __slots__ = ("index", "outs", "piece_frames", "meta", "t_begin")

    def __init__(self, index, outs, piece_frames, meta, t_begin):
        self.index = index
        self.outs = outs
        self.piece_frames = piece_frames
        self.meta = meta
        self.t_begin = t_begin


class PipelinedEngine:
    """Double-buffered host↔device pipeline around a streaming session.

    Drivers use it as::

        eng = PipelinedEngine(sess, depth=2, field="votes", scheduler=sched)
        while serving:
            eng.begin()                     # the kws.step span opens
            with spans.span(spans.ASSEMBLE):
                block = eng.staging(shape)  # fill every row (host work)
            _, drained = eng.submit([block], meta=...)   # dispatch + drain
            with spans.span(spans.SLOTS):
                ...                         # dispatch-time bookkeeping
                for f in drained: integrate(f)   # from ~depth steps ago
            eng.end()                       # step wall-clock sample
        for f in eng.flush(): integrate(f)
        sess.attach_slo(eng.report())

    ``depth`` bounds the in-flight window: after ``submit`` returns, at
    most ``depth - 1`` steps remain unfetched, so ``depth=1`` fetches
    the step it just dispatched — the synchronous loop, same code path.
    ``field`` names the result attribute fetched per piece ("votes" for
    the utterance loop, "events" for detect/cascade).  ``scheduler``
    (optional) is sampled at every ``end()`` for shard-occupancy
    imbalance, and its admission waves since the engine's start (or
    ``reset_telemetry``) are reported.  ``clock`` is injectable for
    fake-clock telemetry tests; the span recorder (``spans``) times on
    it too.
    """

    def __init__(self, session, *, depth: int = 2, field: str = "votes",
                 scheduler=None,
                 clock: Callable[[], float] = time.perf_counter):
        if depth < 1:
            raise ValueError(f"pipeline depth must be >= 1, got {depth}")
        self.session = session
        self.depth = depth
        self.field = field
        self._sched = scheduler
        self._clock = clock
        self._queue: list[_InFlight] = []
        self._index = 0
        self._t_begin: float | None = None
        self._t_first: float | None = None
        self._t_last_end: float | None = None
        self.spans = spans.Recorder(clock)  # host seconds per span
        self._step_span: spans.Step | None = None
        self._step_s: list[float] = []
        self._e2e_s: list[float] = []
        self._imbalance: list[int] = []
        self._admitted_before = self._admission_counts()
        self.decisions = 0
        self._ring: list[np.ndarray | None] = [None] * depth
        self.staging_allocated = 0          # ring blocks allocated
        self.staging_reused = 0             # ring blocks handed out again

    # ------------------------------------------------------- phases --

    def staging(self, shape, dtype=np.float32) -> np.ndarray:
        """The host block for the step about to be submitted: take one
        per step, before ``submit``, and write every element of it (it
        holds what an earlier step left).

        Ring slot ``step % depth``: after the previous ``submit`` at
        most ``depth - 1`` steps are unfetched, so the step that last
        read this slot (``depth`` steps ago) has been fetched.  A new
        shape or dtype drops the whole ring (buffers still in flight
        stay alive with the steps that hold them)."""
        shape, dtype = tuple(shape), np.dtype(dtype)
        i = self._index % self.depth
        buf = self._ring[i]
        if buf is not None and buf.shape == shape and buf.dtype == dtype:
            self.staging_reused += 1
            return buf
        if buf is not None:
            self._ring = [None] * self.depth
        self._ring[i] = buf = _aligned_empty(shape, dtype)
        self.staging_allocated += 1
        return buf

    def begin(self):
        """Open a step (the ``kws.step`` span): the phases entered until
        ``end()`` are counted as the step's."""
        if self._step_span is not None:     # begun again without end()
            self._step_span.close(None)
        self._t_begin = self._clock()
        if self._t_first is None:
            self._t_first = self._t_begin
        self._step_span = spans.Step(self.spans, self._index)

    def submit(self, pieces, meta: Any = None
               ) -> tuple[list[int], list[FetchedStep]]:
        """Dispatch one step's pieces and drain anything beyond depth.

        Returns ``(piece_frames, drained)``: the per-piece completed
        frame counts — available WITHOUT a sync, from the device
        arrays' shapes — and the fetched steps that fell out of the
        pipeline window, oldest first.  ``meta`` may be a mutable
        container the driver fills AFTER submit returns (dispatch-time
        bookkeeping); it is carried by reference and handed back on the
        step's ``FetchedStep``.
        """
        t_begin = self._t_begin if self._t_begin is not None else self._clock()
        outs = [self.session.process_audio(p) for p in pieces]
        piece_frames = [int(getattr(o, self.field).shape[0]) for o in outs]
        self.decisions += sum(piece_frames) * self.session.batch
        self._queue.append(_InFlight(self._index, tuple(outs), piece_frames,
                                     meta, t_begin))
        self._index += 1
        drained = []
        while len(self._queue) > self.depth - 1:
            drained.append(self._fetch_oldest())
        return piece_frames, drained

    def end(self):
        """Close the step: sample wall time and scheduler imbalance."""
        if self._t_begin is None:
            return
        now = self._clock()
        self._step_s.append(now - self._t_begin)
        self._step_span.close(now - self._t_begin)
        self._step_span = None
        self._t_last_end = now
        self._t_begin = None
        if self._sched is not None:
            occ = self._sched.occupancy()
            self._imbalance.append(max(occ) - min(occ))

    def flush(self) -> list[FetchedStep]:
        """Drain every in-flight step (oldest first) — end of stream."""
        drained = []
        while self._queue:
            drained.append(self._fetch_oldest())
        return drained

    def _fetch_oldest(self) -> FetchedStep:
        inf = self._queue.pop(0)
        with spans.span(spans.FETCH):
            arrays = tuple(np.asarray(getattr(o, self.field))
                           for o in inf.outs)
        self._e2e_s.append(self._clock() - inf.t_begin)
        return FetchedStep(inf.index, arrays, inf.piece_frames, inf.meta)

    # ---------------------------------------------------- telemetry --

    @property
    def in_flight(self) -> int:
        return len(self._queue)

    def reset_telemetry(self):
        """Zero the SLO accumulators (keeps the in-flight queue): the
        benchmarks call this after their warmup steps so compile noise
        never reaches the reported percentiles."""
        if self._step_span is not None:     # an open step counts nothing
            self._step_span.close(None)
            self._step_span = None
        self.spans.reset()
        self._step_s = []
        self._e2e_s = []
        self._imbalance = []
        self._admitted_before = self._admission_counts()
        self.decisions = 0
        self._t_begin = None
        self._t_first = None
        self._t_last_end = None

    def _admission_counts(self) -> tuple[int, int]:
        """The scheduler's (waves, slots admitted) so far."""
        if self._sched is None:
            return (0, 0)
        return (self._sched.waves, self._sched.admitted)

    @property
    def last_step_s(self) -> float:
        return self._step_s[-1] if self._step_s else 0.0

    def report(self) -> dict:
        """The SLO telemetry block (DESIGN.md §14) for
        ``StreamSummary.slo`` / ``BENCH_serve.json``."""
        steps = len(self._step_s) or self._index
        n = max(steps, 1)
        steady_s = (self._t_last_end - self._t_first
                    if self._t_last_end is not None
                    and self._t_first is not None else sum(self._step_s))
        imb = np.asarray(self._imbalance or [0], np.float64)
        host = {name: sec * 1e3 / n
                for name, sec in self.spans.seconds.items()}
        host[spans.UNTRACED] = (steady_s * 1e3 / n
                                - sum(host[p] for p in spans.PHASES))
        dispatch = sum(host[p] for p in spans.DISPATCH)
        rep = {
            "depth": self.depth,
            "steps": steps,
            "decisions": self.decisions,
            "steady_state_s": steady_s,
            "decisions_per_s_steady": (self.decisions / steady_s
                                       if steady_s > 0 else 0.0),
            "step_ms": percentiles_ms(self._step_s),
            "e2e_ms": percentiles_ms(self._e2e_s),
            "host_blocked_ms_per_step": {
                "assemble": host[spans.ASSEMBLE],
                "dispatch": dispatch,
                "fetch": host[spans.FETCH],
                "total": host[spans.ASSEMBLE] + dispatch + host[spans.FETCH],
            },
            "host_ms_per_step": host,
            "shard_imbalance": {"mean": float(imb.mean()),
                                "max": int(imb.max())},
            "staging_allocated": self.staging_allocated,
            "staging_reused": self.staging_reused,
            # the session's count since its last reset
            "frame_joins": getattr(self.session, "frame_joins", 0),
        }
        if self._sched is not None:
            (w1, s1), (w0, s0) = (self._admission_counts(),
                                  self._admitted_before)
            waves, slots = w1 - w0, s1 - s0
            rep["admission"] = {"waves": waves, "slots": slots,
                                "mean_wave": slots / waves if waves
                                else 0.0}
        return rep


def warm_session(sess, chunk: int) -> float:
    """Compile the fused audio step OUTSIDE the timed loop.

    Runs one zero block of the serving chunk length through the session,
    blocks until the compiled step has executed, then resets the session
    to pristine state (fresh stream state AND telemetry — the warmup
    chunk leaves no trace; compiled steps are keyed on chunk length and
    survive the reset).  Returns the warmup wall seconds, which the
    serve loops report as compile time separate from steady state.
    """
    t0 = time.perf_counter()
    out = sess.process_audio(np.zeros((sess.batch, chunk), np.float32))
    np.asarray(out.votes)                   # block: compile + first run
    sess.reset()
    return time.perf_counter() - t0


# ---------------------------------------------------------------------------
# Loop drivers: the two serve-loop shapes, shared by serve.py and the
# conformance tests, so sync (depth=1) and async (depth>=2) runs are the
# SAME code path with a different pipeline window.


class _VoteTable:
    """Vote counts of stream incarnations, one reused row per life.

    An incarnation is one admitted stream life on a slot; a churn storm
    or a re-admission starts a new one, so a fetch landing after the slot
    was recycled still credits the life that was live when its chunk was
    dispatched.  Its id is a row here: ``counts[row]`` tallies its votes,
    ``requests[row]`` holds its request.  A row is taken at admission (or
    a storm's restart) and given back when the last step that can add to
    it drains: the step that carried its last chunk, which decides it, or
    for a life a storm cut short, the storm's step.  Steps drain in
    dispatch order, so no late vote reaches a row after it is given back.

    The rows held at once are the live slots' and those of the undrained
    steps' evictions, at most ``batch · depth`` on clean traffic: the
    table's size.  Churn storms can hold more: each of the ``depth``
    undrained steps adds at most ``batch`` decided and ``batch``
    orphaned rows, so never above ``batch · (2 · depth + 1)``.  The
    table then grows by whole ``batch`` rows, and never with the length
    of the run.
    """

    def __init__(self, n_classes: int, batch: int, depth: int):
        self._batch = batch
        rows = batch * depth
        self.counts = np.zeros((rows, n_classes), np.int64)
        self.requests = np.empty(rows, object)
        self._free = np.arange(rows)        # a stack: the first _n_free
        self._n_free = rows

    def take(self, requests: np.ndarray) -> np.ndarray:
        """Rows for new lives serving ``requests`` (an object array)."""
        k = len(requests)
        short = k - self._n_free
        if short > 0:
            n, grow = len(self.counts), -(-short // self._batch) * self._batch
            self.counts = np.concatenate(
                [self.counts, np.zeros((grow, self.counts.shape[1]),
                                       np.int64)])
            self.requests = np.concatenate(
                [self.requests, np.empty(grow, object)])
            self._free = np.concatenate(
                [self._free[:self._n_free], np.arange(n, n + grow),
                 np.empty(n - self._n_free, np.intp)])
            self._n_free += grow
        self._n_free -= k
        rows = self._free[self._n_free:self._n_free + k].copy()
        self.counts[rows] = 0
        self.requests[rows] = requests
        return rows

    def add(self, votes: np.ndarray, rows, n_real):
        """Tally a drained step, ``votes`` (frames, batch): slot ``b``'s
        first ``n_real[b]`` votes go to row ``rows[b]`` (a slot with no
        life has ``n_real`` 0), in one ``bincount`` over
        ``row · n_classes + vote``.  The votes past a slot's ``n_real``
        go to a spare last bin; only frames past the shortest ``n_real``
        are masked."""
        n_bins = self.counts.size
        keys = votes + (rows * self.counts.shape[1]).astype(np.int32)
        lo = int(n_real.min())
        keys[lo:][np.arange(lo, len(keys))[:, None] >= n_real] = n_bins
        flat = self.counts.reshape(-1)
        flat += np.bincount(keys.ravel(), minlength=n_bins + 1)[:n_bins]

    def decide(self, rows) -> list[tuple[Any, int]]:
        """[(request, argmax of its votes)] for ``rows``, in their order."""
        return list(zip(self.requests[rows].tolist(),
                        self.counts[rows].argmax(axis=1).tolist()))

    def release(self, rows):
        self._free[self._n_free:self._n_free + len(rows)] = rows
        self._n_free += len(rows)


class _StepVotes:
    """A step's share of the tally, fixed at dispatch (``FetchedStep.meta``):
    each slot's life (row) and real-frame count; the rows it decides
    (lives whose last chunk it carried, in ``sched.live`` order) and the
    rows a storm in it orphaned."""

    __slots__ = ("rows", "n_real", "decided", "orphaned")


def run_audio_requests(sess, sched, ctl, *, audio_q, chunk: int,
                       chunks_per_utt: int, real_frames: int,
                       injector=None, depth: int = 1, warm: bool = True,
                       clock=time.perf_counter):
    """The continuous-batching utterance loop (kws-audio), pipelined.

    Identical decision semantics to the historical synchronous loop:
    per step, device ops run in the order [pieces..., churn resets,
    admission resets]; eviction happens when a slot has consumed
    ``chunks_per_utt`` chunks (known at dispatch); only real-audio
    frames vote (``real_frames`` bounds the tally against zero-padding
    and idle-slot frames).  Vote VALUES are tallied when their step
    drains, into the incarnation that was live at dispatch
    (``_VoteTable``), and a request is decided when the step that
    carried its last chunk drains.  The slot state is arrays over the
    slot axis, so the bookkeeping costs a few array operations a step.

    Returns ``(done, stats)``: the ordered [(request, predicted class)]
    list and the loop counters (steps, frames_served, pad_frames,
    warmup_s) next to the engine's SLO report, which is also attached
    to the session (``summary().slo``).
    """
    eng = PipelinedEngine(sess, depth=depth, field="votes",
                          scheduler=sched, clock=clock)
    warmup_s = warm_session(sess, chunk) if warm else 0.0

    batch = sess.batch
    table = _VoteTable(sess.n_classes, batch, depth)
    live = np.zeros(batch, bool)
    row = np.zeros(batch, np.intp)          # the slot's current life
    consumed = np.zeros(batch, np.int64)    # chunks consumed
    left = np.zeros(batch, np.int64)        # real frames left to vote on
    seq = np.zeros(batch, np.int64)         # admission number: live order
    n_admitted = 0
    done: list[tuple[Any, int]] = []
    frames_served = pad_frames = steps = 0

    def start(slots: np.ndarray, requests: np.ndarray):
        row[slots] = table.take(requests)
        consumed[slots] = 0
        left[slots] = real_frames

    def integrate(f: FetchedStep):
        d = f.meta
        pieces = f.arrays or (np.zeros((0, batch), np.int32),)
        v = pieces[0] if len(pieces) == 1 else np.concatenate(pieces)
        table.add(v, d.rows, d.n_real)
        done.extend(table.decide(d.decided))
        table.release(d.decided)
        table.release(d.orphaned)

    def admit():
        nonlocal n_admitted
        admitted = sched.admit()
        with spans.span(spans.SLOTS):
            if admitted:
                slots, requests = zip(*admitted)
                slots = np.fromiter(slots, np.intp, len(slots))
                start(slots, np.fromiter(requests, object, len(slots)))
                live[slots] = True
                seq[slots] = np.arange(n_admitted, n_admitted + len(slots))
                n_admitted += len(slots)

    admit()
    while not sched.idle:
        eng.begin()
        with spans.span(spans.ASSEMBLE):
            block = eng.staging((batch, chunk))
            idle = np.ones(batch, bool)
            chunk_of = consumed.tolist()
            for slot, req in sched.live.items():
                c0 = chunk_of[slot]
                seg = audio_q[req, c0 * chunk:(c0 + 1) * chunk]
                n = len(seg)
                block[slot, :n] = seg
                if n < chunk:
                    block[slot, n:] = 0.0       # zero-pad a short chunk
                idle[slot] = False
            block[idle] = 0.0                   # idle slots hear silence
            pieces, actions = ([block], []) if injector is None \
                else injector.inject(block)
        d = _StepVotes()                    # filled below, post-submit
        piece_frames, drained = eng.submit(pieces, meta=d)
        n_f = sum(piece_frames)
        with spans.span(spans.SLOTS):
            orphaned = [np.zeros(0, np.intp)]
            for act in actions:             # driver directives
                if act.kind == "stall":
                    time.sleep(act.detail)
                elif act.kind == "churn_storm":
                    storm = [s for s in act.slots if s in sched.live]
                    sess.reset_streams(storm)   # poof — streams restart
                    # a slot named twice restarts once
                    s = np.array(list(dict.fromkeys(storm)), np.intp)
                    orphaned.append(row[s])     # same request, new life
                    start(s, table.requests[row[s]])
            n_real = np.where(live, np.minimum(left, n_f), 0)
            left -= n_real
            consumed += live
            served = int(n_real.sum())
            frames_served += served
            pad_frames += n_f * batch - served  # idle slots, short tails
            out = np.flatnonzero(live & (consumed >= chunks_per_utt))
            out = out[np.argsort(seq[out])]
            sched.evict_many(out)
            live[out] = False
            d.rows, d.n_real = row.copy(), n_real
            d.decided, d.orphaned = row[out], np.concatenate(orphaned)
            for f in drained:
                integrate(f)
        admit()
        steps += 1
        eng.end()
        if ctl is not None:
            ctl.observe(eng.last_step_s)
    for f in eng.flush():
        integrate(f)

    slo = eng.report()
    slo["warmup_s"] = warmup_s
    sess.attach_slo(slo)
    return done, {"steps": steps, "frames_served": frames_served,
                  "pad_frames": pad_frames, "warmup_s": warmup_s,
                  "slo": slo}


def run_continuous_detect(sess, streams_audio, *, chunk: int,
                          n_samples: int, injector=None, depth: int = 1,
                          warm: bool = True, clock=time.perf_counter):
    """The always-on detection loop (kws-detect / kws-cascade), pipelined.

    Identical decision semantics to the historical synchronous loops:
    per step, fault actions (stall / churn resets) are applied BEFORE
    the pieces are dispatched (the detect loops' order — the audio loop
    applies them after), and every slot's fires are appended in frame
    order: per-piece frame offsets advance at dispatch from the pieces'
    shapes, so the fire positions are exact even though the event
    values land later.

    ``streams_audio`` holds one 1-D sample array per slot; each step's
    block is their ``[off:off + chunk]`` rows, copied into the engine's
    staging ring in the arrays' common dtype (``np.stack``'s).

    Returns ``(fires, frame_base, stats)``: per-slot fire lists (for
    ``det_point``), the total frame count, and the loop stats + SLO
    report (also attached to the session summary).
    """
    from repro.models.detector import fires_from_event_block

    eng = PipelinedEngine(sess, depth=depth, field="events", clock=clock)
    warmup_s = warm_session(sess, chunk) if warm else 0.0
    fires: list[list] = [[] for _ in range(sess.batch)]
    frame_base = 0
    steps = 0
    dtype = np.result_type(*streams_audio)

    def integrate(f: FetchedStep):
        for ev, base in zip(f.arrays, f.meta):
            for slot, frame, cls in fires_from_event_block(ev, base):
                fires[slot].append((frame, cls))

    for off in range(0, n_samples, chunk):
        eng.begin()
        with spans.span(spans.ASSEMBLE):
            segs = [s[off:off + chunk] for s in streams_audio]
            width = len(segs[0])
            block = eng.staging((len(segs), width), dtype)
            for row, seg in enumerate(segs):
                if len(seg) != width:
                    raise ValueError(f"streams end at different samples: "
                                     f"the chunk at {off} is {width} wide "
                                     f"in stream 0, {len(seg)} in {row}")
                block[row] = seg
            pieces, actions = ([block], []) if injector is None \
                else injector.inject(block)
            for act in actions:
                if act.kind == "stall":
                    time.sleep(act.detail)
                elif act.kind == "churn_storm":
                    sess.reset_streams(list(act.slots))
        bases: list[int] = []
        piece_frames, drained = eng.submit(pieces, meta=bases)
        with spans.span(spans.SLOTS):
            for pf in piece_frames:         # offsets fixed at dispatch
                bases.append(frame_base)
                frame_base += pf
            for f in drained:
                integrate(f)
        steps += 1
        eng.end()                           # the step covers the drain
    for f in eng.flush():
        integrate(f)

    slo = eng.report()
    slo["warmup_s"] = warmup_s
    sess.attach_slo(slo)
    return fires, frame_base, {"steps": steps, "warmup_s": warmup_s,
                               "slo": slo}
