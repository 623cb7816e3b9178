"""Serving driver for utterance traffic: ``run_audio_requests``.

The window drives the program's own continuous-batching loop
(``repro.launch.engine.run_audio_requests``) over a ``StreamingKwsSession``
and ``SlotScheduler`` at the traffic's pipeline depth.  The scheduler here
is the program's, with one change of feeding: while the window is open
every ``admit()`` first tops the queue up to the free slots (a closed
loop whose backlog is never empty); at the deadline it stops, and the
loop drains what is live.  Request ``r`` plays pool clip ``r % pool``.

The check compares, for every request the window served, its per-frame
votes (the arrays the loop fetched) and its decision with the plain
reference run on the same clip, and the slots' final hidden state, every
layer's, with the reference run over each slot's last request and the
zero chunks an idle slot processes after it.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import generator
from bench.probes import SPAN_ADMIT, Probe, annotation, hidden_values


def _scheduler_class():
    from repro.launch.streaming import SlotScheduler

    class WindowScheduler(SlotScheduler):
        """SlotScheduler that keeps its backlog full until a deadline
        (or for a fixed number of requests) and records admissions as
        (index of the step that serves the first chunk, slots, requests).
        """

        def __init__(self, session, probe: Probe, *, deadline=None,
                     requests=None):
            super().__init__(session)
            self._probe = probe
            self._deadline = deadline
            self._budget = requests
            self.next_request = 0
            self.admissions: list[tuple[int, np.ndarray, np.ndarray]] = []

        def _open(self) -> bool:
            if self._deadline is not None:
                return time.perf_counter() < self._deadline
            return self.next_request < self._budget

        def admit(self):
            if self._open():
                free = self.n_slots - len(self.live) - len(self)
                if self._budget is not None:
                    free = min(free, self._budget - self.next_request)
                for _ in range(free):
                    self.submit(self.next_request)
                    self.next_request += 1
            with annotation(SPAN_ADMIT):
                admitted = super().admit()
            if admitted:
                slots, reqs = zip(*admitted)
                self.admissions.append((self._probe.steps,
                                        np.asarray(slots), np.asarray(reqs)))
            return admitted

    return WindowScheduler


class PoolAudio:
    """``audio_q[request, samples]`` over a bounded pool of clips."""

    def __init__(self, pool: np.ndarray):
        self.pool = pool

    def __getitem__(self, key):
        req, span = key
        return self.pool[req % len(self.pool), span]


@dataclasses.dataclass
class Prepared:
    session: object
    probe: Probe
    pool: np.ndarray
    chunk: int
    chunks_per_utt: int
    real_frames: int
    depth: int
    frame_s: float


def prepare(run) -> Prepared:
    from repro.launch.engine import run_audio_requests, warm_session
    t = run.cell.traffic
    cfg = run.cell.config
    chunk = int(t["chunk_samples"])
    shift = int(cfg["fex"]["frame_shift"])
    utt = int(t["utterance_samples"])
    session = run.session(slots=int(t["slots"]))
    probe = Probe(session, "votes")
    run.mark("session")
    pool = generator.pool(t, run.seed)
    run.mark("traffic")
    warm_session(session, chunk)
    session.reset_streams([0])              # compiles the admission resets
    session.reset()
    run.mark("compile")
    prep = Prepared(session=session, probe=probe, pool=pool, chunk=chunk,
                    chunks_per_utt=-(-utt // chunk), real_frames=utt // shift,
                    depth=int(t["depth"]),
                    frame_s=shift / float(cfg["sample_rate_hz"]))
    # One short pass of the loop itself, so every program it runs is
    # compiled and loaded before the window.
    sched = _scheduler_class()(probe, probe,
                               requests=int(t["warm_waves"]) * int(t["slots"]))
    run_audio_requests(
        probe, sched, None, audio_q=PoolAudio(pool), chunk=chunk,
        chunks_per_utt=prep.chunks_per_utt, real_frames=prep.real_frames,
        depth=prep.depth, warm=False)
    run.mark("warm_loop")
    session.reset()
    probe.restart()
    run.mark("warm")
    return prep


def serve(prep: Prepared, run) -> dict:
    """The measured window; returns what the check and metrics need."""
    from repro.launch.engine import run_audio_requests
    probe = prep.probe
    t0 = time.perf_counter()
    sched = _scheduler_class()(probe, probe, deadline=t0 + run.seconds)
    done, stats = run_audio_requests(
        probe, sched, None, audio_q=PoolAudio(prep.pool), chunk=prep.chunk,
        chunks_per_utt=prep.chunks_per_utt, real_frames=prep.real_frames,
        depth=prep.depth, warm=False)
    window_s = time.perf_counter() - t0
    return {
        "window_s": window_s,
        "steps": probe.steps,
        "slots": prep.session.batch,
        "chunk_samples": prep.chunk,
        "served_frames": stats["frames_served"],
        "audio_s": stats["frames_served"] * prep.frame_s,
        "report": stats["slo"],
        "done": done,
        "admissions": sched.admissions,
        "fetched": probe.fetched,
    }


def final_state(prep: Prepared) -> np.ndarray:
    """The slots' hidden state after the window, as values, (B, L·H):
    every layer's ``h``, the first layer's first (``probes.hidden_values``:
    the session's ``state`` is one ΔGRU state, or a sequence of L)."""
    return hidden_values(prep.session)


def verify(prep: Prepared, served: dict, h_final: np.ndarray, ref,
           run) -> tuple[dict, int, int]:
    """(numbers compared, attempted, failed).  ``ref`` is the reference
    built for this run (``bench.harness.Reference``)."""
    P = len(prep.pool)
    per_req = prep.chunks_per_utt * prep.chunk
    R = prep.real_frames
    audio = np.zeros((P, per_req), np.float32)
    audio[:, :prep.pool.shape[1]] = prep.pool
    ref_votes = ref.votes(audio)[:R]                       # (R, P)
    n_cls = int(run.cell.config["classes"])
    ref_dec = np.array([np.bincount(ref_votes[:, p], minlength=n_cls).argmax()
                        for p in range(P)])

    admitted = {}
    vote_bad = vote_all = 0
    last = {}                                              # slot -> (step, req)
    fetched = served["fetched"]
    for step, slots, reqs in served["admissions"]:
        steps = [fetched.get(step + c) for c in range(prep.chunks_per_utt)]
        if any(v is None for v in steps):                  # never fetched
            vote_bad += R * len(slots)
        else:
            votes = np.concatenate([v[:, slots] for v in steps])[:R]
            vote_bad += int(np.count_nonzero(votes != ref_votes[:, reqs % P]))
        vote_all += R * len(slots)
        for s, r in zip(slots.tolist(), reqs.tolist()):
            admitted[r] = s
            last[s] = (step, r)
    answered = {}
    for r, pred in served["done"]:
        answered[r] = pred
    unanswered = len(set(admitted) - set(answered)) + \
        len(set(answered) - set(admitted))
    dec_bad = sum(int(pred != ref_dec[r % P]) for r, pred in answered.items())

    # Final hidden state: each slot's last clip, then the zero chunks the
    # loop fed the idle slot until it ended.
    tails = np.zeros(len(h_final), np.int64)
    clips = np.zeros((len(h_final), per_req), np.float32)
    for s, (step, r) in last.items():
        tails[s] = served["steps"] - (step + prep.chunks_per_utt)
        clips[s] = audio[r % P]
    h_ref = ref.final_hidden(clips, prep.chunk, tails)
    gap = np.max(np.abs(h_final - h_ref), axis=-1)
    checks = {
        "unanswered": unanswered,
        "decision_mismatch_pct": 100.0 * dec_bad / max(len(answered), 1),
        "vote_mismatch_pct": 100.0 * vote_bad / max(vote_all, 1),
        "state_gap": float(np.median(gap)),
    }
    return checks, len(admitted), unanswered
