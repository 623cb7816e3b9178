"""Serving driver for continuous streams: ``run_continuous_detect``.

The window drives the program's always-on detection loop
(``repro.launch.engine.run_continuous_detect``) over a detection-mode
``StreamingKwsSession`` (energy VAD + event head) at the traffic's chunk
and pipeline depth: every slot holds one stream for the whole window, a
closed loop with no churn.  Slot ``i`` plays pool stream ``i % pool`` from
its own offset, so few distinct streams feed many slots.

The loop takes its length up front, so the window is sized from the warm
step time: ``seconds`` over the step time, rounded up to whole reference
blocks.  The check compares the events the loop fired and every slot's
final hidden state, every layer's, with the plain reference run over the
same audio.
"""
from __future__ import annotations

import dataclasses
import math
import time

import numpy as np

from bench import generator
from bench.probes import Probe, hidden_values


@dataclasses.dataclass
class Prepared:
    session: object
    probe: Probe
    chunk: int
    depth: int
    warm_step_s: float
    n_samples: int = 0
    views: list | None = None
    pool: np.ndarray | None = None
    frame_s: float = 0.0


def views(pool: np.ndarray, slots: int, stride: int) -> list:
    """Slot ``i``'s audio: pool stream ``i % pool`` from offset
    ``(i // pool) · stride``."""
    return [pool[i % len(pool)][(i // len(pool)) * stride:]
            for i in range(slots)]


def prepare(run) -> Prepared:
    from repro.launch.engine import run_continuous_detect, warm_session
    t = run.cell.traffic
    cfg = run.cell.config
    slots = int(t["slots"])
    chunk = int(t["chunk_samples"])
    session = run.session(slots=slots, detect=True)
    probe = Probe(session, "events")
    run.mark("session")
    warm_session(session, chunk)
    run.mark("compile")
    warm_steps = int(t["warm_steps"])
    zeros = [np.zeros(warm_steps * chunk, np.float32)] * slots
    _, _, stats = run_continuous_detect(
        probe, zeros, chunk=chunk, n_samples=warm_steps * chunk,
        depth=int(t["depth"]), warm=False)
    run.mark("warm_loop")
    step_s = stats["slo"]["steady_state_s"] / max(stats["slo"]["steps"], 1)
    session.reset()
    probe.restart()
    prep = Prepared(session=session, probe=probe, chunk=chunk,
                    depth=int(t["depth"]), warm_step_s=step_s,
                    frame_s=int(cfg["fex"]["frame_shift"])
                    / float(cfg["sample_rate_hz"]))
    # Whole reference blocks of ``block_frames`` steps (one frame a step).
    block = int(t["block_frames"])
    steps = block * max(1, math.ceil(run.seconds / step_s / block))
    steps = min(steps, int(t["max_steps"]))
    prep.n_samples = steps * chunk
    stride = int(t["offset_stride"])
    longest = prep.n_samples + (slots // int(t["pool"])) * stride
    prep.pool = generator.pool(t, run.seed, samples=longest)
    prep.views = views(prep.pool, slots, stride)
    run.mark("traffic")
    return prep


def serve(prep: Prepared, run) -> dict:
    from repro.launch.engine import run_continuous_detect
    probe = prep.probe
    t0 = time.perf_counter()
    fires, frames, stats = run_continuous_detect(
        probe, prep.views, chunk=prep.chunk, n_samples=prep.n_samples,
        depth=prep.depth, warm=False)
    window_s = time.perf_counter() - t0
    slots = prep.session.batch
    return {
        "window_s": window_s,
        "steps": probe.steps,
        "slots": slots,
        "chunk_samples": prep.chunk,
        "served_frames": frames * slots,
        "audio_s": frames * slots * prep.frame_s,
        "report": stats["slo"],
        "fires": fires,
        "frames": frames,
        "summary": prep.session.summary(),
    }


def final_state(prep: Prepared) -> np.ndarray:
    """The slots' hidden state after the window, as values, (B, L·H):
    every layer's ``h``, the first layer's first (``probes.hidden_values``:
    the session's ``state`` is one ΔGRU state, or a sequence of L)."""
    return hidden_values(prep.session)


def verify(prep: Prepared, served: dict, h_final: np.ndarray, ref,
           run) -> tuple[dict, int, int]:
    slots = len(prep.views)
    n_frames = prep.n_samples // prep.chunk
    events, h_ref = ref.detect(prep.views, prep.n_samples)
    ref_fires = {(s, int(f), int(events[f, s]))
                 for f, s in zip(*np.nonzero(events >= 0))}
    got = {(s, int(f), int(c))
           for s, fl in enumerate(served["fires"]) for f, c in fl}
    expected = slots * n_frames
    summary = served["summary"]
    missing = abs(expected - int(summary.frames)) + \
        abs(n_frames - int(served["frames"]))
    checks = {
        "missing_frames": missing,
        "fire_mismatch_pct": 100.0 * len(got ^ ref_fires)
        / max(len(got) + len(ref_fires), 1),
        "state_gap": float(np.median(np.max(np.abs(h_final - h_ref),
                                            axis=-1))),
    }
    return checks, expected, missing
