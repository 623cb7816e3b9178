"""Always-on keyword DETECTION head + detection metrics (DESIGN.md §10).

The IC's deployment scenario is not per-utterance classification: it
listens to an unbounded audio stream and must decide *when* a keyword
occurred.  This module turns the per-frame ΔGRU posteriors into discrete
keyword EVENTS with a posterior-smoothing / hysteresis state machine
("Hello Edge" §6-style posterior handling), and scores event streams
against ground truth with the deployment metrics — false alarms per hour
vs. miss rate — that define an operating point on the DET curve.

Decision head (``detector_scan``), per stream slot and per 16 ms frame:

  1. **Smooth**: an exponential moving average over the per-frame class
     posteriors, ``s_t = s_{t-1} + α (p_t − s_{t-1})`` with ``s_0 = 0``
     (the zero init ramps scores up from silence, suppressing spurious
     fires in the first frames of a fresh stream).
  2. **Score**: the maximum smoothed posterior over the KEYWORD classes
     (class ids ≥ ``first_keyword`` — "silence" and "unknown" never
     fire).
  3. **Hysteresis**: idle → in-event when the score rises ABOVE
     ``fire_threshold`` (this rising edge emits exactly one event,
     labeled with the argmax keyword); in-event → idle when the score
     falls BELOW ``release_threshold``.  While in-event no new events
     fire, so one spoken keyword produces one event, not one per frame.
  4. **Refractory**: after a fire, new fires are additionally suppressed
     for ``refractory_frames`` frames — a floor on the event rate that
     bounds the worst-case FA/hr even at absurd thresholds.

Everything is elementwise along the batch (slot) axis and sequential
along the frame axis only, so the head runs inside the fused serving
step with its state device-resident per slot (sharding-safe, no
collectives), and processing a stream in chunks with the state carried
is bit-identical to processing it in one piece.

Scoring (host-side, exact): a fire is a HIT if it lands inside a ground
truth event's ``[start − tol, end + tol]`` frame window with the right
label (each truth event can be claimed once; fires and events are
matched greedily in time order); every unmatched fire is a FALSE ALARM;
every unclaimed truth event is a MISS.  ``det_point`` reduces a fire
list to (miss rate, FA/hr); sweeping ``fire_threshold`` over a posterior
trace traces the DET curve (``benchmarks/detect_bench.py``).
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

FRAME_S = 0.016                       # 16 ms per decision (paper)
NO_EVENT = -1                         # events-array value for "no fire"


class DetectorConfig(NamedTuple):
    """Static configuration of the detection head (compiled into the
    serving step; a new config is a new operating point → new jit).

    smooth_alpha: EMA coefficient on the per-frame posteriors (1.0 = no
      smoothing; the default ≈ 6-frame / 100 ms time constant).
    fire_threshold: smoothed keyword posterior that opens an event
      (strictly-above comparison).  Either one scalar for every keyword
      or a tuple with one threshold PER keyword class (length =
      n_classes − first_keyword, in class-id order) — per-keyword
      operating points are what scenario-cell calibration produces
      (``calibrate_fire_thresholds``): hard words get permissive
      thresholds, false-alarm-prone words strict ones, at one shared
      FA/hr budget.
    release_threshold: smoothed keyword posterior that closes the event
      (strictly-below comparison; the event closes when EVERY keyword's
      smoothed posterior is below its release level).  Scalar or
      per-keyword tuple like ``fire_threshold``; must be elementwise ≤
      fire_threshold — the gap is the hysteresis band that prevents
      rapid re-triggering on a fluctuating score.
    refractory_frames: minimum frames between two fires (~16 ms each).
    first_keyword: first class id eligible to fire (ids below it —
      silence=0, unknown=1 in ``models.kws.CLASSES`` — never fire).
    """

    smooth_alpha: float = 0.25
    fire_threshold: float | tuple[float, ...] = 0.55
    release_threshold: float | tuple[float, ...] = 0.40
    refractory_frames: int = 30
    first_keyword: int = 2


def band_inverted(cfg: DetectorConfig) -> bool:
    """True when any keyword's release threshold exceeds its fire
    threshold (an inverted hysteresis band degrades the head into a
    refractory-paced pulse generator) — the session-construction check,
    scalar- and per-keyword-aware.  Raises ``ValueError`` when the two
    thresholds are tuples of incompatible lengths."""
    fire = np.asarray(cfg.fire_threshold, np.float32)
    rel = np.asarray(cfg.release_threshold, np.float32)
    try:
        return bool(np.any(rel > fire))
    except ValueError as e:
        raise ValueError(
            f"fire_threshold and release_threshold must broadcast "
            f"(per-keyword tuples need equal lengths): got shapes "
            f"{fire.shape} and {rel.shape}") from e


class DetectorState(NamedTuple):
    """Per-slot carried state of the decision head (device-resident).

    smooth: (B, K) float32 — EMA-smoothed posteriors.
    active: (B,) int32 — class id of the event currently open, or
      ``NO_EVENT`` when idle (the hysteresis latch).
    refract: (B,) int32 — frames left in the refractory window.
    """

    smooth: Array
    active: Array
    refract: Array


def init_detector_state(batch: int, n_classes: int) -> DetectorState:
    """Idle detector: zero smoothed posteriors, no open event."""
    return DetectorState(
        smooth=jnp.zeros((batch, n_classes), jnp.float32),
        active=jnp.full((batch,), NO_EVENT, jnp.int32),
        refract=jnp.zeros((batch,), jnp.int32))


def detector_step(cfg: DetectorConfig, state: DetectorState, post: Array
                  ) -> tuple[DetectorState, Array]:
    """One frame of the decision head.  post: (B, K) posteriors.

    Returns (new_state, event (B,) int32) where event is the fired
    keyword class id on the fire frame and ``NO_EVENT`` otherwise.
    Elementwise in B (sharding-safe).
    """
    smooth = state.smooth + cfg.smooth_alpha * (post.astype(jnp.float32)
                                                - state.smooth)
    kw = smooth[:, cfg.first_keyword:]
    # Scalar thresholds broadcast over the keyword axis; per-keyword
    # tuples give every class its own operating point.  With a scalar
    # this is bit-identical to the max-score formulation: any(kw > th)
    # == max(kw) > th, all(kw < rel) == max(kw) < rel, and the argmax
    # over the exceeding set is the global argmax whenever it fires.
    fire_th = jnp.asarray(cfg.fire_threshold, jnp.float32)
    rel_th = jnp.asarray(cfg.release_threshold, jnp.float32)
    exceed = kw > fire_th                              # (B, K_kw)
    cls = (jnp.argmax(jnp.where(exceed, kw, -jnp.inf), axis=-1)
           + cfg.first_keyword).astype(jnp.int32)

    idle = state.active == NO_EVENT
    fire = idle & (state.refract == 0) & jnp.any(exceed, axis=-1)
    release = (~idle) & jnp.all(kw < rel_th, axis=-1)
    active = jnp.where(fire, cls,
                       jnp.where(release, NO_EVENT, state.active))
    refract = jnp.where(fire, jnp.int32(cfg.refractory_frames),
                        jnp.maximum(state.refract - 1, 0))
    event = jnp.where(fire, cls, NO_EVENT).astype(jnp.int32)
    return DetectorState(smooth=smooth, active=active, refract=refract), event


@jax.named_scope("kws.head")
def detector_scan(cfg: DetectorConfig, state: DetectorState, posts: Array
                  ) -> tuple[DetectorState, Array]:
    """Run the decision head over a chunk of frames.

    Args:
      cfg: the static ``DetectorConfig`` (smoothing, fire/release
        thresholds, refractory) — compiled into the step.
      state: carried ``DetectorState`` (``init_detector_state`` for a
        fresh stream).
      posts: (F, B, K) per-frame class posteriors, frame-major like the
        serving step's logits.

    Returns:
      (carried state, events (F, B) int32) — ``events[f, b]`` is the
      fired keyword class id at frame f of slot b, ``NO_EVENT`` when no
      fire happened there.

    State contract: chunk boundaries are invisible — scanning [a|b] with
    the state carried equals scanning the concatenation (the streaming-
    session contract); everything is elementwise in B, so slot-sharded
    execution is bit-identical too.
    """
    def body(s, p):
        s, ev = detector_step(cfg, s, p)
        return s, ev

    state, events = jax.lax.scan(body, state, posts)
    return state, events


def detector_state_flags(state: DetectorState) -> Array:
    """Per-slot health predicate over the decision head's carried state
    (DESIGN.md §11): (B,) bool, True where the slot's EMA is poisoned.

    The smoothed posteriors are a convex combination of softmax outputs,
    so a healthy slot's ``smooth`` lies in [0, 1] and is finite; anything
    else (a NaN that leaked through the logits, an out-of-range value
    from corrupted memory) means the latch can never fire/release sanely
    again and the slot needs a reset.  Elementwise in B — runs inside
    the fused serving step, sharding-safe, and pure (reads state only).
    """
    s = state.smooth
    bad = ~jnp.isfinite(s) | (s < -1e-6) | (s > 1.0 + 1e-6)
    return jnp.any(bad, axis=-1)


# ---------------------------------------------------------------- metrics --

@dataclasses.dataclass(frozen=True)
class DetPoint:
    """One operating point on the DET curve (exact counts, host-side)."""

    n_events: int          # ground-truth keyword events in the stream
    hits: int
    misses: int
    false_alarms: int
    miss_rate: float       # misses / n_events (0.0 when no events)
    fa_per_hour: float
    hours: float           # audio hours scored (frames × 16 ms)


def fires_from_events(events: np.ndarray, frame_offset: int = 0
                      ) -> list[tuple[int, int]]:
    """Decode a detector ``events`` array into a fire list.

    events: (F,) or (F, 1) int32 from ``detector_scan`` (single stream).
    Returns [(frame, class_id)] with ``frame_offset`` added — pass the
    running frame count when accumulating across serve chunks.
    """
    ev = np.asarray(events).reshape(-1)
    frames = np.flatnonzero(ev != NO_EVENT)
    return [(int(f) + frame_offset, int(ev[f])) for f in frames]


def fires_from_event_block(events: np.ndarray, frame_offset: int = 0
                           ) -> list[tuple[int, int, int]]:
    """Decode a batched ``events`` block into ``(slot, frame, class_id)``.

    events: (F, B) int32, frame-major, as a serving step fetches it.
    One ``np.nonzero`` over the block; its row-major order lists fires
    frame by frame, so each slot's fires come in frame order and
    ``fires_from_events(events[:, b], frame_offset)`` equals slot ``b``'s
    ``(frame, class_id)`` pairs here.
    """
    ev = np.asarray(events)
    frames, slots = np.nonzero(ev != NO_EVENT)
    return list(zip(slots.tolist(), (frames + frame_offset).tolist(),
                    ev[frames, slots].tolist()))


def match_fires(fires: Sequence[tuple[int, int]],
                truth: Sequence[tuple[int, int, int]],
                tol_frames: int = 0) -> tuple[int, int]:
    """Greedy time-order matching of fires against truth events.

    fires: [(frame, class_id)] sorted by frame; truth: [(start_frame,
    end_frame, class_id)] with inclusive bounds.  A fire claims an
    unclaimed truth event whose label matches and whose
    ``[start − tol, end + tol]`` window contains the fire frame,
    preferring an event whose TRUE span contains the fire over a
    tolerance-only match (so when adjacent same-class windows overlap, a
    fire inside event B cannot be mis-credited to the earlier missed
    event A), earliest-start among equals.  Each truth event can be
    claimed once — a second fire on the same event is a false alarm (the
    hysteresis/refractory machinery exists to make that rare).  Returns
    (hits, false_alarms).
    """
    claimed: set[int] = set()
    false_alarms = 0
    for frame, cls in fires:
        exact = tolerated = None
        for i, (start, end, label) in enumerate(truth):
            if i in claimed or label != cls:
                continue
            if start <= frame <= end:
                exact = i
                break
            if tolerated is None and \
                    start - tol_frames <= frame <= end + tol_frames:
                tolerated = i
        hit = exact if exact is not None else tolerated
        if hit is None:
            false_alarms += 1
        else:
            claimed.add(hit)
    return len(claimed), false_alarms


def det_point(fires: Sequence[tuple[int, int]],
              truth: Sequence[tuple[int, int, int]], n_frames: int,
              tol_frames: int = 0, frame_s: float = FRAME_S) -> DetPoint:
    """Reduce a fire list to one (miss rate, FA/hr) operating point.

    ``n_frames`` is the total frames SCORED (it defines the hours the
    false alarms are normalized by), not the frames with speech.
    """
    hits, false_alarms = match_fires(fires, truth, tol_frames)
    n_events = len(truth)
    misses = n_events - hits
    hours = n_frames * frame_s / 3600.0
    return DetPoint(
        n_events=n_events, hits=hits, misses=misses,
        false_alarms=false_alarms,
        miss_rate=misses / n_events if n_events else 0.0,
        fa_per_hour=false_alarms / hours if hours > 0 else 0.0,
        hours=hours)


def calibrate_fire_thresholds(posts: np.ndarray,
                              truth: Sequence[tuple[int, int, int]],
                              base_cfg: DetectorConfig,
                              candidates: Sequence[float],
                              fa_budget_per_hour: float = 60.0,
                              tol_frames: int = 0) -> tuple[float, ...]:
    """Per-keyword fire thresholds from a recorded posterior trace.

    The scenario matrix's per-cell calibration (DESIGN.md §15): one
    shared scalar threshold forces every keyword onto the same operating
    point, but under noise the per-class posterior statistics diverge —
    a babble bed pushes confusable words' false-alarm rates up while
    distinct words keep headroom.  This sweeps each keyword class
    INDEPENDENTLY (all other keyword columns zeroed, so the global
    hysteresis latch sees only the class under calibration — the same
    ``detector_scan`` code path the serving step runs) and picks, per
    class, the most permissive candidate whose class-restricted false
    alarms stay within ``fa_budget_per_hour``; among candidates inside
    the budget, lowest miss count wins, earliest (most permissive)
    among equals.  Falls back to the strictest candidate when none meets
    the budget.

    posts: (F, K) float posterior trace of a CALIBRATION stream (use a
      different seed than the evaluation stream — calibrating on the
      eval stream is leakage).
    truth: ground-truth events of the calibration stream
      (``ContinuousStream.truth_frames``).
    base_cfg: the config whose smoothing/refractory/first_keyword the
      calibrated thresholds will be served with.
    candidates: scalar fire thresholds to sweep (ascending recommended).
    Returns a tuple of length ``K − first_keyword`` suitable for
    ``DetectorConfig(fire_threshold=...)``.
    """
    import jax.numpy as jnp
    if not candidates:
        raise ValueError("candidates must not be empty")
    posts = np.asarray(posts, np.float32)
    n_frames, n_classes = posts.shape
    hours = n_frames * FRAME_S / 3600.0
    fk = base_cfg.first_keyword
    chosen = []
    for cls in range(fk, n_classes):
        cls_truth = [t for t in truth if t[2] == cls]
        solo = posts.copy()
        solo[:, fk:] = 0.0
        solo[:, cls] = posts[:, cls]
        inside_budget = []             # (misses, idx, threshold)
        ordered = sorted(float(c) for c in candidates)
        for idx, cand in enumerate(ordered):
            cfg = base_cfg._replace(fire_threshold=cand,
                                    release_threshold=0.75 * cand)
            state = init_detector_state(1, n_classes)
            _, events = detector_scan(cfg, state,
                                      jnp.asarray(solo[:, None, :]))
            fires = fires_from_events(np.asarray(events))
            hits, fas = match_fires(fires, cls_truth, tol_frames)
            if (fas / hours if hours > 0 else 0.0) <= fa_budget_per_hour:
                inside_budget.append((len(cls_truth) - hits, idx, cand))
        chosen.append(min(inside_budget)[2] if inside_budget
                      else ordered[-1])
    return tuple(chosen)


def pool_points(points: Sequence[DetPoint]) -> DetPoint:
    """Pool per-stream DetPoints into one aggregate operating point
    (counts add; rates are recomputed from the pooled counts)."""
    n_events = sum(p.n_events for p in points)
    hits = sum(p.hits for p in points)
    fas = sum(p.false_alarms for p in points)
    hours = sum(p.hours for p in points)
    misses = n_events - hits
    return DetPoint(
        n_events=n_events, hits=hits, misses=misses, false_alarms=fas,
        miss_rate=misses / n_events if n_events else 0.0,
        fa_per_hour=fas / hours if hours > 0 else 0.0, hours=hours)
