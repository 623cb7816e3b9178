"""Detection head + detection metrics, no model in the loop.

Synthetic posterior traces with known event placements → EXACT expected
smoothing values, hysteresis/refractory behaviour, and FA-per-hour /
miss-rate numbers (the satellite contract: the metrics themselves are
verified arithmetic, not eyeballed output).
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import detector as det


def _scan(cfg, posts, batch=1):
    state = det.init_detector_state(batch, posts.shape[-1])
    state, events = det.detector_scan(cfg, state, jnp.asarray(posts))
    return state, np.asarray(events)


def _pulse(n_frames, k_classes, cls, start, end, level, base=None):
    """Posterior trace: uniform elsewhere, `level` on `cls` in [start,end]."""
    posts = np.full((n_frames, 1, k_classes),
                    (1.0 / k_classes) if base is None else base, np.float32)
    posts[start:end + 1, 0, :] = (1.0 - level) / (k_classes - 1)
    posts[start:end + 1, 0, cls] = level
    return posts


# ------------------------------------------------------------- smoothing --

def test_ema_smoothing_is_exact():
    cfg = det.DetectorConfig(smooth_alpha=0.5, fire_threshold=2.0)
    posts = np.zeros((4, 1, 3), np.float32)
    posts[:, 0, 2] = [1.0, 0.0, 1.0, 1.0]
    state, events = _scan(cfg, posts)
    # s_t = s_{t-1} + 0.5 (p_t - s_{t-1}), s_0 = 0:
    # 0.5, 0.25, 0.625, 0.8125
    np.testing.assert_allclose(float(state.smooth[0, 2]), 0.8125, rtol=1e-6)
    assert (events == det.NO_EVENT).all()     # threshold 2.0: never fires


def test_smooth_alpha_one_is_identity():
    cfg = det.DetectorConfig(smooth_alpha=1.0, fire_threshold=2.0)
    posts = np.random.default_rng(0).uniform(0, 1, (5, 2, 4)) \
        .astype(np.float32)
    state, _ = _scan(cfg, posts, batch=2)
    np.testing.assert_allclose(np.asarray(state.smooth), posts[-1],
                               rtol=1e-6)


# ------------------------------------------------- hysteresis state machine --

def test_fire_on_rising_edge_only_once_per_event():
    # alpha=1: score IS the posterior.  One sustained pulse above the
    # fire threshold must fire exactly once, on its first frame.
    cfg = det.DetectorConfig(smooth_alpha=1.0, fire_threshold=0.5,
                             release_threshold=0.3, refractory_frames=0,
                             first_keyword=2)
    posts = _pulse(20, 4, cls=3, start=5, end=12, level=0.9)
    state, events = _scan(cfg, posts)
    fired = np.flatnonzero(events[:, 0] != det.NO_EVENT)
    assert fired.tolist() == [5]
    assert events[5, 0] == 3
    assert int(state.active[0]) == det.NO_EVENT   # released after the pulse


def test_hysteresis_band_suppresses_rebounce():
    # Score path: 0.6 (fire) → 0.45 (inside band: stays latched) → 0.6
    # (still latched, NO new fire) → 0.2 (release) → 0.6 (fires again).
    cfg = det.DetectorConfig(smooth_alpha=1.0, fire_threshold=0.5,
                             release_threshold=0.3, refractory_frames=0,
                             first_keyword=2)
    levels = [0.6, 0.45, 0.6, 0.2, 0.6]
    posts = np.zeros((5, 1, 4), np.float32)
    for t, lv in enumerate(levels):
        posts[t, 0, 3] = lv
        posts[t, 0, 0] = 1.0 - lv
    _, events = _scan(cfg, posts)
    assert np.flatnonzero(events[:, 0] != det.NO_EVENT).tolist() == [0, 4]


def test_refractory_blocks_immediate_refire():
    cfg = det.DetectorConfig(smooth_alpha=1.0, fire_threshold=0.5,
                             release_threshold=0.3, refractory_frames=6,
                             first_keyword=2)
    # Two one-frame pulses 4 frames apart: the second is inside the
    # refractory window and must NOT fire; a third, 8 frames after the
    # first, fires.
    posts = np.zeros((12, 1, 4), np.float32)
    posts[:, 0, 0] = 1.0
    for t in (0, 4, 8):
        posts[t, 0, 3] = 0.9
        posts[t, 0, 0] = 0.1
    _, events = _scan(cfg, posts)
    assert np.flatnonzero(events[:, 0] != det.NO_EVENT).tolist() == [0, 8]


def test_non_keyword_classes_never_fire():
    cfg = det.DetectorConfig(smooth_alpha=1.0, fire_threshold=0.5,
                             first_keyword=2)
    posts = np.zeros((6, 1, 4), np.float32)
    posts[:, 0, 0] = 0.95                      # "silence" dominates
    posts[:, 0, 1] = 0.05
    _, events = _scan(cfg, posts)
    assert (events == det.NO_EVENT).all()


def test_detector_chunk_split_invariance():
    cfg = det.DetectorConfig()                 # defaults incl. smoothing
    rng = np.random.default_rng(1)
    posts = rng.dirichlet(np.ones(12) * 0.3, size=(40, 3)) \
        .astype(np.float32)
    s_full = det.init_detector_state(3, 12)
    s_full, ev_full = det.detector_scan(cfg, s_full, jnp.asarray(posts))
    s = det.init_detector_state(3, 12)
    parts = []
    for lo, hi in [(0, 7), (7, 8), (8, 29), (29, 40)]:
        s, ev = det.detector_scan(cfg, s, jnp.asarray(posts[lo:hi]))
        parts.append(np.asarray(ev))
    np.testing.assert_array_equal(np.concatenate(parts),
                                  np.asarray(ev_full))
    for a, b in zip(s, s_full):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_per_slot_independence():
    # Slot 0 sees a pulse, slot 1 silence: only slot 0 fires, and slot
    # 1's state is exactly the all-silence state.
    cfg = det.DetectorConfig(smooth_alpha=1.0, fire_threshold=0.5)
    posts = np.zeros((8, 2, 4), np.float32)
    posts[:, :, 0] = 1.0
    posts[3, 0, 3] = 0.9
    posts[3, 0, 0] = 0.1
    _, events = _scan(cfg, posts, batch=2)
    assert events[3, 0] == 3
    assert (events[:, 1] == det.NO_EVENT).all()


# ----------------------------------------------------------------- metrics --

HOUR_FRAMES = int(round(3600 / det.FRAME_S))          # 225000


def test_det_point_exact_fa_per_hour_and_miss_rate():
    truth = [(100, 130, 3), (1000, 1040, 5), (2000, 2030, 7)]
    fires = [(110, 3),        # hit
             (1500, 5),       # outside any window → FA
             (2010, 5)]       # inside event 3's window, wrong label → FA
    p = det.det_point(fires, truth, n_frames=HOUR_FRAMES)
    assert (p.n_events, p.hits, p.misses, p.false_alarms) == (3, 1, 2, 2)
    assert p.miss_rate == pytest.approx(2 / 3)
    assert p.fa_per_hour == pytest.approx(2.0)        # exactly 1 hour scored
    assert p.hours == pytest.approx(1.0)


def test_duplicate_fire_on_claimed_event_is_false_alarm():
    truth = [(100, 130, 3)]
    fires = [(110, 3), (120, 3)]
    hits, fas = det.match_fires(fires, truth)
    assert (hits, fas) == (1, 1)


def test_exact_span_match_preferred_over_tolerance_window():
    # Same-class events A then B close enough that tolerance windows
    # overlap; two fires INSIDE B must score B-hit + FA for the second
    # fire (A stays a miss) — not claim A by window spillover.
    truth = [(100, 120, 3), (140, 160, 3)]
    fires = [(145, 3), (155, 3)]
    assert det.match_fires(fires, truth, tol_frames=31) == (1, 1)
    p = det.det_point(fires, truth, n_frames=HOUR_FRAMES, tol_frames=31)
    assert (p.hits, p.misses, p.false_alarms) == (1, 1, 1)


def test_tolerance_window_extends_matching():
    truth = [(100, 130, 3)]
    assert det.match_fires([(140, 3)], truth, tol_frames=0) == (0, 1)
    assert det.match_fires([(140, 3)], truth, tol_frames=10) == (1, 0)
    assert det.match_fires([(95, 3)], truth, tol_frames=10) == (1, 0)


def test_no_events_no_fires_is_clean_zero():
    p = det.det_point([], [], n_frames=HOUR_FRAMES)
    assert p.miss_rate == 0.0 and p.fa_per_hour == 0.0


def test_fires_from_events_offsets():
    ev = np.full(10, det.NO_EVENT, np.int32)
    ev[4] = 6
    assert det.fires_from_events(ev) == [(4, 6)]
    assert det.fires_from_events(ev, frame_offset=100) == [(104, 6)]


def _event_block(rng, frames, slots, pattern):
    ev = np.full((frames, slots), det.NO_EVENT, np.int32)
    if pattern == "every":
        ev[:] = rng.integers(0, 12, (frames, slots))
    elif pattern == "sparse":
        hit = rng.random((frames, slots)) < 0.1
        ev[hit] = rng.integers(0, 12, int(hit.sum()))
    return ev


def _per_slot(pieces):
    """Each slot's fires, one ``fires_from_events`` call per slot and
    piece: the decode a serving step did before the block decode."""
    fires = [[] for _ in range(pieces[0][0].shape[1])]
    for ev, base in pieces:
        for slot in range(ev.shape[1]):
            fires[slot] += det.fires_from_events(ev[:, slot], base)
    return fires


@pytest.mark.parametrize("offset", [0, 7, 100000])
@pytest.mark.parametrize("pattern", ["none", "every", "sparse"])
@pytest.mark.parametrize("frames", [1, 32])
def test_fires_from_event_block_equals_per_slot_decode(frames, pattern,
                                                       offset):
    ev = _event_block(np.random.default_rng(frames), frames, 16, pattern)
    fires = [[] for _ in range(16)]
    for slot, frame, cls in det.fires_from_event_block(ev, offset):
        fires[slot].append((frame, cls))
    assert fires == _per_slot([(ev, offset)])
    assert all(type(x) is int for f in fires for fire in f for x in fire)
    if pattern == "every":
        assert all(len(f) == frames for f in fires)


def test_fires_from_event_block_over_multi_piece_steps():
    # Steps split into pieces of 0-3 frames, each decoded at its base:
    # every slot's fires stay in frame order across pieces and steps.
    rng = np.random.default_rng(3)
    pieces, base = [], 0
    for _ in range(40):
        frames = int(rng.integers(0, 4))
        pieces.append((_event_block(rng, frames, 8, "sparse"), base))
        base += frames
    fires = [[] for _ in range(8)]
    for ev, b in pieces:
        for slot, frame, cls in det.fires_from_event_block(ev, b):
            fires[slot].append((frame, cls))
    assert fires == _per_slot(pieces)
    assert sum(map(len, fires)) > 0


def test_pool_points_recomputes_rates_from_counts():
    a = det.det_point([(10, 3)], [(5, 20, 3)], n_frames=HOUR_FRAMES)
    b = det.det_point([(50, 4)], [(100, 120, 5)], n_frames=HOUR_FRAMES)
    pooled = det.pool_points([a, b])
    assert (pooled.n_events, pooled.hits, pooled.false_alarms) == (2, 1, 1)
    assert pooled.miss_rate == pytest.approx(0.5)
    assert pooled.fa_per_hour == pytest.approx(0.5)   # 1 FA over 2 hours


# ------------------------------------- per-keyword thresholds (ISSUE 10) --

def test_tuple_thresholds_bitwise_equal_to_scalar():
    """fire/release as uniform tuples must reproduce the scalar config
    event-for-event (the conformance suites rely on this equivalence)."""
    rng = np.random.default_rng(0)
    posts = rng.uniform(0.0, 1.0, (400, 2, 5)).astype(np.float32)
    posts /= posts.sum(-1, keepdims=True)
    scalar = det.DetectorConfig(fire_threshold=0.30, release_threshold=0.2)
    tup = scalar._replace(fire_threshold=(0.30,) * 3,
                          release_threshold=(0.2,) * 3)
    _, ev_s = _scan(scalar, posts, batch=2)
    _, ev_t = _scan(tup, posts, batch=2)
    np.testing.assert_array_equal(ev_s, ev_t)


def test_per_keyword_fire_thresholds_select_independently():
    """Class 2 needs > 0.6 while class 3 needs only > 0.3: a frame with
    (0.5, 0.35) fires class 3, not class 2."""
    cfg = det.DetectorConfig(smooth_alpha=1.0, refractory_frames=0,
                             fire_threshold=(0.6, 0.3),
                             release_threshold=(0.1, 0.1))
    posts = np.full((3, 1, 4), 0.05, np.float32)
    posts[1, 0, 2] = 0.5          # below ITS threshold
    posts[1, 0, 3] = 0.35         # above its own
    _, events = _scan(cfg, posts)
    assert events[1, 0] == 3
    # Swap the tuple: now the same frame fires class 2 instead.
    cfg2 = cfg._replace(fire_threshold=(0.3, 0.6))
    _, events2 = _scan(cfg2, posts)
    assert events2[1, 0] == 2


def test_per_keyword_release_holds_event_open():
    """The event closes only when EVERY keyword drops below its own
    release level."""
    cfg = det.DetectorConfig(smooth_alpha=1.0, refractory_frames=0,
                             fire_threshold=(0.5, 0.5),
                             release_threshold=(0.4, 0.1))
    posts = np.zeros((4, 1, 4), np.float32)
    posts[0, 0, 2] = 0.6          # fire class 2
    posts[1, 0, 3] = 0.2          # class 3 still above ITS release? no:
    posts[2, 0, 3] = 0.2          # 0.2 > 0.1 keeps the latch closed^Wopen
    _, events = _scan(cfg, posts)
    assert events[0, 0] == 2
    state = det.init_detector_state(1, 4)
    state, _ = det.detector_scan(cfg, state, jnp.asarray(posts[:3]))
    assert int(state.active[0]) == 2      # 0.2 > release[1]=0.1: open
    state2 = det.init_detector_state(1, 4)
    state2, _ = det.detector_scan(
        cfg._replace(release_threshold=(0.4, 0.3)), state2,
        jnp.asarray(posts[:3]))
    assert int(state2.active[0]) == det.NO_EVENT   # 0.2 < 0.3: released


def test_band_inverted_per_keyword():
    ok = det.DetectorConfig(fire_threshold=(0.6, 0.4),
                            release_threshold=(0.5, 0.3))
    assert not det.band_inverted(ok)
    bad = ok._replace(release_threshold=(0.5, 0.45))  # one class inverted
    assert det.band_inverted(bad)
    with pytest.raises(ValueError, match="equal lengths"):
        det.band_inverted(ok._replace(release_threshold=(0.1, 0.1, 0.1)))


def test_streaming_session_rejects_per_keyword_inverted_band():
    import jax
    from repro.configs import get_config
    from repro.launch.streaming import StreamingKwsSession
    import repro.models.kws as kws
    cfg = get_config("deltakws")
    params, _ = kws.init_kws(jax.random.PRNGKey(0), cfg, input_dim=16)
    bad = det.DetectorConfig(fire_threshold=(0.5,) * 10,
                             release_threshold=(0.4,) * 9 + (0.6,))
    with pytest.raises(ValueError, match="inverted hysteresis"):
        StreamingKwsSession(params, cfg, threshold=0.0, batch=1,
                            detector=bad)


# --------------------------------------------------- per-cell calibration --

def _calib_posts():
    """(F=30000, K=4) trace: class 2 events are strong (0.9), class 3
    events weak (0.5) with three 0.7-level false-alarm bumps."""
    posts = np.full((30000, 4), 0.01, np.float32)
    truth = []
    for s in (1000, 5000):                       # class-2 events
        posts[s:s + 41, 2] = 0.9
        truth.append((s, s + 40, 2))
    for s in (9000, 13000):                      # class-3 events
        posts[s:s + 41, 3] = 0.5
        truth.append((s, s + 40, 3))
    for s in (20000, 22000, 24000):              # class-3 FA bumps
        posts[s:s + 11, 3] = 0.7
    return posts, sorted(truth)


def test_calibration_picks_per_class_operating_points():
    posts, truth = _calib_posts()
    base = det.DetectorConfig(smooth_alpha=1.0, first_keyword=2)
    ths = det.calibrate_fire_thresholds(
        posts, truth, base, candidates=(0.35, 0.8),
        fa_budget_per_hour=10.0)                 # 0.133 h ⇒ ≤ 1 FA
    # class 2: both candidates are FA-free and hit both events → the
    # most permissive wins; class 3: 0.35 trips all three bumps (22.5
    # FA/hr, over budget) → forced up to 0.8 despite the misses.
    assert ths == (0.35, 0.8)


def test_calibration_falls_back_to_strictest_when_budget_unreachable():
    posts, truth = _calib_posts()
    posts[:, 3] = 0.95                           # class 3 fires always
    base = det.DetectorConfig(smooth_alpha=1.0, first_keyword=2)
    ths = det.calibrate_fire_thresholds(
        posts, truth, base, candidates=(0.3, 0.5),
        fa_budget_per_hour=0.5)
    assert ths[1] == 0.5                         # strictest candidate
    with pytest.raises(ValueError, match="candidates"):
        det.calibrate_fire_thresholds(posts, truth, base, candidates=())


def test_calibrated_tuple_round_trips_through_detector_scan():
    posts, truth = _calib_posts()
    base = det.DetectorConfig(smooth_alpha=1.0, first_keyword=2)
    ths = det.calibrate_fire_thresholds(posts, truth, base,
                                        candidates=(0.35, 0.8),
                                        fa_budget_per_hour=10.0)
    cfg = base._replace(fire_threshold=ths,
                        release_threshold=tuple(0.75 * t for t in ths))
    _, events = _scan(cfg, posts[:, None, :])
    fires = det.fires_from_events(events)
    hits, fas = det.match_fires(fires, truth, tol_frames=4)
    assert hits >= 2                             # both class-2 events
    assert fas == 0                              # bumps under 0.8 gate
