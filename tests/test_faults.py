"""Fault-tolerance contracts: injection replay, self-healing slots,
graceful degradation, exact telemetry (DESIGN.md §11).

Five contract families (ISSUE acceptance):
  * ``FaultPlan`` replay is BIT-EXACT and consumption-independent —
    a failing soak reproduces from two integers;
  * poisoned slots are quarantined within the supervisor's strike
    budget and a healed slot's stream is bit-identical to a fresh one
    (both numerics);
  * on clean audio the supervisor is invisible: zero recoveries and
    bit-identical decisions with it on or off;
  * the admission controller sheds at the queue bound and walks the
    Δ_TH ladder up/down with hysteresis;
  * the split-int32 telemetry counters stay exact far past the 2²⁴
    float32 wedge point and flag (rather than wrap) at capacity.
"""
import sys
import pathlib

import jax
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).parent))
from _hypothesis_compat import given, settings, st  # noqa: E402

from repro.launch.faults import (FaultInjector, FaultPlan, FaultSpec,
                                 adversarial_plan, parse_fault_specs)
from repro.launch.serve import (AdmissionController, OverloadPolicy,
                                build_parser, validate_args)
from repro.launch.streaming import (HEALTH_INPUT, QUARANTINE_DEFAULT,
                                    StreamInputError, StreamingKwsSession,
                                    SupervisorConfig, LayerCounts,
                                    _bump, _count_add, _count_value,
                                    _count_values, _count_zero,
                                    _zero_accum, _HI_SAT,
                                    _Count)

CHUNK = 512                      # 4 frames at frame_shift=128


@pytest.fixture(scope="module")
def kws_bits():
    from repro.configs import get_config
    from repro.frontend import FeatureExtractor
    from repro.models import kws
    cfg = get_config("deltakws")
    fex = FeatureExtractor()
    params, _ = kws.init_kws(jax.random.PRNGKey(0), cfg,
                             input_dim=fex.cfg.n_active)
    return params, cfg, fex


def _session(kws_bits, batch=2, **kw):
    params, cfg, fex = kws_bits
    kw.setdefault("supervisor", SupervisorConfig())
    kw.setdefault("input_policy", "trust")
    return StreamingKwsSession(params, cfg, threshold=0.1, batch=batch,
                               fex=fex, **kw)


def _audio(batch, n=CHUNK, seed=0):
    rng = np.random.default_rng(seed)
    return rng.uniform(-0.5, 0.5, (batch, n)).astype(np.float32)


# ------------------------------------------------------------ fault replay
@settings(deadline=None, max_examples=8)
@given(seed=st.integers(min_value=0, max_value=2 ** 31 - 1))
def test_fault_replay_bit_identical(seed):
    """Equal plans + equal blocks → bit-identical chunk lists and action
    logs, independent of everything else."""
    plan = adversarial_plan(seed, nan_rate=0.5, structure_rate=0.4,
                            churn_rate=0.5, stall_rate=0.3)
    a, b = FaultInjector(plan, 4), FaultInjector(plan, 4)
    for step in range(6):
        block = _audio(4, seed=step)
        ca, aa = a.inject(block)
        cb, ab = b.inject(block)
        assert aa == ab
        assert len(ca) == len(cb)
        for x, y in zip(ca, cb):
            np.testing.assert_array_equal(x, y)


def test_fault_actions_independent_of_block_content():
    """WHAT fires (kind, victims, offsets) is a function of (seed, step,
    spec) alone — different audio, same action log."""
    plan = adversarial_plan(3, nan_rate=0.5, structure_rate=0.4)
    a, b = FaultInjector(plan, 4), FaultInjector(plan, 4)
    for step in range(6):
        _, aa = a.inject(_audio(4, seed=step))
        _, ab = b.inject(_audio(4, seed=1000 + step))
        assert aa == ab


def test_removing_a_spec_does_not_reshuffle_the_others():
    """Per-spec derived rngs: dropping the LAST spec leaves every other
    spec's firings untouched (the replay contract's real payoff)."""
    full = adversarial_plan(11, nan_rate=0.5, structure_rate=0.4)
    trimmed = FaultPlan(seed=11, specs=full.specs[:-1])
    a, b = FaultInjector(full, 4), FaultInjector(trimmed, 4)
    for step in range(8):
        block = _audio(4, seed=step)
        _, aa = a.inject(block)
        _, ab = b.inject(block)
        assert [x for x in aa if x.kind != "stall"] == \
            [x for x in ab if x.kind != "stall"]


def test_fault_spec_and_parse_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        FaultSpec("gamma_ray", 0.1)
    with pytest.raises(ValueError, match="rate"):
        FaultSpec("nan_burst", 1.5)
    with pytest.raises(ValueError, match="burst_samples"):
        FaultSpec("nan_burst", 0.1, burst_samples=0)
    with pytest.raises(ValueError, match="kind:rate"):
        parse_fault_specs("nan_burst")
    specs = parse_fault_specs("nan_burst:0.05, clip:0.1")
    assert [s.kind for s in specs] == ["nan_burst", "clip"]
    assert parse_fault_specs("") == ()
    with pytest.raises(ValueError, match="slot"):
        FaultInjector(FaultPlan(0, (FaultSpec("clip", 0.1, slots=(9,)),)),
                      n_slots=4)
    with pytest.raises(ValueError, match="block"):
        FaultInjector(adversarial_plan(0), 4).inject(_audio(3))


def test_structural_faults_preserve_sample_totals():
    """Split/dup/drop reshape delivery, never invent samples: the chunk
    list's total sample count is 0, 1x, or 2x the block's."""
    plan = adversarial_plan(5, structure_rate=0.9)
    inj = FaultInjector(plan, 2)
    for step in range(10):
        chunks, actions = inj.inject(_audio(2, seed=step))
        total = sum(c.shape[1] for c in chunks)
        dropped = any(a.kind == "drop_chunk" for a in actions)
        dups = sum(a.kind == "dup_chunk" for a in actions)
        assert total == (0 if dropped else CHUNK * (2 ** dups))


# ------------------------------------------------- self-healing contracts
@pytest.mark.parametrize("numerics", ["float32", "int8"])
def test_quarantine_within_strike_budget(kws_bits, numerics):
    """A NaN-poisoned slot is flagged, quarantined within
    ``quarantine_after`` chunks, and clean afterward — and only the
    poisoned slot is touched."""
    sess = _session(kws_bits, numerics=numerics,
                    supervisor=SupervisorConfig(quarantine_after=1))
    sess.process_audio(_audio(2, seed=1))
    poison = _audio(2, seed=2)
    poison[1, :64] = np.nan
    sess.process_audio(poison)
    assert sess.unhealthy_slots().get(1, 0) & HEALTH_INPUT
    s = sess.summary()
    assert s.recoveries == 1
    assert s.recovery_reasons.get("input_nonfinite") == 1
    sess.process_audio(_audio(2, seed=3))
    assert not {k: v for k, v in sess.unhealthy_slots().items()
                if v & QUARANTINE_DEFAULT}


@pytest.mark.parametrize("numerics", ["float32", "int8"])
def test_healed_slot_bit_identical_to_fresh(kws_bits, numerics):
    """After quarantine+reset, the slot's subsequent decisions equal a
    fresh session's bit for bit (the soak's recovery gate, in small)."""
    follow = [_audio(2, seed=s) for s in (20, 21)]
    poison = _audio(2, seed=19)
    poison[0, :64] = np.nan

    healed_sess = _session(kws_bits, numerics=numerics)
    healed_sess.process_audio(poison)
    assert healed_sess.summary().recoveries == 1
    healed = [np.asarray(healed_sess.process_audio(c).votes)
              for c in follow]

    fresh_sess = _session(kws_bits, numerics=numerics)
    clean = _audio(2, seed=19)                # clean twin of the poison
    fresh_sess.process_audio(clean)
    fresh_sess.reset_streams([0])             # same reset point
    fresh = [np.asarray(fresh_sess.process_audio(c).votes)
             for c in follow]

    for h, f in zip(healed, fresh):
        np.testing.assert_array_equal(h[:, 0], f[:, 0])
        np.testing.assert_array_equal(h[:, 1], f[:, 1])  # bystander too


def test_supervisor_invisible_on_clean_audio(kws_bits):
    """Clean streams: zero recoveries, and decisions bit-identical with
    the supervisor on or off (health checks never perturb the step)."""
    on = _session(kws_bits)
    off = _session(kws_bits, supervisor=None)
    for s in range(3):
        chunk = _audio(2, seed=40 + s)
        np.testing.assert_array_equal(
            np.asarray(on.process_audio(chunk).votes),
            np.asarray(off.process_audio(chunk).votes))
    assert on.summary().recoveries == 0
    assert on.unhealthy_slots() == {}


def test_mesh_one_is_unsharded(kws_bits):
    """``make_slot_mesh(1)`` IS the unsharded engine (None), so the
    health path has a single code path at one device; and the mesh
    constructor rejects nonsense counts."""
    from repro.launch.mesh import make_slot_mesh
    assert make_slot_mesh(1) is None
    with pytest.raises(ValueError, match=">= 1"):
        make_slot_mesh(0)
    with pytest.raises(ValueError, match=">= 1"):
        make_slot_mesh(-2)
    sess = _session(kws_bits, mesh=make_slot_mesh(1))
    assert sess.n_shards == 1


# ---------------------------------------------------- input-edge policing
def test_input_policy_reject_raises_typed_error(kws_bits):
    sess = _session(kws_bits, input_policy="reject")
    bad = _audio(2)
    bad[0, 7] = np.inf
    with pytest.raises(StreamInputError):
        sess.process_audio(bad)
    assert isinstance(StreamInputError("x"), ValueError)


def test_input_policy_sanitize_matches_manual_repair(kws_bits):
    bad = _audio(2, seed=8)
    bad[0, :16] = np.nan
    bad[1, 3] = -np.inf
    repaired = np.nan_to_num(bad, nan=0.0, posinf=1.0 - 2.0 ** -11,
                             neginf=-1.0)
    a = _session(kws_bits, input_policy="sanitize")
    b = _session(kws_bits, input_policy="reject")
    np.testing.assert_array_equal(
        np.asarray(a.process_audio(bad).votes),
        np.asarray(b.process_audio(repaired).votes))
    assert a.summary().recoveries == 0        # sanitized ≠ sick


def test_integer_codes_decode_and_out_of_range_rejects(kws_bits):
    f = _audio(1, seed=9)
    codes = np.round(f * 32768.0).astype(np.int16)
    a = _session(kws_bits, batch=1)
    b = _session(kws_bits, batch=1)
    np.testing.assert_array_equal(
        np.asarray(a.process_audio(codes).votes),
        np.asarray(b.process_audio(codes.astype(np.float32)
                                   / 32768.0).votes))
    c = _session(kws_bits, batch=1)
    with pytest.raises(StreamInputError, match="range"):
        c.process_audio(np.full((1, CHUNK), 40000, np.int32))
    with pytest.raises(StreamInputError):
        c.process_audio(np.zeros((1, CHUNK), np.complex64))
    with pytest.raises(ValueError, match="input_policy"):
        _session(kws_bits, input_policy="yolo")


def _coerce_as_before(arr, policy):
    """The float policing as it was before the sum-based check: a
    float32 copy, then an exact count of the non-finite samples."""
    arr = np.asarray(arr).astype(np.float32)
    if policy == "trust":
        return arr
    n_bad = int(np.count_nonzero(~np.isfinite(arr)))
    if n_bad:
        if policy == "reject":
            raise StreamInputError(
                f"{n_bad} non-finite samples in audio chunk "
                f"(input_policy='reject'; use 'sanitize' to squash "
                f"them instead)")
        arr = np.nan_to_num(arr, nan=0.0, posinf=1.0 - 2.0 ** -11,
                            neginf=-1.0)
    return arr


def _hostile(kind):
    a = _audio(2, seed=21)
    if kind == "nan":
        a[0, 5] = np.nan
    elif kind == "posinf":
        a[1, 0] = np.inf
    elif kind == "neginf":
        a[0, -1] = -np.inf
    elif kind == "both_inf":            # their sum is NaN, not ±inf
        a[0, 3], a[1, 9] = np.inf, -np.inf
    elif kind == "overflow":            # finite, but the float32 sum is inf
        a[:, :64] = 3e38
    elif kind == "overflow_f64":        # finite float64, decoded to float32
        a = a.astype(np.float64)
        a[0, :64] = -3e38
    return a


@pytest.mark.parametrize("policy", ["reject", "sanitize", "trust"])
@pytest.mark.parametrize("kind", ["clean", "nan", "posinf", "neginf",
                                  "both_inf", "overflow", "overflow_f64"])
def test_finite_check_matches_the_exact_count(kws_bits, kind, policy):
    sess = _session(kws_bits, input_policy=policy)
    arr = _hostile(kind)
    try:
        want = _coerce_as_before(arr.copy(), policy)
    except StreamInputError as e:
        with pytest.raises(StreamInputError) as got:
            sess._coerce_audio(arr)
        assert str(got.value) == str(e)
        return
    out = sess._coerce_audio(arr)
    assert out.dtype == np.float32
    np.testing.assert_array_equal(out, want)    # NaN == NaN here
    if kind.startswith("overflow"):             # finite: never rejected
        assert np.isfinite(out).all()


@pytest.mark.parametrize("policy", ["reject", "sanitize", "trust"])
def test_clean_float32_is_not_copied_and_codes_still_decode(kws_bits,
                                                            policy):
    sess = _session(kws_bits, input_policy=policy)
    clean = _audio(2, seed=22)
    assert sess._coerce_audio(clean) is clean
    codes = np.round(clean * 32767.0).astype(np.int16)
    out = sess._coerce_audio(codes)
    assert out.dtype == np.float32
    np.testing.assert_array_equal(out, codes.astype(np.float32) / 32768.0)
    assert not np.shares_memory(out, codes)


# ------------------------------------------------------- exact telemetry
def test_split_counters_exact_past_float32_wedge():
    """The counters keep ±1 exactness past 2²⁴ — exactly where a float32
    accumulator wedges (16 777 216 + 1 == 16 777 216 in float32) — and
    past 2³¹, where an UNSPLIT int32 would wrap."""
    wedge = np.float32(1 << 24)
    assert np.float32(wedge + np.float32(1.0)) == wedge  # guarded mode
    c = _count_add(_count_add(_count_zero(1), 1 << 24), 1)
    total, saturated = _count_value(c)
    assert total == (1 << 24) + 1 and not saturated
    for _ in range(40):                      # 40 × 10⁹ > 2³¹
        c = _count_add(c, 1_000_000_000)
    total, saturated = _count_value(c)
    assert total == (1 << 24) + 1 + 40 * 1_000_000_000 and not saturated


@settings(deadline=None, max_examples=6)
@given(n=st.integers(min_value=1, max_value=60),
       d=st.integers(min_value=0, max_value=2 ** 29))
def test_split_counters_match_python_ints(n, d):
    c = _count_zero(1)
    for _ in range(n):
        c = _count_add(c, d)
    total, saturated = _count_value(c)
    assert total == n * d and not saturated


def test_split_counters_flag_saturation_instead_of_wrapping():
    import jax.numpy as jnp
    c = _Count(hi=jnp.full((1,), _HI_SAT, jnp.int32),
               lo=jnp.zeros((1,), jnp.int32))
    total, saturated = _count_value(c)
    assert saturated and total > 0
    c2 = _count_add(c, (1 << 31) - 1)        # hi stays pinned, no wrap
    total2, saturated2 = _count_value(c2)
    assert saturated2 and total2 >= total


def test_chunk_mac_counts_exact_past_int32():
    """One chunk of the 2-layer ΔGRU-768 cell (1024 slots × 32 frames,
    every Δ transmitted) is ~1.2·10^11 MACs, far past 2^31: ``_bump``
    scales each layer's int32 transmit sum inside the split counters, so
    ``macs`` and ``macs_dense`` stay exact (arrays of counts, no model
    run)."""
    import jax.numpy as jnp
    F, B, I, H = 32, 1024, 10, 768
    full = lambda n: jnp.full((F, B), n, jnp.int32)
    counts = (LayerCounts(full(I), full(H), I, H),
              LayerCounts(full(H), full(H), H, H))
    acc = _bump(_zero_accum(1, 2), counts, F * B, F * B * 128)
    dense = F * B * ((I + H) + (H + H)) * 3 * H
    assert dense > 50 * 2 ** 31
    assert _count_value(acc.macs) == (dense, False)
    assert _count_value(acc.macs_dense) == (dense, False)
    assert _count_values(acc.nz_dx) == ([F * B * I, F * B * H], False)
    assert _count_values(acc.nz_dh) == ([F * B * H, F * B * H], False)
    # Half the Δs, twice over: still exact.
    half = (LayerCounts(full(I // 2), full(H // 2), I, H),
            LayerCounts(full(H // 2), full(H // 2), H, H))
    acc = _bump(_bump(acc, half, F * B, 0), half, F * B, 0)
    assert _count_value(acc.macs) == (2 * dense, False)


@settings(deadline=None, max_examples=6)
@given(d=st.integers(min_value=0, max_value=2 ** 31 - 1),
       scale=st.integers(min_value=1, max_value=2 ** 15 - 1))
def test_scaled_count_add_matches_python_ints(d, scale):
    import jax.numpy as jnp
    c = _count_add(_count_zero(1), jnp.int32(d), scale)
    c = _count_add(c, jnp.int32(d), scale)
    assert _count_value(c) == (2 * d * scale, False)


def test_summary_tracks_host_counted_frames(kws_bits):
    sess = _session(kws_bits)
    host = 0
    for s in range(3):
        out = sess.process_audio(_audio(2, seed=60 + s))
        host += int(np.asarray(out.votes).shape[0]) * 2
    s = sess.summary()
    assert s.frames == host and not s.overflowed


# ------------------------------------------------- graceful degradation
class _StubSession:
    def __init__(self):
        self.thresholds = []

    def set_threshold(self, t):
        self.thresholds.append(t)


class _StubSched:
    def __init__(self):
        self.items = []

    def __len__(self):
        return len(self.items)

    def submit(self, payload):
        self.items.append(payload)


def _controller(max_queue=4, watchdog_ms=None):
    sess, sched = _StubSession(), _StubSched()
    pol = OverloadPolicy(thresholds=(0.1, 0.2, 0.4), max_queue=max_queue,
                         high_water=0.75, low_water=0.25, up_after=2,
                         down_after=3, watchdog_ms=watchdog_ms)
    return AdmissionController(sess, sched, pol), sess, sched


def test_controller_sheds_at_the_queue_bound():
    ctl, _, sched = _controller(max_queue=4)
    assert all(ctl.submit(i) for i in range(4))
    assert not ctl.submit(99)
    assert ctl.shed == 1 and len(sched) == 4


def test_controller_escalates_and_releases_with_hysteresis():
    ctl, sess, sched = _controller(max_queue=4)
    sched.items = [0, 1, 2, 3]                # pressure 1.0
    ctl.observe(0.001)
    assert ctl.level == 0                     # one high step < up_after
    ctl.observe(0.001)
    assert ctl.level == 1 and ctl.escalations == 1
    assert sess.thresholds[-1] == 0.2
    sched.items = [0, 1]                      # dead band: 0.5 pressure
    for _ in range(10):
        ctl.observe(0.001)
    assert ctl.level == 1                     # hysteresis holds the rung
    sched.items = []                          # low pressure
    ctl.observe(0.001)
    ctl.observe(0.001)
    assert ctl.level == 1                     # two low steps < down_after
    ctl.observe(0.001)
    assert ctl.level == 0 and ctl.releases == 1
    assert sess.thresholds[-1] == 0.1


def test_controller_dead_band_resets_streaks():
    ctl, _, sched = _controller(max_queue=4)
    sched.items = [0, 1, 2, 3]
    ctl.observe(0.001)                        # high x1
    sched.items = [0, 1]
    ctl.observe(0.001)                        # dead band: streak resets
    sched.items = [0, 1, 2, 3]
    ctl.observe(0.001)                        # high x1 again
    assert ctl.level == 0 and ctl.escalations == 0


def test_watchdog_breach_counts_as_pressure():
    ctl, _, _ = _controller(watchdog_ms=1.0)
    ctl.observe(0.5)                          # 500 ms step, empty queue
    ctl.observe(0.5)
    assert ctl.watchdog_breaches == 2 and ctl.level == 1


def test_controller_caps_at_the_top_rung():
    ctl, _, sched = _controller(max_queue=4)
    sched.items = [0, 1, 2, 3]
    for _ in range(20):
        ctl.observe(0.001)
    assert ctl.level == 2 and ctl.escalations == 2
    assert ctl.stats()["threshold"] == 0.4


def test_overload_policy_validation():
    with pytest.raises(ValueError, match="ascending"):
        OverloadPolicy(thresholds=(0.4, 0.1))
    with pytest.raises(ValueError, match="ascending"):
        OverloadPolicy(thresholds=(0.1, 0.1))
    with pytest.raises(ValueError, match="rung"):
        OverloadPolicy(thresholds=())
    with pytest.raises(ValueError, match="low_water"):
        OverloadPolicy(high_water=0.2, low_water=0.6)
    with pytest.raises(ValueError, match="max_queue"):
        OverloadPolicy(max_queue=0)
    with pytest.raises(ValueError, match="up_after"):
        OverloadPolicy(up_after=0)


# ------------------------------------------------------- CLI validation
def _args(*extra):
    return build_parser().parse_args(["--mode", "kws-audio", *extra])


@pytest.mark.parametrize("flags,match", [
    (("--slots", "7", "--devices", "2"), "divide"),
    (("--slots", "0"), "slots"),
    (("--threshold", "-0.5"), "threshold"),
    (("--threshold", "nan"), "threshold"),
    (("--watchdog-ms", "-1"), "watchdog"),
    (("--max-queue", "0"), "max-queue"),
    (("--faults", "bogus_kind:0.5"), "fault"),
    (("--faults", "nan_burst"), "kind:rate"),
    (("--degrade-thresholds", "0.05"), "ascending"),
])
def test_validate_args_rejects(flags, match):
    with pytest.raises(ValueError, match=match):
        validate_args(_args(*flags))


def test_validate_args_accepts_the_documented_fault_run():
    import shlex
    from repro import commands
    words = shlex.split(commands.SERVE_FAULTS_CMD)
    flags = words[words.index("repro.launch.serve") + 1:]
    validate_args(build_parser().parse_args(flags))


def test_soak_cli_parses_the_documented_command():
    import importlib
    import shlex
    from repro import commands
    sb = importlib.import_module("benchmarks.serve_bench")
    words = shlex.split(commands.SOAK_CMD)
    args = sb.build_parser().parse_args(words[words.index(
        "benchmarks/serve_bench.py") + 1:])
    assert args.soak and args.cooldown_steps > 8  # > down_after: releases


# ------------------------------------------------ data-layer fail-early
def test_continuous_stream_rejects_bad_combinations():
    from repro.data.continuous import (make_stream, make_streams,
                                       synth_frame_batch)
    rng = np.random.default_rng(0)
    for kw, match in [
        (dict(duration_s=0.0), "duration_s"),
        (dict(duration_s=-5.0), "duration_s"),
        (dict(duration_s=np.nan), "duration_s"),
        (dict(snr_db=np.inf), "snr_db"),
        (dict(events_per_min=-1.0), "events_per_min"),
        (dict(min_gap_s=-0.1), "min_gap_s"),
        (dict(keyword_classes=()), "keyword_classes"),
        (dict(keyword_classes=(0,)), "keyword"),   # silence can't place
    ]:
        with pytest.raises(ValueError, match=match):
            make_stream(np.random.default_rng(0), **kw)
    with pytest.raises(ValueError, match="n_streams"):
        make_streams(0, 0, duration_s=1.0)
    with pytest.raises(ValueError, match="frame"):
        synth_frame_batch(rng, 1, duration_s=0.005)
    # the boundary existing callers sit on still works
    s = make_stream(np.random.default_rng(0), duration_s=1.0)
    assert s.duration_s == 1.0
