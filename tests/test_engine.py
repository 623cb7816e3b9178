"""Async pipelined serving engine (DESIGN.md §14).

Three families:

* Fake-clock SLO telemetry — the engine's percentile / host-span /
  throughput math checked against a scripted clock and a stub session
  (the regression tests for the serve-loop timing-skew bugfix: wall
  timing must come from the injectable monotonic clock, warmup must
  stay out of steady state).
* Conformance — ``depth>=2`` must equal ``depth=1`` (the synchronous
  loop) decision for decision and counter for counter, in float AND
  int8, under churn storms, chunk-splitting fault plans and (slow,
  child process) mesh=2.
* Scheduler guards — the double-evict / unknown-slot ``ValueError``
  and the unhealthy-slot admission refusal.
"""
import dataclasses
import pathlib
import subprocess
import sys
import types

import numpy as np
import pytest

REPO = pathlib.Path(__file__).parent.parent


# ---------------------------------------------------------------------------
# Fake clock + stub session: telemetry math with zero device noise

class FakeClock:
    """Monotonic clock advancing by a scripted amount per call."""

    def __init__(self, ticks):
        self.ticks = list(ticks)
        self.now = 0.0

    def __call__(self):
        if self.ticks:
            self.now += self.ticks.pop(0)
        return self.now


class _StubOut:
    def __init__(self, frames, batch):
        self.votes = np.zeros((frames, batch), np.int32)


class StubSession:
    """Shape-compatible stand-in: 4 frames per piece, no device."""

    def __init__(self, batch=2):
        self.batch = batch

    def process_audio(self, piece):
        return _StubOut(4, self.batch)


def test_percentiles_ms_math():
    from repro.launch.engine import percentiles_ms
    assert percentiles_ms([]) == {"p50": 0.0, "p99": 0.0, "p999": 0.0}
    p = percentiles_ms([0.001] * 99 + [0.101])   # one 101 ms straggler
    assert p["p50"] == pytest.approx(1.0)
    # p99.9 sits closer to the straggler than p99 does — the tail field
    # exists precisely to catch what p99 averages away.
    assert p["p999"] > p["p99"] >= p["p50"]


def test_engine_rejects_bad_depth():
    from repro.launch.engine import PipelinedEngine
    with pytest.raises(ValueError, match="depth"):
        PipelinedEngine(StubSession(), depth=0)


class SpanStubSession(StubSession):
    """A StubSession whose ``process_audio`` enters the dispatch phases
    (police, frame, h2d, launch) the real session enters."""

    def process_audio(self, piece):
        from repro.launch import spans
        for name in spans.DISPATCH:
            with spans.span(name):
                pass
        return super().process_audio(piece)


def test_fake_clock_phase_attribution():
    # Scripted clock, one tick per read: begin +0; the assemble span
    # (+0, +1ms); police, frame, h2d, launch (+0, +0.5ms each: the 2ms
    # dispatch); fetch (+0, +3ms); the e2e read +0; end (+1ms).
    # depth=1 → the fetch happens inside submit.
    from repro.launch import spans
    from repro.launch.engine import PipelinedEngine
    clk = FakeClock([0.0, 0.0, 0.001] + [0.0, 0.0005] * 4
                    + [0.0, 0.003, 0.0, 0.001])
    eng = PipelinedEngine(SpanStubSession(batch=2), depth=1, clock=clk)
    eng.begin()
    with spans.span(spans.ASSEMBLE):
        pass
    piece_frames, drained = eng.submit([None])
    eng.end()
    assert piece_frames == [4] and len(drained) == 1
    assert drained[0].n_frames == 4
    rep = eng.report()
    hb = rep["host_blocked_ms_per_step"]
    assert hb["assemble"] == pytest.approx(1.0)
    assert hb["dispatch"] == pytest.approx(2.0)
    assert hb["fetch"] == pytest.approx(3.0)
    assert hb["total"] == pytest.approx(6.0)
    # The same phases under their span names; the 1 ms between the
    # fetch and end() is in the step but in no phase.
    host = rep["host_ms_per_step"]
    assert host["kws.police"] == pytest.approx(0.5)
    assert host["kws.fetch"] == pytest.approx(3.0)
    assert host["untraced"] == pytest.approx(1.0)
    # Step wall time = everything from begin to end = 7 ms.
    assert rep["step_ms"]["p50"] == pytest.approx(7.0)
    assert host["kws.step"] == pytest.approx(7.0)
    # e2e decision latency = begin → fetch done = 6 ms.
    assert rep["e2e_ms"]["p50"] == pytest.approx(6.0)
    assert rep["decisions"] == 4 * 2
    # Steady-state throughput uses first-begin → last-end wall time, so
    # 8 decisions in 7 ms.
    assert rep["steady_state_s"] == pytest.approx(0.007)
    assert rep["decisions_per_s_steady"] == pytest.approx(8 / 0.007)


def test_fake_clock_depth2_overlaps_fetch():
    # With depth=2, step 1's submit does NOT fetch (queue fits); the
    # fetch of step 1 happens during step 2 — e2e latency spans both
    # steps while per-step host-blocked fetch time stays put.
    from repro.launch.engine import PipelinedEngine
    clk = FakeClock([1.0] * 64)              # 1 s per clock read
    eng = PipelinedEngine(StubSession(), depth=2, clock=clk)
    eng.begin()
    _, drained = eng.submit([None])
    eng.end()
    assert drained == [] and eng.in_flight == 1
    eng.begin()
    _, drained = eng.submit([None])
    eng.end()
    assert [f.index for f in drained] == [0]
    assert [f.index for f in eng.flush()] == [1]
    assert eng.in_flight == 0
    rep = eng.report()
    assert rep["depth"] == 2 and rep["steps"] == 2
    # Step 0's e2e crossed into step 1: strictly longer than any step.
    assert rep["e2e_ms"]["p999"] > rep["step_ms"]["p999"]


def test_reset_telemetry_keeps_queue():
    from repro.launch.engine import PipelinedEngine
    eng = PipelinedEngine(StubSession(), depth=3, clock=FakeClock([1.0] * 64))
    for _ in range(2):
        eng.begin()
        eng.submit([None])
        eng.end()
    assert eng.in_flight == 2
    eng.reset_telemetry()                    # warmup boundary in benches
    assert eng.in_flight == 2                # in-flight steps survive
    assert eng.report()["host_blocked_ms_per_step"]["total"] == 0.0
    assert len(eng.flush()) == 2             # and still drain afterwards


def test_fetch_order_is_dispatch_order_and_meta_rides_along():
    from repro.launch.engine import PipelinedEngine
    eng = PipelinedEngine(StubSession(), depth=4, clock=FakeClock([0.0] * 99))
    metas = []
    for i in range(3):
        eng.begin()
        m = []                              # mutable, filled post-submit
        eng.submit([None], meta=m)
        m.append(i)
        metas.append(m)
        eng.end()
    drained = eng.flush()
    assert [f.index for f in drained] == [0, 1, 2]
    assert [f.meta for f in drained] == [[0], [1], [2]]


# ---------------------------------------------------------------------------
# Conformance: async == sync, bit for bit

def _session_bits():
    import jax
    from repro.configs import get_config
    from repro.frontend import FeatureExtractor
    from repro.models import kws
    cfg = get_config("deltakws")
    fex = FeatureExtractor()
    params, _ = kws.init_kws(jax.random.PRNGKey(0), cfg,
                             input_dim=fex.cfg.n_active)
    return cfg, fex, params


def _audio_run(depth, *, numerics="float32", faults=None, chunk=1000,
               requests=5, slots=2, utt=4000, record=None):
    """One full kws-audio serve through the loop driver at ``depth``
    (0.5 s utterances).  ``record``, a list, receives per dispatched
    piece a copy of the block as handed to the session and its (device)
    vote array."""
    from repro.launch.engine import run_audio_requests
    from repro.launch.faults import FaultInjector, FaultPlan, \
        parse_fault_specs
    from repro.launch.streaming import SlotScheduler, StreamingKwsSession
    cfg, fex, params = _session_bits()
    rng = np.random.default_rng(11)
    audio_q = rng.uniform(-0.5, 0.5, (requests, utt)).astype(np.float32)
    sess = StreamingKwsSession(params, cfg, threshold=0.1, batch=slots,
                               fex=fex, numerics=numerics,
                               input_policy="trust")
    if record is not None:
        serve = sess.process_audio

        def process_audio(block):
            out = serve(block)
            record.append((np.array(block), out.votes))
            return out
        sess.process_audio = process_audio
    sched = SlotScheduler(sess)
    for req in range(requests):
        sched.submit(req)
    injector = None
    if faults:
        injector = FaultInjector(FaultPlan(seed=5,
                                           specs=parse_fault_specs(faults)),
                                 slots)
    done, stats = run_audio_requests(
        sess, sched, ctl=None, audio_q=audio_q, chunk=chunk,
        chunks_per_utt=-(-utt // chunk),
        real_frames=utt // fex.cfg.frame_shift,
        injector=injector, depth=depth, warm=False)
    summ = dataclasses.replace(sess.summary(), slo={})   # timing differs
    return done, stats, summ


@pytest.fixture(scope="module")
def fresh_block_run():
    """The synchronous loop with a fresh zeroed block every step — the
    loop as it was before the staging ring — run once per numerics."""
    from repro.launch.engine import PipelinedEngine
    runs: dict = {}

    def run(numerics):
        if numerics not in runs:
            record: list = []
            with pytest.MonkeyPatch.context() as mp:
                mp.setattr(PipelinedEngine, "staging",
                           lambda self, shape, dtype=np.float32:
                           np.zeros(shape, dtype))
                done, stats, summ = _audio_run(1, numerics=numerics,
                                               record=record)
            runs[numerics] = done, stats, summ, record
        return runs[numerics]
    return run


@pytest.mark.parametrize("depth", [1, 2, 3])
@pytest.mark.parametrize("numerics", ["float32", "int8"])
def test_audio_conformance_async_equals_sync(fresh_block_run, numerics,
                                             depth):
    # chunk=1000 is NOT frame-aligned (frame shift 128): every step
    # carries a sample remainder across the chunk boundary, the hardest
    # alignment case for late integration.  The reference is the sync
    # loop on fresh blocks; every depth runs on the engine's staging
    # ring, whose blocks the CPU device reads in place.
    done1, stats1, summ1, rec1 = fresh_block_run(numerics)
    rec: list = []
    done2, stats2, summ2 = _audio_run(depth, numerics=numerics, record=rec)
    assert done2 == done1                   # same requests, same classes
    assert summ2 == summ1                   # every telemetry counter
    assert stats2["frames_served"] == stats1["frames_served"]
    assert stats2["pad_frames"] == stats1["pad_frames"]
    assert stats2["steps"] == stats1["steps"]
    assert len(rec) == len(rec1)
    for (block2, votes2), (block1, votes1) in zip(rec, rec1):
        np.testing.assert_array_equal(block2, block1)
        np.testing.assert_array_equal(np.asarray(votes2),
                                      np.asarray(votes1))
    # No chunk but the first starts on a frame boundary (1000·k mod 128
    # is 0 only at k = 16), so every later step joins a remainder.
    assert summ2.frame_joins == stats2["steps"] - 1


def test_audio_conformance_under_fault_storms():
    # Churn storms re-admit mid-flight; chunk splits (one_sample_chunk)
    # make multi-piece steps; drops make zero-frame steps.  The async
    # pipeline must integrate every vote into the incarnation that was
    # live at dispatch — depth 3 keeps two steps unfetched across the
    # storms.
    faults = "churn_storm:0.2,one_sample_chunk:0.25,drop_chunk:0.15"
    done1, stats1, summ1 = _audio_run(1, faults=faults)
    done3, stats3, summ3 = _audio_run(3, faults=faults)
    assert done3 == done1
    assert summ3 == summ1
    assert stats3["frames_served"] == stats1["frames_served"]


# ---------------------------------------------------------------------------
# Differential: the slot-axis bookkeeping against the per-slot loop it
# replaced.  ``oracle_run_audio_requests`` is that loop, kept verbatim as
# the reference; ``ReplaySession`` feeds both the same vote arrays.

class _OracleIncarnation:
    """One admitted stream life on a slot: vote tally + chunk progress.

    A churn storm or re-admission starts a NEW incarnation, so a fetch
    landing after the slot was recycled still credits the life that was
    live when its chunk was dispatched.  ``progress`` is the sync
    loop's per-slot [chunks consumed, real frames left to vote on]."""

    __slots__ = ("req", "counts", "progress")

    def __init__(self, req, n_classes, real_frames):
        self.req = req
        self.counts = np.zeros(n_classes, np.int64)
        self.progress = [0, real_frames]


def oracle_run_audio_requests(sess, sched, ctl, *, audio_q, chunk: int,
                              chunks_per_utt: int, real_frames: int,
                              injector=None, depth: int = 1,
                              warm: bool = True, clock=None):
    """The utterance loop with per-slot Python bookkeeping, as it was
    before the slot state became arrays."""
    import time
    from repro.launch import spans
    from repro.launch.engine import FetchedStep, PipelinedEngine, \
        warm_session
    _Incarnation = _OracleIncarnation
    eng = PipelinedEngine(sess, depth=depth, field="votes",
                          scheduler=sched, clock=clock or time.perf_counter)
    warmup_s = warm_session(sess, chunk) if warm else 0.0

    incarnations: dict[int, _Incarnation] = {}   # slot -> current life
    order: list[_Incarnation] = []               # eviction order
    frames_served = pad_frames = steps = 0

    def integrate(f: FetchedStep):
        v = (np.concatenate(f.arrays, axis=0) if f.arrays
             else np.zeros((0, sess.batch), np.int32))
        for inc, slot, n_real in f.meta:
            inc.counts += np.bincount(v[:n_real, slot],
                                      minlength=sess.n_classes)

    def admit():
        admitted = sched.admit()
        with spans.span(spans.SLOTS):
            for slot, req in admitted:
                incarnations[slot] = _Incarnation(req, sess.n_classes,
                                                  real_frames)

    admit()
    while not sched.idle:
        eng.begin()
        with spans.span(spans.ASSEMBLE):
            block = eng.staging((sess.batch, chunk))
            idle = np.ones(sess.batch, bool)
            for slot, req in sched.live.items():
                c0 = incarnations[slot].progress[0]
                seg = audio_q[req, c0 * chunk:(c0 + 1) * chunk]
                n = len(seg)
                block[slot, :n] = seg
                if n < chunk:
                    block[slot, n:] = 0.0       # zero-pad a short chunk
                idle[slot] = False
            block[idle] = 0.0                   # idle slots hear silence
            pieces, actions = ([block], []) if injector is None \
                else injector.inject(block)
        contribs: list[tuple] = []          # filled below, post-submit
        piece_frames, drained = eng.submit(pieces, meta=contribs)
        n_f = sum(piece_frames)
        with spans.span(spans.SLOTS):
            for act in actions:             # driver directives
                if act.kind == "stall":
                    time.sleep(act.detail)
                elif act.kind == "churn_storm":
                    storm = [s for s in act.slots if s in sched.live]
                    sess.reset_streams(storm)   # poof — streams restart
                    for s in storm:             # same request, new life
                        incarnations[s] = _Incarnation(sched.live[s],
                                                       sess.n_classes,
                                                       real_frames)
            pad_frames += n_f * (sess.batch - len(sched.live))  # idle
            for slot in list(sched.live):
                inc = incarnations[slot]
                st = inc.progress
                n_real = min(n_f, st[1])
                contribs.append((inc, slot, n_real))
                st[1] -= n_real
                frames_served += n_real
                pad_frames += n_f - n_real
                st[0] += 1
                if st[0] >= chunks_per_utt:
                    sched.evict(slot)
                    order.append(inc)
            for f in drained:
                integrate(f)
        admit()
        steps += 1
        eng.end()
        if ctl is not None:
            ctl.observe(eng.last_step_s)
    for f in eng.flush():
        integrate(f)

    done = [(inc.req, int(inc.counts.argmax())) for inc in order]
    slo = eng.report()
    slo["warmup_s"] = warmup_s
    sess.attach_slo(slo)
    return done, {"steps": steps, "frames_served": frames_served,
                  "pad_frames": pad_frames, "warmup_s": warmup_s,
                  "slo": slo}


class ReplaySession:
    """A session stand-in that plays recorded votes: call ``k`` of
    ``process_audio`` returns the votes seeded by ``k``, over the frames
    a 128-sample frame shift completes (a carried remainder included).
    ``log`` records every device operation the loop issues, in order:
    each piece (its width and a digest of its samples) and each
    ``reset_streams`` wave.  Few classes, so tallies often tie."""

    frame_shift = 128

    def __init__(self, batch=3, n_classes=3, n_shards=1, seed=0):
        self.batch = batch
        self.n_classes = n_classes
        self.n_shards = n_shards
        self.seed = seed
        self.log: list[tuple] = []
        self._carry = 0

    def shard_of_slot(self, i):
        return i // (self.batch // self.n_shards)

    def flagged_slots(self):
        return frozenset()

    def reset_streams(self, slots):
        self.log.append(("reset", tuple(int(s) for s in slots)))

    def attach_slo(self, report):
        pass

    def process_audio(self, piece):
        piece = np.asarray(piece)
        width = self._carry + piece.shape[1]
        frames, self._carry = divmod(width, self.frame_shift)
        call = sum(1 for op in self.log if op[0] == "piece")
        self.log.append(("piece", piece.shape[1], hash(piece.tobytes())))
        rng = np.random.default_rng([self.seed, call])
        votes = rng.integers(0, self.n_classes, (frames, self.batch),
                             dtype=np.int32)
        return types.SimpleNamespace(votes=votes)


_STORMS = "churn_storm:0.2,one_sample_chunk:0.25,drop_chunk:0.15"


def _replay_run(loop, depth, *, faults=None, requests=12, slots=3,
                shards=1, utt=4000, chunk=1000):
    from repro.launch.faults import FaultInjector, FaultPlan, \
        parse_fault_specs
    from repro.launch.streaming import SlotScheduler
    sess = ReplaySession(batch=slots, n_shards=shards)
    sched = SlotScheduler(sess)
    for req in range(requests):
        sched.submit(req)
    audio_q = np.random.default_rng(11).uniform(
        -0.5, 0.5, (requests, utt)).astype(np.float32)
    injector = None
    if faults:
        injector = FaultInjector(FaultPlan(seed=5,
                                           specs=parse_fault_specs(faults)),
                                 slots)
    done, stats = loop(sess, sched, None, audio_q=audio_q, chunk=chunk,
                       chunks_per_utt=-(-utt // chunk),
                       real_frames=utt // ReplaySession.frame_shift,
                       injector=injector, depth=depth, warm=False)
    return done, stats, sess.log


@pytest.fixture
def vote_tables(monkeypatch):
    """The vote tables the loops under test build, for their size."""
    from repro.launch import engine
    tables = []

    class Recorded(engine._VoteTable):
        def __init__(self, *args):
            super().__init__(*args)
            tables.append(self)
    monkeypatch.setattr(engine, "_VoteTable", Recorded)
    return tables


@pytest.mark.parametrize("depth", [1, 2, 3])
@pytest.mark.parametrize("traffic", [
    dict(),                                 # clean
    dict(faults=_STORMS, requests=40),      # storms, splits, drops
    # Two shards admit slots out of index order (0, 2, 1, 3).
    dict(slots=4, shards=2, requests=14),
    dict(slots=4, shards=2, requests=40, faults=_STORMS),
], ids=["clean", "storms", "shards2", "shards2-storms"])
def test_audio_loop_equals_per_slot_oracle(vote_tables, traffic, depth):
    from repro.launch.engine import run_audio_requests
    want_done, want, want_log = _replay_run(oracle_run_audio_requests,
                                            depth, **traffic)
    done, got, log = _replay_run(run_audio_requests, depth, **traffic)
    assert done == want_done                # same pairs, same order
    assert len(done) == traffic.get("requests", 12)
    if "faults" in traffic:                 # restarts reorder the answers
        assert [r for r, _ in done] != sorted(r for r, _ in done)
    for key in ("frames_served", "pad_frames", "steps"):
        assert got[key] == want[key], key
    assert log == want_log                  # device operations, in order
    slots = traffic.get("slots", 3)
    table, = vote_tables
    # Clean traffic holds at most batch · depth lives; one storm a step
    # at most doubles that.
    bound = slots * depth * (2 if "faults" in traffic else 1)
    assert len(table.counts) <= bound


def test_audio_loop_table_stays_bounded_over_a_long_run(vote_tables):
    # 5,000 requests through 8 slots at depth 2: the count table keeps
    # its batch · depth rows and reuses them, and the answers still
    # equal the per-slot loop's.
    from repro.launch.engine import run_audio_requests
    kw = dict(requests=5000, slots=8, utt=2000, chunk=1024)
    want_done, want, want_log = _replay_run(oracle_run_audio_requests, 2,
                                            **kw)
    done, got, log = _replay_run(run_audio_requests, 2, **kw)
    assert done == want_done and len(done) == 5000
    assert (got["frames_served"], got["pad_frames"], got["steps"]) == \
        (want["frames_served"], want["pad_frames"], want["steps"])
    assert log == want_log
    table, = vote_tables
    assert table.counts.shape == (8 * 2, 3)
    assert len(table.requests) == 8 * 2


def test_detect_conformance_async_equals_sync():
    from repro.launch.streaming import StreamingKwsSession
    from repro.launch.engine import run_continuous_detect
    from repro.models.detector import DetectorConfig
    cfg, fex, params = _session_bits()
    rng = np.random.default_rng(12)
    audio = rng.uniform(-0.5, 0.5, (2, 6144)).astype(np.float32)

    def run(depth):
        sess = StreamingKwsSession(params, cfg, threshold=0.1, batch=2,
                                   fex=fex, detector=DetectorConfig())
        fires, base, stats = run_continuous_detect(
            sess, list(audio), chunk=2048, n_samples=6144,
            depth=depth, warm=False)
        return fires, base, dataclasses.replace(sess.summary(), slo={})

    f1, b1, s1 = run(1)
    f2, b2, s2 = run(2)
    assert f2 == f1 and b2 == b1 and s2 == s1


def test_summary_carries_slo_block():
    # The serve loops attach the engine report to the session summary.
    done, stats, _ = _audio_run(2)
    assert done                             # everything served
    slo = stats["slo"]
    for key in ("step_ms", "e2e_ms", "host_blocked_ms_per_step",
                "host_ms_per_step", "shard_imbalance",
                "decisions_per_s_steady"):
        assert key in slo
    assert slo["step_ms"].keys() == {"p50", "p99", "p999"}


# ---------------------------------------------------------------------------
# The staging ring and block ownership

def test_staging_ring_reuses_a_block_only_after_its_fetch():
    from repro.launch.engine import PipelinedEngine
    depth = 3
    eng = PipelinedEngine(StubSession(), depth=depth,
                          clock=FakeClock([0.0] * 999))
    blocks = []
    for k in range(8):
        eng.begin()
        buf = eng.staging((2, 8))
        assert buf.shape == (2, 8) and buf.dtype == np.float32
        assert buf.ctypes.data % 64 == 0    # CPU asarray shares it
        # Never a block an unfetched step holds; the one of the step
        # ``depth`` back, which the last submit fetched.
        unfetched = blocks[len(blocks) - eng.in_flight:]
        assert all(buf is not b for b in unfetched)
        if k >= depth:
            assert buf is blocks[k - depth]
        blocks.append(buf)
        eng.submit([buf])
        eng.end()
    rep = eng.report()
    assert rep["staging_allocated"] == depth
    assert rep["staging_reused"] == 8 - depth
    eng.begin()
    wide = eng.staging((2, 16))             # a new shape: a new ring
    assert all(wide is not b for b in blocks)
    assert eng.staging_allocated == depth + 1
    assert eng.staging((2, 16), np.float64).dtype == np.float64
    assert eng.staging_allocated == depth + 2


@pytest.mark.parametrize("depth", [1, 2])
def test_staging_idle_rows_and_short_tails_are_zero(depth):
    # 3 slots, 4 requests of 4000 samples in 1024-sample chunks: steps
    # 0-3 serve requests 0-2, the last chunk 928 samples wide; steps
    # 4-7 serve request 3 alone, so two slots that held audio go idle.
    # The ring hands back blocks that held those rows: stale samples
    # would show.
    utt, chunk = 4000, 1024
    rec: list = []
    _audio_run(depth, chunk=chunk, requests=4, slots=3, utt=utt,
               record=rec)
    audio_q = np.random.default_rng(11).uniform(
        -0.5, 0.5, (4, utt)).astype(np.float32)
    assert len(rec) == 8
    for k, (block, _) in enumerate(rec):
        c = k % 4
        want = []
        for req in (range(3) if k < 4 else [3]):
            row = np.zeros(chunk, np.float32)
            seg = audio_q[req, c * chunk:(c + 1) * chunk]
            row[:len(seg)] = seg
            want.append(row)
        live = [row for row in block if np.any(row != 0)]
        key = lambda r: r[0]                # audio samples are never 0
        assert len(live) == len(want)
        for got, exp in zip(sorted(live, key=key), sorted(want, key=key)):
            np.testing.assert_array_equal(got, exp)
        zero = [row for row in block if not np.any(row != 0)]
        assert len(zero) == 3 - len(want)
        assert all(not np.signbit(row).any() for row in zero)


@pytest.mark.parametrize("depth", [1, 2, 3])
def test_staging_counters_on_aligned_chunks(depth):
    from repro.launch.engine import run_continuous_detect
    from repro.launch.streaming import StreamingKwsSession
    from repro.models.detector import DetectorConfig
    # 1024 = 8 frames: no step carries a remainder.
    _, stats, summ = _audio_run(depth, chunk=1024)
    slo = stats["slo"]
    assert slo["staging_allocated"] == depth
    assert slo["staging_reused"] == stats["steps"] - depth
    assert slo["frame_joins"] == 0 and summ.frame_joins == 0
    cfg, fex, params = _session_bits()
    audio = np.random.default_rng(12).uniform(
        -0.5, 0.5, (2, 6144)).astype(np.float32)
    sess = StreamingKwsSession(params, cfg, threshold=0.1, batch=2,
                               fex=fex, detector=DetectorConfig())
    _, _, stats = run_continuous_detect(sess, list(audio), chunk=1024,
                                        n_samples=6144, depth=depth,
                                        warm=False)
    slo = stats["slo"]
    assert slo["staging_allocated"] == depth
    assert slo["staging_reused"] == 6 - depth
    assert slo["frame_joins"] == 0 and sess.summary().frame_joins == 0


@pytest.mark.parametrize("batch", [1, 2])
def test_frame_path_keeps_no_view_of_the_callers_array(batch):
    # Non-aligned 1000-sample chunks fed through ONE 64-byte aligned
    # buffer (CPU asarray reads it in place): after each step's result
    # is fetched the buffer is poisoned with NaN, then refilled.  A
    # carried remainder that viewed the buffer would carry the NaN into
    # the next step; the run must equal one fed fresh arrays.
    from repro.launch.engine import _aligned_empty
    from repro.launch.streaming import StreamingKwsSession
    cfg, fex, params = _session_bits()
    audio = np.random.default_rng(13).uniform(
        -0.5, 0.5, (batch, 6000)).astype(np.float32)

    def session():
        return StreamingKwsSession(params, cfg, threshold=0.1, batch=batch,
                                   fex=fex, input_policy="trust")

    reused, fresh = session(), session()
    buf = _aligned_empty((batch, 1000), np.dtype(np.float32))
    for lo in range(0, 6000, 1000):
        buf[:] = audio[:, lo:lo + 1000]
        got = reused.process_audio(buf)
        got = [np.asarray(got.logits), np.asarray(got.votes)]
        buf[:] = np.nan
        want = fresh.process_audio(audio[:, lo:lo + 1000].copy())
        np.testing.assert_array_equal(got[0], np.asarray(want.logits))
        np.testing.assert_array_equal(got[1], np.asarray(want.votes))
    assert reused.summary() == fresh.summary()
    assert reused.frame_joins == fresh.frame_joins == 5


ENGINE_MESH_CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import dataclasses
import numpy as np
import jax
from repro.configs import get_config
from repro.frontend import FeatureExtractor
from repro.launch.engine import run_audio_requests
from repro.launch.mesh import make_slot_mesh
from repro.launch.streaming import SlotScheduler, StreamingKwsSession
from repro.models import kws

cfg = get_config("deltakws")
fex = FeatureExtractor()
params, _ = kws.init_kws(jax.random.PRNGKey(0), cfg,
                         input_dim=fex.cfg.n_active)
utt, chunk, requests = 4000, 1000, 6
rng = np.random.default_rng(11)
audio_q = rng.uniform(-0.5, 0.5, (requests, utt)).astype(np.float32)

def run(depth, loop=run_audio_requests):
    sess = StreamingKwsSession(params, cfg, threshold=0.1, batch=4,
                               fex=fex, mesh=make_slot_mesh(2))
    assert sess.n_shards == 2
    sched = SlotScheduler(sess)
    for req in range(requests):
        sched.submit(req)
    done, stats = loop(
        sess, sched, ctl=None, audio_q=audio_q, chunk=chunk,
        chunks_per_utt=-(-utt // chunk),
        real_frames=utt // fex.cfg.frame_shift, depth=depth, warm=False)
    return done, dataclasses.replace(sess.summary(), slo={}), stats

d1, s1, st1 = run(1)
d2, s2, st2 = run(2)
assert d2 == d1, (d1, d2)
assert s2 == s1
assert st2["frames_served"] == st1["frames_served"]
assert st2["slo"]["shard_imbalance"]["max"] <= 1
# The per-slot loop the slot-axis bookkeeping replaced, at each depth.
from test_engine import oracle_run_audio_requests
for depth, got in ((1, (d1, s1, st1)), (2, (d2, s2, st2)), (3, run(3))):
    want = run(depth, oracle_run_audio_requests)
    assert got[0] == want[0], (depth, got[0], want[0])
    assert got[1] == want[1]
    for key in ("frames_served", "pad_frames", "steps"):
        assert got[2][key] == want[2][key], (depth, key)
print("ENGINE_MESH2_OK")
"""


@pytest.mark.slow
def test_engine_mesh2_conformance():
    import os
    r = subprocess.run(
        [sys.executable, "-c", ENGINE_MESH_CHILD], capture_output=True,
        text=True, env={**os.environ, "PYTHONPATH":
                        f"{REPO / 'src'}{os.pathsep}{REPO / 'tests'}"},
        timeout=540)
    assert r.returncode == 0, f"{r.stdout[-2000:]}\n{r.stderr[-3000:]}"
    assert "ENGINE_MESH2_OK" in r.stdout


# ---------------------------------------------------------------------------
# Scheduler guards (regression: double evict used to corrupt the free
# list via a bare KeyError path; unhealthy slots used to be re-admitted)

def _sched():
    from repro.launch.streaming import SlotScheduler, StreamingKwsSession
    cfg, fex, params = _session_bits()
    sess = StreamingKwsSession(params, cfg, threshold=0.1, batch=4, fex=fex)
    return sess, SlotScheduler(sess)


def test_evict_unknown_slot_raises_valueerror():
    _sess, sched = _sched()
    # A never-admitted slot is on the free list — the error names that
    # state instead of the old bare KeyError.
    with pytest.raises(ValueError, match=r"slot 0.*free"):
        sched.evict(0)
    with pytest.raises(ValueError, match=r"slot 9.*out of range"):
        sched.evict(9)


def test_double_evict_raises_not_corrupts():
    _sess, sched = _sched()
    sched.submit(0)
    (slot, _req), = sched.admit()
    sched.evict(slot)
    with pytest.raises(ValueError, match="already free"):
        sched.evict(slot)                   # regression: bare KeyError +
    # the free list must NOT hold the slot twice — draining the queue
    # admits 4 distinct slots, not a duplicated one.
    for r in range(4):
        sched.submit(r)
    admitted = sched.admit()
    assert sorted(s for s, _ in admitted) == [0, 1, 2, 3]


def _stub_sched(batch=8, shards=2, live=8):
    from repro.launch.streaming import SlotScheduler
    sched = SlotScheduler(ReplaySession(batch=batch, n_shards=shards))
    for r in range(live):
        sched.submit(r)
    sched.admit()
    return sched


def test_evict_many_refuses_what_evict_refuses():
    sched = _stub_sched(live=3)              # slots 0, 4, 1 live
    before = (dict(sched.live), [list(f) for f in sched._free])
    with pytest.raises(ValueError, match=r"slot 2: already free"):
        sched.evict_many([0, 2])             # on the free list
    with pytest.raises(ValueError, match=r"slot 9.*out of range"):
        sched.evict_many([9])
    with pytest.raises(ValueError, match=r"slot 4: already free"):
        sched.evict_many([4, 1, 4])          # repeated in one call
    # Refused calls free nothing.
    assert (dict(sched.live), [list(f) for f in sched._free]) == before
    sched.evict(4)
    with pytest.raises(ValueError, match=r"slot 4: already free"):
        sched.evict_many([0, 4])


@pytest.mark.parametrize("shards", [1, 2, 4])
def test_evict_many_equals_evict_one_by_one(shards):
    order = [5, 0, 7, 2, 3, 6]
    one, many = _stub_sched(shards=shards), _stub_sched(shards=shards)
    want = [one.evict(s) for s in order]
    assert many.evict_many(np.array(order)) == want
    assert many.live == one.live
    assert many._free == one._free           # per shard, in order
    assert all(type(s) is int for f in many._free for s in f)
    assert many.evict_many([]) == []
    for r in range(8):                       # and admits alike after
        one.submit(r)
        many.submit(r)
    assert many.admit() == one.admit()


def test_admit_refuses_supervisor_flagged_slots():
    sess, sched = _sched()
    sess._flagged = frozenset({3})          # what _maybe_heal caches
    for r in range(5):
        sched.submit(r)
    admitted = sched.admit()
    assert sorted(s for s, _ in admitted) == [0, 1, 2]
    assert len(sched) == 2                  # requests wait, not shed
    # Once the supervisor clears the flag the slot is usable again.
    sess._flagged = frozenset()
    assert [s for s, _ in sched.admit()] == [3]


def test_admit_order_unchanged_when_nothing_flagged():
    # The health filter must not perturb the historical admission order.
    _sess, sched = _sched()
    for r in range(4):
        sched.submit(r)
    assert [(s, r) for s, r in sched.admit()] == [(0, 0), (1, 1),
                                                  (2, 2), (3, 3)]


def test_reset_streams_names_the_first_slot_out_of_range():
    sess, _ = _sched()
    with pytest.raises(ValueError, match=r"stream 7 out of range \[0, 4\)"):
        sess.reset_streams(np.array([1, 7, -1]))
    with pytest.raises(ValueError, match=r"stream -1 out of range"):
        sess.reset_streams([-1, 9])
    sess.reset_streams([])                  # an empty wave is no reset


def _admit_one_request_at_a_time(sched):
    """``SlotScheduler.admit`` as the per-request greedy loop it was: each
    request to the least-loaded shard with a healthy free slot (ties to
    the lowest shard), there to the healthy entry nearest the end of the
    free list; then one reset of the wave."""
    flagged = sched._sess.flagged_slots()
    per = sched.n_slots // sched.n_shards
    admitted = []
    while sched._queue:
        usable = [s for s in range(sched.n_shards)
                  if any(sl not in flagged for sl in sched._free[s])]
        if not usable:
            break
        shard = min(usable, key=lambda s: per - len(sched._free[s]))
        free = sched._free[shard]
        idx = next(i for i in range(len(free) - 1, -1, -1)
                   if free[i] not in flagged)
        slot = free.pop(idx)
        payload = sched._queue.popleft()
        sched.live[slot] = payload
        admitted.append((slot, payload))
    if admitted:
        sched._sess.reset_streams([slot for slot, _ in admitted])
    return admitted


class FlaggingSession(ReplaySession):
    """A ReplaySession whose supervisor-flagged set the test sets."""

    flagged = frozenset()

    def flagged_slots(self):
        return self.flagged


def _admission_rounds(shards, flagged, queue, seed, rounds=12):
    """Seeded rounds of ``evict_many``, flags and ``submit`` against two
    schedulers over 4 slots a shard; yields ``(sched, twin)`` after the
    submits, for the test to admit through each.  ``flagged`` is
    "none", "one" (one slot, live or free) or "shard" (every free slot of
    one shard); ``queue`` sizes the queue against the healthy free slots:
    "short", "equal" or "long"."""
    from repro.launch.streaming import SlotScheduler
    rng = np.random.default_rng(seed)
    batch = 4 * shards
    pair = [SlotScheduler(FlaggingSession(batch=batch, n_shards=shards))
            for _ in range(2)]
    request = 0
    for rnd in range(rounds):
        sched = pair[0]
        if sched.live and rnd:
            out = rng.permutation(list(sched.live))
            out = out[:rng.integers(0, len(out) + 1)]
            for s in pair:
                s.evict_many(out)
        if flagged == "one":
            bad = frozenset({int(rng.integers(batch))})
        elif flagged == "shard":
            bad = frozenset(sched._free[rnd % shards])
        else:
            bad = frozenset()
        healthy = sum(s not in bad for f in sched._free for s in f)
        want = {"short": healthy // 2, "equal": healthy,
                "long": healthy + 3}[queue]
        for _ in range(max(0, want - len(sched))):
            for s in pair:
                s.submit(f"r{request}")
            request += 1
        for s in pair:
            s._sess.flagged = bad
        yield pair


@pytest.mark.parametrize("queue", ["short", "equal", "long"])
@pytest.mark.parametrize("flagged", ["none", "one", "shard"])
@pytest.mark.parametrize("shards", [1, 2, 4])
def test_bulk_admit_equals_per_request_loop(shards, flagged, queue):
    for sched, twin in _admission_rounds(shards, flagged, queue,
                                         seed=[shards, len(flagged),
                                               len(queue)]):
        want = _admit_one_request_at_a_time(twin)
        got = sched.admit()
        assert got == want                  # slots, payloads, order
        assert all(type(s) is int for s, _ in got)
        assert list(sched.live.items()) == list(twin.live.items())
        assert sched._free == twin._free    # per shard, in order
        assert all(type(s) is int for f in sched._free for s in f)
        assert list(sched._queue) == list(twin._queue)
        assert sched._sess.log == twin._sess.log   # the reset waves
        assert not set(s for s, _ in got) & sched._sess.flagged


@pytest.mark.parametrize("shards", [1, 2, 4])
def test_occupancy_from_free_lists_and_admission_counts(shards):
    from repro.launch.engine import run_audio_requests, \
        run_continuous_detect
    waves = slots = 0
    for sched, twin in _admission_rounds(shards, "one", "long", seed=shards):
        got = sched.admit()
        _admit_one_request_at_a_time(twin)  # keeps the pair in step
        waves, slots = waves + bool(got), slots + len(got)
        walk = [0] * shards
        for slot in sched.live:
            walk[sched._sess.shard_of_slot(slot)] += 1
        assert sched.occupancy() == walk
        assert (sched.waves, sched.admitted) == (waves, slots)

    # The utterance loop's engine has the scheduler: the report counts
    # the waves the run admitted (without faults, every reset is one).
    done, stats, log = _replay_run(run_audio_requests, 2, requests=14,
                                   slots=4, shards=shards)
    resets = [op[1] for op in log if op[0] == "reset"]
    n = sum(map(len, resets))
    assert n == len(done) == 14
    assert stats["slo"]["admission"] == {
        "waves": len(resets), "slots": n, "mean_wave": n / len(resets)}

    # The detect loop's has none, and its report no admission key.
    class EventSession(ReplaySession):
        def process_audio(self, piece):
            out = super().process_audio(piece)
            return types.SimpleNamespace(events=out.votes)
    audio = np.zeros((4, 2048), np.float32)
    *_, stats = run_continuous_detect(
        EventSession(batch=4, n_shards=shards), list(audio), chunk=1024,
        n_samples=2048, depth=2, warm=False)
    assert "admission" not in stats["slo"]


# ---------------------------------------------------------------------------
# serve.py CLI: --sync-loop escape hatch + the timing-split output

def test_serve_cli_sync_loop_and_timing_lines(capsys):
    from repro.launch import serve
    rc = serve.main(["--mode", "kws-audio", "--slots", "2", "--requests",
                     "3", "--train-steps", "0", "--chunk-samples", "2048",
                     "--sync-loop"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "pipeline depth 1" in out
    assert "end-to-end" in out              # end-to-end vs steady-state
    assert "steady-state:" in out           # are SEPARATE lines now
    assert "warmup/compile" in out
    assert "p99.9" in out
    assert "host-blocked/step" in out


def test_serve_cli_rejects_bad_depth():
    from repro.launch import serve
    with pytest.raises(SystemExit):
        serve.main(["--mode", "kws-audio", "--inflight-depth", "0"])


# ---------------------------------------------------------------------------
# kernel_bench gate (regression: single-pass timing flaked at 0.99x)

def test_int8_gate_reports_best_of_n_and_dispersion():
    sys.path.insert(0, str(REPO / "benchmarks"))
    try:
        from kernel_bench import check_int8_ratio
    finally:
        sys.path.pop(0)
    summary = {"int8_speed_ratio_interpret": 1.4, "timing_repeats": 3,
               "int8_speed_ratio_samples": [0.99, 1.4, 1.2],
               "int8_speed_ratio_dispersion": (1.4 - 0.99) / 1.4,
               "float_us_per_frame_interpret": 10.0,
               "int8_us_per_frame_interpret": 7.1}
    check_int8_ratio(summary)               # best pass clears the gate
    with pytest.raises(AssertionError, match=r"best of 3.*dispersion"):
        check_int8_ratio({**summary, "int8_speed_ratio_interpret": 0.5})
