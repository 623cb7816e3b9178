"""Host spans of a serving step (``launch/spans.py``) and the named step.

* The span recorder as the engine reports it: every span under its name
  in ``host_ms_per_step``, phases + ``untraced`` = the steady-state
  step, the historical ``host_blocked_ms_per_step`` keys as sums of
  their spans, nothing counted outside a step — all on a manual clock.
* Phases are disjoint: a nested phase is refused.
* No span and no clock read per slot: the loop drivers' span entries and
  clock reads per step do not grow with the slot count.
* The fused step lowers as the module ``jit_kws_audio_step`` with the
  ``kws.fex`` / ``kws.gru`` / ``kws.head`` scopes, with and without a
  slot mesh, with and without the detection head.
"""
import os
import pathlib
import re
import subprocess
import sys
import time

import numpy as np
import pytest

from repro.launch import spans

REPO = pathlib.Path(__file__).parent.parent


class ManualClock:
    """A clock that moves only when the test moves it."""

    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now

    def advance(self, ms: float):
        self.now += ms * 1e-3


class _Votes:
    """A result array whose fetch takes ``fetch_ms`` on the clock."""

    def __init__(self, clock, fetch_ms, batch):
        self._clock = clock
        self._fetch_ms = fetch_ms
        self.shape = (4, batch)

    def __array__(self, dtype=None, copy=None):
        self._clock.advance(self._fetch_ms)
        return np.zeros(self.shape, np.int32)


class _Out:
    def __init__(self, votes):
        self.votes = votes


class TimedStub:
    """A session stand-in that spends scripted clock time in each of
    ``process_audio``'s phases (``dispatch_ms``, name → ms) and
    ``fetch_ms`` in its result's fetch; 4 frames a piece."""

    def __init__(self, clock, dispatch_ms, fetch_ms=0.0, batch=2):
        self.clock = clock
        self.dispatch_ms = dispatch_ms
        self.fetch_ms = fetch_ms
        self.batch = batch

    def process_audio(self, piece):
        for name, ms in self.dispatch_ms.items():
            with spans.span(name):
                self.clock.advance(ms)
        return _Out(_Votes(self.clock, self.fetch_ms, self.batch))


def _engine(clock, depth=1, **stub):
    from repro.launch.engine import PipelinedEngine
    return PipelinedEngine(TimedStub(clock, **stub), depth=depth,
                           clock=clock)


QUARTER_MS = {spans.POLICE: 0.5, spans.FRAME: 0.5, spans.H2D: 0.5,
              spans.LAUNCH: 0.5}


@pytest.mark.parametrize("name", spans.PHASES)
def test_every_span_lands_under_its_name(name):
    clk = ManualClock()
    eng = _engine(clk, dispatch_ms={})
    for _ in range(2):
        eng.begin()
        with spans.span(name):
            clk.advance(3.0)
        eng.submit([None])
        eng.end()
    host = eng.report()["host_ms_per_step"]
    assert set(host) == set(spans.NAMES) | {spans.UNTRACED}
    assert host[name] == pytest.approx(3.0)
    assert host[spans.STEP] == pytest.approx(3.0)
    assert host[spans.UNTRACED] == pytest.approx(0.0)
    for other in set(spans.PHASES) - {name}:
        assert host[other] == 0.0


def test_phases_and_untraced_sum_to_the_steady_state_step():
    # Two steps with time in every phase, some time inside each step that
    # no span covers (0.25 ms) and a gap between the steps (2 ms).
    clk = ManualClock()
    eng = _engine(clk, depth=2, dispatch_ms=QUARTER_MS, fetch_ms=3.0)
    for i in range(2):
        if i:
            clk.advance(2.0)                 # between end() and begin()
        eng.begin()
        with spans.span(spans.ASSEMBLE):
            clk.advance(1.0)
        eng.submit([None])
        clk.advance(0.25)                    # inside the step, no span
        for name, ms in ((spans.SLOTS, 1.5), (spans.ADMIT, 0.75),
                         (spans.HEAL, 0.1)):
            with spans.span(name):
                clk.advance(ms)
        eng.end()
    rep = eng.report()
    host = rep["host_ms_per_step"]
    steady_ms = rep["steady_state_s"] * 1e3 / rep["steps"]
    phases = sum(host[p] for p in spans.PHASES)
    assert phases + host[spans.UNTRACED] == pytest.approx(steady_ms)
    # one fetch (step 0's, drained in step 1) over two steps
    assert host[spans.FETCH] == pytest.approx(1.5)
    assert host[spans.UNTRACED] == pytest.approx(0.25 + 2.0 / 2)
    hb = rep["host_blocked_ms_per_step"]
    assert hb["assemble"] == pytest.approx(host[spans.ASSEMBLE]) == 1.0
    assert hb["dispatch"] == pytest.approx(
        sum(host[p] for p in spans.DISPATCH)) == pytest.approx(2.0)
    assert hb["fetch"] == host[spans.FETCH]
    assert hb["total"] == pytest.approx(
        hb["assemble"] + hb["dispatch"] + hb["fetch"])
    assert host[spans.STEP] == pytest.approx(rep["step_ms"]["p50"])


def test_spans_outside_a_step_are_traced_not_counted():
    clk = ManualClock()
    eng = _engine(clk, depth=2, dispatch_ms={})
    with spans.span(spans.ADMIT):            # before the first step
        clk.advance(5.0)
    eng.begin()
    eng.submit([None])
    eng.end()
    eng.flush()                              # the flush fetch: after it
    host = eng.report()["host_ms_per_step"]
    assert host[spans.ADMIT] == 0.0 and host[spans.FETCH] == 0.0
    eng.reset_telemetry()
    assert set(eng.report()["host_ms_per_step"].values()) == {0.0}


def test_nested_phases_are_refused():
    with spans.span(spans.ASSEMBLE):
        with pytest.raises(spans.SpanNestingError, match="kws.assemble"):
            with spans.span(spans.POLICE):
                pass
        with pytest.raises(spans.SpanNestingError):
            spans.Step(spans.Recorder(), 0)  # a step inside a phase
    with spans.span(spans.POLICE):           # the refusal left no trace
        pass
    with pytest.raises(ValueError, match="not a phase"):
        spans.span(spans.STEP)
    # kws.step is the one span that encloses others.
    rec = spans.Recorder(ManualClock())
    step = spans.Step(rec, 0)
    with spans.span(spans.FETCH):
        pass
    step.close(0.0)
    assert rec.entries[spans.FETCH] == 1 and rec.entries[spans.STEP] == 1


# ---------------------------------------------------------------------------
# Loop drivers: span entries and clock reads per step do not depend on
# the slot count.

class CountingClock:
    def __init__(self):
        self.reads = 0

    def __call__(self):
        self.reads += 1
        return time.perf_counter()


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


def _counted(monkeypatch, run):
    """(span entries per name, clock reads, steps) of one driver run."""
    entries: dict[str, int] = {}
    add = spans.Recorder.add

    def counting_add(self, name, seconds):
        entries[name] = entries.get(name, 0) + 1
        add(self, name, seconds)

    monkeypatch.setattr(spans.Recorder, "add", counting_add)
    clock = CountingClock()
    steps = run(clock)
    monkeypatch.setattr(spans.Recorder, "add", add)
    return entries, clock.reads, steps


def _audio_loop(slots):
    from repro.launch.engine import run_audio_requests
    from repro.launch.streaming import SlotScheduler, StreamingKwsSession
    cfg, fex, params = _session_bits()
    utt, chunk = 1024, 512
    audio_q = np.random.default_rng(3).uniform(
        -0.5, 0.5, (2 * slots, utt)).astype(np.float32)

    def run(clock):
        sess = StreamingKwsSession(params, cfg, threshold=0.1, batch=slots,
                                   fex=fex, input_policy="trust")
        sched = SlotScheduler(sess)
        for req in range(2 * slots):         # two full waves
            sched.submit(req)
        _, stats = run_audio_requests(
            sess, sched, None, audio_q=audio_q, chunk=chunk,
            chunks_per_utt=utt // chunk,
            real_frames=utt // fex.cfg.frame_shift, depth=2, warm=False,
            clock=clock)
        return stats["steps"]
    return run


def _detect_loop(slots):
    from repro.launch.engine import run_continuous_detect
    from repro.launch.streaming import StreamingKwsSession
    from repro.models.detector import DetectorConfig
    cfg, fex, params = _session_bits()
    audio = np.random.default_rng(4).uniform(
        -0.5, 0.5, (slots, 1024)).astype(np.float32)

    def run(clock):
        sess = StreamingKwsSession(params, cfg, threshold=0.1, batch=slots,
                                   fex=fex, detector=DetectorConfig())
        _, _, stats = run_continuous_detect(
            sess, list(audio), chunk=256, n_samples=1024, depth=2,
            warm=False, clock=clock)
        return stats["steps"]
    return run


@pytest.mark.parametrize("loop", [_audio_loop, _detect_loop])
def test_no_span_or_clock_read_per_slot(monkeypatch, loop):
    small = _counted(monkeypatch, loop(4))
    large = _counted(monkeypatch, loop(16))
    assert small[2] == large[2] > 0           # the same number of steps
    assert small == large
    entries = small[0]
    assert entries[spans.STEP] == small[2]
    for name in (spans.ASSEMBLE, spans.POLICE, spans.FRAME, spans.H2D,
                 spans.LAUNCH, spans.SLOTS):
        assert entries[name] >= small[2], name


# ---------------------------------------------------------------------------
# The fused step's name and scopes on the device

def _lowered_text(mesh_devices: int, mode: str) -> str:
    """The fused int8 step's lowered module text, with debug locations,
    in a child process with two virtual CPUs and a slot mesh over
    ``mesh_devices`` of them (0: no mesh); ``mode`` "detect" adds the
    VAD gate and detection head."""
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import jax
from repro.configs import get_config
from repro.frontend import FeatureExtractor
from repro.launch.mesh import make_slot_mesh
from repro.launch.streaming import StreamingKwsSession
from repro.models import kws
from repro.models.detector import DetectorConfig
cfg = get_config("deltakws")
fex = FeatureExtractor()
params, _ = kws.init_kws(jax.random.PRNGKey(0), cfg,
                         input_dim=fex.cfg.n_active)
mesh = make_slot_mesh({mesh_devices}) if {mesh_devices} else None
kw = dict(detector=DetectorConfig()) if {mode!r} == "detect" else {{}}
sess = StreamingKwsSession(params, cfg, threshold=0.1, batch=4, fex=fex,
                           numerics="int8", mesh=mesh, **kw)
assert sess.n_shards == max({mesh_devices}, 1)
step, args = sess.fused_audio_step(256)
print(step.lower(*args).as_text(debug_info=True))
"""
    r = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={**os.environ, "PYTHONPATH": str(REPO / "src"),
             "JAX_PLATFORMS": "cpu"}, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


@pytest.mark.parametrize("mode", ["audio", "detect"])
@pytest.mark.parametrize("mesh_devices", [0, 2])
def test_fused_step_is_named_and_scoped(mesh_devices, mode):
    text = _lowered_text(mesh_devices, mode)
    assert re.search(r"^module @jit_kws_audio_step ", text, re.M)
    scopes = set(re.findall(r"kws\.(?:fex|gru|head)", text))
    assert scopes == {"kws.fex", "kws.gru", "kws.head"}
    assert "kws.head/kws.head" not in text   # the head scope never nests
