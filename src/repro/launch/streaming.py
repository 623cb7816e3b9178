"""Zero-sync streaming KWS sessions — raw audio in, decisions out (DESIGN.md §4/§5).

The IC's deployment mode is an always-on stream: 8 kHz audio enters the
FEx, one decision leaves per 16 ms frame, and every piece of state (biquad
registers, envelope, x̂/ĥ/M) is resident on-chip.  The serving image of
that is a session whose FEx state, delta state and op-count telemetry live
on DEVICE between chunks: the host hands over a chunk of raw audio, gets
device arrays back, and never forces a per-frame sync.

``StreamingKwsSession`` composes the batched sequence-resident FEx kernel
(``kernels.iir_fex.batched_iir_fex``) with the fused sequence-resident
ΔGRU kernel (``kernels.delta_gru_seq``) into ONE jitted audio→decision
step per chunk — no host hop between FEx and ΔGRU:

    sess = StreamingKwsSession(params, cfg, threshold=0.1, fex=fex)
    for audio in audio_chunks:                # (samples,) raw 8 kHz audio
        out = sess.process_audio(audio)       # device arrays, NO sync
        votes = np.asarray(out.votes)         # ONE fetch per chunk
    print(sess.summary())                     # one fetch for telemetry

Pre-computed feature chunks are still accepted via ``process_chunk``.
Chunk boundaries are invisible to the model either way: processing [a|b]
equals processing the concatenation in one shot, bit for bit, including
audio chunks that end mid-frame (the trailing ``< frame_shift`` samples
are carried host-side and prepended to the next chunk — they are host
data already, so no device sync is involved).

Scale-out (DESIGN.md §6): pass ``mesh=make_slot_mesh(...)`` and the
session becomes a sharded continuous-batching engine — the SLOT axis
(one live stream per slot) is partitioned over the mesh's "data" axis
with ``shard_map``, weights/coefficients are replicated, per-stream
FEx+ΔGRU state and telemetry are sharded on slots, and the hot path has
neither host syncs nor cross-device collectives (telemetry is kept as
per-shard partial sums, reduced on the host once per ``summary()``).
``reset_stream`` is slot-local — a jitted dynamic row update that only
the owning shard executes — so stream churn on one shard never stalls
the others.  At mesh=None (or one device) the engine is bit-identical
to the original single-device session.  ``SlotScheduler`` maps a
request queue onto the global slots, balancing admissions across
shards.

Numerics (DESIGN.md §9): ``numerics="int8"`` swaps the fused float step
for the bit-true integer pipeline — 12-bit ADC codes → integer FEx →
int8-weight/int16-state ΔGRU → integer FC — consuming a promoted
``IntKwsBundle`` (``train.promote``) and carrying every piece of stream
state as integer codes.  Same shard/scheduler machinery, decisions are
argmaxes over int32 logit codes, bit-identical to the golden
fixed-point model (``core.fixed_point``).

Fault tolerance (DESIGN.md §11): the fused step also emits a per-slot
HEALTH bitmask — finite-state predicates over the FEx biquad registers,
the ΔGRU x̂/ĥ/M, the VAD hold and the detector EMA (saturation-rail
compares in the int8 engine, where state cannot go non-finite) plus a
non-finite-input flag computed before the ADC quantizer.  Pass
``supervisor=SupervisorConfig(...)`` and a host-side supervisor reads
that mask (one tiny fetch per ``check_every`` chunks), quarantines
slots whose poisoned state can never recover on its own, and resets
them through the same mask-batched ``reset_streams`` that serves
continuous-batching churn — a healed slot is bit-identical to a fresh
stream.  Recovery counts and reasons surface in ``StreamSummary``; on
healthy streams every flag is zero and the engine is bit-identical to
an unsupervised session.  ``input_policy`` guards the ``process_audio``
boundary (reject / sanitize / trust hostile samples), telemetry counters
are carried as split int32 pairs exact to 2^61 (``overflowed`` flags the
saturation that would silently wedge float32 partial sums at 2^24), and
``set_threshold`` re-points the compiled step at a different Δ_TH
operating point mid-stream — the graceful-degradation lever the serve
launcher's admission controller drives under overload.

Detection (DESIGN.md §10): pass ``detector=DetectorConfig(...)`` and the
session serves the always-on scenario the IC was built for — continuous
audio in, discrete keyword EVENTS out.  The fused step grows two stages:
an energy VAD (``frontend.vad``) that sample-and-holds the features
during silence so the Δ-encoder transmits nothing (the temporal-sparsity
/ energy knob), and a posterior-smoothing hysteresis head
(``models.detector``) that turns per-frame posteriors into one event per
spoken keyword.  Both carry per-slot device state, compose with either
numerics and the slot mesh, and keep every bit-invariance guarantee
above; ``process_audio`` returns ``DetectResult`` (events + gate trace).
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import delta_gru as dg
from repro.core import fixed_point as fp
from repro.core.energy_model import (cascade_frame_cost, fex_energy_nj,
                                     frame_cost, vad_energy_nj)
from repro.core.quantize import quantize_audio_12b
from repro.frontend.fex import (FeatureExtractor, FExConfig, FExState,
                                _pack_state, _unpack_state, fex_scan,
                                init_fex_state)
from repro.frontend.vad import (VADConfig, VADState, VAD_OFF, frame_energy,
                                init_vad_state, vad_gate, vad_state_flags)
from repro.kernels.gru_math import f32_dot
from repro.kernels.platform import resolve_interpret, shard_map_kernels
from repro.launch import spans
from repro.models import kws
from repro.models import detector as det_mod
from repro.models.detector import (DetectorConfig, DetectorState,
                                   detector_scan, detector_state_flags,
                                   init_detector_state)
from repro.parallel import sharding as shp
from jax.sharding import PartitionSpec as P

Array = jax.Array


class ChunkResult(NamedTuple):
    """Device-side per-chunk outputs — nothing here has been synced."""

    logits: Array   # (frames, batch, n_classes) per-frame logits
    votes: Array    # (frames, batch) int32 per-frame argmax
    nz: Array       # (frames, batch) transmitted deltas per frame


class DetectResult(NamedTuple):
    """Per-chunk outputs of the DETECTION pipeline (``detector=`` mode).

    Everything frame-major and device-side, like ``ChunkResult``; the
    extra fields are the decision head's fires and the VAD gate trace.
    """

    logits: Array   # (frames, batch, n_classes) per-frame logits
    votes: Array    # (frames, batch) int32 per-frame argmax
    nz: Array       # (frames, batch) transmitted deltas per frame
    events: Array   # (frames, batch) int32 — fired class id, -1 = none
    gate: Array     # (frames, batch) bool — VAD gate (True = open)
    awake: Any = None  # (frames, batch) bool stage-1 wake trace
                       #   (cascade sessions only; None otherwise)


class StreamInputError(ValueError):
    """Typed rejection at the ``process_audio`` boundary (DESIGN.md §11):
    non-finite samples, un-decodable dtypes, or out-of-range integer
    codes.  Raised BEFORE anything reaches the device, so a hostile
    chunk cannot poison carried stream state."""


# ------------------------------------------------- two-stage wake cascade --
class CascadeConfig(NamedTuple):
    """Policy of the stage-0 → stage-1 wake cascade (DESIGN.md §13).

    A ~16-unit always-on micro-ΔGRU (stage 0) watches a reduced channel
    set and scores every frame for "an event might be here"; the big
    stage-1 network only runs while that score says so.  Hysteresis +
    hangover keep stage 1 powered across the body of a candidate event
    so the detection head sees a contiguous posterior trace.

    wake_threshold: stage-0 event posterior at/above which an asleep
      slot WAKES stage 1 (this frame already runs awake).
    sleep_threshold: posterior at/above which an awake slot stays awake
      with its hangover refreshed; must be <= wake_threshold (the
      hysteresis band that stops flapping mid-keyword).
    hangover_frames: frames stage 1 stays powered after the score drops
      below sleep_threshold — covers keyword tails and brief dips.
    s0_threshold: stage-0's own Δ_TH (fixed — the ``set_threshold``
      degradation lever moves only the stage-1 operating point).
    s0_channels: leading FEx channels stage 0 taps (the paper's
      reduced-channel always-on configuration; must match the stage-0
      model's input width).
    """

    wake_threshold: float = 0.5
    sleep_threshold: float = 0.25
    hangover_frames: int = 15
    s0_threshold: float = 0.0
    s0_channels: int = 4


class CascadeState(NamedTuple):
    """Per-slot cascade state, device-resident like every other stream
    state: the wake latch + hangover countdown, and the stage-0
    micro-ΔGRU's own delta state (float or integer codes)."""

    awake: Array        # (B,) bool — stage 1 powered
    hang: Array         # (B,) int32 hangover countdown
    s0: dg.DeltaState   # stage-0 carried delta state


def init_cascade_state(batch: int, s0_gru, *, int8: bool) -> CascadeState:
    """Fresh cascade state: everyone asleep, stage-0 state zeroed (M
    seeded at the stage-0 bias, like any fresh ΔGRU stream)."""
    I = s0_gru.w_x.shape[0]
    H = s0_gru.w_h.shape[0]
    s0 = (fp.init_int_delta_state(batch, I, H, s0_gru) if int8
          else dg.init_delta_state(batch, I, H, s0_gru))
    return CascadeState(awake=jnp.zeros((batch,), bool),
                        hang=jnp.zeros((batch,), jnp.int32), s0=s0)


def cascade_wake_scan(cfg: CascadeConfig, awake: Array, hang: Array,
                      score: Array):
    """The wake/sleep state machine over one chunk of stage-0 scores.

    score: (F, B) stage-0 event posteriors.  Per frame: a score at/above
    ``wake_threshold`` wakes the slot; an awake slot holds while the
    score stays at/above ``sleep_threshold`` (hangover refreshed) and
    for ``hangover_frames`` more frames after it drops; otherwise it
    sleeps.  Causal — frame t's wake decision uses frame t's score, so
    the stage-1 mask for the chunk is available before stage 1 runs.

    Returns ``(awake_trace (F, B) bool, awake', hang')`` — the per-frame
    stage-1 power mask plus the carried latch/countdown.
    """

    def body(carry, s):
        awake, hang = carry
        wake = s >= cfg.wake_threshold
        hold = awake & (s >= cfg.sleep_threshold)
        new_awake = wake | hold | (awake & (hang > 0))
        hang = jnp.where(wake | hold, jnp.int32(cfg.hangover_frames),
                         jnp.maximum(hang - 1, 0))
        return (new_awake, hang), new_awake

    (awake, hang), trace = jax.lax.scan(body, (awake, hang), score)
    return trace, awake, hang


# --------------------------------------------------------- health bitmask --
# Per-slot health flags computed INSIDE the fused serving step (pure reads
# of the carried state — the datapath is untouched, so enabling the check
# changes no output bit).  Each bit names one failure mode of DESIGN.md
# §11's catalog; HEALTH_REASONS maps bits to the telemetry reason strings.
HEALTH_INPUT = 1 << 0      # non-finite samples entered this chunk
HEALTH_FEX = 1 << 1        # biquad registers non-finite / rail-pinned
HEALTH_GRU = 1 << 2        # ΔGRU x̂/ĥ/M non-finite / x̂ off-grid (int)
HEALTH_DET = 1 << 3        # detector EMA non-finite or outside [0, 1]
HEALTH_VAD = 1 << 4        # VAD hold register non-finite
HEALTH_SAT = 1 << 5        # int accumulator at the 24-bit saturation rail
HEALTH_REASONS = {
    HEALTH_INPUT: "input_nonfinite",
    HEALTH_FEX: "fex_state",
    HEALTH_GRU: "gru_state",
    HEALTH_DET: "detector_state",
    HEALTH_VAD: "vad_state",
    HEALTH_SAT: "accumulator_saturation",
}
# Default quarantine set: every unrecoverable-state bit.  HEALTH_SAT is
# excluded — a saturating accumulator is the fixed-point design WORKING
# (it recovers as soon as the input calms down), so it is counted as
# telemetry (``StreamSummary.sat_events``) rather than treated as poison.
QUARANTINE_DEFAULT = (HEALTH_INPUT | HEALTH_FEX | HEALTH_GRU
                      | HEALTH_DET | HEALTH_VAD)

_FEX_MAG_BOUND = 1e6       # float biquad register blow-up bound
_INT16_RAIL = 32767        # int16 register saturation rail
_FEAT_CODE_BOUND = 1 << 12  # 12-bit feature grid + 1 bit of slack
_ACC_RAIL = (1 << 23) - 1  # 24-bit saturating accumulator rail


class SupervisorConfig(NamedTuple):
    """Host-side self-healing policy (DESIGN.md §11).

    check_every: chunks between health-mask fetches (each fetch is one
      (batch,) int32 sync — 1 checks after every chunk).
    quarantine_after: consecutive flagged checks before a slot is reset
      (1 = immediate; raise it to ride out transient flags).
    quarantine_mask: which HEALTH_* bits trigger a reset (default: every
      poisoned-state bit; saturation stays telemetry-only).
    """

    check_every: int = 1
    quarantine_after: int = 1
    quarantine_mask: int = QUARANTINE_DEFAULT


def _slot_any(bad: Array) -> Array:
    """(B, ...) bool → per-slot (B,) any-reduction."""
    if bad.ndim == 1:
        return bad
    return jnp.any(bad.reshape(bad.shape[0], -1), axis=1)


def _flag(bit: int, bad: Array) -> Array:
    return jnp.where(bad, jnp.int32(bit), jnp.int32(0))


def slot_health(input_bad: Array, fex_state: FExState | None,
                gru_state, vad_state: VADState | None,
                det_state: DetectorState | None) -> Array:
    """Fuse the per-slot health predicates into one (B,) int32 bitmask.

    Pure reads over the carried state trees, elementwise along the slot
    axis (sharding-safe, no collectives).  Float state checks are
    finiteness/magnitude predicates; integer-code state cannot go
    non-finite, so the int8 engine checks saturation rails instead —
    the "saturation-flag counters" of the paper's datapath, priced at a
    handful of compares per slot per chunk.  ``input_bad`` is the
    pre-quantizer non-finite-sample flag (computed before the 12-bit
    clip, which would otherwise launder an Inf into full-scale).
    """
    flags = _flag(HEALTH_INPUT, input_bad)
    if fex_state is not None:
        if jnp.issubdtype(fex_state.filt.dtype, jnp.floating):
            bad = _slot_any(~jnp.isfinite(fex_state.filt)
                            | (jnp.abs(fex_state.filt) > _FEX_MAG_BOUND))
            bad |= _slot_any(~jnp.isfinite(fex_state.env))
        else:
            f32 = fex_state.filt.astype(jnp.int32)
            e32 = fex_state.env.astype(jnp.int32)
            bad = _slot_any(jnp.abs(f32) >= _INT16_RAIL)
            bad |= _slot_any(jnp.abs(e32) >= _INT16_RAIL)
        flags |= _flag(HEALTH_FEX, bad)
    if gru_state is not None:
        for layer, st in enumerate(_layers(gru_state)):
            flags |= _gru_flags(st, first=layer == 0)
    if vad_state is not None:
        flags |= _flag(HEALTH_VAD, vad_state_flags(vad_state))
    if det_state is not None:
        flags |= _flag(HEALTH_DET, detector_state_flags(det_state))
    return flags


def _gru_flags(state, *, first: bool) -> Array:
    """HEALTH_GRU / HEALTH_SAT flags of one ΔGRU layer's state.  An int
    layer's x̂ holds feature codes only in the first layer; above it x̂
    holds the int16 hidden codes below, which no code bound exceeds."""
    if jnp.issubdtype(state.h.dtype, jnp.floating):
        bad = _slot_any(~jnp.isfinite(state.h))
        for leaf in (state.x_hat, state.h_hat, state.m_x, state.m_h):
            bad |= _slot_any(~jnp.isfinite(leaf))
        return _flag(HEALTH_GRU, bad)
    flags = jnp.zeros(state.h.shape[:1], jnp.int32)
    if first:
        x32 = state.x_hat.astype(jnp.int32)
        flags = _flag(HEALTH_GRU, _slot_any(jnp.abs(x32) > _FEAT_CODE_BOUND))
    sat = _slot_any(jnp.abs(state.m_x) >= _ACC_RAIL)
    sat |= _slot_any(jnp.abs(state.m_h) >= _ACC_RAIL)
    return flags | _flag(HEALTH_SAT, sat)


# ------------------------------------------------------ exact telemetry --
# jax's default config has no int64 on device, and float32 partial sums
# silently stop incrementing at 2^24 — a real soak bug: MAC counts wedge
# after ~20 minutes of a busy 64-slot session.  Each counter is carried
# as a SPLIT PAIR of int32 lanes (lo < 2^30, hi = carries of 2^30):
# exact to 2^61 (decades of always-on fleet audio), with the hi lane
# saturating — not wrapping — at _HI_SAT, surfaced as
# ``StreamSummary.overflowed``.
_COUNT_SHIFT = 30
_COUNT_MASK = (1 << _COUNT_SHIFT) - 1
_HI_SAT = (1 << 31) - 8            # saturation rail (room for carries)


class _Count(NamedTuple):
    """One exact counter: value = hi·2^30 + lo, both (n_shards,) int32."""

    hi: Array
    lo: Array


def _count_zero(n_shards: int) -> _Count:
    return _Count(hi=jnp.zeros((n_shards,), jnp.int32),
                  lo=jnp.zeros((n_shards,), jnp.int32))


def _count_add(c: _Count, d, scale: int = 1) -> _Count:
    """Add ``d · scale`` with carry propagation: ``d`` a device count
    (int32, 0 ≤ d < 2^31; an array adds elementwise) and ``scale`` <
    2^15, or ``d`` a Python int of any size below 2^61 (split on the
    host).  Saturates the hi lane instead of wrapping."""
    if isinstance(d, int):
        v = d * scale
        lo = c.lo + (v & _COUNT_MASK)
        hi = c.hi + (v >> _COUNT_SHIFT) + (lo >> _COUNT_SHIFT)
        return _Count(hi=jnp.minimum(hi, _HI_SAT), lo=lo & _COUNT_MASK)
    # d·scale = (d >> 15)·scale·2^15 + (d & 0x7FFF)·scale: both partial
    # products stay under 2^31, and each is carried into hi before the
    # next lands on lo.
    d = jnp.asarray(d, jnp.int32)
    a = (d & 0x7FFF) * scale
    b = (d >> 15) * scale
    lo = c.lo + a
    hi = c.hi + (lo >> _COUNT_SHIFT)
    lo = (lo & _COUNT_MASK) + ((b & 0x7FFF) << 15)
    hi = hi + (b >> 15) + (lo >> _COUNT_SHIFT)
    return _Count(hi=jnp.minimum(hi, _HI_SAT), lo=lo & _COUNT_MASK)


def _count_value(c: _Count) -> tuple[int, bool]:
    """Host-side reduction of a fetched counter: (exact value across
    shards as a python int, saturated?)."""
    hi = np.asarray(c.hi, np.int64)
    lo = np.asarray(c.lo, np.int64)
    return (int(hi.sum()) << _COUNT_SHIFT) + int(lo.sum()), \
        bool(np.any(hi >= _HI_SAT))


def _count_values(c: _Count) -> tuple[list[int], bool]:
    """``_count_value`` of a per-layer counter ((n_shards, L) lanes):
    one exact value per layer."""
    hi = np.asarray(c.hi, np.int64)
    lo = np.asarray(c.lo, np.int64)
    return ([(int(h) << _COUNT_SHIFT) + int(v)
             for h, v in zip(hi.sum(0), lo.sum(0))],
            bool(np.any(hi >= _HI_SAT)))


class _Accum(NamedTuple):
    """Device-resident telemetry accumulated across chunks.

    ``frames``/``fex_samples`` count DECISIONS / samples across ALL
    streams of the batch (matching ``macs``, which is batch-summed), so
    per-decision quantities stay correct for multi-stream sessions.

    Every field is a ``_Count`` of ``(n_shards,)`` PER-SHARD partial
    sums (``(1,)`` unsharded) — exact int32 split pairs, see above.
    Keeping the partials sharded instead of psum-reducing them keeps the
    hot path free of collectives — the one host-side ``summary()`` fetch
    does the final reduction.

    Per-layer fields (``nz_dx``, ``nz_dh``) carry an (n_shards, L)
    pair, one column per ΔGRU layer of the stack, the first first.
    """

    macs: _Count         # (stage-1) ΔGRU MACs actually executed
    macs_dense: _Count   # dense-equivalent MACs
    frames: _Count       # decisions made
    fex_samples: _Count  # raw audio samples through the FEx
    vad_open: _Count     # frame-slots the VAD gate was open
                         #   (== frames when no VAD is gating)
    s0_macs: _Count      # stage-0 micro-ΔGRU MACs (0 without a cascade)
    awake: _Count        # frame-slots stage 1 was powered
                         #   (== frames when no cascade is gating)
    nz_dx: _Count        # transmitted input Δs, per layer
    nz_dh: _Count        # transmitted hidden Δs, per layer


@dataclasses.dataclass
class StreamSummary:
    frames: int            # decisions made = frames × streams
    chunks: int
    sparsity: float
    energy_nj_per_decision: float
    latency_ms: float
    dense_energy_nj: float
    fex_samples: int = 0
    fex_energy_nj_per_decision: float = 0.0
    vad_duty: float = 1.0                  # gate-open fraction of frames
    vad_energy_nj_per_decision: float = 0.0
    stage1_duty: float = 1.0               # stage-1 awake fraction (cascade)
    s0_energy_nj_per_decision: float = 0.0  # always-on stage-0 cost
    frames_entered_stage1: int = 0         # frame-slots stage 1 executed
    overflowed: bool = False               # any telemetry counter saturated
    recoveries: int = 0                    # slots auto-reset by supervisor
    recovery_reasons: dict = dataclasses.field(default_factory=dict)
    sat_events: int = 0                    # HEALTH_SAT slot-checks observed
    # Per ΔGRU layer, the first first: Δs transmitted (input + hidden)
    # and temporal sparsity, 1 − transmitted / (frames · (I + H)).
    layer_transmits: tuple = ()
    layer_sparsity: tuple = ()
    # Chunks joined to a carried sample remainder: how the host framed
    # the audio, not what the streams decided, so — unlike ``chunks`` —
    # it takes no part in comparing two summaries.
    frame_joins: int = dataclasses.field(default=0, compare=False)
    # Serve-loop SLO telemetry attached by the pipelined engine
    # (launch.engine): step/e2e latency percentiles, host time per span
    # (launch.spans), shard imbalance.  Empty unless a serve driver
    # called ``attach_slo`` — plain session runs are unaffected.
    slo: dict = dataclasses.field(default_factory=dict)


def _zero_accum(n_shards: int = 1, n_layers: int = 1) -> _Accum:
    per_layer = _Count(hi=jnp.zeros((n_shards, n_layers), jnp.int32),
                       lo=jnp.zeros((n_shards, n_layers), jnp.int32))
    return _Accum(*[per_layer if f in ("nz_dx", "nz_dh")
                    else _count_zero(n_shards) for f in _Accum._fields])


def _layers(x) -> tuple:
    """A ΔGRU stack's per-layer entries: a stack (a tuple or list of
    weights, formats or states) as a tuple, one layer's entry (a
    NamedTuple or GruFormats) as a one-tuple."""
    return tuple(x) if type(x) in (tuple, list) else (x,)


class LayerCounts(NamedTuple):
    """One ΔGRU layer's transmitted Δs over a chunk, and its widths."""

    nz_dx: Array    # (F, B) int32
    nz_dh: Array    # (F, B) int32
    in_dim: int
    hidden: int


@jax.named_scope("kws.gru")
def _gru_stack(gru, state, xs, *, threshold: float, backend: str,
               interpret: bool | None, gfmt=None):
    """The ΔGRU stage of every fused step: the stack's layers in turn
    over the chunk, layer 1 on ``xs`` (T, B, I), each layer above on the
    hidden states of the layer below.  Layer l at frame t reads only
    layer l−1 at frame t, so running each layer over the whole chunk in
    turn is the frame-interleaved order.  ``gru``/``state`` (and, int8,
    ``gfmt``) are one layer's entry or a tuple per layer; with ``gfmt``
    the layers run the integer datapath on codes.  Each layer's kernel
    runs under the named scope ``kws.gru.<l>`` (l = 0 first).

    Returns (the top layer's hidden states, the new state in the form
    of ``state``, one ``LayerCounts`` per layer)."""
    grus, states = _layers(gru), _layers(state)
    fmts = _layers(gfmt) if gfmt is not None else (None,) * len(grus)
    new, counts = [], []
    for layer, (g, st, fmt) in enumerate(zip(grus, states, fmts)):
        in_dim = xs.shape[-1]
        with jax.named_scope(f"kws.gru.{layer}"):
            if fmt is None:
                xs, st, stats = dg.delta_gru_scan(
                    g, xs, threshold=threshold, state=st, backend=backend,
                    interpret=interpret)
                nz_dx, nz_dh = stats.nz_dx, stats.nz_dh
            else:
                xs, st, nz_dx, nz_dh = fp.int_gru_scan(
                    g, fmt, xs, threshold, state=st, backend=backend,
                    interpret=interpret)
        new.append(st)
        counts.append(LayerCounts(nz_dx, nz_dh, in_dim, g.w_h.shape[0]))
    state = tuple(new) if type(state) in (tuple, list) else new[0]
    return xs, state, tuple(counts)


def _transmits(counts) -> Array:
    """(F, B) Δs transmitted per frame and slot, summed over layers."""
    return sum(c.nz_dx + c.nz_dh for c in counts)


@jax.named_scope("kws.head")
def _classify(w_fc, b_fc, hs, nz):
    logits = f32_dot(hs, w_fc) + b_fc             # (F, B, 12)
    votes = jnp.argmax(logits, -1).astype(jnp.int32)
    return ChunkResult(logits=logits, votes=votes, nz=nz)


def _bump(acc: _Accum, counts, n_frames: int, n_samples: int,
          vad_open=None, awake=None, s0=()) -> _Accum:
    """Accumulate one chunk's telemetry.  ``counts`` are the stage-1
    stack's ``LayerCounts``, ``s0`` the cascade's stage-0 ones (none
    without a cascade, so its MAC count stays 0).  ``vad_open`` is the
    device-side count of gate-open frame-slots (detect mode); ungated
    paths count every frame as open so ``vad_duty`` reads 1.0.
    ``awake`` is the cascade's stage-1 power count — cascade-free paths
    default to every frame awake (duty 1.0).

    Exact at any chunk size the transmit counts fit: each layer's chunk
    sum of transmitted Δs is an int32 (under 2^31), scaled by its 3H
    MACs per Δ inside the split counter (``_count_add``), and the dense
    count is a Python int from the static shapes.
    """
    def macs(c, layer_counts):
        for lc in layer_counts:
            c = _count_add(c, jnp.sum(lc.nz_dx + lc.nz_dh), 3 * lc.hidden)
        return c

    per_layer = lambda f: jnp.stack([jnp.sum(getattr(lc, f))
                                     for lc in counts])
    dense = n_frames * sum((lc.in_dim + lc.hidden) * 3 * lc.hidden
                           for lc in counts)
    return _Accum(
        macs=macs(acc.macs, counts),
        macs_dense=_count_add(acc.macs_dense, dense),
        frames=_count_add(acc.frames, n_frames),
        fex_samples=_count_add(acc.fex_samples, n_samples),
        vad_open=_count_add(acc.vad_open,
                            n_frames if vad_open is None else vad_open),
        s0_macs=macs(acc.s0_macs, s0),
        awake=_count_add(acc.awake, n_frames if awake is None else awake),
        nz_dx=_count_add(acc.nz_dx, per_layer("nz_dx")),
        nz_dh=_count_add(acc.nz_dh, per_layer("nz_dh")),
    )


def _feats_bad(feats) -> Array:
    """(F, B, C) frame-major features → per-slot (B,) non-finite flag."""
    return jnp.any(~jnp.isfinite(feats), axis=(0, 2))


def _process_chunk(gru, w_fc, b_fc, state, acc: _Accum, feats, *,
                   threshold: float, backend: str, interpret: bool | None):
    """Pure chunk step:
    (state, acc, feats (F,B,C)) -> (state', acc', out, health)."""
    in_bad = _feats_bad(feats)
    hs, state, counts = _gru_stack(gru, state, feats, threshold=threshold,
                                   backend=backend, interpret=interpret)
    out = _classify(w_fc, b_fc, hs, _transmits(counts))
    health = slot_health(in_bad, None, state, None, None)
    return (state, _bump(acc, counts, feats.shape[0] * feats.shape[1], 0),
            out, health)


@jax.named_scope("kws.head")
def _classify_int(w_fc, b_fc, hs_codes, nz, logit_frac: int):
    """FC + argmax on integer codes — the decision is the argmax over
    int32 logit codes (bit-true); the dequantized logits are returned
    for the float-typed ChunkResult surface."""
    codes = fp.int_fc(hs_codes, w_fc, b_fc)           # (F, B, 12) int32
    votes = jnp.argmax(codes, -1).astype(jnp.int32)
    return ChunkResult(logits=fp.from_code(codes, logit_frac),
                       votes=votes, nz=nz)


def _logit_frac(gfmt) -> int:
    """The FC head's logit grid of a (stack of) promoted formats."""
    return _layers(gfmt)[-1].logit_frac


def _process_chunk_int(gru, w_fc, b_fc, state, acc: _Accum, feats, *,
                       threshold: float, gfmt, backend: str,
                       interpret: bool | None):
    """Integer mirror of ``_process_chunk``: feats (F, B, C) floats on the
    12-bit grid → code domain → int ΔGRU stack → int FC.  ``state``
    carries integer codes (int16/int32 ``DeltaState``s)."""
    in_bad = _feats_bad(feats)
    xs = fp.to_code(feats, _layers(gfmt)[0].feat_frac, 16, jnp.int16)
    hs, state, counts = _gru_stack(gru, state, xs, threshold=threshold,
                                   backend=backend, interpret=interpret,
                                   gfmt=gfmt)
    out = _classify_int(w_fc, b_fc, hs, _transmits(counts),
                        _logit_frac(gfmt))
    health = slot_health(in_bad, None, state, None, None)
    return (state, _bump(acc, counts, feats.shape[0] * feats.shape[1], 0),
            out, health)


def _process_audio_chunk_int(gru, w_fc, b_fc, coef, fex_state: FExState,
                             state, acc: _Accum, audio, *, threshold: float,
                             backend: str, fex_backend: str,
                             interpret: bool | None, frame_shift: int,
                             gfmt, ffmt: fp.FexFormats):
    """Fused INTEGER audio→decision step: 12-bit ADC → int FEx → int ΔGRU
    stack → int FC in one jitted graph — the deployed datapath, bit-true
    against the golden fixed-point model.  ``fex_state`` holds int16
    register codes, ``state`` int16/int32 ΔGRU codes."""
    in_bad = jnp.any(~jnp.isfinite(audio), axis=1)    # pre-quantizer
    audio = quantize_audio_12b(audio.astype(jnp.float32))
    audio_codes = fp.to_code(audio, ffmt.feat_frac, 16, jnp.int16)
    feats, fex_buf = fp.int_fex_scan(
        audio_codes, coef, _pack_state(fex_state), ffmt,
        frame_shift=frame_shift, backend=fex_backend, interpret=interpret)
    xs = jnp.moveaxis(feats, 1, 0)                    # (F, B, C) codes
    hs, state, counts = _gru_stack(gru, state, xs, threshold=threshold,
                                   backend=backend, interpret=interpret,
                                   gfmt=gfmt)
    out = _classify_int(w_fc, b_fc, hs, _transmits(counts),
                        _logit_frac(gfmt))
    decisions = xs.shape[0] * xs.shape[1]             # frames × streams
    acc = _bump(acc, counts, decisions, decisions * frame_shift)
    fex_state = _unpack_state(fex_buf)
    health = slot_health(in_bad, fex_state, state, None, None)
    return fex_state, state, acc, out, health


def _process_audio_chunk(gru, w_fc, b_fc, coef, fex_state: FExState,
                         state, acc: _Accum, audio, *, threshold: float,
                         backend: str, fex_backend: str,
                         interpret: bool | None, frame_shift: int,
                         env_alpha: float, log_eps: float):
    """Fused audio→decision step: FEx → ΔGRU stack → FC in one jitted
    graph.

    audio: (B, S) raw samples, S a multiple of frame_shift.  Nothing in
    here leaves the device — only final logits/votes/counters do, when
    the caller fetches them.
    """
    in_bad = jnp.any(~jnp.isfinite(audio), axis=1)   # pre-quantizer
    audio = quantize_audio_12b(audio.astype(jnp.float32))
    feats, fex_state = fex_scan(
        audio, coef, fex_state, frame_shift=frame_shift,
        env_alpha=env_alpha, log_eps=log_eps, compress=True,
        backend=fex_backend, interpret=interpret)
    xs = jnp.moveaxis(feats, 1, 0)                # (F, B, C)
    hs, state, counts = _gru_stack(gru, state, xs, threshold=threshold,
                                   backend=backend, interpret=interpret)
    out = _classify(w_fc, b_fc, hs, _transmits(counts))
    decisions = xs.shape[0] * xs.shape[1]            # frames × streams
    acc = _bump(acc, counts, decisions, decisions * frame_shift)
    health = slot_health(in_bad, fex_state, state, None, None)
    return fex_state, state, acc, out, health


def _detect_tail(w_fc, b_fc, hs, nz, gate, *, logit_frac=None,
                 det_cfg: DetectorConfig, det_state: DetectorState,
                 awake=None):
    """Shared back half of the detect steps: FC → posterior smoothing →
    hysteresis events.  ``logit_frac`` set = integer FC on hidden CODES
    (the decision head consumes the dequantized — grid-exact — logits).
    ``awake`` (cascade sessions) masks fires on asleep frames: a frozen
    stage-1 h keeps emitting its held logits, and a keyword event may
    not fire while stage 0 says nothing is happening."""
    if logit_frac is None:
        cls = _classify(w_fc, b_fc, hs, nz)
    else:
        cls = _classify_int(w_fc, b_fc, hs, nz, logit_frac)
    post = jax.nn.softmax(cls.logits, axis=-1)       # (F, B, K)
    det_state, events = detector_scan(det_cfg, det_state, post)
    if awake is not None:
        events = jnp.where(awake, events, jnp.int32(-1))
    out = DetectResult(logits=cls.logits, votes=cls.votes, nz=cls.nz,
                       events=events, gate=gate, awake=awake)
    return det_state, out


def _process_audio_chunk_detect(gru, w_fc, b_fc, coef,
                                fex_state: FExState, state,
                                vad_state: VADState,
                                det_state: DetectorState, acc: _Accum,
                                audio, *, threshold: float, backend: str,
                                fex_backend: str, interpret: bool | None,
                                frame_shift: int, env_alpha: float,
                                log_eps: float, vad_cfg: VADConfig,
                                det_cfg: DetectorConfig):
    """Fused always-on DETECTION step: audio → FEx → VAD gate → ΔGRU
    stack → FC → posterior smoothing/hysteresis, one jitted graph, all
    state (filters, hold/hangover, x̂/ĥ/M, smoothed posteriors)
    slot-resident on device.  The VAD clamps the delta path by
    sample-and-holding the features during silence — Δx = 0 exactly, no
    kernel change."""
    in_bad = jnp.any(~jnp.isfinite(audio), axis=1)   # pre-quantizer
    audio = quantize_audio_12b(audio.astype(jnp.float32))
    energy = frame_energy(audio, frame_shift)        # (F, B)
    feats, fex_state = fex_scan(
        audio, coef, fex_state, frame_shift=frame_shift,
        env_alpha=env_alpha, log_eps=log_eps, compress=True,
        backend=fex_backend, interpret=interpret)
    xs = jnp.moveaxis(feats, 1, 0)                   # (F, B, C)
    xs, gate, vad_state = vad_gate(xs, energy, vad_state, vad_cfg)
    hs, state, counts = _gru_stack(gru, state, xs, threshold=threshold,
                                   backend=backend, interpret=interpret)
    det_state, out = _detect_tail(w_fc, b_fc, hs, _transmits(counts), gate,
                                  det_cfg=det_cfg, det_state=det_state)
    decisions = xs.shape[0] * xs.shape[1]
    acc = _bump(acc, counts, decisions, decisions * frame_shift,
                vad_open=jnp.sum(gate))
    health = slot_health(in_bad, fex_state, state, vad_state, det_state)
    return fex_state, state, vad_state, det_state, acc, out, health


def _process_audio_chunk_detect_int(gru, w_fc, b_fc, coef,
                                    fex_state: FExState, state,
                                    vad_state: VADState,
                                    det_state: DetectorState, acc: _Accum,
                                    audio, *, threshold: float,
                                    backend: str, fex_backend: str,
                                    interpret: bool | None,
                                    frame_shift: int, gfmt,
                                    ffmt: fp.FexFormats,
                                    vad_cfg: VADConfig,
                                    det_cfg: DetectorConfig):
    """Integer mirror of ``_process_audio_chunk_detect``: the VAD holds
    int16 FEATURE CODES (a held code stream is a zero integer delta,
    bit-true), the detector smooths posteriors from the dequantized int32
    logit codes (grid-exact floats, deterministic)."""
    in_bad = jnp.any(~jnp.isfinite(audio), axis=1)   # pre-quantizer
    audio = quantize_audio_12b(audio.astype(jnp.float32))
    energy = frame_energy(audio, frame_shift)        # float — pre-codes
    audio_codes = fp.to_code(audio, ffmt.feat_frac, 16, jnp.int16)
    feats, fex_buf = fp.int_fex_scan(
        audio_codes, coef, _pack_state(fex_state), ffmt,
        frame_shift=frame_shift, backend=fex_backend, interpret=interpret)
    xs = jnp.moveaxis(feats, 1, 0)                   # (F, B, C) codes
    xs, gate, vad_state = vad_gate(xs, energy, vad_state, vad_cfg)
    hs, state, counts = _gru_stack(gru, state, xs, threshold=threshold,
                                   backend=backend, interpret=interpret,
                                   gfmt=gfmt)
    det_state, out = _detect_tail(w_fc, b_fc, hs, _transmits(counts), gate,
                                  logit_frac=_logit_frac(gfmt),
                                  det_cfg=det_cfg, det_state=det_state)
    decisions = xs.shape[0] * xs.shape[1]
    acc = _bump(acc, counts, decisions, decisions * frame_shift,
                vad_open=jnp.sum(gate))
    fex_state = _unpack_state(fex_buf)
    health = slot_health(in_bad, fex_state, state, vad_state, det_state)
    return fex_state, state, vad_state, det_state, acc, out, health


def _process_audio_chunk_cascade(gru: dg.DeltaGRUParams, w_fc, b_fc,
                                 gru0: dg.DeltaGRUParams, w_fc0, b_fc0,
                                 coef, fex_state: FExState,
                                 state: dg.DeltaState,
                                 cas_state: CascadeState,
                                 vad_state: VADState,
                                 det_state: DetectorState, acc: _Accum,
                                 audio, *, threshold: float, backend: str,
                                 fex_backend: str, interpret: bool | None,
                                 frame_shift: int, env_alpha: float,
                                 log_eps: float, vad_cfg: VADConfig,
                                 det_cfg: DetectorConfig,
                                 cas_cfg: CascadeConfig):
    """Fused TWO-STAGE cascade step (DESIGN.md §13): audio → FEx → VAD →
    always-on stage-0 micro-ΔGRU → wake state machine → wake-gated
    stage-1 ΔGRU → FC → detection head, one jitted graph.  Stage 0 runs
    every frame on its reduced channel set; stage 1 executes only on
    awake frames — asleep slots keep their entire delta state bit-frozen
    and execute zero stage-1 MACs (``masked_delta_gru_scan``)."""
    in_bad = jnp.any(~jnp.isfinite(audio), axis=1)   # pre-quantizer
    audio = quantize_audio_12b(audio.astype(jnp.float32))
    energy = frame_energy(audio, frame_shift)        # (F, B)
    feats, fex_state = fex_scan(
        audio, coef, fex_state, frame_shift=frame_shift,
        env_alpha=env_alpha, log_eps=log_eps, compress=True,
        backend=fex_backend, interpret=interpret)
    xs = jnp.moveaxis(feats, 1, 0)                   # (F, B, C)
    xs, gate, vad_state = vad_gate(xs, energy, vad_state, vad_cfg)
    # Stage 0: always on, leading-channel subset, its own Δ_TH.
    xs0 = xs[..., :cas_cfg.s0_channels]
    hs0, s0_state, stats0 = dg.delta_gru_scan(
        gru0, xs0, threshold=cas_cfg.s0_threshold, state=cas_state.s0,
        backend=backend, interpret=interpret)
    score = jax.nn.softmax(f32_dot(hs0, w_fc0) + b_fc0, axis=-1)[..., 1]
    awake_t, awake, hang = cascade_wake_scan(cas_cfg, cas_state.awake,
                                             cas_state.hang, score)
    hs, state, stats = dg.masked_delta_gru_scan(gru, xs, threshold, state,
                                                awake_t)
    counts = (LayerCounts(stats.nz_dx, stats.nz_dh, xs.shape[-1],
                          gru.w_h.shape[0]),)
    det_state, out = _detect_tail(w_fc, b_fc, hs, _transmits(counts), gate,
                                  det_cfg=det_cfg, det_state=det_state,
                                  awake=awake_t)
    decisions = xs.shape[0] * xs.shape[1]
    acc = _bump(acc, counts, decisions, decisions * frame_shift,
                vad_open=jnp.sum(gate), awake=jnp.sum(awake_t),
                s0=(LayerCounts(stats0.nz_dx, stats0.nz_dh, xs0.shape[-1],
                                gru0.w_h.shape[0]),))
    health = slot_health(in_bad, fex_state, state, vad_state, det_state)
    health |= slot_health(jnp.zeros_like(in_bad), None, s0_state,
                          None, None)
    cas_state = CascadeState(awake=awake, hang=hang, s0=s0_state)
    return (fex_state, state, cas_state, vad_state, det_state, acc, out,
            health)


def _process_audio_chunk_cascade_int(gru: fp.IntGruWeights, w_fc, b_fc,
                                     gru0: fp.IntGruWeights, w_fc0, b_fc0,
                                     coef, fex_state: FExState,
                                     state: dg.DeltaState,
                                     cas_state: CascadeState,
                                     vad_state: VADState,
                                     det_state: DetectorState,
                                     acc: _Accum, audio, *,
                                     threshold: float, backend: str,
                                     fex_backend: str,
                                     interpret: bool | None,
                                     frame_shift: int, gfmt: fp.GruFormats,
                                     ffmt: fp.FexFormats,
                                     gfmt0: fp.GruFormats,
                                     vad_cfg: VADConfig,
                                     det_cfg: DetectorConfig,
                                     cas_cfg: CascadeConfig):
    """Integer mirror of ``_process_audio_chunk_cascade``: both stages
    run the deployed code-domain datapath (stage 0 through its OWN
    promoted formats ``gfmt0`` — its own golden fixed-point path), the
    wake machine scores dequantized — grid-exact — stage-0 logits, and
    asleep slots freeze their integer stage-1 state bit-for-bit
    (``masked_int_gru_scan``)."""
    in_bad = jnp.any(~jnp.isfinite(audio), axis=1)   # pre-quantizer
    audio = quantize_audio_12b(audio.astype(jnp.float32))
    energy = frame_energy(audio, frame_shift)        # float — pre-codes
    audio_codes = fp.to_code(audio, ffmt.feat_frac, 16, jnp.int16)
    feats, fex_buf = fp.int_fex_scan(
        audio_codes, coef, _pack_state(fex_state), ffmt,
        frame_shift=frame_shift, backend=fex_backend, interpret=interpret)
    xs = jnp.moveaxis(feats, 1, 0)                   # (F, B, C) codes
    xs, gate, vad_state = vad_gate(xs, energy, vad_state, vad_cfg)
    xs0 = xs[..., :cas_cfg.s0_channels]
    hs0, s0_state, nzx0, nzh0 = fp.int_gru_scan(
        gru0, gfmt0, xs0, cas_cfg.s0_threshold, state=cas_state.s0,
        backend=backend, interpret=interpret)
    logits0 = fp.from_code(fp.int_fc(hs0, w_fc0, b_fc0), gfmt0.logit_frac)
    score = jax.nn.softmax(logits0, axis=-1)[..., 1]
    awake_t, awake, hang = cascade_wake_scan(cas_cfg, cas_state.awake,
                                             cas_state.hang, score)
    hs, state, nz_dx, nz_dh = fp.masked_int_gru_scan(
        gru, gfmt, xs, threshold, state, awake_t)
    counts = (LayerCounts(nz_dx, nz_dh, xs.shape[-1], gru.w_h.shape[0]),)
    det_state, out = _detect_tail(w_fc, b_fc, hs, _transmits(counts), gate,
                                  logit_frac=gfmt.logit_frac,
                                  det_cfg=det_cfg, det_state=det_state,
                                  awake=awake_t)
    decisions = xs.shape[0] * xs.shape[1]
    acc = _bump(acc, counts, decisions, decisions * frame_shift,
                vad_open=jnp.sum(gate), awake=jnp.sum(awake_t),
                s0=(LayerCounts(nzx0, nzh0, xs0.shape[-1],
                                gru0.w_h.shape[0]),))
    fex_state = _unpack_state(fex_buf)
    health = slot_health(in_bad, fex_state, state, vad_state, det_state)
    health |= slot_health(jnp.zeros_like(in_bad), None, s0_state,
                          None, None)
    cas_state = CascadeState(awake=awake, hang=hang, s0=s0_state)
    return (fex_state, state, cas_state, vad_state, det_state, acc, out,
            health)


def _reset_layer(state: dg.DeltaState, bias, mask) -> dg.DeltaState:
    m = mask[:, None]

    def zero(a):
        return jnp.where(m, jnp.zeros((), a.dtype), a)

    return dg.DeltaState(
        h=zero(state.h), x_hat=zero(state.x_hat), h_hat=zero(state.h_hat),
        m_x=jnp.where(m, bias.astype(state.m_x.dtype), state.m_x),
        m_h=zero(state.m_h))


@jax.jit
def _reset_gru_slots(state, bias, mask):
    """Fresh-stream state for every slot where ``mask`` is True, in every
    layer: ``state``/``bias`` are one layer's ``DeltaState`` and bias, or
    a tuple of each per layer of a stack.

    Mask-select instead of per-slot dynamic updates: ONE compiled
    elementwise op resets an arbitrary admission wave (continuous
    batching can churn every slot of a shard in one serve step — a
    dispatch per slot would cost more than the chunk step itself).
    Slot-local by construction: under a sharded state the op is
    elementwise along the slot axis, so each shard rewrites only its own
    rows — no collectives, no reshard, no stall for other shards.
    """
    if type(state) in (tuple, list):
        return tuple(_reset_layer(st, b, mask)
                     for st, b in zip(state, bias))
    return _reset_layer(state, bias, mask)


@jax.jit
def _reset_fex_slots(state: FExState, mask) -> FExState:
    """Quiescent filters for every slot where ``mask`` is True (see above).
    Dtype-preserving: serves both the float state and the int8 path's
    int16 register codes."""
    return FExState(
        filt=jnp.where(mask[:, None, None],
                       jnp.zeros((), state.filt.dtype), state.filt),
        env=jnp.where(mask[:, None],
                      jnp.zeros((), state.env.dtype), state.env))


@jax.jit
def _reset_vad_slots(state: VADState, mask) -> VADState:
    """Fresh-stream VAD state for masked slots (see _reset_gru_slots):
    zero hold (matches x̂ = 0), no hangover.  Dtype-preserving (int16
    code hold in the int8 engine)."""
    return VADState(
        hold=jnp.where(mask[:, None], jnp.zeros((), state.hold.dtype),
                       state.hold),
        hang=jnp.where(mask, jnp.int32(0), state.hang))


@jax.jit
def _reset_det_slots(state: DetectorState, mask) -> DetectorState:
    """Idle detector for masked slots: zero smoothed posteriors, no open
    event, no refractory — bit-identical to a fresh stream's head."""
    return DetectorState(
        smooth=jnp.where(mask[:, None], 0.0, state.smooth),
        active=jnp.where(mask, jnp.int32(-1), state.active),
        refract=jnp.where(mask, jnp.int32(0), state.refract))


@jax.jit
def _reset_cascade_slots(state: CascadeState, bias0, mask) -> CascadeState:
    """Fresh cascade state for masked slots (see _reset_gru_slots):
    asleep, no hangover, stage-0 delta state zeroed with its M seeded at
    the stage-0 bias — bit-identical to a fresh stream's cascade."""
    return CascadeState(
        awake=jnp.where(mask, False, state.awake),
        hang=jnp.where(mask, jnp.int32(0), state.hang),
        s0=_reset_gru_slots(state.s0, bias0, mask))


class StreamingKwsSession:
    """Carries FEx + ΔGRU state and telemetry on device across chunks.

    Args:
      params: the trained KWS parameter tree (``models.kws.init_kws``).
      cfg: an ArchConfig (``d_model`` = GRU width).
      threshold: Δ_TH override (default ``cfg.delta_threshold``).
      batch: number of parallel streams sharing the session.
      input_dim: feature channels per frame (default: inferred lazily
        from the first chunk / the FEx configuration).
      backend: ΔGRU backend — "pallas" (default, one kernel launch per
        chunk) or "xla".
      fex: a ``FeatureExtractor`` (or ``FExConfig``) enabling raw-audio
        chunks via ``process_audio``; default-constructed on first use.
      fex_backend: FEx backend inside the fused step — default picks
        "pallas" when kernels compile (TPU) and the XLA scan under the
        interpreter, where the scan body is faster (identical numerics
        either way, so the choice is invisible).
      mesh: a 1-D ("data",) device mesh (``launch.mesh.make_slot_mesh``)
        turning the session into a sharded engine: slots partitioned
        over the mesh, weights replicated, telemetry per-shard.  ``batch``
        must divide by the mesh size.  ``None`` (default) = unsharded,
        bit-identical to the sharded engine on one device.
      numerics: "float32" (default) or "int8" — the deployed integer
        datapath: 12-bit ADC → integer FEx → int8-weight/int16-state
        ΔGRU → integer FC, bit-true against the golden fixed-point model
        (``core.fixed_point``).  All stream state is carried as integer
        codes; decisions are argmaxes over int32 logit codes.
      bundle: a promoted ``IntKwsBundle`` (``train.promote``) to serve.
        With a bundle, ``params`` may be None and the bundle's Δ_TH is
        authoritative; without one (numerics="int8"), ``params`` is
        promoted in place — the train→deploy fold at session creation.
      quantize_8b: 8-bit STE weight quantization on the FLOAT path (the
        pre-§9 approximation; the bit-true route is ``numerics="int8"``).
      interpret: force the Pallas interpreter on/off (None = platform
        default via ``kernels.platform.resolve_interpret``).
      detector: a ``models.detector.DetectorConfig`` switching the
        session into always-on DETECTION mode (DESIGN.md §10):
        ``process_audio`` runs audio → FEx → VAD gate → ΔGRU → FC →
        posterior-smoothing/hysteresis head in the one fused step and
        returns ``DetectResult`` (per-frame fired events + gate trace).
        Detector state is per-slot, device-resident, slot-sharded, and
        reset by ``reset_streams`` like every other stream state.
      vad: a ``frontend.vad.VADConfig`` for the energy gate that clamps
        the ΔGRU delta path during silence (detect mode only; default
        ``VADConfig()``; pass ``vad=VAD_OFF`` to disable gating while
        keeping the detection head).
      cascade: a ``CascadeConfig`` enabling the two-stage wake cascade
        (DESIGN.md §13) on top of detection mode: an always-on stage-0
        micro-ΔGRU (trained on ``cascade.s0_channels`` leading FEx
        channels, binary event/no-event head) scores every frame, and
        the big stage-1 network executes only while the wake state
        machine says a candidate event is live — asleep slots keep
        their entire stage-1 delta state bit-frozen and execute zero
        stage-1 MACs.  Requires ``detector`` (the cascade gates the
        always-on pipeline) and ``stage0_params``.  Per-stage energy is
        priced by ``core.energy_model.cascade_frame_cost`` in
        ``summary()``.
      stage0_params: the stage-0 micro model's parameter tree (a
        ``models.kws.init_kws`` tree with ``vocab_size=2`` and the
        reduced ``d_model``/input width).  Under ``numerics="int8"`` it
        is promoted through its own golden fixed-point path
        (``core.fixed_point.promote_kws``) at session creation, so both
        stages serve the deployed code-domain datapath.
      supervisor: a ``SupervisorConfig`` enabling the self-healing
        supervisor (DESIGN.md §11): the per-slot health mask the fused
        step emits is fetched every ``check_every`` chunks, and slots
        whose quarantine-mask bits stay set for ``quarantine_after``
        consecutive checks are auto-reset to fresh-stream state (the
        same mask-batched ``reset_streams`` continuous batching uses);
        recoveries and their reasons surface in ``StreamSummary``.
        ``None`` (default) disables healing — flags are still computed
        (the datapath is identical) but nobody reads them.
      input_policy: what ``process_audio`` does with hostile samples —
        "reject" (default) raises ``StreamInputError`` on non-finite
        samples, "sanitize" squashes NaN to silence and clamps ±Inf to
        the 12-bit rails, "trust" forwards them to the device untouched
        (the soak harness uses this to exercise device-side healing).
        Un-decodable dtypes and out-of-range integer codes always
        reject, under every policy.

    State contract: between ``process_audio`` calls, ALL stream state —
    FEx registers, carried sample remainder length aside, ΔGRU x̂/ĥ/M,
    VAD hold/hangover, detector smooth/latch — lives on device, sharded
    on the slot axis; chunk boundaries (any split, frame-aligned or
    not) and mesh size do not change a single output bit.
    """

    def __init__(self, params, cfg, *, threshold: float | None = None,
                 batch: int = 1, input_dim: int | None = None,
                 quantize_8b: bool = False, backend: str = "pallas",
                 interpret: bool | None = None,
                 fex: FeatureExtractor | FExConfig | None = None,
                 fex_backend: str | None = None, mesh=None,
                 numerics: str = "float32",
                 bundle: fp.IntKwsBundle | None = None,
                 detector: DetectorConfig | None = None,
                 vad: VADConfig | None = None,
                 cascade: CascadeConfig | None = None,
                 stage0_params=None,
                 supervisor: SupervisorConfig | None = None,
                 input_policy: str = "reject"):
        if numerics not in ("float32", "int8"):
            raise ValueError(f"unknown numerics: {numerics!r}")
        if input_policy not in ("reject", "sanitize", "trust"):
            raise ValueError(f"unknown input_policy: {input_policy!r} "
                             f"(choose reject / sanitize / trust)")
        if vad is not None and detector is None:
            raise ValueError("vad gating is part of detection mode: pass "
                             "a DetectorConfig alongside the VADConfig")
        if detector is not None and det_mod.band_inverted(detector):
            raise ValueError(
                f"inverted hysteresis band: release_threshold "
                f"({detector.release_threshold}) must be <= fire_threshold "
                f"({detector.fire_threshold}) elementwise — an inverted "
                f"band degrades the head into a refractory-paced pulse "
                f"generator")
        if cascade is not None:
            if detector is None:
                raise ValueError("the wake cascade gates the always-on "
                                 "pipeline: pass a DetectorConfig "
                                 "alongside the CascadeConfig")
            if stage0_params is None:
                raise ValueError("cascade mode needs the stage-0 micro "
                                 "model: pass stage0_params")
            if cascade.sleep_threshold > cascade.wake_threshold:
                raise ValueError(
                    f"inverted wake hysteresis: sleep_threshold "
                    f"({cascade.sleep_threshold}) must be <= "
                    f"wake_threshold ({cascade.wake_threshold})")
            if cascade.hangover_frames < 0:
                raise ValueError("hangover_frames must be >= 0")
            s0_in = int(np.asarray(stage0_params["w_x"]).shape[0])
            if s0_in != cascade.s0_channels:
                raise ValueError(
                    f"stage-0 model consumes {s0_in} channels but "
                    f"cascade.s0_channels={cascade.s0_channels}")
        self._detector = detector
        self._vad = (vad if vad is not None else VADConfig()) \
            if detector is not None else None
        self.cfg = cfg
        self.batch = batch
        self.mesh = mesh
        self.numerics = numerics
        self.n_shards = shp.check_slot_partition(mesh, batch)
        self.threshold = (cfg.delta_threshold if threshold is None
                          else threshold)
        self._fex = (FeatureExtractor(fex) if isinstance(fex, FExConfig)
                     else fex)
        self._bundle = bundle
        if numerics == "int8":
            if bundle is None:
                self._bundle = fp.promote_kws(params, self.threshold,
                                              fex=self._fex)
            self.threshold = self._bundle.threshold
            self._gru = shp.put_replicated(self._bundle.gru, mesh)
            self._w_fc, self._b_fc = shp.put_replicated(
                (self._bundle.w_fc, self._bundle.b_fc), mesh)
        else:
            self._gru, self._w_fc, self._b_fc = kws.serving_weights(
                params, quantize_8b, mesh)
        self.n_layers = len(_layers(self._gru))
        if cascade is not None and self.n_layers > 1:
            raise ValueError(
                f"the wake cascade gates one stage-1 ΔGRU layer; a "
                f"{self.n_layers}-layer stack is served in audio and "
                f"detect mode only")
        # The class count rides the FC head's shape — an 11/35-class (or
        # 2-class stage-0) head serves through the same session code.
        self.n_classes = int(self._b_fc.shape[-1])
        self._cascade = cascade
        self._bundle0 = None
        if cascade is not None:
            if numerics == "int8":
                # Stage 0 gets its OWN promotion: per-tensor exponents
                # from its own trained dynamic range (gfmt0 ≠ gfmt).
                self._bundle0 = fp.promote_kws(stage0_params,
                                               cascade.s0_threshold)
                self._gru0 = shp.put_replicated(self._bundle0.gru, mesh)
                self._w_fc0, self._b_fc0 = shp.put_replicated(
                    (self._bundle0.w_fc, self._bundle0.b_fc), mesh)
            else:
                self._gru0, self._w_fc0, self._b_fc0 = \
                    kws.serving_weights(stage0_params, quantize_8b, mesh)
            self._s0_hidden = int(self._gru0.w_h.shape[0])
            self._s0_classes = int(self._b_fc0.shape[-1])
        self._state: dg.DeltaState | None = None
        self._cas_state: CascadeState | None = None
        self._coef = None                           # replicated FEx coeffs
        self._fex_state: FExState | None = None
        self._vad_state: VADState | None = None
        self._det_state: DetectorState | None = None
        self._audio_rem: np.ndarray | None = None   # carried tail samples
        self.frame_joins = 0                # steps that joined a tail
        self._acc = shp.put_slot_sharded(
            _zero_accum(self.n_shards, self.n_layers), mesh)
        self._chunks = 0
        self._input_dim = input_dim
        if fex_backend is None:
            fex_backend = "xla" if resolve_interpret(interpret) else "pallas"
        self._fex_backend = fex_backend
        self._backend = backend
        self._interpret = interpret
        self.supervisor = supervisor
        self.input_policy = input_policy
        self._last_health: Array | None = None
        self._strikes = np.zeros((batch,), np.int64)
        self._recoveries = 0
        self._recovery_reasons: dict[str, int] = {}
        self._sat_events = 0
        self._flagged: frozenset[int] = frozenset()
        self._slo: dict = {}
        # Compiled steps are cached PER Δ_TH: ``set_threshold`` (the
        # degradation lever) re-points at a cached jit instead of paying
        # a retrace every time the controller steps up and back down.
        self._step_cache: dict[float, list] = {}
        self._fex_kw: dict | None = None            # set by _require_fex
        self._step = None
        self._audio_step_fn = None
        self._audio_step = None
        self._use_threshold(self.threshold)
        if input_dim is not None:
            self._init_state(input_dim)

    def _make_step_fns(self, threshold: float):
        """Build (jitted feature step, audio-step partial) for one Δ_TH.

        _process_chunk(gru, w_fc, b_fc, state, acc, feats): state/acc are
        slot-major, feats is time-major with slots on axis 1.  The int8
        step has the same argument geometry, so the shard wrapper is
        numerics-agnostic.
        """
        det_kw = ({"vad_cfg": self._vad, "det_cfg": self._detector}
                  if self._detector is not None else {})
        if self._cascade is not None:
            det_kw["cas_cfg"] = self._cascade
        if self.numerics == "int8":
            if self._backend not in ("pallas", "xla"):
                raise ValueError(f"unknown ΔGRU backend: {self._backend!r}")
            step_fn = functools.partial(
                _process_chunk_int, threshold=threshold,
                gfmt=self._bundle.gfmt, backend=self._backend,
                interpret=self._interpret)
            if self._cascade is not None:
                audio_fn = _process_audio_chunk_cascade_int
                det_kw["gfmt0"] = self._bundle0.gfmt
            else:
                audio_fn = (_process_audio_chunk_detect_int
                            if self._detector is not None
                            else _process_audio_chunk_int)
            audio_step_fn = functools.partial(
                audio_fn, threshold=threshold,
                backend=self._backend, fex_backend=self._fex_backend,
                interpret=self._interpret, gfmt=self._bundle.gfmt, **det_kw)
        else:
            step_fn = functools.partial(
                _process_chunk, threshold=threshold,
                backend=self._backend, interpret=self._interpret)
            if self._cascade is not None:
                audio_fn = _process_audio_chunk_cascade
            else:
                audio_fn = (_process_audio_chunk_detect
                            if self._detector is not None
                            else _process_audio_chunk)
            audio_step_fn = functools.partial(
                audio_fn, threshold=threshold,
                backend=self._backend, fex_backend=self._fex_backend,
                interpret=self._interpret, **det_kw)
        step = jax.jit(self._shard(
            step_fn, n_args=6, slot_major=(3, 4), time_major=(5,),
            n_state_out=2))
        return step, audio_step_fn

    def _build_audio_step(self, audio_step_fn):
        """Jit + shard the fused audio step once the FEx kwargs are known.

        Every variant is jitted under one stable name, so the device
        trace shows the step as the module ``jit_kws_audio_step``."""
        fn = functools.partial(audio_step_fn, **self._fex_kw)
        if self._cascade is not None:
            # _process_audio_chunk_cascade[_int](gru, w_fc, b_fc, gru0,
            # w_fc0, b_fc0, coef, fex_state, state, cas_state, vad_state,
            # det_state, acc, audio): five state trees + acc + audio are
            # slot-major; both stages' weights are replicated.
            sharded = self._shard(
                fn, n_args=14, slot_major=(7, 8, 9, 10, 11, 12, 13),
                time_major=(), n_state_out=6)
        elif self._detector is not None:
            # _process_audio_chunk_detect[_int](gru, w_fc, b_fc, coef,
            # fex_state, state, vad_state, det_state, acc, audio):
            # the four state trees + acc + audio are slot-major.
            sharded = self._shard(
                fn, n_args=10, slot_major=(4, 5, 6, 7, 8, 9),
                time_major=(), n_state_out=5)
        else:
            # _process_audio_chunk[_int](gru, w_fc, b_fc, coef,
            # fex_state, state, acc, audio): fex_state/state/acc/audio
            # are slot-major.
            sharded = self._shard(
                fn, n_args=8, slot_major=(4, 5, 6, 7), time_major=(),
                n_state_out=3)

        def kws_audio_step(*args):
            return sharded(*args)

        return jax.jit(kws_audio_step)

    def kernel_tuning_report(self) -> dict:
        """Which autotuned kernel configs THIS session's steps resolve.

        The dispatch layers consult the ``kernels.autotune`` cache at
        trace time with the per-shard shapes the session actually runs.
        This reports the RAW cached config under each of those keys —
        the dispatch additionally sanitizes knobs against the concrete
        chunk geometry (a ``block_t`` only applies when it divides the
        chunk's frame count), so a listed knob may still fall back to
        its default for an incompatible chunk.  An empty config means
        cold cache → static defaults.  Never raises (a broken cache
        reads as empty); purely observational.
        """
        from repro.kernels import autotune
        enabled = autotune.autotune_enabled()
        b_shard = self.batch // self.n_shards
        report: dict = {"platform": autotune.platform_tag(self._interpret),
                        "fex_backend": self._fex_backend,
                        "cache": str(autotune.cache_path()),
                        "enabled": enabled,
                        "kernels": {}}

        def entry(kernel, shape, dtype, threshold):
            cfg = (autotune.lookup(kernel, shape, dtype, threshold,
                                   self._interpret) if enabled else None)
            return {"shape": list(shape), "config": cfg or {}}

        H = int(_layers(self._gru)[0].w_h.shape[0])
        gru_kernel = ("delta_gru_seq_int" if self.numerics == "int8"
                      else "delta_gru_seq")
        gru_dtype = "int8" if self.numerics == "int8" else "float32"
        if self._input_dim is not None:
            report["kernels"][gru_kernel] = entry(
                gru_kernel, (b_shard, int(self._input_dim), H), gru_dtype,
                self.threshold)
        if self._fex is not None:
            fcfg = self._fex.cfg
            is_int = self.numerics == "int8"
            fex_kernel = "batched_iir_fex_int" if is_int else "batched_iir_fex"
            report["kernels"][fex_kernel] = entry(
                fex_kernel,
                (b_shard, int(fcfg.n_active), int(fcfg.frame_shift)),
                "int16" if is_int else "float32", 0.0)
        return report

    def _use_threshold(self, threshold: float):
        """Point the session's compiled steps at one Δ_TH (cached)."""
        cached = self._step_cache.get(threshold)
        if cached is None:
            step, audio_step_fn = self._make_step_fns(threshold)
            cached = [step, audio_step_fn, None]
            self._step_cache[threshold] = cached
        if cached[2] is None and self._fex_kw is not None:
            cached[2] = self._build_audio_step(cached[1])
        self.threshold = threshold
        self._step, self._audio_step_fn, self._audio_step = cached

    def set_threshold(self, threshold: float):
        """Re-point the serving step at a different Δ_TH operating point
        mid-stream — the graceful-degradation lever (DESIGN.md §11).

        Carried stream state (FEx/ΔGRU/VAD/detector) is untouched: the
        next chunk simply runs with the new delta deadband, trading
        accuracy for compute along the measured nJ/decision curve
        (``BENCH_detect.json``).  Compiled steps are cached per distinct
        threshold, so a controller stepping up under overload and back
        down on release pays one compile per operating POINT, not per
        switch.  Raises ``ValueError`` for non-finite or negative
        thresholds.  No-op when the threshold is already current.
        """
        threshold = float(threshold)
        if not np.isfinite(threshold) or threshold < 0.0:
            raise ValueError(f"delta threshold must be finite and >= 0, "
                             f"got {threshold}")
        if threshold == self.threshold:
            return
        self._use_threshold(threshold)

    def _shard(self, fn, *, n_args: int, slot_major: tuple[int, ...],
               time_major: tuple[int, ...], n_state_out: int):
        """Wrap a pure chunk step in shard_map over the slot mesh.

        ``slot_major``: positions of per-stream args with the slot axis
        FIRST (state trees, telemetry, raw audio) → prefix P("data");
        ``time_major``: frame-major inputs with slots on axis 1 →
        P(None, "data"); every other arg (weights, coefficients) is
        replicated.  Outputs follow the fixed (state…, acc, ChunkResult,
        health) convention: ``n_state_out`` slot-major trees, the
        time-major ChunkResult, then the slot-major (B,) health mask.
        No-op without a mesh — the unsharded session is byte-for-byte
        the pre-sharding code path.
        """
        if self.mesh is None:
            return fn
        specs = [P()] * n_args
        for i in slot_major:
            specs[i] = P(shp.SLOT_AXIS)
        for i in time_major:
            specs[i] = P(None, shp.SLOT_AXIS)
        out_specs = tuple([P(shp.SLOT_AXIS)] * n_state_out
                          + [P(None, shp.SLOT_AXIS), P(shp.SLOT_AXIS)])
        return shard_map_kernels(fn, self.mesh, in_specs=tuple(specs),
                                 out_specs=out_specs)

    def _init_state(self, input_dim: int):
        """Fresh ΔGRU state: one ``DeltaState``, or a tuple of one per
        layer of a stack (layer 1 reads ``input_dim`` features, each
        layer above the ``d_model`` hidden state below)."""
        self._input_dim = input_dim
        H = self.cfg.d_model
        init = (fp.init_int_delta_state if self.numerics == "int8"
                else dg.init_delta_state)
        grus = _layers(self._bundle.gru if self.numerics == "int8"
                       else self._gru)
        states = tuple(init(self.batch, input_dim if layer == 0 else H, H,
                            g) for layer, g in enumerate(grus))
        state = states[0] if self.n_layers == 1 else states
        self._state = shp.put_slot_sharded(state, self.mesh)

    def _fresh_fex_state(self, n_channels: int) -> FExState:
        if self.numerics == "int8":
            return _unpack_state(
                fp.init_int_fex_state(self.batch, n_channels))
        return init_fex_state(self.batch, n_channels)

    def _require_fex(self) -> FeatureExtractor:
        if self._fex is None:
            self._fex = FeatureExtractor()
        fcfg = self._fex.cfg
        if self._input_dim is None:
            self._init_state(fcfg.n_active)
        elif self._input_dim != fcfg.n_active:
            raise ValueError(f"FEx emits {fcfg.n_active} channels, session "
                             f"state is {self._input_dim}-wide")
        if self._fex_state is None:
            if self.numerics == "int8":
                # Fold the FEx coefficient bank into the bundle if the
                # promotion happened without one (feature-mode bundles).
                # fold_fex copies — a caller-shared bundle is untouched.
                self._bundle = fp.fold_fex(self._bundle, self._fex)
                self._coef = shp.put_replicated(self._bundle.coef,
                                                self.mesh)
                self._fex_kw = {"frame_shift": fcfg.frame_shift,
                                "ffmt": self._bundle.ffmt}
            else:
                self._coef = shp.put_replicated(self._fex.coef, self.mesh)
                self._fex_kw = {"frame_shift": fcfg.frame_shift,
                                "env_alpha": fcfg.env_alpha,
                                "log_eps": fcfg.log_eps}
            self._fex_state = shp.put_slot_sharded(
                self._fresh_fex_state(fcfg.n_active), self.mesh)
            self._audio_rem = np.zeros((self.batch, 0), np.float32)
            if self._detector is not None:
                # VAD holds what the ΔGRU eats: float features on the
                # float path, int16 feature CODES in the int8 engine.
                hold_dtype = (jnp.int16 if self.numerics == "int8"
                              else jnp.float32)
                self._vad_state = shp.put_slot_sharded(
                    init_vad_state(self.batch, fcfg.n_active, hold_dtype),
                    self.mesh)
                self._det_state = shp.put_slot_sharded(
                    init_detector_state(self.batch, self.n_classes),
                    self.mesh)
            if self._cascade is not None:
                s0_gru = (self._bundle0.gru if self.numerics == "int8"
                          else self._gru0)
                self._cas_state = shp.put_slot_sharded(
                    init_cascade_state(self.batch, s0_gru,
                                       int8=self.numerics == "int8"),
                    self.mesh)
            # Re-enter the cache now that the FEx kwargs are known —
            # this builds (and caches) the fused audio step.
            self._use_threshold(self.threshold)
        return self._fex

    def _coerce_audio(self, audio) -> np.ndarray:
        """Decode + police one raw-audio chunk per ``input_policy``.

        Integer arrays are treated as ADC codes: range-checked against
        int16 and decoded to float (a wrong-range code is a framing bug,
        not audio — always rejected).  Float arrays are policed for
        non-finite samples according to the policy; anything else (text,
        objects, complex, bools) cannot be audio and raises
        ``StreamInputError`` outright.  Clean float32 input comes back
        as the caller's own array, uncopied.
        """
        arr = np.asarray(audio)
        if arr.dtype.kind in "iu":
            if arr.size and (int(arr.min()) < -32768
                             or int(arr.max()) > 32767):
                raise StreamInputError(
                    f"integer audio must be int16-range ADC codes in "
                    f"[-32768, 32767]; got values in "
                    f"[{int(arr.min())}, {int(arr.max())}]")
            return arr.astype(np.float32) / 32768.0
        if arr.dtype.kind != "f":
            raise StreamInputError(
                f"audio dtype {arr.dtype} is not decodable: pass float "
                f"samples in [-1, 1) or int16-range integer codes")
        arr = arr.astype(np.float32, copy=False)
        if self.input_policy == "trust":
            return arr
        # A finite sum proves every sample finite (NaN and ±inf
        # propagate through a sum) without a temporary; only a sum that
        # is not finite — a bad sample, or huge finite ones overflowing
        # — pays for the exact count.
        with np.errstate(over="ignore", invalid="ignore"):
            total = np.add.reduce(arr, axis=None)
        if np.isfinite(total):
            return arr
        n_bad = int(np.count_nonzero(~np.isfinite(arr)))
        if n_bad:
            if self.input_policy == "reject":
                raise StreamInputError(
                    f"{n_bad} non-finite samples in audio chunk "
                    f"(input_policy='reject'; use 'sanitize' to squash "
                    f"them instead)")
            arr = np.nan_to_num(arr, nan=0.0, posinf=1.0 - 2.0 ** -11,
                                neginf=-1.0)
        return arr

    def process_audio(self, audio) -> ChunkResult:
        """Run a chunk of RAW audio through the fused FEx→ΔGRU→FC step.

        ``audio``: (samples,) for a single stream, or (batch, samples)
        float in [-1, 1) — or int16-range integer ADC codes, which are
        decoded.  Hostile inputs are policed per the session's
        ``input_policy`` (``StreamInputError`` under the default
        "reject").  One jitted device step per chunk — zero host syncs
        inside the chunk.  Samples past the last whole 16 ms frame are
        buffered host-side and prepended to the next chunk, so chunk
        boundaries (frame-aligned or not) are bit-invisible.

        Returns DEVICE arrays with one row per COMPLETED frame (possibly
        zero rows when the chunk is shorter than the carried remainder's
        complement).  Like ``process_chunk``, the step is compiled per
        chunk length.

        Ownership: clean float32 audio is neither copied nor joined when
        no remainder is carried (``frame_joins`` counts the steps that
        joined one), so the dispatched step reads the caller's array —
        on CPU ``jnp.asarray`` may alias it.  The session keeps no view
        of that array past the call (the carried remainder is a copy);
        a caller that reuses the array must first fetch the step's
        result, as ``PipelinedEngine``'s staging ring does.
        """
        fex = self._require_fex()
        with spans.span(spans.POLICE):
            audio = self._coerce_audio(audio)
        if audio.ndim == 1:
            audio = audio[None]
        if audio.shape[0] != self.batch:
            raise ValueError(f"audio carries {audio.shape[0]} streams, "
                             f"session was created with batch={self.batch}")
        with spans.span(spans.FRAME):
            if self._audio_rem.shape[1]:    # join the carried tail
                audio = np.concatenate([self._audio_rem, audio], axis=1)
                self.frame_joins += 1
            shift = fex.cfg.frame_shift
            n_frames = audio.shape[1] // shift
            cut = n_frames * shift
            self._audio_rem = audio[:, cut:].copy()   # never a view
            block = audio[:, :cut]
        if n_frames == 0:
            z = jnp.zeros((0, self.batch), jnp.int32)
            logits = jnp.zeros((0, self.batch, self.n_classes))
            if self._cascade is not None:
                return DetectResult(logits=logits, votes=z, nz=z, events=z,
                                    gate=jnp.zeros((0, self.batch), bool),
                                    awake=jnp.zeros((0, self.batch), bool))
            if self._detector is not None:
                return DetectResult(logits=logits, votes=z, nz=z, events=z,
                                    gate=jnp.zeros((0, self.batch), bool))
            return ChunkResult(logits=logits, votes=z, nz=z)
        with spans.span(spans.H2D):
            block = jnp.asarray(block)
        with spans.span(spans.LAUNCH):
            outs = self._audio_step(*self._audio_step_args(block))
            if self._cascade is not None:
                (self._fex_state, self._state, self._cas_state,
                 self._vad_state, self._det_state, self._acc, out,
                 health) = outs
            elif self._detector is not None:
                (self._fex_state, self._state, self._vad_state,
                 self._det_state, self._acc, out, health) = outs
            else:
                self._fex_state, self._state, self._acc, out, health = outs
            self._last_health = health
            self._chunks += 1
        self._maybe_heal()
        return out

    def _audio_step_args(self, block):
        """The fused audio step's arguments for one (batch, S) block —
        the positional layouts ``_build_audio_step`` documents."""
        if self._cascade is not None:
            return (self._gru, self._w_fc, self._b_fc,
                    self._gru0, self._w_fc0, self._b_fc0, self._coef,
                    self._fex_state, self._state, self._cas_state,
                    self._vad_state, self._det_state, self._acc, block)
        if self._detector is not None:
            return (self._gru, self._w_fc, self._b_fc, self._coef,
                    self._fex_state, self._state, self._vad_state,
                    self._det_state, self._acc, block)
        return (self._gru, self._w_fc, self._b_fc, self._coef,
                self._fex_state, self._state, self._acc, block)

    def fused_audio_step(self, n_samples: int):
        """``(step, args)``: the jitted fused audio step and the argument
        tuple ``process_audio`` would hand it for a frame-aligned chunk
        of ``n_samples`` per slot (a zero block, current state).  For
        inspection — ``step.lower(*args).compile().as_text()`` shows
        what the device runs; calling it changes no session state."""
        fex = self._require_fex()
        n = n_samples // fex.cfg.frame_shift * fex.cfg.frame_shift
        block = jnp.zeros((self.batch, n), jnp.float32)
        return self._audio_step, self._audio_step_args(
            shp.put_slot_sharded(block, self.mesh))

    def process_chunk(self, feats) -> ChunkResult:
        """Run one chunk of pre-computed FRAMES through the resident ΔGRU.

        ``feats``: (frames, channels) for a single stream, or
        (frames, batch, channels).  Returns DEVICE arrays — call
        ``np.asarray``/``jax.device_get`` on the result at most once per
        chunk; nothing in here blocks on the device.

        The step is compiled per chunk LENGTH: feeding equal-sized
        chunks reuses the compiled kernel, while every new length pays
        a one-off retrace/compile (a host stall).  For jitter-free
        serving, buffer audio to a fixed frames-per-chunk; a single
        ragged tail chunk at end-of-stream costs one extra compile.
        """
        if self._detector is not None:
            raise ValueError("detection mode needs raw audio (the VAD "
                             "gates on sample energy): use process_audio")
        feats = jnp.asarray(feats, jnp.float32)
        if feats.ndim == 2:
            feats = feats[:, None, :]                 # (F, 1, C)
        if feats.shape[0] == 0:
            raise ValueError("empty chunk: need at least one frame")
        if feats.shape[1] != self.batch:
            raise ValueError(f"chunk carries {feats.shape[1]} streams, "
                             f"session was created with batch={self.batch}")
        if self._state is None:
            self._init_state(feats.shape[-1])
        elif feats.shape[-1] != self._input_dim:
            raise ValueError(f"chunk has {feats.shape[-1]} feature channels,"
                             f" session state is {self._input_dim}-wide")
        self._state, self._acc, out, health = self._step(
            self._gru, self._w_fc, self._b_fc, self._state, self._acc, feats)
        self._last_health = health
        self._chunks += 1
        self._maybe_heal()
        return out

    @property
    def state(self):
        """The slots' ΔGRU state: one ``DeltaState``, or for a stack a
        tuple of one per layer, the first layer's first."""
        return self._state

    @property
    def fex_state(self) -> FExState | None:
        return self._fex_state

    def reset(self):
        """Forget stream state + telemetry (keeps weights/compiled step)."""
        if self._input_dim is not None:
            self._init_state(self._input_dim)
        if self._fex_state is not None:
            self._fex_state = shp.put_slot_sharded(
                self._fresh_fex_state(self._input_dim), self.mesh)
            self._audio_rem = np.zeros((self.batch, 0), np.float32)
        if self._vad_state is not None:
            self._vad_state = shp.put_slot_sharded(
                init_vad_state(self.batch, self._input_dim,
                               self._vad_state.hold.dtype), self.mesh)
        if self._det_state is not None:
            self._det_state = shp.put_slot_sharded(
                init_detector_state(self.batch, self.n_classes), self.mesh)
        if self._cas_state is not None:
            s0_gru = (self._bundle0.gru if self.numerics == "int8"
                      else self._gru0)
            self._cas_state = shp.put_slot_sharded(
                init_cascade_state(self.batch, s0_gru,
                                   int8=self.numerics == "int8"), self.mesh)
        self._acc = shp.put_slot_sharded(
            _zero_accum(self.n_shards, self.n_layers), self.mesh)
        self._chunks = 0
        self.frame_joins = 0
        self._last_health = None
        self._strikes = np.zeros((self.batch,), np.int64)
        self._recoveries = 0
        self._recovery_reasons = {}
        self._sat_events = 0
        self._flagged = frozenset()
        self._slo = {}

    def reset_stream(self, i: int):
        """Reset ONE stream slot to a fresh-stream state (continuous
        batching: a finished utterance's slot is re-admitted without
        disturbing the other streams).  See ``reset_streams``."""
        self.reset_streams([i])

    def reset_streams(self, slots):
        """Reset a WAVE of stream slots to fresh-stream state in one go.

        Slot-LOCAL device-side updates — no sync, and under a mesh no
        collectives either: the jitted mask-select is elementwise along
        the (sharded) slot axis, so each shard rewrites only its own
        rows and churn on one shard never stalls the streams on others.
        Batched on purpose: continuous batching can re-admit dozens of
        slots after one serve step, and a dispatch per slot would
        dominate the step itself; a wave is two dispatches total.

        Caveat: the carried sample remainder's LENGTH is shared across
        streams, so the reset zeroes a slot's buffered samples but
        cannot drop them — after a reset mid-remainder the new stream
        starts up to ``frame_shift−1`` zero samples early relative to a
        fresh session.  Feed frame-aligned chunks (the serve launcher's
        default) to keep resets exactly fresh."""
        slots = list(slots)
        for i in slots:
            if not (0 <= i < self.batch):
                raise ValueError(f"stream {i} out of range [0, {self.batch})")
        if not slots:
            return
        mask = np.zeros((self.batch,), bool)
        mask[slots] = True
        mask = jnp.asarray(mask)
        if self._state is not None:
            bias = tuple(g.b for g in _layers(self._gru))
            self._state = _reset_gru_slots(
                self._state, bias if self.n_layers > 1 else bias[0], mask)
        if self._fex_state is not None:
            self._fex_state = _reset_fex_slots(self._fex_state, mask)
        if self._vad_state is not None:
            self._vad_state = _reset_vad_slots(self._vad_state, mask)
        if self._det_state is not None:
            self._det_state = _reset_det_slots(self._det_state, mask)
        if self._cas_state is not None:
            self._cas_state = _reset_cascade_slots(self._cas_state,
                                                   self._gru0.b, mask)
        if self._audio_rem is not None and self._audio_rem.shape[1]:
            self._audio_rem[slots] = 0.0
        self._strikes[slots] = 0          # a reset slot restarts clean
        if self._flagged:
            self._flagged = self._flagged - set(slots)

    # ------------------------------------------------ self-healing --

    def _quarantine(self, flags: np.ndarray, mask: int) -> list[int]:
        """Reset every slot whose strike count cleared the bar; returns
        the slots reset.  ``flags`` is the fetched (batch,) health mask,
        ``mask`` the quarantine bit set."""
        bad = (flags & mask) != 0
        self._strikes = np.where(bad, self._strikes + 1, 0)
        after = (self.supervisor.quarantine_after
                 if self.supervisor is not None else 1)
        victims = np.flatnonzero(self._strikes >= after)
        if victims.size == 0:
            return []
        for s in victims:
            for bit, reason in HEALTH_REASONS.items():
                if flags[s] & bit & mask:
                    self._recovery_reasons[reason] = \
                        self._recovery_reasons.get(reason, 0) + 1
        self._recoveries += int(victims.size)
        out = [int(s) for s in victims]
        self.reset_streams(out)
        return out

    def _maybe_heal(self):
        """One supervisor tick (called after every processed chunk)."""
        sup = self.supervisor
        if sup is None or self._last_health is None:
            return
        if self._chunks % sup.check_every:
            return
        with spans.span(spans.HEAL):
            flags = np.asarray(jax.device_get(self._last_health))
            self._sat_events += int(np.count_nonzero(flags & HEALTH_SAT))
            healed = self._quarantine(flags, sup.quarantine_mask)
            # Host-side cache of who is STILL flagged (below the strike
            # bar, not yet quarantined) — the scheduler consults this at
            # admit() without adding a device fetch to the hot path.
            bad = (flags & sup.quarantine_mask) != 0
            if healed:
                bad[healed] = False       # quarantined slots restart clean
            self._flagged = frozenset(int(s) for s in np.flatnonzero(bad))

    def heal(self, mask: int | None = None) -> list[int]:
        """Force one supervisor pass NOW, ignoring ``check_every`` and
        the strike bar: every slot currently flagged by ``mask``
        (default: the supervisor's quarantine mask, or
        ``QUARANTINE_DEFAULT`` without one) is reset immediately.
        Returns the slots reset.  Safe without a supervisor — this is
        the manual lever the serve loop can pull between steps.
        """
        if self._last_health is None:
            return []
        if mask is None:
            mask = (self.supervisor.quarantine_mask
                    if self.supervisor is not None else QUARANTINE_DEFAULT)
        with spans.span(spans.HEAL):
            flags = np.asarray(jax.device_get(self._last_health))
            victims = [int(s) for s in np.flatnonzero((flags & mask) != 0)]
            if victims:
                for s in victims:
                    for bit, reason in HEALTH_REASONS.items():
                        if flags[s] & bit & mask:
                            self._recovery_reasons[reason] = \
                                self._recovery_reasons.get(reason, 0) + 1
                self._recoveries += len(victims)
                self.reset_streams(victims)
        return victims

    def unhealthy_slots(self) -> dict[int, int]:
        """Slots flagged by the LAST processed chunk: {slot: HEALTH_*
        bitmask}, empty when everything is healthy (or nothing ran yet).
        One host fetch of a (batch,) int32 — cheap enough to poll."""
        if self._last_health is None:
            return {}
        flags = np.asarray(jax.device_get(self._last_health))
        return {int(i): int(flags[i]) for i in np.flatnonzero(flags)}

    def flagged_slots(self) -> frozenset:
        """Slots the supervisor currently holds under suspicion: flagged
        by the last health check but still below the quarantine strike
        bar.  HOST-CACHED — refreshed by the supervisor's own fetch in
        ``_maybe_heal``, so reading it never syncs the device.  Always
        empty without a supervisor.  ``SlotScheduler.admit`` refuses
        these slots: a fresh stream admitted into a still-poisoned slot
        would inherit its predecessor's corrupted state."""
        return self._flagged

    def attach_slo(self, report: dict):
        """Attach a serve-loop SLO telemetry block (``launch.engine``'s
        ``PipelinedEngine.report()``) to this session; ``summary()``
        carries it in ``StreamSummary.slo``.  Cleared by ``reset``."""
        self._slo = dict(report)

    def shard_of_slot(self, i):
        """Which mesh shard owns global slot ``i`` (block partitioning);
        an int array of slots gives their shards."""
        return i // (self.batch // self.n_shards)

    def _layer_telemetry(self, acc: _Accum, frames: int):
        """(Δs transmitted per layer, or None if a counter saturated;
        temporal sparsity per layer) from the fetched telemetry."""
        dx, sat_x = _count_values(acc.nz_dx)
        dh, sat_h = _count_values(acc.nz_dh)
        tx = tuple(a + b for a, b in zip(dx, dh))
        H = int(_layers(self._gru)[0].w_h.shape[0])
        width = [(self._input_dim or 0) + H] + [2 * H] * (len(tx) - 1)
        sparsity = tuple(1.0 - t / (frames * w) if frames else 0.0
                         for t, w in zip(tx, width))
        return (None if sat_x or sat_h else tx), sparsity

    def summary(self) -> StreamSummary:
        """Fetch device telemetry ONCE and price it with the IC model.

        The fetch is the only cross-shard reduction in the engine: the
        per-shard partial sums come back as ``(n_shards,)`` vectors and
        are summed here, on the host.
        """
        acc = jax.device_get(self._acc)
        totals: dict[str, int] = {}
        overflow = False
        for name, cnt in zip(_Accum._fields, acc):
            totals[name], sat = _count_value(cnt)
            overflow = overflow or sat
        layer_tx, layer_sp = self._layer_telemetry(acc, totals["frames"])
        overflow = overflow or layer_tx is None
        robust = dict(overflowed=overflow, recoveries=self._recoveries,
                      recovery_reasons=dict(self._recovery_reasons),
                      sat_events=self._sat_events,
                      frame_joins=self.frame_joins, slo=dict(self._slo),
                      layer_transmits=layer_tx or (),
                      layer_sparsity=layer_sp)
        if totals["frames"] == 0:
            # Nothing processed yet: report an identifiable empty state,
            # not a spurious 100%-sparsity / 0-energy datapoint.
            return StreamSummary(frames=0, chunks=0, sparsity=0.0,
                                 energy_nj_per_decision=0.0, latency_ms=0.0,
                                 dense_energy_nj=0.0, **robust)
        frames = max(totals["frames"], 1)
        macs_pf = totals["macs"] / frames
        dense_pf = totals["macs_dense"] / frames
        # Active FEx channels: known only when a FEx is attached (audio
        # mode); feature-mode sessions keep the paper's 10-channel model
        # default — the GRU input width is NOT a channel count.
        n_ch = self._fex.cfg.n_active if self._fex is not None else 10
        c = frame_cost(macs_pf, n_channels=n_ch)
        # The energy detector is only powered when the gate is actually
        # configured (detect mode, non-negative threshold — VAD_OFF is
        # an unpowered comparator); its cost joins the headline total.
        vad_nj = (vad_energy_nj(float(totals["fex_samples"])) / frames
                  if self._vad is not None
                  and self._vad.energy_threshold >= 0 else 0.0)
        cascade_kw: dict = {}
        energy_nj, latency_ms = c.energy_nj_per_decision, c.latency_ms
        if self._cascade is not None:
            # Two-stage pricing: stage-0 always on, stage-1 FC/SRAM
            # duty-weighted by the awake fraction.  ``macs`` already
            # counts only awake stage-1 frames (the masked scan zeroes
            # asleep stats), so macs_pf is the executed average.
            duty = totals["awake"] / frames
            cc = cascade_frame_cost(
                totals["s0_macs"] / frames, macs_pf, duty,
                s0_hidden=self._s0_hidden, s0_classes=self._s0_classes,
                s1_hidden=int(self._gru.w_h.shape[0]),
                s1_classes=self.n_classes, n_channels=n_ch)
            energy_nj, latency_ms = cc.energy_nj_per_decision, cc.latency_ms
            cascade_kw = dict(stage1_duty=duty,
                              s0_energy_nj_per_decision=cc.s0_energy_nj,
                              frames_entered_stage1=totals["awake"])
        return StreamSummary(
            frames=totals["frames"], chunks=self._chunks,
            sparsity=1.0 - totals["macs"] / max(totals["macs_dense"], 1),
            energy_nj_per_decision=energy_nj + vad_nj,
            latency_ms=latency_ms,
            dense_energy_nj=frame_cost(dense_pf,
                                       n_channels=n_ch).energy_nj_per_decision,
            fex_samples=totals["fex_samples"],
            # Priced from COUNTED samples (audio-in mode); agrees with the
            # model's per-frame FEx share when every frame saw 128 samples.
            fex_energy_nj_per_decision=fex_energy_nj(
                float(totals["fex_samples"]), n_ch) / frames,
            vad_duty=totals["vad_open"] / frames,
            vad_energy_nj_per_decision=vad_nj,
            **cascade_kw,
            **robust,
        )


_DOUBLE_EVICT = "already free (double evict?)"


class SlotScheduler:
    """Admission/eviction queue mapping live streams onto global slots.

    Host-side bookkeeping only — nothing here touches the device except
    the slot-local ``reset_stream`` issued at admission, so scheduling
    never adds a sync to the hot path.  Under a sharded session the free
    list is kept PER SHARD and admissions go to the least-loaded shard:
    with churn (utterances finishing at different times) this keeps every
    device's slot tile near-equally occupied instead of draining one
    shard while another is full — the whole-batch step always runs at the
    speed of the busiest shard, so balance IS throughput.

    Usage::

        sched = SlotScheduler(sess)
        sched.submit(request_id)          # any hashable payload
        for slot, req in sched.admit():   # fills free slots, resets them
            ...
        sched.evict(slot)                 # stream finished; slot is free
    """

    def __init__(self, session: StreamingKwsSession):
        self._sess = session
        self.n_slots = session.batch
        self.n_shards = session.n_shards
        self._queue: collections.deque = collections.deque()
        self._free: list[list[int]] = [[] for _ in range(self.n_shards)]
        for s in range(self.n_slots - 1, -1, -1):    # pop() yields low first
            self._free[session.shard_of_slot(s)].append(s)
        self.live: dict[int, Any] = {}               # slot -> payload

    def __len__(self) -> int:
        return len(self._queue)

    @property
    def idle(self) -> bool:
        return not self.live and not self._queue

    def submit(self, payload: Any):
        """Enqueue one stream request (admitted at the next ``admit()``)."""
        self._queue.append(payload)

    def occupancy(self) -> list[int]:
        """Live streams per shard (the balance ``admit`` maintains)."""
        counts = [0] * self.n_shards
        for slot in self.live:
            counts[self._sess.shard_of_slot(slot)] += 1
        return counts

    def admit(self) -> list[tuple[int, Any]]:
        """Map queued requests onto free slots, least-loaded shard first.

        Slots the supervisor currently flags as unhealthy
        (``session.flagged_slots()``) are SKIPPED — admitting a fresh
        stream into a quarantine-pending slot would hand it corrupted
        state; the slot stays on the free list and becomes admittable
        again once the supervisor heals or clears it.  The whole
        admission wave is reset to fresh-stream state with ONE batched
        slot-local reset (see ``reset_streams``).  Returns the
        (slot, payload) admissions.
        """
        with spans.span(spans.ADMIT):
            flagged = self._sess.flagged_slots()
            admitted = []
            while self._queue:
                usable = [s for s in range(self.n_shards)
                          if any(sl not in flagged for sl in self._free[s])]
                if not usable:
                    break                 # full, or only unhealthy slots
                shard = min(usable, key=self._shard_load)
                free = self._free[shard]  # pop highest-priority healthy
                idx = next(i for i in range(len(free) - 1, -1, -1)
                           if free[i] not in flagged)
                slot = free.pop(idx)
                payload = self._queue.popleft()
                self.live[slot] = payload
                admitted.append((slot, payload))
            if admitted:
                self._sess.reset_streams([slot for slot, _ in admitted])
        return admitted

    def _shard_load(self, shard: int) -> int:
        per = self.n_slots // self.n_shards
        return per - len(self._free[shard])

    def evict(self, slot: int) -> Any:
        """Free a finished stream's slot; returns its payload.

        Guarded: evicting a slot that is not live raises a ``ValueError``
        naming the slot and its actual state — a bare ``KeyError`` (or
        worse, silently double-freeing, which would put the slot on the
        free list twice and let two streams share it) hid scheduler bugs
        as crashes far from the cause.
        """
        if slot not in self.live:
            raise self._not_live(slot)
        payload = self.live.pop(slot)
        self._free[self._sess.shard_of_slot(slot)].append(slot)
        return payload

    def evict_many(self, slots) -> list:
        """``[evict(s) for s in slots]`` as one call (``slots`` a 1-D int
        array or sequence): the same payloads, free lists and refusals,
        with the free-list appends done per shard.  Checked before
        anything is freed: a slot that is not live, or that ``slots``
        repeats, raises ``evict``'s ``ValueError`` and leaves the
        scheduler as it was."""
        order = np.asarray(slots, np.int64)
        slots = order.tolist()
        wanted = set(slots)
        if len(wanted) != len(slots) or not wanted <= self.live.keys():
            seen = set()
            for slot in slots:
                if slot in seen:
                    raise ValueError(f"cannot evict slot {slot}: "
                                     f"{_DOUBLE_EVICT}")
                if slot not in self.live:
                    raise self._not_live(slot)
                seen.add(slot)
        payloads = list(map(self.live.pop, slots))
        if len(self._free) == 1:
            self._free[0].extend(slots)
        else:
            shard = self._sess.shard_of_slot(order)
            for s, free in enumerate(self._free):
                free.extend(order[shard == s].tolist())
        return payloads

    def _not_live(self, slot: int) -> ValueError:
        if not 0 <= slot < self.n_slots:
            state = f"out of range [0, {self.n_slots})"
        elif slot in self._free[self._sess.shard_of_slot(slot)]:
            state = _DOUBLE_EVICT
        else:
            state = "never admitted"
        return ValueError(f"cannot evict slot {slot}: {state}")
