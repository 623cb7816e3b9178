"""Zero-sync streaming KWS sessions — raw audio in, decisions out (DESIGN.md §4/§5).

The IC's deployment mode is an always-on stream: 8 kHz audio enters the
FEx, one decision leaves per 16 ms frame, and every piece of state (biquad
registers, envelope, x̂/ĥ/M) is resident on-chip.  The serving image of
that is a session whose stream state and op-count telemetry live on
DEVICE between chunks: the host hands over a chunk of raw audio, gets
device arrays back, and never forces a per-frame sync.

    sess = StreamingKwsSession(params, cfg, threshold=0.1, fex=fex)
    for audio in audio_chunks:                # (samples,) raw 8 kHz audio
        out = sess.process_audio(audio)       # device arrays, NO sync
        votes = np.asarray(out.votes)         # ONE fetch per chunk
    print(sess.summary())                     # one fetch for telemetry

One fused step per chunk (``_kws_step``, jitted as ``kws_audio_step``)
is composed from the stages the session runs, in this order:

    non-finite input flag → 12-bit ADC → [frame energy] → FEx (kws.fex)
    → [VAD gate] → [stage-0 ΔGRU → wake machine] → recurrent stack
    (kws.gru, one kws.gru.<l> per layer: ΔGRU, or the int8 ΔLSTM of
    ``cfg.cell``; wake-masked under the cascade) → FC head
    (kws.head) → [posterior smoothing + hysteresis events] → telemetry
    → per-slot health mask

``_Stages`` says which bracketed stages run; the numerics object
(``_Float32`` / ``_Int8``) owns everything that differs between the
float datapath and the IC's bit-true integer one (DESIGN.md §9: ADC
codes, the packed int16 FEx registers, int16 feature codes, the
int8-weight ΔGRU, argmax over int32 logit codes — bit-identical to the
golden fixed-point model ``core.fixed_point``).  Every stage's per-slot
state is one ``SlotState`` tree.  Pre-computed features run the same
step from the ΔGRU on (``process_chunk``).  Chunk boundaries are
invisible to the model: processing [a|b] equals processing the
concatenation in one shot, bit for bit, including audio chunks that end
mid-frame (the trailing ``< frame_shift`` samples are carried host-side
and prepended to the next chunk).

Scale-out (DESIGN.md §6): pass ``mesh=make_slot_mesh(...)`` and the
session becomes a sharded continuous-batching engine — the SLOT axis
(one live stream per slot) is partitioned over the mesh's "data" axis
with ``shard_map``, weights/coefficients are replicated, the slot state
and telemetry are sharded on slots, and the hot path has neither host
syncs nor cross-device collectives (telemetry is kept as per-shard
partial sums, reduced on the host once per ``summary()``).
``reset_streams`` is slot-local — one jitted mask-select over the slot
state — so stream churn on one shard never stalls the others.  At
mesh=None (or one device) the engine is bit-identical to the
single-device session.  ``SlotScheduler`` maps a request queue onto the
global slots, balancing admissions across shards.

Fault tolerance (DESIGN.md §11): the step's per-slot HEALTH bitmask holds
finite-state predicates over the carried state (saturation-rail compares
in the int8 engine, where state cannot go non-finite) plus a
non-finite-input flag computed before the ADC quantizer.  Pass
``supervisor=SupervisorConfig(...)`` and a host-side supervisor reads
that mask (one tiny fetch per ``check_every`` chunks), quarantines
slots whose poisoned state can never recover on its own, and resets
them through the same ``reset_streams`` that serves continuous-batching
churn — a healed slot is bit-identical to a fresh stream.
``input_policy`` guards the ``process_audio`` boundary (reject /
sanitize / trust hostile samples), telemetry counters are carried as
split int32 pairs exact to 2^61 (``overflowed`` flags saturation), and
``set_threshold`` re-points the compiled step at a different Δ_TH
operating point mid-stream — the graceful-degradation lever the serve
launcher's admission controller drives under overload.

Detection (DESIGN.md §10, §13): pass ``detector=DetectorConfig(...)``
and the session serves continuous audio in, keyword EVENTS out: the
energy VAD (``frontend.vad``) sample-and-holds the features during
silence so the Δ-encoder transmits nothing, and the decision head
(``models.detector``) turns per-frame posteriors into one event per
spoken keyword; ``process_audio`` returns ``DetectResult``.  Add
``cascade=CascadeConfig(...)`` and an always-on stage-0 micro-ΔGRU
powers the stage-1 network only while a candidate event is live.
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
from repro.kernels.delta_gru_seq import (int_kernel_tiles, int_vmem_plan,
                                         kernel_name)
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


class SlotState(NamedTuple):
    """Every stage's per-slot stream state as one tree: slot-major,
    device-resident, sharded on the slot axis, ``None`` for a stage the
    session does not run.  The fields are in the fused step's argument
    order."""

    fex: FExState | None = None           # biquad registers, envelope
    gru: Any = None                       # x̂/ĥ/M (and a ΔLSTM's c): one
                                          #   layer's state, or a tuple
                                          #   per layer
    cascade: CascadeState | None = None   # wake latch, stage-0 ΔGRU
    vad: VADState | None = None           # feature hold, hangover
    det: DetectorState | None = None      # smoothed posteriors, latch


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


def slot_health(input_bad: Array, slots: SlotState) -> Array:
    """Fuse the per-slot health predicates into one (B,) int32 bitmask.

    Pure reads over the carried state trees, elementwise along the slot
    axis (sharding-safe, no collectives).  Float state checks are
    finiteness/magnitude predicates; integer-code state cannot go
    non-finite, so the int8 engine checks saturation rails instead —
    the "saturation-flag counters" of the paper's datapath, priced at a
    handful of compares per slot per chunk.  ``input_bad`` is the
    pre-quantizer non-finite-sample flag (computed before the 12-bit
    clip, which would otherwise launder an Inf into full-scale).  The
    cascade's stage-0 state is checked by its own call, as a ``gru``.
    """
    flags = _flag(HEALTH_INPUT, input_bad)
    if slots.fex is not None:
        fex = slots.fex
        if jnp.issubdtype(fex.filt.dtype, jnp.floating):
            bad = _slot_any(~jnp.isfinite(fex.filt)
                            | (jnp.abs(fex.filt) > _FEX_MAG_BOUND))
            bad |= _slot_any(~jnp.isfinite(fex.env))
        else:
            f32 = fex.filt.astype(jnp.int32)
            e32 = fex.env.astype(jnp.int32)
            bad = _slot_any(jnp.abs(f32) >= _INT16_RAIL)
            bad |= _slot_any(jnp.abs(e32) >= _INT16_RAIL)
        flags |= _flag(HEALTH_FEX, bad)
    if slots.gru is not None:
        for layer, st in enumerate(_layers(slots.gru)):
            flags |= _gru_flags(st, first=layer == 0)
    if slots.vad is not None:
        flags |= _flag(HEALTH_VAD, vad_state_flags(slots.vad))
    if slots.det is not None:
        flags |= _flag(HEALTH_DET, detector_state_flags(slots.det))
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
    # Per layer, the int kernel's plan at the last step's shapes:
    # {block_b, vmem_bytes, weights ("f32_image" / "int8_planes")}; ()
    # off the int8 Pallas path.  How a chip tiles the work, not what the
    # streams decided, so it takes no part in comparing two summaries.
    layer_plans: tuple = dataclasses.field(default=(), compare=False)
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
    """One recurrent layer's transmitted Δs over a chunk, its widths and
    its cell's gate count."""

    nz_dx: Array    # (F, B) int32
    nz_dh: Array    # (F, B) int32
    in_dim: int
    hidden: int
    gates: int = 3


# ------------------------------------------------------------- numerics --
# What differs between the float and the int8 datapath, owned by one small
# value per session (``_NUMERICS``): the data format (float values against
# integer codes) and the kernels that read it.  Everything else in the
# fused step and the session is the same code for both.
class _Float32:
    """The float datapath: float samples into the FEx, float features and
    ΔGRU state, ``f32_dot`` heads."""

    backends = ("pallas", "pallas-int", "xla")    # ΔGRU dispatch
    hold_dtype = jnp.float32                # the VAD holds float features
    gru_dtype = "float32"
    fex_kernel, fex_dtype = "batched_iir_fex", "float32"

    def gru_kernel(self, cell: str) -> str:
        return "delta_gru_seq"

    def init_gru(self, batch, input_dim, hidden, weights, cell):
        """A fresh layer's state (the float path serves ΔGRUs only)."""
        return dg.init_delta_state(batch, input_dim, hidden, weights)

    def kernel_plan(self, cell, batch, input_dim, hidden, frames, *,
                    threshold, backend, interpret):
        """The int kernel's plan of a layer; none on the float path."""
        return None

    def load(self, params, threshold, *, bundle, fex, quantize_8b, mesh):
        """(replicated (gru, w_fc, b_fc), the promoted bundle or None)."""
        return kws.serving_weights(params, quantize_8b, mesh), None

    def attach_fex(self, bundle, fex, mesh):
        """(bundle, replicated coefficient bank, the FEx's static kwargs)."""
        return (bundle, shp.put_replicated(fex.coef, mesh),
                {"env_alpha": fex.cfg.env_alpha, "log_eps": fex.cfg.log_eps})

    def fresh_fex(self, batch: int, n_channels: int) -> FExState:
        return init_fex_state(batch, n_channels)

    def fex(self, audio, coef, state, *, frame_shift, backend, interpret,
            env_alpha, log_eps):
        """(feats (B, F, C), the FEx state as the scan carries it)."""
        return fex_scan(audio, coef, state, frame_shift=frame_shift,
                        env_alpha=env_alpha, log_eps=log_eps, compress=True,
                        backend=backend, interpret=interpret)

    def fex_state(self, carried) -> FExState:
        return carried

    def features(self, feats, gfmt):
        """``process_chunk``'s float features as the ΔGRU's input."""
        return feats

    def scan(self, gru, fmt, xs, threshold, state, *, backend, interpret,
             cell="gru"):
        """One ΔGRU layer over the chunk: (hs, state, nz_dx, nz_dh)."""
        hs, state, stats = dg.delta_gru_scan(
            gru, xs, threshold=threshold, state=state, backend=backend,
            interpret=interpret)
        return hs, state, stats.nz_dx, stats.nz_dh

    def masked_scan(self, gru, fmt, xs, threshold, state, awake):
        """Stage 1 under the cascade's wake mask: asleep frames leave the
        state bit-frozen and count no Δs."""
        hs, state, stats = dg.masked_delta_gru_scan(gru, xs, threshold,
                                                    state, awake)
        return hs, state, stats.nz_dx, stats.nz_dh

    @jax.named_scope("kws.head")
    def head(self, w_fc, b_fc, hs, nz, gfmt) -> ChunkResult:
        logits = f32_dot(hs, w_fc) + b_fc             # (F, B, K)
        votes = jnp.argmax(logits, -1).astype(jnp.int32)
        return ChunkResult(logits=logits, votes=votes, nz=nz)

    def logits(self, w_fc, b_fc, hs, gfmt):
        """Stage 0's logits (no votes, no scope)."""
        return f32_dot(hs, w_fc) + b_fc


class _Int8:
    """The IC's integer datapath (DESIGN.md §9): 12-bit ADC codes into
    the FEx with its packed int16 registers, int16 feature codes, the
    int8-weight/int16-state ΔGRU, FC and argmax on int32 logit codes —
    bit-true against the golden fixed-point model (``core.fixed_point``).
    ``gfmt`` is the promoted formats of one layer or a tuple per layer."""

    backends = ("pallas", "xla")
    hold_dtype = jnp.int16                  # the VAD holds feature codes
    gru_dtype = "int8"
    fex_kernel, fex_dtype = "batched_iir_fex_int", "int16"

    def gru_kernel(self, cell: str) -> str:
        return kernel_name(cell)

    def init_gru(self, batch, input_dim, hidden, weights, cell):
        return fp.init_int_delta_state(batch, input_dim, hidden, weights,
                                       cell)

    def kernel_plan(self, cell, batch, input_dim, hidden, frames, *,
                    threshold, backend, interpret):
        """{block_b, vmem_bytes, weights} of the int kernel for a layer
        of ``cell`` at these shapes and the tiles it would resolve (None
        off the Pallas backend)."""
        if backend != "pallas":
            return None
        bb, bt = int_kernel_tiles(cell, batch, input_dim, hidden, frames,
                                  threshold, None, None, interpret)
        return int_vmem_plan(input_dim, hidden, frames, batch, bt or 1, bb,
                             interpret, cell=cell)._asdict()

    def load(self, params, threshold, *, bundle, fex, quantize_8b, mesh):
        # Without a bundle, ``params`` is promoted here — the train→deploy
        # fold at session creation, from the model's own dynamic range.
        if bundle is None:
            bundle = fp.promote_kws(params, threshold, fex=fex)
        return shp.put_replicated(
            (bundle.gru, bundle.w_fc, bundle.b_fc), mesh), bundle

    def attach_fex(self, bundle, fex, mesh):
        # Fold the FEx coefficient bank into the bundle if the promotion
        # happened without one (feature-mode bundles).  fold_fex copies —
        # a caller-shared bundle is untouched.
        bundle = fp.fold_fex(bundle, fex)
        return (bundle, shp.put_replicated(bundle.coef, mesh),
                {"ffmt": bundle.ffmt})

    def fresh_fex(self, batch: int, n_channels: int) -> FExState:
        return _unpack_state(fp.init_int_fex_state(batch, n_channels))

    def fex(self, audio, coef, state, *, frame_shift, backend, interpret,
            ffmt):
        codes = fp.to_code(audio, ffmt.feat_frac, 16, jnp.int16)
        return fp.int_fex_scan(codes, coef, _pack_state(state), ffmt,
                               frame_shift=frame_shift, backend=backend,
                               interpret=interpret)

    def fex_state(self, carried) -> FExState:
        return _unpack_state(carried)

    def features(self, feats, gfmt):
        """Features on the 12-bit grid → int16 codes."""
        return fp.to_code(feats, _layers(gfmt)[0].feat_frac, 16, jnp.int16)

    def scan(self, gru, fmt, xs, threshold, state, *, backend, interpret,
             cell="gru"):
        return fp.int_gru_scan(gru, fmt, xs, threshold, state=state,
                               backend=backend, interpret=interpret,
                               cell=cell)

    def masked_scan(self, gru, fmt, xs, threshold, state, awake):
        return fp.masked_int_gru_scan(gru, fmt, xs, threshold, state, awake)

    @jax.named_scope("kws.head")
    def head(self, w_fc, b_fc, hs, nz, gfmt) -> ChunkResult:
        """The decision is the argmax over int32 logit codes (bit-true);
        the logits are dequantized — grid-exact — for the float-typed
        result and the detection head."""
        codes = fp.int_fc(hs, w_fc, b_fc)               # (F, B, K) int32
        votes = jnp.argmax(codes, -1).astype(jnp.int32)
        return ChunkResult(
            logits=fp.from_code(codes, _layers(gfmt)[-1].logit_frac),
            votes=votes, nz=nz)

    def logits(self, w_fc, b_fc, hs, gfmt):
        return fp.from_code(fp.int_fc(hs, w_fc, b_fc), gfmt.logit_frac)


_NUMERICS = {"float32": _Float32(), "int8": _Int8()}


# --------------------------------------------------------------- stages --
class _Stages(NamedTuple):
    """Which stages a fused step runs — decided once per session, bound
    into the step as a static.

    audio: raw audio through the ADC and the FEx (False: features in).
    vad, det: the VAD gate and the detection head, together (detect mode).
    cascade: the stage-0 wake cascade in front of the stage-1 ΔGRU.
    """

    audio: bool
    vad: VADConfig | None = None
    det: DetectorConfig | None = None
    cascade: CascadeConfig | None = None

    @property
    def fields(self) -> tuple[str, ...]:
        """The ``SlotState`` fields the step carries, in argument order."""
        on = (self.audio, True, self.cascade is not None,
              self.det is not None, self.det is not None)
        return tuple(f for f, o in zip(SlotState._fields, on) if o)

    @property
    def n_weights(self) -> int:
        """Replicated leading arguments: the ΔGRU stack and FC head, stage
        0's three, the FEx coefficient bank."""
        return 3 + 3 * (self.cascade is not None) + self.audio


_FEATURES = _Stages(audio=False)             # ``process_chunk``'s step


@jax.named_scope("kws.gru")
def _gru_stack(num, gru, state, xs, *, threshold: float, backend: str,
               interpret: bool | None, gfmt=None, cell: str = "gru"):
    """The recurrent stage of the fused step: the stack's layers in turn
    over the chunk, layer 1 on ``xs`` (T, B, I), each layer above on the
    hidden states of the layer below.  Every layer is of ``cell``
    (``core.delta_gru.CELLS``): ``num.scan`` runs it with that cell's
    kernel — the ΔGRU's, or the ΔLSTM's, which carries c in the state.
    Layer l at frame t reads only layer l−1 at frame t, so running each
    layer over the whole chunk in turn is the frame-interleaved order.
    ``gru``/``state`` (and, int8, ``gfmt``) are one layer's entry or a
    tuple per layer.  Each layer's kernel runs under the named scope
    ``kws.gru.<l>`` (l = 0 first), whatever the cell.

    Returns (the top layer's hidden states, the new state in the form
    of ``state``, one ``LayerCounts`` per layer)."""
    grus, states = _layers(gru), _layers(state)
    fmts = _layers(gfmt) if gfmt is not None else (None,) * len(grus)
    new, counts = [], []
    for layer, (g, st, fmt) in enumerate(zip(grus, states, fmts)):
        in_dim = xs.shape[-1]
        with jax.named_scope(f"kws.gru.{layer}"):
            try:
                xs, st, nz_dx, nz_dh = num.scan(g, fmt, xs, threshold, st,
                                                backend=backend,
                                                interpret=interpret,
                                                cell=cell)
            except NotImplementedError as e:
                raise NotImplementedError(f"layer {layer}: {e}") from None
        new.append(st)
        counts.append(LayerCounts(nz_dx, nz_dh, in_dim, g.w_h.shape[0],
                                  dg.CELLS[cell].gates))
    state = tuple(new) if type(state) in (tuple, list) else new[0]
    return xs, state, tuple(counts)


def _transmits(counts) -> Array:
    """(F, B) Δs transmitted per frame and slot, summed over layers."""
    return sum(c.nz_dx + c.nz_dh for c in counts)


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
    sum of transmitted Δs is an int32 (under 2^31), scaled by its G·H
    MACs per Δ (G its cell's gates: 3H a ΔGRU's, 4H a ΔLSTM's) inside
    the split counter (``_count_add``), and the dense count, (I+H)·G·H a
    frame, is a Python int from the static shapes.
    """
    def macs(c, layer_counts):
        for lc in layer_counts:
            c = _count_add(c, jnp.sum(lc.nz_dx + lc.nz_dh),
                           lc.gates * lc.hidden)
        return c

    per_layer = lambda f: jnp.stack([jnp.sum(getattr(lc, f))
                                     for lc in counts])
    dense = n_frames * sum((lc.in_dim + lc.hidden) * lc.gates * lc.hidden
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


def _kws_step(*args, num, stages: _Stages, threshold: float, backend: str,
              interpret: bool | None, gfmt=None, gfmt0=None,
              cell: str = "gru", fex_backend: str | None = None,
              frame_shift: int = 0, **fex_kw):
    """The fused serving step, composed from ``stages`` in one jitted
    graph over ``num``'s datapath:

        (weights…, slot state…, acc, block) → (slot state…, acc, result,
                                               health)

    ``weights`` are the ``stages.n_weights`` replicated arrays; the slot
    state is the ``stages.fields`` of a ``SlotState``, slot-major;
    ``block`` is (B, S) raw audio, S a multiple of ``frame_shift``, or
    (F, B, C) features.  The stages, in order: the pre-quantizer
    non-finite flag; the 12-bit ADC; [frame energy]; the FEx
    (``kws.fex``), or the feature input; [the VAD gate, which holds the
    features during silence so that Δx = 0]; [stage 0 → the wake
    machine]; the recurrent stack of ``cell`` (``kws.gru``), or, under
    the cascade, the wake-masked stage-1 ΔGRU scan; the head
    (``kws.head``) → [posterior smoothing and hysteresis events];
    telemetry; the per-slot health mask.  Nothing in here leaves the
    device.
    """
    n_w = stages.n_weights
    weights, acc, block = args[:n_w], args[-2], args[-1]
    st = SlotState(**dict(zip(stages.fields, args[n_w:-2])))
    gru, w_fc, b_fc = weights[:3]
    scan = dict(backend=backend, interpret=interpret)
    if stages.audio:
        in_bad = jnp.any(~jnp.isfinite(block), axis=1)    # pre-quantizer
        audio = quantize_audio_12b(block.astype(jnp.float32))
        if stages.vad is not None:
            energy = frame_energy(audio, frame_shift)     # (F, B), float
        feats, fex_carried = num.fex(
            audio, weights[-1], st.fex, frame_shift=frame_shift,
            backend=fex_backend, interpret=interpret, **fex_kw)
        xs = jnp.moveaxis(feats, 1, 0)                    # (F, B, C)
    else:
        in_bad = jnp.any(~jnp.isfinite(block), axis=(0, 2))
        xs = num.features(block, gfmt)
    gate = vad = awake = cascade = None
    if stages.vad is not None:
        xs, gate, vad = vad_gate(xs, energy, st.vad, stages.vad)
    if stages.cascade is None:
        hs, gru_state, counts = _gru_stack(num, gru, st.gru, xs,
                                           threshold=threshold, gfmt=gfmt,
                                           cell=cell, **scan)
        s0 = ()
    else:
        # Stage 0: always on, leading-channel subset, its own Δ_TH and
        # (int8) its own formats; stage 1 runs only on awake frames.
        cas, (gru0, w_fc0, b_fc0) = stages.cascade, weights[3:6]
        xs0 = xs[..., :cas.s0_channels]
        hs0, s0_state, nz_dx0, nz_dh0 = num.scan(
            gru0, gfmt0, xs0, cas.s0_threshold, st.cascade.s0, **scan)
        score = jax.nn.softmax(num.logits(w_fc0, b_fc0, hs0, gfmt0),
                               axis=-1)[..., 1]
        awake, latch, hang = cascade_wake_scan(cas, st.cascade.awake,
                                               st.cascade.hang, score)
        hs, gru_state, nz_dx, nz_dh = num.masked_scan(
            gru, gfmt, xs, threshold, st.gru, awake)
        counts = (LayerCounts(nz_dx, nz_dh, xs.shape[-1], gru.w_h.shape[0]),)
        s0 = (LayerCounts(nz_dx0, nz_dh0, xs0.shape[-1], gru0.w_h.shape[0]),)
        cascade = CascadeState(awake=latch, hang=hang, s0=s0_state)
    out = num.head(w_fc, b_fc, hs, _transmits(counts), gfmt)
    det = None
    if stages.det is not None:
        post = jax.nn.softmax(out.logits, axis=-1)        # (F, B, K)
        det, events = detector_scan(stages.det, st.det, post)
        if awake is not None:
            # A frozen stage-1 h keeps emitting its held logits: no event
            # may fire while stage 0 says nothing is happening.
            events = jnp.where(awake, events, jnp.int32(-1))
        out = DetectResult(*out, events=events, gate=gate, awake=awake)
    decisions = xs.shape[0] * xs.shape[1]                 # frames × streams
    acc = _bump(acc, counts, decisions, decisions * frame_shift,
                vad_open=None if gate is None else jnp.sum(gate),
                awake=None if awake is None else jnp.sum(awake), s0=s0)
    slots = SlotState(
        fex=num.fex_state(fex_carried) if stages.audio else None,
        gru=gru_state, cascade=cascade, vad=vad, det=det)
    health = slot_health(in_bad, slots)
    if cascade is not None:
        health |= slot_health(jnp.zeros_like(in_bad),
                              SlotState(gru=cascade.s0))
    return (*(getattr(slots, f) for f in stages.fields), acc, out, health)


@jax.jit
def _reset_slots(slots, fresh, mask):
    """Fresh-stream state for every slot where ``mask`` is True, in every
    stage: ``fresh`` is one fresh slot's ``SlotState`` ((1, …) leaves),
    selected into the masked rows.

    One mask-select instead of per-slot dynamic updates: ONE compiled
    elementwise op resets an arbitrary admission wave (continuous
    batching can churn every slot of a shard in one serve step — a
    dispatch per slot would cost more than the chunk step itself).
    Slot-local by construction: under a sharded state the op is
    elementwise along the slot axis, so each shard rewrites only its own
    rows — no collectives, no reshard, no stall for other shards.
    """
    def select(now, new):
        return jnp.where(mask.reshape(mask.shape + (1,) * (now.ndim - 1)),
                         new, now)

    return jax.tree.map(select, slots, fresh)


class StreamingKwsSession:
    """Carries FEx + ΔGRU state and telemetry on device across chunks.

    Args:
      params: the trained KWS parameter tree (``models.kws.init_kws``).
      cfg: an ArchConfig (``d_model`` = GRU width, ``num_layers`` the
        stack's depth, ``cell`` its recurrent cell: ``"gru"``, or
        ``"lstm"``, a ΔLSTM stack that the int8 datapath serves).
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
        if numerics not in _NUMERICS:
            raise ValueError(f"unknown numerics: {numerics!r}")
        self.cell = getattr(cfg, "cell", "gru")
        if self.cell not in dg.CELLS:
            raise ValueError(f"unknown recurrent cell {self.cell!r}; "
                             f"known: {sorted(dg.CELLS)}")
        # Only the int8 datapath's scan and kernel carry a cell state c.
        carries_c = dg.CELLS[self.cell].carries_c
        if carries_c and numerics != "int8":
            raise ValueError(f"the {self.cell!r} cell is served by the int8 "
                             f"datapath only: there is no {numerics} "
                             f"kernel for it")
        if carries_c and cascade is not None:
            raise ValueError(f"the wake cascade gates a ΔGRU stage 1; a "
                             f"{self.cell!r} cell is served in audio and "
                             f"detect mode only")
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
        self._num = _NUMERICS[numerics]
        if backend not in self._num.backends:
            raise ValueError(f"unknown ΔGRU backend: {backend!r}")
        (self._gru, self._w_fc, self._b_fc), self._bundle = self._num.load(
            params, self.threshold, bundle=bundle, fex=self._fex,
            quantize_8b=quantize_8b, mesh=mesh)
        if self._bundle is not None:
            self.threshold = self._bundle.threshold
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
        # The fused step's replicated arguments (the FEx bank joins them
        # in the audio step) and the stages it runs.
        self._weights = (self._gru, self._w_fc, self._b_fc)
        self._gfmt0 = None
        if cascade is not None:
            # Stage 0 is loaded on its own: int8 promotes it from its own
            # trained dynamic range (gfmt0 ≠ gfmt).
            stage0, bundle0 = self._num.load(
                stage0_params, cascade.s0_threshold, bundle=None, fex=None,
                quantize_8b=quantize_8b, mesh=mesh)
            self._weights += stage0
            self._gfmt0 = bundle0.gfmt if bundle0 is not None else None
        self._stages = _Stages(audio=True, vad=self._vad, det=detector,
                               cascade=cascade)
        self._slots: SlotState | None = None        # every stage's state
        self._fresh_row: SlotState | None = None    # one fresh slot's
        self._coef = None                           # replicated FEx coeffs
        self._audio_rem: np.ndarray | None = None   # carried tail samples
        self.frame_joins = 0                # steps that joined a tail
        self._frames: int | None = None     # frames of the last step
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
        """Build (jitted feature step, audio-step partial) for one Δ_TH:
        the one composed step (``_kws_step``) over ``process_chunk``'s
        stages and over the session's audio stages."""
        kw = dict(num=self._num, threshold=threshold, backend=self._backend,
                  interpret=self._interpret, cell=self.cell,
                  gfmt=self._bundle.gfmt if self._bundle is not None else None)
        step = jax.jit(self._shard(
            functools.partial(_kws_step, stages=_FEATURES, **kw), _FEATURES))
        return step, functools.partial(
            _kws_step, stages=self._stages, fex_backend=self._fex_backend,
            gfmt0=self._gfmt0, **kw)

    def _build_audio_step(self, audio_step_fn):
        """Jit + shard the fused audio step once the FEx kwargs are known.

        Jitted under one stable name, so the device trace shows the step
        as the module ``jit_kws_audio_step``."""
        sharded = self._shard(functools.partial(audio_step_fn, **self._fex_kw),
                              self._stages)

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

        num = self._num
        H = int(_layers(self._gru)[0].w_h.shape[0])
        if self._input_dim is not None:
            kernel = num.gru_kernel(self.cell)
            report["kernels"][kernel] = entry(
                kernel, (b_shard, int(self._input_dim), H),
                num.gru_dtype, self.threshold)
        if self._fex is not None:
            fcfg = self._fex.cfg
            report["kernels"][num.fex_kernel] = entry(
                num.fex_kernel,
                (b_shard, int(fcfg.n_active), int(fcfg.frame_shift)),
                num.fex_dtype, 0.0)
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

    def _shard(self, fn, stages: _Stages):
        """Wrap a fused step over ``stages`` in shard_map over the slot
        mesh: the weights are replicated, the slot state and telemetry
        slot-major (prefix P("data")), raw audio slot-major and features
        frame-major (P(None, "data")).  Outputs: the slot state and
        telemetry, the frame-major result, the slot-major (B,) health
        mask.  No-op without a mesh — the unsharded session is
        byte-for-byte the pre-sharding code path.
        """
        if self.mesh is None:
            return fn
        slot, n = P(shp.SLOT_AXIS), len(stages.fields) + 1
        frames = P(None, shp.SLOT_AXIS)
        in_specs = ((P(),) * stages.n_weights + (slot,) * n
                    + ((slot if stages.audio else frames),))
        return shard_map_kernels(fn, self.mesh, in_specs=in_specs,
                                 out_specs=(slot,) * n + (frames, slot))

    def _fresh_slots(self, batch: int) -> SlotState:
        """Fresh-stream state of every stage the session's step carries,
        for ``batch`` slots: the recurrent stack (one layer's state — a
        ``DeltaState``, or with the ΔLSTM's zero c a ``DeltaLstmState`` —
        or one per layer of a stack: layer 1 reads the input features,
        each layer above the hidden state below) and, once an FEx is
        attached, the FEx, the cascade (everyone asleep, stage 0 fresh),
        the VAD hold and the detector head."""
        num, C, H = self._num, self._input_dim, self.cfg.d_model

        def gru():
            layers = tuple(num.init_gru(batch, C if layer == 0 else H, H, g,
                                        self.cell)
                           for layer, g in enumerate(_layers(self._gru)))
            return layers if self.n_layers > 1 else layers[0]

        def cascade():
            g0 = self._weights[3]
            return CascadeState(
                awake=jnp.zeros((batch,), bool),
                hang=jnp.zeros((batch,), jnp.int32),
                s0=num.init_gru(batch, g0.w_x.shape[0], g0.w_h.shape[0], g0,
                                "gru"))

        fresh = dict(
            fex=lambda: num.fresh_fex(batch, C), gru=gru, cascade=cascade,
            vad=lambda: init_vad_state(batch, C, num.hold_dtype),
            det=lambda: init_detector_state(batch, self.n_classes))
        stages = self._stages if self._coef is not None else _FEATURES
        return SlotState(**{f: fresh[f]() for f in stages.fields})

    def _init_state(self, input_dim: int):
        """Fresh state of every stage, and the one fresh slot that
        ``reset_streams`` selects into reset rows."""
        self._input_dim = input_dim
        self._slots = shp.put_slot_sharded(self._fresh_slots(self.batch),
                                           self.mesh)
        self._fresh_row = shp.put_replicated(self._fresh_slots(1), self.mesh)

    def _require_fex(self) -> FeatureExtractor:
        if self._fex is None:
            self._fex = FeatureExtractor()
        fcfg = self._fex.cfg
        if self._input_dim not in (None, fcfg.n_active):
            raise ValueError(f"FEx emits {fcfg.n_active} channels, session "
                             f"state is {self._input_dim}-wide")
        if self._coef is None:
            self._bundle, self._coef, fex_kw = self._num.attach_fex(
                self._bundle, self._fex, self.mesh)
            self._fex_kw = {"frame_shift": fcfg.frame_shift, **fex_kw}
            self._audio_rem = np.zeros((self.batch, 0), np.float32)
            gru = self.state
            self._init_state(fcfg.n_active)
            if gru is not None:     # the ΔGRU keeps what it has carried
                self._slots = self._slots._replace(gru=gru)
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
            out = ChunkResult(jnp.zeros((0, self.batch, self.n_classes)), z, z)
            if self._detector is None:
                return out
            no = jnp.zeros((0, self.batch), bool)
            return DetectResult(*out, events=z, gate=no,
                                awake=no if self._cascade else None)
        with spans.span(spans.H2D):
            block = jnp.asarray(block)
        with spans.span(spans.LAUNCH):
            out = self._carry(self._stages,
                              self._audio_step(*self._audio_step_args(block)))
        self._frames = n_frames
        self._maybe_heal()
        return out

    def _audio_step_args(self, block):
        """The fused audio step's arguments for one (batch, S) block:
        the replicated weights and FEx bank, the slot state's present
        fields, the telemetry, the block."""
        return (*self._weights, self._coef,
                *(getattr(self._slots, f) for f in self._stages.fields),
                self._acc, block)

    def _carry(self, stages: _Stages, outs):
        """Keep a fused step's slot state, telemetry and health mask;
        return its result."""
        n = len(stages.fields)
        self._slots = self._slots._replace(**dict(zip(stages.fields,
                                                      outs[:n])))
        self._acc, out, self._last_health = outs[n:]
        self._chunks += 1
        return out

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
        if self._slots is None:
            self._init_state(feats.shape[-1])
        elif feats.shape[-1] != self._input_dim:
            raise ValueError(f"chunk has {feats.shape[-1]} feature channels,"
                             f" session state is {self._input_dim}-wide")
        out = self._carry(_FEATURES, self._step(
            *self._weights, self._slots.gru, self._acc, feats))
        self._frames = int(feats.shape[0])
        self._maybe_heal()
        return out

    @property
    def state(self):
        """The slots' recurrent state: one layer's (a ``DeltaState``, or
        for the ΔLSTM a ``DeltaLstmState`` with c), or for a stack a
        tuple of one per layer, the first layer's first."""
        return self._slots.gru if self._slots is not None else None

    @property
    def fex_state(self) -> FExState | None:
        return self._slots.fex if self._slots is not None else None

    def reset(self):
        """Forget stream state + telemetry (keeps weights/compiled step)."""
        if self._input_dim is not None:
            self._init_state(self._input_dim)
        if self._coef is not None:
            self._audio_rem = np.zeros((self.batch, 0), np.float32)
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
        dominate the step itself; a wave is one dispatch.

        Caveat: the carried sample remainder's LENGTH is shared across
        streams, so the reset zeroes a slot's buffered samples but
        cannot drop them — after a reset mid-remainder the new stream
        starts up to ``frame_shift−1`` zero samples early relative to a
        fresh session.  Feed frame-aligned chunks (the serve launcher's
        default) to keep resets exactly fresh."""
        slots = np.asarray(slots, np.intp)
        bad = (slots < 0) | (slots >= self.batch)
        if bad.any():
            raise ValueError(f"stream {slots[bad.argmax()]} out of range "
                             f"[0, {self.batch})")
        if not slots.size:
            return
        mask = np.zeros((self.batch,), bool)
        mask[slots] = True
        if self._slots is not None:
            self._slots = _reset_slots(self._slots, self._fresh_row,
                                       jnp.asarray(mask))
        if self._audio_rem is not None and self._audio_rem.shape[1]:
            self._audio_rem[slots] = 0.0
        self._strikes[slots] = 0          # a reset slot restarts clean
        if self._flagged:
            self._flagged = self._flagged - set(slots.tolist())

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

    def _layer_plans(self) -> tuple:
        """Each layer's int kernel plan at the shapes of the last step
        (``_Int8.kernel_plan``); () before a step or off the int8
        Pallas path.  From static shapes: no device work."""
        if self._frames is None:
            return ()
        H = int(_layers(self._gru)[0].w_h.shape[0])
        plans = tuple(self._num.kernel_plan(
            self.cell, self.batch // self.n_shards, I, H, self._frames,
            threshold=self.threshold, backend=self._backend,
            interpret=self._interpret)
            for I in [self._input_dim] + [H] * (self.n_layers - 1))
        return plans if None not in plans else ()

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
                      layer_sparsity=layer_sp,
                      layer_plans=self._layer_plans())
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
            gru0, _, b_fc0 = self._weights[3:6]
            cc = cascade_frame_cost(
                totals["s0_macs"] / frames, macs_pf, duty,
                s0_hidden=int(gru0.w_h.shape[0]),
                s0_classes=int(b_fc0.shape[-1]),
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
        self._per_shard = self.n_slots // self.n_shards
        self.waves = 0              # non-empty admission waves
        self.admitted = 0           # slots those waves admitted

    def __len__(self) -> int:
        return len(self._queue)

    @property
    def idle(self) -> bool:
        return not self.live and not self._queue

    def submit(self, payload: Any):
        """Enqueue one stream request (admitted at the next ``admit()``)."""
        self._queue.append(payload)

    def occupancy(self) -> list[int]:
        """Live streams per shard (the balance ``admit`` maintains).

        Read off the free lists: every slot is either live or on its
        shard's free list (flagged slots stay on it), which ``admit``,
        ``evict`` and ``evict_many`` keep true."""
        return [self._per_shard - len(f) for f in self._free]

    def admit(self) -> list[tuple[int, Any]]:
        """Map queued requests onto free slots, least-loaded shard first.

        Slots the supervisor currently flags as unhealthy
        (``session.flagged_slots()``) are SKIPPED — admitting a fresh
        stream into a quarantine-pending slot would hand it corrupted
        state; the slot stays on the free list (and counts as free in
        the shard's load) and becomes admittable again once the
        supervisor heals or clears it.  The whole admission wave is
        reset to fresh-stream state with ONE batched slot-local reset
        (see ``reset_streams``).  Returns the (slot, payload)
        admissions, in admission order.

        The wave is the one a request-at-a-time greedy loop makes —
        each request to the least-loaded shard with a healthy free slot
        (ties to the lowest shard), there to the healthy slot nearest
        the end of the free list — computed in one pass: shard ``s``
        starts at load ``per - len(free[s])``, so its j-th pick comes at
        load ``per - len(free[s]) + j``, and the loop's order is the
        merge of those runs by (load, shard).
        """
        with spans.span(spans.ADMIT):
            flagged = self._sess.flagged_slots()
            # Each shard's healthy free slots in pick order (from the end
            # of its free list), with their positions in it.
            free = [np.asarray(f, np.intp) for f in self._free]
            if flagged:
                bad = np.fromiter(flagged, np.intp, len(flagged))
                pos = [np.flatnonzero(~np.isin(f, bad))[::-1] for f in free]
            else:
                pos = [np.arange(len(f) - 1, -1, -1) for f in free]
            runs = [len(p) for p in pos]
            take = min(len(self._queue), sum(runs))
            if not take:
                return []
            slot = np.concatenate([f[p] for f, p in zip(free, pos)])
            shard = np.repeat(np.arange(self.n_shards), runs)
            if self.n_shards > 1:               # merge the runs
                load = np.concatenate(
                    [np.arange(k) + self._per_shard - len(f)
                     for f, k in zip(free, runs)])
                order = np.lexsort((shard, load))
                slot, shard = slot[order], shard[order]
            slot, shard = slot[:take], shard[:take]
            picked = np.bincount(shard, minlength=self.n_shards).tolist()
            for f, p, n in zip(self._free, pos, picked):
                if n:       # its last n healthy go; flagged ones stay
                    cut = int(p[n - 1])
                    f[cut:] = [s for s in f[cut:] if s in flagged] \
                        if flagged else []
            slots = slot.tolist()
            payloads = [self._queue.popleft() for _ in range(take)]
            self.live.update(zip(slots, payloads))
            self.waves += 1
            self.admitted += take
            self._sess.reset_streams(slot)
        return list(zip(slots, payloads))

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
