"""Serial IIR BPF-based feature extractor (FEx) — JAX implementation.

Pipeline (paper Fig. 4):  12-bit audio @ 8 kHz
  → bank of 4th-order IIR BPFs (two cascaded biquads per channel)
  → envelope detector (full-wave rectify + one-pole low-pass)
  → frame decimation (16 ms shift)
  → channel-wise offset/scale, log₂ compression, normalization
  → 12-bit feature vectors (C channels per 16 ms frame).

Two execution paths, identical numerics (float-exact — both run the same
``kernels.iir_fex.fex_sample_step``/``compress_env`` math in the same
order; asserted in tests/test_fex_stream.py):

  * ``backend="xla"``    — nested ``lax.scan`` (frames outer, samples
    inner).  The bit-exact reference; differentiable.
  * ``backend="pallas"`` — ONE batched sequence-resident kernel per chunk
    (``kernels.iir_fex.batched_iir_fex``): biquad/envelope state lives in
    a VMEM-revisited block across all frame steps, log₂ compression and
    12-bit quantization run in-kernel, and only final features leave VMEM.

Both paths carry an explicit ``FExState`` so audio can be streamed in
chunks with bit-invisible boundaries (the ``delta_gru_seq`` contract).

Faithfulness notes
  * Channel geometry: the paper gives 16 reconfigurable channels and a
    10-channel selection "covering 516 Hz – 4.22 kHz" while processing 8 kHz
    audio.  Exact center frequencies are unpublished (and 4.22 kHz exceeds
    the 8 kHz Nyquist), so we reconstruct the Mel geometry Nyquist-limited:
    16 Mel-spaced centers 100 Hz – 3.95 kHz; ``SELECT_10`` keeps channels
    4..13 (band coverage ≈ 506 Hz – 3.2 kHz; the lower edge matches the
    paper's 516 Hz, the upper edge is Nyquist-capped).  Reported in
    EXPERIMENTS.md.
  * Mixed-precision coefficients: b quantized to 12 bit, a to 8 bit total
    width, integer bits chosen from each coefficient family's dynamic range
    (paper §II-C3) — see ``quantize_sos``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantize import qformat_for, quantize_audio_12b
from repro.frontend import filters
from repro.kernels.iir_fex import (STATE_ROWS, batched_iir_fex, compress_env,
                                   fex_sample_step, pack_coefficients)

Array = jax.Array

FRAME_SHIFT = 128          # samples @ 8 kHz = 16 ms
SELECT_10 = tuple(range(4, 14))


@dataclasses.dataclass(frozen=True)
class FExConfig:
    fs: float = 8000.0
    n_channels: int = 16
    fmin: float = 100.0
    fmax: float = 3950.0
    selection: tuple[int, ...] = SELECT_10
    frame_shift: int = FRAME_SHIFT
    env_tau_s: float = 0.020          # envelope LP time constant
    log_eps: float = 2.0 ** -11       # one 12-bit LSB
    b_bits: int = 12                  # mixed-precision coefficient widths
    a_bits: int = 8
    quantize_coeffs: bool = True

    @property
    def n_active(self) -> int:
        return len(self.selection)

    @property
    def env_alpha(self) -> float:
        return float(1.0 - np.exp(-1.0 / (self.fs * self.env_tau_s)))


class FExState(NamedTuple):
    """Carried FEx state: all on-chip registers of the filter datapath.

    ``filt``: (B, 4, C) DF2T registers (2 sections × 2 per channel);
    ``env``:  (B, C) envelope-detector output.
    Packed to the kernel's (B, 5, C) layout at the call boundary.
    """

    filt: Array
    env: Array


def init_fex_state(batch: int, n_channels: int, dtype=jnp.float32) -> FExState:
    """Quiescent filters, zero envelope."""
    return FExState(filt=jnp.zeros((batch, 4, n_channels), dtype),
                    env=jnp.zeros((batch, n_channels), dtype))


def _pack_state(state: FExState) -> Array:
    """FExState → the kernels' (B, 5, C) buffer layout.  Dtype-
    preserving: float32 registers on the float paths, int16 codes in the
    int8 serving engine — this function is the single owner of the
    row-layout contract."""
    return jnp.concatenate([state.filt, state.env[:, None, :]],
                           axis=1).astype(state.filt.dtype)


def _unpack_state(buf: Array) -> FExState:
    return FExState(filt=buf[:, :STATE_ROWS - 1], env=buf[:, STATE_ROWS - 1])


@functools.partial(jax.jit, static_argnames=("frame_shift", "env_alpha",
                                             "log_eps", "compress"))
def _fex_scan_xla(audio: Array, coef: Array, state_buf: Array,
                  frame_shift: int, env_alpha: float, log_eps: float,
                  compress: bool):
    """Nested-scan reference: frames outer, samples inner — per-sample op
    order identical to the Pallas kernel body (single-source math)."""
    B, T = audio.shape
    n_frames = T // frame_shift
    xf = audio[:, :n_frames * frame_shift].astype(jnp.float32)
    xf = jnp.moveaxis(xf.reshape(B, n_frames, frame_shift), 1, 0)
    coef = coef.astype(jnp.float32)

    def frame_step(s, x_frame):                      # x_frame: (B, S)
        def sample_step(s, x_col):                   # x_col: (B,)
            rows = [s[:, k] for k in range(STATE_ROWS)]
            return jnp.stack(fex_sample_step(x_col[:, None], rows, coef,
                                             env_alpha), axis=1), None

        s, _ = jax.lax.scan(sample_step, s, x_frame.T)
        env = s[:, STATE_ROWS - 1]
        return s, (compress_env(env, log_eps) if compress else env)

    state_buf, feats = jax.lax.scan(frame_step,
                                    state_buf.astype(jnp.float32), xf)
    return jnp.moveaxis(feats, 0, 1), state_buf      # (B, F, C)


@jax.named_scope("kws.fex")
def fex_scan(audio: Array, coef: Array, state: FExState | None = None, *,
             frame_shift: int = FRAME_SHIFT, env_alpha: float = 0.0606,
             log_eps: float = 2.0 ** -11, compress: bool = True,
             backend: str = "xla", block_b: int | None = None,
             unroll: int | None = None,
             interpret: bool | None = None, b_bits: int = 12,
             a_bits: int = 8, coef_formats=None) -> tuple[Array, FExState]:
    """Run the FEx over a chunk of audio, carrying explicit state.

    Args:
      audio: (B, T) float samples in [-1, 1) (callers quantize to the
        12-bit grid; trailing ``T % frame_shift`` samples are ignored —
        carry them to the next chunk).
      coef: (6, C) packed coefficient rows (``pack_coefficients``).
      state: a carried ``FExState`` (None = quiescent filters).
      frame_shift: samples per decision frame (128 = 16 ms @ 8 kHz).
      env_alpha: envelope one-pole low-pass coefficient
        (``FExConfig.env_alpha``).
      log_eps: log₂-compression epsilon (one 12-bit LSB).
      compress: apply in-datapath log₂ + normalize + 12-bit quantization
        (the serving output format); False returns raw envelopes.
      backend: "xla" (bit-exact nested-scan reference, differentiable),
        "pallas" (ONE batched sequence-resident kernel per chunk,
        float-exact against "xla"), or "pallas-int" (the integer-code
        kernel: 12-bit audio, 16-bit registers, mixed-precision
        coefficient codes; returns grid-exact floats, bit-true against
        ``core.fixed_point.int_fex_scan``).
      block_b: batch-tile override for the Pallas kernels.
      unroll: per-sample-loop unroll override for the Pallas kernels
        (must divide ``frame_shift``; bit-exact at any legal value).
        Like ``block_b``, ``None`` consults the ``kernels.autotune``
        cache and otherwise keeps the static default.
      interpret: force the Pallas interpreter on/off (None = platform
        default).
      b_bits / a_bits: coefficient word widths for the "pallas-int"
        fallback format derivation (paper §II-C3).
      coef_formats: the ``sos_formats`` pair (what ``FeatureExtractor``
        passes) so "pallas-int" codes are STRUCTURALLY the promoted
        serving path's; without it the formats are re-derived from the
        packed rows on the ``b_bits``/``a_bits`` budgets (equivalent for
        symmetric-form banks: b1 = 0, b2 = −b0).

    Returns:
      (features (B, T // frame_shift, C), new ``FExState``).

    State contract: every backend advances the SAME carried registers in
    the same order, so chunk boundaries are bit-invisible — processing
    [a|b] with the state carried equals the concatenation in one call.
    """
    B = audio.shape[0]
    C = coef.shape[1]
    if state is None:
        state = init_fex_state(B, C)
    buf = _pack_state(state)
    if backend == "pallas":
        if block_b is None or unroll is None:
            from repro.kernels import autotune
            tuned = autotune.resolve(
                "batched_iir_fex", (B, C, frame_shift), "float32", 0.0,
                interpret=interpret, B=B, frame_shift=frame_shift)
            block_b = block_b if block_b is not None else tuned.get("block_b")
            unroll = unroll if unroll is not None else tuned.get("unroll")
        feats, buf = batched_iir_fex(
            audio, coef, buf, frame_shift=frame_shift, env_alpha=env_alpha,
            log_eps=log_eps, compress=compress, block_b=block_b,
            unroll=unroll, interpret=interpret)
    elif backend == "pallas-int":
        # The integer-code datapath (DESIGN.md §9): quantize the (concrete)
        # coefficient bank onto its mixed-precision grids, run the int
        # kernel on codes, and hand back grid-exact floats so the FExState
        # carry round-trips bit-true.  Eager-only: the coefficient formats
        # are static, so ``coef`` must not be a tracer here (inside a
        # jitted serving step, pre-quantize with ``fixed_point.
        # quantize_fex`` and call ``int_fex_scan`` directly).
        from repro.core import fixed_point as fp
        if not compress:
            raise ValueError("pallas-int FEx always compresses (the "
                             "12-bit feature grid IS its output format)")
        coef_np = np.asarray(coef, np.float64)
        if coef_formats is not None:
            b_fmt, a_fmt = coef_formats
        else:
            # Fallback derivation from the packed rows: [0,3] are the b
            # family (b1=0, b2=−b0, so max |b| equals the bank's),
            # [1,2,4,5] the a family — matches sos_formats for the
            # symmetric-form banks this repo builds.
            b_fmt = qformat_for(float(np.max(np.abs(coef_np[[0, 3]]))),
                                b_bits)
            a_fmt = qformat_for(float(np.max(np.abs(coef_np[[1, 2, 4, 5]]))),
                                a_bits)
        coef_codes, ffmt = fp.quantize_fex(
            coef_np, env_alpha, b_fmt.frac_bits, a_fmt.frac_bits,
            log_eps=log_eps)
        audio_codes = fp.to_code(audio.astype(jnp.float32),
                                 ffmt.feat_frac, 16, jnp.int16)
        feats_c, codes = fp.int_fex_scan(
            audio_codes, coef_codes, fp.fex_state_to_codes(buf, ffmt),
            ffmt, frame_shift=frame_shift, backend="pallas",
            block_b=block_b, unroll=unroll, interpret=interpret)
        feats = fp.from_code(feats_c, ffmt.feat_frac)
        buf = fp.fex_state_from_codes(codes, ffmt)
    elif backend == "xla":
        feats, buf = _fex_scan_xla(audio, coef, buf, frame_shift,
                                   env_alpha, log_eps, compress)
    else:
        raise ValueError(f"unknown FEx backend: {backend!r}")
    return feats, _unpack_state(buf)


def build_sos_bank(cfg: FExConfig) -> np.ndarray:
    """(C_active, 2, 6) SOS bank for the selected channels."""
    bank = filters.make_filterbank(cfg.n_channels, cfg.fmin, cfg.fmax, cfg.fs)
    bank = bank[list(cfg.selection)]
    if cfg.quantize_coeffs:
        bank = quantize_sos(bank, cfg.b_bits, cfg.a_bits)
    return bank


def quantize_sos(bank: np.ndarray, b_bits: int, a_bits: int) -> np.ndarray:
    """Mixed-precision coefficient quantization (paper §II-C3).

    Integer bits per family from the dynamic range across the whole bank,
    remaining bits to the fraction.  b and a are quantized independently.
    """
    bank = np.asarray(bank, dtype=np.float64).copy()
    b_fmt = qformat_for(float(np.max(np.abs(bank[..., :3]))), b_bits)
    a_fmt = qformat_for(float(np.max(np.abs(bank[..., 4:]))), a_bits)
    bank[..., :3] = b_fmt.quantize(bank[..., :3])
    bank[..., 4:] = a_fmt.quantize(bank[..., 4:])
    return bank


def sos_formats(bank: np.ndarray, b_bits: int, a_bits: int):
    b_fmt = qformat_for(float(np.max(np.abs(bank[..., :3]))), b_bits)
    a_fmt = qformat_for(float(np.max(np.abs(bank[..., 4:]))), a_bits)
    return b_fmt, a_fmt


class FeatureExtractor:
    """Callable FEx: audio (B, T) float in [-1,1) → 12-bit features (B, F, C).

    ``backend`` selects the default execution path ("xla" — differentiable
    reference — or "pallas", the sequence-resident serving kernel);
    per-call override via ``__call__``/``scan``.  For streaming, use
    ``init_state``/``scan`` to carry ``FExState`` across chunks.
    """

    def __init__(self, cfg: FExConfig | None = None, *,
                 backend: str = "xla", interpret: bool | None = None):
        self.cfg = cfg or FExConfig()
        self.backend = backend
        self.interpret = interpret
        self.sos = jnp.asarray(build_sos_bank(self.cfg), jnp.float32)
        self.coef = pack_coefficients(self.sos)
        # The mixed-precision coefficient formats, derived ONCE from the
        # bank (single source with the promotion fold — fixed_point.
        # fold_fex runs the same sos_formats call).
        self.coef_formats = sos_formats(np.asarray(self.sos),
                                        self.cfg.b_bits, self.cfg.a_bits)

    def __call__(self, audio: Array, backend: str | None = None) -> Array:
        feats, _ = self.scan(audio, None, backend=backend)
        return feats

    def init_state(self, batch: int) -> FExState:
        return init_fex_state(batch, self.cfg.n_active)

    def scan(self, audio: Array, state: FExState | None,
             backend: str | None = None) -> tuple[Array, FExState]:
        """Streaming entry point: 12-bit-quantize a chunk of raw audio and
        run it through the bank, carrying ``state`` across chunks."""
        cfg = self.cfg
        audio = quantize_audio_12b(audio.astype(jnp.float32))
        return fex_scan(
            audio, self.coef, state, frame_shift=cfg.frame_shift,
            env_alpha=cfg.env_alpha, log_eps=cfg.log_eps, compress=True,
            backend=backend or self.backend, interpret=self.interpret,
            coef_formats=self.coef_formats)

    # -- hardware accounting (per input sample, serial datapath) ------------
    def ops_per_sample(self) -> dict:
        """Multiplier/adder counts per audio sample for the active channels.

        Basic biquad: 5 mult, 4 add → 4th-order: 10 mult, 8 add (paper).
        Symmetry (b1=0, b2=−b0): 3 mult per biquad → 6 per filter; the
        shift-replacement step then halves multipliers again (b0 and one
        `a` realized as shift-adds).
        """
        C = self.cfg.n_active
        return {
            "mults_basic": 10 * C, "adds_basic": 8 * C,
            "mults_symmetric": 6 * C, "adds_symmetric": 8 * C,
            "mults_shift": 5 * C, "adds_shift": 10 * C,
            "env_mults": 2 * C, "env_adds": C,
        }


def frames_per_second(cfg: FExConfig) -> float:
    return cfg.fs / cfg.frame_shift
