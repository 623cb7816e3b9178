"""Golden fixed-point model — the executable spec of the ASIC datapath.

The DeltaKWS IC is not a float machine: 12-bit audio and features, 8-bit
weights (two per 16-bit SRAM word), 16-bit filter/state registers and a
24-bit ΔRNN accumulator.  This module is the single source of truth for
that integer datapath, written as pure jnp ops on integer CODE arrays so
the same functions execute

  * in ``lax.scan`` — the golden reference (``int_gru_scan``/
    ``int_fex_scan`` with ``backend="xla"``), and
  * inside the Pallas kernel bodies (``kernels.delta_gru_seq.
    delta_gru_seq_int``, ``kernels.iir_fex.batched_iir_fex_int``),

which puts the two under the same bit-exactness contract as the float
path (tests/test_fixed_point.py): integer arithmetic is deterministic,
so golden vs kernel is bit-for-bit by construction, on any backend.

Conventions
  * A value ``v`` in format Q(i).(f) is stored as the integer CODE
    ``round(v * 2**f)``, saturated to its word width.  All arithmetic is
    int32; narrower storage (int16 state, int8 weights) is cast up at
    the point of use.
  * Rounding is round-half-up via ``rshift_round`` for shifts and
    ``jnp.round`` (half-to-even) where a float intermediate is
    requantized — both deterministic and shared golden/kernel.
  * The gate nonlinearities are the "ideal LUT": the true σ/tanh
    evaluated on the dequantized, accumulator-saturated pre-activation
    and requantized to the hidden grid.  A real LUT stores exactly these
    values; here they are computed on the fly.  Pre-activations are
    bounded by the 24-bit accumulator saturation, so the float
    intermediates stay exactly representable and IEEE-deterministic.

Formats (the per-tensor QFormat table — DESIGN.md §9):

  tensor                format       storage   grid step
  --------------------  -----------  --------  ------------------
  audio sample          Q0.11        int16     2^-11
  FEx signal/registers  Q2.13        int16     2^-13
  FEx envelope          Q0.15        int16     2^-15
  FEx coeff b / a       Q*.{12,8}b   int32     from dynamic range
  feature / x̂           Q0.11        int16     2^-11
  hidden h / ĥ / gates  Q0.15        int16     2^-15
  ΔLSTM cell state c    Q3.12        int16     2^-12
  ΔGRU weight           Q0.7 × 2^e   int8      per-tensor pow-2 e
  accumulator M, bias   Q5.18        int32     24-bit saturating
  FC logits             Q*.{22-e}    int32     —
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.delta_gru import CELLS
from repro.core.quantize import WEIGHT_Q
from repro.kernels.gru_math import delta_branch, f32_dot, gru_gates

Array = jax.Array


# ---------------------------------------------------------------- primitives
def rshift_round(x, s: int):
    """Arithmetic right shift by ``s`` ≥ 1 with round-half-up."""
    return (x + (1 << (s - 1))) >> s


def align(x, shift: int):
    """Move between grids: ``shift`` ≥ 0 is an exact left shift, < 0 a
    rounded right shift (the only place precision can be dropped)."""
    if shift >= 0:
        return x << shift
    return rshift_round(x, -shift)


def align_pair(p, shift_x: int, shift_h: int):
    """``align`` across a fused ``[x-half | h-half]`` block whose halves
    sit on different grids: the shift amounts become per-COLUMN values
    (a select on the column index, so a kernel body captures no constant
    array), so the whole block moves in one add+shift pass instead of
    two per-half passes.  Bit-identical to ``align`` applied per half."""
    if shift_x == shift_h:
        return align(p, shift_x)
    n = p.shape[-1] // 2
    if (shift_x < 0) == (shift_h < 0):
        first = jax.lax.broadcasted_iota(jnp.int32, p.shape, p.ndim - 1) < n
        if shift_x < 0:
            s = jnp.where(first, -shift_x, -shift_h)
            bias = jnp.where(first, 1 << (-shift_x - 1), 1 << (-shift_h - 1))
            return (p + bias) >> s
        return p << jnp.where(first, shift_x, shift_h)
    return jnp.concatenate([align(p[:, :n], shift_x),
                            align(p[:, n:], shift_h)], axis=-1)


def sat(x, bits: int):
    """Two's-complement saturation to a ``bits``-wide word."""
    lim = 1 << (bits - 1)
    return jnp.clip(x, -lim, lim - 1)


def to_code(x, frac: int, bits: int, dtype=jnp.int32):
    """Float value(s) → integer code on the 2^-frac grid, saturated."""
    xp = jnp if isinstance(x, jax.Array) else np
    c = xp.clip(xp.round(x * float(1 << frac)),
                -(1 << (bits - 1)), (1 << (bits - 1)) - 1)
    return c.astype(dtype)


def from_code(c, frac: int):
    """Integer code → float value.  Exact for codes within 24 bits."""
    xp = jnp if isinstance(c, jax.Array) else np
    return c.astype(xp.float32) * float(2.0 ** -frac)


def _weight_exp(w) -> int:
    """Per-tensor power-of-two exponent: scale = 2^e covers max |w|
    (mirrors ``core.quantize.quantize_weights_8b``)."""
    max_abs = float(np.max(np.abs(np.asarray(w))))
    return int(np.ceil(np.log2(max(max_abs, 1e-12))))


# ------------------------------------------------------------- ΔGRU formats
@dataclasses.dataclass(frozen=True)
class GruFormats:
    """Static format metadata of a promoted ΔGRU or ΔLSTM layer (+FC).
    Frozen/hashable: passed as a jit static argument next to the code
    arrays."""

    feat_frac: int = 11      # x / x̂ grid (the 12-bit feature grid)
    hid_frac: int = 15       # h / ĥ / gate grid
    acc_frac: int = 18       # M accumulator grid
    acc_bits: int = 24       # accumulator word width (saturating)
    cell_frac: int = 12      # the ΔLSTM's c grid (int16 codes)
    e_x: int = 0             # w_x scale exponent (w = code · 2^(e-7))
    e_h: int = 0             # w_h scale exponent
    e_fc: int = 0            # FC weight scale exponent

    @property
    def shift_x(self) -> int:
        """Δx·W_x product grid → accumulator grid."""
        return self.acc_frac - (self.feat_frac + 7 - self.e_x)

    @property
    def shift_h(self) -> int:
        """Δh·W_h product grid → accumulator grid."""
        return self.acc_frac - (self.hid_frac + 7 - self.e_h)

    @property
    def logit_frac(self) -> int:
        """FC output grid: h (Q0.hid) × w_fc (Q0.7 · 2^e_fc)."""
        return self.hid_frac + 7 - self.e_fc

    def th_codes(self, threshold: float) -> tuple[int, int]:
        """Δ_TH on the x- and h-comparison grids.

        FLOOR, not round: for on-grid values k·2^-f the float gate
        ``|Δ| > th`` is exactly ``k > floor(th·2^f)``, so the integer
        compare transmits the same deltas the float path does."""
        return (int(np.floor(threshold * (1 << self.feat_frac))),
                int(np.floor(threshold * (1 << self.hid_frac))))


class IntGruWeights(NamedTuple):
    """Promoted recurrent-layer weights (either cell, G = its gates):
    int8 codes + bias on the accumulator grid."""

    w_x: Array   # (I, G·H) int8, value = code · 2^(e_x - 7)
    w_h: Array   # (H, G·H) int8, value = code · 2^(e_h - 7)
    b: Array     # (G·H,)  int32 on the accumulator grid


def quantize_gru(params, fmt: GruFormats | None = None
                 ) -> tuple[IntGruWeights, GruFormats]:
    """Fold float ``DeltaGRUParams`` into the integer weight set.

    Exponents are chosen per tensor from the trained dynamic range
    (paper §II-C3's procedure applied to the ΔRNN weights); the formats
    those choices imply are returned alongside the codes.
    """
    fmt = fmt or GruFormats()
    e_x, e_h = _weight_exp(params.w_x), _weight_exp(params.w_h)
    fmt = dataclasses.replace(fmt, e_x=e_x, e_h=e_h)
    w_x = WEIGHT_Q.to_int(np.asarray(params.w_x) / 2.0 ** e_x)
    w_h = WEIGHT_Q.to_int(np.asarray(params.w_h) / 2.0 ** e_h)
    b = to_code(np.asarray(params.b), fmt.acc_frac, fmt.acc_bits)
    return IntGruWeights(
        w_x=jnp.asarray(w_x, jnp.int8), w_h=jnp.asarray(w_h, jnp.int8),
        b=jnp.asarray(b, jnp.int32)), fmt


def init_int_delta_state(batch: int, input_dim: int, hidden_dim: int,
                         w: IntGruWeights, cell: str = "gru"):
    """Fresh-stream state in code domain.  Reuses ``DeltaState`` (it is
    a dtype-agnostic NamedTuple), ``DeltaLstmState`` with a zero int16 c
    for a cell that carries c; m_x seeds at the bias codes so
    M == W x̂ + W ĥ + b holds on the accumulator grid."""
    from repro.core.delta_gru import DeltaLstmState, DeltaState
    GH = CELLS[cell].gates * hidden_dim
    state = DeltaState(
        h=jnp.zeros((batch, hidden_dim), jnp.int16),
        x_hat=jnp.zeros((batch, input_dim), jnp.int16),
        h_hat=jnp.zeros((batch, hidden_dim), jnp.int16),
        m_x=jnp.broadcast_to(w.b, (batch, GH)).astype(jnp.int32),
        m_h=jnp.zeros((batch, GH), jnp.int32))
    if not CELLS[cell].carries_c:
        return state
    return DeltaLstmState(*state, c=jnp.zeros((batch, hidden_dim), jnp.int16))


# ------------------------------------------------------------ ΔGRU datapath
def int_delta_branch(v, v_hat, th_code):
    """The Δ encoder on integer codes — exact mirror of
    ``gru_math.delta_branch`` (transmit iff |v − v̂| > Δ_TH)."""
    diff = v - v_hat
    mask = jnp.abs(diff) > th_code
    delta = jnp.where(mask, diff, 0)
    new_v_hat = jnp.where(mask, v, v_hat)
    return delta, new_v_hat, mask


def int_gru_gates(m, h, fmt: GruFormats):
    """Type-2 GRU nonlinearity in code domain (ideal-LUT σ/tanh).

    ``m`` is the FUSED ``[m_x | m_h]`` accumulator block, (B, 6H) int32.
    The accumulator saturation bounds |pre| ≤ 2^(acc_bits-1-acc_frac+1),
    so every dequantized intermediate is f32-exact and the float σ/tanh
    see identical inputs in the golden scan and the kernel body.
    """
    H = h.shape[-1]
    one = 1 << fmt.hid_frac
    step = float(2.0 ** -fmt.acc_frac)
    # r and u share the dequant→σ→requant chain, so the two gates run as
    # ONE elementwise pass over the [r|u] accumulator block and split
    # after — value-identical (σ/round are elementwise), but half the op
    # count, which is what the interpret-mode per-frame cost is made of.
    ru_f = jax.nn.sigmoid((m[:, :2 * H] + m[:, 3 * H:5 * H]
                           ).astype(jnp.float32) * step)
    ru = jnp.round(ru_f * one).astype(jnp.int32)
    r, u = ru[:, :H], ru[:, H:]
    # candidate: the reset gate (on the Q0.hid grid) scales the hidden
    # pre-activation; the product is formed in f32 (int32 would overflow
    # r·m_hc) — exact inputs, IEEE-deterministic arithmetic.  The grid
    # factors 2^-hid and 2^-acc are powers of two, so they commute with
    # IEEE round-to-nearest and can be folded to the edges: the ONE
    # rounding in r·m_hc lands identically whether the operands carry
    # their scale factors or not — bit-identical to the unfolded form,
    # one fewer multiply per frame.
    c_pre = (m[:, 2 * H:3 * H].astype(jnp.float32)
             + r.astype(jnp.float32) * m[:, 5 * H:].astype(jnp.float32)
             * float(1.0 / one)) * step
    c = jnp.round(jnp.tanh(c_pre) * one).astype(jnp.int32)
    h_new = rshift_round(u * h + (one - u) * c, fmt.hid_frac)
    return sat(h_new, 16)


def int_lstm_gates(m, c, fmt: GruFormats):
    """LSTM nonlinearity in code domain (ideal-LUT σ/tanh): ``m`` the
    FUSED ``[m_x | m_h]`` block, (B, 8H) int32, gate-major i, f, g, o;
    ``c`` the int32 cell codes at ``cell_frac``.  Returns (h', c'):

      i, f, g, o = round(σ / tanh((m_x + m_h)·2^−acc_frac)·2^hid_frac)
      c' = sat16(rshr(f·c, hid) + rshr(i·g, 2·hid − cell_frac))
      h' = sat16(rshr(o·round(tanh(c'·2^−cell_frac)·2^hid), hid))

    Every product is under 2^31 (|gate| ≤ 2^15, |c| < 2^15), and the
    float σ/tanh see exact inputs, as in ``int_gru_gates``."""
    H = c.shape[-1]
    one = 1 << fmt.hid_frac
    pre = ((m[:, :4 * H] + m[:, 4 * H:]).astype(jnp.float32)
           * float(2.0 ** -fmt.acc_frac))

    def code(v):
        return jnp.round(v * one).astype(jnp.int32)

    # i and f share σ as one pass over their [i|f] block; o its own.
    i_f = code(jax.nn.sigmoid(pre[:, :2 * H]))
    g = code(jnp.tanh(pre[:, 2 * H:3 * H]))
    o = code(jax.nn.sigmoid(pre[:, 3 * H:]))
    c = sat(rshift_round(i_f[:, H:] * c, fmt.hid_frac)
            + rshift_round(i_f[:, :H] * g, 2 * fmt.hid_frac - fmt.cell_frac),
            16)
    tc = code(jnp.tanh(c.astype(jnp.float32) * float(2.0 ** -fmt.cell_frac)))
    return sat(rshift_round(o * tc, fmt.hid_frac), 16), c


# Exact Δ·W on the MXU.  A Δ code is a difference of saturated int16
# codes, so |d| ≤ 2^16: 17 signed bits.  Split it into byte planes,
# lo = d & 0xFF ∈ [0, 2^8) and hi = d >> 8 ∈ [−2^8, 2^8], and contract
# both against the same float32 image of the int8 weights: every
# product is ≤ 2^15 in magnitude, so a block of ≤ 2^9 contraction rows
# sums to ≤ 2^24, inside float32's exact-integer range.  Longer
# contractions run in blocks of ``PLANE_DOT_BLOCK_K`` rows whose exact
# sums add in int32, and ``(hi << 8) + lo`` recombines in int32 —
# bit-identical to the int32 dot (``jnp.dot(d, w.astype(int32))``,
# which wraps mod 2^32 as the recombination does).  On the chip this
# kept the 64-wide kernel's time, where native int8 planes (three of
# them, for 17 bits) cost 10 % more (PERF.md §6).
PLANE_DOT_BLOCK_K = 512


def packed_int8_dot(d, w_f32):
    """Exact (B, K) int32 ``d`` × (K, N) float32 image of int8 codes as
    the (2B, N) plane products [lo; hi]: one float32 dot where K ≤
    ``PLANE_DOT_BLOCK_K`` (its values exact integers), else the int32
    sum of exact ``PLANE_DOT_BLOCK_K``-row block dots."""
    planes = jnp.concatenate([d & 0xFF, d >> 8],
                             axis=0).astype(jnp.float32)
    K = d.shape[1]
    if K <= PLANE_DOT_BLOCK_K:
        return jnp.dot(planes, w_f32, preferred_element_type=jnp.float32)
    return sum(jnp.dot(planes[:, k0:k0 + PLANE_DOT_BLOCK_K],
                       w_f32[k0:k0 + PLANE_DOT_BLOCK_K],
                       preferred_element_type=jnp.float32).astype(jnp.int32)
               for k0 in range(0, K, PLANE_DOT_BLOCK_K))


def packed_int8_dot_pair(dx, dh, wx_f32, wh_f32):
    """Both ΔGRU contractions through ``packed_int8_dot`` with ONE shared
    recombination: the two plane products concatenate on the OUTPUT
    axis, so one cast and shift/add recombine the fused (B, 6H) int32
    [Δx·W_x | Δh·W_h] block the frame step accumulates."""
    rows = dx.shape[0]
    px, ph = packed_int8_dot(dx, wx_f32), packed_int8_dot(dh, wh_f32)
    if px.dtype != ph.dtype:             # one blocked: join as int32
        px, ph = px.astype(jnp.int32), ph.astype(jnp.int32)
    prod = jnp.concatenate([px, ph], axis=-1).astype(jnp.int32)
    return (prod[rows:] << 8) + prod[:rows]


# The same exact Δ·W with int8 weights held as they are (no float32
# image): a Δ code (|d| < 2^17) is three SIGNED byte digits, d = p0 +
# p1·2^8 + p2·2^16 with p0, p1 ∈ [−128, 127] and p2 ∈ [−1, 1], each an
# int8 plane contracted on the MXU with int32 accumulation: every
# product is ≤ 2^14, so any K < 2^17 rows sums exactly, and the int32
# recombination wraps mod 2^32 as the int32 dot does.
def int8_plane_dot(d, w_i8):
    """Exact (B, K) int32 ``d`` × (K, N) int8 codes as the (3B, N) int32
    plane products [p0; p1; p2]."""
    p0 = ((d + 128) & 0xFF) - 128
    d1 = (d - p0) >> 8
    p1 = ((d1 + 128) & 0xFF) - 128
    planes = jnp.concatenate([p0, p1, (d1 - p1) >> 8], axis=0)
    return jnp.dot(planes.astype(jnp.int8), w_i8,
                   preferred_element_type=jnp.int32)


def int8_plane_dot_pair(dx, dh, wx_i8, wh_i8):
    """Both contractions through ``int8_plane_dot`` with one shared
    recombination into the fused (B, 2·G·H) [Δx·W_x | Δh·W_h] block —
    bit-identical to ``packed_int8_dot_pair`` and to the int32 dot."""
    rows = dx.shape[0]
    prod = jnp.concatenate([int8_plane_dot(dx, wx_i8),
                            int8_plane_dot(dh, wh_i8)], axis=-1)
    return (prod[:rows] + (prod[rows:2 * rows] << 8)
            + (prod[2 * rows:] << 16))


def gru_frame_step(fmt: GruFormats | None, x, h, x_hat, h_hat, m,
                   w_x, w_h, th_x, th_h, dot=None):
    """ONE ΔGRU frame — the single source for golden scan AND kernel body.

    ``m`` is the FUSED ``[m_x | m_h]`` accumulator block, (B, 6H): both
    halves move through align/saturate/gates as ONE array, so the per-
    frame elementwise chain runs once over the block instead of twice
    over the halves.  Values are unchanged — every fused op is element-
    wise (or per-column-constant), so it equals the per-half form bit
    for bit; callers concatenate/split only at scan boundaries.

    ``fmt=None`` is the identity-quant mode: float operands, the exact
    op order of the float sequence kernel (``delta_branch``/``gru_gates``
    + f32 dots) — used by ``backend="pallas-int"`` conformance runs.
    With a ``GruFormats``, everything is integer-code arithmetic.

    ``dot`` swaps the Δ·W contraction implementation (int mode only):
    ``None`` is the plain int32 ``jnp.dot`` pair; the kernel passes an
    exact byte-plane dot (``packed_int8_dot_pair`` on its float32 weight
    image, or ``int8_plane_dot_pair`` on int8 weights held as they
    are), so the frame step stays the single source of the math either
    way.
    Signature: ``dot(dx, dh, w_x, w_h) -> (B, 6H)``.

    Returns ``(h', x̂', ĥ', m', mask_x, mask_h)``.
    """
    if fmt is None:
        n = m.shape[-1] // 2
        dx, x_hat, mask_x = delta_branch(x, x_hat, th_x)
        dh, h_hat, mask_h = delta_branch(h, h_hat, th_h)
        m = m + jnp.concatenate([f32_dot(dx, w_x), f32_dot(dh, w_h)],
                                axis=-1)
        h = gru_gates(m[:, :n], m[:, n:], h, h.shape[-1])
        return h, x_hat, h_hat, m, mask_x, mask_h

    h32, x_hat, h_hat, m, mask_x, mask_h = _int_delta_accumulate(
        fmt, x, h, x_hat, h_hat, m, w_x, w_h, th_x, th_h, dot)
    h_new = int_gru_gates(m, h32, fmt)
    return h_new, x_hat, h_hat, m, mask_x, mask_h


def _int_delta_accumulate(fmt: GruFormats, x, h, x_hat, h_hat, m,
                          w_x, w_h, th_x, th_h, dot):
    """The part of an integer frame every cell shares: Δ-encode x and h
    against the values last transmitted and add the aligned W·Δ into the
    fused 24-bit memory.  Returns (h as int32, x̂', ĥ', m', mask_x,
    mask_h)."""
    x = x.astype(jnp.int32)
    h32 = h.astype(jnp.int32)
    dx, x_hat, mask_x = int_delta_branch(x, x_hat.astype(jnp.int32), th_x)
    dh, h_hat, mask_h = int_delta_branch(h32, h_hat.astype(jnp.int32), th_h)
    if dot is None:
        p = jnp.concatenate(
            [jnp.dot(dx, w_x.astype(jnp.int32),
                     preferred_element_type=jnp.int32),
             jnp.dot(dh, w_h.astype(jnp.int32),
                     preferred_element_type=jnp.int32)], axis=-1)
    else:
        p = dot(dx, dh, w_x, w_h)
    m = sat(m + align_pair(p, fmt.shift_x, fmt.shift_h), fmt.acc_bits)
    return h32, x_hat, h_hat, m, mask_x, mask_h


def lstm_frame_step(fmt: GruFormats, x, h, c, x_hat, h_hat, m, w_x, w_h,
                    th_x, th_h, dot=None):
    """ONE integer ΔLSTM frame — golden scan and kernel body alike: the
    ΔGRU's Δ-encoding and fused (B, 8H) memory, then ``int_lstm_gates``.
    ``dot`` as in ``gru_frame_step``.  Returns ``(h', c', x̂', ĥ', m',
    mask_x, mask_h)``."""
    _, x_hat, h_hat, m, mask_x, mask_h = _int_delta_accumulate(
        fmt, x, h, x_hat, h_hat, m, w_x, w_h, th_x, th_h, dot)
    h_new, c_new = int_lstm_gates(m, c.astype(jnp.int32), fmt)
    return h_new, c_new, x_hat, h_hat, m, mask_x, mask_h


@jax.named_scope("kws.gru")
def int_gru_scan(w: IntGruWeights, fmt: GruFormats, xs_codes,
                 threshold: float, state=None, *, backend: str = "xla",
                 block_b: int | None = None, block_t: int | None = None,
                 interpret: bool | None = None, event_driven: bool = False,
                 cell: str = "gru"):
    """Run the integer ΔGRU over codes ``xs_codes`` (T, B, I) int16.

    ``backend="xla"`` is the golden ``lax.scan``; ``"pallas"`` the fused
    sequence-resident kernel — bit-identical by single-source math.
    Returns ``(hs_codes (T,B,H) int16, final state, nz_dx, nz_dh)``.

    ``cell`` (a key of ``core.delta_gru.CELLS``) runs a layer of another
    cell through the same scan and kernel: ``"lstm"`` steps
    ``lstm_frame_step`` over G·H = 4H memories and carries c in a
    ``DeltaLstmState`` (``int_lstm_scan``).

    ``block_b``/``block_t`` forward to the kernel's tiling knobs
    (numerics-invariant); left ``None``, the dispatch consults the
    ``kernels.autotune`` cache for this (shape, dtype, threshold-bucket,
    platform) and falls back to the static defaults on a cold cache.
    ``interpret`` forwards to the Pallas platform resolution.

    ``event_driven`` enables active-slot compaction on the integer
    datapath (``kernels.compaction``, DESIGN.md §13): slots whose whole
    chunk of codes sits inside the integer Δ dead zone (|x − x̂| ≤ th_x)
    and whose carried state a 1-frame kernel probe proves to be a
    bitwise fixed point are skipped; the rest run compacted through the
    selected backend.  Bit-identical by construction; host-level, so
    not jittable (integer state reaches its fixed point in a handful of
    frames of held input, making this mode *more* effective than the
    float path during VAD-clamped silence).

    Unlike the float ``delta_gru_scan``, there is no block-sparse
    fallback for weights that cannot stay resident in VMEM (no int image
    of ``delta_matvec`` yet) — the kernel's VMEM plan
    (``kernels.delta_gru_seq.int_vmem_plan``) holds them as int8 planes
    where their float32 image does not fit, and REFUSES loudly where
    neither fits instead of compiling a kernel that Mosaic would reject.
    """
    T, B, I = xs_codes.shape
    H = w.w_h.shape[0]
    GH = CELLS[cell].gates * H
    if state is None:
        state = init_int_delta_state(B, I, H, w, cell)
    th_x, th_h = fmt.th_codes(threshold)

    if event_driven:
        from repro.kernels import compaction
        kind = type(state)

        def run(xs_c, st):
            return int_gru_scan(
                w, fmt, jnp.asarray(xs_c), threshold,
                kind(*[jnp.asarray(s) for s in st]), backend=backend,
                interpret=interpret, cell=cell)

        held = compaction.held_slots(xs_codes, state.x_hat, th_x)
        hs, st, nz_dx, nz_dh, _ = compaction.event_driven_seq(
            run, xs_codes, tuple(state), held)
        return (jnp.asarray(hs), kind(*[jnp.asarray(s) for s in st]),
                jnp.asarray(nz_dx), jnp.asarray(nz_dh))

    if backend == "pallas":
        from repro.kernels.delta_gru_seq import (delta_gru_seq_int,
                                                 int_kernel_tiles)
        block_b, block_t = int_kernel_tiles(cell, B, I, H, T, threshold,
                                            block_b, block_t, interpret)
        th = jnp.asarray([[th_x, th_h]], jnp.int32)
        return delta_gru_seq_int(xs_codes, *state[:5], w.w_x, w.w_h, th,
                                 *state[5:], fmt=fmt, cell=cell,
                                 block_b=block_b, block_t=block_t,
                                 interpret=interpret)
    if backend != "xla":
        raise ValueError(f"unknown int ΔGRU backend: {backend!r}")

    # The frame step carries the fused [m_x | m_h] block; the state's
    # halves concatenate once before the scan and split once after.
    def body(carry, x):
        if CELLS[cell].carries_c:
            h, xh, hh, m, c = carry
            h, c, xh, hh, m, mask_x, mask_h = lstm_frame_step(
                fmt, x, h, c, xh, hh, m, w.w_x, w.w_h, th_x, th_h)
            tail = (c.astype(jnp.int16),)
        else:
            h, xh, hh, m = carry
            h, xh, hh, m, mask_x, mask_h = gru_frame_step(
                fmt, x, h, xh, hh, m, w.w_x, w.w_h, th_x, th_h)
            tail = ()
        h16 = h.astype(jnp.int16)
        return ((h16, xh.astype(jnp.int16), hh.astype(jnp.int16), m) + tail,
                (h16, jnp.sum(mask_x, -1).astype(jnp.int32),
                 jnp.sum(mask_h, -1).astype(jnp.int32)))

    m0 = jnp.concatenate([state.m_x, state.m_h], axis=-1)
    (h, xh, hh, m, *c), (hs, nz_dx, nz_dh) = jax.lax.scan(
        body, (state.h, state.x_hat, state.h_hat, m0, *state[5:]), xs_codes)
    final = type(state)(h, xh, hh, m[:, :GH], m[:, GH:], *c)
    return hs, final, nz_dx, nz_dh


def int_lstm_scan(w: IntGruWeights, fmt: GruFormats, xs_codes,
                  threshold: float, state=None, **kw):
    """The integer ΔLSTM over codes (T, B, I): ``int_gru_scan`` with
    ``cell="lstm"`` (``lstm_frame_step``, c carried in the state).
    Returns ``(hs (T,B,H) int16, DeltaLstmState, nz_dx, nz_dh)``."""
    return int_gru_scan(w, fmt, xs_codes, threshold, state, cell="lstm",
                        **kw)


def masked_int_gru_scan(w: IntGruWeights, fmt: GruFormats, xs_codes,
                        threshold: float, state, awake):
    """Wake-gated golden integer scan — stage-1 of the cascade on the
    deployed datapath (DESIGN.md §13).  ``awake`` is a (T, B) bool trace
    from the stage-0 gate; frames where a slot sleeps leave its entire
    integer state (h, x̂, ĥ, fused M) bit-frozen, emit the frozen h
    codes, and count zero transmitted deltas.  Awake frames run
    ``gru_frame_step`` — the same single-source math as ``int_gru_scan``
    — so an everywhere-awake trace is bit-identical to the golden scan
    (and through the kernel-conformance suite, to the Pallas kernel).
    Jit-compatible; returns ``(hs, final state, nz_dx, nz_dh)``.
    """
    from repro.core.delta_gru import DeltaState

    H = w.w_h.shape[0]
    th_x, th_h = fmt.th_codes(threshold)

    def body(carry, inp):
        x, awk = inp
        h, xh, hh, m = carry
        nh, nxh, nhh, nm, mask_x, mask_h = gru_frame_step(
            fmt, x, h, xh, hh, m, w.w_x, w.w_h, th_x, th_h)
        mcol = awk[:, None]
        h = jnp.where(mcol, nh.astype(jnp.int16), h)
        xh = jnp.where(mcol, nxh.astype(jnp.int16), xh)
        hh = jnp.where(mcol, nhh.astype(jnp.int16), hh)
        m = jnp.where(mcol, nm, m)
        z = jnp.int32(0)
        return ((h, xh, hh, m),
                (h, jnp.where(awk, jnp.sum(mask_x, -1).astype(jnp.int32), z),
                 jnp.where(awk, jnp.sum(mask_h, -1).astype(jnp.int32), z)))

    m0 = jnp.concatenate([state.m_x, state.m_h], axis=-1)
    (h, xh, hh, m), (hs, nz_dx, nz_dh) = jax.lax.scan(
        body, (state.h, state.x_hat, state.h_hat, m0), (xs_codes, awake))
    final = DeltaState(h=h, x_hat=xh, h_hat=hh,
                       m_x=m[:, :3 * H], m_h=m[:, 3 * H:])
    return hs, final, nz_dx, nz_dh


# ----------------------------------------------------------------- FC head
def int_fc(h_codes, w_fc, b_fc):
    """FC on hidden codes: int8 weights, int32 accumulate; ``b_fc`` is
    pre-shifted onto the logit grid so no alignment is needed."""
    return jnp.dot(h_codes.astype(jnp.int32), w_fc.astype(jnp.int32),
                   preferred_element_type=jnp.int32) + b_fc


def quantize_fc(w_fc, b_fc, fmt: GruFormats
                ) -> tuple[Array, Array, GruFormats]:
    """Fold the FC head: int8 weight codes + bias on the logit grid."""
    e_fc = _weight_exp(w_fc)
    fmt = dataclasses.replace(fmt, e_fc=e_fc)
    w = jnp.asarray(WEIGHT_Q.to_int(np.asarray(w_fc) / 2.0 ** e_fc),
                    jnp.int8)
    b = jnp.asarray(to_code(np.asarray(b_fc), fmt.logit_frac, 32), jnp.int32)
    return w, b, fmt


# ------------------------------------------------------------- FEx formats
@dataclasses.dataclass(frozen=True)
class FexFormats:
    """Static formats of the integer FEx datapath (frozen/hashable)."""

    sig_frac: int = 13       # Q2.13 signal / biquad registers
    env_frac: int = 15       # Q0.15 envelope
    feat_frac: int = 11      # Q0.11 features
    alpha_frac: int = 15     # envelope LP coefficient grid
    b_frac: int = 11         # biquad b-coefficient fraction bits
    a_frac: int = 6          # biquad a-coefficient fraction bits
    alpha_code: int = 1986   # round(env_alpha · 2^alpha_frac)
    log_range: float = 11.0  # log2 compression range (12-bit features)
    eps_code: int = 16       # log_eps on the envelope grid


STATE_ROWS = 5               # [s0_1, s0_2, s1_1, s1_2, env] — kernel layout


def quantize_fex(coef, env_alpha: float, b_frac: int, a_frac: int,
                 log_eps: float = 2.0 ** -11
                 ) -> tuple[Array, FexFormats]:
    """Packed (6, C) float coefficients → integer codes + formats.

    ``b_frac``/``a_frac`` are the FRACTION bits of the mixed-precision
    coefficient formats (``frontend.fex.sos_formats`` — b: 12-bit total,
    a: 8-bit total, integer bits from the dynamic range)."""
    coef = np.asarray(coef, np.float64)
    codes = np.empty_like(coef)
    codes[[0, 3]] = np.round(coef[[0, 3]] * (1 << b_frac))   # b0 rows
    codes[[1, 2, 4, 5]] = np.round(coef[[1, 2, 4, 5]] * (1 << a_frac))
    base = FexFormats(b_frac=b_frac, a_frac=a_frac)
    # alpha/eps codes derive from the grids the SAME FexFormats declares,
    # so format metadata and codes can never disagree.
    fmt = dataclasses.replace(
        base,
        alpha_code=int(round(env_alpha * (1 << base.alpha_frac))),
        eps_code=int(round(log_eps * (1 << base.env_frac))))
    return jnp.asarray(codes, jnp.int32), fmt


def int_fex_sample_step(x_code, s, coef, fmt: FexFormats):
    """Advance every (stream, channel) cascade by ONE audio sample, in
    code domain — the integer mirror of ``kernels.iir_fex.
    fex_sample_step`` (same structure, each product requantized to the
    16-bit register grid, saturating — the serial MAC datapath).

    Layout-agnostic like the float step: x_code (Q0.11 audio codes), the
    five int32 register-code rows ``s`` and the six coefficient-code rows
    ``coef`` broadcast to one row shape — (B, C) in the golden scan,
    (C, B) in the kernel.  Returns the five new rows.
    """
    b0_0, a1_0, a2_0 = coef[0], coef[1], coef[2]
    b0_1, a1_1, a2_1 = coef[3], coef[4], coef[5]
    x = x_code << (fmt.sig_frac - fmt.feat_frac)             # → Q2.13
    # section 0 (DF2T, symmetric numerator)
    y0 = sat(rshift_round(b0_0 * x, fmt.b_frac) + s[0], 16)
    ns0_1 = sat(rshift_round(-a1_0 * y0, fmt.a_frac) + s[1], 16)
    ns0_2 = sat(rshift_round(-b0_0 * x, fmt.b_frac)
                + rshift_round(-a2_0 * y0, fmt.a_frac), 16)
    # section 1
    y1 = sat(rshift_round(b0_1 * y0, fmt.b_frac) + s[2], 16)
    ns1_1 = sat(rshift_round(-a1_1 * y1, fmt.a_frac) + s[3], 16)
    ns1_2 = sat(rshift_round(-b0_1 * y0, fmt.b_frac)
                + rshift_round(-a2_1 * y1, fmt.a_frac), 16)
    # envelope: full-wave rectify on the Q0.15 grid + one-pole low-pass
    y_env = sat(jnp.abs(y1) << (fmt.env_frac - fmt.sig_frac), 16)
    one = 1 << fmt.alpha_frac
    env = rshift_round((one - fmt.alpha_code) * s[4]
                       + fmt.alpha_code * y_env, fmt.alpha_frac)
    return ns0_1, ns0_2, ns1_1, ns1_2, env


def int_compress_env(env_code, fmt: FexFormats):
    """log₂ + normalize + quantize onto the 12-bit feature grid — the
    integer mirror of ``kernels.iir_fex.compress_env`` (the log is the
    ideal-LUT evaluation on the exact envelope code)."""
    v = (jnp.log2((env_code + fmt.eps_code).astype(jnp.float32)
                  * float(2.0 ** -fmt.env_frac))
         + fmt.log_range) / fmt.log_range
    v = jnp.clip(v, -1.0, 1.0 - 2.0 ** -fmt.feat_frac)
    return sat(jnp.round(v * (1 << fmt.feat_frac)).astype(jnp.int32), 16)


def init_int_fex_state(batch: int, n_channels: int):
    """Zero (B, 5, C) int16 carry — quiescent filters, zero envelope."""
    return jnp.zeros((batch, STATE_ROWS, n_channels), jnp.int16)


def fex_state_to_codes(buf, fmt: FexFormats):
    """(B, 5, C) float state buffer → int16 codes (rows 0–3 on the
    signal grid, row 4 on the envelope grid).  Exact when the floats
    already lie on the grids — the carry round-trip contract."""
    filt = to_code(buf[:, :STATE_ROWS - 1], fmt.sig_frac, 16, jnp.int16)
    env = to_code(buf[:, STATE_ROWS - 1:], fmt.env_frac, 16, jnp.int16)
    return jnp.concatenate([filt, env], axis=1)


def fex_state_from_codes(codes, fmt: FexFormats):
    """Inverse of ``fex_state_to_codes`` — always exact (int16 codes are
    exactly representable in float32)."""
    filt = from_code(codes[:, :STATE_ROWS - 1], fmt.sig_frac)
    env = from_code(codes[:, STATE_ROWS - 1:], fmt.env_frac)
    return jnp.concatenate([filt, env], axis=1)


@jax.named_scope("kws.fex")
def int_fex_scan(audio_codes, coef_codes, state_codes, fmt: FexFormats, *,
                 frame_shift: int = 128, backend: str = "xla",
                 block_b: int | None = None, unroll: int | None = None,
                 interpret: bool | None = None):
    """Integer FEx over a chunk of audio codes (B, T) int16 Q0.11.

    Golden ``backend="xla"`` nested scan vs ``"pallas"`` sequence-resident
    kernel — bit-identical (single-source per-sample math).  Returns
    (feature codes (B, F, C) int16, new state codes (B, 5, C) int16).
    ``block_b``/``unroll`` are the kernel's numerics-invariant tiling
    knobs; left ``None``, the dispatch consults the ``kernels.autotune``
    cache (static defaults on a cold cache).
    """
    if backend == "pallas":
        from repro.kernels import autotune
        from repro.kernels.iir_fex import batched_iir_fex_int
        if block_b is None or unroll is None:
            B = audio_codes.shape[0]
            C = coef_codes.shape[1]
            tuned = autotune.resolve("batched_iir_fex_int",
                                     (B, C, frame_shift), "int16", 0.0,
                                     interpret=interpret, B=B,
                                     frame_shift=frame_shift)
            block_b = block_b if block_b is not None else tuned.get("block_b")
            unroll = unroll if unroll is not None else tuned.get("unroll")
        return batched_iir_fex_int(audio_codes, coef_codes, state_codes,
                                   fmt=fmt, frame_shift=frame_shift,
                                   block_b=block_b, unroll=unroll,
                                   interpret=interpret)
    if backend != "xla":
        raise ValueError(f"unknown int FEx backend: {backend!r}")
    return _int_fex_scan_xla(audio_codes, coef_codes, state_codes, fmt,
                             frame_shift)


@functools.partial(jax.jit, static_argnames=("fmt", "frame_shift"))
def _int_fex_scan_xla(audio_codes, coef_codes, state_codes,
                      fmt: FexFormats, frame_shift: int):
    B, T = audio_codes.shape
    n_frames = T // frame_shift
    xf = audio_codes[:, :n_frames * frame_shift].astype(jnp.int32)
    xf = jnp.moveaxis(xf.reshape(B, n_frames, frame_shift), 1, 0)
    coef = coef_codes.astype(jnp.int32)

    def frame_step(s, x_frame):                      # x_frame: (B, S)
        def sample_step(s, x_col):                   # x_col: (B,)
            rows = [s[:, k] for k in range(STATE_ROWS)]
            return jnp.stack(int_fex_sample_step(x_col[:, None], rows, coef,
                                                 fmt), axis=1), None

        s, _ = jax.lax.scan(sample_step, s, x_frame.T)
        return s, int_compress_env(s[:, STATE_ROWS - 1], fmt)

    s, feats = jax.lax.scan(frame_step, state_codes.astype(jnp.int32), xf)
    return (jnp.moveaxis(feats, 0, 1).astype(jnp.int16),
            s.astype(jnp.int16))


# ----------------------------------------------------- promotion + forward
@dataclasses.dataclass
class IntKwsBundle:
    """Everything the integer serving path consumes: the promoted weight
    codes, the static formats, and the deployment threshold.  ``coef``/
    ``ffmt`` are None until a FEx is folded in (feature-chunk serving
    needs only the GRU+FC half).

    A stack (of either cell) holds ``gru`` and ``gfmt`` as tuples, one
    entry per layer, the first layer's first; each layer's formats carry
    the FC head's exponent, so any of them gives ``logit_frac``."""

    gru: IntGruWeights | tuple
    w_fc: Array                     # (H, 12) int8
    b_fc: Array                     # (12,)  int32 on the logit grid
    gfmt: GruFormats | tuple
    threshold: float
    coef: Array | None = None       # (6, C) int32
    ffmt: FexFormats | None = None


def promote_kws(params, threshold: float, fex=None) -> IntKwsBundle:
    """Fold a (QAT-)trained float parameter tree into the integer bundle.

    Args:
      params: the ``models.kws.init_kws`` tree (w_x/w_h/b/w_fc/b_fc, and
        ``w_x_<l>, w_h_<l>, b_<l>`` for each layer ``l`` of a stack above
        the first), of either cell: the weights' G·H columns fold alike.
      threshold: the float Δ_TH to serve at; stored on the bundle and
        FLOOR-quantized to a code at serving time so the integer gate
        transmits exactly the deltas the float gate transmits on grid
        values.
      fex: optional ``frontend.fex.FeatureExtractor`` whose coefficient
        bank is folded in for the audio-in path (feature-mode bundles
        fold it lazily at session creation — see ``fold_fex``).

    Returns:
      An ``IntKwsBundle``: int8 Q0.7×2^e weight codes, bias codes on
      the Q5.18 accumulator grid, static per-tensor formats, and Δ_TH.
      Each layer of a stack takes its own per-tensor exponents; a layer
      above the first reads the int16 hidden codes below it, so its
      input grid is the hidden grid.

    Pure fold — no retraining, no calibration data: every format is
    either fixed by the IC or derived from the trained dynamic range.
    """
    from repro.core.delta_gru import DeltaGRUParams
    from repro.models.kws import gru_stack
    stack = gru_stack(params)
    layers = (stack,) if isinstance(stack, DeltaGRUParams) else stack
    grus, fmts = [], []
    for layer, gru_p in enumerate(layers):
        base = GruFormats()
        if layer:
            base = dataclasses.replace(base, feat_frac=base.hid_frac)
        gru, gfmt = quantize_gru(gru_p, base)
        grus.append(gru)
        fmts.append(gfmt)
    w_fc, b_fc, top = quantize_fc(params["w_fc"], params["b_fc"], fmts[-1])
    fmts = [dataclasses.replace(f, e_fc=top.e_fc) for f in fmts]
    one = len(grus) == 1
    bundle = IntKwsBundle(gru=grus[0] if one else tuple(grus),
                          w_fc=w_fc, b_fc=b_fc,
                          gfmt=fmts[0] if one else tuple(fmts),
                          threshold=float(threshold))
    return bundle if fex is None else fold_fex(bundle, fex)


def fold_fex(bundle: IntKwsBundle, fex) -> IntKwsBundle:
    """Return a COPY of ``bundle`` with ``fex``'s coefficient bank folded
    in (mixed-precision formats from ``cfg.b_bits``/``cfg.a_bits`` —
    paper §II-C3).  No-op if a bank is already folded; never mutates the
    input, so a bundle shared across sessions stays pristine."""
    if bundle.ffmt is not None:
        return bundle
    from repro.frontend.fex import build_sos_bank, sos_formats
    cfg = fex.cfg
    bank = build_sos_bank(cfg)
    b_fmt, a_fmt = sos_formats(bank, cfg.b_bits, cfg.a_bits)
    coef, ffmt = quantize_fex(fex.coef, cfg.env_alpha, b_fmt.frac_bits,
                              a_fmt.frac_bits, log_eps=cfg.log_eps)
    return dataclasses.replace(bundle, coef=coef, ffmt=ffmt)


def int_forward(bundle: IntKwsBundle, feats, *, backend: str = "xla"):
    """Integer mirror of ``models.kws.forward``: features (B, F, C) —
    float values on the 12-bit grid or int16 codes — to
    ``(logit_codes (B, 12) int32, nz_dx, nz_dh)``.  Decisions are
    ``argmax`` over the integer logit codes; mean-pool is an integer
    rounded division (the deploy-time head)."""
    fmt = bundle.gfmt
    if not jnp.issubdtype(feats.dtype, jnp.integer):
        feats = to_code(feats, fmt.feat_frac, 16, jnp.int16)
    xs = jnp.moveaxis(feats, 1, 0)                    # (F, B, C)
    hs, _, nz_dx, nz_dh = int_gru_scan(bundle.gru, fmt, xs,
                                       bundle.threshold, backend=backend)
    F = hs.shape[0]
    h_sum = jnp.sum(hs.astype(jnp.int32), axis=0)     # exact (≤ 2^21)
    h_mean = jnp.round(h_sum.astype(jnp.float32) / F).astype(jnp.int32)
    logits = int_fc(h_mean, bundle.w_fc, bundle.b_fc)
    return logits, nz_dx, nz_dh
